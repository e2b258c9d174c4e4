"""The measured window: drive the engine from the traffic and time every
request from when it was due.

One thread, one loop. Each pass submits every request whose due time has
passed, then runs one blocking ``engine.step()`` (its outputs are on the host
when it returns), or sleeps until the next due time when the engine is idle.
A request's clock starts at its due time, not at ``add_request``, so a long
step that delays later submissions shows in their time to first token.

The statistics below are pure functions of the records and are tested on a
fake clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from bench.traffic import Req


@dataclasses.dataclass
class Record:
    """What the window saw of one request (times in seconds, window clock)."""

    req: Req
    due: float
    uid: Optional[int] = None       # the engine's id, once submitted
    submitted: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    tokens: int = 0                 # tokens returned inside the window
    finished: bool = False
    failed: str = ""
    served: List[int] = dataclasses.field(default_factory=list)
    prompt_len: int = 0


@dataclasses.dataclass
class Window:
    """Everything one measured window produced."""

    seconds: float
    records: List[Record]
    steps: List[Dict]               # per step: t0, t1, new tokens per uid
    lateness: List[float]           # submit time - due time, per request


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_values(w: Window) -> List[float]:
    """Due time to first token for every request due in the window; one that
    has no first token by the close enters at its wait so far."""
    out = []
    for r in w.records:
        if r.due > w.seconds:
            continue
        t = r.first if (r.first is not None and r.first <= w.seconds) else w.seconds
        out.append(t - r.due)
    return out


def tpot_values(w: Window) -> List[float]:
    """(last token - first token) / (tokens - 1) per request with at least two
    tokens in the window, in-flight ones included."""
    return [
        (r.last - r.first) / (r.tokens - 1)
        for r in w.records if r.tokens >= 2
    ]


def output_tok_s(w: Window) -> float:
    return sum(r.tokens for r in w.records) / w.seconds


def end_to_end(w: Window) -> Dict[str, float]:
    """Every end-to-end metric this window can give (milliseconds, tok/s)."""
    ttft = ttft_values(w)
    tpot = tpot_values(w)
    out = {"output_tok_s": output_tok_s(w)}
    if ttft:
        out["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
        out["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
    if tpot:
        out["tpot_p90_ms"] = 1e3 * percentile(tpot, 90)
    return out


class Engine:
    """What the loop needs of the system under test (``LLMEngine``)."""

    def __init__(self, engine):
        self.engine = engine

    def submit(self, req: Req) -> int:
        from repro.serving import SamplingParams

        return self.engine.add_request(
            prompt=req.prompt,
            sampling=SamplingParams(
                temperature=req.temperature, top_p=req.top_p,
                max_tokens=req.max_tokens, seed=req.seed,
            ),
        )

    def busy(self) -> bool:
        e = self.engine
        return bool(e.backend.active.any() or e.scheduler.has_work())

    def step(self):
        """[(uid, new tokens, finished)] of one engine step."""
        return [(o.uid, list(o.new_tokens), o.finished)
                for o in self.engine.step()]


def run_window(
    engine,
    *,
    seconds: float,
    open_reqs: Sequence[Req] = (),
    sessions: Sequence = (),
    session_due: Sequence[float] = (),
    clock: Callable[[], float] = time.perf_counter,
    sleep: Callable[[float], None] = time.sleep,
    annotate: Callable[[str], contextlib.AbstractContextManager] = (
        lambda name: contextlib.nullcontext()),
    on_step: Optional[Callable[[], None]] = None,
) -> Window:
    """Serve for ``seconds``. ``engine`` has ``submit(req) -> uid``,
    ``busy()`` and ``step() -> [(uid, tokens, finished)]``. Open-loop
    requests carry their due times; closed-loop ``sessions`` send their first
    turn at ``session_due`` and each next turn when the previous completes."""
    records: List[Record] = []
    by_uid: Dict[int, Record] = {}
    session_of: Dict[int, object] = {}
    queue: List[tuple] = []         # (due, order, req, session)
    for r in open_reqs:
        queue.append((r.due, len(queue), r, None))
    for s, due in zip(sessions, session_due):
        queue.append((due, len(queue), s.next_request(), s))
    queue.sort(key=lambda x: (x[0], x[1]))
    steps: List[Dict] = []
    lateness: List[float] = []
    t0 = clock()
    now = 0.0
    with annotate("bench.window"):
        while True:
            now = clock() - t0
            if now >= seconds:
                break
            while queue and queue[0][0] <= now:
                due, _, req, sess = queue.pop(0)
                rec = Record(req=req, due=due, prompt_len=len(req.prompt))
                records.append(rec)
                with annotate("bench.add_request"):
                    try:
                        uid = engine.submit(req)
                    except (ValueError, RuntimeError) as e:
                        rec.failed = f"{type(e).__name__}: {e}"
                        continue
                rec.uid = uid
                rec.submitted = clock() - t0
                lateness.append(rec.submitted - due)
                by_uid[uid] = rec
                if sess is not None:
                    session_of[uid] = sess
            if not engine.busy():
                nxt = queue[0][0] if queue else seconds
                with annotate("bench.sleep"):
                    sleep(max(0.0, min(nxt, seconds) - now))
                continue
            s0 = clock() - t0
            with annotate("bench.step"):
                outs = engine.step()
            t = clock() - t0
            steps.append({"t0": s0, "t1": t,
                          "new": {uid: len(toks) for uid, toks, _ in outs}})
            if on_step is not None:
                on_step()
            for uid, toks, finished in outs:
                rec = by_uid.get(uid)
                if rec is None:
                    continue
                rec.served.extend(int(x) for x in toks)
                if toks and t <= seconds:
                    if rec.first is None:
                        rec.first = t
                    rec.last = t
                    rec.tokens += len(toks)
                if finished:
                    rec.finished = t <= seconds
                    sess = session_of.pop(uid, None)
                    if sess is not None and t <= seconds:
                        nxt = sess.next_request(rec.served)
                        queue.append((t, len(records) + len(queue), nxt, sess))
                        queue.sort(key=lambda x: (x[0], x[1]))
    return Window(seconds=seconds, records=records, steps=steps,
                  lateness=lateness)
