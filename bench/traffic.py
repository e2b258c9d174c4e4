"""The one traffic generator. A mix is a data file under ``traffic/``; this
module turns it and ``--seed`` into requests.

Every seed gets the same work: each length is drawn as a fixed, stratified
set of lognormal quantiles, and the seed only permutes which request gets
which length, which tokens it holds and (open loop) the order of the gaps
between arrivals, which are themselves a fixed set. Runs with different
seeds then differ by order, not by amount.

Two loop kinds:

* ``open``: ``round(rate * seconds)`` requests due in the window, spaced by
  stratified exponential gaps (a Poisson process conditioned on its count).
  A share of them starts with one of a few page-aligned system prompts.
* ``closed``: ``sessions`` conversations whose histories are prefilled in
  set-up; each sends its next turn when its reply completes, and starts over
  with a fresh history when the next turn would not fit the context.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# Tags that keep the seed streams of different draws apart.
_PERM, _TOKENS, _ARRIVAL, _SAMPLING = 1, 2, 3, 4


def stratified(dist: Dict, n: int) -> List[int]:
    """``n`` lengths at the lognormal quantiles (i + 0.5) / n, clipped to
    ``[min, max]``: the same multiset for every seed."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = int(round(dist["median"] * math.exp(dist["sigma"] * z)))
        out.append(min(max(v, dist["min"]), dist["max"]))
    return out


def arrivals(n: int, seconds: float, seed: int) -> np.ndarray:
    """Due times of ``n`` requests in ``(0, seconds)``. The ``n + 1``
    spacings of a Poisson process conditioned on its count are exchangeable
    exponentials that fill the window: they are taken at the quantiles
    (i + 0.5) / (n + 1), scaled to sum to ``seconds``, and put in an order
    drawn from the seed. Every seed gets the same multiset of gaps."""
    q = [-math.log1p(-(i + 0.5) / (n + 1)) for i in range(n + 1)]
    scale = seconds / sum(q)
    gaps = _shuffled([x * scale for x in q], seed, _ARRIVAL)
    return np.cumsum(gaps[:n])


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *tags])


def _shuffled(values: Sequence, seed: int, *tags: int) -> list:
    vals = list(values)
    perm = _rng(seed, _PERM, *tags).permutation(len(vals))
    return [vals[i] for i in perm]


@dataclasses.dataclass
class Req:
    """One request as the generator makes it."""

    key: str
    prompt: np.ndarray
    max_tokens: int
    temperature: float
    top_p: float
    seed: int
    due: Optional[float] = None     # seconds after the window opens

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def _tokens(vocab: int, n: int, seed: int, *tags: int) -> np.ndarray:
    return _rng(seed, _TOKENS, *tags).integers(1, vocab, size=n).astype(np.int32)


def open_requests(mix: Dict, vocab: int, seed: int, seconds: float) -> List[Req]:
    """Open loop: every request due in the window, sorted by due time."""
    n = max(1, int(round(mix["rate"] * seconds)))
    # The requests' sizes are paired by a permutation that does not depend
    # on the seed, so every seed serves the same set of requests.
    users = _shuffled(stratified(mix["user"], n), 0, 1)
    outs = _shuffled(stratified(mix["output"], n), 0, 2)
    sp = mix.get("system_prompts")
    owner: List[Optional[int]] = [None] * n
    systems: List[np.ndarray] = []
    if sp:
        counts = [int(round(n * sp["share"] * w)) for w in sp["weights"]]
        slots = [i for i, c in enumerate(counts) for _ in range(c)][:n]
        owner = _shuffled(slots + [None] * (n - len(slots)), 0, 3)
        systems = [_tokens(vocab, sp["length"], seed, 9, i)
                   for i in range(sp["count"])]
    n_greedy = int(round(n * mix.get("greedy_share", 0.0)))
    greedy = _shuffled([True] * n_greedy + [False] * (n - n_greedy), 0, 4)
    order = _shuffled(range(n), seed, 5)
    due = arrivals(n, seconds, seed)
    samp = _rng(seed, _SAMPLING).integers(0, 2**31 - 1, size=n)
    reqs = []
    for i, j in enumerate(order):
        tail = _tokens(vocab, users[j], seed, 10, i)
        prompt = tail if owner[j] is None else np.concatenate(
            [systems[owner[j]], tail])
        reqs.append(Req(
            key=f"r{i}", prompt=prompt, max_tokens=outs[j],
            temperature=0.0 if greedy[j] else mix["temperature"],
            top_p=1.0 if greedy[j] else mix["top_p"],
            seed=int(samp[i]), due=float(due[i]),
        ))
    return reqs


class Session:
    """One closed-loop conversation. Turn ``k`` of every session draws its
    message and reply lengths from the same stratified set, permuted over the
    sessions by the seed; so do the histories of each restart."""

    def __init__(self, mix: Dict, vocab: int, seed: int, index: int):
        self.mix, self.vocab, self.seed, self.index = mix, vocab, seed, index
        self.turn = 0
        self.generation = 0
        self.history = self._fresh_history()
        self.prompt: Optional[np.ndarray] = None

    def _pick(self, dist_key: str, *tags: int) -> int:
        n = self.mix["sessions"]
        vals = _shuffled(stratified(self.mix[dist_key], n), self.seed, *tags)
        return vals[self.index]

    def _fresh_history(self) -> np.ndarray:
        g = self.generation
        n = self._pick("history", 20, g)
        return _tokens(self.vocab, n, self.seed, 20, g, self.index)

    def next_request(self, reply: Sequence[int] = ()) -> Req:
        """The session's next turn. ``reply``: the tokens served for the
        previous turn (empty for the first)."""
        k = self.turn
        if self.prompt is not None:
            self.history = np.concatenate(
                [self.prompt, np.asarray(reply, np.int32)])
        msg_len = self._pick("message", 21, k)
        reply_len = self._pick("reply", 22, k)
        limit = self.mix["max_context"] - self.mix["reply"]["max"]
        if len(self.history) + msg_len > limit:
            self.generation += 1
            self.history = self._fresh_history()
        msg = _tokens(self.vocab, msg_len, self.seed, 21, k, self.index)
        self.prompt = np.concatenate([self.history, msg])
        self.turn += 1
        return Req(
            key=f"s{self.index}.t{k}", prompt=self.prompt,
            max_tokens=reply_len, temperature=self.mix["temperature"],
            top_p=self.mix["top_p"], seed=0,
        )


def sessions(mix: Dict, vocab: int, seed: int) -> List[Session]:
    return [Session(mix, vocab, seed, i) for i in range(mix["sessions"])]


def first_turn_due(mix: Dict, seed: int) -> List[float]:
    """Stagger of the sessions' first turns over ``stagger_s`` seconds."""
    n = mix["sessions"]
    pts = [(i + 0.5) / n * mix["stagger_s"] for i in range(n)]
    return _shuffled(pts, seed, 30)


def reachable_prefills(mix: Dict, page_size: int) -> List[Tuple[Tuple[int, int], Tuple[int, int]]]:
    """(prefix token range, tail token range) of every kind of prefill the
    mix can cause: what set-up has to warm up and nothing more."""
    if mix["loop"] == "open":
        u = mix["user"]
        out = [((0, 0), (u["min"], u["max"]))]
        sp = mix.get("system_prompts")
        if sp:
            L = sp["length"]
            out.append(((L, L), (u["min"], u["max"])))
            out.append(((0, 0), (L + u["min"], L + u["max"])))
        return out
    h, m, r = mix["history"], mix["message"], mix["reply"]
    first = h["min"] + m["min"]
    last = mix["max_context"] - r["max"]
    ps = page_size
    return [
        ((0, 0), (h["min"], h["max"])),                       # set-up history
        ((0, 0), (first, h["max"] + m["max"])),               # restart
        ((first // ps * ps, last // ps * ps),                 # next turn
         (r["min"] + m["min"], ps - 1 + r["max"] + m["max"])),
    ]
