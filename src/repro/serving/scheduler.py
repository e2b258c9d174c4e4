"""Serving scheduler: admission, fairness, and preemption *policy*.

Everything the pre-PR-5 engines decided inline — who enters the batch,
in what order, who gets evicted under page pressure — lives here, behind
a backend-agnostic protocol, so the dense and paged execution backends
are pure mechanism:

  * **Admission** walks the ready queue in (effective-priority, arrival)
    order with head-of-line blocking: the first request the backend
    cannot hold ends the round (skipping it forever would starve large
    requests). Preempted requests re-enter first — their pages were taken
    from them, they do not re-queue behind new arrivals.
  * **Fairness** is priority + FCFS with aging: a request's effective
    priority grows by one per ``aging_rounds`` scheduling rounds it waits,
    so any fixed-priority stream is eventually outranked — no starvation
    (property-tested in ``tests/test_scheduler.py``).
  * **Page budget / prefix-match scoring**: before touching the backend's
    allocator, the scheduler prices the request — pages needed minus
    prefix-cache matches (``backend.quote``) plus decode headroom — and
    declines it when the budget cannot fit free + evictable capacity.
    The backend's ``try_admit`` stays authoritative (it may still return
    None), but the *decision* is policy, not mechanism.
  * **NUMA/occupancy awareness**: growing the decode batch only helps
    until the (batch x kv-head) grid covers the topology's NUMA domains
    with full waves; past that point the analytic decode model
    (``core.perf_model.estimate_dense_decode`` / ``estimate_paged_decode``
    via ``backend.decode_time_model``) shows marginal tokens/s gains
    collapsing. The scheduler computes the smallest batch whose modeled
    aggregate throughput stops improving and refuses to admit beyond it
    (``occupancy_cap``) — admission is throughput-aware, not just
    capacity-aware. The model is injectable for tests.
  * **Preemption policy**: ``choose_victim`` picks the lowest-priority,
    newest active row — the backend only executes the eviction.

``SchedulerStats`` is the observable summary ``LLMEngine.step`` keeps
up to date: tokens/s, prefix hit rate, preemptions, page occupancy.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import List, Optional, Sequence, Tuple

#: Admission verdict distinct from "does not fit": the request's prefix
#: matches pages the *current* flush is about to publish — admit it next
#: round (as an extend) instead of prefilling the shared prefix twice.
DEFERRED = object()

#: Denominators below this are "no time measured/modeled", not a rate:
#: guards every tokens/s division (a denormal decode_time_model result
#: used to print as 10^15 modeled tok/s — PR 7 satellite fix).
MIN_RATE_DENOM_S = 1e-9


def safe_rate(count: float, seconds: float) -> float:
    """``count / seconds`` with the near-zero denominator reported as
    0.0 (unknown) instead of inf/garbage."""
    return count / seconds if seconds > MIN_RATE_DENOM_S else 0.0


def default_choose_victim(candidates: Sequence[Tuple[int, int, int]],
                          protect: int = -1) -> Optional[int]:
    """The preemption rule, shared by the scheduler and standalone
    backends: among active ``(priority, submit_order, row)`` rows, evict
    the lowest priority, newest among ties; never ``protect`` (the row
    whose decode triggered the pressure)."""
    pool = [
        (prio, -order, row)
        for prio, order, row in candidates
        if row != protect
    ]
    if not pool:
        return None
    return min(pool)[2]


@dataclasses.dataclass
class SchedulerStats:
    """Serving counters surfaced by ``LLMEngine.stats()`` / ``step()``.

    The throughput fields are deliberately three *different* numbers and
    must never be conflated (PR 7 satellite):

      * ``tokens_per_s`` — tokens over total engine wall time (prefill,
        scheduling, and host bookkeeping included);
      * ``measured_tok_s`` — tokens over *decode-phase* wall time only:
        the apples-to-apples measurement for ``modeled_tok_s``;
      * ``modeled_tok_s`` — ``core.perf_model``'s analytic prediction at
        the current batch. A near-zero modeled tick reports 0.0 (unknown)
        rather than a 10^15 tok/s artifact (:func:`safe_rate`).

    ``prefix_hit_rate`` is ``None`` when the backend has no prefix cache
    at all (dense stripes) — distinct from a real 0.0 hit rate on a
    paged engine whose trace simply never shared a prefix.
    """

    kv_layout: str = "dense"
    running: int = 0
    waiting: int = 0
    completed: int = 0
    tokens_generated: int = 0
    elapsed_s: float = 0.0
    tokens_per_s: float = 0.0
    prefix_hit_rate: Optional[float] = None
    page_occupancy: float = 0.0    # used / total pages (dense: used slots)
    preemptions: int = 0
    resumed_tokens: int = 0
    prefill_launches: int = 0
    batched_prefills: int = 0
    occupancy_cap: int = 0         # scheduler's modeled max useful batch
    modeled_tok_s: float = 0.0     # perf_model tokens/s at current batch
    measured_tok_s: float = 0.0    # tokens / measured decode wall time
    decode_elapsed_s: float = 0.0  # decode-phase wall time (measured)
    steps_per_sync: int = 1        # fused decode ticks per host sync (live)
    num_devices: int = 1           # serving-mesh width (1 = single device)
    kv_dtype: str = "fp32"         # pool storage dtype (fp32/int8/fp8)
    demoted_pages: int = 0         # pages demoted device -> host tier
    promoted_pages: int = 0        # pages promoted host tier -> device
    host_bytes_resident: int = 0   # host-tier bytes currently held
    decode_splits: int = 1         # paged decode plan: split-K ranges
    pages_per_block: int = 0       # paged decode plan: pages a kernel block
    latent_bytes_per_token: int = 0  # latent attention: one token's row, a layer
    held_experts: Optional[Tuple[int, int]] = None  # [start, stop) held here

    def summary(self) -> str:
        prefix = ("n/a" if self.prefix_hit_rate is None
                  else f"{self.prefix_hit_rate:.2f}")
        mesh = f" x{self.num_devices}dev" if self.num_devices > 1 else ""
        tier = ""
        if self.demoted_pages or self.promoted_pages:
            tier = (f" | tier {self.demoted_pages} demoted / "
                    f"{self.promoted_pages} promoted "
                    f"({self.host_bytes_resident} host bytes)")
        dtype = f" {self.kv_dtype}" if self.kv_dtype != "fp32" else ""
        block = ""
        if self.pages_per_block:
            block = (f" | decode blocks of {self.pages_per_block} pages x "
                     f"{self.decode_splits} splits")
        return (
            f"[{self.kv_layout}{dtype}{mesh} N={self.steps_per_sync}] "
            f"{self.completed} done / {self.running} "
            f"running / {self.waiting} waiting | "
            f"{self.tokens_generated} tokens in {self.elapsed_s:.2f}s "
            f"({self.tokens_per_s:.1f} tok/s wall, measured decode "
            f"{self.measured_tok_s:.1f}, modeled "
            f"{self.modeled_tok_s:.0f}) | prefix hit "
            f"{prefix} | occupancy "
            f"{self.page_occupancy:.2f} (cap {self.occupancy_cap}) | "
            f"{self.preemptions} preemptions "
            f"({self.resumed_tokens} tokens resumed) | "
            f"{self.prefill_launches} prefill launches "
            f"({self.batched_prefills} batched)"
            f"{block}{tier}"
        )


@dataclasses.dataclass
class _Waiting:
    req: object
    arrival: int
    rounds_waited: int = 0


class Scheduler:
    """Admission / fairness / preemption policy over an execution backend.

    The backend protocol (``serving.backends`` implements it; tests drive
    fakes): ``rows``, ``num_active``, ``try_admit(req, resume_tokens,
    pending_hashes) -> record | None | DEFERRED``, optional ``quote(req)
    -> (total_pages, matched_pages)`` + ``free_pages`` / ``evictable_pages``
    / ``reserve_pages`` (and ``sync_reserve_pages`` when fused multi-step
    decode grows rows between syncs) for the page budget, optional
    ``decode_time_model(batch, mean_len=...) -> seconds`` for the
    occupancy cap (batch-only models also accepted), optional
    ``prefill_time_saved(req) -> seconds`` for the admission tie-break.
    """

    def __init__(self, *, aging_rounds: int = 4, decode_time_model=None):
        if aging_rounds < 1:
            raise ValueError("aging_rounds must be >= 1")
        self.aging_rounds = aging_rounds
        self._decode_time_model = decode_time_model
        self._waiting: List[_Waiting] = []
        self._requeue: "deque[Tuple[object, List]]" = deque()
        self._arrival = 0
        # Occupancy cap memo, keyed by the live-mean-context bucket the
        # batch is currently in (None = backend exposes no live lengths).
        self._occupancy_cap: dict = {}

    # -- queue state -------------------------------------------------------

    def add(self, req) -> None:
        self._waiting.append(_Waiting(req, self._arrival))
        self._arrival += 1

    def requeue(self, req, generated: Sequence) -> None:
        """Re-enter a preempted request at the front (its generated tokens
        replay through the extend path on re-admission)."""
        self._requeue.appendleft((req, list(generated)))

    @property
    def num_waiting(self) -> int:
        return len(self._waiting) + len(self._requeue)

    def has_work(self) -> bool:
        return bool(self._waiting or self._requeue)

    # -- policy ------------------------------------------------------------

    def _effective_priority(self, w: _Waiting) -> int:
        return w.req.priority + w.rounds_waited // self.aging_rounds

    def _prefill_savings(self, backend, req) -> float:
        """Modeled prefill seconds saved by admitting ``req`` now (prefix
        reuse about to be exploited). Zero for backends without the hook
        (dense slots, test fakes) so the FCFS order is unchanged there."""
        saved = getattr(backend, "prefill_time_saved", None)
        return float(saved(req)) if saved is not None else 0.0

    def _ranked(self, backend=None) -> List[_Waiting]:
        """(effective-priority, modeled-prefill-savings, arrival) order:
        within a priority class the candidate whose admission saves the
        most modeled prefill time (largest live prefix-cache hit) goes
        first; arrival breaks the remaining ties (FCFS)."""
        return sorted(
            self._waiting,
            key=lambda w: (
                -self._effective_priority(w),
                -(self._prefill_savings(backend, w.req)
                  if backend is not None else 0.0),
                w.arrival,
            ),
        )

    def page_budget_ok(self, backend, req) -> bool:
        """Price an admission before touching the allocator: fresh pages
        (prefix-cache matches deducted) plus decode headroom must fit the
        backend's free + evictable capacity. Backends without a page pool
        (dense slots) always pass — their row check is in try_admit."""
        quote = getattr(backend, "quote", None)
        if quote is None:
            return True
        total, matched = quote(req)
        fresh = total - matched
        budget = backend.free_pages + backend.evictable_pages
        # Fused multi-step decode grows every active row by up to N tokens
        # between host syncs; ``sync_reserve_pages`` prices that headroom
        # (it degenerates to ``reserve_pages`` at N == 1).
        reserve = getattr(backend, "sync_reserve_pages", None)
        if reserve is None:
            reserve = getattr(backend, "reserve_pages", 0)
        return fresh + reserve <= budget

    @staticmethod
    def _live_mean_len(backend) -> Optional[float]:
        """Mean context length over the backend's live rows, or None when
        the backend exposes no live lengths (dense fakes, empty batch)."""
        lengths = getattr(backend, "lengths", None)
        active = getattr(backend, "active", None)
        if lengths is None or active is None:
            return None
        try:
            live = lengths[active]
        except Exception:
            return None
        if getattr(live, "size", 0) == 0:
            return None
        return float(live.mean())

    def occupancy_cap(self, backend) -> int:
        """Largest decode batch before modeled aggregate tokens/s starts
        *declining* — the NUMA-occupancy point past which another row
        costs more (tail-domain contention, combine overhead) than its
        token is worth. A bandwidth-bound linear model (time ~ batch)
        keeps tokens/s flat, so the cap stays at ``backend.rows`` — the
        gate only binds when the model says occupancy actually hurts.

        Re-priced as the batch *ages*: the sweep is evaluated at the live
        mean sequence length (bucketed to powers of two so the memo stays
        small), not the admission-time length — a batch that has grown
        long contexts has a different occupancy knee than a fresh one.
        Backends without live lengths (or models that only take ``batch``)
        fall back to the model's own default shape; backends without any
        model fall back to their row count."""
        from repro.obs.drift import context_bucket

        mean_len = self._live_mean_len(backend)
        bucket = None if mean_len is None else context_bucket(mean_len)
        cached = self._occupancy_cap.get(bucket)
        if cached is not None:
            return cached
        model = self._decode_time_model or getattr(
            backend, "decode_time_model", None
        )
        cap = backend.rows
        if model is not None:
            best = 0.0
            for b in range(1, backend.rows + 1):
                if bucket is None:
                    t = model(b)
                else:
                    try:
                        t = model(b, mean_len=float(bucket))
                    except TypeError:  # injected batch-only test models
                        t = model(b)
                tok_s = b / t if t > 0 else float("inf")
                if tok_s < best * (1.0 - 1e-9):
                    cap = b - 1
                    break
                best = max(best, tok_s)
        self._occupancy_cap[bucket] = max(cap, 1)
        return self._occupancy_cap[bucket]

    def _admission_ok(self, backend, req) -> bool:
        if backend.num_active >= self.occupancy_cap(backend):
            return False
        return self.page_budget_ok(backend, req)

    def schedule(self, backend, records: List) -> List:
        """One admission round: drain preempted work first, then the ready
        queue in (effective-priority, arrival) order, head-of-line
        blocking, stopping at the occupancy cap. Admission *records* are
        appended to ``records`` (caller-owned so a mid-round backend error
        still leaves the already-claimed rows visible for flushing) and
        must be flushed by the caller before the next decode tick."""
        pending = set()

        def take(rec):
            records.append(rec)
            pending.update(rec.get("pending_publish", ()))

        while self._requeue:
            req, toks = self._requeue[0]
            if not self._admission_ok(backend, req):
                break
            try:
                rec = backend.try_admit(
                    req, resume_tokens=toks, pending_hashes=pending
                )
            except ValueError:
                # Poison request: eject it so one bad entry cannot wedge
                # the queue head forever, then surface the error.
                self._requeue.popleft()
                raise
            if rec is None or rec is DEFERRED:
                break
            self._requeue.popleft()
            take(rec)
        if not self._requeue:
            for w in self._ranked(backend):
                if not self._admission_ok(backend, w.req):
                    break
                try:
                    rec = backend.try_admit(w.req, pending_hashes=pending)
                except ValueError:
                    self._waiting.remove(w)
                    raise
                if rec is None or rec is DEFERRED:
                    break
                self._waiting.remove(w)
                take(rec)
        for w in self._waiting:
            w.rounds_waited += 1
        return records

    def choose_victim(
        self, candidates: Sequence[Tuple[int, int, int]], protect: int = -1
    ) -> Optional[int]:
        """Preemption policy (see :func:`default_choose_victim`)."""
        return default_choose_victim(candidates, protect)

    def choose_steps_per_sync(self, backend) -> int:
        """Adaptive fused-decode depth (ROADMAP 3's remaining half): pick
        the smallest power-of-two N whose amortized host-sync overhead
        drops under 10% of the *live batch's* modeled decode tick
        (``perf_model.choose_steps_per_sync``). A deep batch with long
        contexts has slow ticks — N stays small and preemption stays
        responsive; a shallow batch with fast ticks is host-bound — N
        grows until the sync cost amortizes. Backends without a decode
        model keep the engine's current N."""
        from repro.core import perf_model

        model = self._decode_time_model or getattr(
            backend, "decode_time_model", None
        )
        if model is None:
            return max(int(getattr(backend, "steps_per_sync", 1)), 1)
        batch = max(backend.num_active, 1)
        mean_len = self._live_mean_len(backend)
        try:
            tick = (model(batch) if mean_len is None
                    else model(batch, mean_len=mean_len))
        except TypeError:  # injected batch-only test models
            tick = model(batch)
        return perf_model.choose_steps_per_sync(decode_tick_s=float(tick))
