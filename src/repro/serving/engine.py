"""``LLMEngine``: the one public serving entry point.

Pre-PR-5 the serving API was two sibling engine classes with duplicated
submit/step/run lifecycles, greedy-only host sampling, and a blocking
``run()`` as the only entry point — callers picked dense vs paged by
picking a class. The facade collapses that:

  * ``LLMEngine(cfg, params, kv_layout="auto")`` — layout is a *policy*
    resolved through the plan layer (``kernels.plan.resolve_kv_layout``,
    the paper's NUMA decode model), not a class choice; models the paged
    subsystem cannot hold (multi-codebook, SSM/hybrid, cross-attention)
    fall back to dense automatically;
  * ``add_request(...)`` / ``step() -> list[RequestOutput]`` — continuous
    batching with **streaming** outputs: each tick emits the tokens it
    appended, and terminating requests carry a ``finish_reason``;
  * ``generate(requests)`` — the blocking convenience loop over ``step``;
  * sampling is per-request (``SamplingParams``) and runs **on device**:
    one jitted batched sampler per tick (``serving.sampling``), keyed per
    request so outputs are reproducible across batch compositions and
    across preemption/resume. ``temperature=0`` is exact argmax — greedy
    outputs bit-match the pre-refactor engines;
  * admission / fairness / preemption policy lives in
    ``serving.scheduler`` (page budget, priority + FCFS aging, the
    NUMA-occupancy admission cap from ``core.perf_model``); the execution
    backends (``serving.backends``) are pure cache mechanism;
  * observability is injected (``telemetry=repro.obs.Telemetry.create()``):
    ``step()`` runs under spans (schedule / flush / decode, with the
    prefill launches, first-token sample and the scan's launch / sync /
    replay as their children, each also a profiler annotation), requests
    get lifecycle events (arrival -> admitted -> first_sampled ->
    first_token -> finish, with preempt/resume), each decode tick's wall
    time feeds the model-vs-measured drift collector, and all instruments
    are pre-bound at construction (``obs-no-hot-loop-allocs`` lint rule).
    The default is ``repro.obs.NULL_TELEMETRY`` — shared no-op
    instruments, no span/metric objects allocated per step.

``ServingEngine`` / ``PagedServingEngine`` survive as deprecated shims
over the facade; nothing outside ``repro.serving`` may construct them
(grep-enforced in ``tests/test_serving.py``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
import warnings
from typing import Callable, Dict, Iterable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.cache.pool import OutOfPages
from repro.configs.base import ModelConfig
from repro.kernels import plan as plan_lib
from repro.models import mla as mla_lib
from repro.obs import NULL_TELEMETRY, Telemetry
from repro.serving import sampling as sampling_lib
from repro.serving.backends import DenseBackend, PagedBackend
from repro.serving.request import (
    FINISH_LENGTH,
    FINISH_STOP,
    Request,
    RequestOutput,
    SamplingParams,
)
from repro.serving.scheduler import (
    DEFERRED,
    Scheduler,
    SchedulerStats,
    safe_rate,
)

__all__ = [
    "LLMEngine", "Request", "RequestOutput", "SamplingParams", "Result",
    "ServingEngine", "PagedServingEngine",
]

KV_LAYOUTS = ("auto", "dense", "paged")


def _paged_supported(cfg: ModelConfig) -> bool:
    """Whether the paged subsystem can hold this model: pure self-attention
    stacks, single-codebook streams (mirrors ``init_paged_caches``)."""
    if cfg.num_codebooks != 1:
        return False
    return all(
        spec.kind == "attn" and not spec.cross_attn
        for spec in cfg.all_layers()
    )


class LLMEngine:
    """Unified serving facade: one engine, scheduler-driven, both layouts.

    ``kv_layout="auto"`` resolves dense vs paged through the plan layer's
    analytic NUMA decode model for this engine's steady-state shape;
    ``"dense"`` / ``"paged"`` pin it. Capacity knobs: ``max_batch`` decode
    rows and a ``cache_len`` dense stripe, or ``num_pages`` x
    ``page_size`` pool with ``max_pages_per_seq`` (default
    ``cache_len // page_size``) for paged. ``prompt_buckets=None`` picks
    per-layout defaults.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        kv_layout: str = "auto",
        max_batch: int = 8,
        cache_len: int = 2048,
        prompt_buckets=None,
        num_pages: int = 128,
        page_size: int = 16,
        max_pages_per_seq: Optional[int] = None,
        prefix_sharing: bool = True,
        reserve_pages: int = 1,
        batch_prefills: bool = True,
        mapping: Optional[str] = None,
        scheduler: Optional[Scheduler] = None,
        telemetry: Optional[Telemetry] = None,
        steps_per_sync=1,
        mesh=None,
        shard_params: bool = False,
        device_hbm_bytes=None,
        kv_dtype: str = "fp32",
        host_pool_bytes=None,
        detokenizer: Optional[Callable[[Sequence], str]] = None,
    ):
        if kv_layout not in KV_LAYOUTS:
            raise ValueError(
                f"kv_layout must be one of {KV_LAYOUTS}, got {kv_layout!r}"
            )
        # Quantized pools and the host tier are paged-subsystem features:
        # force the layout rather than silently dropping the knobs when
        # "auto" would have resolved dense.
        if (kv_dtype != "fp32" or host_pool_bytes) and kv_layout == "auto":
            kv_layout = "paged"
        if (kv_dtype != "fp32" or host_pool_bytes) and kv_layout == "dense":
            raise ValueError(
                "kv_dtype / host_pool_bytes require the paged KV layout"
            )
        # "auto": the scheduler re-picks N from the live batch's modeled
        # tick time before every sync (perf_model.choose_steps_per_sync);
        # powers of two only, so the fused launcher's jit keys stay O(log)
        # and steady-state decode never retraces.
        self._auto_steps = steps_per_sync == "auto"
        if self._auto_steps:
            steps_per_sync = 1
        elif not isinstance(steps_per_sync, int) or steps_per_sync < 1:
            raise ValueError(
                f"steps_per_sync must be a positive int or 'auto', "
                f"got {steps_per_sync!r}"
            )
        # Serving mesh: an int requests that many host devices on a 1-D
        # "model" axis (lazy import — launch depends on serving); a Mesh
        # passes through; None = single-device. The backends shard only
        # the KV caches over it (head-parallel); params are replicated
        # unless ``shard_params`` opts into tensor-parallel weights —
        # replication keeps every reduction device-local, which is what
        # makes sharded decode bit-exact vs the single-device engine.
        if isinstance(mesh, int):
            from repro.launch.mesh import make_serving_mesh

            mesh = make_serving_mesh(mesh)
        # ``mapping`` overrides the config's kernel-schedule policy for
        # this engine ("auto" or a paper schedule name); ``with_mapping``
        # validates a pinned name at construction instead of mid-trace.
        cfg = plan_lib.with_mapping(cfg, mapping)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            import jax

            if shard_params:
                from repro.distributed import sharding as sharding_lib

                params = jax.device_put(
                    params, sharding_lib.param_shardings(
                        mesh, jax.eval_shape(lambda p: p, params))
                )
            else:
                params = jax.device_put(
                    params, NamedSharding(mesh, PartitionSpec())
                )
        if kv_layout == "auto":
            if not _paged_supported(cfg):
                kv_layout = "dense"
            else:
                pick = plan_lib.resolve_kv_layout(
                    (max_batch, cfg.n_heads, cfg.n_kv_heads,
                     max(cache_len // 2, 1), cfg.head_dim),
                    capacity=cache_len,
                    page_size=page_size,
                    dtype_bytes=jnp.dtype(cfg.compute_dtype).itemsize,
                )
                kv_layout = "paged" if pick.startswith("paged") else "dense"
        if kv_layout == "dense":
            self.backend = DenseBackend(
                cfg, params, rows=max_batch, cache_len=cache_len,
                prompt_buckets=prompt_buckets or (128, 512, 2048),
                mesh=mesh,
            )
        else:
            self.backend = PagedBackend(
                cfg, params, num_pages=num_pages, page_size=page_size,
                rows=max_batch,
                max_pages_per_seq=(
                    max_pages_per_seq
                    # Default: a sequence may use the dense-equivalent
                    # stripe, clamped to what the pool can actually hold.
                    or max(1, min(cache_len // page_size, num_pages - 1))
                ),
                prompt_buckets=prompt_buckets or (32, 64, 128),
                prefix_sharing=prefix_sharing,
                reserve_pages=reserve_pages,
                batch_prefills=batch_prefills,
                mesh=mesh,
                device_hbm_bytes=device_hbm_bytes,
                kv_dtype=kv_dtype,
                host_pool_bytes=host_pool_bytes,
            )
        self.cfg = cfg
        self.scheduler = scheduler or Scheduler()
        self.backend.choose_victim = self.scheduler.choose_victim
        self.backend.on_preempt = self._on_preempt
        #: Decode steps fused into one jitted lax.scan per sync; the host
        #: (scheduler, output flush, telemetry) intervenes every N tokens.
        self.steps_per_sync = int(steps_per_sync)
        self.backend.steps_per_sync = self.steps_per_sync

        self._pending: Dict[int, np.ndarray] = {}   # row -> next token
        self._last_ticks = 0                        # live ticks, last scan
        self._last_held = None                      # held-expert slots a tick
        self._moe_layers = sum(1 for s in cfg.all_layers() if s.ffn == "moe")
        self._last_kv_lens: List[List[int]] = []    # per live tick (traced)
        self._streamed: Dict[int, int] = {}         # uid -> tokens emitted
        #: uid -> buffered outputs for live stream() consumers. Only uids
        #: with an open stream() generator have an entry; everything else
        #: flows through step()/generate() unchanged.
        self._stream_q: Dict[int, List[RequestOutput]] = {}
        #: Optional token->text hook: when set, every streamed
        #: RequestOutput carries ``text`` = detokenizer(new_tokens) — the
        #: incremental piece, not the whole completion.
        self._detokenizer = detokenizer
        self._completed: List[RequestOutput] = []
        self._next_uid = 0
        self._tokens_generated = 0
        self._elapsed = 0.0
        self._decode_elapsed = 0.0
        self._first_emitted: set = set()            # uids past first token

        # Telemetry: every instrument is bound HERE, once — the decode
        # hot path only touches pre-bound objects (obs-no-hot-loop-allocs
        # lint rule). The default NULL_TELEMETRY shares module-level
        # no-op singletons, so a disabled engine allocates nothing per
        # step.
        self.telemetry = telemetry or NULL_TELEMETRY
        self._tr = self.telemetry.tracer
        self.backend.tracer = self._tr
        self._drift = self.telemetry.drift
        m = self.telemetry.metrics
        self._m_requests = m.counter(
            "serving_requests_total", "requests accepted by add_request")
        self._m_steps = m.counter(
            "serving_steps_total", "engine ticks (step() calls)")
        self._m_tokens = m.counter(
            "serving_tokens_total", "tokens streamed to callers")
        self._m_admitted = m.counter(
            "serving_admissions_total", "admission records flushed")
        self._m_preempt = m.counter(
            "serving_preemptions_total", "rows evicted under page pressure")
        self._m_finished = m.counter(
            "serving_finished_total", "requests that reached finish")
        self._h_step = m.histogram(
            "serving_step_seconds", "one full step(): schedule+flush+decode")
        self._h_schedule = m.histogram(
            "serving_schedule_seconds", "admission-policy time per step")
        self._h_flush = m.histogram(
            "serving_flush_seconds", "prefill flush time per step")
        self._h_decode = m.histogram(
            "serving_decode_step_seconds",
            "fused decode + sample + bookkeeping per tick")
        self._g_running = m.gauge(
            "serving_running", "active decode rows")
        self._g_waiting = m.gauge(
            "serving_waiting", "queued + requeued requests")
        self._m_demotions = m.counter(
            "serving_kv_demotions_total",
            "KV pages demoted device -> host tier")
        self._m_promotions = m.counter(
            "serving_kv_promotions_total",
            "KV pages promoted host tier -> device")
        self._g_device_kv = m.gauge(
            "serving_kv_device_bytes_resident",
            "device KV pool bytes held by live pages")
        self._g_host_kv = m.gauge(
            "serving_kv_host_bytes_resident",
            "host-tier KV bytes held by demoted pages")
        # Backend tier counters are monotonic totals; the engine exports
        # deltas so telemetry resets don't double-count.
        self._tier_seen = {"demoted_pages": 0, "promoted_pages": 0}

    # -- public surface ----------------------------------------------------

    @property
    def kv_layout(self) -> str:
        return self.backend.kv_layout

    @property
    def mapping(self):
        """The plan-resolved kernel schedule for the backend's steady
        state (a pinned paper schedule passes through unchanged)."""
        return self.backend.mapping

    def add_request(
        self,
        request=None,
        *,
        prompt=None,
        sampling: Optional[SamplingParams] = None,
        uid: Optional[int] = None,
        priority: Optional[int] = None,
    ) -> int:
        """Queue one request; returns its uid. Pass a :class:`Request` or
        a raw ``prompt`` (+ optional ``sampling`` / ``priority``).
        Requests that can never be served (outgrow the cache; overflow
        every prefill bucket with prefix sharing off) are rejected
        *here*, not mid-decode."""
        if request is None:
            if prompt is None:
                raise ValueError("pass a Request or a prompt")
            if uid is None:
                uid = self._next_uid
            request = Request(uid, prompt, sampling,
                              0 if priority is None else priority)
        elif (prompt is not None or sampling is not None or uid is not None
              or priority is not None):
            raise ValueError("pass either a Request or prompt/... keywords")
        self._next_uid = max(self._next_uid, request.uid + 1)
        self.backend.validate(request)
        self.scheduler.add(request)
        self._m_requests.inc()
        self._tr.request_event(request.uid, "arrival",
                               prompt_len=len(request.prompt),
                               priority=request.priority)
        return request.uid

    def step(self, max_steps: Optional[int] = None) -> List[RequestOutput]:
        """One serving sync: admit + flush prefills, then up to
        ``max_steps`` (default: the engine's ``steps_per_sync``) fused
        decode ticks in **one jitted ``lax.scan``** over every active row,
        sampled on device with per-request params. Stop-token detection
        and per-row done masks stay on device; the host reconstructs
        outputs once, here. Returns the streamed increments — one
        :class:`RequestOutput` per request that gained tokens or finished
        this sync.

        Instrumented (when telemetry is on) as one ``step`` span holding
        ``schedule`` / ``flush`` / ``decode`` child spans. ``flush`` holds
        one ``prefill`` per launch and ``sample_first``; ``decode`` holds
        ``decode.launch`` / ``decode.sync`` / ``decode.replay`` and, once
        the scan is on the host, its live ``ticks`` and each live tick's
        KV lengths (``kv_lens``). The scan's wall time is folded into the
        drift collector under its live (batch, mean-context) cell as one
        sample per live scan tick."""
        if self._auto_steps:
            # Re-pick N from the live batch depth BEFORE admission: the
            # scheduler's page-budget check prices decode headroom off
            # ``backend.steps_per_sync`` (sync_reserve_pages), so the N
            # the scan will run with is the N admission was priced at.
            self.steps_per_sync = self.scheduler.choose_steps_per_sync(
                self.backend)
            self.backend.steps_per_sync = self.steps_per_sync
        n_steps = self.steps_per_sync if max_steps is None else int(max_steps)
        if n_steps < 1:
            raise ValueError(f"max_steps must be >= 1, got {max_steps}")
        t0 = time.perf_counter()
        records: List = []
        with self._tr.span("step"):
            try:
                with self._tr.span("schedule"):
                    self.scheduler.schedule(self.backend, records)
            finally:
                self._h_schedule.observe(time.perf_counter() - t0)
                # Flush even when a late admission raises (oversized
                # prompt, bucket overflow): rows admitted this round are
                # already claimed and must not reach a decode tick — or a
                # caller that catches the error — unprefilled.
                for rec in records:
                    uid = rec["req"].uid
                    self._m_admitted.inc()
                    # A row whose output list is pre-seeded was admitted
                    # with replay tokens: that is a preemption resume.
                    if self.backend.out[rec["row"]]:
                        self._tr.request_event(uid, "resume", row=rec["row"])
                    else:
                        self._tr.request_event(
                            uid, "admitted", row=rec["row"],
                            prompt_tokens=int(rec["n"]),
                            reused_pages=len(rec.get("matched", ())))
                if records:
                    tf = time.perf_counter()
                    with self._tr.span("flush", rows=len(records)) as sp:
                        self._flush(records)
                        held = getattr(self.backend, "flush_held_slots", None)
                        if self._tr.enabled and held:
                            sp.args["held_slots"] = sum(held)
                            sp.args["moe_layers"] = self._moe_layers
                    self._h_flush.observe(time.perf_counter() - tf)
            outputs: List[RequestOutput] = []
            if self.backend.active.any():
                nb = self.backend.num_active
                mean_len = 0.0
                if self._drift.enabled:
                    live = self.backend.lengths[self.backend.active]
                    mean_len = float(live.mean()) if live.size else 0.0
                td = time.perf_counter()
                with self._tr.span("decode", batch=nb, steps=n_steps) as sp:
                    outputs = self._decode_tick(n_steps)
                    if self._tr.enabled:
                        sp.args["ticks"] = self._last_ticks
                        sp.args["kv_lens"] = self._last_kv_lens
                        if self._last_held is not None:
                            sp.args["held_slots"] = self._last_held
                            sp.args["moe_layers"] = self._moe_layers
                dt = time.perf_counter() - td
                self._h_decode.observe(dt)
                self._decode_elapsed += dt
                self._drift.record(nb, mean_len, dt,
                                   ticks=self._last_ticks)
            self._emit_lifecycle(outputs)
        self._m_steps.inc()
        self._g_running.set(self.backend.num_active)
        self._g_waiting.set(self.scheduler.num_waiting)
        self._observe_tier()
        dt_all = time.perf_counter() - t0
        self._h_step.observe(dt_all)
        self._elapsed += dt_all
        return outputs

    def _emit_lifecycle(self, outputs: List[RequestOutput]) -> None:
        """Per-request lifecycle events for this tick's streamed
        increments: first_token on the first emission, one ``tokens``
        event per emission (the measured inter-token stream), finish on
        termination."""
        detok = self._detokenizer
        for o in outputs:
            if detok is not None:
                o.text = detok(o.new_tokens)
            n = len(o.new_tokens)
            if n:
                self._m_tokens.inc(n)
                if o.uid not in self._first_emitted:
                    self._first_emitted.add(o.uid)
                    self._tr.request_event(o.uid, "first_token")
                self._tr.request_event(o.uid, "tokens", n=n)
            if o.finished:
                self._m_finished.inc()
                self._first_emitted.discard(o.uid)
                self._tr.request_event(o.uid, "finish",
                                       reason=o.finish_reason,
                                       tokens=len(o.tokens))
            # Route a copy to any open stream() consumer of this uid —
            # whoever drives step() (generate, a load harness, another
            # stream), the push iterator still sees its own increments.
            buf = self._stream_q.get(o.uid)
            if buf is not None:
                buf.append(o)

    def _observe_tier(self) -> None:
        """Export the KV-tier residency picture once per step: demotion /
        promotion deltas since last observation plus bytes resident on
        each side. Separate from step() so the instruments are only
        *used* (inc/set) in the hot path, never looked up."""
        b = self.backend
        stats = getattr(b, "stats", None)
        if not stats or "demoted_pages" not in stats:
            return
        for key, ctr in (("demoted_pages", self._m_demotions),
                         ("promoted_pages", self._m_promotions)):
            delta = stats[key] - self._tier_seen[key]
            if delta:
                ctr.inc(delta)
                self._tier_seen[key] = stats[key]
        page_bytes = b.kv_pool_bytes() // max(b.pool.num_pages, 1)
        self._g_device_kv.set(b.pool.used_pages * page_bytes)
        self._g_host_kv.set(
            b.host.bytes_resident if b.host is not None else 0)

    def generate(self, requests: Iterable = ()) -> List[RequestOutput]:
        """Blocking convenience: queue ``requests``, drive :meth:`step`
        until every queued request (including preempted ones) finishes,
        and return their final outputs in completion order. If a queued
        request can never be admitted, raises ``OutOfPages`` with the
        outputs that *did* finish this call on its ``completed``
        attribute (they also remain in the engine's history)."""
        for r in requests:
            self.add_request(r)
        done: List[RequestOutput] = []
        while self.backend.active.any() or self.scheduler.has_work():
            idle_before = not self.backend.active.any()
            outs = self.step()
            done.extend(o for o in outs if o.finished)
            if idle_before and not outs and not self.backend.active.any():
                # The scheduler saw an empty engine and still admitted
                # nothing: no queued request can ever fit.
                err = OutOfPages(
                    "pool too small for any queued request; grow "
                    "num_pages or shrink prompts"
                )
                err.completed = done  # don't lose finished work
                raise err
        return done

    async def stream(
        self,
        request=None,
        *,
        prompt=None,
        sampling: Optional[SamplingParams] = None,
        priority: Optional[int] = None,
    ):
        """Push-style consumption of one request::

            async for out in engine.stream(prompt=toks, sampling=sp):
                print(out.text or out.new_tokens, end="")

        Queues the request and yields its :class:`RequestOutput`
        increments as they are produced, ending after the finished
        output. The iterator *drives* ``step()`` whenever it has nothing
        buffered; concurrent consumers (several streams, or a stream
        alongside ``generate()``) cooperate — every ``step()`` caller
        routes increments into each open stream's buffer, so each
        consumer sees exactly its own outputs regardless of who ticked
        the engine. Yields control to the event loop between ticks, so
        streams interleave under any asyncio runner. Raises
        ``OutOfPages`` (like :meth:`generate`) when the request can never
        be admitted."""
        uid = self.add_request(request, prompt=prompt, sampling=sampling,
                               priority=priority)
        q = self._stream_q.setdefault(uid, [])
        try:
            while True:
                while q:
                    out = q.pop(0)
                    yield out
                    if out.finished:
                        return
                idle_before = not self.backend.active.any()
                outs = self.step()
                if (idle_before and not outs
                        and not self.backend.active.any() and not q):
                    raise OutOfPages(
                        "pool too small for any queued request; grow "
                        "num_pages or shrink prompts"
                    )
                await asyncio.sleep(0)
        finally:
            self._stream_q.pop(uid, None)

    def close(self) -> None:
        """Teardown: release every live row and (for the paged backend)
        prove the page pool is fully free again. Raises
        :class:`repro.cache.pool.RefcountLeakError` if any path dropped a
        sequence without releasing its pages — serving tests call this so
        leaks fail loudly instead of surviving to the next admission."""
        self.backend.shutdown()

    def stats(self) -> SchedulerStats:
        b = self.backend
        prefix = b.prefix_stats() if hasattr(b, "prefix_stats") else {}
        nb = max(b.num_active, 1)
        t = b.decode_time_model(nb)
        plan = b.decode_plan() if hasattr(b, "decode_plan") else None
        return SchedulerStats(
            kv_layout=b.kv_layout,
            running=b.num_active,
            waiting=self.scheduler.num_waiting,
            completed=len(self._completed),
            tokens_generated=self._tokens_generated,
            elapsed_s=self._elapsed,
            tokens_per_s=safe_rate(self._tokens_generated, self._elapsed),
            # None (not 0.0) when the backend has no prefix cache at all.
            prefix_hit_rate=prefix.get("prefix_hit_rate"),
            page_occupancy=b.page_occupancy,
            preemptions=b.stats["preemptions"],
            resumed_tokens=b.stats["resumed_tokens"],
            prefill_launches=b.stats["prefill_launches"],
            batched_prefills=b.stats["batched_prefills"],
            occupancy_cap=self.scheduler.occupancy_cap(b),
            modeled_tok_s=safe_rate(nb, t),
            measured_tok_s=safe_rate(
                self._tokens_generated, self._decode_elapsed),
            decode_elapsed_s=self._decode_elapsed,
            steps_per_sync=self.steps_per_sync,
            num_devices=b.num_devices,
            kv_dtype=str(prefix.get("kv_dtype", "fp32")),
            demoted_pages=int(prefix.get("demoted_pages", 0)),
            promoted_pages=int(prefix.get("promoted_pages", 0)),
            host_bytes_resident=int(prefix.get("host_bytes_resident", 0)),
            decode_splits=plan.num_splits if plan else 1,
            pages_per_block=plan.pages_per_block if plan else 0,
            latent_bytes_per_token=(
                mla_lib.latent_bytes_per_token(self.cfg)[0]
                if self.cfg.mla else 0),
            held_experts=(
                (self.cfg.moe.held_start,
                 self.cfg.moe.held_start + self.cfg.moe.held)
                if self.cfg.moe else None),
        )

    def drift_model_fn(self):
        """``(batch, mean_len) -> modeled seconds`` for
        :meth:`repro.obs.DriftCollector.report` — the backend's analytic
        decode model evaluated at the drift cell's live context."""
        model = self.backend.decode_time_model
        return lambda batch, mean_len: model(batch, mean_len=mean_len)

    def reset_metrics(self) -> None:
        """Zero telemetry *and* the engine's own wall-clock accumulators
        (load harnesses call this after warmup so measured numbers do not
        include compilation)."""
        self.telemetry.reset()
        self._elapsed = 0.0
        self._decode_elapsed = 0.0
        self._tokens_generated = 0

    # -- internals ---------------------------------------------------------

    def _on_preempt(self, row: int, req, generated: List) -> None:
        self._pending.pop(row, None)
        self.scheduler.requeue(req, generated)
        self._m_preempt.inc()
        self._tr.request_event(req.uid, "preempt", row=row,
                               generated=len(generated))

    def _seed_for(self, req) -> int:
        seed = req.sampling.seed
        return (req.uid if seed is None else seed) & 0x7FFFFFFF

    def _sampling_arrays(self, size, slots_rows):
        """``(size,)``-shaped per-slot sampling-param arrays for one
        device call. ``slots_rows``: (array slot, backend row) pairs —
        slots may be sparse (inactive rows keep inert defaults); the
        stream position is the row's generated-token count at call
        time."""
        temps = np.zeros((size,), np.float32)
        top_k = np.zeros((size,), np.int32)
        top_p = np.ones((size,), np.float32)
        seeds = np.zeros((size,), np.int32)
        pos = np.zeros((size,), np.int32)
        for slot, row in slots_rows:
            req = self.backend.row_req(row)
            sp = req.sampling
            temps[slot], top_k[slot], top_p[slot] = (
                sp.temperature, sp.top_k, sp.top_p
            )
            seeds[slot] = self._seed_for(req)
            pos[slot] = len(self.backend.out[row])
        return temps, top_k, top_p, seeds, pos

    def _flush(self, records: List) -> None:
        """Run the admitted prefills and sample each row's first token on
        device (stream position = tokens generated so far, so a resumed
        request continues its sample stream exactly)."""
        first = self.backend.flush(records)
        rows = sorted(first)
        if not rows:
            return
        with self._tr.span("sample_first"):
            logits = np.stack([first[r] for r in rows])
            params = self._sampling_arrays(len(rows), list(enumerate(rows)))
            toks = np.asarray(sampling_lib.sample_tokens(logits, *params))
        for i, r in enumerate(rows):
            self._pending[r] = toks[i]
            # A resumed row's output list holds its replayed tokens; its
            # first token was sampled before the preemption.
            if not self.backend.out[r]:
                self._tr.request_event(self.backend.row_req(r).uid,
                                       "first_sampled")

    def _stop_array(self, rows) -> np.ndarray:
        """Per-row stop-token ids, padded with -1 to a power-of-two width
        (the width is a jit-key component — bucketing bounds the fused
        launcher's compilations). Width 0 disables on-device stop
        detection entirely: no active row has stop tokens, or the stream
        is multi-codebook (scalar-token stop semantics don't apply)."""
        b = self.backend
        if self.cfg.num_codebooks != 1:
            return np.zeros((b.rows, 0), np.int32)
        width = max(
            (len(b.row_req(r).sampling.stop_token_ids) for r in rows),
            default=0,
        )
        if width == 0:
            return np.zeros((b.rows, 0), np.int32)
        width = 1 << (width - 1).bit_length()
        stops = np.full((b.rows, width), -1, np.int32)
        for r in rows:
            ids = b.row_req(r).sampling.stop_token_ids
            stops[r, : len(ids)] = ids
        return stops

    def _decode_tick(self, n_steps: int) -> List[RequestOutput]:
        """Launch the fused scan: reserve cache room for the whole sync,
        gather per-row tokens/sampling params, run up to ``n_steps``
        decode ticks on device, and hand the results to the sanctioned
        once-per-sync host sync point (:meth:`_sync_scan`)."""
        b = self.backend
        with self._tr.span("decode.launch"):
            # May preempt rows under page pressure; a preempted row drops
            # out of the scan entirely (its done mask starts True).
            b.reserve_rows(n_steps)
            rows = [r for r in range(b.rows) if b.active[r]]
            if not rows:
                self._last_ticks = 0
                self._last_kv_lens = []
                self._last_held = None
                return []
            shape = (b.rows,) if self.cfg.num_codebooks == 1 else (
                b.rows, self.cfg.num_codebooks)
            tok = np.zeros(shape, np.int32)
            for row in rows:
                if row in self._pending:
                    nxt = self._pending.pop(row)
                else:
                    nxt = b.out[row][-1]
                tok[row] = nxt
            temps, top_k, top_p, seeds, pos = self._sampling_arrays(
                b.rows, [(r, r) for r in rows])
            max_toks = np.zeros((b.rows,), np.int32)
            for r in rows:
                max_toks[r] = b.row_req(r).sampling.max_tokens
            ys, lengths_f = b.fused_decode(
                tok, pos, self._stop_array(rows), max_toks,
                temps, top_k, top_p, seeds, n_steps,
            )
        return self._sync_scan(ys, lengths_f)

    def _sync_scan(self, ys, lengths_f) -> List[RequestOutput]:
        """The once-per-sync host sync point: pull the scan's per-tick
        masks/tokens to host, replay them into per-row output lists,
        terminate finished rows, and emit the streamed increments — one
        :class:`RequestOutput` per row per sync, however many ticks ran."""
        b = self.backend
        with self._tr.span("decode.sync"):
            tok_seq, nxt_seq, live, appended, fed_stop, hit_max, *held = (
                np.asarray(y) for y in ys)
            lengths_f = np.asarray(lengths_f)
        with self._tr.span("decode.replay"):
            self._last_ticks = int(live.any(axis=1).sum())
            # An expert stack's held-expert slots of each live tick,
            # summed over its layers.
            self._last_held = (held[0][: self._last_ticks].tolist()
                               if held else None)
            if self._tr.enabled:
                # Live ticks come first; a row live at tick t attends over
                # its pre-scan length plus the t + 1 tokens fed so far.
                self._last_kv_lens = [
                    (b.lengths[live[t]] + t + 1).tolist()
                    for t in range(self._last_ticks)
                ]
            b.commit_scan(lengths_f)
            outputs: List[RequestOutput] = []
            for r in range(b.rows):
                col = live[:, r]
                if not col.any():
                    continue
                req = b.row_req(r)
                reason = None
                last_t = 0
                for t in range(col.shape[0]):
                    if not col[t]:
                        break
                    last_t = t
                    b.out[r].append(tok_seq[t, r].copy())
                    self._tokens_generated += 1
                    # Mask priority mirrors the single-step rules: a fed stop
                    # token outranks the length cap; a freshly *sampled* stop
                    # token is appended (no K/V write) and terminates.
                    if fed_stop[t, r]:
                        reason = FINISH_STOP
                        break
                    if hit_max[t, r]:
                        reason = FINISH_LENGTH
                        break
                    if appended[t, r]:
                        b.out[r].append(nxt_seq[t, r].copy())
                        self._tokens_generated += 1
                        reason = FINISH_STOP
                        break
                if reason is not None:
                    outputs.append(self._finish(r, req, reason))
                else:
                    self._pending[r] = nxt_seq[last_t, r]
                    delta = self._delta(req.uid, b.out[r])
                    if delta:
                        outputs.append(RequestOutput(
                            uid=req.uid, prompt_len=len(req.prompt),
                            new_tokens=delta, tokens=list(b.out[r]),
                        ))
            return outputs

    def _delta(self, uid: int, out: List) -> List:
        """Tokens not yet streamed for ``uid`` (replayed resume tokens
        were already emitted before the preemption — never re-streamed)."""
        emitted = self._streamed.get(uid, 0)
        if len(out) <= emitted:
            return []
        self._streamed[uid] = len(out)
        return list(out[emitted:])

    def _finish(self, row: int, req, reason: str) -> RequestOutput:
        toks = list(self.backend.out[row])
        delta = self._delta(req.uid, toks)
        self._streamed.pop(req.uid, None)
        self._pending.pop(row, None)
        self.backend.release(row)
        out = RequestOutput(
            uid=req.uid, prompt_len=len(req.prompt), new_tokens=delta,
            tokens=toks, finished=True, finish_reason=reason,
        )
        self._completed.append(out)
        return out


# -----------------------------------------------------------------------------
# Deprecated shims (kept importable; construction outside repro.serving is
# grep-enforced away in tests/test_serving.py)
# -----------------------------------------------------------------------------


@dataclasses.dataclass
class Result:
    """Legacy blocking-run result (pre-PR-5); prefer RequestOutput."""

    uid: int
    tokens: List
    prompt_len: int


class _EngineShim:
    """Thin adapter: legacy constructor surface -> ``LLMEngine``.

    ``rng_seed`` is accepted and ignored — sampling is now on-device and
    keyed per request (``SamplingParams.seed``), not by a shared host RNG.
    Unknown attributes delegate to the facade's backend (``pool``,
    ``prefix``, ``stats``, ``_prefill_p``, ...), then the facade.
    """

    def __init__(self, engine: LLMEngine):
        self._engine = engine
        self.results: List[Result] = []
        self._synced = 0

    def _sync_results(self) -> None:
        """Mirror the facade's completion history into the legacy
        ``results`` list — kept current by both run() and step(), so
        hand-driven submit()+step() loops see their finishes too."""
        done = self._engine._completed
        for o in done[self._synced:]:
            self.results.append(
                Result(uid=o.uid, tokens=list(o.tokens),
                       prompt_len=o.prompt_len)
            )
        self._synced = len(done)

    def run(self, requests) -> List[Result]:
        try:
            self._engine.generate(requests)
        finally:
            self._sync_results()
        return self.results

    def submit(self, req, resume_tokens=()) -> bool:
        """Legacy one-at-a-time admission: admit + flush immediately."""
        rec = self._engine.backend.try_admit(req, resume_tokens=resume_tokens)
        if rec is None or rec is DEFERRED:
            return False
        self._engine._flush([rec])
        return True

    def step(self) -> None:
        self._engine.step()
        self._sync_results()

    @property
    def mapping(self):
        return self._engine.mapping

    def __getattr__(self, name):
        engine = self.__dict__["_engine"]
        try:
            return getattr(engine.backend, name)
        except AttributeError:
            return getattr(engine, name)


class ServingEngine(_EngineShim):
    """DEPRECATED: use ``LLMEngine(cfg, params, kv_layout="dense")``."""

    def __init__(self, cfg, params, *, num_slots=8, cache_len=2048,
                 prompt_buckets=(128, 512, 2048), rng_seed=0, mapping=None):
        warnings.warn(
            "ServingEngine is deprecated; use LLMEngine(kv_layout='dense')",
            DeprecationWarning, stacklevel=2,
        )
        super().__init__(LLMEngine(
            cfg, params, kv_layout="dense", max_batch=num_slots,
            cache_len=cache_len, prompt_buckets=prompt_buckets,
            mapping=mapping,
        ))


class PagedServingEngine(_EngineShim):
    """DEPRECATED: use ``LLMEngine(cfg, params, kv_layout="paged")``."""

    def __init__(self, cfg, params, *, num_pages=128, page_size=16,
                 max_batch=8, max_pages_per_seq=16,
                 prompt_buckets=(32, 64, 128), rng_seed=0, mapping=None,
                 prefix_sharing=True, reserve_pages=1, batch_admissions=True):
        warnings.warn(
            "PagedServingEngine is deprecated; use "
            "LLMEngine(kv_layout='paged')",
            DeprecationWarning, stacklevel=2,
        )
        super().__init__(LLMEngine(
            cfg, params, kv_layout="paged", max_batch=max_batch,
            num_pages=num_pages, page_size=page_size,
            max_pages_per_seq=max_pages_per_seq,
            prompt_buckets=prompt_buckets, prefix_sharing=prefix_sharing,
            reserve_pages=reserve_pages, batch_prefills=batch_admissions,
            mapping=mapping,
        ))
