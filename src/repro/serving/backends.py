"""Execution backends for the serving facade: mechanism, not policy.

The pre-PR-5 ``ServingEngine`` / ``PagedServingEngine`` sibling classes
each owned a full submit/step/run lifecycle with host-side sampling baked
in. This module keeps only what actually differs between the two KV
layouts — cache plumbing — behind one protocol the scheduler and the
``LLMEngine`` facade drive:

  * ``try_admit(req, resume_tokens, pending_hashes)`` reserves a decode
    row (and, paged, its pages) and returns an admission record — or
    ``None`` (does not fit). A request whose prefix is being prefilled
    by a record admitted earlier in the *same* round shares those
    in-flight pages block-level (``flush`` orders the launches so the
    borrower's extend reads published content);
  * ``flush(records)`` runs the reserved prefills — one launch per shared
    jit key with the admitted rows stacked on the batch axis — and
    returns each row's last-position logits (sampling is the engine's
    job, on device);
  * ``reserve_rows(n)`` / ``fused_decode(...)`` / ``commit_scan(...)``
    advance up to N decode ticks in **one jitted ``lax.scan``** — decode
    kernel, on-device sampler, stop-token/max-token done masks, and the
    cache append all stay on device, so the host intervenes once per N
    tokens instead of once per token (ROADMAP item 3). Page-pool pressure
    inside ``reserve_rows`` (and the single-step ``prepare_row`` kept as
    the bit-exactness oracle) consults the injected ``choose_victim``
    policy and reports evictions through ``on_preempt`` — the backend
    executes preemption, the scheduler decides it;
  * ``release(row)`` frees a finished row; ``quote``/``free_pages``/
    ``evictable_pages``/``decode_time_model`` feed the scheduler's page
    budget and NUMA-occupancy admission policy.

``DenseBackend`` is the slot-per-sequence dense-stripe layout;
``PagedBackend`` is the paged pool with hash-chain prefix sharing,
per-token page append, COW, and head-major (NUMA head-aligned) placement
consumed natively by the paged kernels. All kernel scheduling flows
through ``kernels.plan``; the backends never thread schedule names or
query offsets.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.pool import (
    NULL_PAGE,
    OutOfPages,
    PagePool,
    SequencePages,
    SequenceReleasedError,
)
from repro.cache import quant
from repro.cache.prefix import PrefixCache, page_hashes
from repro.cache.tier import HostPageStore
from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.kernels import plan as plan_lib
from repro.models import transformer
from repro.obs import NULL_TELEMETRY
from repro.obs.tracing import NULL_SPAN
from repro.serving import sampling as sampling_lib
from repro.serving.scheduler import default_choose_victim


class _SeqState:
    """One active decode row."""

    __slots__ = ("req", "pages", "submit_order")

    def __init__(self, req, pages, submit_order):
        self.req = req
        self.pages = pages
        self.submit_order = submit_order


def _held_slot_counter(cfg: ModelConfig):
    """Wraps a prefill's outputs: an expert stack's ``(logits, caches,
    aux)`` becomes ``(logits, caches, held)``, ``held`` the routed slots
    of the real tail tokens (``last_positions`` + 1 per row) that landed
    on held experts, over every layer; other stacks' outputs pass."""
    if cfg.moe is None:
        return lambda out, last_positions: out

    def count(out, last_positions):
        logits, caches, aux = out
        per_tok = aux["moe_held_slots"].reshape(last_positions.shape[0], -1)
        real = jnp.arange(per_tok.shape[1])[None] <= last_positions[:, None]
        return logits, caches, jnp.sum(jnp.where(real, per_tok, 0))

    return count


def _map_groups(fn, caches, *others):
    """``fn(layer, *other_layers, scanned)`` over every layer of a cache
    tree: the scanned periods (a leading periods axis), the remainder and
    the lead layers (none), in the tree's own structure."""
    out = {}
    for group, scanned in (("scanned", True), ("rem", False), ("lead", False)):
        if group in caches:
            out[group] = tuple(
                fn(*layers, scanned)
                for layers in zip(caches[group], *(o[group] for o in others))
            )
    return out


class _Backend:
    """Shared row bookkeeping + policy hooks."""

    kv_layout: str
    rows: int
    #: Serving mesh (1-D "model" axis) the KV caches are sharded over;
    #: None = single-device. Set by :meth:`_setup_mesh`.
    mesh = None
    num_devices: int = 1
    #: Span recorder for the backend's own phases (``prefill``); the
    #: engine hands over its tracer.
    tracer = NULL_TELEMETRY.tracer

    def _setup_mesh(self, mesh, specs) -> None:
        """Place the cache tree under ``specs`` on ``mesh`` and remember
        the shardings so the jitted hot paths can re-constrain (GSPMD
        would otherwise be free to re-layout the donated scan carry).
        Head-sharded placement only moves bytes — every jitted program
        computes the same values, so sharded decode stays bit-exact."""
        from jax.sharding import NamedSharding, PartitionSpec

        self.mesh = mesh
        self.num_devices = int(mesh.devices.size) if mesh is not None else 1
        if mesh is None:
            self._cache_shardings = None
            return
        self._cache_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        self.caches = jax.device_put(self.caches, self._cache_shardings)

    def _on_mesh(self, fn):
        """``fn`` traced under ``ops.kernel_mesh``: on a serving mesh its
        Pallas kernels run per device on that device's heads (Mosaic
        kernels cannot be partitioned automatically). Off-mesh, ``fn``."""
        if self.num_devices == 1:
            return fn
        mesh = self.mesh

        @functools.wraps(fn)
        def traced_on_mesh(*args):
            with kernel_ops.kernel_mesh(mesh):
                return fn(*args)

        return traced_on_mesh

    def _constrain(self, caches):
        """Inside-jit sharding pin for the cache tree (identity off-mesh)."""
        if getattr(self, "_cache_shardings", None) is None:
            return caches
        return jax.tree.map(
            jax.lax.with_sharding_constraint, caches, self._cache_shardings
        )

    @staticmethod
    def _check_head_shards(cfg: ModelConfig, mesh) -> int:
        """Validate the head-sharded split and return the device count."""
        if mesh is None:
            return 1
        n = int(mesh.devices.size)
        if n > 1:
            from repro.distributed import sharding as sharding_lib

            # Raises with a clear message when Hkv % devices != 0.
            sharding_lib.kv_head_shards(cfg.n_kv_heads, n)
        return n

    def _init_rows(self, rows: int):
        self.rows = rows
        self.lengths = np.zeros((rows,), np.int32)
        self.active = np.zeros((rows,), bool)
        #: Generated tokens per row (includes replayed resume tokens) —
        #: row state, because preemption requeues them for replay.
        self.out: List[List] = [[] for _ in range(rows)]
        self._submit_counter = 0
        # Policy hooks, wired by LLMEngine; standalone backends fall back
        # to the default victim rule and collect orphaned preemptions.
        self.preempted: List[Tuple[object, List]] = []
        self.choose_victim: Callable = default_choose_victim
        self.on_preempt: Callable = (
            lambda row, req, toks: self.preempted.append((req, toks))
        )
        self.stats = {
            "preemptions": 0, "prefix_evictions": 0, "pages_reused": 0,
            "prompt_pages": 0, "cow_copies": 0, "extend_prefills": 0,
            "resumed_tokens": 0, "prefill_launches": 0,
            "batched_prefills": 0, "decode_traces": 0,
        }
        #: How many decode steps one engine sync fuses (set by LLMEngine;
        #: the scheduler prices page growth against it).
        self.steps_per_sync = 1
        # Fused-decode launchers, keyed (n_steps, stop-width bucket,
        # multi-codebook) — O(1) keys per engine, so steady-state decode
        # never retraces (stats["decode_traces"] counts traces).
        self._scan_cache: Dict = {}

    @property
    def num_active(self) -> int:
        return int(self.active.sum())

    def _bucket_for(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"prompt length {n} exceeds buckets {self.prompt_buckets}"
        )

    def fits_buckets(self, n: int) -> bool:
        return any(n <= b for b in self.prompt_buckets)

    # -- fused multi-step decode (the host-free hot loop) -------------------

    def reserve_rows(self, n_steps: int) -> None:
        """Reserve cache capacity for up to ``n_steps`` tokens per active
        row before a fused scan launches. Dense stripes pre-reserve every
        position at admission, so the base implementation is a no-op; the
        paged backend overrides it with page reservation."""

    def commit_scan(self, new_lengths: np.ndarray) -> None:
        """Adopt the post-scan per-row lengths. The paged backend also
        returns unconsumed reserved pages here (early stop / all-done
        exit); rows the scan finished are released by the engine *after*
        this commit, so trims always see live sequences."""
        self.lengths = np.array(new_lengths, dtype=self.lengths.dtype)

    def fused_decode(self, tok, gen, stops, max_toks,
                     temps, top_k, top_p, seeds, n_steps: int):
        """Run up to ``n_steps`` decode ticks in one jitted ``lax.scan``.

        Per scan tick, entirely on device: decode kernel -> per-request
        sampler (the same ``_sample_batch`` program the single-step path
        jits, so outputs are bit-exact) -> stop-token / max-token done-mask
        update -> cache append (paged rows write into pages reserved by
        :meth:`reserve_rows`; dense rows bump their stripe position). Rows
        finish mid-scan by freezing: their length stops advancing and (for
        paged) their page-table row nulls out so the re-fed token sinks
        into the null page — no live or shared page is ever re-written.
        A ``lax.cond`` skips the remaining ticks once every row is done.

        ``tok``: (rows,)[,K] token to feed first (the per-row pending
        sample); ``gen``: per-row generated-token counts (the sampler's
        stream position is scan-carried from here, so a fused run consumes
        the identical keyed sample stream as N single steps); ``stops``:
        (rows, W) stop-token ids padded with -1 (W == 0 disables stop
        detection — multi-codebook streams); ``max_toks``: per-row
        ``max_tokens``. Returns ``(ys, final_lengths)`` where ``ys`` are
        per-tick device arrays (fed token, next sample, live /
        appended-stop / fed-stop / hit-max masks) the engine reconstructs
        host state from once per sync, and ``final_lengths`` feeds
        :meth:`commit_scan`.
        """
        fn = self._fused_decode_fn(
            int(n_steps), int(stops.shape[1]),
            self.cfg.num_codebooks != 1,
        )
        paged = self.kv_layout == "paged"
        pt = (jnp.asarray(self.page_table) if paged
              else jnp.zeros((self.rows, 1), jnp.int32))
        carry, ys = fn(
            self.params, self.caches, pt, jnp.asarray(tok, jnp.int32),
            jnp.asarray(self.lengths), jnp.asarray(gen, jnp.int32),
            jnp.asarray(~self.active), jnp.asarray(temps),
            jnp.asarray(top_k), jnp.asarray(top_p), jnp.asarray(seeds),
            jnp.asarray(stops, jnp.int32), jnp.asarray(max_toks, jnp.int32),
        )
        self.caches = carry[0]
        return ys, carry[3]

    def _fused_decode_fn(self, n_steps: int, stop_width: int, multi: bool):
        key = (n_steps, stop_width, multi)
        fn = self._scan_cache.get(key)
        if fn is None:
            fn = self._build_fused_decode(n_steps, stop_width, multi)
            self._scan_cache[key] = fn
        return fn

    def _build_fused_decode(self, n_steps: int, stop_width: int,
                            multi: bool):
        from repro import compat

        cfg = self.cfg
        paged = self.kv_layout == "paged"
        stats = self.stats
        constrain = self._constrain
        # An expert stack also returns, per tick, the live rows' routed
        # slots that landed on held experts, summed over its layers.
        experts = cfg.moe is not None

        def decode_scan(params, caches, pt, tok, lengths, gen, done,
                        temps, top_k, top_p, seeds, stops, max_toks):
            # Trace-time side effect: fires once per compilation, so a
            # flat counter after warmup proves zero steady-state retraces.
            stats["decode_traces"] += 1
            caches = constrain(caches)

            def tick(carry):
                caches, pt, tok, lengths, gen, done = carry
                live = ~done
                lengths1 = lengths + live.astype(lengths.dtype)
                kw = dict(page_table=pt) if paged else {}
                if experts:
                    logits, caches1, aux = transformer.decode_step(
                        params, cfg, tok, caches, lengths1, return_aux=True,
                        **kw)
                    held = jnp.sum(jnp.where(live, aux["moe_held_slots"], 0))
                else:
                    logits, caches1 = transformer.decode_step(
                        params, cfg, tok, caches, lengths1, **kw)
                # Keep the scan carry head-sharded: without the pin GSPMD
                # may re-layout the donated caches between ticks, turning
                # the device-local page walk into resharding traffic.
                caches1 = constrain(caches1)
                gen1 = gen + live.astype(gen.dtype)
                nxt = sampling_lib._sample_batch(
                    logits, temps, top_k, top_p, seeds, gen1
                ).astype(tok.dtype)
                if stop_width:
                    fed_stop = live & (tok[:, None] == stops).any(axis=1)
                    nxt_stop = (nxt[:, None] == stops).any(axis=1)
                else:
                    fed_stop = nxt_stop = jnp.zeros_like(done)
                hit_max = live & (gen1 >= max_toks)
                done_fed = fed_stop | hit_max
                # A freshly sampled stop token is recorded in the output
                # but never decoded (no K/V write) — mirror of the
                # single-step path's early-stop append.
                append_nxt = live & ~done_fed & nxt_stop
                gen2 = gen1 + append_nxt.astype(gen.dtype)
                newly = done_fed | append_nxt
                pt1 = (jnp.where(newly[:, None], jnp.int32(NULL_PAGE), pt)
                       if paged else pt)
                keep = live & ~newly
                tok1 = (jnp.where(keep[:, None], nxt, tok) if multi
                        else jnp.where(keep, nxt, tok))
                y = (tok, nxt, live, append_nxt, fed_stop, hit_max)
                if experts:
                    y += (held.astype(jnp.int32),)
                return (caches1, pt1, tok1, lengths1, gen2, done | newly), y

            def skip(carry):
                # All rows done: early exit — carry is untouched and the
                # tick's masks read "nothing happened" on the host.
                tok = carry[2]
                false = jnp.zeros_like(carry[5])
                y = (tok, tok, false, false, false, false)
                if experts:
                    y += (jnp.zeros((), jnp.int32),)
                return carry, y

            def body(carry, _):
                return jax.lax.cond(carry[5].all(), skip, tick, carry)

            carry0 = (caches, pt, tok, lengths, gen, done)
            return jax.lax.scan(body, carry0, None, length=n_steps)

        # Donate the KV caches: the scan carry aliases its input buffers
        # in place of a copy (halves peak cache HBM on TPU/GPU; a silent
        # hint on CPU).
        return compat.donating_jit(self._on_mesh(decode_scan),
                                   donate_argnums=(1,))


# -----------------------------------------------------------------------------
# Dense slots
# -----------------------------------------------------------------------------


class DenseBackend(_Backend):
    """Slot-based dense KV: each row owns a ``cache_len`` stripe; new
    requests prefill into free slots (jitted per bucketed prompt length);
    one fused decode step advances every active slot. No preemption —
    a slot is committed until its sequence finishes."""

    kv_layout = "dense"

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        rows: int = 8,
        cache_len: int = 2048,
        prompt_buckets=(128, 512, 2048),
        mesh=None,
    ):
        self._check_head_shards(cfg, mesh)
        self.cfg = cfg
        self.params = params
        self.cache_len = cache_len
        self.prompt_buckets = tuple(b for b in prompt_buckets if b <= cache_len)
        self._init_rows(rows)
        self.caches = transformer.init_caches(
            params, cfg, rows, cache_len, image_len=cfg.vision_tokens or 0,
        )
        specs = None
        if mesh is not None:
            from repro.distributed import sharding as sharding_lib

            # (rows, Hkv, S, hd) stripes: heads on "model" (batch axes
            # resolve replicated on the 1-D serving mesh).
            specs = sharding_lib.cache_specs(cfg, mesh, self.caches)
        self._setup_mesh(mesh, specs)
        self.slot_req: List[Optional[object]] = [None] * rows
        constrain = self._constrain

        def decode_step(params, tok, caches, lengths):
            return transformer.decode_step(
                params, cfg, tok, constrain(caches), lengths)

        self._decode = jax.jit(self._on_mesh(decode_step))
        self._prefill = {}

    # -- capacity ----------------------------------------------------------

    def validate(self, req) -> None:
        n = len(req.prompt)
        if not self.fits_buckets(n):
            raise ValueError(
                f"prompt length {n} exceeds buckets {self.prompt_buckets}"
            )
        if n + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request {req.uid}: prompt {n} + max_tokens "
                f"{req.max_new_tokens} exceeds the dense cache stripe "
                f"({self.cache_len} tokens)"
            )

    def decode_time_model(self, batch: int,
                          mean_len: Optional[float] = None) -> float:
        # ``mean_len`` is accepted for protocol parity with the paged
        # model (drift calibration passes the live mean context) but
        # ignored: a dense decode streams the full stripe regardless of
        # how much of it is live.
        from repro import compat
        from repro.core import perf_model

        return perf_model.estimate_dense_decode(
            batch=batch, num_q_heads=self.cfg.n_heads,
            num_kv_heads=self.cfg.n_kv_heads, capacity=self.cache_len,
            head_dim=self.cfg.head_dim,
            dtype_bytes=jnp.dtype(self.cfg.compute_dtype).itemsize,
            topo=plan_lib.topology_for(compat.default_backend()),
        ).time

    @property
    def page_occupancy(self) -> float:
        return self.num_active / self.rows if self.rows else 0.0

    def prefix_stats(self) -> Dict[str, object]:
        """Dense stripes have no prefix cache: every sharing counter is a
        structural zero and ``prefix_hit_rate`` is **None** — "no cache",
        not "a cache that never hit" (PR 7 satellite; the old facade
        silently reported 0.0 here, indistinguishable from a cold paged
        cache)."""
        return {
            "prefix_entries": 0.0,
            "pages_reused": 0.0,
            "prompt_pages": 0.0,
            "prefix_hit_rate": None,
            "prefix_lookup_hits": 0.0,
            "prefix_lookup_queries": 0.0,
            "prefix_evictions": 0.0,
            "preemptions": float(self.stats["preemptions"]),
            "resumed_tokens": float(self.stats["resumed_tokens"]),
            "prefill_launches": float(self.stats["prefill_launches"]),
            "batched_prefills": float(self.stats["batched_prefills"]),
        }

    # -- admission / prefill ----------------------------------------------

    def try_admit(self, req, resume_tokens: Sequence = (),
                  pending_hashes=()):
        if resume_tokens:
            raise ValueError("dense backend does not preempt, so it "
                             "cannot resume")
        free = np.flatnonzero(~self.active)
        if len(free) == 0:
            return None
        n = len(req.prompt)
        bucket = self._bucket_for(n)
        row = int(free[0])
        self.lengths[row] = n
        self.active[row] = True
        self.slot_req[row] = req
        self.out[row] = []
        self._submit_counter += 1
        return {"req": req, "row": row, "n": n, "bucket": bucket,
                "prompt": np.asarray(req.prompt)}

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill:
            cfg = self.cfg

            def prefill(params, tokens, last_positions):
                return transformer.prefill(
                    params, cfg, tokens, cache_len=self.cache_len,
                    last_positions=last_positions,
                )

            self._prefill[bucket] = jax.jit(self._on_mesh(prefill))
        return self._prefill[bucket]

    def _write_slot_cache(self, slot: int, new_caches):
        """Copy a single-sequence prefilled cache into the slot stripe.

        Cache leaves carry batch at axis 1 for scanned stacks
        ((n_periods, B, ...)) and axis 0 for remainder layers.
        """

        def assign(dst, src):
            return dst.at[:, slot : slot + 1].set(src.astype(dst.dtype))

        def assign_rem(dst, src):
            return dst.at[slot : slot + 1].set(src.astype(dst.dtype))

        self.caches = {
            "scanned": jax.tree.map(
                assign, self.caches["scanned"], new_caches["scanned"]
            ),
            "rem": jax.tree.map(
                assign_rem, self.caches["rem"], new_caches["rem"]
            ),
        }

    def flush(self, records) -> Dict[int, np.ndarray]:
        """Prefill each admitted record into its slot; returns per-row
        last-position logits for the engine's first-token sample."""
        first_logits: Dict[int, np.ndarray] = {}
        for rec in records:
            n, bucket, tok = rec["n"], rec["bucket"], rec["prompt"]
            pad_width = [(0, bucket - n)] + [(0, 0)] * (tok.ndim - 1)
            padded = np.pad(tok, pad_width)[None]
            self.stats["prefill_launches"] += 1
            logits, caches1 = self._prefill_fn(bucket)(
                self.params, jnp.asarray(padded),
                jnp.asarray([n - 1], jnp.int32),
            )
            self._write_slot_cache(rec["row"], caches1)
            first_logits[rec["row"]] = np.asarray(logits)[0]
        return first_logits

    # -- decode / teardown -------------------------------------------------

    def prepare_row(self, row: int) -> None:
        pass  # dense stripes pre-reserve every position

    def decode(self, tok: np.ndarray):
        self.lengths = self.lengths + self.active.astype(np.int32)
        logits, self.caches = self._decode(
            self.params, jnp.asarray(tok), self.caches,
            jnp.asarray(self.lengths),
        )
        return logits

    def row_req(self, row: int):
        return self.slot_req[row]

    def release(self, row: int) -> None:
        self.active[row] = False
        self.slot_req[row] = None

    def shutdown(self) -> None:
        """Teardown: mark every slot free. Dense rows own no pool pages,
        so there is nothing to leak-check — this exists so LLMEngine.close
        is backend-agnostic."""
        for row in range(self.rows):
            self.active[row] = False
            self.slot_req[row] = None

    @property
    def mapping(self):
        """Plan-resolved steady-state prefill schedule (stats / capacity
        planning); a pinned paper schedule passes through unchanged."""
        return plan_lib.plan_for_config(
            self.cfg,
            (self.rows, self.cfg.n_heads, self.cfg.n_kv_heads,
             self.cache_len, self.cache_len, self.cfg.head_dim),
            phase=plan_lib.PREFILL,
        ).mapping


# -----------------------------------------------------------------------------
# Paged pool
# -----------------------------------------------------------------------------


class PagedBackend(_Backend):
    """Paged KV-cache backend (PR 2-4 mechanism, policy extracted).

    ``rows`` is only the width of the fused decode step (a jit-static
    shape); *capacity* is the page pool — admission succeeds when a
    request's non-shared prompt pages fit the free list with
    ``reserve_pages`` of decode headroom. Prefix sharing, per-token page
    append with COW, preemption + resume-by-replay, and head-major (NUMA
    head-aligned) placement all live here; who is admitted or evicted is
    the scheduler's call.

    Restrictions: pure self-attention stacks (``init_paged_caches``
    enforces it) and single-codebook token streams.
    """

    kv_layout = "paged"

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        num_pages: int = 128,
        page_size: int = 16,
        rows: int = 8,
        max_pages_per_seq: int = 16,
        prompt_buckets=(32, 64, 128),
        prefix_sharing: bool = True,
        reserve_pages: int = 1,
        batch_prefills: bool = True,
        mesh=None,
        device_hbm_bytes=None,
        kv_dtype: str = "fp32",
        host_pool_bytes=None,
    ):
        if cfg.num_codebooks != 1:
            raise ValueError("paged backend supports single-codebook models")
        self.kv_dtype = quant.validate_kv_dtype(kv_dtype)
        num_devices = self._check_head_shards(cfg, mesh)
        # Per-device page budgets: each device holds a (Hkv/D)-head slice
        # of every page, so a byte budget translates to a per-device page
        # capacity — and the *pool* is one global allocator, so the
        # tightest device clamps it (a page exists on every device or on
        # none; page tables stay replicated).
        self._page_budgets = None
        if device_hbm_bytes is not None:
            budgets = (
                tuple(float(b) for b in device_hbm_bytes)
                if isinstance(device_hbm_bytes, (tuple, list))
                else (float(device_hbm_bytes),) * num_devices
            )
            if len(budgets) != num_devices:
                raise ValueError(
                    f"device_hbm_bytes has {len(budgets)} entries for "
                    f"{num_devices} devices"
                )
            slice_bytes = self._page_slice_bytes(
                cfg, page_size, num_devices, kv_dtype
            )
            caps = tuple(int(b // slice_bytes) for b in budgets)
            clamp = min(caps)
            if clamp < 1 + max_pages_per_seq:
                limit = caps.index(clamp)
                raise ValueError(
                    f"device {limit} page budget holds {clamp} pages "
                    f"({budgets[limit]:.3g} B / {slice_bytes} B per page "
                    f"slice) < 1 + max_pages_per_seq={max_pages_per_seq}"
                )
            self._page_budgets = caps
            num_pages = min(num_pages, clamp)
        for b in prompt_buckets:
            if b % page_size:
                raise ValueError(
                    f"prompt bucket {b} must be a multiple of page_size "
                    f"{page_size}"
                )
        if num_pages - 1 < max_pages_per_seq:
            # A lone max-size sequence must always be able to grow to its
            # cap (evicting idle prefix pages on the way); otherwise decode
            # hits OutOfPages with nothing to preempt.
            raise ValueError(
                f"num_pages={num_pages} (usable {num_pages - 1}) cannot "
                f"hold one max_pages_per_seq={max_pages_per_seq} sequence"
            )
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.cache_len = max_pages_per_seq * page_size
        self.prompt_buckets = tuple(
            b for b in prompt_buckets if b <= self.cache_len
        )
        self.reserve_pages = reserve_pages
        self.prefix_sharing = prefix_sharing
        self.batch_prefills = batch_prefills
        self._init_rows(rows)

        self.pool = PagePool(num_pages, page_size)
        self.prefix = PrefixCache(self.pool)
        # Host tier: an LRU store of demoted pages behind the device pool,
        # keyed by the same chain hashes the prefix cache uses — so it is
        # only reachable with prefix sharing on (the hash chain IS the
        # promotion key; without it nothing ever demotes).
        self.host: Optional[HostPageStore] = None
        if host_pool_bytes:
            if not prefix_sharing:
                raise ValueError(
                    "host_pool_bytes requires prefix_sharing=True: demoted "
                    "pages are keyed by the prefix hash chain"
                )
            self.host = HostPageStore(
                int(host_pool_bytes),
                self._page_slice_bytes(cfg, page_size, 1, kv_dtype),
            )
        self.stats.update({
            "demoted_pages": 0, "promoted_pages": 0,
            "inflight_pages_reused": 0,
        })
        #: Same-flush block-level sharing: chain hash -> (physical page,
        #: publishing request uid) for pages admitted-but-not-yet-flushed
        #: this round. Cleared by :meth:`flush` once everything published.
        self._pending_pages: Dict[bytes, Tuple[int, int]] = {}
        #: An expert stack's held-expert slots of the last flush, one
        #: count per launch (``_held_slot_counter``).
        self.flush_held_slots: List[int] = []
        self.caches = transformer.init_paged_caches(
            params, cfg, num_pages, page_size, kv_dtype=kv_dtype
        )
        specs = None
        if mesh is not None:
            from repro.distributed import sharding as sharding_lib

            specs = sharding_lib.paged_cache_specs(mesh, self.caches)
        self._setup_mesh(mesh, specs)
        # Inactive rows keep all-null page tables and length 0: the decode
        # step writes their token into the reserved null page and the
        # kernel emits zeros for them.
        self.page_table = np.zeros((rows, max_pages_per_seq), np.int32)
        self.seqs: List[Optional[_SeqState]] = [None] * rows

        constrain = self._constrain

        # Each program is named before it is jitted: the name is the
        # module's in a profiler trace (``jit_<name>``).
        def decode_step(params, tok, caches, lengths, pt):
            return transformer.decode_step(
                params, cfg, tok, constrain(caches), lengths, page_table=pt)

        def scatter_tail(caches, tails, pids):
            return constrain(self._scatter_tail(caches, tails, pids))

        def copy_page(caches, src, dst):
            return constrain(self._copy_page(caches, src, dst))

        def restore_page(caches, payload, dst):
            return constrain(self._restore_page(caches, payload, dst))

        self._decode = jax.jit(self._on_mesh(decode_step))
        self._prefill_p: Dict = {}
        self._scatter_jit = jax.jit(scatter_tail)
        self._copy_jit = jax.jit(copy_page)
        self._restore_jit = jax.jit(restore_page)

    # -- capacity ----------------------------------------------------------

    @staticmethod
    def _page_slice_bytes(cfg: ModelConfig, page_size: int,
                          num_devices: int, kv_dtype: str = "fp32") -> int:
        """Bytes one physical page occupies in ONE device's HBM: the
        (Hkv / D)-head K+V slice of that page, summed over every layer
        (one pool per attention layer, all driven by the same ids).
        Quantized pools store 1-byte codes plus one fp32 scale per
        (kv head, page) for K and V each. A latent-attention stack holds
        one lane-padded latent row per token a layer."""
        if cfg.mla is not None:
            from repro.models import mla as mla_lib

            return (cfg.n_layers * page_size
                    * mla_lib.latent_bytes_per_token(cfg)[1])
        heads_dev = -(-cfg.n_kv_heads // max(num_devices, 1))
        if kv_dtype in quant.QMAX:
            per_head = page_size * cfg.head_dim * quant.kv_itemsize(kv_dtype) + 4
        else:
            per_head = (
                page_size * cfg.head_dim
                * jnp.dtype(cfg.compute_dtype).itemsize
            )
        return 2 * cfg.n_layers * heads_dev * per_head

    def kv_pool_bytes(self) -> int:
        """Total device bytes the paged pools (+ scale metadata) occupy
        across the mesh — the capacity headline the kv_dtype knob shrinks
        (int8 lands at ~0.25x the fp32 pool)."""
        return (
            self._page_slice_bytes(
                self.cfg, self.page_size, self.num_devices, self.kv_dtype
            )
            * self.pool.num_pages * self.num_devices
        )

    @property
    def _kv_dtype_bytes(self) -> int:
        """Per-element pool bytes the perf models should price: the code
        width for quantized pools (HBM traffic shrinks with storage —
        dequant happens in VMEM), the compute itemsize otherwise."""
        if self.kv_dtype in quant.QMAX:
            return quant.kv_itemsize(self.kv_dtype)
        return jnp.dtype(self.cfg.compute_dtype).itemsize

    def device_page_budgets(self) -> Optional[Dict[str, object]]:
        """Per-device page capacities under ``device_hbm_bytes`` (None
        when no budget was given): capacities, the limiting device, and
        the effective pool size after the clamp — what the scheduler's
        ``page_budget_ok`` is implicitly pricing via ``free_pages``."""
        if self._page_budgets is None:
            return None
        caps = self._page_budgets
        return {
            "capacities": caps,
            "limiting_device": caps.index(min(caps)),
            "effective_num_pages": self.pool.num_pages,
        }

    def validate(self, req) -> None:
        tok = np.asarray(req.prompt)
        if tok.ndim != 1:
            raise ValueError("paged backend expects flat token prompts")
        n = len(tok)
        if self.pool.pages_needed(n + req.max_new_tokens) > self.max_pages_per_seq:
            raise ValueError(
                f"request {req.uid}: prompt {n} + max_new_tokens "
                f"{req.max_new_tokens} can outgrow max_pages_per_seq="
                f"{self.max_pages_per_seq} ({self.cache_len} tokens) "
                "mid-decode; reject at admission instead"
            )
        if not self.prefix_sharing and not self.fits_buckets(n):
            # With sharing on, a long prompt may still be servable through
            # a prefix match (a runtime condition, checked at admission);
            # without it the tail is always the full prompt — reject now.
            raise ValueError(
                f"prompt length {n} exceeds buckets {self.prompt_buckets}"
            )

    def quote(self, req) -> Tuple[int, int]:
        """Page-budget quote for the scheduler: (total pages the prompt
        needs, shared pages it would reuse *without allocating*). A pure
        peek — nothing is reserved, LRU order and hit-rate counters stay
        untouched (the scheduler may price a blocked request every
        round). Reuse counts device prefix-cache matches plus the
        in-flight continuation (pages a record admitted this round will
        publish at flush — the borrower increfs rather than allocates).
        Host-tier matches are deliberately **excluded**: a promoted page
        still consumes a fresh device page, so for the page budget it is
        indistinguishable from a prefill — only
        :meth:`prefill_time_saved` prices the recompute it avoids."""
        n = len(req.prompt)
        total = self.pool.pages_needed(n)
        matched = 0
        if self.prefix_sharing and n > 1:
            limit = (n - 1) // self.page_size
            hashes = req.page_hashes(self.page_size)
            matched = len(self.prefix.lookup(hashes[:limit], touch=False))
            while (matched < limit
                   and hashes[matched] in self._pending_pages):
                matched += 1
        return total, matched

    @property
    def free_pages(self) -> int:
        return self.pool.free_pages

    @property
    def evictable_pages(self) -> int:
        return len(self.prefix)

    @property
    def page_occupancy(self) -> float:
        return self.pool.used_pages / max(self.pool.num_pages - 1, 1)

    def decode_time_model(self, batch: int,
                          mean_len: Optional[float] = None) -> float:
        # Default planning shape is half-full sequences; drift calibration
        # passes the cell's *measured* live mean context instead, so the
        # comparison prices what the machine actually decoded. On a mesh
        # the sharded estimate prices the per-device head slice plus the
        # attention-output gather.
        from repro import compat
        from repro.core import numa, perf_model

        kw = dict(
            batch=batch, num_q_heads=self.cfg.n_heads,
            num_kv_heads=self.cfg.n_kv_heads,
            mean_len=(max(int(mean_len), self.page_size) if mean_len
                      else max(self.cache_len // 2, self.page_size)),
            page_size=self.page_size, head_dim=self.cfg.head_dim,
            dtype_bytes=self._kv_dtype_bytes,
        )
        chip = plan_lib.topology_for(compat.default_backend())
        if self.num_devices > 1:
            return perf_model.estimate_sharded_paged_decode(
                mesh=numa.mesh_topology(self.num_devices, chip=chip), **kw
            ).time
        return perf_model.estimate_paged_decode(topo=chip, **kw).time

    def prefill_time_saved(self, req) -> float:
        """Modeled prefill seconds cache reuse would save this request if
        admitted *now* — the scheduler's cost-aware tie-break within a
        priority class. Priced as (full prefill) minus (extend over the
        matched prefix), both via
        :func:`core.perf_model.estimate_extend_prefill`; a host-tier
        continuation of the match adds its saved recompute **minus** the
        device<->host transfer (:func:`core.perf_model.
        estimate_tier_transfer`) — promotion is only credited where the
        link beats the FLOPs, which is exactly the demote-vs-recompute
        call the tier exists to win. Zero when nothing matches."""
        from repro import compat
        from repro.core import perf_model

        _, matched = self.quote(req)
        n = len(req.prompt)
        limit = (n - 1) // self.page_size
        host_run = 0
        if self.host is not None and matched < limit:
            hashes = req.page_hashes(self.page_size)
            for h in hashes[matched:limit]:
                if h not in self.host:
                    break
                host_run += 1
        if matched <= 0 and host_run <= 0:
            return 0.0
        prefix = min(matched * self.page_size, n - 1)
        both = min((matched + host_run) * self.page_size, n - 1)
        topo = plan_lib.topology_for(compat.default_backend())
        dtype_bytes = self._kv_dtype_bytes

        def _t(prefix_len: int) -> float:
            return perf_model.estimate_extend_prefill(
                batch=1, num_q_heads=self.cfg.n_heads,
                num_kv_heads=self.cfg.n_kv_heads,
                prefix_len=prefix_len, tail_len=n - prefix_len,
                page_size=self.page_size, head_dim=self.cfg.head_dim,
                dtype_bytes=dtype_bytes, topo=topo,
            ).time

        saved = max(_t(0) - _t(prefix), 0.0)
        if host_run > 0:
            transfer = perf_model.estimate_tier_transfer(
                host_run * self.host.page_nbytes
            )
            saved += max(_t(prefix) - _t(both) - transfer, 0.0)
        return saved

    # -- jitted cache plumbing ---------------------------------------------

    @staticmethod
    def _scatter_tail(caches, tail_caches, pids):
        """Write prefilled tails' dense K/V into freshly allocated pages.

        pids: (rows, bucket/ps) destinations, one row per admitted
        sequence in the (possibly batched) prefill; entries past a tail's
        real pages are the null page (their writes are garbage sinks by
        design — with several rows the null page takes whichever write
        lands last, all equally garbage). Quantized pools store per-page
        codes and set the destinations' scale entries in the same jitted
        program (``cache.quant.scatter_pages``); the pages axis is third
        from the end for both the flat and the scanned stacks, so one
        reshape serves both.
        """
        flat = pids.reshape(-1)

        def s(pages, scales, dense, scanned, kv_dtype):
            if scanned:
                npp, rows, hkv, bucket, hd = dense.shape
                ps = pages.shape[3]
                new = dense.reshape(npp, rows, hkv, bucket // ps, ps, hd)
                new = new.transpose(0, 2, 1, 3, 4, 5).reshape(
                    npp, hkv, rows * (bucket // ps), ps, hd
                )
            else:
                rows, hkv, bucket, hd = dense.shape
                ps = pages.shape[2]
                new = dense.reshape(rows, hkv, bucket // ps, ps, hd)
                new = new.transpose(1, 0, 2, 3, 4).reshape(
                    hkv, rows * (bucket // ps), ps, hd
                )
            return quant.scatter_pages(pages, scales, new, flat, kv_dtype)

        def layer(c, t, scanned):
            a = c["attn"]
            if "latent_pages" in a:
                lp, _ = s(a["latent_pages"], None, t["attn"]["latent"],
                          scanned, "fp32")
                return {"attn": {"latent_pages": lp}}
            kv_dtype = quant.kv_dtype_of(a["k_pages"].dtype)
            kp, ks = s(a["k_pages"], a.get("k_scales"), t["attn"]["k"],
                       scanned, kv_dtype)
            vp, vs = s(a["v_pages"], a.get("v_scales"), t["attn"]["v"],
                       scanned, kv_dtype)
            out = {"k_pages": kp, "v_pages": vp}
            if ks is not None:
                out["k_scales"] = ks
                out["v_scales"] = vs
            return {"attn": out}

        return _map_groups(layer, caches, tail_caches)

    @staticmethod
    def _copy_page(caches, src, dst):
        """Physical page copy (copy-on-write), every layer at once. The
        scale entry follows the page (``cache.quant.cow_scales``) so a
        forked quantized page dequantizes identically."""

        def cp(pages, scanned):
            if scanned:
                return pages.at[:, :, dst].set(pages[:, :, src])
            return pages.at[:, dst].set(pages[:, src])

        def layer(c, scanned):
            a = c["attn"]
            if "latent_pages" in a:
                return {"attn": {"latent_pages": cp(a["latent_pages"], scanned)}}
            out = {
                "k_pages": cp(a["k_pages"], scanned),
                "v_pages": cp(a["v_pages"], scanned),
            }
            if "k_scales" in a:
                out["k_scales"] = quant.cow_scales(a["k_scales"], src, dst)
                out["v_scales"] = quant.cow_scales(a["v_scales"], src, dst)
            return {"attn": out}

        return _map_groups(layer, caches)

    @staticmethod
    def _restore_page(caches, payload, dst):
        """Inverse of :meth:`_page_payload`: write one promoted page's
        host payload (codes + scale entries, every layer) into physical
        page ``dst``. ``dst`` is traced, so one compilation serves every
        promotion."""

        def put(pages, page, scanned):
            page = jnp.asarray(page).astype(pages.dtype)
            if scanned:
                return pages.at[:, :, dst].set(page)
            return pages.at[:, dst].set(page)

        def layer(c, pl, scanned):
            a = c["attn"]
            if "latent_pages" in a:
                return {"attn": {"latent_pages": put(a["latent_pages"],
                                                     pl["latent"], scanned)}}
            out = {
                "k_pages": put(a["k_pages"], pl["k"], scanned),
                "v_pages": put(a["v_pages"], pl["v"], scanned),
            }
            if "k_scales" in a:
                out["k_scales"] = a["k_scales"].at[..., dst].set(
                    jnp.asarray(pl["ks"], a["k_scales"].dtype)
                )
                out["v_scales"] = a["v_scales"].at[..., dst].set(
                    jnp.asarray(pl["vs"], a["v_scales"].dtype)
                )
            return {"attn": out}

        return _map_groups(layer, caches, payload)

    def _page_payload(self, pid: int):
        """Host (numpy) copy of one physical page across every layer's
        pools — codes plus scale entries, the opaque payload the
        :class:`HostPageStore` holds and :meth:`_restore_page` writes
        back. Pages-axis indexing mirrors the pool layouts: scanned
        stacks carry a leading periods axis."""

        def grab(c, scanned):
            a = c["attn"]
            idx = (
                (slice(None), slice(None), pid) if scanned
                else (slice(None), pid)
            )
            if "latent_pages" in a:
                return {"latent": np.asarray(a["latent_pages"][idx])}
            out = {
                "k": np.asarray(a["k_pages"][idx]),
                "v": np.asarray(a["v_pages"][idx]),
            }
            if "k_scales" in a:
                out["ks"] = np.asarray(a["k_scales"][..., pid])
                out["vs"] = np.asarray(a["v_scales"][..., pid])
            return out

        return _map_groups(grab, self.caches)

    # -- prefill -----------------------------------------------------------

    @staticmethod
    def _prefix_page_bucket(pages: int) -> int:
        """Bucket a live prefix page count to the next power of two: the
        page-table width is a jit constant, so bucketing bounds tail-
        prefill compilations at O(log smax) under diverse prefix lengths
        (the live length stays dynamic via ``prefix_len``)."""
        if pages <= 0:
            return 0
        return 1 << (pages - 1).bit_length()

    def _prefill_paged_fn(self, bucket: int, prefix_pages: int, rows: int = 1):
        """Jitted tail prefill, keyed by (tail bucket, prefix-page bucket,
        admitted rows) — ``rows > 1`` is the batched-admission launch: the
        admitted sequences stack on the batch axis of one call.

        The nonzero-prefix variant runs the **extend phase**: one
        backend-resolved ``AttentionPlan`` per key drives the paged
        prefill kernel, which reads prefix K/V straight from the page
        table — the pool tensors ride in as arguments, never gathered to
        dense.
        """
        key = (bucket, prefix_pages, rows)
        if key not in self._prefill_p:
            cfg = self.cfg
            count = _held_slot_counter(cfg)

            if prefix_pages == 0:
                def prefill(params, tokens, last_positions):
                    return count(transformer.prefill(
                        params, cfg, tokens, cache_len=bucket,
                        last_positions=last_positions,
                        return_aux=cfg.moe is not None,
                    ), last_positions)

                fn = prefill
            else:
                plan = plan_lib.plan_for_config(
                    cfg,
                    (rows, cfg.n_heads, cfg.n_kv_heads, bucket,
                     prefix_pages * self.page_size + bucket, cfg.head_dim),
                    phase=plan_lib.EXTEND, kv_layout=plan_lib.PAGED,
                    page_size=self.page_size, prefix_pages=prefix_pages,
                    kv_dtype=self.kv_dtype,
                )

                def extend(params, tokens, last_positions, caches,
                           page_table, prefix_len):
                    return count(transformer.prefill(
                        params, cfg, tokens, cache_len=bucket,
                        last_positions=last_positions,
                        prefix_caches=caches, page_table=page_table,
                        prefix_len=prefix_len, plan=plan,
                        return_aux=cfg.moe is not None,
                    ), last_positions)

                fn = extend
            self._prefill_p[key] = jax.jit(self._on_mesh(fn))
        return self._prefill_p[key]

    # -- admission ---------------------------------------------------------

    def _make_room(self, pages_needed: int) -> bool:
        """Free pages until ``pages_needed`` fit: evict idle prefix-cache
        pages first (pure capacity — with a host tier their content
        demotes instead of being lost, so nothing recomputes either way),
        then report whether the caller should preempt."""
        short = pages_needed - self.pool.free_pages
        if short > 0 and len(self.prefix):
            on_evict = self._demote_entry if self.host is not None else None
            self.stats["prefix_evictions"] += self.prefix.evict(
                short, on_evict=on_evict
            )
            short = pages_needed - self.pool.free_pages
        return short <= 0

    def _demote_entry(self, h: bytes, pid: int) -> None:
        """Prefix-eviction hook: copy the page's payload host-side before
        the device page frees. Runs under pool pressure only (cold pages:
        prefix-cache LRU tail — which includes preempted and finished
        sequences' published prefixes)."""
        if self.host.admit(h, self._page_payload(pid)):
            self.stats["demoted_pages"] += 1

    def _promote_chain(self, hashes) -> List[int]:
        """Continue a device prefix miss into the host tier: restore the
        longest host-resident run of ``hashes`` into freshly allocated
        device pages and publish them to the device prefix cache, so the
        caller extends off them exactly as if they had never left.
        Residency stays exclusive: :meth:`HostPageStore.take` consumes
        the host copy as each device page lands. Stops early (keeping
        what it restored evictable) when the pool cannot free a page."""
        run = self.host.lookup_chain(hashes)
        pids: List[int] = []
        for h in run:
            if not self._make_room(1):
                break
            try:
                pid = self.pool.alloc()
            except OutOfPages:
                break
            if h not in self.host:
                # _make_room's own demotions overflowed the host LRU onto
                # this very entry: the run is broken, stop cleanly.
                self.pool.decref(pid)
                break
            payload = self.host.take(h)
            self.caches = self._restore_jit(
                self.caches, payload, jnp.asarray(pid, jnp.int32)
            )
            self.prefix.insert([h], [pid])
            self.pool.decref(pid)  # the prefix cache owns it now
            pids.append(pid)
            self.stats["promoted_pages"] += 1
        return pids

    def _reserve(self, num_tokens: int, matched) -> Optional[SequencePages]:
        """Page-table reservation for one admission attempt: pin the matched
        prefix pages (lookup takes no references, and ``_make_room``'s
        prefix eviction would otherwise be free to recycle exactly these
        pages — they look idle until the sequence increfs them), make room,
        allocate. Returns None when the pool cannot satisfy it."""
        for p in matched:
            self.pool.incref(p)
        try:
            need = self.pool.pages_needed(num_tokens) - len(matched)
            if not self._make_room(need + self.reserve_pages):
                return None
            try:
                return self.pool.allocate_sequence(
                    num_tokens, shared_prefix=matched
                )
            except OutOfPages:
                return None
        finally:
            for p in matched:
                self.pool.decref(p)

    def try_admit(self, req, resume_tokens: Sequence = (),
                  pending_hashes=()):
        """Reserve a decode row and pages for a request; no prefill yet.

        Prefix-cache lookup happens first: shared full pages are reused
        (prefilled once, by whoever computed them) and only the tail is
        prefilled — through the paged prefill kernel, which reads the
        prefix straight from its pages. The match then continues
        block-level through pages a record admitted earlier in the
        *same* round will publish at flush (the borrower shares those
        in-flight pages instead of re-prefilling them; :meth:`flush`
        orders its launch after the publisher's), and finally into the
        host tier, promoting the longest demoted run back into fresh
        device pages. Returns an admission record for :meth:`flush`;
        None when the pool/rows cannot hold the request.
        ``pending_hashes`` is accepted for protocol compatibility but
        unused — the backend's own in-flight page map is authoritative.
        The row is claimed here (so subsequent admissions in the same
        round see it taken); the caller must flush before the next
        decode tick.

        ``resume_tokens``: tokens a preempted run of this request already
        generated. They are replayed through the same extend path (they
        are just more prompt from the cache's point of view), so decode
        resumes mid-stream instead of restarting from scratch.
        """
        free_rows = np.flatnonzero(~self.active)
        if len(free_rows) == 0:
            return None
        tok = np.asarray(req.prompt)
        if tok.ndim != 1:
            raise ValueError("paged backend expects flat token prompts")
        orig_n = len(tok)
        if len(resume_tokens):
            tok = np.concatenate(
                [tok, np.asarray([int(t) for t in resume_tokens], tok.dtype)]
            )
        n = len(tok)
        ps = self.page_size
        total_pages = self.pool.pages_needed(n)
        if total_pages > self.max_pages_per_seq:
            raise ValueError(
                f"prompt needs {total_pages} pages > "
                f"max_pages_per_seq {self.max_pages_per_seq}"
            )
        self.validate(req)

        if not self.prefix_sharing:
            hashes = []
        elif len(resume_tokens):
            hashes = page_hashes(tok, ps)  # replay extends the stream
        else:
            hashes = req.page_hashes(ps)   # memoized on the request
        # Reuse at most (n-1)//ps pages: at least one tail token must be
        # prefilled here to produce the next-token logits.
        limit = (n - 1) // ps
        matched = self.prefix.lookup(hashes[:limit])
        after: set = set()
        if self.prefix_sharing:
            # Continue block-level through same-round in-flight pages: the
            # borrower increfs the publisher's pages and records the
            # dependency so flush publishes before it extends.
            inflight = 0
            while (len(matched) < limit
                   and hashes[len(matched)] in self._pending_pages):
                pid, owner = self._pending_pages[hashes[len(matched)]]
                matched.append(pid)
                after.add(owner)
                inflight += 1
            self.stats["inflight_pages_reused"] += inflight
            # ... and finally into the host tier: promote the longest
            # demoted run back into fresh device pages.
            if self.host is not None and len(matched) < limit:
                matched.extend(
                    self._promote_chain(hashes[len(matched):limit])
                )

        # Validate the prefill bucket before touching the allocator (a late
        # ValueError must not leak pages).
        if not self.fits_buckets(n - len(matched) * ps):
            if len(resume_tokens):
                # A replay tail no bucket holds: drop replayed tokens until
                # it fits (decode regenerates them exactly — the sampler is
                # keyed per request and stream position). The prefix match
                # for a truncated sequence is the full match capped at its
                # page count, so the fit is computable without re-hashing;
                # keep the longest replay that fits.
                m_full = len(matched)
                for keep in range(len(resume_tokens) - 1, -1, -1):
                    nk = orig_n + keep
                    mk = min(m_full, (nk - 1) // ps)
                    if self.fits_buckets(nk - mk * ps):
                        return self.try_admit(
                            req, list(resume_tokens)[:keep], pending_hashes
                        )
                # Not even the bare prompt fits (its prefix pages were
                # evicted since first admission): fall through to the
                # admission error below.
            raise ValueError(
                f"prompt tail {n - len(matched) * ps} exceeds buckets "
                f"{self.prompt_buckets}"
            )
        seq = self._reserve(n, matched)
        if seq is None and matched and self.fits_buckets(n):
            # Reuse blocked admission (the pinned prefix pages were the only
            # evictable capacity): fall back to prefilling from scratch so a
            # servable request is never starved by its own cached prefix.
            # Prompts only servable *through* reuse stay queued instead
            # (pages free up as sequences finish).
            matched = []
            after = set()
            seq = self._reserve(n, matched)
        if seq is None:
            return None
        m = len(matched)
        tail = tok[m * ps :]
        bucket = self._bucket_for(len(tail))
        self.stats["pages_reused"] += m
        self.stats["prompt_pages"] += total_pages

        # Claim the decode row now — pages and row are spoken for; the
        # prefill itself runs at flush time.
        row = int(free_rows[0])
        self.seqs[row] = _SeqState(
            req=req, pages=seq, submit_order=self._submit_counter
        )
        self._submit_counter += 1
        self.page_table[row] = NULL_PAGE
        self.page_table[row, : len(seq.pages)] = seq.pages
        self.lengths[row] = n
        self.active[row] = True
        self.out[row] = list(resume_tokens)
        self.stats["resumed_tokens"] += len(resume_tokens)
        if self.prefix_sharing:
            # Expose the fresh full pages this record will prefill for
            # same-round block-level sharing (matched ones are already
            # published, pending, or just promoted).
            for i in range(m, n // ps):
                self._pending_pages[hashes[i]] = (seq.pages[i], req.uid)
        return {
            "req": req, "row": row, "seq": seq, "matched": matched,
            "tail": tail, "bucket": bucket, "n": n, "hashes": hashes,
            "mb": self._prefix_page_bucket(m) if m else 0,
            "pending_publish": tuple(hashes[: n // ps]),
            "after": frozenset(after),
        }

    def flush(self, records) -> Dict[int, np.ndarray]:
        """Run the admitted records' tail prefills in **dependency
        waves**: a borrower of same-round in-flight pages launches
        strictly after every record it borrows from has scattered and
        published (its ``after`` uid set), so an extend never reads pages
        whose contents this same flush still owes. Dependencies always
        point to earlier admissions, so the partition terminates.
        Within a wave, one launch per shared (tail-bucket,
        prefix-page-bucket) jit key with the admitted rows stacked on the
        batch axis (``batch_prefills=False`` launches one row at a time —
        the bit-exactness oracle in tests). Returns per-row last-position
        logits."""
        first_logits: Dict[int, np.ndarray] = {}
        self.flush_held_slots = []
        todo = list(records)
        published: set = set()
        while todo:
            wave = [
                r for r in todo if r.get("after", frozenset()) <= published
            ]
            if not wave:  # unreachable by construction; never deadlock
                wave = list(todo)
            done = {id(r) for r in wave}
            todo = [r for r in todo if id(r) not in done]
            self._flush_wave(wave, first_logits)
            published.update(r["req"].uid for r in wave)
        # Everything admitted this round is now published (or matched):
        # later rounds share through the prefix cache proper.
        self._pending_pages.clear()
        return first_logits

    def _flush_wave(self, records, first_logits: Dict[int, np.ndarray]):
        """One dependency wave of :meth:`flush`: group by jit key, run
        the tail prefills, scatter each row's K/V into its fresh pages,
        publish full pages to the prefix cache. The paged prefill kernel
        takes per-row ``prefix_len`` / ``tail_len``, so rows with
        different live lengths share a launch; rows are independent
        (per-row page tables, per-row online softmax), so outputs are
        bit-exact regardless of batching."""
        ps = self.page_size
        groups: Dict[Tuple[int, int], list] = {}
        if self.batch_prefills:
            for rec in records:
                groups.setdefault((rec["bucket"], rec["mb"]), []).append(rec)
        else:
            for i, rec in enumerate(records):
                groups[(rec["bucket"], rec["mb"], i)] = [rec]
        for (bucket, mb, *_), grp in groups.items():
            with self._prefill_span(bucket, mb, grp):
                rows = len(grp)
                padded = np.stack([
                    np.pad(r["tail"], (0, bucket - len(r["tail"])))
                    for r in grp
                ])
                last = jnp.asarray(
                    [len(r["tail"]) - 1 for r in grp], jnp.int32
                )
                self.stats["prefill_launches"] += 1
                self.stats["batched_prefills"] += rows > 1
                fn = self._prefill_paged_fn(bucket, mb, rows)
                if mb == 0:
                    logits, tail_caches, *held = fn(
                        self.params, jnp.asarray(padded), last
                    )
                else:
                    # Extend phase: each page-table row is padded to the
                    # power-of-two page bucket with null pages (the kernel
                    # masks them via the dynamic prefix_len), so every
                    # prefix length in a bucket shares one compilation —
                    # and the pool is consumed in place, no gather.
                    pt = np.full((rows, mb), NULL_PAGE, np.int32)
                    for i, r in enumerate(grp):
                        pt[i, : len(r["matched"])] = r["matched"]
                    plens = jnp.asarray(
                        [len(r["matched"]) * ps for r in grp], jnp.int32
                    )
                    self.stats["extend_prefills"] += rows
                    logits, tail_caches, *held = fn(
                        self.params, jnp.asarray(padded), last, self.caches,
                        jnp.asarray(pt), plens,
                    )
                # Scatter every row's tail K/V into its fresh pages
                # (buckets are page-aligned; destinations beyond a tail's
                # real pages sink into the null page).
                pids = np.full((rows, bucket // ps), NULL_PAGE, np.int32)
                for i, r in enumerate(grp):
                    tail_pages = r["seq"].pages[len(r["matched"]):]
                    pids[i, : len(tail_pages)] = tail_pages
                self.caches = self._scatter_jit(
                    self.caches, tail_caches, jnp.asarray(pids)
                )
                logits_np = np.asarray(logits)
                if held:
                    # Read once the logits' transfer has waited for this
                    # launch, so the count adds no wait of its own.
                    self.flush_held_slots.append(int(np.asarray(held[0])))
                for i, r in enumerate(grp):
                    # Publish this prompt's full pages for later requests.
                    if self.prefix_sharing:
                        nfull = r["n"] // ps
                        if self.host is not None:
                            # A freshly prefilled page supersedes any
                            # host copy under the same hash (the content
                            # is hash-determined): drop it so residency
                            # stays exclusive — device OR host, never both.
                            for h in r["hashes"][:nfull]:
                                self.host.discard(h)
                        self.prefix.insert(
                            r["hashes"][:nfull], r["seq"].pages[:nfull]
                        )
                    first_logits[r["row"]] = logits_np[i]

    def _prefill_span(self, bucket: int, mb: int, grp):
        """``prefill`` span of one launch: its jit key and each row's
        tokens (computed only when the tracer records)."""
        if not self.tracer.enabled:
            return NULL_SPAN
        ps = self.page_size
        return self.tracer.span(
            "prefill", bucket=bucket, prefix_pages=mb, rows=len(grp),
            tail_tokens=[len(r["tail"]) for r in grp],
            prefix_tokens=[len(r["matched"]) * ps for r in grp],
            uids=[r["req"].uid for r in grp],
        )

    # -- preemption / decode ----------------------------------------------

    def _preempt_one(self, protect: int) -> bool:
        """Evict one active sequence — which one is the injected
        ``choose_victim`` policy's call (lowest priority, newest by
        default) — and report it through ``on_preempt`` so the scheduler
        requeues it with its generated-so-far tokens (replayed through the
        extend path on re-admission); never the row ``protect``."""
        candidates = [
            (s.req.priority, s.submit_order, row)
            for row, s in enumerate(self.seqs)
            if s is not None and self.active[row] and row != protect
        ]
        row = self.choose_victim(candidates, protect) if candidates else None
        if row is None:
            return False
        state = self.seqs[row]
        self.stats["preemptions"] += 1
        self.pool.release(state.pages)
        generated = list(self.out[row])
        self.active[row] = False
        self.seqs[row] = None
        self.page_table[row] = NULL_PAGE
        self.lengths[row] = 0
        self.out[row] = []
        self.on_preempt(row, state.req, generated)
        return True

    def reserve_rows(self, n_steps: int) -> None:
        """Reserve every active row's next ``min(n_steps, remaining)``
        token slots before a fused scan launches, preempting other rows
        under pool pressure (same retry policy as :meth:`prepare_row`,
        amortized over the whole sync). COW copies surface here — the
        scan itself never touches a shared page. Over-reserved slots
        (early stop) return to the pool in :meth:`commit_scan`."""
        for row in range(self.rows):
            if not self.active[row]:
                continue
            state = self.seqs[row]
            # Remaining output budget bounds the reservation: a scan never
            # writes past max_tokens, so never past validate()'s cap.
            remaining = state.req.max_new_tokens - len(self.out[row])
            target = state.pages.length + min(n_steps, max(remaining, 1))
            cows: List[Tuple[int, int]] = []
            while state.pages.length < target:
                try:
                    self.pool.reserve_tokens(
                        state.pages, target - state.pages.length, cows
                    )
                except OutOfPages:
                    # Partial progress is kept (seq.length advanced, COWs
                    # in ``cows``); free room and re-request the rest.
                    if not (self._make_room(1) or self._preempt_one(row)):
                        raise OutOfPages(
                            "pool exhausted and nothing left to preempt"
                        )
            for src, dst in cows:
                self.stats["cow_copies"] += 1
                self.caches = self._copy_jit(
                    self.caches, jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32),
                )
            self.page_table[row] = NULL_PAGE
            self.page_table[row, : len(state.pages.pages)] = state.pages.pages

    def commit_scan(self, new_lengths: np.ndarray) -> None:
        """Trim each live row's reservation down to what the scan actually
        consumed (its final length) and rebuild the page tables; unused
        reserved pages go straight back on the free list."""
        for row in range(self.rows):
            state = self.seqs[row]
            if state is None or not self.active[row]:
                continue
            want = int(new_lengths[row])
            if want < state.pages.length:
                self.pool.trim_tokens(state.pages, want)
            self.page_table[row] = NULL_PAGE
            self.page_table[row, : len(state.pages.pages)] = state.pages.pages
        self.lengths = np.array(new_lengths, dtype=self.lengths.dtype)

    @property
    def sync_reserve_pages(self) -> int:
        """Decode headroom the scheduler must price at admission: with N
        fused steps per sync, every live row (plus the candidate) can
        grow ceil(N / page_size) pages before the host next intervenes —
        a scan must never run out of pages mid-flight."""
        n = self.steps_per_sync
        if n <= 1:
            return self.reserve_pages
        per_row = -(-n // self.page_size)
        return self.reserve_pages + per_row * (self.num_active + 1)

    def prepare_row(self, row: int) -> None:
        """Reserve the next token's slot in row's page table, preempting
        others if the pool is exhausted mid-decode."""
        state = self.seqs[row]
        while True:
            try:
                _, _, cow = self.pool.append_token(state.pages)
                break
            except OutOfPages:
                if not (self._make_room(1) or self._preempt_one(row)):
                    raise OutOfPages(
                        "pool exhausted and nothing left to preempt"
                    )
        if cow is not None:
            src, dst = cow
            self.stats["cow_copies"] += 1
            # Traced page ids: one jitted copy program serves every pair.
            self.caches = self._copy_jit(
                self.caches, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32),
            )
        if state.pages.num_pages() > self.max_pages_per_seq:
            raise ValueError(
                f"sequence {state.req.uid} outgrew max_pages_per_seq="
                f"{self.max_pages_per_seq}; cap prompt+max_new_tokens at "
                f"{self.cache_len} tokens"
            )
        self.page_table[row] = NULL_PAGE
        self.page_table[row, : len(state.pages.pages)] = state.pages.pages

    def decode(self, tok: np.ndarray):
        self.lengths = self.lengths + self.active.astype(np.int32)
        logits, self.caches = self._decode(
            self.params, jnp.asarray(tok), self.caches,
            jnp.asarray(self.lengths), jnp.asarray(self.page_table),
        )
        return logits

    def row_req(self, row: int):
        return self.seqs[row].req

    def release(self, row: int) -> None:
        state = self.seqs[row]
        if state is None:
            # Double release used to AttributeError (or, worse, silently
            # pass once pool.release no-op'd); surface it as the typed
            # pool error so the sanitizer and callers see one family.
            raise SequenceReleasedError(
                f"release of row {row}, which holds no sequence"
            )
        # Pages the prefix cache references survive; the rest free now.
        self.pool.release(state.pages)
        self.active[row] = False
        self.seqs[row] = None
        self.page_table[row] = NULL_PAGE
        self.lengths[row] = 0

    # -- teardown / invariants ---------------------------------------------

    def live_page_refs(self) -> Dict[int, int]:
        """Pool references this backend can account for: one per live
        sequence page-table entry plus one per prefix-cache entry. The
        pool's refcounts must equal exactly this at any quiescent point."""
        refs: Dict[int, int] = {}
        for state in self.seqs:
            if state is None:
                continue
            for pid in state.pages.pages:
                refs[pid] = refs.get(pid, 0) + 1
        for pid in self.prefix.pages():
            refs[pid] = refs.get(pid, 0) + 1
        return refs

    def check_leaks(self, raise_on_leak: bool = True):
        """Audit the pool against :meth:`live_page_refs`; raises
        :class:`repro.cache.pool.RefcountLeakError` on any page whose
        refcount the live rows + prefix cache cannot explain."""
        return self.pool.check_leaks(
            self.live_page_refs(), raise_on_leak=raise_on_leak
        )

    def shutdown(self) -> None:
        """Teardown: release every live row, drain the prefix cache, then
        prove the pool is fully free. A leak here means some path dropped
        a SequencePages without releasing it."""
        for row in range(self.rows):
            if self.seqs[row] is not None:
                self.release(row)
        self.prefix.drain()
        if self.host is not None:
            self.host.drain()
        self._pending_pages.clear()
        self.pool.check_leaks()

    # -- introspection -----------------------------------------------------

    @property
    def mapping(self):
        """Resolved decode-shape schedule (decode & window are part of the
        plan key, so this differs from the prefill resolution)."""
        return self.decode_plan().mapping

    def decode_plan(self) -> plan_lib.AttentionPlan:
        """The resolved steady-state decode plan, scored jointly over
        (domain, device) when this backend runs on a mesh — exposes
        ``num_splits`` / ``pages_per_block`` / ``split_device_pure`` for
        stats and tests."""
        return plan_lib.plan_for_config(
            self.cfg,
            (self.rows, self.cfg.n_heads, self.cfg.n_kv_heads,
             1, self.cache_len, self.cfg.head_dim),
            phase=plan_lib.DECODE, kv_layout=plan_lib.PAGED,
            page_size=self.page_size, num_devices=self.num_devices,
            kv_dtype=self.kv_dtype, latent=self.cfg.mla is not None,
        )

    def modeled_kv_layout(self) -> str:
        """What the analytic model would pick for this backend's steady
        state (paged head-aligned vs interleaved vs dense stripes)."""
        live = self.lengths[self.active]
        mean_len = int(live.mean()) if live.size else self.cache_len // 2
        return plan_lib.resolve_kv_layout(
            (self.rows, self.cfg.n_heads, self.cfg.n_kv_heads,
             max(mean_len, 1), self.cfg.head_dim),
            capacity=self.cache_len,
            page_size=self.page_size,
            dtype_bytes=self._kv_dtype_bytes,
        )

    def prefix_stats(self) -> Dict[str, object]:
        reused = self.stats["pages_reused"]
        total = self.stats["prompt_pages"]
        pc = self.prefix.counters()
        return {
            "prefix_entries": float(len(self.prefix)),
            "pages_reused": float(reused),
            "prompt_pages": float(total),
            "prefix_hit_rate": reused / total if total else 0.0,
            "prefix_lookup_hits": float(pc["hits"]),
            "prefix_lookup_queries": float(pc["queries"]),
            "prefix_evictions": float(pc["evictions"]),
            "preemptions": float(self.stats["preemptions"]),
            "resumed_tokens": float(self.stats["resumed_tokens"]),
            "extend_prefills": float(self.stats["extend_prefills"]),
            "prefill_launches": float(self.stats["prefill_launches"]),
            "batched_prefills": float(self.stats["batched_prefills"]),
            "cow_copies": float(self.stats["cow_copies"]),
            "free_pages": float(self.pool.free_pages),
            "inflight_pages_reused": float(
                self.stats["inflight_pages_reused"]
            ),
            "kv_dtype": self.kv_dtype,
            "kv_pool_bytes": float(self.kv_pool_bytes()),
            "demoted_pages": float(self.stats["demoted_pages"]),
            "promoted_pages": float(self.stats["promoted_pages"]),
            "host_entries": (
                float(len(self.host)) if self.host is not None else 0.0
            ),
            "host_bytes_resident": (
                float(self.host.bytes_resident)
                if self.host is not None else 0.0
            ),
        }
