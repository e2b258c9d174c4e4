"""The attention-plan layer: one resolver for every attention phase.

After PR 2 the NUMA-aware *schedule* — the thing the paper says decides
attention performance — was resolved in four different places: the ops
dispatch (``resolve_mapping`` / ``resolve_kv_layout`` plus three entry
points), the model attention layer (``cfg.mapping_name`` lookups), the
transformer prefill (``q_offset`` threading) and the serving engines
(pinned-mapping validation, gather-then-dense prefix prefill). This module
collapses all of that into a single value:

  ``AttentionPlan`` — phase (prefill | extend | decode), KV layout (dense |
  paged), the resolved ``MappingConfig``, the concrete kernel impl, the
  decode KV chunk, the split-K ``num_splits`` (PR 4: chosen by
  ``perf_model.estimate_decode_splits``' occupancy model), the paged
  decode kernel's ``pages_per_block`` (:func:`decode_pages_per_block`),
  the NUMA placement policy, and the backend/interpret environment it was
  resolved for. The paged-extend impl is likewise a scored choice
  (``perf_model.estimate_extend_prefill``): the prefix-aware kernel vs
  the gather route, per shape.

produced by one resolver:

  ``plan_attention(shape, ...)`` — scores (grid order x KV residency x
  block size) candidates with the analytic NUMA model (``core.perf_model``)
  plus the exact HBM-traffic model (``hbm_block_fetches``), picks the
  kernel implementation for the phase/backend, and LRU-caches the result.
  The cache key includes the **backend and the interpret flag** (the PR-1
  resolver silently shared entries across backends when tests flipped
  ``JAX_PLATFORMS``), so a plan resolved for a CPU dry-run can never leak
  into a TPU trace.

Call sites execute plans instead of hand-threading ``mapping_name`` /
``q_offset`` / chunk arguments through four layers:

  * ``kernels.ops`` builds a plan when none is passed and dispatches on
    ``plan.impl`` / ``plan.mapping`` / ``plan.chunk``;
  * ``models.attention`` / ``models.transformer`` resolve via
    :func:`plan_for_config` (which is where ``cfg.mapping_name`` /
    ``cfg.attn_impl`` policy is read — nowhere else);
  * ``serving.engine`` builds one **extend** plan per (tail-bucket,
    prefix-page-bucket) jit key and hands it to ``transformer.prefill``.

The legacy entry points ``ops.resolve_mapping`` / ``ops.resolve_kv_layout``
survive as thin wrappers over this module (see ops.py).

Phases
------
  * ``PREFILL`` — full-sequence attention, causal, dense K/V.
  * ``EXTEND``  — prefix-extension prefill: the query block sits after an
    already-cached prefix. With ``kv_layout=PAGED`` this resolves to the
    paged prefix-aware Pallas prefill kernel
    (``kernels.paged_prefill_attention``) which reads prefix K/V straight
    from the page table; the dense variant is the legacy XLA
    ``q_offset`` route, kept as the oracle/fallback.
  * ``DECODE``  — one query token against a cache (dense stripe or pages).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Iterator, List, Optional, Tuple

from repro import compat
from repro.kernels.flash_attention import (
    BLOCK_FIRST,
    HEAD_FIRST,
    PAPER_MAPPINGS,
    MappingConfig,
    hbm_block_fetches,
)

# Phases
PREFILL = "prefill"
EXTEND = "extend"
DECODE = "decode"
PHASES = (PREFILL, EXTEND, DECODE)

# KV layouts
DENSE = "dense"
PAGED = "paged"


# -----------------------------------------------------------------------------
# The plan
# -----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    """One resolved attention schedule: everything a call site needs to
    execute attention for one (phase, layout, shape, backend) cell.

    Frozen + hashable so it can ride jit closures and custom_vjp nondiff
    arguments, and so equal plans are interchangeable cache entries.
    """

    phase: str                     # PREFILL | EXTEND | DECODE
    kv_layout: str                 # DENSE | PAGED
    impl: str                      # concrete: "pallas"|"xla_flash"|"xla_flash_tri"|"xla"|"ref"
    mapping: MappingConfig         # grid order / residency / blocks
    backend: str                   # backend the plan was resolved for
    interpret: bool                # Pallas interpret mode on this backend
    chunk: Optional[int] = None    # decode KV chunk (dense flash-decode)
    page_size: Optional[int] = None     # paged layouts
    prefix_pages: int = 0          # EXTEND: page-table width (bucketed)
    window: Optional[int] = None   # sliding window the plan was scored for
    placement: Optional[str] = None     # paged: head_aligned | interleaved
    num_splits: int = 1            # DECODE: split-K ranges (occupancy model)
    num_devices: int = 1           # mesh width the plan was scored for
    #: Paged pools' storage format (``cache.quant``): "fp32" | "int8" |
    #: "fp8". Quantized plans expect per-page scales next to the page
    #: table at call time; dense layouts are always fp32.
    kv_dtype: str = "fp32"
    #: DECODE on a mesh: True when the joint (domain, device) model kept
    #: split-K ranges device-pure (head-sharded pool, every range local to
    #: its owner's HBM); False when striping the pool across devices won
    #: (fast fabric + too few KV heads to feed every device). None off-mesh.
    split_device_pure: Optional[bool] = None
    #: DECODE/PAGED: pages the paged decode kernel loads per compute block
    #: (:func:`decode_pages_per_block`). 1 elsewhere.
    pages_per_block: int = 1

    @property
    def prefix_capacity(self) -> int:
        """Max prefix tokens this (extend) plan can attend: the page-table
        width times the page size. The *live* prefix length is dynamic
        (``prefix_len`` arrays at call time) and may be smaller — the jit
        key buckets pages to powers of two to bound compilations."""
        return self.prefix_pages * (self.page_size or 0)


# -----------------------------------------------------------------------------
# Mapping scoring (moved verbatim from the PR-1 ops.resolve_mapping body)
# -----------------------------------------------------------------------------

#: Candidate (block_m, block_n) tilings, preference-ordered. The MXU-native
#: 128x128 default first; larger variants only win when the model says so
#: (e.g. less padding waste). Sub-128 blocks are excluded — the analytic
#: model would pick them for their smaller causal-diagonal waste, but they
#: under-fill the 128x128 MXU; short sequences still clamp via min(bm, sq).
_CANDIDATE_BLOCKS = ((128, 128), (256, 128), (128, 256))

#: Grid order -> paper mapping name for the analytic model. Every emitted
#: candidate has acc_parallel=True, so both orders score as their swizzled
#: variant (the naive_* names carry perf_model's ACC-replication penalty for
#: schedules we never emit); residency is decided by the candidate filter
#: plus the exact HBM-traffic tie-break, not by the analytic proxy.
_PAPER_NAME = {
    HEAD_FIRST: "swizzled_head_first",
    BLOCK_FIRST: "swizzled_block_first",
}


def tpu_device_kind() -> str:
    """``device_kind`` of the attached TPU (raises when none is attached)."""
    import jax

    return jax.devices("tpu")[0].device_kind


def topology_for(backend: str):
    """The topology plans for ``backend`` are priced against: the attached
    chip's for TPU (keyed on its ``device_kind``; an unknown kind raises),
    the named CPU rehearsal topology for CPU hosts."""
    from repro.core import numa

    if backend == "gpu":
        return numa.MI300X
    if backend == "tpu":
        kind = tpu_device_kind()
        if kind not in numa.TPU_BY_DEVICE_KIND:
            raise ValueError(
                f"no topology for TPU device kind {kind!r}; add it to "
                "core.numa.TPU_BY_DEVICE_KIND"
            )
        return numa.TPU_BY_DEVICE_KIND[kind]
    return numa.CPU_REHEARSAL


def vmem_limits(backend: str) -> Tuple[int, int]:
    """``(vmem_budget_bytes, vmem_limit_bytes)`` for the flash kernels. On
    a TPU they come from its VMEM: the compiler limit is three quarters of
    the core's VMEM, and resident K/V (both pipeline buffers) may take half
    of it. Other backends run the kernels interpreted (or only model them)
    and take the ``MappingConfig`` defaults, the v5e figures."""
    if backend != "tpu":
        return MappingConfig.vmem_budget_bytes, MappingConfig.vmem_limit_bytes
    limit = topology_for(backend).cache_bytes * 3 // 4
    return limit // 2, limit


@functools.lru_cache(maxsize=1024)
def _score_mapping(
    batch: int,
    num_q_heads: int,
    num_kv_heads: int,
    seq_q: int,
    seq_kv: int,
    head_dim: int,
    dtype_bytes: int,
    backend: str,
    vmem_budget_bytes: int,
    vmem_limit_bytes: int,
    decode: bool,
    window: Optional[int],
) -> MappingConfig:
    from repro.core import perf_model
    from repro.core import swizzle
    from repro.core.cache_sim import AttentionWorkload
    from repro.core.swizzle import AttentionGrid

    topo = topology_for(backend)
    group = max(1, num_q_heads // max(num_kv_heads, 1))
    # A sliding window bounds the KV each row actually touches: score (and
    # choose blocks for) the live span, rounded up to a whole tile, not the
    # full cache. Decode shapes attend every prior position, so they score
    # non-causal — a causal model would halve their tile count and pick
    # systematically undersized blocks.
    causal = not decode
    if window is not None and window > 0:
        seq_kv = min(seq_kv, -(-(window + (0 if decode else seq_q)) // 128) * 128)

    def _clamp(block, seq):
        # Never emit a block shorter than the sequence rounded up to the
        # sublane quantum (16 covers bf16's 16 and f32's 8): ops pads the
        # sequence to the block size, and a non-multiple-of-sublane block
        # only works in interpret mode — Mosaic rejects the layout.
        return min(block, max(16, -(-seq // 16) * 16))

    best = None  # (time, traffic, candidate_rank, config)
    rank = 0
    for bm, bn in _CANDIDATE_BLOCKS:
        bm_eff = _clamp(bm, seq_q)
        bn_eff = _clamp(bn, seq_kv)
        for order in (HEAD_FIRST, BLOCK_FIRST):
            for kv_resident in (True, False):
                # Sawtooth wavefront (ROADMAP 5(a)) is a streaming-only
                # refinement: serpentine KV sweeps share boundary tiles, so
                # it enters the candidate space wherever a sweep exists
                # (head_first streaming). Listed after linear so it wins
                # only on the exact-traffic tie-break, never on rank.
                traversals = (swizzle.LINEAR,)
                if not kv_resident and order == HEAD_FIRST:
                    traversals = (swizzle.LINEAR, swizzle.SAWTOOTH)
                for traversal in traversals:
                    cand = MappingConfig(
                        order=order,
                        kv_resident=kv_resident,
                        acc_parallel=True,
                        block_m=bm_eff,
                        block_n=bn_eff,
                        vmem_budget_bytes=vmem_budget_bytes,
                        vmem_limit_bytes=vmem_limit_bytes,
                        traversal=traversal,
                    )
                    if kv_resident and not cand.resolve_resident(
                        seq_kv, head_dim, dtype_bytes
                    ):
                        # Over-budget residency degenerates to streaming;
                        # keep only the honest streaming candidate.
                        continue
                    # perf_model.estimate models a square (seq_kv x seq_kv)
                    # launch: it recomputes blocks_per_head from
                    # wl.seq_len, so feed it the same convention. For
                    # rectangular shapes (bucketed prefill vs long cache)
                    # the analytic time is a square proxy; the exact
                    # rectangular traffic enters via the tie-break below.
                    grid = AttentionGrid(
                        batch=batch,
                        num_q_heads=num_q_heads,
                        blocks_per_head=-(-seq_kv // bm_eff),
                        group_size=group,
                    )
                    wl = AttentionWorkload(
                        grid=grid,
                        seq_len=seq_kv,
                        head_dim=head_dim,
                        block_m=bm_eff,
                        block_n=bn_eff,
                        causal=causal,
                        dtype_bytes=dtype_bytes,
                    )
                    est = perf_model.estimate(_PAPER_NAME[order], wl, topo)
                    traffic = hbm_block_fetches(
                        batch=batch,
                        num_q_heads=num_q_heads,
                        num_kv_heads=num_kv_heads,
                        seq_q=seq_q,
                        seq_kv=seq_kv,
                        head_dim=head_dim,
                        dtype_bytes=dtype_bytes,
                        mapping=cand,
                    )["total_bytes"]
                    key = (est.time, traffic, rank)
                    rank += 1
                    if best is None or key < best[0]:
                        best = (key, cand)
    return best[1]


# -----------------------------------------------------------------------------
# KV-layout scoring (moved from the PR-2 ops.resolve_kv_layout body)
# -----------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _score_kv_layout(
    batch: int,
    num_q_heads: int,
    num_kv_heads: int,
    mean_len: int,
    capacity: int,
    page_size: int,
    head_dim: int,
    dtype_bytes: int,
    backend: str,
    shared_prefix_len: int,
) -> Tuple[str, float, float]:
    from repro.core import perf_model

    topo = topology_for(backend)
    dense = perf_model.estimate_dense_decode(
        batch=batch, num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
        capacity=capacity, head_dim=head_dim, dtype_bytes=dtype_bytes,
        topo=topo,
    )
    candidates = {"dense": dense.time}
    for policy in ("head_aligned", "interleaved"):
        est = perf_model.estimate_paged_decode(
            batch=batch, num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
            mean_len=mean_len, page_size=page_size, head_dim=head_dim,
            dtype_bytes=dtype_bytes, topo=topo, policy=policy,
            shared_prefix_len=shared_prefix_len,
        )
        candidates[f"paged:{policy}"] = est.time
    best = min(candidates, key=candidates.get)
    return best, candidates[best], candidates["dense"]


def resolve_kv_layout(
    shape: Tuple[int, int, int, int, int],
    *,
    capacity: int,
    page_size: int = 64,
    dtype_bytes: int = 2,
    backend: Optional[str] = None,
    shared_prefix_len: int = 0,
) -> str:
    """Rank KV layouts for a decode mix; returns ``"dense"``,
    ``"paged:head_aligned"`` or ``"paged:interleaved"``.

    ``shape`` is ``(batch, num_q_heads, num_kv_heads, mean_len, head_dim)``
    — the decode batch and its mean live sequence length; ``capacity`` is
    the dense per-slot stripe the paged layout would replace. Scored with
    ``core.perf_model``'s paged/dense decode estimates (page-granular
    traffic, once-per-domain shared-prefix reuse, link-cost for remote
    pages) — the decode analogue of the mapping scoring above."""
    b, hq, hkv, mean_len, head_dim = (int(x) for x in shape)
    best, _, _ = _score_kv_layout(
        b, hq, hkv, mean_len, int(capacity), int(page_size),
        head_dim, int(dtype_bytes),
        backend or compat.default_backend(),
        int(shared_prefix_len),
    )
    return best


# -----------------------------------------------------------------------------
# Impl + chunk resolution
# -----------------------------------------------------------------------------

_DENSE_PREFILL_IMPLS = ("pallas", "xla_flash", "xla_flash_tri", "ref")


@functools.lru_cache(maxsize=512)
def _score_extend_route(
    batch: int,
    num_q_heads: int,
    num_kv_heads: int,
    tail_len: int,
    prefix_len: int,
    page_size: int,
    head_dim: int,
    dtype_bytes: int,
    backend: str,
) -> str:
    """Paged-vs-gather extend route for one shape: "pallas" (the paged
    prefix-aware kernel) or "xla" (gather the prefix to dense, run the
    dense flash oracle). Scored with
    ``perf_model.estimate_extend_prefill`` under both models — the paged
    kernel reads each prefix page once but its grid is only B x Hkv wide;
    the gather route triples the prefix traffic (read + write-back + dense
    re-read, fabric cost included) to regain full occupancy. Ties keep
    the kernel (no gather is the better default at equal cost)."""
    from repro.core import perf_model

    topo = topology_for(backend)
    kw = dict(
        batch=batch, num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
        prefix_len=prefix_len, tail_len=tail_len, page_size=page_size,
        head_dim=head_dim, dtype_bytes=dtype_bytes, topo=topo,
    )
    paged = perf_model.estimate_extend_prefill(gather=False, **kw)
    gather = perf_model.estimate_extend_prefill(gather=True, **kw)
    return "pallas" if paged.time <= gather.time else "xla"


def _resolve_impl(phase: str, kv_layout: str, impl: str, backend: str) -> str:
    """Concrete kernel implementation for a phase/layout on a backend.

    ``impl`` is the caller's policy (``cfg.attn_impl``), usually "auto".
    Decode phases coerce the prefill-only xla_flash* impls to the dense
    "xla" oracle (this coercion previously lived in models/attention.py).
    """
    if phase == DECODE:
        if impl in ("auto",):
            return "pallas" if backend == "tpu" else "xla"
        if impl in ("xla_flash", "xla_flash_tri"):
            return "xla"
        if impl in ("pallas", "xla", "ref"):
            return impl
        raise ValueError(f"unknown decode impl {impl!r}")
    if phase == EXTEND and kv_layout == PAGED:
        # The headline kernel: paged prefix-aware Pallas prefill — the only
        # non-gather route, so "auto" resolves to it on every backend (CPU
        # hosts run it in interpret mode). An explicitly pinned compiled
        # CPU impl (xla_flash*) coerces to the compiled gather oracle
        # instead, mirroring the decode-phase coercion — never silently to
        # the interpreter.
        if impl in ("auto", "pallas"):
            return "pallas"
        if impl in ("xla", "ref", "xla_flash", "xla_flash_tri"):
            return "xla"
        raise ValueError(f"unknown paged-extend impl {impl!r}")
    if phase == EXTEND:
        # Dense extend: the legacy q-offset route. The Pallas forward does
        # not carry the offset, so "pallas"/"auto" fall back to xla_flash —
        # this is the oracle path the paged kernel is tested against.
        if impl in ("auto", "pallas"):
            return "xla_flash"
        if impl in _DENSE_PREFILL_IMPLS:
            return impl
        raise ValueError(f"unknown dense-extend impl {impl!r}")
    # PREFILL
    if impl == "auto":
        return "pallas" if backend == "tpu" else "xla_flash"
    if impl in _DENSE_PREFILL_IMPLS:
        return impl
    raise ValueError(f"unknown prefill impl {impl!r}")


def _decode_chunk(mapping: MappingConfig, smax: int) -> int:
    """KV chunk for the dense flash-decode kernel: the resolver's block_n,
    preferring a divisor of the cache capacity (largest sublane-multiple
    divisor <= block_n) so the serving hot loop never pays a pad copy.
    Only truly odd capacities keep the non-dividing chunk (ops pads)."""
    chunk = min(mapping.block_n, smax)
    if smax % chunk:
        divisor = next(
            (c for c in range(chunk, 7, -1) if smax % c == 0 and c % 8 == 0),
            None,
        )
        if divisor is not None:
            chunk = divisor
    return chunk


#: Target compute block of the paged decode kernel, in tokens. Chosen by a
#: sweep of 128-1024 on a TPU v5e at the benchmark's decode shapes
#: (PERF.md): larger blocks ran faster up to the largest.
PAGED_DECODE_BLOCK_TOKENS = 1024


def decode_pages_per_block(
    max_pages: int,
    page_size: int,
    head_dim: int,
    dtype_bytes: int,
    *,
    num_splits: int = 1,
    vmem_budget_bytes: Optional[int] = None,
) -> int:
    """Pages per compute block of the paged decode kernel, from the shape
    alone: :data:`PAGED_DECODE_BLOCK_TOKENS` worth of pages, clamped to the
    table (one block covers it whole) and to ``ceil(max_pages /
    num_splits)`` so every split-K range keeps a block of its own, then
    halved until the block's VMEM fits ``vmem_budget_bytes`` (the
    backend's resident-K/V budget when None). The block need not divide
    ``max_pages``: the tail block's slots past the table are never
    copied."""
    if vmem_budget_bytes is None:
        vmem_budget_bytes = vmem_limits(compat.default_backend())[0]
    ppb = max(1, PAGED_DECODE_BLOCK_TOKENS // page_size)
    ppb = min(ppb, max_pages, -(-max_pages // max(1, num_splits)))
    ppb = max(1, ppb)
    while ppb > 1 and paged_decode_vmem_bytes(
        ppb * page_size, head_dim, dtype_bytes
    ) > vmem_budget_bytes:
        ppb //= 2
    return ppb


def paged_decode_vmem_bytes(block_tokens: int, head_dim: int,
                            dtype_bytes: int) -> int:
    """VMEM the paged decode kernel holds for one block size: K and V
    double buffers in the pool's dtype, plus their fp32 widenings."""
    return block_tokens * head_dim * (2 * 2 * dtype_bytes + 2 * 4)


# -----------------------------------------------------------------------------
# The resolver
# -----------------------------------------------------------------------------


@functools.lru_cache(maxsize=2048)
def _plan_cached(
    batch: int,
    num_q_heads: int,
    num_kv_heads: int,
    seq_q: int,
    seq_kv: int,
    head_dim: int,
    phase: str,
    kv_layout: str,
    backend: str,
    interpret: bool,
    dtype_bytes: int,
    window: Optional[int],
    page_size: Optional[int],
    prefix_pages: int,
    mapping_name: str,
    impl: str,
    vmem_budget_bytes: int,
    vmem_limit_bytes: int,
    num_devices: int,
    device_link_bw: Optional[float],
    kv_dtype: str,
) -> AttentionPlan:
    if mapping_name != "auto":
        mapping = PAPER_MAPPINGS[mapping_name]  # KeyError = fail fast
    elif phase == EXTEND and kv_layout == PAGED:
        # The paged prefill kernel takes no MappingConfig (its schedule is
        # the fixed head-first page walk); skip the candidate sweep and
        # carry the default paper schedule for introspection only.
        mapping = MappingConfig()
    else:
        mapping = _score_mapping(
            batch, num_q_heads, num_kv_heads, seq_q, seq_kv, head_dim,
            dtype_bytes, backend, vmem_budget_bytes, vmem_limit_bytes,
            phase == DECODE, window,
        )

    chunk = None
    if phase == DECODE and kv_layout == DENSE:
        chunk = _decode_chunk(mapping, seq_kv)

    placement = None
    if kv_layout == PAGED:
        # Head-major pools are head-aligned by construction (cache.layout);
        # the plan records the placement the kernels assume.
        placement = "head_aligned"

    resolved_impl = _resolve_impl(phase, kv_layout, impl, backend)
    if phase == EXTEND and kv_layout == PAGED and impl == "auto" \
            and prefix_pages > 0:
        # Route choice (PR-4 satellite): the paged kernel reads the prefix
        # once but exposes only B x Hkv parallel cells; the gather route
        # pays ~3x the prefix bytes to recover the dense flash grid's
        # occupancy. perf_model charges both (occupancy factors included)
        # and the cheaper route wins — an explicitly pinned impl skips
        # this and goes through _resolve_impl's coercions above.
        resolved_impl = _score_extend_route(
            batch, num_q_heads, num_kv_heads, seq_q,
            prefix_pages * (page_size or 0), page_size, head_dim,
            dtype_bytes, backend,
        )

    num_splits = 1
    split_device_pure = None
    if phase == DECODE:
        # Split-K (PR 4): sequence-parallel decode, chosen by occupancy —
        # cells x splits vs the domain count, combine overhead charged
        # explicitly. The granule is what the kernel can actually split
        # at: KV chunks for the dense stripe, pages for the pool.
        # On a mesh (PR 9) the same sweep scores placement jointly over
        # (domain, device): device-pure ranges ride local HBM, straddled
        # ones pay the inter-device tier for crossing bytes.
        from repro.core import numa, perf_model

        granule = chunk if kv_layout == DENSE else page_size
        if granule:
            mesh = None
            if num_devices > 1:
                mesh = numa.mesh_topology(
                    num_devices, chip=topology_for(backend),
                    device_link_bw=device_link_bw,
                )
            split = perf_model.estimate_decode_splits(
                batch=batch, num_q_heads=num_q_heads,
                num_kv_heads=num_kv_heads, seq_kv=seq_kv, granule=granule,
                head_dim=head_dim, dtype_bytes=dtype_bytes,
                topo=topology_for(backend), window=window, mesh=mesh,
            )
            num_splits = split.num_splits
            split_device_pure = split.device_pure

    pages_per_block = 1
    if phase == DECODE and kv_layout == PAGED:
        pages_per_block = decode_pages_per_block(
            -(-seq_kv // page_size), page_size, head_dim, dtype_bytes,
            num_splits=num_splits, vmem_budget_bytes=vmem_budget_bytes,
        )

    return AttentionPlan(
        phase=phase,
        kv_layout=kv_layout,
        impl=resolved_impl,
        mapping=mapping,
        backend=backend,
        interpret=interpret,
        chunk=chunk,
        page_size=page_size,
        prefix_pages=prefix_pages,
        window=window,
        placement=placement,
        num_splits=num_splits,
        num_devices=num_devices,
        split_device_pure=split_device_pure,
        kv_dtype=kv_dtype,
        pages_per_block=pages_per_block,
    )


def plan_attention(
    shape: Tuple[int, int, int, int, int, int],
    *,
    phase: str = PREFILL,
    kv_layout: str = DENSE,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    dtype_bytes: int = 2,
    window: Optional[int] = None,
    page_size: Optional[int] = None,
    prefix_pages: int = 0,
    mapping_name: str = "auto",
    impl: str = "auto",
    vmem_budget_bytes: Optional[int] = None,
    num_devices: int = 1,
    device_link_bw: Optional[float] = None,
    kv_dtype: str = "fp32",
) -> AttentionPlan:
    """Resolve the best :class:`AttentionPlan` for an attention shape.

    ``shape`` is ``(batch, num_q_heads, num_kv_heads, seq_q, seq_kv,
    head_dim)``. Conventions per phase:

      * PREFILL: ``seq_q`` = ``seq_kv`` = the prompt length;
      * EXTEND:  ``seq_q`` = the tail length, ``seq_kv`` = prefix + tail
        (pass ``prefix_pages`` / ``page_size`` for the paged layout —
        ``prefix_pages`` is the *bucketed* page-table width, part of the
        plan so equal jit keys share one plan);
      * DECODE:  ``seq_q`` = 1, ``seq_kv`` = the cache capacity.

    ``backend`` defaults to the host's jit target and ``interpret`` to
    ``compat.use_interpret(backend)`` — both are part of the cache key, so
    flipping ``JAX_PLATFORMS`` between calls can never reuse a stale plan.
    ``mapping_name`` / ``impl`` carry the config policy ("auto" or a pinned
    ``PAPER_MAPPINGS`` name / kernel impl); this is the only layer that
    interprets them.

    ``num_devices`` > 1 scores DECODE split-K placement jointly over
    (domain, device) via ``numa.mesh_topology`` — ``device_link_bw``
    overrides the inter-device fabric figure (``None`` = the chip preset's
    link) — and records the verdict in ``plan.split_device_pure``.

    ``vmem_budget_bytes=None`` takes the resident-K/V budget from the
    chip's VMEM (:func:`vmem_limits`); the compiler limit always does.
    """
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r}; expected one of {PHASES}")
    if kv_layout not in (DENSE, PAGED):
        raise ValueError(f"unknown kv layout {kv_layout!r}")
    if kv_layout == PAGED and page_size is None:
        raise ValueError("paged plans require page_size")
    from repro.cache import quant as quant_lib

    quant_lib.validate_kv_dtype(kv_dtype)
    if kv_dtype != "fp32" and kv_layout != PAGED:
        raise ValueError(
            f"kv_dtype={kv_dtype!r} requires the paged KV layout "
            "(dense stripes are always fp32)"
        )
    if kv_dtype != "fp32":
        # Quantized pools stream 1-byte codes: the traffic/occupancy models
        # score the bytes that actually move.
        dtype_bytes = quant_lib.kv_itemsize(kv_dtype)
    b, hq, hkv, sq, skv, d = (int(x) for x in shape)
    backend = backend or compat.default_backend()
    if interpret is None:
        interpret = compat.use_interpret(backend)
    budget, limit = vmem_limits(backend)
    if vmem_budget_bytes is not None:
        budget = vmem_budget_bytes
    plan = _plan_cached(
        b, hq, hkv, sq, skv, d,
        phase, kv_layout, backend, bool(interpret),
        int(dtype_bytes),
        int(window) if window else None,
        int(page_size) if page_size else None,
        int(prefix_pages),
        mapping_name, impl,
        int(budget),
        int(limit),
        int(num_devices),
        float(device_link_bw) if device_link_bw is not None else None,
        kv_dtype,
    )
    for plans in _RECORDERS:
        plans.append(plan)
    return plan


#: Open :func:`record_plans` blocks, innermost last.
_RECORDERS: List[List[AttentionPlan]] = []


@contextlib.contextmanager
def record_plans() -> Iterator[List[AttentionPlan]]:
    """Collect every plan :func:`plan_attention` returns inside the
    block, cache hits included — which schedules and impls a traced
    program actually ran with (plans resolve at trace time)."""
    plans: List[AttentionPlan] = []
    _RECORDERS.append(plans)
    try:
        yield plans
    finally:
        _RECORDERS.remove(plans)


@functools.lru_cache(maxsize=256)
def _plan_for_mapping_cached(
    mapping: MappingConfig,
    phase: str,
    kv_layout: str,
    backend: str,
    interpret: bool,
    impl: str,
    window: Optional[int],
) -> AttentionPlan:
    return AttentionPlan(
        phase=phase,
        kv_layout=kv_layout,
        impl=_resolve_impl(phase, kv_layout, impl, backend),
        mapping=mapping,
        backend=backend,
        interpret=interpret,
        window=window,
    )


def plan_for_mapping(
    mapping: MappingConfig,
    *,
    phase: str = PREFILL,
    kv_layout: str = DENSE,
    impl: str = "auto",
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> AttentionPlan:
    """A plan carrying a caller-supplied ``MappingConfig`` verbatim (paper
    A/B pins, kernel tests): only the impl/backend environment is resolved
    — no candidate scoring runs for a schedule that is already decided."""
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r}; expected one of {PHASES}")
    backend = backend or compat.default_backend()
    if interpret is None:
        interpret = compat.use_interpret(backend)
    return _plan_for_mapping_cached(
        mapping, phase, kv_layout, backend, bool(interpret), impl,
        int(window) if window else None,
    )


# -----------------------------------------------------------------------------
# Config-policy helpers (the only readers of cfg.mapping_name / cfg.attn_impl)
# -----------------------------------------------------------------------------


def plan_for_config(
    cfg,
    shape: Tuple[int, int, int, int, int, int],
    *,
    phase: str = PREFILL,
    kv_layout: str = DENSE,
    window: Optional[int] = None,
    dtype_bytes: Optional[int] = None,
    page_size: Optional[int] = None,
    prefix_pages: int = 0,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    num_devices: int = 1,
    device_link_bw: Optional[float] = None,
    kv_dtype: str = "fp32",
) -> AttentionPlan:
    """:func:`plan_attention` with the schedule/impl policy read from a
    ``ModelConfig``. Models, engines and benchmarks call this instead of
    touching ``cfg.mapping_name`` / ``cfg.attn_impl`` themselves."""
    if dtype_bytes is None:
        import jax.numpy as jnp

        dtype_bytes = jnp.dtype(cfg.compute_dtype).itemsize
    return plan_attention(
        shape,
        phase=phase,
        kv_layout=kv_layout,
        backend=backend,
        interpret=interpret,
        dtype_bytes=dtype_bytes,
        window=window,
        page_size=page_size,
        prefix_pages=prefix_pages,
        mapping_name=getattr(cfg, "mapping_name", "auto"),
        impl=getattr(cfg, "attn_impl", "auto"),
        num_devices=num_devices,
        device_link_bw=device_link_bw,
        kv_dtype=kv_dtype,
    )


def with_mapping(cfg, mapping: Optional[str]):
    """Return ``cfg`` with its kernel-schedule policy overridden (and
    validated): ``mapping`` is "auto" or a ``PAPER_MAPPINGS`` name. A bad
    pinned name raises here, at engine construction, instead of surfacing
    mid-trace."""
    if mapping is not None and mapping != cfg.mapping_name:
        cfg = dataclasses.replace(cfg, mapping_name=mapping)
    if cfg.mapping_name != "auto":
        PAPER_MAPPINGS[cfg.mapping_name]  # KeyError = fail fast
    return cfg
