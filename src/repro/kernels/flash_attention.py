"""FlashAttention-2 Pallas TPU kernels with NUMA-aware grid scheduling.

This is the TPU-native translation of the paper's Swizzled Head-first
Mapping. On a GPU the mapping strategy is a workgroup-ID remap; on TPU the
same freedom lives in (a) the *grid iteration order* and (b) the BlockSpec
``index_map``s, because the Pallas pipeline skips the HBM->VMEM copy of any
block whose index is unchanged between consecutive grid steps (revisiting).

Two structural axes, mirroring paper §3.2/3.3:

  order="head_first"   grid (b, h, m, ...) — all row blocks of one head
                       before the next head: one ACC at a time per core.
  order="block_first"  grid (b, m, h, ...) — heads cycle fastest: the
                       paper's fragmented baseline; no operand survives
                       between consecutive grid steps.

  kv_resident=True     the whole K/V of the current (batch, kv-head) is a
                       single VMEM-resident block, revisited across every
                       q-block (and every q-head of a GQA group). K/V is
                       fetched from HBM ONCE per ACC — the TPU analogue of
                       the paper's 97 % L2 hit rate. Requires
                       4*S*D*dtype (K and V, double-buffered) <= the
                       VMEM budget.
  kv_resident=False    K/V streamed in (block_n, D) tiles (classic FA2);
                       under head_first the Q block is still revisited
                       across the KV sweep.

``hbm_block_fetches`` computes, statically, how many HBM block copies each
configuration performs — the dry-run "hit rate" analogue reported in
benchmarks (no hardware counters needed).

Megacore: ``acc_parallel=True`` marks the batch/head grid dimensions
``PARALLEL`` so a two-core chip splits the grid along ACC boundaries
(swizzled); ``False`` leaves them ARBITRARY (sequential, single-ACC-stream).

All kernels validate in ``interpret=True`` mode against ``ref.py`` (see
tests/test_flash_attention.py for the shape x dtype x flag sweeps).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core import swizzle

NEG_INF = -1e30

HEAD_FIRST = "head_first"
BLOCK_FIRST = "block_first"


@dataclasses.dataclass(frozen=True)
class MappingConfig:
    """NUMA-aware scheduling configuration for the attention kernels.

    The paper's four strategies map onto TPU as:
      swizzled_head_first : order=head_first, kv_resident=True,  acc_parallel=True
      naive_head_first    : order=head_first, kv_resident=False, acc_parallel=False
      swizzled_block_first: order=block_first, kv_resident=True, acc_parallel=True
      naive_block_first   : order=block_first, kv_resident=False, acc_parallel=False
    (block_first + kv_resident thrashes by construction: the resident block
    changes at every grid step — kept for the paper's baseline measurements.)
    """

    order: str = HEAD_FIRST
    kv_resident: bool = True
    acc_parallel: bool = True
    block_m: int = 128
    block_n: int = 128
    # VMEM the resident K/V may occupy, counting both pipeline buffers of
    # K and of V; beyond this the wrapper streams. The plan derives it and
    # the limit below from the chip's VMEM (``plan.vmem_limits``); these
    # defaults are the v5e figures (128 MiB VMEM per core).
    vmem_budget_bytes: int = 48 * 1024 * 1024
    # Scoped-VMEM limit handed to the compiler for the forward and
    # backward kernels: the resident budget plus room for the Q/O blocks
    # and the in-kernel f32 tiles.
    vmem_limit_bytes: int = 96 * 1024 * 1024
    # KV-sweep traversal for the *streaming* path (sawtooth wavefront,
    # ROADMAP 5(a)): "linear" walks tiles 0..num_n-1 every sweep;
    # "sawtooth" serpentines so consecutive sweeps share their boundary
    # tile and Pallas skips its HBM->VMEM copy. Ignored when the K/V is
    # VMEM-resident (there is no per-tile sweep to reorder).
    traversal: str = swizzle.LINEAR

    def resolve_resident(self, skv: int, head_dim: int, dtype_bytes: int) -> bool:
        if not self.kv_resident:
            return False
        # K and V, each double-buffered by the Pallas pipeline.
        return 4 * skv * head_dim * dtype_bytes <= self.vmem_budget_bytes


PAPER_MAPPINGS = {
    "swizzled_head_first": MappingConfig(order=HEAD_FIRST, kv_resident=True, acc_parallel=True),
    "naive_head_first": MappingConfig(order=HEAD_FIRST, kv_resident=False, acc_parallel=False),
    "swizzled_block_first": MappingConfig(order=BLOCK_FIRST, kv_resident=True, acc_parallel=True),
    "naive_block_first": MappingConfig(order=BLOCK_FIRST, kv_resident=False, acc_parallel=False),
}


def _dim_semantics(order: str, acc_parallel: bool, ndims: int):
    """PARALLEL on the leading (batch, head) dims when ACC-aligned."""
    par = compat.PARALLEL
    arb = compat.ARBITRARY
    if not acc_parallel:
        return (arb,) * ndims
    if order == HEAD_FIRST:
        # (b, h, ...) — split cores at ACC boundaries.
        return (par, par) + (arb,) * (ndims - 2)
    # block_first: (b, m, h, ...) — b parallel only (m-split would stripe
    # ACCs across cores; that *is* the naive scheme, expressed by
    # acc_parallel=False).
    return (par,) + (arb,) * (ndims - 1)


def _block_mask(
    rows,  # (bm, 1) absolute row ids
    cols,  # (1, bn) absolute col ids
    *,
    causal: bool,
    window: Optional[int],
    kv_len: int,
):
    mask = cols < kv_len  # padding guard
    if causal:
        mask &= cols <= rows
    if window is not None and window > 0:
        mask &= cols > rows - window
    return mask


def _apply_softcap(s, softcap: Optional[float]):
    if softcap is not None and softcap > 0:
        return softcap * jnp.tanh(s / softcap)
    return s


# -----------------------------------------------------------------------------
# Forward, streaming K/V (classic FA2; order decides revisiting behaviour)
# -----------------------------------------------------------------------------


def _fwd_stream_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, window, softcap, kv_len, num_n, block_m, block_n, order,
    traversal,
):
    if order == HEAD_FIRST:
        m_idx = pl.program_id(2)
    else:
        m_idx = pl.program_id(1)
    # n_seq is the *position in the sweep* (init/emit anchors); n_idx is
    # the KV tile this step actually loads — under sawtooth odd sweeps
    # walk the tiles in reverse, mirroring the kv BlockSpec index_map.
    n_seq = pl.program_id(3)
    n_idx = swizzle.kv_tile_order(traversal, m_idx, n_seq, num_n)

    @pl.when(n_seq == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = m_idx * block_m + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
    cols = n_idx * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)

    # Block-level relevance (causal / window / padding) — skip compute, not
    # the copy (the grid is rectangular on TPU; see kv_resident=True for the
    # variant that skips the work *and* the traffic).
    q_start = m_idx * block_m
    kv_start = n_idx * block_n
    relevant = kv_start < kv_len
    if causal:
        relevant &= kv_start <= q_start + block_m - 1
    if window is not None and window > 0:
        relevant &= kv_start + block_n - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        s = _apply_softcap(s, softcap)
        mask = _block_mask(rows, cols, causal=causal, window=window, kv_len=kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(n_seq == num_n - 1)
    def _emit():
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:, 0:1] + jnp.log(l_safe)
        lse_ref[0, 0] = jnp.where(l == 0.0, NEG_INF, lse)


# -----------------------------------------------------------------------------
# Forward, VMEM-resident K/V (the paper-faithful TPU schedule)
# -----------------------------------------------------------------------------


def _fwd_resident_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref,
    *, scale, causal, window, softcap, kv_len, block_m, block_n, order,
):
    if order == HEAD_FIRST:
        m_idx = pl.program_id(2)
    else:
        m_idx = pl.program_id(1)

    skv = k_ref.shape[2]
    num_n = skv // block_n
    q = q_ref[0, 0].astype(jnp.float32)
    rows = m_idx * block_m + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)

    q_start = m_idx * block_m
    # Work-skipping: only the causally/window-relevant KV chunk range is
    # visited — the resident layout makes the *compute* sub-quadratic-per-
    # block without paying rectangular-grid copies.
    if causal:
        n_hi = jnp.minimum(
            (q_start + block_m + block_n - 1) // block_n, num_n
        ).astype(jnp.int32)
    else:
        n_hi = jnp.int32(num_n)
    if window is not None and window > 0:
        n_lo = jnp.maximum((q_start - window + 1) // block_n, 0).astype(jnp.int32)
    else:
        n_lo = jnp.int32(0)

    def body(n, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, 0, pl.ds(n * block_n, block_n), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(n * block_n, block_n), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        s = _apply_softcap(s, softcap)
        cols = n * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
        mask = _block_mask(rows, cols, causal=causal, window=window, kv_len=kv_len)
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc

    d = q_ref.shape[-1]
    init = (
        jnp.full((block_m, 1), NEG_INF, jnp.float32),
        jnp.zeros((block_m, 1), jnp.float32),
        jnp.zeros((block_m, d), jnp.float32),
    )
    m_fin, l_fin, acc = jax.lax.fori_loop(n_lo, n_hi, body, init)
    l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse = m_fin + jnp.log(l_safe)
    lse_ref[0, 0] = jnp.where(l_fin == 0.0, NEG_INF, lse)


# -----------------------------------------------------------------------------
# pallas_call builders
# -----------------------------------------------------------------------------


def _fwd_cost(b, hq, sq, skv, d, causal, dtype_bytes):
    frac = 0.5 if causal and sq == skv else 1.0
    flops = 4.0 * b * hq * sq * skv * d * frac
    bytes_accessed = dtype_bytes * b * (2 * hq * sq * d + 2 * hq * skv * d)
    return pl.CostEstimate(
        flops=int(flops), bytes_accessed=int(bytes_accessed), transcendentals=int(b * hq * sq * skv * frac)
    )


def flash_attention_fwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mapping: MappingConfig = MappingConfig(),
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    kv_len: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas FA2 forward. q: (B,Hq,Sq,D), k/v: (B,Hkv,Skv,D).

    Returns (o, lse). Sq/Skv must be multiples of the block sizes (the ops.py
    wrapper pads); ``kv_len`` masks padding keys.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not divisible by Hkv={hkv}")
    group = hq // hkv
    if scale is None:
        scale = 1.0 / d**0.5
    if kv_len is None:
        kv_len = skv
    bm, bn = mapping.block_m, mapping.block_n
    bm = min(bm, sq)
    bn = min(bn, skv)
    if sq % bm or skv % bn:
        raise ValueError(f"Sq={sq}/Skv={skv} not divisible by blocks {bm}/{bn}")
    num_m, num_n = sq // bm, skv // bn
    resident = mapping.resolve_resident(skv, d, q.dtype.itemsize)

    if mapping.order == HEAD_FIRST:
        def gidx(b_, h_, m_):
            return b_, h_, m_
    else:
        def gidx(b_, m_, h_):
            return b_, h_, m_

    # lse carries a trailing unit dim: a (bm, 1) block is tileable on TPU
    # (the last dim equals the array's), where a (bm,) row over (B, Hq, S)
    # is not.
    out_shape = [
        jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
    ]

    if resident:
        grid = (b, hq, num_m) if mapping.order == HEAD_FIRST else (b, num_m, hq)

        def q_idx(*g):
            b_, h_, m_ = gidx(*g)
            return (b_, h_, m_, 0)

        def kv_idx(*g):
            b_, h_, m_ = gidx(*g)
            return (b_, h_ // group, 0, 0)

        kernel = functools.partial(
            _fwd_resident_kernel,
            scale=scale, causal=causal, window=window, softcap=softcap,
            kv_len=kv_len, block_m=bm, block_n=bn, order=mapping.order,
        )
        fn = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bm, d), q_idx),
                pl.BlockSpec((1, 1, skv, d), kv_idx),
                pl.BlockSpec((1, 1, skv, d), kv_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bm, d), q_idx),
                pl.BlockSpec((1, 1, bm, 1), q_idx),
            ],
            out_shape=out_shape,
            compiler_params=compat.tpu_compiler_params(
                dimension_semantics=_dim_semantics(
                    mapping.order, mapping.acc_parallel, len(grid)
                ),
                vmem_limit_bytes=mapping.vmem_limit_bytes,
            ),
            cost_estimate=_fwd_cost(b, hq, sq, skv, d, causal, q.dtype.itemsize),
            interpret=interpret,
            name=f"fa2_fwd_resident_{mapping.order}",
        )
        o, lse = fn(q, k, v)
        return o, lse[..., 0]

    # streaming
    grid = (
        (b, hq, num_m, num_n)
        if mapping.order == HEAD_FIRST
        else (b, num_m, hq, num_n)
    )

    def q_idx(*g):
        b_, h_, m_ = gidx(*g[:3])
        return (b_, h_, m_, 0)

    def kv_idx(*g):
        b_, h_, m_ = gidx(*g[:3])
        n_ = swizzle.kv_tile_order(mapping.traversal, m_, g[3], num_n)
        return (b_, h_ // group, n_, 0)

    kernel = functools.partial(
        _fwd_stream_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        kv_len=kv_len, num_n=num_n, block_m=bm, block_n=bn, order=mapping.order,
        traversal=mapping.traversal,
    )
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm, d), q_idx),
            pl.BlockSpec((1, 1, bn, d), kv_idx),
            pl.BlockSpec((1, 1, bn, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bm, d), q_idx),
            pl.BlockSpec((1, 1, bm, 1), q_idx),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bm, d), jnp.float32),
            pltpu.VMEM((bm, 128), jnp.float32),
            pltpu.VMEM((bm, 128), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=_dim_semantics(
                mapping.order, mapping.acc_parallel, len(grid)
            ),
            vmem_limit_bytes=mapping.vmem_limit_bytes,
        ),
        cost_estimate=_fwd_cost(b, hq, sq, skv, d, causal, q.dtype.itemsize),
        interpret=interpret,
        name=f"fa2_fwd_stream_{mapping.order}",
    )
    o, lse = fn(q, k, v)
    return o, lse[..., 0]


# -----------------------------------------------------------------------------
# Static HBM-traffic model (the dry-run analogue of the paper's hit rates)
# -----------------------------------------------------------------------------


def hbm_block_fetches(
    *,
    batch: int,
    num_q_heads: int,
    num_kv_heads: int,
    seq_q: int,
    seq_kv: int,
    head_dim: int,
    dtype_bytes: int = 2,
    mapping: MappingConfig = MappingConfig(),
) -> dict:
    """Bytes each operand is copied HBM->VMEM under a mapping, computed from
    the grid order + index maps (Pallas skips copies for revisited blocks).

    This is exact for the compiled pipeline (no cache stochasticity on TPU)
    and is what benchmarks/fig13 reports for the TPU port alongside the
    MI300X simulator numbers.
    """
    bm, bn = mapping.block_m, mapping.block_n
    num_m = -(-seq_q // bm)
    num_n = -(-seq_kv // bn)
    q_bytes = seq_q * head_dim * dtype_bytes
    kv_bytes = 2 * seq_kv * head_dim * dtype_bytes  # K and V, whole sequence
    kv_tile_bytes = 2 * bn * head_dim * dtype_bytes  # K and V, one (bn, D) tile

    resident = mapping.resolve_resident(seq_kv, head_dim, dtype_bytes)
    if resident:
        # The resident block is the whole (Skv, D) K/V of one kv head, copied
        # as a unit whenever its grid index changes between consecutive steps.
        if mapping.order == HEAD_FIRST:
            # KV block revisited across all m of a head AND across the g
            # q-heads of its group: fetched once per (batch, kv head).
            kv_fetches = batch * num_kv_heads
        else:
            # (b, m, h): the resident block swaps inside every m sweep, so
            # each (batch, q-block) re-fetches every kv head — the thrashing
            # baseline of paper Fig. 8. Consecutive q-heads of one GQA group
            # share the block index, so the pipeline still skips those
            # copies (num_kv_heads fetches per sweep, not num_q_heads); with
            # a single kv head the index never changes at all.
            if num_kv_heads == 1:
                kv_fetches = batch
            else:
                kv_fetches = batch * num_m * num_kv_heads
        kv_traffic = kv_fetches * kv_bytes
    else:
        # Streaming: the full num_n-tile K/V sweep is refetched for every
        # (q-head, q-block) pair under either order (no cache between HBM and
        # VMEM on TPU; order only changes which ACC is live, not the traffic).
        kv_fetches = batch * num_q_heads * num_m * num_n
        if (mapping.traversal == swizzle.SAWTOOTH
                and mapping.order == HEAD_FIRST and num_n > 1):
            # Serpentine sweeps share their boundary tile: the last tile of
            # sweep m is the first tile of sweep m+1, so Pallas skips its
            # copy — one tile saved per consecutive-sweep boundary. Within a
            # q-head that is num_m - 1 boundaries; across the g q-heads of a
            # GQA group (same kv head, consecutive under head_first) the
            # head boundary also matches when num_m is even (the last sweep
            # ends where the next head's first sweep starts).
            group = max(1, num_q_heads // max(num_kv_heads, 1))
            saved_per_kv = (num_m - 1) * group + (
                (group - 1) if num_m % 2 == 0 else 0
            )
            kv_fetches -= batch * num_kv_heads * saved_per_kv
        kv_traffic = kv_fetches * kv_tile_bytes
    # Q: each (bm, D) block is copied once per (batch, q-head, q-block) —
    # under head_first the block is revisited across the whole KV sweep, and
    # under block_first it still changes only when m does.
    q_traffic = batch * num_q_heads * num_m * (bm * head_dim * dtype_bytes)
    ideal = batch * (num_kv_heads * kv_bytes + num_q_heads * q_bytes)
    total = kv_traffic + q_traffic
    return {
        "kv_bytes": kv_traffic,
        "q_bytes": q_traffic,
        "total_bytes": total,
        "ideal_bytes": ideal,
        "reuse_efficiency": ideal / total,
    }
