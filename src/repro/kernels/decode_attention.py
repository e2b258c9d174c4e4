"""Flash-decode Pallas kernel: one new token against a long KV cache.

Serving-shape companion of ``flash_attention.py`` (decode_32k / long_500k
dry-run cells lower this). The ACC structure survives in decode: all query
heads of a GQA group read the same KV cache, so the q-block of the kernel is
the *whole group* — KV is fetched once per (batch, kv head) and the group
dimension rides the MXU rows. Grid order is head-first by construction
(one ACC per (b, hkv) grid cell), i.e. the paper's co-location applied to
decode; there is no block-first analogue because a single token has one row
block.

Sequence lengths are dynamic (per-request): ``lengths`` rides in SMEM via
scalar prefetch (as in the paged kernels) and gates both the masking and
the chunk relevance test (``decode_common.chunk_relevant``), so compute
scales with the actual prefix length, not the cache capacity.

Split-K (PR 4): with ``num_splits > 1`` a third PARALLEL grid axis
partitions the chunk walk into ``num_splits`` contiguous ranges
(``cache.layout.decode_split_ranges`` — the same boundary arithmetic the
paged kernel snaps to domain stripes). Each (b, hkv, split) cell emits its
partial online-softmax state ``(acc, m, l)`` instead of a normalized row,
and ``decode_common.combine_split_states`` merges the splits — so a
long-context, small-batch decode step exposes ``B x Hkv x num_splits``
parallel cells instead of idling all but ``B x Hkv`` compute domains.
``num_splits`` is chosen per shape by the plan layer
(``perf_model.estimate_decode_splits``); callers never hardcode it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.cache import layout as layout_lib
from repro.kernels import decode_common

NEG_INF = decode_common.NEG_INF


def split_chunk_index_map(cps, num_chunks):
    """K/V BlockSpec index map of the dense split-K kernel for ``cps``
    chunks per split over ``num_chunks`` total. The tail split's overhang
    clamps to the last real chunk — the DMA must name a valid block; the
    kernel's range test skips its compute. Module-level so the access
    tracer replays the exact function handed to ``pallas_call``."""

    def kv_index(b_, h_, s_, j_, *prefetch):
        return (b_, h_, jnp.minimum(s_ * cps + j_, num_chunks - 1), 0)

    return kv_index


def _split_out_index_map(b_, h_, s_, j_, ln):
    return (b_, h_, s_, 0, 0)


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, softcap, window, chunk, num_chunks, group_padded,
):
    n_idx = pl.program_id(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(n_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    chunk_start = n_idx * chunk

    @pl.when(decode_common.chunk_relevant(chunk_start, chunk, length, window))
    def _compute():
        decode_common.accumulate_kv_block(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], acc_ref, m_ref, l_ref,
            scale=scale, softcap=softcap, window=window,
            block_start=chunk_start, block_len=chunk, length=length,
        )

    @pl.when(n_idx == num_chunks - 1)
    def _emit():
        l = l_ref[:, 0:1]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _decode_split_kernel(
    len_ref, q_ref, k_ref, v_ref, acc_out, m_out, l_out,
    acc_ref, m_ref, l_ref,
    *, scale, softcap, window, chunk, num_chunks, chunks_per_split,
):
    """Stage one of split-K decode: one (b, hkv, split) cell walks its
    chunk range and emits raw ``(acc, m, l)`` — no normalization here;
    the combine stage owns it. Ranges past ``num_chunks`` (non-divisible
    splits: the BlockSpec clamps their DMA to the last real chunk) are
    skipped by the relevance test and emit the empty state."""
    s_idx = pl.program_id(2)
    j_idx = pl.program_id(3)
    length = len_ref[pl.program_id(0)]

    @pl.when(j_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_global = s_idx * chunks_per_split + j_idx
    chunk_start = n_global * chunk
    relevant = (n_global < num_chunks) & decode_common.chunk_relevant(
        chunk_start, chunk, length, window
    )

    @pl.when(relevant)
    def _compute():
        decode_common.accumulate_kv_block(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], acc_ref, m_ref, l_ref,
            scale=scale, softcap=softcap, window=window,
            block_start=chunk_start, block_len=chunk, length=length,
        )

    @pl.when(j_idx == chunks_per_split - 1)
    def _emit():
        acc_out[0, 0, 0] = acc_ref[...]
        m_out[0, 0, 0] = m_ref[...]
        l_out[0, 0, 0] = l_ref[...]


def flash_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    chunk: int = 512,
    num_splits: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, Hq, D); caches: (B, Hkv, Smax, D); lengths: (B,) int32.

    Returns (B, Hq, D). Smax must be a multiple of ``chunk`` (ops.py pads).
    The GQA group dimension is padded to the sublane count inside.
    ``num_splits > 1`` runs the sequence-parallel (split-K) path: the
    chunk walk is partitioned across a PARALLEL grid axis and the partial
    softmax states are merged by ``decode_common.combine_split_states``
    (clamped to the chunk count; 1 keeps the one-pass kernel).
    """
    b, hq, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / d**0.5
    chunk = min(chunk, smax)
    num_chunks = smax // chunk

    gp = max(8, -(-group // 8) * 8)  # pad group to sublane multiple
    qg = q.reshape(b, hkv, group, d)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    lengths = lengths.reshape(b).astype(jnp.int32)

    ranges = layout_lib.decode_split_ranges(num_chunks, num_splits)
    num_splits = len(ranges)
    if num_splits > 1:
        return _flash_decode_split(
            qg, k_cache, v_cache, lengths, ranges,
            scale=scale, softcap=softcap, window=window, chunk=chunk,
            num_chunks=num_chunks, gp=gp, group=group, interpret=interpret,
            out_dtype=q.dtype,
        )

    fn = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            scale=scale, softcap=softcap, window=window,
            chunk=chunk, num_chunks=num_chunks, group_padded=gp,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, num_chunks),
            in_specs=[
                pl.BlockSpec((1, 1, gp, d), lambda b_, h_, n_, ln: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, n_, ln: (b_, h_, n_, 0)),
                pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, n_, ln: (b_, h_, n_, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, gp, d), lambda b_, h_, n_, ln: (b_, h_, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((gp, d), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=(
                compat.PARALLEL,
                compat.PARALLEL,
                compat.ARBITRARY,
            ),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4.0 * b * hq * smax * d),
            bytes_accessed=int(
                q.dtype.itemsize * b * (2 * hkv * smax * d + 2 * hq * d)
            ),
            transcendentals=int(b * hq * smax),
        ),
        interpret=interpret,
        name="flash_decode",
    )
    out = fn(lengths, qg, k_cache, v_cache)
    return out[:, :, :group, :].reshape(b, hq, d)


def _flash_decode_split(
    qg, k_cache, v_cache, lengths, ranges,
    *, scale, softcap, window, chunk, num_chunks, gp, group, interpret,
    out_dtype,
):
    b, hkv, _, d = k_cache.shape
    num_splits = len(ranges)
    cps = ranges[0][1] - ranges[0][0]  # chunks per split (tail may be short)

    kv_index = split_chunk_index_map(cps, num_chunks)

    fn = pl.pallas_call(
        functools.partial(
            _decode_split_kernel,
            scale=scale, softcap=softcap, window=window,
            chunk=chunk, num_chunks=num_chunks, chunks_per_split=cps,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, num_splits, cps),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, gp, d), lambda b_, h_, s_, j_, ln: (b_, h_, 0, 0)
                ),
                pl.BlockSpec((1, 1, chunk, d), kv_index),
                pl.BlockSpec((1, 1, chunk, d), kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, gp, d), _split_out_index_map),
                pl.BlockSpec((1, 1, 1, gp, 128), _split_out_index_map),
                pl.BlockSpec((1, 1, 1, gp, 128), _split_out_index_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((gp, d), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, num_splits, gp, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, num_splits, gp, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, num_splits, gp, 128), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=(
                compat.PARALLEL,
                compat.PARALLEL,
                compat.PARALLEL,
                compat.ARBITRARY,
            ),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4.0 * b * hkv * group * num_chunks * chunk * d),
            bytes_accessed=int(
                k_cache.dtype.itemsize
                * b * (2 * hkv * num_chunks * chunk * d + 2 * hkv * group * d)
            ),
            transcendentals=int(b * hkv * group * num_chunks * chunk),
        ),
        interpret=interpret,
        name="flash_decode_split",
    )
    acc, m, l = fn(lengths, qg, k_cache, v_cache)
    out = decode_common.combine_split_states(acc, m[..., :1], l[..., :1])
    return out[:, :, :group, :].reshape(b, hkv * group, d).astype(out_dtype)
