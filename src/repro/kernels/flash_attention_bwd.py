"""FlashAttention-2 backward Pallas kernels (dQ and dK/dV).

Mirrors the paper's §4.6 evaluation: the backward pass has the same ACC
structure as the forward (all row blocks of a head share K/V; all column
blocks share Q/dO), so the same head-first vs block-first grid-order choice
applies. Two kernels, following the standard FA2 decomposition:

  * ``_dq_kernel``  — grid over (batch, q-head, q-block, kv-block): streams
    K/V, accumulates dQ in VMEM scratch, emits on the last kv-block.
  * ``_dkv_kernel`` — grid over (batch, kv-head, kv-block, group, q-block):
    K/V tile is revisited across the whole (group x q-block) inner sweep —
    fetched once per ACC under head-first order — while Q/dO/LSE/delta
    stream. dK/dV accumulate across the GQA group inside the kernel, so no
    (B, Hq, S, D)-sized partials ever materialize.

Numerics: p is recomputed from the saved forward LSE; the softcap derivative
(1 - tanh^2) is folded in when configured. Rows whose LSE is -inf (padding /
fully-masked) contribute nothing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

from repro.kernels.flash_attention import (
    BLOCK_FIRST,
    HEAD_FIRST,
    NEG_INF,
    MappingConfig,
    _apply_softcap,
    _block_mask,
    _dim_semantics,
)


def _recompute_p(q, k, lse, rows, cols, *, scale, causal, window, softcap, kv_len):
    """Recompute the (block_m, block_n) probability tile and the capped
    logits (needed for the softcap chain rule)."""
    s_raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    s = _apply_softcap(s_raw, softcap)
    mask = _block_mask(rows, cols, causal=causal, window=window, kv_len=kv_len)
    valid_row = lse > NEG_INF / 2  # (bm, 1): padding / fully-masked guard
    p = jnp.where(mask & valid_row, jnp.exp(s - lse), 0.0)
    return p, s, mask


def _ds_raw(p, dp, delta, s_capped, softcap):
    ds = p * (dp - delta)
    if softcap is not None and softcap > 0:
        ds = ds * (1.0 - (s_capped / softcap) ** 2)
    return ds


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale, causal, window, softcap, kv_len, num_n, block_m, block_n, order,
):
    m_idx = pl.program_id(2) if order == HEAD_FIRST else pl.program_id(1)
    n_idx = pl.program_id(3)

    @pl.when(n_idx == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

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
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
        cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
        p, s_capped, _ = _recompute_p(
            q, k, lse, rows, cols,
            scale=scale, causal=causal, window=window, softcap=softcap, kv_len=kv_len,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = _ds_raw(p, dp, delta, s_capped, softcap)
        dq_acc[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(n_idx == num_n - 1)
    def _emit():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale, causal, window, softcap, kv_len, num_m, group, block_m, block_n, order,
):
    n_idx = pl.program_id(2) if order == HEAD_FIRST else pl.program_id(1)
    g_idx = pl.program_id(3)
    m_idx = pl.program_id(4)

    @pl.when((g_idx == 0) & (m_idx == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = m_idx * block_m
    kv_start = n_idx * block_n
    relevant = kv_start < kv_len
    if causal:
        relevant &= q_start + block_m - 1 >= kv_start
    if window is not None and window > 0:
        relevant &= q_start <= kv_start + block_n - 1 + window - 1

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
        cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
        p, s_capped, _ = _recompute_p(
            q, k, lse, rows, cols,
            scale=scale, causal=causal, window=window, softcap=softcap, kv_len=kv_len,
        )
        # dV += P^T dO ; dP = dO V^T ; dS = P*(dP - delta) ; dK += dS^T Q
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = _ds_raw(p, dp, delta, s_capped, softcap)
        dk_acc[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when((g_idx == group - 1) & (m_idx == num_m - 1))
    def _emit():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_cost(b, hq, sq, skv, d, causal, dtype_bytes):
    frac = 0.5 if causal and sq == skv else 1.0
    flops = 10.0 * b * hq * sq * skv * d * frac  # 5 matmuls
    bytes_accessed = dtype_bytes * b * hq * (4 * sq * d + 4 * skv * d)
    return pl.CostEstimate(
        flops=int(flops),
        bytes_accessed=int(bytes_accessed),
        transcendentals=int(b * hq * sq * skv * frac),
    )


def flash_attention_bwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    o: jnp.ndarray,
    lse: jnp.ndarray,
    do: jnp.ndarray,
    *,
    mapping: MappingConfig = MappingConfig(),
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    kv_len: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (dq, dk, dv). Shapes as in the forward."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / d**0.5
    if kv_len is None:
        kv_len = skv
    bm = min(mapping.block_m, sq)
    bn = min(mapping.block_n, skv)
    num_m, num_n = sq // bm, skv // bn

    # delta = rowsum(dO * O): tiny elementwise reduction, done in XLA.
    # Row statistics ride as (B, Hq, S, 1) so their (bm, 1) blocks tile on
    # TPU (see the forward's lse output).
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    lse = lse[..., None]

    # ---- dQ ----
    if mapping.order == HEAD_FIRST:
        def gidx3(b_, h_, m_):
            return b_, h_, m_
        dq_grid = (b, hq, num_m, num_n)
    else:
        def gidx3(b_, m_, h_):
            return b_, h_, m_
        dq_grid = (b, num_m, hq, num_n)

    def q_idx(*g):
        b_, h_, m_ = gidx3(*g[:3])
        return (b_, h_, m_, 0)

    def kv_idx(*g):
        b_, h_, m_ = gidx3(*g[:3])
        return (b_, h_ // group, g[3], 0)

    dq_fn = pl.pallas_call(
        functools.partial(
            _dq_kernel,
            scale=scale, causal=causal, window=window, softcap=softcap,
            kv_len=kv_len, num_n=num_n, block_m=bm, block_n=bn, order=mapping.order,
        ),
        grid=dq_grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm, d), q_idx),
            pl.BlockSpec((1, 1, bn, d), kv_idx),
            pl.BlockSpec((1, 1, bn, d), kv_idx),
            pl.BlockSpec((1, 1, bm, d), q_idx),
            pl.BlockSpec((1, 1, bm, 1), q_idx),
            pl.BlockSpec((1, 1, bm, 1), q_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, bm, d), q_idx),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bm, d), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=_dim_semantics(
                mapping.order, mapping.acc_parallel, len(dq_grid)
            ),
            vmem_limit_bytes=mapping.vmem_limit_bytes,
        ),
        cost_estimate=_bwd_cost(b, hq, sq, skv, d, causal, q.dtype.itemsize),
        interpret=interpret,
        name=f"fa2_dq_{mapping.order}",
    )
    dq = dq_fn(q, k, v, do, lse, delta)

    # ---- dK/dV ----
    if mapping.order == HEAD_FIRST:
        def gidx_kv(b_, hkv_, n_):
            return b_, hkv_, n_
        dkv_grid = (b, hkv, num_n, group, num_m)
    else:
        def gidx_kv(b_, n_, hkv_):
            return b_, hkv_, n_
        dkv_grid = (b, num_n, hkv, group, num_m)

    def kv_idx2(*g):
        b_, hkv_, n_ = gidx_kv(*g[:3])
        return (b_, hkv_, n_, 0)

    def q_idx2(*g):
        b_, hkv_, n_ = gidx_kv(*g[:3])
        return (b_, hkv_ * group + g[3], g[4], 0)

    dkv_fn = pl.pallas_call(
        functools.partial(
            _dkv_kernel,
            scale=scale, causal=causal, window=window, softcap=softcap,
            kv_len=kv_len, num_m=num_m, group=group, block_m=bm, block_n=bn,
            order=mapping.order,
        ),
        grid=dkv_grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm, d), q_idx2),
            pl.BlockSpec((1, 1, bn, d), kv_idx2),
            pl.BlockSpec((1, 1, bn, d), kv_idx2),
            pl.BlockSpec((1, 1, bm, d), q_idx2),
            pl.BlockSpec((1, 1, bm, 1), q_idx2),
            pl.BlockSpec((1, 1, bm, 1), q_idx2),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bn, d), kv_idx2),
            pl.BlockSpec((1, 1, bn, d), kv_idx2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, d), jnp.float32),
            pltpu.VMEM((bn, d), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=_dim_semantics(
                mapping.order, mapping.acc_parallel, len(dkv_grid)
            ),
            vmem_limit_bytes=mapping.vmem_limit_bytes,
        ),
        cost_estimate=_bwd_cost(b, hq, sq, skv, d, causal, q.dtype.itemsize),
        interpret=interpret,
        name=f"fa2_dkv_{mapping.order}",
    )
    dk, dv = dkv_fn(q, k, v, do, lse, delta)
    return dq, dk, dv
