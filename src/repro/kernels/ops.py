"""Public jit'd attention ops: plan-driven dispatch, padding, custom VJP.

Three implementations behind one API:

  * ``pallas``    — the NUMA-aware Pallas kernels (flash_attention.py /
                    flash_attention_bwd.py / decode_attention.py /
                    paged_decode_attention.py / paged_prefill_attention.py).
                    Real Mosaic lowering on TPU; ``interpret=True`` elsewhere.
  * ``xla_flash`` — chunked online-softmax in pure jnp (lax.scan over KV
                    chunks). Differentiable, remat-friendly, O(S·chunk)
                    memory. Used for the multi-pod dry-run (the CPU backend
                    cannot lower Mosaic) and for CPU-hosted training smokes.
  * ``xla_flash_tri`` — beyond-paper §Perf variant: causally-triangular
                    unrolled chunking that skips above-diagonal work, halving
                    attention HLO FLOPs on training shapes (see
                    EXPERIMENTS.md §Perf).
  * ``ref``       — exact attention (tests only).

Scheduling lives in **``kernels.plan``** (PR 3): every public op accepts an
:class:`~repro.kernels.plan.AttentionPlan` and, when none is passed, builds
one via ``plan.plan_attention`` for its phase (prefill / extend / decode)
and KV layout (dense / paged). The legacy entry points below —
``resolve_mapping`` and ``resolve_kv_layout`` — are thin wrappers over that
resolver, kept for benchmarks and tests that only want the mapping or the
layout ranking.

On a serving mesh (:func:`kernel_mesh`) every Pallas kernel runs under
``shard_map``, once per device on that device's slice of heads: Mosaic
kernels cannot be partitioned automatically.

The paged serving pair: ``paged_decode_attention`` dispatches the
page-table flash-decode kernel, and ``paged_prefill_attention`` dispatches
the prefix-aware paged prefill kernel — prefix K/V read straight from the
page table, no gather and no XLA ``q_offset`` fallback (which survives on
``flash_attention`` as the oracle route).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import plan as plan_lib
from repro.kernels import ref as ref_mod
from repro.kernels.decode_attention import flash_decode
from repro.kernels.paged_decode_attention import paged_flash_decode
from repro.kernels.paged_prefill_attention import paged_flash_prefill
from repro.kernels.flash_attention import (
    MappingConfig,
    flash_attention_fwd,
    hbm_block_fetches,  # noqa: F401  (re-export: benchmarks/tests import it here)
)
from repro.kernels.flash_attention_bwd import flash_attention_bwd
from repro.kernels.plan import AttentionPlan, plan_attention  # noqa: F401


# -----------------------------------------------------------------------------
# Head-parallel kernels on a serving mesh
# -----------------------------------------------------------------------------

_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_mesh", default=None
)


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Inside the block, Pallas kernels traced by this module run once per
    device of the 1-D ``mesh``, each on its device's contiguous slice of
    query and KV heads, and their outputs are gathered back to replicated.
    The serving backends trace their jitted steps under it; a mesh of one
    device (or None) changes nothing."""
    if mesh is not None and mesh.devices.size > 1 and len(mesh.axis_names) != 1:
        raise ValueError(f"kernel_mesh needs a 1-D mesh, got {mesh.axis_names}")
    active = mesh if mesh is not None and mesh.devices.size > 1 else None
    token = _KERNEL_MESH.set(active)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def _head_parallel(kernel, args: Sequence, head_axes: Sequence[Optional[int]],
                   out_head_axis: int):
    """``kernel(*args)``, or under :func:`kernel_mesh` that call per device
    with ``args[i]`` split along ``head_axes[i]`` (None = replicated) and
    the output re-gathered along ``out_head_axis``. Every head's attention
    is computed whole on one device, so the result equals the one-device
    call."""
    mesh = _KERNEL_MESH.get()
    if mesh is None:
        return kernel(*args)
    (axis,) = mesh.axis_names

    def spec(head_axis):
        return P() if head_axis is None else P(*([None] * head_axis), axis)

    def body(*xs):
        return jax.lax.all_gather(
            kernel(*xs), axis, axis=out_head_axis, tiled=True
        )

    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(spec(a) for a in head_axes),
        out_specs=P(), check_vma=False,
    )(*args)


# -----------------------------------------------------------------------------
# Legacy resolvers: thin wrappers over the plan layer
# -----------------------------------------------------------------------------


def resolve_mapping(
    shape: Tuple[int, int, int, int, int, int],
    backend: Optional[str] = None,
    *,
    dtype_bytes: int = 2,
    vmem_budget_bytes: Optional[int] = None,
    decode: bool = False,
    window: Optional[int] = None,
) -> MappingConfig:
    """Pick the best ``MappingConfig`` for an attention shape.

    Thin wrapper over :func:`repro.kernels.plan.plan_attention` (which owns
    the scoring and the cache); returns only the plan's mapping. ``shape``
    is ``(batch, num_q_heads, num_kv_heads, seq_q, seq_kv, head_dim)``;
    ``decode`` / ``window`` select the phase and are part of the plan key,
    so a decode-over-long-cache shape resolves to a different schedule than
    a prefill of the same nominal (seq_q, seq_kv).
    """
    return plan_attention(
        shape,
        phase=plan_lib.DECODE if decode else plan_lib.PREFILL,
        backend=backend,
        dtype_bytes=dtype_bytes,
        window=window,
        vmem_budget_bytes=vmem_budget_bytes,
    ).mapping


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# -----------------------------------------------------------------------------
# Pallas path with custom VJP
# -----------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _pallas_attention(q, k, v, causal, window, softcap, scale, mapping, interpret):
    o, _ = flash_attention_fwd(
        q, k, v, mapping=mapping, causal=causal, window=window,
        softcap=softcap, scale=scale, interpret=interpret,
    )
    return o


def _pallas_attention_fwd(q, k, v, causal, window, softcap, scale, mapping, interpret):
    o, lse = flash_attention_fwd(
        q, k, v, mapping=mapping, causal=causal, window=window,
        softcap=softcap, scale=scale, interpret=interpret,
    )
    return o, (q, k, v, o, lse)


def _pallas_attention_bwd(causal, window, softcap, scale, mapping, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o, lse, do, mapping=mapping, causal=causal, window=window,
        softcap=softcap, scale=scale, interpret=interpret,
    )
    return dq, dk, dv


_pallas_attention.defvjp(_pallas_attention_fwd, _pallas_attention_bwd)


# -----------------------------------------------------------------------------
# XLA flash (scan over KV chunks) — dry-run / CPU path
# -----------------------------------------------------------------------------


def _xla_flash(q, k, v, *, causal, window, softcap, scale, kv_len, chunk=1024,
               unroll=False, q_offset=0):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    chunk = min(chunk, skv)
    nc = -(-skv // chunk)
    kp = _pad_to(k, 2, chunk).reshape(b, hkv, nc, chunk, d)
    vp = _pad_to(v, 2, chunk).reshape(b, hkv, nc, chunk, d)
    qg = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    if scale is None:
        scale = 1.0 / d**0.5
    # Rows sit at absolute positions q_offset + i (prefix-extension prefill:
    # the query block starts after an already-cached prefix).
    rows = q_offset + jnp.arange(sq)[:, None]

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        kc, vc, off = xs
        s = jnp.einsum(
            "bhgqd,bhcd->bhgqc", qg, kc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale
        if softcap is not None and softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        cols = off + jnp.arange(chunk)[None, :]
        mask = cols < kv_len
        if causal:
            mask &= cols <= rows
        if window is not None and window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask[None, None, None], s, ref_mod.NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhgqc,bhcd->bhgqd", p, vc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc), None

    init = (
        jnp.full((b, hkv, g, sq, 1), ref_mod.NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, g, sq, 1), jnp.float32),
        jnp.zeros((b, hkv, g, sq, d), jnp.float32),
    )
    xs = (
        jnp.moveaxis(kp, 2, 0),
        jnp.moveaxis(vp, 2, 0),
        jnp.arange(nc) * chunk,
    )
    (m_fin, l_fin, acc), _ = jax.lax.scan(step, init, xs,
                                          unroll=nc if unroll else 1)
    l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
    o = (acc / l_safe).reshape(b, hq, sq, d)
    return o.astype(q.dtype)


def _xla_flash_tri(q, k, v, *, causal, window, softcap, scale, kv_len, chunk=1024,
                   q_offset=0):
    """Causal-triangular variant: q chunk i only attends kv[: (i+1)*chunk].

    Unrolled over q chunks with per-iteration static shapes, so the
    above-diagonal half of the score matrix is never built — the compiled
    HLO carries ~half the attention FLOPs of the scan variant on causal
    training shapes. Falls back to the scan variant when not causal, when
    q/kv lengths differ, or when the query block is offset (prefix-cache
    extension prefill).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if not causal or sq != skv or sq % chunk or q_offset:
        return _xla_flash(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_len=kv_len, chunk=chunk, q_offset=q_offset,
        )
    nq = sq // chunk
    outs = []
    for i in range(nq):
        qi = q[:, :, i * chunk : (i + 1) * chunk]
        hi = (i + 1) * chunk
        lo = 0
        if window is not None and window > 0:
            lo = max(0, (i * chunk - window + 1) // chunk * chunk)
        ki = k[:, :, lo:hi]
        vi = v[:, :, lo:hi]
        # positions are absolute: shift rows by q_offset via kv_len masking
        oi = _xla_flash_offset(
            qi, ki, vi, abs_q=i * chunk, abs_k=lo, causal=True, window=window,
            softcap=softcap, scale=scale, kv_len=min(kv_len, hi), chunk=chunk,
        )
        outs.append(oi)
    return jnp.concatenate(outs, axis=2)


def _xla_flash_offset(
    q, k, v, *, abs_q, abs_k, causal, window, softcap, scale, kv_len, chunk
):
    """One (q-chunk x kv-prefix) tile with absolute position masking."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    if scale is None:
        scale = 1.0 / d**0.5
    qg = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    s = jnp.einsum(
        "bhgqd,bhcd->bhgqc", qg, k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    if softcap is not None and softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    rows = abs_q + jnp.arange(sq)[:, None]
    cols = abs_k + jnp.arange(skv)[None, :]
    mask = cols < kv_len
    if causal:
        mask &= cols <= rows
    if window is not None and window > 0:
        mask &= cols > rows - window
    s = jnp.where(mask[None, None, None], s, ref_mod.NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqc,bhcd->bhgqd", p / jnp.where(l == 0, 1, l),
                   v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


# -----------------------------------------------------------------------------
# Public API
# -----------------------------------------------------------------------------


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    mapping: Optional[MappingConfig] = None,
    impl: str = "auto",
    chunk_unroll: bool = False,
    q_offset: int = 0,
    plan: Optional[AttentionPlan] = None,
) -> jnp.ndarray:
    """Multi-head / grouped-query attention. q: (B,Hq,Sq,D); k,v: (B,Hkv,Skv,D).

    ``plan=None`` resolves an :class:`AttentionPlan` for this shape (phase
    ``prefill``, or dense ``extend`` when ``q_offset`` is nonzero); an
    explicit ``mapping`` overrides the plan's schedule (paper A/B pins).

    ``q_offset`` places the query block at absolute positions
    ``[q_offset, q_offset + Sq)`` against a longer KV — the dense
    prefix-extension route. The Pallas forward does not carry the offset,
    so this path runs XLA flash; it is the oracle the paged prefill kernel
    (:func:`paged_prefill_attention`) is tested against.
    """
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    if plan is None:
        phase = plan_lib.EXTEND if q_offset else plan_lib.PREFILL
        if mapping is not None:
            # The schedule is already decided (paper A/B pins, kernel
            # tests): resolve only the impl/backend environment.
            plan = plan_lib.plan_for_mapping(
                mapping, phase=phase, impl=impl, window=window,
            )
        else:
            plan = plan_attention(
                (b, hq, k.shape[1], sq, skv, d),
                phase=phase, window=window,
                dtype_bytes=q.dtype.itemsize, impl=impl,
            )
    impl = plan.impl
    if q_offset and impl == "pallas":
        # Safety net for hand-built prefill plans reused with an offset.
        impl = "xla_flash"
    if impl == "ref":
        return ref_mod.attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
            q_offset=q_offset,
        )
    if impl == "xla_flash":
        return _xla_flash(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_len=skv, unroll=chunk_unroll, q_offset=q_offset,
        )
    if impl == "xla_flash_tri":
        return _xla_flash_tri(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_len=skv, q_offset=q_offset,
        )
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    if mapping is None:
        mapping = plan.mapping
    bm, bn = mapping.block_m, mapping.block_n
    qp = _pad_to(q, 2, bm)
    kp = _pad_to(k, 2, bn)
    vp = _pad_to(v, 2, bn)
    o = _head_parallel(
        lambda q_, k_, v_: _pallas_attention(
            q_, k_, v_, causal, window, softcap, scale, mapping,
            plan.interpret,
        ),
        (qp, kp, vp), (1, 1, 1), 1,
    )
    return o[:, :, :sq]


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    impl: str = "auto",
    plan: Optional[AttentionPlan] = None,
) -> jnp.ndarray:
    """Single-token decode. q: (B,Hq,D); caches: (B,Hkv,Smax,D); lengths: (B,)."""
    b, hq, d = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    if plan is None:
        plan = plan_attention(
            (b, hq, hkv, 1, smax, d),
            phase=plan_lib.DECODE, window=window,
            dtype_bytes=q.dtype.itemsize, impl=impl,
        )
    if plan.impl in ("xla", "ref"):
        return ref_mod.decode_attention(
            q, k_cache, v_cache, lengths, softcap=softcap, scale=scale, window=window
        )
    if plan.impl != "pallas":
        raise ValueError(f"unknown impl {plan.impl!r}")
    # The KV chunk comes from the plan (the resolver's block_n, preferring a
    # divisor of the capacity). Only truly odd capacities pay the
    # pad-to-chunk copy; the padded tail sits beyond every ``lengths``
    # entry, so masking never admits it. ``num_splits`` likewise rides the
    # plan (the occupancy model's split-K choice); the kernel clamps it to
    # the chunk count.
    chunk = min(plan.chunk or smax, smax)
    if smax % chunk:
        k_cache = _pad_to(k_cache, 2, chunk)
        v_cache = _pad_to(v_cache, 2, chunk)
    return _head_parallel(
        lambda q_, k_, v_, ln: flash_decode(
            q_, k_, v_, ln,
            softcap=softcap, scale=scale, window=window, chunk=chunk,
            num_splits=plan.num_splits,
            interpret=plan.interpret,
        ),
        (q, k_cache, v_cache, lengths), (1, 1, 1, None), 1,
    )


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    impl: str = "auto",
    plan: Optional[AttentionPlan] = None,
) -> jnp.ndarray:
    """Paged single-token decode. q: (B,Hq,D); k/v_pages: (Hkv,P,ps,D)
    head-major; page_table: (B,max_pages) physical ids (null-page padded);
    lengths: (B,). The pallas path consumes the page table natively via
    scalar prefetch; xla/ref gathers a dense view first (oracle/dry-run).

    ``k_scales``/``v_scales`` — (Hkv, P) fp32 per-page dequant factors for
    quantized pools (``cache.quant``); they ride the same scalar-prefetch
    path as the page table, and ``None`` means the pools are fp32.
    """
    b, hq, d = q.shape
    hkv, _, ps, _ = k_pages.shape
    if plan is None:
        plan = plan_attention(
            (b, hq, hkv, 1, page_table.shape[1] * ps, d),
            phase=plan_lib.DECODE, kv_layout=plan_lib.PAGED, page_size=ps,
            window=window, dtype_bytes=k_pages.dtype.itemsize, impl=impl,
        )
    if plan.impl in ("xla", "ref"):
        return ref_mod.paged_decode_attention(
            q, k_pages, v_pages, page_table, lengths,
            softcap=softcap, scale=scale, window=window,
            k_scales=k_scales, v_scales=v_scales,
        )
    if plan.impl != "pallas":
        raise ValueError(f"unknown impl {plan.impl!r}")
    args = [q, k_pages, v_pages, page_table, lengths]
    head_axes = [1, 0, 0, None, None]
    if k_scales is not None:
        args += [k_scales, v_scales]
        head_axes += [0, 0]

    def kernel(q_, kp, vp, pt, ln, ks=None, vs=None):
        return paged_flash_decode(
            q_, kp, vp, pt, ln,
            softcap=softcap, scale=scale, window=window,
            k_scales=ks, v_scales=vs,
            num_splits=plan.num_splits,
            pages_per_block=plan.pages_per_block,
            interpret=plan.interpret,
        )

    return _head_parallel(kernel, args, head_axes, 1)


def paged_prefill_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    k_tail: jnp.ndarray,
    v_tail: jnp.ndarray,
    prefix_len: jnp.ndarray,
    tail_len: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    impl: str = "auto",
    plan: Optional[AttentionPlan] = None,
) -> jnp.ndarray:
    """Prefix-extension prefill over paged prefix K/V (PR-3 headline).

    q: (B,Hq,St,D) tail queries at absolute positions ``prefix_len[b]+i``;
    k/v_pages: (Hkv,P,ps,D) head-major pool; page_table:
    (B,max_prefix_pages) physical ids in logical order (null-page padded
    past the live prefix); k/v_tail: (B,Hkv,St,D) the tail's fresh K/V;
    prefix_len/tail_len: (B,) live prefix/tail tokens (dynamic — the page
    table width is a bucketed jit constant, the live lengths are not).

    The pallas path reads the prefix straight from the page table (no
    gather, no dense copy); xla/ref is the gather-based oracle.
    ``k_scales``/``v_scales`` are the quantized pools' (Hkv, P) per-page
    dequant factors (``None`` for fp32 pools); the tail K/V is always
    fresh fp32 activations and never quantized.
    """
    b, hq, st, d = q.shape
    hkv, _, ps, _ = k_pages.shape
    if plan is None:
        plan = plan_attention(
            (b, hq, hkv, st, page_table.shape[1] * ps + st, d),
            phase=plan_lib.EXTEND, kv_layout=plan_lib.PAGED, page_size=ps,
            prefix_pages=page_table.shape[1], window=window,
            dtype_bytes=q.dtype.itemsize, impl=impl,
        )
    if plan.impl in ("xla", "ref"):
        return ref_mod.paged_prefill_attention(
            q, k_pages, v_pages, page_table, k_tail, v_tail,
            prefix_len, tail_len,
            softcap=softcap, scale=scale, window=window,
            k_scales=k_scales, v_scales=v_scales,
        )
    if plan.impl != "pallas":
        raise ValueError(f"unknown impl {plan.impl!r}")
    args = [q, k_pages, v_pages, page_table, k_tail, v_tail, prefix_len,
            tail_len]
    head_axes = [1, 0, 0, None, 1, 1, None, None]
    if k_scales is not None:
        args += [k_scales, v_scales]
        head_axes += [0, 0]

    def kernel(q_, kp, vp, pt, kt, vt, pl_, tl, ks=None, vs=None):
        return paged_flash_prefill(
            q_, kp, vp, pt, kt, vt, pl_, tl,
            softcap=softcap, scale=scale, window=window,
            k_scales=ks, v_scales=vs,
            interpret=plan.interpret,
        )

    return _head_parallel(kernel, args, head_axes, 1)


# -----------------------------------------------------------------------------
# KV-layout resolution: thin wrapper over the plan layer
# -----------------------------------------------------------------------------


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
    ``"paged:head_aligned"`` or ``"paged:interleaved"``. Thin wrapper over
    :func:`repro.kernels.plan.resolve_kv_layout` (which owns the scoring and
    the cache) — kept as the legacy entry point for benchmarks/engines."""
    return plan_lib.resolve_kv_layout(
        shape,
        capacity=capacity,
        page_size=page_size,
        dtype_bytes=dtype_bytes,
        backend=backend,
        shared_prefix_len=shared_prefix_len,
    )
