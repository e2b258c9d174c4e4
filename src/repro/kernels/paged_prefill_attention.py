"""Paged prefix-aware prefill Pallas kernel (PR-3 headline).

Prefix-extension prefill: a request whose first ``prefix_len`` tokens were
already prefilled by an earlier request sharing the prefix (paged engine,
``cache.prefix``) only computes its tail's Q/K/V — but every tail query
must still attend the whole prefix. Before this kernel that meant
gathering the prefix's pages to a dense view and running the XLA
``q_offset`` flash path (off Pallas entirely). Here the prefix K/V is read
**straight from the page table**, exactly like ``paged_decode_attention``:

  * the page table rides in SMEM via scalar prefetch and the prefix K/V
    BlockSpec index maps read it directly
    (``index_map = lambda b, h, s, pt, ...: (h, pt[b, s], 0, 0)``) — no
    gather, no dense copy;
  * grid ``(B, Hkv, prefix_pages + tail_tiles)`` is head-first: the leading
    two dims stay PARALLEL so a megacore splits at ACC boundaries, and the
    head-major pool keeps every page in its head's domain stripe
    (``cache.layout.HEAD_ALIGNED`` by construction);
  * the whole GQA group rides in the q block (``(G*Sq, D)`` folded rows),
    so each prefix page is fetched once per (batch, kv-head) — never per
    q-head — the paper's ACC co-location carried into prefill.

The KV walk is two-phase under one online softmax: steps ``< prefix_pages``
sweep the scalar-prefetched pages, later steps sweep the dense tail K/V
(just produced by the projections; the caller scatters it into fresh pages
afterwards). Lengths are **dynamic**: ``prefix_len`` (B,) masks the live
prefix inside a power-of-two-bucketed page table (entries past the live
prefix hold the reserved null page — the copy still issues, the compute is
skipped), and ``tail_len`` (B,) masks bucket padding; rows at or past the
live tail emit exact zeros, so length-0 tails are well-defined.

The XLA ``flash_attention(q_offset=...)`` route survives as the oracle this
kernel is tested against in interpret mode (tests/test_paged_prefill.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

NEG_INF = -1e30


def prefix_page_index_map(mp):
    """K/V prefix-page BlockSpec index map for an ``mp``-column page table:
    grid cell (batch, kv-head, step) DMAs physical page ``pt[b, s]`` of
    head ``h``. Tail-sweep steps (``s >= mp``) clamp to the last table
    entry — the copy still issues (a valid physical page; the engine
    null-pads) but compute is gated off by the phase predicate.
    Module-level so the domain-purity access tracer replays the exact
    function handed to ``pallas_call``."""

    def page_idx(b_, h_, s_, pt, plen, tlen, *scales):
        return (h_, pt[b_, jnp.minimum(s_, mp - 1)], 0, 0)

    return page_idx


def _online_update(s, valid, v, acc_ref, m_ref, l_ref):
    """One online-softmax step of a prefill cell over a block of keys,
    shared by both prefill kernels: ``s`` the ``(rows, keys)`` scores,
    ``valid`` their mask, ``v`` the block's values. The probabilities take
    ``v``'s dtype for the product, which accumulates in float32."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = jnp.broadcast_to(
        l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape,
    )
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _paged_prefill_kernel(
    pt_ref, plen_ref, tlen_ref,   # scalar-prefetch: (B, mp), (B,), (B,)
    *refs,                        # [ks, vs,] q, kp, vp, kt, vt, o, acc, m, l
    scale, softcap, window, page_size, num_prefix, num_tail, seq_tail,
    quantized,
):
    if quantized:
        (ks_ref, vs_ref, q_ref, kp_ref, vp_ref, kt_ref, vt_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, kp_ref, vp_ref, kt_ref, vt_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
        ks_ref = vs_ref = None
    b_idx = pl.program_id(0)
    h_idx = pl.program_id(1)
    s_idx = pl.program_id(2)
    plen = plen_ref[b_idx]
    tlen = tlen_ref[b_idx]
    num_steps = num_prefix + num_tail
    rows = q_ref.shape[2]          # G * seq_tail (GQA group folded in)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Tail-local row index of each folded (group, tail-position) row; its
    # absolute position is plen + row_i. Rows at/past the live tail are
    # fully masked and emit zeros.
    row_i = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0) % seq_tail
    row_ok = row_i < tlen

    def scores(k):
        q = q_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap is not None and softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        return s

    # ---- phase A: prefix pages (page-table-indirected) -------------------
    prefix_live = s_idx * page_size < plen
    if window is not None and window > 0:
        # Pages wholly before the earliest row's window (rows start at
        # absolute position plen) contribute nothing — skip the compute,
        # as the decode kernel does; the in-mask handles the boundary.
        prefix_live &= s_idx * page_size + page_size - 1 >= plen - window

    @pl.when((s_idx < num_prefix) & prefix_live)
    def _prefix():
        k = kp_ref[0, 0].astype(jnp.float32)     # (page_size, D)
        v = vp_ref[0, 0].astype(jnp.float32)
        if quantized:
            # The prefix pages are quantized codes; their per-(head, page)
            # scales prefetched next to the page table dequantize them
            # here, in VMEM. The dense tail (phase B) is fresh fp32.
            pid = pt_ref[b_idx, jnp.minimum(s_idx, num_prefix - 1)]
            k = k * ks_ref[h_idx, pid]
            v = v * vs_ref[h_idx, pid]
        col = s_idx * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        # Prefix columns are always causally visible (col < plen <= row
        # absolute position); only liveness and the window mask apply.
        valid = (col < plen) & row_ok
        if window is not None and window > 0:
            valid &= col > (plen + row_i) - window
        _online_update(scores(k), valid, v, acc_ref, m_ref, l_ref)

    # ---- phase B: dense tail (freshly projected K/V) ---------------------
    t_idx = s_idx - num_prefix
    @pl.when((s_idx >= num_prefix) & (t_idx * page_size < tlen))
    def _tail():
        k = kt_ref[0, 0].astype(jnp.float32)     # (page_size, D)
        v = vt_ref[0, 0].astype(jnp.float32)
        col = t_idx * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        # Tail columns sit at absolute plen + col: causality and the window
        # reduce to tail-local comparisons (plen cancels).
        valid = (col <= row_i) & (col < tlen) & row_ok
        if window is not None and window > 0:
            valid &= col > row_i - window
        _online_update(scores(k), valid, v, acc_ref, m_ref, l_ref)

    @pl.when(s_idx == num_steps - 1)
    def _emit():
        l = l_ref[:, 0:1]
        o_ref[0, 0] = (
            acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


def paged_flash_prefill(
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
    interpret: bool = False,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Prefix-extension prefill over paged prefix K/V + dense tail K/V.

    q: (B, Hq, St, D) tail queries at absolute positions
    ``prefix_len[b] + i``; k/v_pages: (Hkv, P, page_size, D) head-major
    pool; page_table: (B, max_prefix_pages) physical page ids in logical
    order (entries past the live prefix must hold a valid id — the null
    page); k/v_tail: (B, Hkv, St, D) the tail's freshly projected K/V;
    prefix_len: (B,) live prefix tokens (<= max_prefix_pages * page_size,
    need not be a page multiple); tail_len: (B,) live tail tokens (rows
    past it emit zeros). Returns (B, Hq, St, D).

    ``k_scales`` / ``v_scales`` (``(Hkv, P)`` fp32, both or neither):
    quantized-pool mode — the prefix pages hold 1-byte codes and their
    scales prefetch into SMEM next to the page table; the kernel
    dequantizes each prefix page in VMEM. The dense tail K/V stays fp32
    either way (it was just projected; quantization happens when the
    engine scatters it into pages).
    """
    b, hq, st, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    if k_tail.shape != (b, hkv, st, d):
        raise ValueError(
            f"tail K/V shape {k_tail.shape} != {(b, hkv, st, d)}"
        )
    if hq % hkv:
        raise ValueError(f"Hq={hq} not divisible by Hkv={hkv}")
    if page_size % 8:
        raise ValueError(f"page_size {page_size} must be a sublane multiple (8)")
    group = hq // hkv
    if scale is None:
        scale = 1.0 / d**0.5

    # Pad the tail to whole page-size tiles (the padded tail rows/cols are
    # masked via tail_len) so the tail sweep reuses the page tile shape.
    st_p = max(page_size, -(-st // page_size) * page_size)
    if st_p != st:
        pad = ((0, 0), (0, 0), (0, st_p - st), (0, 0))
        q = jnp.pad(q, pad)
        k_tail = jnp.pad(k_tail, pad)
        v_tail = jnp.pad(v_tail, pad)
    num_tail = st_p // page_size

    # An empty page table would break the clamped index map; give it one
    # (never-live) column so prefix_len == 0 batches still trace.
    mp = page_table.shape[1]
    if mp == 0:
        page_table = jnp.zeros((b, 1), jnp.int32)
        mp = 1

    # Fold the GQA group into the q block: each page is then fetched once
    # per (batch, kv-head) grid cell, never per q-head. st_p is a multiple
    # of page_size >= 8, so the folded row count stays sublane-aligned.
    rows = group * st_p
    qg = q.reshape(b, hkv, rows, d)

    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    quantized = k_scales is not None

    grid = (b, hkv, mp + num_tail)
    kernel = functools.partial(
        _paged_prefill_kernel,
        scale=scale, softcap=softcap, window=window,
        page_size=page_size, num_prefix=mp, num_tail=num_tail, seq_tail=st_p,
        quantized=quantized,
    )

    page_idx = prefix_page_index_map(mp)

    def tail_idx(b_, h_, s_, pt, plen, tlen, *scales):
        return (b_, h_, jnp.clip(s_ - mp, 0, num_tail - 1), 0)

    def q_idx(b_, h_, s_, pt, plen, tlen, *scales):
        return (b_, h_, 0, 0)

    prefetch = [
        page_table.astype(jnp.int32),
        prefix_len.astype(jnp.int32),
        tail_len.astype(jnp.int32),
    ]
    if quantized:
        prefetch += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]

    fn = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, rows, d), q_idx),
                pl.BlockSpec((1, 1, page_size, d), page_idx),
                pl.BlockSpec((1, 1, page_size, d), page_idx),
                pl.BlockSpec((1, 1, page_size, d), tail_idx),
                pl.BlockSpec((1, 1, page_size, d), tail_idx),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, d), q_idx),
            scratch_shapes=[
                pltpu.VMEM((rows, d), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=(
                compat.PARALLEL,
                compat.PARALLEL,
                compat.ARBITRARY,
            ),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4.0 * b * hq * st_p * (mp * page_size + st_p) * d),
            bytes_accessed=int(
                q.dtype.itemsize
                * b * hkv * (2 * (mp + num_tail) * page_size * d
                             + 2 * group * st_p * d)
            ),
            transcendentals=int(b * hq * st_p * (mp * page_size + st_p)),
        ),
        interpret=interpret,
        name="paged_flash_prefill",
    )
    out = fn(*prefetch, qg, k_pages, v_pages, k_tail, v_tail)
    return out.reshape(b, hq, st_p, d)[:, :, :st]


def _latent_prefill_kernel(
    pt_ref, plen_ref, tlen_ref,   # scalar-prefetch: (B, mp), (B,), (B,)
    q_ref, pool_hbm, tail_hbm,    # q block, latent pool and tail in HBM
    o_ref, buf, sem, acc_ref, m_ref, l_ref,
    *, scale, v_dim, page_size, pages_per_block, heads, num_tail_pages,
):
    """One cell: ``rows`` query rows (positions x heads, position-major) of
    one sequence, walked over the blocks it needs, prefix blocks first
    (pages named by the table) and then the tail's own blocks up to the
    cell's last position. Each block is ``pages_per_block`` page-shaped
    slots copied once into a double buffer; its first ``v_dim`` columns
    are the values."""
    b_idx = pl.program_id(0)
    t_idx = pl.program_id(1)
    rows, d = q_ref.shape[1], q_ref.shape[2]
    tq = rows // heads                      # positions per cell
    bk = pages_per_block * page_size
    mp = pt_ref.shape[1]
    plen = plen_ref[b_idx]
    tlen = tlen_ref[b_idx]
    t0 = t_idx * tq
    live = t0 < tlen                        # a cell past the tail does nothing
    n_pref = jnp.where(live, (plen + bk - 1) // bk, 0)
    tile_end = jnp.minimum(t0 + tq, tlen)
    n_tail = jnp.where(live, (tile_end + bk - 1) // bk, 0)
    total = n_pref + n_tail

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def start(block, slot):
        @pl.when(block < n_pref)
        def _prefix():
            for j in range(pages_per_block):
                page = pt_ref[b_idx, jnp.minimum(block * pages_per_block + j,
                                                 mp - 1)]
                pltpu.make_async_copy(pool_hbm.at[0, page], buf.at[slot, j],
                                      sem.at[slot]).start()

        @pl.when(block >= n_pref)
        def _tail():
            for j in range(pages_per_block):
                tp = jnp.minimum((block - n_pref) * pages_per_block + j,
                                 num_tail_pages - 1)
                pltpu.make_async_copy(tail_hbm.at[b_idx, tp], buf.at[slot, j],
                                      sem.at[slot]).start()

    def wait(slot):
        # Every slot copy moves one page-shaped tile; the wait counts them.
        for j in range(pages_per_block):
            pltpu.make_async_copy(pool_hbm.at[0, 0], buf.at[slot, j],
                                  sem.at[slot]).wait()

    @pl.when(total > 0)
    def _prime():
        start(0, 0)

    q = q_ref[0]                                           # (rows, D)
    row_pos = t0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads
    row_ok = row_pos < tlen

    def body(block, carry):
        slot = jax.lax.rem(block, 2)

        @pl.when(block + 1 < total)
        def _prefetch():
            start(block + 1, 1 - slot)

        wait(slot)
        is_pref = block < n_pref
        first = jnp.where(is_pref, block, block - n_pref) * bk
        limit = jnp.where(is_pref, plen, tlen)
        k = buf[slot].reshape(bk, d)
        col = first + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        # Prefix columns precede every row; tail columns are causal.
        valid = (col < limit) & (is_pref | (col <= row_pos)) & row_ok
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        # Dead slots may hold anything (NaN included): zero their values.
        v_col = first + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v = jnp.where(v_col < limit, k[:, :v_dim], jnp.zeros((), k.dtype))
        _online_update(s, valid, v, acc_ref, m_ref, l_ref)
        return carry

    jax.lax.fori_loop(0, total, body, 0)
    l = l_ref[:, 0:1]
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_latent_prefill(
    q: jnp.ndarray,
    latent_pages: Optional[jnp.ndarray],
    page_table: Optional[jnp.ndarray],
    latent_tail: jnp.ndarray,
    prefix_len: jnp.ndarray,
    tail_len: jnp.ndarray,
    *,
    v_dim: int,
    scale: float,
    page_size: int = 16,
    rows: Optional[int] = None,
    block_tokens: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed latent-attention prefill (DeepSeek-V2 MLA): a tail of
    queries over a cached prefix in the paged latent pool plus the tail's
    own latents, every head attending the one shared key of each token.

    q: (B, St, H, D) absorbed queries at tail-local positions
    (absolute ``prefix_len[b] + i``); latent_pages: (1, P, page_size, D)
    or None (no prefix: a fresh prefill); page_table: (B, mp) or None;
    latent_tail: (B, St, D); prefix_len / tail_len: (B,) live prefix and
    tail tokens (tail rows past ``tail_len`` emit zeros). Values are the
    first ``v_dim`` columns of each latent. Returns (B, St, H, v_dim).

    The grid is ``(B, cells)``: each cell takes ``rows`` query rows
    (``rows // H`` positions of every head) and walks only the blocks of
    ``block_tokens`` keys it needs, copying each once into a
    double-buffered VMEM block (:data:`plan.LATENT_PREFILL_ROWS` and
    :data:`plan.LATENT_PREFILL_BLOCK_TOKENS` by default). Both products
    run in the pool's dtype with float32 accumulation.
    """
    from repro.kernels import plan as plan_lib

    b, st, heads, d = q.shape
    dtype = latent_tail.dtype
    if latent_pages is None:
        latent_pages = jnp.zeros((1, 1, page_size, d), dtype)
        page_table = jnp.zeros((b, 1), jnp.int32)
    page_size = latent_pages.shape[2]
    if latent_pages.shape[0] != 1 or latent_pages.shape[3] != d:
        raise ValueError(f"latent pool {latent_pages.shape} for q {q.shape}")
    if page_size % 8:
        raise ValueError(f"page_size {page_size} must be a sublane multiple (8)")
    if page_table.shape[1] == 0:
        page_table = jnp.zeros((b, 1), jnp.int32)
    rows = rows or plan_lib.LATENT_PREFILL_ROWS
    block_tokens = block_tokens or plan_lib.LATENT_PREFILL_BLOCK_TOKENS
    st_pages = -(-st // page_size) * page_size
    tq = max(page_size, (rows // heads) // page_size * page_size)
    tq = min(tq, st_pages)
    st_p = -(-st // tq) * tq
    ppb = max(1, min(block_tokens // page_size, st_p // page_size))
    if st_p != st:
        q = jnp.pad(q, ((0, 0), (0, st_p - st), (0, 0), (0, 0)))
        latent_tail = jnp.pad(latent_tail, ((0, 0), (0, st_p - st), (0, 0)))
    qr = q.astype(dtype).reshape(b, st_p * heads, d)
    tail = latent_tail.reshape(b, st_p // page_size, page_size, d)
    cell_rows = tq * heads
    grid = (b, st_p // tq)
    prefetch = [page_table.astype(jnp.int32), prefix_len.astype(jnp.int32),
                tail_len.astype(jnp.int32)]
    mp = page_table.shape[1]
    itemsize = jnp.dtype(dtype).itemsize
    fn = pl.pallas_call(
        functools.partial(
            _latent_prefill_kernel, scale=scale, v_dim=v_dim,
            page_size=page_size, pages_per_block=ppb, heads=heads,
            num_tail_pages=st_p // page_size,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, cell_rows, d), lambda b_, t_, *_: (b_, t_, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, cell_rows, v_dim),
                                   lambda b_, t_, *_: (b_, t_, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page_size, d), dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((cell_rows, v_dim), jnp.float32),
                pltpu.VMEM((cell_rows, 128), jnp.float32),
                pltpu.VMEM((cell_rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, st_p * heads, v_dim), q.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=(compat.PARALLEL, compat.PARALLEL),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2.0 * b * heads * st_p * (mp * page_size + st_p)
                      * (d + v_dim)),
            bytes_accessed=int(itemsize * b * (
                (mp * page_size + st_p) * d + heads * st_p * (d + v_dim))),
            transcendentals=int(b * heads * st_p * (mp * page_size + st_p)),
        ),
        interpret=interpret,
        name="paged_latent_prefill",
    )
    out = fn(*prefetch, qr, latent_pages, tail)
    return out.reshape(b, st_p, heads, v_dim)[:, :st]
