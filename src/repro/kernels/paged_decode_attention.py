"""Paged flash-decode Pallas kernel: decode over a page-table-indirected KV.

The serving-native sibling of ``decode_attention.py``. There the KV cache is
a dense per-slot stripe ``(B, Hkv, Smax, D)``; here it is a pool of
fixed-size pages ``(Hkv, num_pages, page_size, D)`` plus a per-sequence page
table, so sequences grow page-at-a-time, share prefix pages, and never
reserve capacity they don't use. The kernel consumes that layout *natively*:
the pools stay in HBM (``memory_space=pl.ANY``), the page table and lengths
ride in SMEM via scalar prefetch, and the kernel copies the pages it needs
itself, with no gather materializing a dense view first.

The walk. The grid is ``(B, Hkv)``: one cell per (batch row, KV head).
A cell walks its row's KV in compute blocks of ``pages_per_block`` pages
(``plan.decode_pages_per_block`` picks the count from the shape), and only
the blocks that hold a position the row attends: ``[first, last)`` with
``last = ceil(length / bk)`` and, under a sliding window, ``first =
max(0, length - window) // bk`` (``decode_common.live_block_range``). A
row of length 0 spends its cell and nothing more. For each block the
kernel issues one async copy per page of K and of V into a double-buffered
VMEM block ``(2, pages_per_block, page_size, D)``, starting the next
block's copies before it computes on the current one.
:func:`kv_block_pages` names the (head, page) each slot copies and whether
it is live; the domain-purity access tracer
(``repro.analysis.access_trace``) replays the same function.
Slots past the row's live pages copy the null page their table entry holds
(past the table's end, its last entry): copying them costs less on a v5e
than a branch per page (PERF.md). The block arithmetic masks their
positions in the scores and in ``v``, so nothing they hold, NaN included,
reaches the accumulator.

The NUMA structure of the dense kernel is preserved:
  * the grid is head-first — one ACC owns each (batch, kv-head) cell, and
    both grid axes are PARALLEL so a megacore splits at ACC boundaries;
  * the physical page array is **head-major**: all pages of one KV head are
    contiguous, i.e. they live in that head's domain stripe
    (``cache.layout.HEAD_ALIGNED``). The cell and its pages share a domain
    by construction — the serving-scale form of the paper's WG->XCD
    co-location;
  * the GQA group is the q block, so each page is fetched once per
    (batch, kv-head), never per q-head.

Split-K (PR 4): ``num_splits > 1`` adds a PARALLEL grid axis over
contiguous **block ranges** (``cache.layout.decode_split_ranges`` over the
table's blocks). Each (b, hkv, split) cell runs the same walk, cut to its
range, and emits partial ``(acc, m, l)``; ``decode_common.
combine_split_states`` merges them. Split boundaries are page-granular by
construction and, because the pool is head-major, **no split ever
straddles NUMA domains** (``layout.split_ranges_domain_aligned``).
``num_splits`` comes from the plan layer's occupancy model; the
long-context, small-batch serving regime is where it exceeds 1.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.cache import layout as layout_lib
from repro.kernels import decode_common

NEG_INF = decode_common.NEG_INF


def kv_block_pages(
    b, h, block, page_table, lengths, *, pages_per_block: int,
    page_size: int, window: Optional[int] = None,
) -> List[Tuple[object, Tuple[object, object]]]:
    """What block ``block`` of cell ``(b, h)`` copies: one ``(live, (head,
    page))`` pair per slot of the block. Every slot is copied, ``(head,
    page)`` indexing the head-major pool; the table index is clamped to its
    last slot, so a slot past the table still names a valid page, and a
    slot past the row's live pages names the null page its table entry
    holds. ``live`` says whether the slot's page holds a position the row
    attends (``decode_common.chunk_relevant``) and lies inside the table:
    only live slots reach the accumulator.

    Module-level so the kernel's DMA loop and the domain-purity access
    tracer (``repro.analysis.access_trace``) call the *same* function:
    ``page_table`` / ``lengths`` are the SMEM refs in the kernel and numpy
    arrays in the tracer."""
    max_pages = page_table.shape[1]
    length = lengths[b]
    slots = []
    for j in range(pages_per_block):
        slot = block * pages_per_block + j
        live = (slot < max_pages) & decode_common.chunk_relevant(
            slot * page_size, page_size, length, window
        )
        page = page_table[b, jnp.minimum(slot, max_pages - 1)]
        slots.append((live, (h, page)))
    return slots


def _q_index_map(b_, h_, *rest):
    return (b_, h_, 0, 0)


def _split_out_index_map(b_, h_, s_, *prefetch):
    return (b_, h_, s_, 0, 0)


def _scale_index_map(b_, h_, *rest):
    return (h_, b_, 0, 0)


def _paged_decode_kernel(
    pt_ref, len_ref,            # scalar-prefetch: (B, max_pages), (B,)
    *refs,                      # q, k/v HBM, [ks, vs,] out(s), scratch
    scale, softcap, window, page_size, pages_per_block, blocks_per_split,
    quantized, split,
):
    """One walk for both paths: a cell's ``[first, last)`` live blocks,
    cut to its split's range under split-K. One-pass emits the normalized
    output; split-K emits the raw ``(acc, m, l)``."""
    q_ref, k_hbm, v_hbm, *refs = refs
    if quantized:
        ks_ref, vs_ref, *refs = refs
    if split:
        acc_out, m_out, l_out, *refs = refs
    else:
        o_ref, *refs = refs
    k_buf, v_buf, sem, acc_ref, m_ref, l_ref = refs
    d = k_buf.shape[-1]
    bk = pages_per_block * page_size
    b_idx = pl.program_id(0)
    h_idx = pl.program_id(1)
    length = len_ref[b_idx]
    first, last = decode_common.live_block_range(length, window, bk)
    if split:
        s_idx = pl.program_id(2)
        first = jnp.maximum(first, s_idx * blocks_per_split)
        last = jnp.minimum(last, (s_idx + 1) * blocks_per_split)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block_slots(block):
        return kv_block_pages(
            b_idx, h_idx, block, pt_ref, len_ref,
            pages_per_block=pages_per_block, page_size=page_size,
            window=window,
        )

    def copies(block, buf):
        out = []
        for j, (_, (head, page)) in enumerate(block_slots(block)):
            out.append(pltpu.make_async_copy(
                k_hbm.at[head, page], k_buf.at[buf, j], sem.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[head, page], v_buf.at[buf, j], sem.at[1, buf]))
        return out

    def start(block, buf):
        for c in copies(block, buf):
            c.start()

    def wait(block, buf):
        for c in copies(block, buf):
            c.wait()

    def page_scales(scale_ref, block):
        """``(1, bk)`` row: each position's page scale (0 on dead slots)."""
        max_pages = pt_ref.shape[1]
        col_page = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) // page_size
        row = jnp.zeros((1, bk), jnp.float32)
        for j, (live, _) in enumerate(block_slots(block)):
            slot = jnp.minimum(block * pages_per_block + j, max_pages - 1)
            row = jnp.where(col_page == j,
                            jnp.where(live, scale_ref[0, 0, 0, slot], 0.0),
                            row)
        return row

    @pl.when(first < last)
    def _prime():
        start(first, 0)

    def body(block, carry):
        buf = jax.lax.rem(block - first, 2)

        @pl.when(block + 1 < last)
        def _prefetch():
            start(block + 1, 1 - buf)

        wait(block, buf)
        k_scale = v_scale = None
        if quantized:
            k_scale = page_scales(ks_ref, block)
            v_scale = page_scales(vs_ref, block)
        decode_common.accumulate_kv_block(
            q_ref[0, 0],
            k_buf[buf].astype(jnp.float32).reshape(bk, d),
            v_buf[buf].astype(jnp.float32).reshape(bk, d),
            acc_ref, m_ref, l_ref,
            scale=scale, softcap=softcap, window=window,
            block_start=block * bk, block_len=bk, length=length,
            k_scale=k_scale, v_scale=v_scale,
        )
        return carry

    jax.lax.fori_loop(first, last, body, 0)

    if split:
        acc_out[0, 0, 0] = acc_ref[...]
        m_out[0, 0, 0] = m_ref[...]
        l_out[0, 0, 0] = l_ref[...]
    else:
        l = l_ref[:, 0:1]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def paged_flash_decode(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    num_splits: int = 1,
    pages_per_block: Optional[int] = None,
    interpret: bool = False,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """q: (B, Hq, D); k/v_pages: (Hkv, P, page_size, D) head-major;
    page_table: (B, max_pages) int32 physical page ids (entries past a
    sequence's live pages must point at a valid page — the null page);
    lengths: (B,) int32. Returns (B, Hq, D).

    ``pages_per_block`` is the compute block in pages; None takes the
    plan layer's choice for this shape (``plan.decode_pages_per_block``).
    ``num_splits > 1`` runs the sequence-parallel (split-K) path over
    domain-aligned ranges of blocks (the block shrinks to at most
    ``ceil(max_pages / num_splits)`` pages, so each range has one; 1 keeps
    the one-pass walk).

    ``k_scales`` / ``v_scales`` (``(Hkv, P)`` fp32, both or neither) mark
    the pools as quantized codes (``cache.quant``): each page of a block is
    dequantized with its own ``(head, page)`` scale in VMEM. The scales
    are gathered through the page table to one ``(max_pages,)`` row per
    cell, which the pipeline brings into SMEM cell by cell: a whole pool's
    tables would not fit SMEM at long contexts and many heads.
    """
    b, hq, d = q.shape
    hkv, num_pages, page_size, _ = k_pages.shape
    max_pages = page_table.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / d**0.5
    if page_size % 8:
        raise ValueError(f"page_size {page_size} must be a sublane multiple (8)")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    quantized = k_scales is not None
    if pages_per_block is None:
        from repro.kernels import plan as plan_lib

        pages_per_block = plan_lib.decode_pages_per_block(
            max_pages, page_size, d, k_pages.dtype.itemsize,
        )
    # Every split-K range keeps a block of its own.
    pages_per_block = min(pages_per_block,
                          -(-max_pages // max(1, num_splits)))

    gp = max(8, -(-group // 8) * 8)  # pad GQA group to the sublane quantum
    qg = q.reshape(b, hkv, group, d)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))

    num_blocks = -(-max_pages // pages_per_block)
    ranges = layout_lib.decode_split_ranges(num_blocks, num_splits)
    num_splits = len(ranges)
    split = num_splits > 1

    page_table = page_table.astype(jnp.int32)
    prefetch = [page_table, lengths.astype(jnp.int32)]
    in_specs = [
        pl.BlockSpec((1, 1, gp, d), _q_index_map),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scale_rows = []
    if quantized:
        for sc in (k_scales, v_scales):
            rows = sc.astype(jnp.float32)[:, page_table]  # (Hkv, B, max_pages)
            scale_rows.append(rows.reshape(hkv, b, 1, max_pages))
            in_specs.append(pl.BlockSpec(
                (1, 1, 1, max_pages), _scale_index_map,
                memory_space=pltpu.SMEM,
            ))
    if split:
        grid = (b, hkv, num_splits)
        out_specs = [
            pl.BlockSpec((1, 1, 1, gp, d), _split_out_index_map),
            pl.BlockSpec((1, 1, 1, gp, 128), _split_out_index_map),
            pl.BlockSpec((1, 1, 1, gp, 128), _split_out_index_map),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, hkv, num_splits, gp, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, num_splits, gp, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, num_splits, gp, 128), jnp.float32),
        ]
    else:
        grid = (b, hkv)
        out_specs = pl.BlockSpec((1, 1, gp, d), _q_index_map)
        out_shape = jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype)
    block_shape = (2, pages_per_block, page_size, d)
    fn = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            scale=scale, softcap=softcap, window=window,
            page_size=page_size, pages_per_block=pages_per_block,
            blocks_per_split=ranges[0][1] - ranges[0][0],
            quantized=quantized, split=split,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM(block_shape, k_pages.dtype),
                pltpu.VMEM(block_shape, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((gp, d), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=(compat.PARALLEL,) * len(grid),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4.0 * b * hq * max_pages * page_size * d),
            bytes_accessed=int(
                b * (2 * k_pages.dtype.itemsize * hkv * max_pages
                     * page_size * d + 2 * q.dtype.itemsize * hq * d)
            ),
            transcendentals=int(b * hq * max_pages * page_size),
        ),
        interpret=interpret,
        name="paged_flash_decode_split" if split else "paged_flash_decode",
    )
    if not split:
        out = fn(*prefetch, qg, k_pages, v_pages, *scale_rows)
        return out[:, :, :group, :].reshape(b, hq, d)
    acc, m, l = fn(*prefetch, qg, k_pages, v_pages, *scale_rows)
    out = decode_common.combine_split_states(acc, m[..., :1], l[..., :1])
    return out[:, :, :group, :].reshape(b, hq, d).astype(q.dtype)
