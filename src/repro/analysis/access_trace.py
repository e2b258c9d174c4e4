"""Domain-purity access tracer for the paged / split-K attention kernels.

The perf model's NUMA claims are *analytic*: ``cache.layout`` proves that
``decode_split_ranges`` boundaries are domain-pure under the head-major
pool by reasoning over logical page indices. The kernels, however, touch
whatever their **BlockSpec index maps** say — a refactor that changes a
``lambda`` inside ``pallas_call`` could silently break the co-location
story while every numeric test still passes (attention output does not
depend on where a page lives).

This module closes that gap: it replays the *exact* addressing functions
the kernels use (the paged decode walk's ``decode_common.live_block_range``
and ``kv_block_pages``, and the index maps ``prefix_page_index_map`` /
``split_chunk_index_map`` — module-level precisely so tracer and kernel
cannot diverge) over a concrete page table, records which physical page
every grid cell DMAs, and asserts:

  * **domain purity** — each cell's *live* fetches (the ones whose compute
    actually runs; clamped tail-overhang DMAs of the BlockSpec kernels are
    recorded but skipped by ``decode_common.chunk_relevant``, same as in
    the kernel) stay inside one memory domain;
  * **domain locality** — under ``HEAD_ALIGNED`` each live fetch lands in
    the very domain that executes the cell (``domain_of_head``);
  * **range consistency** — the split-K cells' live logical pages are
    exactly the ``decode_split_ranges`` partition the plan layer reasons
    about, so model and kernel agree on who reads what.

Runs everywhere the interpret path runs (pure host arithmetic — no Pallas
launch needed); the ``--smoke`` CI step traces a ``num_splits > 1`` paged
plan on every push.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.cache import layout as layout_lib
from repro.kernels import decode_common
from repro.kernels.decode_attention import split_chunk_index_map
from repro.kernels.paged_decode_attention import kv_block_pages
from repro.kernels.paged_prefill_attention import prefix_page_index_map

__all__ = [
    "AccessTrace",
    "CellTrace",
    "DomainPurityError",
    "trace_dense_split_decode",
    "trace_paged_decode",
    "trace_paged_prefill",
    "trace_plan",
]


class DomainPurityError(AssertionError):
    """A grid cell's live page fetches straddle NUMA domains (or miss the
    cell's own domain) — the co-location claim the perf model banks on
    does not hold for this (plan, page table)."""


@dataclasses.dataclass(frozen=True)
class CellTrace:
    """What one grid cell touches."""

    cell: Tuple[int, ...]        # grid coordinates: (b, h) or (b, h, s)
    head: int
    cell_domain: int             # domain executing the cell (head-first grid)
    touched: Tuple[int, ...]     # every physical page the index map DMAs
    live: Tuple[int, ...]        # the subset whose compute actually runs
    live_logical: Tuple[int, ...]  # logical page/chunk indices of `live`

    def live_domains(
        self, policy: str, num_kv_heads: int, num_domains: int
    ) -> Tuple[int, ...]:
        return tuple(sorted({
            layout_lib.domain_of_page(
                pid, self.head, policy, num_kv_heads, num_domains)
            for pid in self.live
        }))


@dataclasses.dataclass
class AccessTrace:
    kernel: str
    policy: str
    num_kv_heads: int
    num_domains: int
    cells: List[CellTrace]

    @property
    def touched_pages(self) -> int:
        return sum(len(c.touched) for c in self.cells)

    @property
    def live_pages(self) -> int:
        return sum(len(c.live) for c in self.cells)

    def assert_domain_pure(self) -> "AccessTrace":
        """Every cell's live fetches read from at most one domain."""
        for c in self.cells:
            doms = c.live_domains(
                self.policy, self.num_kv_heads, self.num_domains)
            if len(doms) > 1:
                raise DomainPurityError(
                    f"{self.kernel}: cell {c.cell} (head {c.head}) reads "
                    f"pages {c.live} from domains {doms} under "
                    f"{self.policy!r} — a split straddles the fabric"
                )
        return self

    def assert_domain_local(self) -> "AccessTrace":
        """Every cell's live fetches read the cell's *own* domain — the
        stronger property HEAD_ALIGNED promises (purity plus locality)."""
        self.assert_domain_pure()
        for c in self.cells:
            doms = c.live_domains(
                self.policy, self.num_kv_heads, self.num_domains)
            if doms and doms != (c.cell_domain,):
                raise DomainPurityError(
                    f"{self.kernel}: cell {c.cell} (domain "
                    f"{c.cell_domain}) reads pages {c.live} homed in "
                    f"domain {doms[0]} under {self.policy!r} — pure but "
                    "not local"
                )
        return self


def _pt_lookup(pt: np.ndarray, idx) -> int:
    # Index maps return jnp scalars (jnp.minimum); concretize for numpy.
    return int(np.asarray(idx))


def trace_paged_decode(
    page_table: np.ndarray,
    lengths: Sequence[int],
    *,
    num_kv_heads: int,
    page_size: int,
    num_splits: int = 1,
    pages_per_block: int = 1,
    window: Optional[int] = None,
    policy: str = layout_lib.HEAD_ALIGNED,
    num_domains: int = 2,
) -> AccessTrace:
    """Replay the paged decode kernel's walk (one-pass or split-K) over
    ``page_table``/``lengths`` and return the per-cell access trace: each
    cell walks its row's live blocks (``decode_common.live_block_range``,
    cut to its
    split's range of blocks) and copies every slot ``kv_block_pages``
    names; the live slots are the ones whose positions reach the
    accumulator. ``num_splits > 1`` additionally cross-checks every cell's
    live logical pages against ``decode_split_ranges`` over the table's
    blocks — the same partition ``split_ranges_domain_aligned`` certifies
    analytically."""
    pt = np.asarray(page_table, dtype=np.int64)
    lens = np.asarray(lengths, dtype=np.int64)
    b, max_pages = pt.shape
    bk = pages_per_block * page_size
    num_blocks = -(-max_pages // pages_per_block)
    ranges = layout_lib.decode_split_ranges(num_blocks, num_splits)
    split = len(ranges) > 1
    kernel = "paged_flash_decode_split" if split else "paged_flash_decode"
    cells: List[CellTrace] = []
    for b_ in range(b):
        first, last = (int(x) for x in decode_common.live_block_range(
            int(lens[b_]), window, bk))
        for h_ in range(num_kv_heads):
            for s_, (start, end) in enumerate(ranges):
                touched, live, logical = [], [], []
                for block in range(max(first, start), min(last, end)):
                    slots = kv_block_pages(
                        b_, h_, block, pt, lens,
                        pages_per_block=pages_per_block,
                        page_size=page_size, window=window,
                    )
                    for j, (is_live, (_, page)) in enumerate(slots):
                        touched.append(_pt_lookup(pt, page))
                        if bool(is_live):
                            live.append(touched[-1])
                            logical.append(block * pages_per_block + j)
                # The walk must cover exactly this range's slice of the
                # row's live pages (the relevance predicate, page by page).
                expect = tuple(
                    p for p in range(start * pages_per_block,
                                     min(end * pages_per_block, max_pages))
                    if decode_common.chunk_relevant(
                        p * page_size, page_size, int(lens[b_]), window)
                )
                if tuple(logical) != expect:
                    raise DomainPurityError(
                        f"{kernel}: cell {(b_, h_, s_)} walks logical "
                        f"pages {tuple(logical)}; its live pages are "
                        f"{expect}"
                    )
                cells.append(CellTrace(
                    cell=(b_, h_, s_) if split else (b_, h_), head=h_,
                    cell_domain=layout_lib.domain_of_head(
                        h_, num_kv_heads, num_domains),
                    touched=tuple(touched), live=tuple(live),
                    live_logical=tuple(logical),
                ))
    return AccessTrace(
        kernel=kernel, policy=policy, num_kv_heads=num_kv_heads,
        num_domains=num_domains, cells=cells,
    )


def trace_paged_prefill(
    page_table: np.ndarray,
    prefix_lens: Sequence[int],
    *,
    num_kv_heads: int,
    page_size: int,
    num_tail: int = 1,
    policy: str = layout_lib.HEAD_ALIGNED,
    num_domains: int = 2,
) -> AccessTrace:
    """Replay the paged prefill kernel's prefix-page index map: grid
    (b, hkv, mp + num_tail). Steps past the prefix (the dense-tail sweep)
    clamp to the last table slot — recorded as touched, never live."""
    pt = np.asarray(page_table, dtype=np.int64)
    plens = np.asarray(prefix_lens, dtype=np.int64)
    b, mp = pt.shape
    page_idx = prefix_page_index_map(mp)
    cells: List[CellTrace] = []
    for b_ in range(b):
        live_prefix = -(-int(plens[b_]) // page_size)
        for h_ in range(num_kv_heads):
            touched, live, logical = [], [], []
            for s_ in range(mp + num_tail):
                _, pid, _, _ = page_idx(b_, h_, s_, pt, plens, None)
                pid = _pt_lookup(pt, pid)
                touched.append(pid)
                if s_ < live_prefix:
                    live.append(pid)
                    logical.append(s_)
            cells.append(CellTrace(
                cell=(b_, h_), head=h_,
                cell_domain=layout_lib.domain_of_head(
                    h_, num_kv_heads, num_domains),
                touched=tuple(touched), live=tuple(live),
                live_logical=tuple(logical),
            ))
    return AccessTrace(
        kernel="paged_prefill", policy=policy, num_kv_heads=num_kv_heads,
        num_domains=num_domains, cells=cells,
    )


def trace_dense_split_decode(
    lengths: Sequence[int],
    *,
    capacity: int,
    chunk: int,
    num_kv_heads: int,
    num_splits: int,
    window: Optional[int] = None,
    num_domains: int = 2,
) -> AccessTrace:
    """Dense split-K analogue: the KV stripe has no page table (logical
    chunk == physical chunk), so the trace proves the index map walks
    exactly the ``decode_split_ranges`` partition with the tail overhang
    clamped. Domains follow the head-first grid (dense stripes are sharded
    by head), so the HEAD_ALIGNED checks apply unchanged."""
    lens = np.asarray(lengths, dtype=np.int64)
    num_chunks = -(-capacity // chunk)
    ranges = layout_lib.decode_split_ranges(num_chunks, num_splits)
    if len(ranges) < 2:
        raise ValueError("dense split trace needs an effective split > 1")
    cps = ranges[0][1] - ranges[0][0]
    kv_index = split_chunk_index_map(cps, num_chunks)
    cells: List[CellTrace] = []
    for b_ in range(len(lens)):
        for h_ in range(num_kv_heads):
            for s_, (start, end) in enumerate(ranges):
                touched, live, logical = [], [], []
                for j_ in range(cps):
                    _, _, c_idx, _ = kv_index(b_, h_, s_, j_)
                    c_idx = int(np.asarray(c_idx))
                    touched.append(c_idx)
                    c_global = s_ * cps + j_
                    if c_global < num_chunks and bool(
                        decode_common.chunk_relevant(
                            c_global * chunk, chunk, int(lens[b_]), window)
                    ):
                        live.append(c_idx)
                        logical.append(c_global)
                if logical and not (
                    start <= logical[0] and logical[-1] < end
                ):
                    raise DomainPurityError(
                        f"flash_decode_split: cell {(b_, h_, s_)} walked "
                        f"chunks {logical} outside its range {(start, end)}"
                    )
                cells.append(CellTrace(
                    cell=(b_, h_, s_), head=h_,
                    cell_domain=layout_lib.domain_of_head(
                        h_, num_kv_heads, num_domains),
                    touched=tuple(touched), live=tuple(live),
                    live_logical=tuple(logical),
                ))
    return AccessTrace(
        kernel="flash_decode_split", policy=layout_lib.HEAD_ALIGNED,
        num_kv_heads=num_kv_heads, num_domains=num_domains, cells=cells,
    )


def trace_plan(
    plan,
    page_table: np.ndarray,
    lengths: Sequence[int],
    *,
    num_kv_heads: int,
    num_domains: int = 2,
    window: Optional[int] = None,
) -> AccessTrace:
    """Trace whatever kernel an :class:`repro.kernels.plan.AttentionPlan`
    would launch for this page table (paged one-pass or split-K decode),
    using the plan's own ``page_size``/``num_splits``/``pages_per_block``/
    ``placement``."""
    policy = getattr(plan, "placement", None) or layout_lib.HEAD_ALIGNED
    return trace_paged_decode(
        page_table, lengths,
        num_kv_heads=num_kv_heads,
        page_size=plan.page_size,
        num_splits=max(1, int(plan.num_splits or 1)),
        pages_per_block=plan.pages_per_block,
        window=window if window is not None else plan.window,
        policy=policy,
        num_domains=num_domains,
    )
