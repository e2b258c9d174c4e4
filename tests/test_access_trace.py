"""Domain-purity access tracer (repro.analysis.access_trace).

The tracer replays the kernels' exported BlockSpec index maps — the same
functions ``pallas_call`` receives — so these tests prove the NUMA claims
about what the kernels *touch*, independent of their numeric output.
"""

import numpy as np
import pytest

from repro.analysis import access_trace as at
from repro.cache import layout
from repro.kernels import plan as plan_lib


def _table(b, mp, start=1):
    """Distinct physical ids per row (no sharing, no nulls)."""
    return np.arange(start, start + b * mp).reshape(b, mp)


# --- paged decode -------------------------------------------------------------


def test_one_pass_trace_touches_every_table_slot():
    """The walk copies exactly the pages of each row's live blocks (slots
    past the table clamp to its last entry) and no block past the row's
    length; compute is gated to the live pages."""
    pt = _table(2, 6)
    for ppb in (1, 4):
        tr = at.trace_paged_decode(pt, [48, 20], num_kv_heads=4,
                                   page_size=8, pages_per_block=ppb)
        assert len(tr.cells) == 2 * 4
        for c in tr.cells:
            b = c.cell[0]
            live = -(-(48 if b == 0 else 20) // 8)
            blocks = -(-live // ppb)
            assert c.touched == tuple(                 # live blocks only
                pt[b, min(slot, 5)] for slot in range(blocks * ppb))
            assert c.live == tuple(pt[b, :live])       # compute gated
            assert c.live_logical == tuple(range(live))
        tr.assert_domain_local()                       # head-major pool


def test_one_pass_trace_with_a_window():
    """Under a sliding window the walk starts at the block holding the
    window's edge and copies only the pages the window reaches."""
    pt = _table(1, 12)
    tr = at.trace_paged_decode(pt, [90], num_kv_heads=2, page_size=8,
                               pages_per_block=4, window=30)
    # Positions 60..89 attend: pages 7..11 (the edge falls inside page 7,
    # in the second block of four).
    for c in tr.cells:
        assert c.live_logical == (7, 8, 9, 10, 11)
        assert c.live == tuple(pt[0, 7:12])
        assert c.touched == tuple(pt[0, 4:12])   # blocks 1 and 2, whole
    tr.assert_domain_local()


def test_split_trace_matches_decode_split_ranges():
    pt = _table(1, 12)
    tr = at.trace_paged_decode(pt, [96], num_kv_heads=2, page_size=8,
                               num_splits=4)
    ranges = layout.decode_split_ranges(12, 4)
    per_head = {}
    for c in tr.cells:
        per_head.setdefault(c.head, []).append(c)
    for head, cells in per_head.items():
        assert [c.live_logical for c in cells] == \
            [tuple(range(s, e)) for s, e in ranges]
    tr.assert_domain_local()


def test_split_trace_clamps_tail_overhang():
    # 10 pages over 4 splits -> 3 pages a split, last split covers (9, 10):
    # the walk stops at the range's end, so the short tail split copies its
    # one page and nothing past the table.
    pt = _table(1, 10)
    tr = at.trace_paged_decode(pt, [80], num_kv_heads=1, page_size=8,
                               num_splits=4)
    tail = tr.cells[-1]
    assert tail.touched == (pt[0, 9],)
    assert tail.live == (pt[0, 9],)
    assert tail.live_logical == (9,)


def test_window_gates_live_pages():
    pt = _table(1, 8)
    full = at.trace_paged_decode(pt, [64], num_kv_heads=1, page_size=8)
    windowed = at.trace_paged_decode(pt, [64], num_kv_heads=1, page_size=8,
                                     window=16)
    assert full.live_pages == 8
    assert windowed.live_pages == 2   # only the last two pages attend
    assert windowed.touched_pages == 2  # and only their blocks are copied


def test_interleaved_straddle_fails_purity():
    """The tracer agrees with split_ranges_domain_aligned: an identity
    page table under INTERLEAVED straddles exactly when the analytic
    check says a range does."""
    mp, splits, hkv, doms = 8, 2, 2, 2
    pt = np.tile(np.arange(mp), (1, 1))  # logical == physical
    ranges = layout.decode_split_ranges(mp, splits)
    assert not layout.split_ranges_domain_aligned(
        ranges, head=0, policy=layout.INTERLEAVED,
        num_kv_heads=hkv, num_domains=doms)
    tr = at.trace_paged_decode(pt, [mp * 8], num_kv_heads=hkv, page_size=8,
                               num_splits=splits,
                               policy=layout.INTERLEAVED, num_domains=doms)
    with pytest.raises(at.DomainPurityError):
        tr.assert_domain_pure()
    # and HEAD_ALIGNED over the same ranges is certified by both
    assert layout.split_ranges_domain_aligned(
        ranges, head=0, policy=layout.HEAD_ALIGNED,
        num_kv_heads=hkv, num_domains=doms)
    at.trace_paged_decode(pt, [mp * 8], num_kv_heads=hkv, page_size=8,
                          num_splits=splits).assert_domain_local()


def test_pure_but_not_local_is_distinguished():
    # Single-domain interleaved placement: every page in domain 0, but
    # heads 1.. of a 4-head/2-domain grid execute in domain 1.
    pt = np.zeros((1, 4), dtype=np.int64) + 2  # pid 2 -> 2 % 2 == 0
    tr = at.trace_paged_decode(pt * 0 + 2, [32], num_kv_heads=4, page_size=8,
                               policy=layout.INTERLEAVED, num_domains=2)
    tr.assert_domain_pure()   # one domain per cell: pure
    with pytest.raises(at.DomainPurityError):
        tr.assert_domain_local()  # but heads 2,3 read cross-domain


# --- paged prefill ------------------------------------------------------------


def test_prefill_trace_clamps_tail_sweep():
    pt = _table(2, 3)
    tr = at.trace_paged_prefill(pt, [24, 9], num_kv_heads=2, page_size=8,
                                num_tail=2)
    for c in tr.cells:
        b = c.cell[0]
        # 3 prefix steps + 2 tail steps, tail clamped to the last slot
        assert c.touched == tuple(pt[b]) + (pt[b, 2], pt[b, 2])
        live = -(-(24 if b == 0 else 9) // 8)
        assert c.live_logical == tuple(range(live))
    tr.assert_domain_local()


# --- dense split decode -------------------------------------------------------


def test_dense_split_trace_walks_the_partition():
    tr = at.trace_dense_split_decode([300, 100], capacity=512, chunk=64,
                                     num_kv_heads=4, num_splits=4)
    ranges = layout.decode_split_ranges(512 // 64, 4)
    for c in tr.cells:
        b, _, s = c.cell
        start, end = ranges[s]
        length = 300 if b == 0 else 100
        live_chunks = -(-length // 64)
        expect = tuple(p for p in range(start, end) if p < live_chunks)
        assert c.live_logical == expect
    tr.assert_domain_local()


# --- plan-level entry point ---------------------------------------------------


def test_trace_plan_for_a_real_split_plan():
    """The acceptance-bar path: resolve a real paged DECODE plan with
    num_splits > 1 and trace it end to end."""
    shape = (1, 4, 1, 1, 32768, 64)
    plan = plan_lib.plan_attention(
        shape, phase=plan_lib.DECODE, kv_layout=plan_lib.PAGED,
        page_size=32, backend="cpu", dtype_bytes=4, impl="pallas",
    )
    assert plan.num_splits > 1
    assert plan.placement == layout.HEAD_ALIGNED
    mp = 32768 // 32
    pt = _table(1, mp)
    tr = at.trace_plan(plan, pt, [32768], num_kv_heads=1, num_domains=2)
    tr.assert_domain_local()
    assert tr.kernel == "paged_flash_decode_split"
    assert {c.cell[2] for c in tr.cells} == \
        set(range(len(layout.decode_split_ranges(mp, plan.num_splits))))
