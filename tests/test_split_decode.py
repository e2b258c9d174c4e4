"""Split-K decode: parity with the one-pass kernels and the oracles.

The PR-4 acceptance sweep: dense + paged split-K decode vs the one-pass
kernels and the ``ref.py`` oracles across ``num_splits in {1, 2, 7}``,
non-divisible split boundaries, sliding window, softcap, GQA/MQA, and
length-0 rows — plus the plan layer's occupancy-driven split choice and
the provable domain alignment of the paged split ranges.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import layout
from repro.kernels import decode_common, ops, ref
from repro.kernels import plan as plan_lib
from repro.kernels.decode_attention import flash_decode
from repro.kernels.paged_decode_attention import paged_flash_decode

from paged_cases import mk_edge_batch


def mk(b, hq, hkv, smax, d, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    kc = jax.random.normal(ks[1], (b, hkv, smax, d), dtype)
    vc = jax.random.normal(ks[2], (b, hkv, smax, d), dtype)
    return q, kc, vc


def mk_paged(b, hq, hkv, d, ps, max_pages, seed=0, dtype=jnp.float32):
    """Random q / head-major pool / shuffled page tables / lengths."""
    rng = np.random.default_rng(seed)
    num_pages = 1 + b * max_pages
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    kp = jax.random.normal(ks[1], (hkv, num_pages, ps, d), dtype)
    vp = jax.random.normal(ks[2], (hkv, num_pages, ps, d), dtype)
    avail = list(rng.permutation(np.arange(1, num_pages)))
    pt = np.zeros((b, max_pages), np.int32)
    lengths = np.zeros((b,), np.int32)
    for i in range(b):
        lengths[i] = rng.integers(1, max_pages * ps + 1)
        live = -(-int(lengths[i]) // ps)
        pt[i, :live] = [avail.pop() for _ in range(live)]
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths)


# --- dense split-K -----------------------------------------------------------


@pytest.mark.parametrize("b,hq,hkv,smax,d,chunk", [
    (2, 8, 2, 1024, 64, 128),     # GQA
    (1, 25, 5, 512, 64, 64),      # hymba-like odd group
    (2, 4, 1, 512, 128, 128),     # MQA (gemma-like)
])
@pytest.mark.parametrize("num_splits", [1, 2, 7])
@pytest.mark.parametrize("window,softcap", [(None, None), (64, 50.0)])
def test_dense_split_parity(b, hq, hkv, smax, d, chunk, num_splits, window,
                            softcap):
    """Split-K output matches the one-pass kernel and both oracles to fp32
    tolerance. num_splits=7 over 8/4 chunks exercises non-divisible
    boundaries (uneven ranges + an empty trailing range)."""
    q, kc, vc = mk(b, hq, hkv, smax, d)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, smax + 1, size=(b,)), jnp.int32
    )
    kw = dict(window=window, softcap=softcap)
    o = flash_decode(q, kc, vc, lengths, chunk=chunk,
                     num_splits=num_splits, interpret=True, **kw)
    o_one = flash_decode(q, kc, vc, lengths, chunk=chunk, interpret=True, **kw)
    o_ref = ref.decode_attention(q, kc, vc, lengths, **kw)
    o_split_ref = ref.split_decode_attention(
        q, kc, vc, lengths, num_splits=num_splits, granule=chunk, **kw
    )
    assert jnp.max(jnp.abs(o - o_one)) < 2e-5
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5
    assert jnp.max(jnp.abs(o - o_split_ref)) < 2e-5


def test_dense_split_length_zero_row_is_zero():
    """A length-0 row has no live split: every partial carries the empty
    (0, -inf, 0) state and the combine's l == 0 guard emits exact zeros."""
    q, kc, vc = mk(3, 8, 2, 512, 64, seed=3)
    lengths = jnp.asarray([0, 17, 512], jnp.int32)
    o = flash_decode(q, kc, vc, lengths, chunk=128, num_splits=2,
                     interpret=True)
    o_ref = ref.decode_attention(q, kc, vc, lengths)
    assert jnp.max(jnp.abs(o[0])) == 0.0
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


def test_dense_split_window_inside_one_split():
    """A window much smaller than a split range: only one split sees
    relevant chunks, the rest must contribute empty states."""
    q, kc, vc = mk(2, 8, 2, 1024, 64, seed=4)
    lengths = jnp.asarray([700, 1024], jnp.int32)
    for window in (8, 100):
        o = flash_decode(q, kc, vc, lengths, window=window, chunk=128,
                         num_splits=4, interpret=True)
        o_ref = ref.decode_attention(q, kc, vc, lengths, window=window)
        assert jnp.max(jnp.abs(o - o_ref)) < 2e-5, window


def test_dense_split_clamps_to_chunk_count():
    """num_splits > chunks degenerates gracefully (one chunk per split)."""
    q, kc, vc = mk(2, 8, 2, 256, 64, seed=5)
    lengths = jnp.asarray([100, 256], jnp.int32)
    o = flash_decode(q, kc, vc, lengths, chunk=128, num_splits=64,
                     interpret=True)
    o_ref = ref.decode_attention(q, kc, vc, lengths)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


# --- paged split-K -----------------------------------------------------------


@pytest.mark.parametrize("b,hq,hkv,d", [
    (2, 8, 2, 64),       # GQA
    (1, 25, 5, 64),      # odd group
    (2, 4, 1, 128),      # MQA
])
@pytest.mark.parametrize("num_splits", [1, 2, 7])
@pytest.mark.parametrize("window,softcap", [(None, None), (24, 50.0)])
def test_paged_split_parity(b, hq, hkv, d, num_splits, window, softcap):
    """Paged split-K vs the one-pass paged kernel and the gather oracle;
    8 pages into 7 splits exercises non-divisible page ranges."""
    q, kp, vp, pt, lengths = mk_paged(b, hq, hkv, d, ps=16, max_pages=8)
    kw = dict(window=window, softcap=softcap)
    o = paged_flash_decode(q, kp, vp, pt, lengths, num_splits=num_splits,
                           interpret=True, **kw)
    o_one = paged_flash_decode(q, kp, vp, pt, lengths, interpret=True, **kw)
    o_ref = ref.paged_decode_attention(q, kp, vp, pt, lengths, **kw)
    assert jnp.max(jnp.abs(o - o_one)) < 2e-5
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


@pytest.mark.parametrize("pages_per_block", [1, 2, 3])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 1), (4, 4)])
@pytest.mark.parametrize("window", [None, 37])
def test_paged_split_block_walk_vs_oracle(pages_per_block, hq, hkv, window):
    """The block-walk edge cases (lengths 0, 1, bk - 1, bk, bk + 1 and the
    whole table in one batch) through three split-K ranges of blocks: the
    splits run the same walk as one pass, cut to their ranges."""
    q, kp, vp, pt, lengths = mk_edge_batch(hq, hkv, 32, pages_per_block)
    o = paged_flash_decode(q, kp, vp, pt, lengths, window=window,
                           num_splits=3, pages_per_block=pages_per_block,
                           interpret=True)
    o_ref = ref.paged_decode_attention(q, kp, vp, pt, lengths, window=window)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5
    assert jnp.max(jnp.abs(o[0])) == 0.0


def test_paged_split_length_zero_row_is_zero():
    q, kp, vp, pt, lengths = mk_paged(3, 8, 2, 64, ps=16, max_pages=6, seed=3)
    lengths = lengths.at[1].set(0)
    o = paged_flash_decode(q, kp, vp, pt, lengths, num_splits=3,
                           interpret=True)
    o_ref = ref.paged_decode_attention(q, kp, vp, pt, lengths)
    assert jnp.max(jnp.abs(o[1])) == 0.0
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


def test_paged_split_matches_dense_split():
    """Same sequences through the paged and dense split kernels (page size
    as the dense chunk), same split count."""
    q, kp, vp, pt, lengths = mk_paged(3, 8, 2, 64, ps=16, max_pages=8, seed=1)
    o_paged = paged_flash_decode(q, kp, vp, pt, lengths, num_splits=3,
                                 interpret=True)
    k_dense = ref.gather_pages(kp, pt)
    v_dense = ref.gather_pages(vp, pt)
    o_dense = flash_decode(q, k_dense, v_dense, lengths, chunk=16,
                           num_splits=3, interpret=True)
    assert jnp.max(jnp.abs(o_paged - o_dense)) < 2e-5


# --- split boundaries: domain alignment --------------------------------------


def test_split_ranges_are_domain_aligned_under_head_major_pool():
    """The kernel's split boundaries (decode_split_ranges) must be provably
    domain-pure under the head-aligned placement the pool uses — for every
    head, split count, and table width — and provably NOT under the naive
    interleaved placement (why the pool is head-major)."""
    for max_pages, num_splits in [(8, 2), (8, 7), (13, 4), (16, 16), (5, 2)]:
        ranges = layout.decode_split_ranges(max_pages, num_splits)
        # page-granular, contiguous, covering
        assert ranges[0][0] == 0 and ranges[-1][1] == max_pages
        for (a0, a1), (b0, b1) in zip(ranges, ranges[1:]):
            assert a1 == b0 and a0 <= a1
        for hkv in (1, 2, 8):
            for h in range(hkv):
                assert layout.split_ranges_domain_aligned(
                    ranges, head=h, policy=layout.HEAD_ALIGNED,
                    num_kv_heads=hkv, num_domains=8,
                )
    wide = layout.decode_split_ranges(8, 2)  # 4-page ranges
    assert not layout.split_ranges_domain_aligned(
        wide, head=0, policy=layout.INTERLEAVED, num_kv_heads=8,
        num_domains=8,
    )


# --- plan-driven dispatch ----------------------------------------------------


def test_plan_chooses_splits_by_occupancy():
    """The occupancy model splits exactly when cells x splits can cover
    idle domains at long context, and never at high occupancy."""
    # B x Hkv = 1 on the 2-domain megacore topology, 32k context: split.
    lonely = plan_lib.plan_attention(
        (1, 4, 1, 1, 32768, 64), phase=plan_lib.DECODE, backend="cpu",
        dtype_bytes=4,
    )
    assert lonely.num_splits > 1
    # A full batch (cells >> domains): one pass.
    busy = plan_lib.plan_attention(
        (8, 8, 2, 1, 2048, 64), phase=plan_lib.DECODE, backend="cpu",
    )
    assert busy.num_splits == 1
    # Paged plans pick splits too (page granule), at B*Hkv < domains.
    paged = plan_lib.plan_attention(
        (1, 32, 4, 1, 32768, 128), phase=plan_lib.DECODE,
        kv_layout=plan_lib.PAGED, page_size=64, backend="gpu",
    )
    assert paged.num_splits > 1
    # Non-decode phases never split.
    assert plan_lib.plan_attention((2, 8, 2, 512, 512, 64)).num_splits == 1


def test_ops_decode_executes_plan_num_splits():
    """ops.decode_attention / paged_decode_attention run whatever split
    count rides the plan and stay parity-clean — no call-site changes."""
    q, kc, vc = mk(2, 8, 2, 512, 64, seed=6)
    lengths = jnp.asarray([100, 300], jnp.int32)
    base = plan_lib.plan_attention(
        (2, 8, 2, 1, 512, 64), phase=plan_lib.DECODE, backend="cpu",
        impl="pallas",
    )
    split_plan = dataclasses.replace(base, num_splits=3)
    o = ops.decode_attention(q, kc, vc, lengths, plan=split_plan)
    o_ref = ref.decode_attention(q, kc, vc, lengths)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5

    q2, kp, vp, pt, lengths2 = mk_paged(2, 8, 2, 64, ps=16, max_pages=6,
                                        seed=7)
    pbase = plan_lib.plan_attention(
        (2, 8, 2, 1, 96, 64), phase=plan_lib.DECODE,
        kv_layout=plan_lib.PAGED, page_size=16, backend="cpu", impl="pallas",
    )
    psplit = dataclasses.replace(pbase, num_splits=2)
    o2 = ops.paged_decode_attention(q2, kp, vp, pt, lengths2, plan=psplit)
    o2_ref = ref.paged_decode_attention(q2, kp, vp, pt, lengths2)
    assert jnp.max(jnp.abs(o2 - o2_ref)) < 2e-5


def test_split_estimate_charges_combine_overhead():
    """estimate_decode_splits: the combine cost is explicit — at short
    context the launch overhead outweighs the occupancy win and the model
    keeps one pass even at B x Hkv = 1."""
    from repro.core import numa, perf_model

    short = perf_model.estimate_decode_splits(
        batch=1, num_q_heads=4, num_kv_heads=1, seq_kv=1024, granule=128,
        head_dim=64, dtype_bytes=2, topo=numa.TPU_V5P_MEGACORE,
    )
    assert short.num_splits == 1
    long = perf_model.estimate_decode_splits(
        batch=1, num_q_heads=4, num_kv_heads=1, seq_kv=131072, granule=128,
        head_dim=64, dtype_bytes=2, topo=numa.TPU_V5P_MEGACORE,
    )
    assert long.num_splits > 1 and long.speedup > 1.0
    assert long.times[0][1] == long.base_time
    # With all domains already covered, splitting never wins.
    full = perf_model.estimate_decode_splits(
        batch=16, num_q_heads=32, num_kv_heads=8, seq_kv=131072, granule=128,
        head_dim=128, dtype_bytes=2, topo=numa.MI300X,
    )
    assert full.num_splits == 1


def test_combine_split_states_empty_and_all_empty():
    """The shared combine: empty splits vanish, all-empty rows emit zeros."""
    g, d = 8, 16
    acc = jnp.zeros((2, 3, g, d))
    m = jnp.full((2, 3, g, 1), decode_common.NEG_INF)
    l = jnp.zeros((2, 3, g, 1))
    # row 0: split 1 live, others empty; row 1: all empty.
    acc = acc.at[0, 1].set(2.0)
    m = m.at[0, 1].set(0.5)
    l = l.at[0, 1].set(2.0)
    out = decode_common.combine_split_states(acc, m, l)
    assert jnp.allclose(out[0], 1.0)       # 2.0 / 2.0, empties contribute 0
    assert jnp.max(jnp.abs(out[1])) == 0.0  # l* == 0 guard
