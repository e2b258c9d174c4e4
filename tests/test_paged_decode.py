"""Paged flash-decode kernel vs dense flash-decode and the pure-JAX oracle.

All kernel runs are interpret-mode (CPU CI); the page tables are random
permutations of the physical pool with shared prefix pages between rows, so
the page-indexed BlockSpec index map is exercised out of logical order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.paged_decode_attention import paged_flash_decode

from paged_cases import mk_edge_batch


def mk_paged(b, hq, hkv, d, ps, max_pages, seed=0, num_pages=None,
             shared_pages=0, dtype=jnp.float32):
    """Random q/page-pool/table/lengths. Rows share the first
    ``shared_pages`` physical pages (prefix sharing); the rest are a
    shuffled disjoint allocation. Lengths are random, >= shared prefix."""
    rng = np.random.default_rng(seed)
    num_pages = num_pages or (1 + shared_pages + b * max_pages)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k_pages = jax.random.normal(ks[1], (hkv, num_pages, ps, d), dtype)
    v_pages = jax.random.normal(ks[2], (hkv, num_pages, ps, d), dtype)
    avail = list(rng.permutation(np.arange(1 + shared_pages, num_pages)))
    pt = np.zeros((b, max_pages), np.int32)
    lengths = np.zeros((b,), np.int32)
    for i in range(b):
        lo = max(shared_pages * ps, 1)
        lengths[i] = rng.integers(lo, max_pages * ps + 1)
        live = -(-int(lengths[i]) // ps)
        row = list(range(1, 1 + min(shared_pages, live)))
        row += [avail.pop() for _ in range(live - len(row))]
        pt[i, :live] = row
    return q, k_pages, v_pages, jnp.asarray(pt), jnp.asarray(lengths)


@pytest.mark.parametrize("b,hq,hkv,d", [
    (2, 8, 2, 64),       # GQA
    (3, 4, 4, 32),       # MHA
    (1, 25, 5, 64),      # odd group (hymba-like)
    (2, 4, 1, 128),      # MQA (gemma-like)
])
@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("softcap", [None, 50.0])
def test_paged_vs_oracle(b, hq, hkv, d, window, softcap):
    q, kp, vp, pt, lengths = mk_paged(b, hq, hkv, d, ps=16, max_pages=6,
                                      shared_pages=2)
    o = paged_flash_decode(q, kp, vp, pt, lengths, window=window,
                           softcap=softcap, interpret=True)
    o_ref = ref.paged_decode_attention(q, kp, vp, pt, lengths, window=window,
                                       softcap=softcap)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


def test_paged_matches_dense_flash_decode():
    """Same sequences through the paged and the dense kernels."""
    q, kp, vp, pt, lengths = mk_paged(3, 8, 2, 64, ps=16, max_pages=8, seed=1)
    o_paged = paged_flash_decode(q, kp, vp, pt, lengths, interpret=True)
    k_dense = ref.gather_pages(kp, pt)
    v_dense = ref.gather_pages(vp, pt)
    o_dense = flash_decode(q, k_dense, v_dense, lengths, chunk=32,
                           interpret=True)
    assert jnp.max(jnp.abs(o_paged - o_dense)) < 2e-5


def test_paged_ignores_dead_table_entries():
    """Entries past a sequence's live pages (null-page padded) and data in
    unreferenced physical pages must not leak into the output."""
    q, kp, vp, pt, lengths = mk_paged(2, 4, 2, 32, ps=16, max_pages=4, seed=2)
    o1 = paged_flash_decode(q, kp, vp, pt, lengths, interpret=True)
    # Poison the null page and every unreferenced page.
    live = set()
    ptn = np.asarray(pt)
    for i, L in enumerate(np.asarray(lengths)):
        live |= set(ptn[i, : -(-int(L) // 16)].tolist())
    poison = jnp.asarray(
        [1e6 if p not in live else 0.0 for p in range(kp.shape[1])],
        kp.dtype,
    )[None, :, None, None]
    o2 = paged_flash_decode(q, kp + poison, vp + poison, pt, lengths,
                            interpret=True)
    assert jnp.max(jnp.abs(o1 - o2)) == 0.0


def test_paged_length_zero_row_is_zero():
    q, kp, vp, pt, lengths = mk_paged(3, 8, 2, 64, ps=16, max_pages=4, seed=3)
    lengths = lengths.at[1].set(0)
    o = paged_flash_decode(q, kp, vp, pt, lengths, interpret=True)
    o_ref = ref.paged_decode_attention(q, kp, vp, pt, lengths)
    assert jnp.max(jnp.abs(o[1])) == 0.0
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


def test_paged_shared_prefix_rows_agree():
    """Two rows with identical page tables and lengths produce identical
    outputs for identical queries — the physical sharing is transparent."""
    b, hq, hkv, d, ps = 2, 4, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q1 = jax.random.normal(ks[0], (1, hq, d), jnp.float32)
    q = jnp.concatenate([q1, q1], axis=0)
    kp = jax.random.normal(ks[1], (hkv, 8, ps, d), jnp.float32)
    vp = jax.random.normal(ks[2], (hkv, 8, ps, d), jnp.float32)
    pt = jnp.asarray([[3, 5, 0, 0], [3, 5, 0, 0]], jnp.int32)
    lengths = jnp.asarray([28, 28], jnp.int32)
    o = paged_flash_decode(q, kp, vp, pt, lengths, interpret=True)
    assert jnp.max(jnp.abs(o[0] - o[1])) == 0.0


def test_ops_paged_dispatch():
    q, kp, vp, pt, lengths = mk_paged(2, 8, 2, 64, ps=16, max_pages=4, seed=4)
    o1 = ops.paged_decode_attention(q, kp, vp, pt, lengths, impl="pallas")
    o2 = ops.paged_decode_attention(q, kp, vp, pt, lengths, impl="xla")
    assert jnp.max(jnp.abs(o1 - o2)) < 2e-5
    with pytest.raises(ValueError):
        ops.paged_decode_attention(q, kp, vp, pt, lengths, impl="nope")


def test_page_size_must_be_sublane_multiple():
    q = jnp.zeros((1, 4, 32))
    kp = jnp.zeros((2, 4, 12, 32))  # page_size 12: not a multiple of 8
    pt = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError):
        paged_flash_decode(q, kp, kp, pt, jnp.asarray([5], jnp.int32),
                           interpret=True)


# --- the block walk ------------------------------------------------------------

# 3 divides neither the 8-page table nor a power of two: the tail block
# overhangs the table.
BLOCKS = [1, 3, 4, 8]
HEADS = [(8, 2), (4, 1), (4, 4)]   # GQA, MQA, MHA


@pytest.mark.parametrize("pages_per_block", BLOCKS)
@pytest.mark.parametrize("hq,hkv", HEADS)
@pytest.mark.parametrize("window", [None, 37])
def test_block_walk_vs_oracle(pages_per_block, hq, hkv, window):
    """Lengths on every block edge in one batch, against the oracle; the
    window of 37 puts its left edge inside a page and inside a block."""
    q, kp, vp, pt, lengths = mk_edge_batch(hq, hkv, 32, pages_per_block)
    o = paged_flash_decode(q, kp, vp, pt, lengths, window=window,
                           pages_per_block=pages_per_block, interpret=True)
    o_ref = ref.paged_decode_attention(q, kp, vp, pt, lengths, window=window)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5
    assert jnp.max(jnp.abs(o[0])) == 0.0          # the length-0 row


@pytest.mark.parametrize("pages_per_block", [1, 3, 8])
def test_block_walk_ignores_nan_in_dead_pages(pages_per_block):
    """NaN in the null page and in every page no row references: the walk
    never copies them, and masks what its block buffer holds past a row's
    live pages, so the output is finite and equal to the clean run."""
    q, kp, vp, pt, lengths = mk_edge_batch(8, 2, 32, pages_per_block, seed=5)
    clean = paged_flash_decode(q, kp, vp, pt, lengths,
                               pages_per_block=pages_per_block, interpret=True)
    referenced = set(np.asarray(pt)[np.asarray(pt) > 0].tolist())
    dead = np.asarray([p not in referenced for p in range(kp.shape[1])])
    poison = jnp.where(jnp.asarray(dead)[None, :, None, None], jnp.nan, 0.0)
    o = paged_flash_decode(q, kp + poison, vp + poison, pt, lengths,
                           pages_per_block=pages_per_block, interpret=True)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert jnp.max(jnp.abs(o - clean)) == 0.0
