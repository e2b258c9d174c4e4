"""Shared cases for the paged decode kernel's tests (one-pass, split-K,
quantized pools): a batch whose lengths sit on the block walk's edges."""

import jax
import jax.numpy as jnp
import numpy as np

PS, MAX_PAGES = 16, 8


def mk_edge_batch(hq, hkv, d, pages_per_block, seed=0):
    """One batch whose lengths sit on the block walk's edges: 0, 1, bk - 1,
    bk, bk + 1 and the whole table (bk = pages_per_block * page size).
    Every row owns distinct pages in shuffled order; dead table entries
    hold the null page 0."""
    bk = pages_per_block * PS
    cap = MAX_PAGES * PS
    lengths = np.asarray([0, 1, bk - 1, bk, min(bk + 1, cap), cap], np.int32)
    b = len(lengths)
    rng = np.random.default_rng(seed)
    num_pages = 1 + b * MAX_PAGES
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    kp = jax.random.normal(ks[1], (hkv, num_pages, PS, d), jnp.float32)
    vp = jax.random.normal(ks[2], (hkv, num_pages, PS, d), jnp.float32)
    avail = list(rng.permutation(np.arange(1, num_pages)))
    pt = np.zeros((b, MAX_PAGES), np.int32)
    for i, n in enumerate(lengths):
        live = -(-int(n) // PS)
        pt[i, :live] = [avail.pop() for _ in range(live)]
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths)
