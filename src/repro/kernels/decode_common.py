"""Shared decode-kernel arithmetic: chunk relevance + split-state combine.

Both flash-decode kernels (dense ``decode_attention.py`` and paged
``paged_decode_attention.py``) walk the KV axis in fixed-size units — KV
chunks for the dense stripe, blocks of whole pages for the pool — and both
need the same softmax bookkeeping (:func:`accumulate_kv_block`) and:

  * :func:`chunk_relevant` (and its range form :func:`live_block_range`)
    — may a KV unit starting at ``chunk_start``
    contain *any* position the query attends? This gates the whole
    unit's compute (``pl.when``); per-position masking inside the unit
    does the fine trimming. The predicate is exact (sound *and*
    complete): it is True iff at least one position in
    ``[chunk_start, chunk_start + chunk_len)`` is valid under the decode
    mask ``pos < length`` (and ``pos > length - 1 - window`` for sliding
    windows) — property-tested in ``tests/test_decode_relevance.py``.

  * :func:`combine_split_states` — merge per-split partial online-softmax
    states. With split-K decode (PR 4) a new PARALLEL grid axis
    partitions the KV units into ``num_splits`` ranges; each split emits
    its running ``(acc, m, l)`` instead of a normalized output, and this
    second stage rescales every split to the global row max and
    normalizes once. It is a pure vectorized-JAX stage: the state tensor
    is tiny (``B x Hkv x splits x group x D`` floats) next to the KV
    traffic of stage one, so it fuses into the surrounding jit rather
    than warranting its own Mosaic kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def chunk_relevant(chunk_start, chunk_len: int, length, window):
    """True iff the KV unit ``[chunk_start, chunk_start + chunk_len)`` can
    hold a valid key for a decode row of ``length`` live tokens.

    ``chunk_start`` / ``length`` may be traced scalars (the kernels call
    this on grid indices and SMEM lengths); ``chunk_len`` and ``window``
    are Python ints (jit constants). A position ``pos`` is valid when
    ``pos < length`` and, under a sliding window of size W, additionally
    ``pos > length - 1 - W``. The unit holds a valid position iff its
    first position precedes ``length`` and its last position reaches the
    window's left edge.
    """
    relevant = chunk_start < length
    if window is not None and window > 0:
        relevant &= chunk_start + chunk_len - 1 >= length - window
    return relevant


def live_block_range(length, window, block_len: int):
    """``[first, last)``: the blocks of ``block_len`` positions (block ``i``
    starts at ``i * block_len``) for which :func:`chunk_relevant` holds —
    ``last = ceil(length / block_len)`` and, under a sliding window,
    ``first = max(0, length - window) // block_len``. Empty for ``length
    == 0``. ``length`` may be a traced SMEM scalar; the paged decode
    kernel bounds its walk with this."""
    last = (length + block_len - 1) // block_len
    if window is not None and window > 0:
        first = jnp.maximum(length - window, 0) // block_len
    else:
        first = jnp.zeros_like(last)
    return first, last


def accumulate_kv_block(
    q, k, v, acc_ref, m_ref, l_ref,
    *, scale, softcap, window, block_start, block_len: int, length,
    k_scale=None, v_scale=None, native: bool = False,
):
    """One online-softmax step over a KV block, shared by every decode
    kernel body (dense one-pass and split-K, and the paged kernel's one
    walk).

    q: the ``(G, D)`` q block; k/v: the ``(block_len, D)`` KV block, both
    already loaded from VMEM; acc/m/l_ref: VMEM running state ``(G, D)`` /
    ``(G, 128)`` / ``(G, 128)``. ``block_start`` and ``length`` may be
    traced (loop index x block size, SMEM length); ``block_len`` /
    ``window`` / ``scale`` / ``softcap`` are jit constants. Positions at
    or past ``length`` (and outside the sliding window) are masked
    per-element in the scores *and* in ``v``: a dead position may hold
    anything, NaN included (the paged kernel copies the null page into a
    block's dead slots, and ``0 * NaN`` is NaN). The caller skips whole
    blocks with no live position.

    ``k_scale`` / ``v_scale`` are the quantized pools' dequant factors: a
    ``(1, block_len)`` row giving each position its page's ``(head,
    page)`` scale. They multiply the scores' and the
    probabilities' columns, which is the product of dequantized codes
    without widening a ``(block_len, D)`` scale; the 1-byte codes widen to
    fp32 here, in VMEM — HBM streamed only the codes. ``None`` leaves the
    pools untouched.

    ``native`` keeps q, k and v in their own (narrower) dtype for both
    products, which accumulate in float32; the probabilities are rounded
    to v's dtype. Without it every operand is widened to float32 first.
    """
    if not native:
        q = q.astype(jnp.float32)            # (G, D)
        k = k.astype(jnp.float32)            # (block_len, D)
        v = v.astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if k_scale is not None:
        s = s * k_scale
    if softcap is not None and softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    valid = _valid(block_start, (1, block_len), 1, length, window)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = jnp.broadcast_to(
        l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape
    )
    if v_scale is not None:
        p = p * v_scale
    v_valid = _valid(block_start, (block_len, 1), 0, length, window)
    if native:
        v = jnp.where(v_valid, v, jnp.zeros((), v.dtype))
        p = p.astype(v.dtype)
    else:
        v = jnp.where(v_valid, v, 0.0)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _valid(block_start, shape, axis, length, window):
    """The decode mask over one block's positions, laid along ``axis``."""
    pos = block_start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    valid = pos < length
    if window is not None and window > 0:
        valid &= pos > length - 1 - window
    return valid


def combine_split_states(acc, m, l):
    """Merge per-split online-softmax states into the final attention row.

    acc: ``(..., S, G, D)`` unnormalized value accumulators, one per split;
    m, l: ``(..., S, G, 1)`` running row max / normalizer of each split.
    Returns ``(..., G, D)`` float32 — ``sum_s exp(m_s - m*) acc_s`` over
    ``sum_s exp(m_s - m*) l_s`` with ``m* = max_s m_s``.

    Splits that saw no relevant KV carry ``(0, NEG_INF, 0)``: their
    rescale factor underflows to exactly 0 against any live split, and a
    row with *no* live split (length 0) has ``l* == 0`` and emits exact
    zeros — the same guard the one-pass kernels' emit step applies.
    """
    m_star = jnp.max(m, axis=-3, keepdims=True)
    alpha = jnp.exp(m - m_star)
    l_star = jnp.sum(l * alpha, axis=-3)
    acc_star = jnp.sum(acc * alpha, axis=-3)
    return acc_star / jnp.where(l_star == 0.0, 1.0, l_star)
