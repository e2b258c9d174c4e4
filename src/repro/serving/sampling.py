"""On-device batched sampling for the serving engine.

One jitted call samples every decode row of a tick at once, with
*per-row* sampling params (the pre-PR-5 engines sampled on the host, one
row at a time, greedy-or-temperature only):

  * ``temperature == 0`` rows take the exact ``argmax`` branch — bitwise
    identical to the host ``np.argmax`` the old engines used, which is
    what keeps the facade's greedy outputs bit-matching the pre-refactor
    engines;
  * ``temperature > 0`` rows are softmax-sampled after top-k and top-p
    (nucleus) filtering. Top-p keeps the smallest prefix of the sorted
    distribution whose mass reaches ``top_p`` (the boundary token that
    crosses the mass is included; ties at the cutoff probability are all
    kept);
  * the RNG is keyed **per request**, not per tick: row key =
    ``fold_in(fold_in(PRNGKey(seed), stream_pos), codebook)`` where
    ``stream_pos`` is how many tokens the request has generated so far.
    A request's sample stream therefore does not depend on which batch
    rows it shares a tick with, and resume-after-preemption continues the
    stream exactly.

Multi-codebook (MusicGen-style) logits ``(B, K, V)`` sample one token per
codebook with a codebook-distinct key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Floor applied to positive temperatures only (temperature == 0 never
#: reaches the stochastic branch); keeps the scale finite under jit.
_MIN_TEMPERATURE = 1e-6


def _sample_one(logits, temperature, top_k, top_p, key):
    """Sample one token from one row's ``(V,)`` logits."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    scaled = logits.astype(jnp.float32) / jnp.maximum(
        temperature, _MIN_TEMPERATURE
    )
    # Top-k: keep the k largest logits (0 disables). The threshold is the
    # k-th largest value; ties with it survive.
    k = jnp.where(top_k <= 0, v, jnp.clip(top_k, 1, v))
    desc = jnp.sort(scaled)[::-1]
    kth = desc[jnp.clip(k - 1, 0, v - 1)]
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)
    # Top-p over the k-filtered distribution: walking the sorted probs,
    # a token is kept while the mass *before* it is < top_p — the smallest
    # set whose mass reaches top_p, boundary token included.
    probs = jax.nn.softmax(masked)
    p_desc = jnp.sort(probs)[::-1]
    csum = jnp.cumsum(p_desc)
    keep = (csum - p_desc) < top_p
    cutoff = p_desc[jnp.maximum(jnp.sum(keep) - 1, 0)]
    masked = jnp.where(probs < cutoff, -jnp.inf, masked)

    sampled = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(temperature <= 0, greedy, sampled)


@jax.jit
def _sample_batch(logits, temperature, top_k, top_p, seed, stream_pos):
    """Batched sampler: ``logits`` ``(B, V)`` or ``(B, K, V)`` ->
    ``(B,)`` / ``(B, K)`` int32 tokens. All param arrays are ``(B,)``."""

    def row_key(s, pos):
        return jax.random.fold_in(jax.random.PRNGKey(s), pos)

    keys = jax.vmap(row_key)(seed, stream_pos)
    if logits.ndim == 2:
        return jax.vmap(_sample_one)(logits, temperature, top_k, top_p, keys)

    b, num_codebooks, _ = logits.shape

    def row(lg, t, k, p, key):
        cb_keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            key, jnp.arange(num_codebooks)
        )
        return jax.vmap(_sample_one, in_axes=(0, None, None, None, 0))(
            lg, t, k, p, cb_keys
        )

    return jax.vmap(row)(logits, temperature, top_k, top_p, keys)


def sample_tokens(logits, temperature, top_k, top_p, seed, stream_pos):
    """Sample next tokens for a batch of rows with per-row params.

    ``logits``: ``(B, V)`` float (or ``(B, K, V)`` multi-codebook).
    ``temperature``/``top_p``: ``(B,)`` float; ``top_k``/``seed``/
    ``stream_pos``: ``(B,)`` int. Returns int32 ``(B,)`` (or ``(B, K)``).
    Rows with ``temperature <= 0`` are exact argmax and consume no
    randomness.

    The row count is padded to a power of two (greedy rows of zeros,
    dropped from the result), so a caller whose batch varies, as the
    engine's first-token sample after each flush does, compiles one
    program per bucket, not one per count. Rows are independent, so the
    padding changes no row's token.
    """
    rows = np.shape(logits)[0]
    bucket = 1 << max(rows - 1, 0).bit_length()
    args = [
        _pad_rows(a, dt, bucket) for a, dt in (
            (logits, None), (temperature, np.float32), (top_k, np.int32),
            (top_p, np.float32), (seed, np.int32), (stream_pos, np.int32))
    ]
    out = _sample_batch(*(jnp.asarray(a) for a in args))
    return out if bucket == rows else out[:rows]


def _pad_rows(x, dtype, rows):
    """``x`` as ``dtype`` (its own when None) with zero rows appended up
    to ``rows``: on the host for host arrays, so the padding compiles
    nothing."""
    if isinstance(x, jax.Array):
        x = x if dtype is None else x.astype(dtype)
        pad = jnp.pad
    else:
        x = np.asarray(x, dtype)
        pad = np.pad
    extra = rows - x.shape[0]
    if not extra:
        return x
    return pad(x, [(0, extra)] + [(0, 0)] * (x.ndim - 1))
