"""The plain reference: the entry point and the helpers a family's forward
is written with.

Each family (``bench/families/<name>.py``) writes its model's forward from
the published description in straightforward ``jax.numpy`` float32, with no
kernel, cache or batching, and imports nothing of the program. ``logits``
pads and blocks the positions and runs that forward under
``jax.default_matmul_precision("highest")``, so that float32 matmuls stay
float32 on a TPU.

Departures, shared with the configuration as run (see its file): RMSNorm
multiplies by (1 + scale) with eps 1e-6; a sliding window W lets query i
see keys [i - W + 1, i].

``quantized=True`` gives the control: every matmul input (weights per
tensor, activations per row) rounded to float8 e4m3 with an absmax scale,
the precision step below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import registry

EPS = 1e-6
#: Query rows per attention block: bounds the score tensor at
#: heads x BLOCK x padded length floats.
BLOCK = 256
#: Sequences are padded to a multiple of this (causal, so padding at the
#: end changes no earlier position) to bound the number of compilations.
PAD = 512
F8_MAX = 448.0


def _q8(x, axis=None):
    """Round to float8 e4m3 with an absmax scale (per tensor, or per slice
    along ``axis``)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, quantized):
    if quantized:
        a, w = _q8(a, axis=-1), _q8(w)
    return a @ w


def _rmsnorm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + scale)


def _rope(x, pos, theta):
    """x: (S, H, D); rotate the two halves of each head."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, window, quantized):
    """q: (S, Hq, D); k, v: (S, Hkv, D); causal, optionally windowed, in
    blocks of query rows."""
    s, hq, d = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    if quantized:
        k, v = _q8(k, axis=-1), _q8(v, axis=-1)
    cols = jnp.arange(s)
    kt = k.transpose(1, 2, 0)            # (H, D, S)
    vh = v.transpose(1, 0, 2)            # (H, S, D)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, 0)
        if quantized:
            qb = _q8(qb, axis=-1)
        rows = i * BLOCK + jnp.arange(BLOCK)
        sc = jnp.einsum("bhd,hds->hbs", qb, kt) / jnp.sqrt(jnp.float32(d))
        mask = cols[None, :] <= rows[:, None]
        if window is not None:
            mask &= cols[None, :] > rows[:, None] - window
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if quantized:
            p = _q8(p, axis=-1)
        return jnp.einsum("hbs,hsd->bhd", p, vh)

    out = jax.lax.map(block, jnp.arange(s // BLOCK))
    return out.reshape(s, hq, d)


def logits(params, conf: Dict, tokens: np.ndarray, score_pos: np.ndarray,
           quantized: bool = False, family: Optional[ModuleType] = None
           ) -> np.ndarray:
    """Reference logits (len(score_pos), vocab) of ``tokens`` at
    ``score_pos``: the logits each position gives for the token after it,
    from ``family`` (by default the one ``conf`` names in this checkout)."""
    if family is None:
        family = registry.load_family(conf)
    n, m = len(tokens), len(score_pos)
    padded = np.zeros((-(-n // PAD) * PAD,), np.int32)
    padded[:n] = tokens
    pos = np.full((-(-m // BLOCK) * BLOCK,), score_pos[-1], np.int32)
    pos[:m] = score_pos
    with jax.default_matmul_precision("highest"):
        out = family.forward(params, jnp.asarray(padded), jnp.asarray(pos),
                             conf, quantized)
    return np.asarray(out)[:m]


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap by which a token's reference logit lies below the
    reference's best at its position."""
    picked = np.take_along_axis(ref_logits, np.asarray(tokens)[:, None], axis=1)[:, 0]
    return float(np.max(ref_logits.max(axis=1) - picked))
