"""Core layers: norms, rotary embeddings, MLPs, embeddings.

Functional style: every layer is an ``init_*(key, ...) -> params`` plus an
``apply`` function over a plain-dict pytree. No flax dependency — parameters
stack cleanly along a leading axis for ``lax.scan``-over-layers, and
PartitionSpecs attach by tree path (distributed/sharding.py).

Naming convention for sharding rules: weight dict keys end in semantic tags
(``_dm`` model-sharded on dim -1, ``_md`` model-sharded on dim 0, ``_r``
replicated); see ``distributed.sharding.spec_for_path``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def default_dtype():
    return jnp.float32  # params kept in f32; compute dtype set per-model


def normal_init(key, shape, scale) -> jnp.ndarray:
    """N(0, scale**2) params. The barrier keeps a jitted init from folding
    ``scale`` into the normal sampler's own constant, so jitted and
    op-by-op inits agree bitwise."""
    n = jax.random.normal(key, shape, default_dtype())
    return jax.lax.optimization_barrier(n) * scale


# -----------------------------------------------------------------------------
# Norms
# -----------------------------------------------------------------------------


def init_rmsnorm(dim: int) -> dict:
    return {"scale_r": jnp.zeros((dim,), default_dtype())}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # Gemma-style (1 + scale): zero-init is identity.
    return (x * (1.0 + params["scale_r"].astype(jnp.float32))).astype(dtype)


# -----------------------------------------------------------------------------
# Rotary position embeddings
# -----------------------------------------------------------------------------


def rotary_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rotary(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0
) -> jnp.ndarray:
    """x: (B, H, S, D); positions: (B, S) or (S,) absolute positions."""
    d = x.shape[-1]
    freqs = rotary_freqs(d, theta)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, None]  # (B, 1, S, D/2)
    sin = jnp.sin(angles)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -----------------------------------------------------------------------------
# Dense / gated MLP
# -----------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "wi_gate_dm": normal_init(k1, (d_model, d_ff), s_in),
        "wi_up_dm": normal_init(k2, (d_model, d_ff), s_in),
        "wo_md": normal_init(k3, (d_ff, d_model), s_out),
    }


def mlp(params: dict, x: jnp.ndarray, activation: str = "silu") -> jnp.ndarray:
    dtype = x.dtype
    gate = x @ params["wi_gate_dm"].astype(dtype)
    up = x @ params["wi_up_dm"].astype(dtype)
    act = _activate(gate, activation)
    return (act * up) @ params["wo_md"].astype(dtype)


def _activate(x, name: str):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(name)


# -----------------------------------------------------------------------------
# Embedding / unembedding
# -----------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int) -> dict:
    # 1/sqrt(d) keeps tied-unembedding logits O(1) at init.
    scale = d_model**-0.5
    return {
        "table_vd": normal_init(key, (vocab, d_model), scale)
    }


def embed(params: dict, tokens: jnp.ndarray, *, scale_by_dim: bool = False,
          compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    x = jnp.take(params["table_vd"], tokens, axis=0).astype(compute_dtype)
    if scale_by_dim:
        x = x * jnp.asarray(math.sqrt(params["table_vd"].shape[1]), compute_dtype)
    return x


def unembed(params: dict, x: jnp.ndarray, *, softcap: Optional[float] = None
            ) -> jnp.ndarray:
    """Project to vocab logits (tied table). Returns float32 logits."""
    logits = x.astype(jnp.float32) @ params["table_vd"].astype(jnp.float32).T
    if softcap is not None and softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def init_linear(key, d_in: int, d_out: int, tag: str = "dm") -> dict:
    s = 1.0 / math.sqrt(d_in)
    return {f"w_{tag}": normal_init(key, (d_in, d_out), s)}


def linear(params: dict, x: jnp.ndarray, tag: str = "dm") -> jnp.ndarray:
    return x @ params[f"w_{tag}"].astype(x.dtype)


# -----------------------------------------------------------------------------
# Losses
# -----------------------------------------------------------------------------


def softmax_cross_entropy(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    *,
    z_loss: float = 0.0,
) -> Tuple[jnp.ndarray, dict]:
    """Mean token cross-entropy in f32 with optional z-loss regularizer.

    logits: (..., V) f32; targets: (...) int32; mask: (...) 0/1.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss > 0.0:
        nll = nll + z_loss * lse**2
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / denom
    acc = jnp.sum((jnp.argmax(logits, -1) == targets) * mask) / denom
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}
