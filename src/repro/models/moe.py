"""Mixture-of-Experts FFN: top-k routing with capacity-bounded scatter dispatch.

TPU-native MoE (Mixtral 8e top-2; Moonlight 64e top-6): static shapes
throughout, no ragged ops. Dispatch is sort-free scatter into per-expert
buffers of capacity ``C = ceil(tokens * top_k / E * capacity_factor)``;
overflow tokens are dropped (their combine weight is zero) — the standard
GShard/Switch discipline.

Expert parallelism: the (E, C, d) dispatch buffer and the expert weights are
sharded on the ``model`` ("expert") axis via sharding constraints injected by
``distributed.sharding.shard_moe`` (a callable threaded through to avoid a
mesh dependency here). Under pjit this lowers to the canonical
all-to-all -> grouped-GEMM -> all-to-all schedule.

Aux losses: load-balance (Switch) + router z-loss, returned for the trainer.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models import layers


def init_moe(key, d_model: int, cfg: MoEConfig) -> dict:
    ks = jax.random.split(key, 5)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(cfg.d_ff)
    e = cfg.num_experts
    p = {
        "router_de": layers.normal_init(ks[0], (d_model, e), s_in),
        # Expert weights: leading expert dim is the EP shard axis.
        "wi_gate_edm": layers.normal_init(ks[1], (e, d_model, cfg.d_ff), s_in),
        "wi_up_edm": layers.normal_init(ks[2], (e, d_model, cfg.d_ff), s_in),
        "wo_emd": layers.normal_init(ks[3], (e, cfg.d_ff, d_model), s_out),
    }
    if cfg.num_shared_experts:
        p["shared"] = layers.init_mlp(
            ks[4], d_model, cfg.d_ff * cfg.num_shared_experts
        )
    return p


def moe_ffn(
    params: dict,
    x: jnp.ndarray,
    cfg: MoEConfig,
    *,
    shard_buffers: Callable[[jnp.ndarray], jnp.ndarray] = lambda t: t,
    capacity: Optional[int] = None,
) -> Tuple[jnp.ndarray, dict]:
    """x: (B, S, D) -> (out (B, S, D), aux {lb_loss, z_loss, ...})."""
    b, s, d = x.shape
    n = b * s
    e, k = cfg.num_experts, cfg.top_k
    xt = x.reshape(n, d)

    # --- Routing (f32 for numerics) ---
    logits = xt.astype(jnp.float32) @ params["router_de"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # (n, e)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)              # (n, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # --- Aux losses ---
    me = jnp.mean(probs, axis=0)                                  # mean prob/expert
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, e, dtype=jnp.float32), axis=1), axis=0
    )                                                             # mean assignment
    lb_loss = e * jnp.sum(me * ce) / k
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # --- Capacity-bounded positions: rank of each (token, slot) within its
    # expert, computed with a cumulative one-hot sum (static shapes).
    if capacity is None:
        capacity = int(math.ceil(n * k / e * cfg.capacity_factor))
        capacity = max(8, min(capacity, n))
    flat_expert = expert_idx.reshape(-1)                          # (n*k,) slot-major? no: token-major
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)      # (n*k, e)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1)              # inclusive -> 0-based
    pos = jnp.take_along_axis(pos_in_expert, flat_expert[:, None], axis=1)[:, 0]
    keep = pos < capacity
    gate_flat = gate_vals.reshape(-1) * keep.astype(jnp.float32)

    # --- Scatter tokens into (E, C, D) buffers ---
    token_idx = jnp.repeat(jnp.arange(n), k)
    slot = jnp.where(keep, flat_expert * capacity + pos, e * capacity)  # drop row
    buf = jnp.zeros((e * capacity + 1, d), xt.dtype)
    buf = buf.at[slot].add(xt[token_idx] * keep[:, None].astype(xt.dtype))
    buf = buf[: e * capacity].reshape(e, capacity, d)
    buf = shard_buffers(buf)

    # --- Expert computation: grouped GEMMs over the expert dim ---
    dt = xt.dtype
    gate_h = jnp.einsum("ecd,edf->ecf", buf, params["wi_gate_edm"].astype(dt))
    up_h = jnp.einsum("ecd,edf->ecf", buf, params["wi_up_edm"].astype(dt))
    h = jax.nn.silu(gate_h) * up_h
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["wo_emd"].astype(dt))
    out_buf = shard_buffers(out_buf)

    # --- Gather back and combine with gate weights ---
    out_flat = out_buf.reshape(e * capacity, d)
    gathered = out_flat[jnp.where(keep, flat_expert * capacity + pos, 0)]
    gathered = gathered * gate_flat[:, None].astype(dt)
    out = jnp.zeros((n, d), dt).at[token_idx].add(gathered)

    if "shared" in params:
        out = out + layers.mlp(params["shared"], xt)

    aux = {
        "moe_lb_loss": lb_loss,
        "moe_z_loss": z_loss,
        "moe_dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return out.reshape(b, s, d), aux
