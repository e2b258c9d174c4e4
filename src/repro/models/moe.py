"""Mixture-of-Experts FFN: top-k routing, capacity-bounded or dropless.

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

Serving runs :func:`moe_ffn_dropless`: no token is ever dropped, so a row's
output never depends on the rows it is batched with. A device may hold only
some experts (``MoEConfig.held_start`` / ``num_held``: one device's share of
an expert-parallel deployment); the router still scores every expert, and
the layer adds only its held experts' part. Every held expert runs on every
token, weighted by its combine weight (zero where the token did not pick
it): exact, and bound by the expert weights' bytes at decode batch sizes.
Each token's routed slots that land on held experts are counted
(``moe_held_slots``).
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
    e = cfg.held
    p = {
        "router_de": layers.normal_init(ks[0], (d_model, cfg.num_experts), s_in),
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
    if cfg.held != e:
        raise ValueError("the capacity route holds every expert; "
                         "a device's held share is served dropless")
    gate_vals = _gates(gate_vals, cfg)

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
        "moe_held_slots": _held_slots(expert_idx, cfg),
    }
    return out.reshape(b, s, d), aux


def _gates(top_probs, cfg: MoEConfig):
    """Combine weights of the top-k slots: renormalised over the k when
    ``norm_topk_prob``, times ``routed_scaling_factor``."""
    if cfg.norm_topk_prob:
        top_probs = top_probs / jnp.maximum(
            jnp.sum(top_probs, axis=-1, keepdims=True), 1e-9)
    return top_probs * cfg.routed_scaling_factor


def _held_hits(expert_idx, cfg: MoEConfig):
    """(n, k, held) one-hot of each routed slot over the held experts."""
    held = cfg.held_start + jnp.arange(cfg.held)
    return expert_idx[..., None] == held


def _held_slots(expert_idx, cfg: MoEConfig):
    """(n,) int32: each token's routed slots that land on held experts."""
    return jnp.sum(_held_hits(expert_idx, cfg), axis=(1, 2), dtype=jnp.int32)


def _router(params, xt, cfg: MoEConfig):
    """float32 logits, softmax scores and the greedy top-k."""
    logits = xt.astype(jnp.float32) @ params["router_de"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)
    return logits, probs, gate_vals, expert_idx


def moe_ffn_dropless(params: dict, x: jnp.ndarray, cfg: MoEConfig
                     ) -> Tuple[jnp.ndarray, dict]:
    """x: (B, S, D) -> (out (B, S, D), aux). Routes over all
    ``cfg.num_experts`` and adds ``sum_{k: e_k held} w_k E_{e_k}(x)`` plus the
    shared experts: every held expert runs on every token, weighted by its
    combine weight, so nothing is dropped at any batch size."""
    b, s, d = x.shape
    n = b * s
    xt = x.reshape(n, d)
    logits, probs, gate_vals, expert_idx = _router(params, xt, cfg)
    hits = _held_hits(expert_idx, cfg)                            # (n, k, h)
    combine = jnp.sum(jnp.where(hits, _gates(gate_vals, cfg)[..., None], 0.0),
                      axis=1)                                     # (n, h)
    dt = xt.dtype
    gate_h = jnp.einsum("nd,edf->enf", xt, params["wi_gate_edm"].astype(dt))
    up_h = jnp.einsum("nd,edf->enf", xt, params["wi_up_edm"].astype(dt))
    h = jax.nn.silu(gate_h) * up_h * combine.T[..., None].astype(dt)
    out = jnp.einsum("enf,efd->nd", h, params["wo_emd"].astype(dt))
    if "shared" in params:
        out = out + layers.mlp(params["shared"], xt)
    e, k = cfg.num_experts, cfg.top_k
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(expert_idx, e, dtype=jnp.float32),
                          axis=1), axis=0)
    aux = {
        "moe_lb_loss": e * jnp.sum(jnp.mean(probs, axis=0) * ce) / k,
        "moe_z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "moe_dropped_frac": jnp.zeros((), jnp.float32),
        "moe_held_slots": jnp.sum(hits, axis=(1, 2), dtype=jnp.int32),
    }
    return out.reshape(b, s, d), aux
