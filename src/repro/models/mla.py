"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section 2.1).

Per token, with no query compression:

  * ``q = h W_q`` gives every head a ``qk_nope`` part and a ``qk_rope``
    part; ``[c, k_pe] = h W_kv_a``, and ``c`` is RMS-normed;
  * ``[k_nope, v] = c W_kv_b`` per head; rope touches ``q_pe`` and the one
    ``k_pe`` all heads share;
  * ``score = (q_nope . k_nope + q_pe . k_pe) * s``, causal softmax,
    ``o = p v`` per head, then ``W_o``.

Serving never expands keys or values. The paged pool holds one row per
token, ``[c, k_pe]`` (``kv_lora_rank + qk_rope_head_dim`` values, zero-padded
to a multiple of 128 lanes: a narrower minor axis takes that room in HBM
anyway, and the TPU copies only whole 128-lane tiles of it). The per-head
products are absorbed into the query and the output: ``q_lat = W_UK q_nope``
so ``q_lat . c = q_nope . k_nope``, and ``o = W_UV^T (sum p c)``. Attention
is then one shared key per token for every head, and the value is the key's
first ``kv_lora_rank`` columns: the latent kernels
(``paged_latent_decode`` / ``paged_latent_prefill``) read each row once.
Prefill, fresh or extending a cached prefix, runs the same absorbed form,
so there is one cache format. Training (``mla_block``) runs the expanded
form.

Rope (YaRN when ``cfg.mla.yarn`` is set) rotates the two halves of the rope
dims.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import quant as quant_lib
from repro.configs.base import MLAConfig, ModelConfig
from repro.kernels import ops
from repro.kernels import plan as plan_lib
from repro.kernels import ref as ref_mod
from repro.models import layers

#: Lanes the pool's minor axis is padded to.
LANES = 128


def pool_width(a: MLAConfig) -> int:
    """Width of a latent pool row: the latent, padded to whole lanes."""
    return -(-a.latent_dim // LANES) * LANES


def init_mla(key, cfg: ModelConfig) -> dict:
    a, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 4)
    return {
        "wq_dm": layers.normal_init(ks[0], (d, h * a.qk_head_dim), d**-0.5),
        "wkv_a_dm": layers.normal_init(ks[1], (d, a.latent_dim), d**-0.5),
        "kv_norm": layers.init_rmsnorm(a.kv_lora_rank),
        "wkv_b_dm": layers.normal_init(
            ks[2], (a.kv_lora_rank, h * (a.qk_nope_head_dim + a.v_head_dim)),
            a.kv_lora_rank**-0.5),
        "wo_md": layers.normal_init(ks[3], (h * a.v_head_dim, d),
                                    (h * a.v_head_dim) ** -0.5),
    }


@functools.lru_cache(maxsize=None)
def rope_tables(a: MLAConfig) -> Tuple[np.ndarray, float, float]:
    """(inverse frequencies of the rope dims, cos/sin scale, softmax
    scale). With YaRN the frequencies are interpolated, cos/sin scale by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` and the
    softmax scale is ``qk_head_dim^-0.5 * mscale(factor, mscale_all_dim)^2``
    (DeepSeek-V2's ``modeling_deepseek.py``)."""
    dr = a.qk_rope_head_dim
    softmax_scale = a.qk_head_dim ** -0.5
    y = a.yarn
    if y is None:
        return np.asarray(layers.rotary_freqs(dr, a.rope_theta)), 1.0, softmax_scale
    inv = layers.yarn_inv_freq(dr, a.rope_theta, y.factor, y.original_max_position,
                               y.beta_fast, y.beta_slow)
    cs = (layers.yarn_mscale(y.factor, y.mscale)
          / layers.yarn_mscale(y.factor, y.mscale_all_dim))
    if y.mscale_all_dim:
        softmax_scale *= layers.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return inv, cs, softmax_scale


def _rope(x, positions, a: MLAConfig):
    """x: (B, H, S, D) -> roped, positions (B, S) or (S,)."""
    inv, cs, _ = rope_tables(a)
    return layers.apply_rotary_freqs(x, positions, inv, cs)


def _project(params, x, cfg: ModelConfig, positions):
    """x (B, S, D) -> q_nope (B, S, H, dn), roped q_pe (B, S, H, dr) and the
    latent row (B, S, r + dr): the normed ``c`` and the roped ``k_pe``."""
    a = cfg.mla
    b, s, _ = x.shape
    h, dn, r = cfg.n_heads, a.qk_nope_head_dim, a.kv_lora_rank
    q = (x @ params["wq_dm"].astype(x.dtype)).reshape(b, s, h, a.qk_head_dim)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv = x @ params["wkv_a_dm"].astype(x.dtype)
    c = layers.rmsnorm(params["kv_norm"], kv[..., :r])
    q_pe = _rope(q_pe.transpose(0, 2, 1, 3), positions, a).transpose(0, 2, 1, 3)
    k_pe = _rope(kv[:, None, :, r:], positions, a)[:, 0]
    return q_nope, q_pe, jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)


def _kv_b(params, cfg: ModelConfig, dtype):
    """W_kv_b as (r, H, dn + dv): per head, W_UK then W_UV."""
    a = cfg.mla
    return params["wkv_b_dm"].astype(dtype).reshape(
        a.kv_lora_rank, cfg.n_heads, a.qk_nope_head_dim + a.v_head_dim)


def _absorbed_q(params, q_nope, q_pe, cfg: ModelConfig, width: int):
    """[W_UK q_nope, q_pe] per head, zero-padded to ``width``: (B, S, H, W)."""
    a = cfg.mla
    w_uk = _kv_b(params, cfg, q_nope.dtype)[..., : a.qk_nope_head_dim]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
    q = jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)
    return _pad_lanes(q, width)


def _pad_lanes(x, width: int):
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _output(params, o_lat, cfg: ModelConfig, dtype):
    """Per-head latent outputs (B, S, H, r) -> W_UV, heads joined, W_o."""
    a = cfg.mla
    b, s = o_lat.shape[:2]
    w_uv = _kv_b(params, cfg, dtype)[..., a.qk_nope_head_dim:]
    o = jnp.einsum("bshr,rhd->bshd", o_lat.astype(dtype), w_uv)
    return o.reshape(b, s, cfg.n_heads * a.v_head_dim) @ params["wo_md"].astype(dtype)


def mla_block(params, x, cfg: ModelConfig, *, positions=None) -> jnp.ndarray:
    """Full-sequence causal MLA in the expanded form (training)."""
    a = cfg.mla
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q_nope, q_pe, latent = _project(params, x, cfg, positions)
    c, k_pe = latent[..., : a.kv_lora_rank], latent[..., a.kv_lora_rank:]
    kv = jnp.einsum("bsr,rhd->bshd", c, _kv_b(params, cfg, x.dtype))
    k_nope, v = kv[..., : a.qk_nope_head_dim], kv[..., a.qk_nope_head_dim:]
    k_pe = jnp.broadcast_to(k_pe[:, :, None], q_pe.shape)
    q = jnp.concatenate([q_nope, q_pe], axis=-1).transpose(0, 2, 1, 3)
    k = jnp.concatenate([k_nope, k_pe], axis=-1).transpose(0, 2, 1, 3)
    o = ref_mod.attention(q, k, v.transpose(0, 2, 1, 3), causal=True,
                          scale=rope_tables(a)[2])
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * a.v_head_dim)
    return o @ params["wo_md"].astype(x.dtype)


def _latent_plan(cfg: ModelConfig, shape, **kw) -> plan_lib.AttentionPlan:
    return plan_lib.plan_for_config(cfg, shape, kv_layout=plan_lib.PAGED,
                                    latent=True, **kw)


def mla_prefill(
    params, x, cfg: ModelConfig, *, cache_len: int,
    pool: Optional[jnp.ndarray] = None,
    page_table: Optional[jnp.ndarray] = None,
    prefix_len: Optional[jnp.ndarray] = None,
    tail_len: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, dict]:
    """Prefill of a tail ``x`` (B, S, D), over the cached prefix that
    ``pool`` / ``page_table`` / ``prefix_len`` name (None: a fresh prompt),
    absorbed. Returns the output and the tail's latent rows
    ``{"latent": (B, 1, cache_len, W)}`` for the caller to scatter into
    pages."""
    a = cfg.mla
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    width = pool_width(a) if pool is None else pool.shape[-1]
    q_nope, q_pe, latent = _project(params, x, cfg, positions)
    latent = _pad_lanes(latent, width)
    q = _absorbed_q(params, q_nope, q_pe, cfg, width)
    if prefix_len is None:
        prefix_len = jnp.zeros((b,), jnp.int32)
    if tail_len is None:
        tail_len = jnp.full((b,), s, jnp.int32)
    pages = 0 if page_table is None else page_table.shape[1]
    ps = 16 if pool is None else pool.shape[2]
    plan = _latent_plan(cfg, (b, cfg.n_heads, 1, s, pages * ps + s, width),
                        phase=plan_lib.EXTEND, page_size=ps, prefix_pages=pages)
    o_lat = ops.paged_latent_prefill(
        q, pool, page_table, latent, prefix_len, tail_len,
        v_dim=a.kv_lora_rank, scale=rope_tables(a)[2], plan=plan,
    )
    cache = {"latent": jnp.pad(latent[:, None], ((0, 0), (0, 0),
                                                 (0, cache_len - s), (0, 0)))}
    return _output(params, o_lat, cfg, x.dtype), cache


def mla_decode_paged(params, x, cfg: ModelConfig, cache: dict,
                     page_table: jnp.ndarray, lengths: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, dict]:
    """One-token absorbed decode over the paged latent pool: the new row
    goes into the sequence's tail page, then every head attends the pool.
    x: (B, 1, D); cache ``{"latent_pages": (1, P, ps, W)}``; lengths:
    (B,) including the new token (0: an inactive row, whose write sinks
    into the null page)."""
    a = cfg.mla
    pages = cache["latent_pages"]
    b = x.shape[0]
    ps, width = pages.shape[2], pages.shape[3]
    positions = (lengths - 1)[:, None]
    q_nope, q_pe, latent = _project(params, x, cfg, positions)
    idx = jnp.maximum(lengths - 1, 0)
    pids = jnp.take_along_axis(page_table, (idx // ps)[:, None], axis=1)[:, 0]
    pages, _ = quant_lib.append_rows(
        pages, None, _pad_lanes(latent, width)[:, 0][None], pids, idx % ps,
        "fp32",
    )
    q = _absorbed_q(params, q_nope, q_pe, cfg, width)[:, 0]
    plan = _latent_plan(cfg, (b, cfg.n_heads, 1, 1, page_table.shape[1] * ps, width),
                        phase=plan_lib.DECODE, page_size=ps,
                        dtype_bytes=pages.dtype.itemsize)
    o_lat = ops.paged_latent_decode(
        q, pages, page_table, lengths, v_dim=a.kv_lora_rank,
        scale=rope_tables(a)[2], plan=plan,
    )
    return _output(params, o_lat[:, None], cfg, x.dtype), {"latent_pages": pages}


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int, dtype) -> dict:
    """One layer's latent pool: ``(1, P, page_size, W)``, one row a token."""
    return {"latent_pages": jnp.zeros(
        (1, num_pages, page_size, pool_width(cfg.mla)), dtype)}


def latent_bytes_per_token(cfg: ModelConfig) -> Tuple[int, int]:
    """(bytes of one token's latent row a layer, bytes it takes in the
    lane-padded pool)."""
    item = jnp.dtype(cfg.compute_dtype).itemsize
    return cfg.mla.latent_dim * item, pool_width(cfg.mla) * item

