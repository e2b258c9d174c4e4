"""Attention blocks: GQA self-attention, cross-attention, decode paths.

The kernel-facing layer of the model stack. The paper's technique enters in
two ways:
  * the :class:`~repro.kernels.plan.AttentionPlan` handed to the
    ``kernels.ops`` entry points (grid order / KV residency / megacore
    semantics / kernel impl) — resolved here via ``plan_for_config``, the
    only place the config's schedule policy is read,
  * head layout: q/k/v projections emit heads in ACC-contiguous order so the
    model-axis shard boundaries coincide with KV groups
    (``core.placement.ACC_ALIGNED``) — KV is never duplicated across shards.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.cache import quant as quant_lib
from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels import ops
from repro.kernels import plan as plan_lib
from repro.models import layers


def init_attention(key, cfg: ModelConfig) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    so = 1.0 / math.sqrt(h * hd)
    p = {
        "wq_dm": layers.normal_init(ks[0], (d, h * hd), s),
        "wk_dm": layers.normal_init(ks[1], (d, hkv * hd), s),
        "wv_dm": layers.normal_init(ks[2], (d, hkv * hd), s),
        "wo_md": layers.normal_init(ks[3], (h * hd, d), so),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.init_rmsnorm(hd)
        p["k_norm"] = layers.init_rmsnorm(hd)
    return p


def _plan(cfg: ModelConfig, shape, *, phase, window=None, kv_layout=plan_lib.DENSE,
          page_size=None, prefix_pages=0, dtype_bytes=None,
          kv_dtype="fp32") -> plan_lib.AttentionPlan:
    """The layer's attention plan: schedule + impl for this call shape,
    resolved (and LRU-cached) by the plan layer from the config policy."""
    return plan_lib.plan_for_config(
        cfg, shape, phase=phase, window=window, kv_layout=kv_layout,
        page_size=page_size, prefix_pages=prefix_pages, dtype_bytes=dtype_bytes,
        kv_dtype=kv_dtype,
    )


def _project_qkv(params, x, cfg: ModelConfig, positions, rope_theta, kv_x=None,
                 rope: bool = True):
    """x: (B, S, D) -> q (B,H,S,hd), k/v (B,Hkv,Skv,hd)."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if kv_x is None else kv_x
    skv = src.shape[1]
    q = (x @ params["wq_dm"].astype(x.dtype)).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = (src @ params["wk_dm"].astype(x.dtype)).reshape(b, skv, hkv, hd).transpose(0, 2, 1, 3)
    v = (src @ params["wv_dm"].astype(x.dtype)).reshape(b, skv, hkv, hd).transpose(0, 2, 1, 3)
    if cfg.head_placement == "striped":
        # Naive round-robin head placement (paper baseline): the physical
        # head order emitted by the (sharded) projections is striped across
        # model shards, so regrouping into logical ACC order moves Q and K/V
        # across shards — the pod-scale analogue of the fragmented L2. The
        # permutation gathers land as collectives in the compiled HLO;
        # benchmarks/roofline A/Bs this against acc_aligned.
        from repro.core import placement as placement_lib

        plan = placement_lib.plan(
            h, hkv, cfg.placement_shards, placement_lib.STRIPED
        )
        q = jnp.take(q, jnp.asarray(plan.q_perm), axis=1)
        k = jnp.take(k, jnp.asarray(plan.kv_perm), axis=1)
        v = jnp.take(v, jnp.asarray(plan.kv_perm), axis=1)
    if cfg.qk_norm:
        q = layers.rmsnorm(params["q_norm"], q)
        k = layers.rmsnorm(params["k_norm"], k)
    if rope:
        q = layers.apply_rotary(q, positions, rope_theta)
        k = layers.apply_rotary(k, positions, rope_theta)
    return q, k, v


def attention_block(
    params: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    spec: LayerSpec,
    *,
    positions: Optional[jnp.ndarray] = None,
    encoder_states: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence self- (or cross-) attention. x: (B, S, D)."""
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    cross = spec.cross_attn and encoder_states is not None
    q, k, v = _project_qkv(
        params, x, cfg, positions, spec.rope_theta,
        kv_x=encoder_states if cross else None,
        rope=not cross,
    )
    window = None if cross else spec.window
    plan = _plan(
        cfg, (b, cfg.n_heads, k.shape[1], s, k.shape[2], cfg.head_dim),
        phase=plan_lib.PREFILL, window=window, dtype_bytes=q.dtype.itemsize,
    )
    o = ops.flash_attention(
        q, k, v,
        causal=not cross,
        window=window,
        softcap=cfg.attn_softcap,
        plan=plan,
        chunk_unroll=cfg.attn_chunk_unroll,
    )
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    return o @ params["wo_md"].astype(x.dtype)


def attention_prefill(
    params, x, cfg: ModelConfig, spec: LayerSpec, *, cache_len: int,
    positions=None, encoder_states=None,
) -> Tuple[jnp.ndarray, dict]:
    """Like attention_block but also returns the populated KV cache
    (padded to ``cache_len``) for subsequent decode steps."""
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    cross = spec.cross_attn and encoder_states is not None
    q, k, v = _project_qkv(
        params, x, cfg, positions, spec.rope_theta,
        kv_x=encoder_states if cross else None, rope=not cross,
    )
    window = None if cross else spec.window
    plan = _plan(
        cfg, (b, cfg.n_heads, k.shape[1], s, k.shape[2], cfg.head_dim),
        phase=plan_lib.PREFILL, window=window, dtype_bytes=q.dtype.itemsize,
    )
    o = ops.flash_attention(
        q, k, v, causal=not cross, window=window,
        softcap=cfg.attn_softcap, plan=plan,
        chunk_unroll=cfg.attn_chunk_unroll,
    )
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    pad = cache_len - k.shape[2]
    cache = {
        "k": jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))),
        "v": jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))),
    }
    return o @ params["wo_md"].astype(x.dtype), cache


def attention_prefill_paged(
    params, x, cfg: ModelConfig, spec: LayerSpec, cache: dict,
    page_table: jnp.ndarray, prefix_len: jnp.ndarray, tail_len: jnp.ndarray,
    *, cache_len: int, positions: jnp.ndarray,
    plan: Optional[plan_lib.AttentionPlan] = None,
) -> Tuple[jnp.ndarray, dict]:
    """Prefix-extension prefill over the paged KV pool (PR-3 headline).

    The first ``prefix_len[b]`` positions were already prefilled by an
    earlier request sharing this prefix (paged engine, ``cache.prefix``);
    their K/V stays **in its pages** — the paged prefill kernel reads it
    straight from ``page_table`` (B, prefix_pages), no gather. Only the
    tail's K/V is computed and returned, padded to ``cache_len`` (the
    caller scatters it into fresh pages). ``positions`` must already carry
    the absolute query positions (``prefix_len[b] + i``); ``tail_len``
    masks bucket padding (rows past it emit zeros).
    """
    b, s, d = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions, spec.rope_theta)
    k_pages, v_pages = cache["k_pages"], cache["v_pages"]
    kv_dtype = quant_lib.kv_dtype_of(k_pages.dtype)
    if plan is None:
        plan = _plan(
            cfg,
            (b, cfg.n_heads, cfg.n_kv_heads,
             s, page_table.shape[1] * k_pages.shape[2] + s, cfg.head_dim),
            phase=plan_lib.EXTEND, kv_layout=plan_lib.PAGED,
            page_size=k_pages.shape[2], prefix_pages=page_table.shape[1],
            window=spec.window, dtype_bytes=q.dtype.itemsize,
            kv_dtype=kv_dtype,
        )
    o = ops.paged_prefill_attention(
        q, k_pages, v_pages, page_table, k, v, prefix_len, tail_len,
        softcap=cfg.attn_softcap, window=spec.window, plan=plan,
        k_scales=cache.get("k_scales"), v_scales=cache.get("v_scales"),
    )
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    pad = cache_len - k.shape[2]
    cache_out = {
        "k": jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))),
        "v": jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))),
    }
    return o @ params["wo_md"].astype(x.dtype), cache_out


def attention_decode(
    params, x, cfg: ModelConfig, spec: LayerSpec, cache: dict, lengths: jnp.ndarray,
    *, is_cross: bool = False,
) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x: (B, 1, D); cache k/v: (B, Hkv, Smax, hd);
    lengths: (B,) prefix length *including* the new token. ``is_cross``:
    the cache holds static encoder (image) K/V — read-only."""
    b, _, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if is_cross:
        # Cross-attn KV is static (image tokens): cache holds it untouched.
        q = (x @ params["wq_dm"].astype(x.dtype)).reshape(b, 1, h, hd).transpose(0, 2, 1, 3)
        if cfg.qk_norm:
            q = layers.rmsnorm(params["q_norm"], q)
        kv_len = jnp.full((b,), cache["k"].shape[2], jnp.int32)
        plan = _plan(
            cfg, (b, h, hkv, 1, cache["k"].shape[2], hd),
            phase=plan_lib.DECODE, dtype_bytes=q.dtype.itemsize,
        )
        o = ops.decode_attention(
            q[:, :, 0], cache["k"], cache["v"], kv_len,
            softcap=cfg.attn_softcap, plan=plan,
        )
        o = o.reshape(b, 1, h * hd)
        return o @ params["wo_md"].astype(x.dtype), cache

    positions = (lengths - 1)[:, None]  # (B, 1) absolute position of new token
    q, k_new, v_new = _project_qkv(params, x, cfg, positions, spec.rope_theta)
    # In-place row write at position lengths-1 (donated cache buffers alias).
    idx = lengths - 1

    def _write(c, new, i):
        return jax.lax.dynamic_update_slice(c, new, (0, i, 0))

    k = jax.vmap(_write)(cache["k"], k_new, idx)
    v = jax.vmap(_write)(cache["v"], v_new, idx)
    plan = _plan(
        cfg, (b, h, hkv, 1, k.shape[2], hd),
        phase=plan_lib.DECODE, window=spec.window, dtype_bytes=q.dtype.itemsize,
    )
    o = ops.decode_attention(
        q[:, :, 0], k, v, lengths,
        softcap=cfg.attn_softcap, window=spec.window, plan=plan,
    )
    o = o.reshape(b, 1, h * hd)
    return o @ params["wo_md"].astype(x.dtype), {"k": k, "v": v}


def attention_decode_paged(
    params, x, cfg: ModelConfig, spec: LayerSpec, cache: dict,
    page_table: jnp.ndarray, lengths: jnp.ndarray,
) -> Tuple[jnp.ndarray, dict]:
    """One-token decode over the paged KV pool.

    x: (B, 1, D); cache k/v_pages: (Hkv, P, page_size, hd) head-major;
    page_table: (B, max_pages) physical ids (null-page padded); lengths:
    (B,) length *including* the new token. The new K/V row is scattered
    into the sequence's tail page, then the paged flash-decode kernel
    consumes the page table natively. Rows whose table is all null pages
    (inactive decode slots) harmlessly write the reserved null page.
    """
    b, _, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k_pages, v_pages = cache["k_pages"], cache["v_pages"]
    ps = k_pages.shape[2]

    positions = (lengths - 1)[:, None]
    q, k_new, v_new = _project_qkv(params, x, cfg, positions, spec.rope_theta)

    # Clamp for inactive rows (length 0): they index the null-padded table
    # head and write the reserved null page.
    idx = jnp.maximum(lengths - 1, 0)
    pids = jnp.take_along_axis(page_table, (idx // ps)[:, None], axis=1)[:, 0]
    offs = idx % ps
    kv_dtype = quant_lib.kv_dtype_of(k_pages.dtype)
    # (B, Hkv, 1, hd) -> (Hkv, B, hd); scatter one row per (head, sequence).
    # Quantized pools append through the rescale-on-append path (the page's
    # codes shrink when a louder token widens its scale); fp32 degenerates
    # to the plain scatter with scales passed through as None.
    k_pages, ksc = quant_lib.append_rows(
        k_pages, cache.get("k_scales"), k_new[:, :, 0].transpose(1, 0, 2),
        pids, offs, kv_dtype,
    )
    v_pages, vsc = quant_lib.append_rows(
        v_pages, cache.get("v_scales"), v_new[:, :, 0].transpose(1, 0, 2),
        pids, offs, kv_dtype,
    )
    plan = _plan(
        cfg, (b, h, hkv, 1, page_table.shape[1] * ps, hd),
        phase=plan_lib.DECODE, kv_layout=plan_lib.PAGED, page_size=ps,
        window=spec.window, dtype_bytes=q.dtype.itemsize, kv_dtype=kv_dtype,
    )
    o = ops.paged_decode_attention(
        q[:, :, 0], k_pages, v_pages, page_table, lengths,
        softcap=cfg.attn_softcap, window=spec.window, plan=plan,
        k_scales=ksc, v_scales=vsc,
    )
    o = o.reshape(b, 1, h * hd)
    cache_out = {"k_pages": k_pages, "v_pages": v_pages}
    if ksc is not None:
        cache_out["k_scales"] = ksc
        cache_out["v_scales"] = vsc
    return o @ params["wo_md"].astype(x.dtype), cache_out


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> dict:
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, hkv, cache_len, hd), dtype),
        "v": jnp.zeros((batch, hkv, cache_len, hd), dtype),
    }


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int, dtype,
                     kv_dtype: str = "fp32") -> dict:
    """Head-major page pool for one layer: all pages of a KV head are
    contiguous (``cache.layout.HEAD_ALIGNED`` placement by construction).

    ``kv_dtype`` != "fp32" stores 1-byte codes (``cache.quant``) plus one
    fp32 scale per (kv head, physical page) for K and V each — the scale
    arrays are page-table metadata and ride next to it into the kernels.
    """
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    quant_lib.validate_kv_dtype(kv_dtype)
    if kv_dtype == "fp32":
        return {
            "k_pages": jnp.zeros((hkv, num_pages, page_size, hd), dtype),
            "v_pages": jnp.zeros((hkv, num_pages, page_size, hd), dtype),
        }
    sdt = quant_lib.storage_dtype(kv_dtype)
    return {
        "k_pages": jnp.zeros((hkv, num_pages, page_size, hd), sdt),
        "v_pages": jnp.zeros((hkv, num_pages, page_size, hd), sdt),
        "k_scales": jnp.zeros((hkv, num_pages), jnp.float32),
        "v_scales": jnp.zeros((hkv, num_pages), jnp.float32),
    }
