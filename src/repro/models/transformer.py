"""The model stack: scan-over-periods composition of heterogeneous blocks.

Depth is expressed as ``n_periods`` repetitions of ``cfg.layer_pattern`` plus
an unrolled remainder, so compile time is O(|pattern|), not O(n_layers) —
llama3-405b's 126 layers compile one body. Within a period each position has
a static ``LayerSpec`` (attn/mamba/hybrid x mlp/moe x window x cross), so
heterogeneous stacks (gemma3 5:1 local:global, llama-3.2-vision every-5th
cross-attn) scan cleanly with full static shapes.

Three entry points per model: ``forward`` (training), ``prefill`` (builds KV
caches), ``decode_step`` (one token, cache-threaded). MoE aux losses ride
the scan carry.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn_lib
from repro.models import layers, mla as mla_lib, moe as moe_lib, ssm as ssm_lib

Params = Dict[str, Any]


# -----------------------------------------------------------------------------
# Per-layer init / apply
# -----------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, spec: LayerSpec) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"ln1": layers.init_rmsnorm(cfg.d_model)}
    if spec.kind in ("attn", "hybrid"):
        p["attn"] = (mla_lib.init_mla(ks[0], cfg) if cfg.mla
                     else attn_lib.init_attention(ks[0], cfg))
    if spec.kind in ("mamba", "hybrid"):
        p["mamba"] = ssm_lib.init_mamba(ks[1], cfg.d_model, cfg.ssm)
    if spec.kind == "hybrid":
        p["ln_attn_out"] = layers.init_rmsnorm(cfg.d_model)
        p["ln_mamba_out"] = layers.init_rmsnorm(cfg.d_model)
    if spec.cross_attn:
        p["ln_cross"] = layers.init_rmsnorm(cfg.d_model)
        p["cross"] = attn_lib.init_attention(ks[2], cfg)
        p["cross_gate_r"] = jnp.zeros((), layers.default_dtype())
    if spec.ffn == "mlp":
        p["ln2"] = layers.init_rmsnorm(cfg.d_model)
        p["mlp"] = layers.init_mlp(ks[3], cfg.d_model, cfg.d_ff)
    elif spec.ffn == "moe":
        p["ln2"] = layers.init_rmsnorm(cfg.d_model)
        p["moe"] = moe_lib.init_moe(ks[4], cfg.d_model, cfg.moe)
    return p


def _zero_aux(tokens: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """The layers' summed aux. ``tokens``: also count, per token, the
    routed slots landing on this device's held experts (serving modes of
    an expert stack)."""
    aux = {
        "moe_lb_loss": jnp.zeros((), jnp.float32),
        "moe_z_loss": jnp.zeros((), jnp.float32),
        "moe_dropped_frac": jnp.zeros((), jnp.float32),
    }
    if tokens is not None:
        aux["moe_held_slots"] = jnp.zeros((tokens,), jnp.int32)
    return aux


def _aux_tokens(cfg: ModelConfig, mode: str, x) -> Optional[int]:
    if cfg.moe is None or mode == "train":
        return None
    return x.shape[0] * x.shape[1]


def apply_layer(
    p: Params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    spec: LayerSpec,
    *,
    mode: str,                   # "train" | "prefill" | "decode"
    cache: Optional[Params] = None,
    lengths: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,
    encoder_states: Optional[jnp.ndarray] = None,
    cache_len: int = 0,
    page_table: Optional[jnp.ndarray] = None,
    prefix_len: Optional[jnp.ndarray] = None,
    plan=None,
    shard_moe=lambda t: t,
) -> Tuple[jnp.ndarray, Optional[Params], Dict[str, jnp.ndarray]]:
    """Returns (x, new_cache, aux).

    In decode mode a cache holding ``k_pages`` routes through the paged
    decode path (``page_table`` required). In prefill mode a non-None
    ``cache`` holds the *paged* K/V pool of an already-prefilled shared
    prefix (prefix-extension prefill): ``page_table`` names its pages,
    ``prefix_len`` (B,) its live length, ``lengths`` (B,) the live tail
    length, and ``plan`` the engine-resolved
    :class:`~repro.kernels.plan.AttentionPlan` for the extend phase.
    """
    aux = _zero_aux(_aux_tokens(cfg, mode, x))
    h = layers.rmsnorm(p["ln1"], x)
    new_cache: Params = {}

    def run_mla():
        if mode == "train":
            return mla_lib.mla_block(p["attn"], h, cfg, positions=positions), None
        c = None if cache is None else cache.get("attn")
        if mode == "prefill":
            return mla_lib.mla_prefill(
                p["attn"], h, cfg, cache_len=cache_len,
                pool=None if c is None else c["latent_pages"],
                page_table=page_table, prefix_len=prefix_len, tail_len=lengths,
                positions=positions,
            )
        if c is None or "latent_pages" not in c:
            raise ValueError("latent attention decodes over the paged latent pool")
        return mla_lib.mla_decode_paged(p["attn"], h, cfg, c, page_table, lengths)

    def run_attn():
        if cfg.mla is not None:
            return run_mla()
        if mode == "train":
            return attn_lib.attention_block(
                p["attn"], h, cfg, spec, positions=positions,
            ), None
        if mode == "prefill":
            c = None if cache is None else cache.get("attn")
            if c is not None:
                if "k_pages" not in c:
                    # Falling through to plain prefill would silently drop
                    # the prefix; the dense prefix_kv route was removed in
                    # favor of the paged prefill kernel.
                    raise ValueError(
                        "prefill-mode prefix caches must be paged "
                        "(k_pages/v_pages pools)"
                    )
                return attn_lib.attention_prefill_paged(
                    p["attn"], h, cfg, spec, c, page_table, prefix_len,
                    lengths, cache_len=cache_len, positions=positions,
                    plan=plan,
                )
            return attn_lib.attention_prefill(
                p["attn"], h, cfg, spec, cache_len=cache_len, positions=positions,
            )
        if cache is not None and "k_pages" in cache["attn"]:
            return attn_lib.attention_decode_paged(
                p["attn"], h, cfg, spec, cache["attn"], page_table, lengths,
            )
        return attn_lib.attention_decode(
            p["attn"], h, cfg, spec, cache["attn"], lengths,
        )

    if spec.kind == "attn":
        y, c = run_attn()
        if c is not None:
            new_cache["attn"] = c
        x = x + y
    elif spec.kind == "mamba":
        if mode in ("train", "prefill"):
            y = ssm_lib.mamba_block(p["mamba"], h, cfg.d_model, cfg.ssm)
            if mode == "prefill":
                # Re-run final state via chunked scan is already inside; for
                # prefill we need the cache: recompute cheaply in decode form
                # is wasteful — mamba_block_with_cache returns it.
                y, c = _mamba_with_cache(p["mamba"], h, cfg)
                new_cache["mamba"] = c
        else:
            y, c = ssm_lib.mamba_decode(p["mamba"], h, cfg.d_model, cfg.ssm, cache["mamba"])
            new_cache["mamba"] = c
        x = x + y
    elif spec.kind == "hybrid":
        ya, c = run_attn()
        if c is not None:
            new_cache["attn"] = c
        if mode in ("train",):
            ym = ssm_lib.mamba_block(p["mamba"], h, cfg.d_model, cfg.ssm)
        elif mode == "prefill":
            ym, cm = _mamba_with_cache(p["mamba"], h, cfg)
            new_cache["mamba"] = cm
        else:
            ym, cm = ssm_lib.mamba_decode(p["mamba"], h, cfg.d_model, cfg.ssm, cache["mamba"])
            new_cache["mamba"] = cm
        # Hymba: parallel attention + SSM heads, normalized and averaged.
        x = x + 0.5 * (
            layers.rmsnorm(p["ln_attn_out"], ya) + layers.rmsnorm(p["ln_mamba_out"], ym)
        )
    else:
        raise ValueError(spec.kind)

    if spec.cross_attn:
        hc = layers.rmsnorm(p["ln_cross"], x)
        gate = jnp.tanh(p["cross_gate_r"]).astype(x.dtype)
        if mode == "decode":
            yc, cc = attn_lib.attention_decode(
                p["cross"], hc, cfg, spec, cache["cross"], lengths, is_cross=True,
            )
            new_cache["cross"] = cc
        elif mode == "prefill":
            yc, cc = attn_lib.attention_prefill(
                p["cross"], hc, cfg, spec, cache_len=encoder_states.shape[1],
                encoder_states=encoder_states,
            )
            new_cache["cross"] = cc
        else:
            yc = attn_lib.attention_block(
                p["cross"], hc, cfg, spec, encoder_states=encoder_states,
            )
        x = x + gate * yc

    if spec.ffn == "mlp":
        x = x + layers.mlp(p["mlp"], layers.rmsnorm(p["ln2"], x))
    elif spec.ffn == "moe":
        hm = layers.rmsnorm(p["ln2"], x)
        if mode == "train":
            y, moe_aux = moe_lib.moe_ffn(p["moe"], hm, cfg.moe,
                                         shard_buffers=shard_moe)
        else:
            y, moe_aux = moe_lib.moe_ffn_dropless(p["moe"], hm, cfg.moe)
        aux = {k: aux[k] + moe_aux[k] for k in aux}
        x = x + y
    return x, (new_cache or None), aux


def _mamba_with_cache(params, h, cfg: ModelConfig):
    """Prefill path for SSM blocks: full-sequence output + decode cache."""
    d_in, nh, g, n, w = ssm_lib._dims(cfg.d_model, cfg.ssm)
    proj = h @ params["win_dm"].astype(h.dtype)
    z, xbc, dt_raw = ssm_lib._split_proj(proj, d_in, g, n, nh)
    xbc_conv = ssm_lib._causal_conv(
        xbc, params["conv_w"].astype(h.dtype), params["conv_b_r"].astype(h.dtype)
    )
    xs = xbc_conv[..., :d_in].reshape(*h.shape[:2], nh, d_in // nh)
    b_mat = xbc_conv[..., d_in : d_in + g * n].reshape(*h.shape[:2], g, n)
    c_mat = xbc_conv[..., d_in + g * n :].reshape(*h.shape[:2], g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias_r"].astype(jnp.float32))
    a = -jnp.exp(params["a_log_r"].astype(jnp.float32))
    y, h_final = ssm_lib._ssd(cfg.ssm)(xs, dt, a, b_mat, c_mat, cfg.ssm.chunk)
    y = y + xs * params["d_skip_r"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(*h.shape[:2], d_in)
    y = layers.rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = y @ params["wout_md"].astype(h.dtype)
    cache = {
        "conv": xbc[:, -(w - 1):, :],  # pre-activation history
        "ssm": h_final,
    }
    return out, cache


# -----------------------------------------------------------------------------
# Model init
# -----------------------------------------------------------------------------


def init_model(key, cfg: ModelConfig) -> Params:
    """Random params for ``cfg`` from ``key``. Jitted with ``cfg``
    static, so a full-width init holds only its outputs and one tensor's
    temporaries on the device (bitwise equal to
    :func:`init_model_eager`)."""
    return _init_model_jit(key, cfg)


def init_model_eager(key, cfg: ModelConfig) -> Params:
    """The op-by-op body of :func:`init_model`."""
    pattern, rem = cfg.pattern_for_depth()
    n_periods = cfg.n_periods
    ks = jax.random.split(key, 4 + len(rem))
    lead = cfg.lead_pattern
    params: Params = {}
    if cfg.num_codebooks > 1:
        params["embed"] = {
            "table_kvd": layers.normal_init(
                ks[0], (cfg.num_codebooks, cfg.vocab, cfg.d_model),
                cfg.d_model**-0.5,
            )
        }
    else:
        params["embed"] = layers.init_embedding(ks[0], cfg.vocab, cfg.d_model)
    if cfg.vision_tokens:
        params["vision_proj"] = layers.init_linear(ks[1], cfg.vision_dim, cfg.d_model)

    # Scanned period stacks: one stacked tree per pattern position, built
    # by vmapping the layer init over the periods' keys (bitwise what a
    # per-period loop and a stack would give, without the per-period list).
    stacks = []
    for j, spec in enumerate(pattern):
        keys = jnp.stack([
            jax.random.fold_in(ks[2], p * len(pattern) + j)
            for p in range(n_periods)
        ])
        stacks.append(jax.vmap(lambda k, spec=spec: init_layer(k, cfg, spec))(keys))
    if lead:
        params["layers_lead"] = tuple(
            init_layer(jax.random.fold_in(ks[1], 1 << 20 | i), cfg, spec)
            for i, spec in enumerate(lead)
        )
    params["layers"] = tuple(stacks)
    params["layers_rem"] = tuple(
        init_layer(ks[4 + i], cfg, spec) for i, spec in enumerate(rem)
    )
    params["ln_f"] = layers.init_rmsnorm(cfg.d_model)
    if not cfg.tie_embeddings:
        params["head"] = layers.init_linear(ks[3], cfg.d_model, cfg.vocab)
    return params


_init_model_jit = jax.jit(init_model_eager, static_argnums=1)


def _embed_tokens(params, cfg: ModelConfig, tokens):
    dt = jnp.dtype(cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        # tokens: (B, S, K) -> sum of per-codebook embeddings (MusicGen).
        tab = params["embed"]["table_kvd"]
        x = sum(
            jnp.take(tab[k_], tokens[..., k_], axis=0) for k_ in range(cfg.num_codebooks)
        ).astype(dt)
        return x
    x = layers.embed(params["embed"], tokens, scale_by_dim=cfg.embed_scale, compute_dtype=dt)
    return x


def _logits(params, cfg: ModelConfig, x):
    if cfg.num_codebooks > 1:
        tab = params["embed"]["table_kvd"]  # (K, V, D)
        logits = jnp.einsum(
            "bsd,kvd->bskv", x.astype(jnp.float32), tab.astype(jnp.float32)
        )
    elif cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x, softcap=cfg.final_softcap)
        return logits
    else:
        logits = x.astype(jnp.float32) @ params["head"]["w_dm"].astype(jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


# -----------------------------------------------------------------------------
# Forward passes
# -----------------------------------------------------------------------------


def _run_stack(
    params, cfg: ModelConfig, x, *, mode, caches=None, lengths=None,
    positions=None, encoder_states=None, cache_len=0, page_table=None,
    prefix_len=None, plan=None, shard_moe=lambda t: t, remat: bool = False,
):
    pattern, rem = cfg.pattern_for_depth()
    aux_tot = _zero_aux(_aux_tokens(cfg, mode, x))
    kw = dict(
        mode=mode, lengths=lengths, positions=positions,
        encoder_states=encoder_states, cache_len=cache_len,
        page_table=page_table, prefix_len=prefix_len, plan=plan,
        shard_moe=shard_moe,
    )

    new_lead = []
    lead_caches = caches.get("lead") if caches else None
    for i, spec in enumerate(cfg.lead_pattern):
        c_i = None if lead_caches is None else lead_caches[i]
        x, nc, a = apply_layer(params["layers_lead"][i], x, cfg, spec,
                               cache=c_i, **kw)
        new_lead.append(nc)
        aux_tot = {k: aux_tot[k] + a[k] for k in aux_tot}

    def period_body(carry, xs_cache):
        x, aux = carry
        stacked_params, period_caches = xs_cache
        new_caches = []
        for j, spec in enumerate(pattern):
            c_j = None if period_caches is None else period_caches[j]
            x, nc, a = apply_layer(
                stacked_params[j], x, cfg, spec, mode=mode, cache=c_j,
                lengths=lengths, positions=positions,
                encoder_states=encoder_states, cache_len=cache_len,
                page_table=page_table, prefix_len=prefix_len, plan=plan,
                shard_moe=shard_moe,
            )
            new_caches.append(nc)
            aux = {k: aux[k] + a[k] for k in aux}
        out_caches = tuple(new_caches) if any(c is not None for c in new_caches) else None
        return (x, aux), out_caches

    body = period_body
    if remat and mode == "train":
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if cfg.remat_policy == "dots"
            else None  # default: nothing saveable — recompute the period
        )
        body = jax.checkpoint(period_body, policy=policy)

    period_caches = caches["scanned"] if caches else None
    xs = (params["layers"], period_caches)
    (x, aux_tot), new_scanned = jax.lax.scan(
        body, (x, aux_tot), xs, unroll=cfg.scan_unroll
    )

    new_rem = []
    rem_caches = caches["rem"] if caches else None
    for i, spec in enumerate(rem):
        c_i = None if rem_caches is None else rem_caches[i]
        x, nc, a = apply_layer(
            params["layers_rem"][i], x, cfg, spec, mode=mode, cache=c_i,
            lengths=lengths, positions=positions, encoder_states=encoder_states,
            cache_len=cache_len, page_table=page_table, prefix_len=prefix_len,
            plan=plan, shard_moe=shard_moe,
        )
        new_rem.append(nc)
        aux_tot = {k: aux_tot[k] + a[k] for k in aux_tot}
    new_caches = None
    if mode in ("prefill", "decode"):
        new_caches = {"scanned": new_scanned, "rem": tuple(new_rem)}
        if cfg.lead_pattern:
            new_caches["lead"] = tuple(new_lead)
    return x, new_caches, aux_tot


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    image_embeds: Optional[jnp.ndarray] = None,
    remat: bool = True,
    shard_moe=lambda t: t,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Training forward: tokens (B,S[,K]) -> logits (B,S,V[,K]), aux."""
    x = _embed_tokens(params, cfg, tokens)
    enc = None
    if cfg.vision_tokens and image_embeds is not None:
        enc = layers.linear(params["vision_proj"], image_embeds.astype(x.dtype))
    x, _, aux = _run_stack(
        params, cfg, x, mode="train", encoder_states=enc, shard_moe=shard_moe,
        remat=remat,
    )
    x = layers.rmsnorm(params["ln_f"], x)
    return _logits(params, cfg, x), aux


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    cache_len: int,
    image_embeds: Optional[jnp.ndarray] = None,
    last_positions: Optional[jnp.ndarray] = None,
    prefix_caches: Optional[Params] = None,
    page_table: Optional[jnp.ndarray] = None,
    prefix_len: Optional[jnp.ndarray] = None,
    plan=None,
    shard_moe=lambda t: t,
    return_aux: bool = False,
) -> Tuple[jnp.ndarray, Params]:
    """Prefill: returns (logits at the last real position (B,V[,K]), caches)
    and, with ``return_aux``, the layers' summed aux third (an expert
    stack's ``moe_held_slots`` is per token, (B*S,)).

    ``last_positions`` (B,): per-sequence index of the final prompt token
    (for right-padded prompts); defaults to S-1. Only one position's logits
    are materialized — at prefill_32k scale the full (B, S, V) tensor would
    be hundreds of GB.

    ``prefix_caches`` + ``page_table`` + ``prefix_len``: prefix-extension
    prefill. ``tokens`` holds only the tail; each attention layer
    additionally attends the shared prefix's K/V **in place in its pages**
    (``prefix_caches`` is the paged pool tree, ``page_table`` (B, pages)
    names the prefix's pages, ``prefix_len`` (B,) its live token count —
    dynamic, so one compilation serves every prefix length in a page
    bucket). ``plan`` is the caller-resolved extend-phase
    :class:`~repro.kernels.plan.AttentionPlan` (None lets each layer
    resolve its own). The returned caches cover the tail only — the caller
    owns where tail K/V physically lands
    (``serving.engine.PagedServingEngine`` scatters it into fresh pages).
    """
    x = _embed_tokens(params, cfg, tokens)
    enc = None
    if cfg.vision_tokens and image_embeds is not None:
        enc = layers.linear(params["vision_proj"], image_embeds.astype(x.dtype))
    positions = None
    tail_len = None
    if prefix_caches is not None:
        if page_table is None or prefix_len is None:
            raise ValueError(
                "prefix-extension prefill needs page_table and prefix_len"
            )
        b, s = tokens.shape[:2]
        positions = prefix_len[:, None] + jnp.arange(s)[None, :]
        tail_len = (
            last_positions + 1 if last_positions is not None
            else jnp.full((b,), s, jnp.int32)
        )
    x, caches, aux = _run_stack(
        params, cfg, x, mode="prefill", encoder_states=enc,
        cache_len=cache_len, caches=prefix_caches, lengths=tail_len,
        positions=positions, page_table=page_table, prefix_len=prefix_len,
        plan=plan, shard_moe=shard_moe,
    )
    if last_positions is None:
        x = x[:, -1:]
    else:
        x = jnp.take_along_axis(x, last_positions[:, None, None], axis=1)
    x = layers.rmsnorm(params["ln_f"], x)
    if return_aux:
        return _logits(params, cfg, x)[:, 0], caches, aux
    return _logits(params, cfg, x)[:, 0], caches


def decode_step(
    params: Params,
    cfg: ModelConfig,
    token: jnp.ndarray,            # (B,) or (B, K)
    caches: Params,
    lengths: jnp.ndarray,          # (B,) length INCLUDING the new token
    *,
    page_table: Optional[jnp.ndarray] = None,
    shard_moe=lambda t: t,
    return_aux: bool = False,
) -> Tuple[jnp.ndarray, Params]:
    """One decode step: returns (logits (B,V[,K]), updated caches) and,
    with ``return_aux``, the layers' summed aux third.

    ``page_table`` (B, max_pages): required when ``caches`` are paged
    (``init_paged_caches``); ignored for dense caches."""
    tok = token[:, None] if token.ndim == 1 else token[:, None, :]
    x = _embed_tokens(params, cfg, tok)
    x, new_caches, aux = _run_stack(
        params, cfg, x, mode="decode", caches=caches, lengths=lengths,
        page_table=page_table, shard_moe=shard_moe,
    )
    x = layers.rmsnorm(params["ln_f"], x)
    if return_aux:
        return _logits(params, cfg, x)[:, 0], new_caches, aux
    return _logits(params, cfg, x)[:, 0], new_caches


def init_caches(params: Params, cfg: ModelConfig, batch: int, cache_len: int,
                image_len: int = 0) -> Params:
    """Zero caches with the same tree structure prefill would emit."""
    if cfg.mla is not None:
        raise ValueError("latent attention is served over a paged latent "
                         "pool (init_paged_caches), not dense stripes")
    dt = jnp.dtype(cfg.compute_dtype)
    pattern, rem = cfg.pattern_for_depth()

    def one(spec: LayerSpec):
        c = {}
        if spec.kind in ("attn", "hybrid"):
            c["attn"] = attn_lib.init_cache(cfg, batch, cache_len, dt)
        if spec.kind in ("mamba", "hybrid"):
            c["mamba"] = ssm_lib.init_mamba_cache(cfg.d_model, cfg.ssm, batch, dt)
        if spec.cross_attn:
            c["cross"] = attn_lib.init_cache(cfg, batch, max(image_len, 1), dt)
        return c or None

    scanned = tuple(
        jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape), one(spec)
        )
        for spec in pattern
    )
    caches = {"scanned": scanned, "rem": tuple(one(s) for s in rem)}
    if cfg.lead_pattern:
        caches["lead"] = tuple(one(s) for s in cfg.lead_pattern)
    return caches


def init_paged_caches(
    params: Params, cfg: ModelConfig, num_pages: int, page_size: int,
    kv_dtype: str = "fp32",
) -> Params:
    """Paged zero caches: one head-major page pool per attention layer, all
    indexed by the same physical page ids (one allocator drives every
    layer, vLLM-style). Only pure-attention stacks support paging — SSM
    state and cross-attention K/V are not page-structured. ``kv_dtype``
    selects the pool storage format (``cache.quant``): quantized pools
    carry per-(head, page) scale arrays next to the code pools. A latent
    attention stack holds one latent pool per layer (``mla``), and the
    layers before the scanned periods sit under ``"lead"``."""
    dt = jnp.dtype(cfg.compute_dtype)
    pattern, rem = cfg.pattern_for_depth()
    for spec in cfg.all_layers():
        if spec.kind != "attn" or spec.cross_attn:
            raise ValueError(
                "paged caches require a pure self-attention stack; "
                f"got layer kind={spec.kind!r} cross_attn={spec.cross_attn}"
            )
    if cfg.mla is not None and kv_dtype != "fp32":
        raise ValueError(f"the latent pool is stored in {cfg.compute_dtype}; "
                         f"kv_dtype={kv_dtype!r} is not supported")

    def one(_spec: LayerSpec):
        if cfg.mla is not None:
            return {"attn": mla_lib.init_paged_cache(cfg, num_pages, page_size, dt)}
        return {"attn": attn_lib.init_paged_cache(
            cfg, num_pages, page_size, dt, kv_dtype=kv_dtype
        )}

    scanned = tuple(
        jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape), one(spec)
        )
        for spec in pattern
    )
    caches = {"scanned": scanned, "rem": tuple(one(s) for s in rem)}
    if cfg.lead_pattern:
        caches["lead"] = tuple(one(s) for s in cfg.lead_pattern)
    return caches
