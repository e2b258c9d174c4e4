"""DeepSeek-V2: latent attention (MLA) on every layer, a leading dense SwiGLU
layer, then sparse-expert layers, as one chip's share of an expert-parallel
deployment. Equations: arXiv:2405.04434 section 2.1 and the model's
``modeling_deepseek.py``.

Per layer, ``h`` the RMS-normed input:

- attention, no query compression: ``q = h W_q`` (a ``qk_nope_head_dim`` and
  a ``qk_rope_head_dim`` part a head); ``[c, k_pe] = h W_kv_a``; ``c``
  RMS-normed; ``[k_nope, v] = c W_kv_b`` per head; YaRN rope on ``q_pe`` and
  the one ``k_pe`` every head shares; ``score = (q_nope . k_nope + q_pe .
  k_pe) * qk_head_dim^-0.5 * mscale^2``, causal softmax, ``o = p v``, ``W_o``;
- FFN: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the rest route over every expert (float32 logits,
  softmax, greedy top-k, weights not renormalised when ``norm_topk_prob`` is
  false, times ``routed_scaling_factor``) and add the held experts' part,
  ``sum_{k: e_k held} w_k E_{e_k}(h)``, plus the shared experts as one SwiGLU
  of ``n_shared_experts * moe_intermediate_size``.

``expert_parallel`` in the configuration file names the deployment: how many
chips share each expert layer, the router's published expert count, and the
first expert held here; ``n_routed_experts`` counts the experts held. The
reference computes the same share: what absent experts would add is left
out in program and reference alike.

The reference, in plain float32 ``jax.numpy`` with ``bench/reference.py``'s
helpers, runs the expanded form (keys and values per head) and imports
nothing of the program; the program serves the absorbed form over a paged
latent cache. It reads the served tree by name: ``embed.table_vd``,
``layers_lead[i]`` (the dense layers: ``ln1``, ``attn.{wq_dm, wkv_a_dm,
kv_norm.scale_r, wkv_b_dm, wo_md}``, ``ln2``, ``mlp``), ``layers[0]``
(stacked over the expert layers: ``ln1``, ``attn``, ``ln2``,
``moe.{router_de, wi_gate_edm, wi_up_edm, wo_emd, shared}``), ``ln_f`` and
``head.w_dm``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import BLOCK, _mm, _q8, _rmsnorm
from bench.work import Shape, Stack

ARCHITECTURES = ("DeepseekV2ForCausalLM",)


def _refuse_what_it_lacks(conf: Dict) -> None:
    name = conf.get("name")

    def refuse(why):
        raise ValueError(f"{name}: {why}: the deepseek_v2 family does not "
                         f"compute it")

    if conf.get("q_lora_rank") is not None:
        refuse(f"'q_lora_rank' {conf['q_lora_rank']!r} (query compression)")
    if conf.get("scoring_func", "softmax") != "softmax":
        refuse(f"'scoring_func' {conf['scoring_func']!r}")
    if conf.get("topk_method", "greedy") != "greedy":
        refuse(f"'topk_method' {conf['topk_method']!r}")
    if conf.get("n_group", 1) > 1 or conf.get("topk_group", 1) > 1:
        refuse("group-limited routing ('n_group' > 1)")
    if conf.get("num_nextn_predict_layers"):
        refuse("'num_nextn_predict_layers' (multi-token prediction)")
    rs = conf.get("rope_scaling")
    if rs is not None and rs.get("type", rs.get("rope_type")) != "yarn":
        refuse(f"'rope_scaling' of type {rs.get('type', rs.get('rope_type'))!r}")
    if conf.get("moe_layer_freq", 1) != 1:
        refuse(f"'moe_layer_freq' {conf['moe_layer_freq']!r}")
    if conf.get("attention_bias"):
        refuse("'attention_bias' true")
    if conf.get("hidden_act", "silu") != "silu":
        refuse(f"'hidden_act' {conf['hidden_act']!r}")
    if conf.get("tie_word_embeddings"):
        refuse("'tie_word_embeddings' true")
    for arch in conf.get("architectures", ()):
        if arch not in ARCHITECTURES:
            refuse(f"'architectures' {arch!r}")


def _held(conf: Dict) -> Tuple[int, int, int]:
    """(router's expert count, first expert held, experts held)."""
    ep = conf.get("expert_parallel", {})
    held = conf["n_routed_experts"]
    total = ep.get("router_experts", held)
    start = ep.get("held_start", 0)
    if not 0 <= start <= total - held:
        raise ValueError(f"{conf.get('name')}: experts [{start}, {start + held}) "
                         f"are not among the router's {total}")
    return total, start, held


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import (LatentModelConfig, LayerSpec, MLAConfig,
                                    MoEConfig, YarnConfig)
    from repro.models import mla as mla_lib

    _refuse_what_it_lacks(conf)
    total, start, held = _held(conf)
    rs = conf.get("rope_scaling")
    yarn = None if rs is None else YarnConfig(
        factor=float(rs["factor"]),
        original_max_position=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]))
    mla = MLAConfig(
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"], rope_theta=float(conf["rope_theta"]),
        yarn=yarn)
    n_dense = conf["first_k_dense_replace"]
    return LatentModelConfig(
        name=conf["name"],
        d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"],
        # One latent pool "head" a layer, as wide as its lane-padded row.
        n_kv_heads=1,
        head_dim=mla_lib.pool_width(mla),
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        lead_pattern=(LayerSpec(ffn="mlp"),) * n_dense,
        layer_pattern=(LayerSpec(ffn="moe"),),
        moe=MoEConfig(
            num_experts=total, top_k=conf["num_experts_per_tok"],
            d_ff=conf["moe_intermediate_size"],
            num_shared_experts=conf["n_shared_experts"],
            norm_topk_prob=bool(conf["norm_topk_prob"]),
            routed_scaling_factor=float(conf["routed_scaling_factor"]),
            held_start=start, num_held=held),
        mla=mla,
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=False,
        max_seq_len=conf["max_position_embeddings"],
        compute_dtype=conf["torch_dtype"],
    )


@dataclasses.dataclass(frozen=True)
class LatentLayer(Shape):
    """A latent-attention layer. Decode and prefill attention, absorbed:
    every head's query (``latent`` wide) meets one shared latent row a
    token, read once (``latent`` values of ``kv_bytes``), and gathers its
    first ``rank`` columns: ``2 * heads * (latent + rank)`` FLOPs a
    (query, key) pair. ``ffn_params``: weights one token multiplies through
    in the FFN."""

    latent: int = 0
    rank: int = 0
    attn_params: int = 0
    ffn_params: int = 0

    def decode_attn_call(self, lengths):
        hq, dl, r = self.n_heads, self.latent, self.rank
        flops = nbytes = 0.0
        for n in lengths:
            flops += 2.0 * hq * (dl + r) * int(n)
            nbytes += int(n) * dl * self.kv_bytes + hq * (dl + r) * self.act_bytes
        return flops, nbytes

    def prefill_attn_call(self, prefix, tail):
        hq, dl, r = self.n_heads, self.latent, self.rank
        flops = 2.0 * hq * (dl + r) * self.prefill_pairs(prefix, tail)
        nbytes = (prefix + tail) * dl * self.kv_bytes
        nbytes += tail * hq * (dl + r) * self.act_bytes
        return flops, nbytes

    def matmul_params(self):
        return self.attn_params + self.ffn_params


def _swiglu(d: int, f: int) -> int:
    return 3 * d * f


def work_stack(conf: Dict) -> Stack:
    """Two groups: the leading dense layers and the expert layers. An
    expert layer counts the held experts' share of the routed slots,
    ``top_k * held / router_experts`` experts a token, plus the shared
    experts and the router."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    r, dn, dr, dv = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                     conf["qk_rope_head_dim"], conf["v_head_dim"])
    total, _, held = _held(conf)
    attn = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    f = conf["moe_intermediate_size"]
    routed = conf["num_experts_per_tok"] * held / total
    moe = int(round((routed + conf["n_shared_experts"]) * _swiglu(d, f))) + d * total
    n_dense = conf["first_k_dense_replace"]

    def layer(count, ffn):
        return LatentLayer(
            n_layers=count, d_model=d, n_heads=h, n_kv_heads=1, head_dim=dv,
            d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
            latent=r + dr, rank=r, attn_params=attn, ffn_params=ffn)

    n_moe = conf["num_hidden_layers"] - n_dense
    return Stack(conf["vocab_size"], d, (
        (n_dense, layer(n_dense, _swiglu(d, conf["intermediate_size"]))),
        (n_moe, layer(n_moe, moe)),
    ))


def yarn_inv_freq(dim: int, base: float, rs: Dict) -> np.ndarray:
    """YaRN's inverse frequencies (``DeepseekV2YarnRotaryEmbedding``)."""
    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def forward(params, tokens, score_pos, conf: Dict, quantized: bool):
    """Reference logits at ``score_pos`` of the padded ``tokens``."""
    return _forward(params, tokens, score_pos, _conf_key(conf), quantized)


def _conf_key(conf: Dict):
    rs = conf.get("rope_scaling")
    dr = conf["qk_rope_head_dim"]
    scale = (conf["qk_nope_head_dim"] + dr) ** -0.5
    inv, cs = 1.0 / float(conf["rope_theta"]) ** (np.arange(0, dr, 2) / dr), 1.0
    if rs is not None:
        inv = yarn_inv_freq(dr, float(conf["rope_theta"]), rs)
        cs = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
            rs["factor"], rs["mscale_all_dim"])
        if rs["mscale_all_dim"]:
            scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    total, start, held = _held(conf)
    return (conf["num_attention_heads"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], dr, conf["v_head_dim"],
            tuple(float(x) for x in inv), float(cs), float(scale),
            conf["num_experts_per_tok"], bool(conf["norm_topk_prob"]),
            float(conf["routed_scaling_factor"]), start, held,
            conf["first_k_dense_replace"])


def _rope(x, pos, inv, cs):
    """x: (S, H, D): rotate the two halves of the rope dims."""
    ang = pos[:, None, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, scale, quantized):
    """Causal attention in blocks of query rows. q, k: (S, H, Dqk); v:
    (S, H, Dv)."""
    s, h, _ = q.shape
    if quantized:
        k, v = _q8(k, axis=-1), _q8(v, axis=-1)
    cols = jnp.arange(s)
    kt = k.transpose(1, 2, 0)
    vh = v.transpose(1, 0, 2)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, 0)
        if quantized:
            qb = _q8(qb, axis=-1)
        rows = i * BLOCK + jnp.arange(BLOCK)
        sc = jnp.einsum("bhd,hds->hbs", qb, kt) * scale
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if quantized:
            p = _q8(p, axis=-1)
        return jnp.einsum("hbs,hsd->bhd", p, vh)

    out = jax.lax.map(block, jnp.arange(s // BLOCK))
    return out.reshape(s, h, v.shape[-1])


@functools.partial(jax.jit, static_argnames=("key", "quantized"))
def _forward(params, tokens, score_pos, key, quantized):
    (h, r, dn, dr, dv, inv, cs, scale, top_k, norm_topk, rsf, start, held,
     n_dense) = key
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    mm = lambda a, w: _mm(a, f32(w), quantized)  # noqa: E731
    x = f32(params["embed"]["table_vd"])[tokens]
    pos = jnp.arange(tokens.shape[0])
    s = tokens.shape[0]

    def attention(x, a):
        q = mm(x, a["wq_dm"]).reshape(s, h, dn + dr)
        kv = mm(x, a["wkv_a_dm"])
        c = _rmsnorm(kv[:, :r], f32(a["kv_norm"]["scale_r"]))
        k_pe = _rope(kv[:, None, r:], pos, inv, cs)
        kvb = mm(c, a["wkv_b_dm"]).reshape(s, h, dn + dv)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv, cs)], -1)
        k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_pe, (s, h, dr))], -1)
        o = _attention(q, k, kvb[..., dn:], scale, quantized)
        return mm(o.reshape(s, h * dv), a["wo_md"])

    def swiglu(x, m):
        g = jax.nn.silu(mm(x, m["wi_gate_dm"]))
        return mm(g * mm(x, m["wi_up_dm"]), m["wo_md"])

    def experts(x, m):
        logits = mm(x, m["router_de"])
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, top_k)
        if norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        w = w * rsf
        y = swiglu(x, m["shared"])
        for j in range(held):
            e = start + j
            gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
            ye = mm(jax.nn.silu(mm(x, m["wi_gate_edm"][j])) * mm(x, m["wi_up_edm"][j]),
                    m["wo_emd"][j])
            y = y + gate[:, None] * ye
        return y

    def layer(x, p, ffn):
        x = x + attention(_rmsnorm(x, f32(p["ln1"]["scale_r"])), p["attn"])
        return x + ffn(_rmsnorm(x, f32(p["ln2"]["scale_r"])), p)

    for i in range(n_dense):
        x = layer(x, params["layers_lead"][i], lambda x, p: swiglu(x, p["mlp"]))
    x, _ = jax.lax.scan(
        lambda x, p: (layer(x, p, lambda x, p: experts(x, p["moe"])), None),
        x, params["layers"][0])
    x = _rmsnorm(x[score_pos], f32(params["ln_f"]["scale_r"]))
    return mm(x, params["head"]["w_dm"])
