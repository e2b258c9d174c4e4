"""A decoder whose layers are all alike and dense: the Llama / Mistral /
DeepSeek-LLM family. Pre-norm RoPE attention (MHA or GQA; one sliding window
or none, one rope theta on every layer), a SwiGLU MLP, an untied head.

The reference, in plain float32 ``jax.numpy`` (the helpers of
``bench/reference.py``), reads the benchmark's weights by their names in the
served tree: ``embed.table_vd``, ``layers[0]`` (stacked over layers:
``ln1``, ``attn.{wq,wk,wv}_dm``, ``attn.wo_md``, ``ln2``,
``mlp.{wi_gate,wi_up}_dm``, ``mlp.wo_md``), ``ln_f`` and ``head.w_dm``.

A configuration file that names a mechanism this family does not compute is
refused when it is loaded, never served as a dense model under its name.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference import _attention, _mm, _rmsnorm, _rope
from bench.work import Shape, Stack, as_stack

#: Keys of a published config that name sparse experts, layers of more than
#: one kind, latent attention or a rope this family does not compute.
REFUSED_KEYS = (
    "num_experts", "n_routed_experts", "num_local_experts", "layer_types",
    "mlp_layer_types", "kv_lora_rank", "q_lora_rank", "rope_parameters",
    "partial_rotary_factor",
)
#: The published model classes this family was written for.
ARCHITECTURES = ("LlamaForCausalLM", "MistralForCausalLM")


def _refuse_what_it_lacks(conf: Dict) -> None:
    name = conf.get("name")
    for key in REFUSED_KEYS:
        if key in conf:
            raise ValueError(f"{name}: {key!r} names a mechanism the dense_decoder "
                             f"family does not compute; name a family that does")
    for key in ("attention_bias", "mlp_bias"):
        if conf.get(key):
            raise ValueError(f"{name}: {key!r} true: the dense_decoder family "
                             f"computes no bias")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: 'hidden_act' {conf['hidden_act']!r}: the "
                         f"dense_decoder family computes a SiLU-gated MLP")
    if conf.get("rope_scaling") is not None:
        raise ValueError(f"{name}: 'rope_scaling' is not computed by the "
                         f"dense_decoder family")
    if conf.get("tie_word_embeddings"):
        raise ValueError(f"{name}: 'tie_word_embeddings' true: the dense_decoder "
                         f"reference reads an untied head")
    for arch in conf.get("architectures", ()):
        if arch not in ARCHITECTURES:
            raise ValueError(f"{name}: 'architectures' {arch!r} is not one of "
                             f"{ARCHITECTURES}, which the dense_decoder family computes")


def _head_dim(conf: Dict) -> int:
    return conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"]


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import LayerSpec, ModelConfig

    _refuse_what_it_lacks(conf)
    window = conf.get("sliding_window")
    theta = float(conf["rope_theta"])
    return ModelConfig(
        name=conf["name"],
        d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=_head_dim(conf),
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        layer_pattern=(LayerSpec(window=window, rope_theta=theta),),
        rope_theta=theta,
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        max_seq_len=conf["max_position_embeddings"],
        compute_dtype=conf["torch_dtype"],
    )


def work_stack(conf: Dict) -> Stack:
    """The widths ``work.py`` counts with: one group of alike layers."""
    return as_stack(Shape(
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=_head_dim(conf),
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        window=conf.get("sliding_window"),
    ))


def forward(params, tokens, score_pos, conf: Dict, quantized: bool):
    """Reference logits at ``score_pos`` of the padded ``tokens``."""
    return _forward(params, tokens, score_pos, _conf_key(conf), quantized)


@functools.partial(jax.jit, static_argnames=("conf_key", "quantized"))
def _forward(params, tokens, score_pos, conf_key, quantized):
    conf = dict(conf_key)
    hq, hkv, hd = conf["heads"], conf["kv_heads"], conf["head_dim"]
    window, theta = conf["window"], conf["theta"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(params["embed"]["table_vd"])[tokens]
    pos = jnp.arange(tokens.shape[0])
    s = tokens.shape[0]

    def layer(x, p):
        a = p["attn"]
        h = _rmsnorm(x, f32(p["ln1"]["scale_r"]))
        q = _mm(h, f32(a["wq_dm"]), quantized).reshape(s, hq, hd)
        k = _mm(h, f32(a["wk_dm"]), quantized).reshape(s, hkv, hd)
        v = _mm(h, f32(a["wv_dm"]), quantized).reshape(s, hkv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q, k, v, window, quantized).reshape(s, hq * hd)
        x = x + _mm(o, f32(a["wo_md"]), quantized)
        m = p["mlp"]
        h = _rmsnorm(x, f32(p["ln2"]["scale_r"]))
        g = jax.nn.silu(_mm(h, f32(m["wi_gate_dm"]), quantized))
        u = _mm(h, f32(m["wi_up_dm"]), quantized)
        return x + _mm(g * u, f32(m["wo_md"]), quantized), None

    x, _ = jax.lax.scan(layer, x, params["layers"][0])
    x = _rmsnorm(x[score_pos], f32(params["ln_f"]["scale_r"]))
    return _mm(x, f32(params["head"]["w_dm"]), quantized)


def _conf_key(conf: Dict):
    """The hashable slice of a configuration file the reference needs."""
    return tuple(sorted({
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": _head_dim(conf),
        "window": conf.get("sliding_window"),
        "theta": float(conf["rope_theta"]),
    }.items()))
