"""A test-only family: sliding-window and global GQA layers in one repeating
pattern, each kind with its own rope theta (the gemma3 / Mellum layout).
``tiny.make_root`` writes it as ``bench/families/windowed_global.py`` of a
temporary checkout; no file of the benchmark knows it.

Keys beside the dense decoder's: ``layer_types``, one per pattern position,
``"sliding_attention"`` or ``"full_attention"``; ``sliding_window`` and
``rope_local_base_freq`` for sliding layers; ``rope_theta`` for full ones.
Every pattern position is its own stack of weights in the served tree
(``layers[i]``, stacked over periods), and the reference reads each one.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench.reference import _attention, _mm, _rmsnorm, _rope
from bench.work import Shape, Stack


def _kinds(conf: Dict) -> List[Tuple[Optional[int], float]]:
    """(window, rope theta) of each pattern position."""
    out = []
    for t in conf["layer_types"]:
        if t == "sliding_attention":
            out.append((conf["sliding_window"], float(conf["rope_local_base_freq"])))
        elif t == "full_attention":
            out.append((None, float(conf["rope_theta"])))
        else:
            raise ValueError(f"{conf['name']}: layer type {t!r}")
    if conf["num_hidden_layers"] % len(out):
        raise ValueError(f"{conf['name']}: {conf['num_hidden_layers']} layers are "
                         f"not whole periods of {len(out)}")
    return out


def _head_dim(conf: Dict) -> int:
    return conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"]


def model_config(conf: Dict):
    from repro.configs.base import LayerSpec, ModelConfig

    return ModelConfig(
        name=conf["name"],
        d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=_head_dim(conf),
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        layer_pattern=tuple(LayerSpec(window=w, rope_theta=th) for w, th in _kinds(conf)),
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=False,
        max_seq_len=conf["max_position_embeddings"],
        compute_dtype=conf["torch_dtype"],
    )


def work_stack(conf: Dict) -> Stack:
    """One group per layer kind, each counted at its own window."""
    kinds = _kinds(conf)
    periods = conf["num_hidden_layers"] // len(kinds)
    groups = []
    for window in dict.fromkeys(w for w, _ in kinds):
        count = periods * sum(1 for w, _ in kinds if w == window)
        groups.append((count, Shape(
            n_layers=count, d_model=conf["hidden_size"],
            n_heads=conf["num_attention_heads"],
            n_kv_heads=conf["num_key_value_heads"], head_dim=_head_dim(conf),
            d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
            window=window)))
    return Stack(conf["vocab_size"], conf["hidden_size"], tuple(groups))


def forward(params, tokens, score_pos, conf: Dict, quantized: bool):
    key = (conf["num_attention_heads"], conf["num_key_value_heads"],
           _head_dim(conf), tuple(_kinds(conf)))
    return _forward(params, tokens, score_pos, key, quantized)


@functools.partial(jax.jit, static_argnames=("key", "quantized"))
def _forward(params, tokens, score_pos, key, quantized):
    hq, hkv, hd, kinds = key
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(params["embed"]["table_vd"])[tokens]
    pos = jnp.arange(tokens.shape[0])
    s = tokens.shape[0]

    def layer(x, p, window, theta):
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
        return x + _mm(g * u, f32(m["wo_md"]), quantized)

    def period(x, ps):
        for p, (window, theta) in zip(ps, kinds):
            x = layer(x, p, window, theta)
        return x, None

    x, _ = jax.lax.scan(period, x, params["layers"])
    x = _rmsnorm(x[score_pos], f32(params["ln_f"]["scale_r"]))
    return _mm(x, f32(params["head"]["w_dm"]), quantized)
