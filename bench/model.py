"""A configuration file turned into the program's model and its weights.

The weights are the benchmark's, not the program's: one jitted call makes
every leaf on the device from ``--seed``, in the structure and type the
program serves (``transformer.init_model``'s tree), and the plain reference
reads the same arrays. Nothing the program initialises is used.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

from bench.work import Shape

#: Standard deviation of the RMSNorm gains around 1 (stored as 1 + scale).
NORM_SCALE_STD = 0.1


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import LayerSpec, ModelConfig

    head_dim = conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"]
    window = conf.get("sliding_window")
    theta = float(conf["rope_theta"])
    return ModelConfig(
        name=conf["name"],
        d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=head_dim,
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        layer_pattern=(LayerSpec(window=window, rope_theta=theta),),
        rope_theta=theta,
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        max_seq_len=conf["max_position_embeddings"],
        compute_dtype=conf["torch_dtype"],
    )


def work_shape(conf: Dict) -> Shape:
    """The widths ``work.py`` counts with."""
    return Shape(
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        window=conf.get("sliding_window"),
    )


def key_data(seed: int) -> np.ndarray:
    """Two 32-bit words of key material from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _std(path, shape) -> float:
    name = str(getattr(path[-1], "key", path[-1]))
    if name.startswith("table"):
        return shape[-1] ** -0.5
    if len(shape) == 1 or name.startswith("scale"):
        return NORM_SCALE_STD
    return 1.0 / math.sqrt(shape[-2])


def make_params(cfg, seed: int):
    """Every weight of ``cfg`` from ``seed``, made on the device in one
    jitted call, in the program's tree and dtypes."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer

    shapes = jax.eval_shape(lambda k: transformer.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(kd):
        key = jax.random.wrap_key_data(kd)
        leaves = []
        for i, (path, sd) in enumerate(flat):
            std = _std(path, sd.shape[1:] if _stacked(path) else sd.shape)
            x = jax.random.normal(jax.random.fold_in(key, i), sd.shape, jnp.float32)
            leaves.append((x * std).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(jnp.asarray(key_data(seed)))


def _stacked(path) -> bool:
    """Leaves under ``layers`` carry a leading axis of scanned periods."""
    return str(getattr(path[0], "key", path[0])) == "layers"
