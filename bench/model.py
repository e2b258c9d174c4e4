"""A configuration file turned into the program's model and its weights.

The model and the widths come from the configuration's family
(``bench/families/``). The weights are the benchmark's, not the program's:
one jitted call makes every leaf on the device from ``--seed``, in the
structure and type the program serves (``transformer.init_model``'s tree),
and the plain reference reads the same arrays. Nothing the program
initialises is used.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

from bench import registry
from bench.work import Stack

#: Standard deviation of the RMSNorm gains around 1 (stored as 1 + scale).
NORM_SCALE_STD = 0.1


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file, from the
    family it names (``registry.load_family``)."""
    return registry.load_family(conf).model_config(conf)


def work_shape(conf: Dict) -> Stack:
    """The widths ``work.py`` counts with, from the configuration's family."""
    return registry.load_family(conf).work_stack(conf)


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
