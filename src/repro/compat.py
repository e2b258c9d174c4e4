"""JAX API surface the repo funnels through one module.

The supported JAX is 0.9.0 (``requirements-dev.txt``, ``pyproject.toml``).
Every API that has been renamed across JAX releases goes through here, so
a JAX bump is a one-file change:

  * ``tpu_compiler_params(...)`` — Pallas TPU compiler params
    (``pltpu.CompilerParams``). Callers pass the grid-dimension semantics
    :data:`PARALLEL` / :data:`ARBITRARY` re-exported here.
  * ``make_mesh(...)`` — ``jax.make_mesh`` with ``axis_types`` spelled as
    :data:`AXIS_AUTO` / :data:`AXIS_EXPLICIT` / :data:`AXIS_MANUAL`.
  * backend / interpret detection — ``default_backend()`` / ``on_tpu()`` /
    ``use_interpret()`` centralize the "can this host lower Mosaic?" test
    that the kernels, ops dispatch and models previously duplicated.
  * ``enable_compilation_cache()`` — JAX's persistent compilation cache at
    ``JAX_COMPILATION_CACHE_DIR`` when that is set, else at the fixed
    :data:`DEFAULT_CACHE_DIR` inside the checkout.
  * ``donating_jit(...)`` — ``jax.jit`` with ``donate_argnums`` that stays
    quiet on backends where donation is unsupported (CPU XLA warns
    "Some donated buffers were not usable" on every call).

No other module may name the version-dependent symbols directly (the
list is ``analysis.lint._VERSIONED_JAX``) — the
``compat-only-versioned-jax`` linter rule
(``repro.analysis.lint``, run by CI as ``python -m repro.analysis --strict``
and by tier-1 via ``tests/test_mapping_resolver.py``) enforces this over
the AST.
"""

from __future__ import annotations

import functools
import pathlib
import warnings
from typing import Callable, Optional, Sequence

import jax
from jax.experimental.pallas import tpu as pltpu

#: Grid-dimension semantics for ``tpu_compiler_params``.
PARALLEL = pltpu.GridDimensionSemantics.PARALLEL
ARBITRARY = pltpu.GridDimensionSemantics.ARBITRARY

#: Mesh axis types for ``make_mesh``.
AXIS_AUTO = jax.sharding.AxisType.Auto
AXIS_EXPLICIT = jax.sharding.AxisType.Explicit
AXIS_MANUAL = jax.sharding.AxisType.Manual

#: Where the persistent compilation cache lives when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set: one fixed path inside the
#: checkout (the path is part of the cache key, so it must not move
#: between runs). Listed in ``.gitignore``.
DEFAULT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def tpu_compiler_params(
    *, dimension_semantics: Optional[Sequence] = None, **kwargs
):
    """Build ``pltpu.CompilerParams``. ``dimension_semantics`` entries are
    :data:`PARALLEL` / :data:`ARBITRARY`; remaining kwargs
    (``vmem_limit_bytes``, ...) are forwarded unchanged."""
    return pltpu.CompilerParams(
        dimension_semantics=(
            None if dimension_semantics is None else tuple(dimension_semantics)
        ),
        **kwargs,
    )


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    axis_types: Optional[Sequence] = None,
    devices=None,
) -> jax.sharding.Mesh:
    """``jax.make_mesh``; ``axis_types`` entries are :data:`AXIS_AUTO` /
    :data:`AXIS_EXPLICIT` / :data:`AXIS_MANUAL`."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=None if axis_types is None else tuple(axis_types),
        devices=devices,
    )


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    A directory already configured (``JAX_COMPILATION_CACHE_DIR``, which
    JAX reads at import) is kept as it is; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`. Every compilation is cached, however small
    or fast, so a second process with the same programs compiles nothing.
    Program entry points call this once; the library never does.
    """
    cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir is None:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def donating_jit(
    fn: Callable,
    *,
    donate_argnums: Sequence[int] = (),
    static_argnums: Sequence[int] = (),
) -> Callable:
    """``jax.jit`` with buffer donation, quiet where donation is a no-op.

    On TPU/GPU the donated KV-cache buffers are reused in place (the decode
    scan's carry aliases its input, halving peak HBM for the caches). CPU
    XLA cannot alias them and emits a ``UserWarning`` per call; that
    warning is filtered here so CI logs stay readable — behaviour is
    unchanged either way (donation is an optimization hint).
    """
    jitted = jax.jit(
        fn,
        donate_argnums=tuple(donate_argnums),
        static_argnums=tuple(static_argnums),
    )

    @functools.wraps(fn)
    def call(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*[Dd]onat", category=UserWarning
            )
            return jitted(*args, **kwargs)

    call.jitted = jitted
    return call


def default_backend() -> str:
    """The platform jit lowers to by default: 'tpu' | 'gpu' | 'cpu'."""
    return jax.default_backend()


def on_tpu() -> bool:
    return default_backend() == "tpu"


def use_interpret(backend: Optional[str] = None) -> bool:
    """True when Pallas TPU kernels must run in interpret mode.

    Mosaic lowering exists only on TPU; every other backend (CPU hosts,
    dry-runs, CI) gets the Python interpreter so the same kernel code is
    runnable everywhere.
    """
    return (backend or default_backend()) != "tpu"
