"""Tests for the compat layer and the auto mapping resolver.

Covers the PR's acceptance criteria directly:
  * ``resolve_mapping`` prefers kv-resident head-first exactly when
    ``4*S*D*dtype`` (K and V, double-buffered) fits the VMEM budget
    (``MappingConfig.resolve_resident``),
  * the HBM traffic model never reports reuse_efficiency > 1,
  * no versioned JAX API (CompilerParams / TPUCompilerParams / AxisType)
    is referenced outside ``src/repro/compat.py``.
"""

import jax
import jax.numpy as jnp
import pytest

from repro import compat
from repro.kernels import ops
from repro.kernels.flash_attention import (
    BLOCK_FIRST,
    HEAD_FIRST,
    MappingConfig,
    hbm_block_fetches,
)


# --- resolve_mapping ---------------------------------------------------------


@pytest.mark.parametrize(
    "seq", [1024, 4096, 49152, 49152 + 128, 131072, 131072 + 128, 262144]
)
def test_resolver_residency_matches_vmem_budget(seq):
    """kv_resident head-first is chosen exactly when 4*S*D*dtype fits VMEM."""
    d, dtype_bytes = 128, 2
    mc = ops.resolve_mapping((1, 16, 4, seq, seq, d), dtype_bytes=dtype_bytes)
    fits = MappingConfig().resolve_resident(seq, d, dtype_bytes)
    # (budget boundary: 4*49152*128*2 == 48 MiB fits; one block more spills)
    assert fits == (seq <= 49152)
    assert mc.kv_resident == fits
    assert mc.vmem_limit_bytes == MappingConfig().vmem_limit_bytes
    assert mc.order == HEAD_FIRST
    assert mc.acc_parallel


def test_resolver_respects_explicit_budget():
    seq, d = 8192, 128
    assert ops.resolve_mapping((1, 8, 8, seq, seq, d)).kv_resident
    tiny = ops.resolve_mapping(
        (1, 8, 8, seq, seq, d), vmem_budget_bytes=seq * d  # << 4*S*D*2
    )
    assert not tiny.kv_resident


def test_resolver_is_cached_and_hashable():
    a = ops.resolve_mapping((2, 8, 2, 2048, 2048, 64))
    b = ops.resolve_mapping((2, 8, 2, 2048, 2048, 64))
    assert a is b  # same LRU entry
    hash(a)  # usable as a custom_vjp nondiff arg


def test_resolver_backends_agree_on_headline_result(monkeypatch):
    """Every modeled backend prefers the paper's swizzled head-first when
    K/V fits; the paper's Fig. 12 headline is backend-independent. The TPU
    is priced as the v5e its device kind names."""
    from repro.kernels import plan as plan_lib

    monkeypatch.setattr(plan_lib, "tpu_device_kind", lambda: "TPU v5 lite")
    for backend in ("cpu", "gpu", "tpu"):
        mc = ops.resolve_mapping((8, 32, 8, 8192, 8192, 128), backend)
        assert (mc.order, mc.kv_resident) == (HEAD_FIRST, True), backend


def test_flash_attention_auto_mapping_runs():
    """ops.flash_attention(mapping=None) resolves and matches the oracle."""
    from repro.kernels import ref

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, impl="pallas")
    o_ref = ref.attention(q, k, v, causal=True)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


# --- HBM traffic model -------------------------------------------------------


@pytest.mark.parametrize("seq_q,seq_kv", [(4096, 4096), (200, 2048), (384, 260)])
@pytest.mark.parametrize("order", [HEAD_FIRST, BLOCK_FIRST])
@pytest.mark.parametrize("kv_resident", [True, False])
def test_reuse_efficiency_bounded(seq_q, seq_kv, order, kv_resident):
    r = hbm_block_fetches(
        batch=2, num_q_heads=16, num_kv_heads=4, seq_q=seq_q, seq_kv=seq_kv,
        head_dim=128,
        mapping=MappingConfig(order=order, kv_resident=kv_resident),
    )
    assert 0.0 < r["reuse_efficiency"] <= 1.0
    assert r["total_bytes"] == r["kv_bytes"] + r["q_bytes"]
    assert r["total_bytes"] >= r["ideal_bytes"]


def test_sawtooth_wins_long_context_streaming():
    """ROADMAP 5(a): once K/V spills the VMEM budget (streaming), the
    serpentine sawtooth traversal shares one boundary tile per KV sweep,
    the exact traffic model prices it strictly below linear at equal
    modeled time, and the resolver's tie-break picks it."""
    import dataclasses

    from repro.core import swizzle

    mc = ops.resolve_mapping((1, 16, 4, 262144, 262144, 128), dtype_bytes=2)
    assert not mc.kv_resident          # 256K KV never fits residency
    assert mc.order == HEAD_FIRST
    assert mc.traversal == swizzle.SAWTOOTH
    kw = dict(batch=1, num_q_heads=16, num_kv_heads=4, seq_q=262144,
              seq_kv=262144, head_dim=128, dtype_bytes=2)
    saw = hbm_block_fetches(mapping=mc, **kw)
    lin = hbm_block_fetches(
        mapping=dataclasses.replace(mc, traversal=swizzle.LINEAR), **kw
    )
    assert saw["kv_bytes"] < lin["kv_bytes"]
    assert saw["total_bytes"] < lin["total_bytes"]
    assert 0.0 < saw["reuse_efficiency"] <= 1.0


def test_sawtooth_streaming_kernel_matches_oracle():
    """The serpentine kv index_map + in-kernel tile remap is numerically
    the same attention: odd sweeps visit tiles in reverse, online softmax
    is order-independent up to float tolerance."""
    from repro.core import swizzle
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention_fwd

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 384, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 384, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 384, 64), jnp.float32)
    mc = MappingConfig(kv_resident=False, block_m=128, block_n=128,
                       traversal=swizzle.SAWTOOTH)
    o, _ = flash_attention_fwd(
        q, k, v, mapping=mc, causal=True, interpret=compat.use_interpret()
    )
    o_ref = ref.attention(q, k, v, causal=True)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


def test_streaming_traffic_counts_tiles():
    """The streaming sweep is num_n tiles per (head, q-block) — a ceil-padded
    seq_kv pays for whole tiles, not raw bytes (the pre-fix math ignored
    num_n and silently under-counted the padded case)."""
    common = dict(batch=1, num_q_heads=4, num_kv_heads=4, seq_q=256,
                  head_dim=64)
    mc = MappingConfig(kv_resident=False)  # block_n = 128
    exact = hbm_block_fetches(seq_kv=256, mapping=mc, **common)
    padded = hbm_block_fetches(seq_kv=257, mapping=mc, **common)  # 3 tiles
    assert padded["kv_bytes"] == exact["kv_bytes"] * 3 // 2


# --- compat layer ------------------------------------------------------------


def test_compat_compiler_params_builds():
    p = compat.tpu_compiler_params(
        dimension_semantics=(compat.PARALLEL, compat.ARBITRARY)
    )
    assert p is not None
    # kwargs pass through to whichever dataclass the installed JAX has
    p2 = compat.tpu_compiler_params(
        dimension_semantics=(compat.PARALLEL,), vmem_limit_bytes=1 << 20
    )
    assert p2.vmem_limit_bytes == 1 << 20


def test_compat_make_mesh_host():
    n = len(jax.devices())
    mesh = compat.make_mesh(
        (n, 1), ("data", "model"),
        axis_types=(compat.AXIS_AUTO, compat.AXIS_AUTO),
    )
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.size == n


def test_compat_interpret_detection():
    assert compat.use_interpret("cpu")
    assert not compat.use_interpret("tpu")
    assert compat.use_interpret() == (not compat.on_tpu())


def test_installed_jax_is_the_pinned_one():
    """One installation: the JAX the compat layer is written for is the
    one ``requirements-dev.txt`` and ``pyproject.toml`` pin."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    pin = f"jax=={jax.__version__}"
    assert pin in (root / "requirements-dev.txt").read_text()
    assert f'"{pin}"' in (root / "pyproject.toml").read_text()


def test_no_versioned_jax_api_outside_compat():
    """The next JAX bump must be a one-file change: only compat.py may name
    the version-dependent symbols. The contract's single implementation is
    the linter's ``compat-only-versioned-jax`` rule (repro.analysis.lint);
    this test just runs it over the live tree."""
    from repro.analysis import run_rules

    assert run_rules(rules=["compat-only-versioned-jax"]) == []


def test_compilation_cache_dir_is_fixed_or_from_env():
    """With no directory configured the cache lands at the fixed,
    git-ignored path in the checkout; a configured one (what
    JAX_COMPILATION_CACHE_DIR sets) is kept."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert compat.enable_compilation_cache() == compat.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compat.DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        assert compat.enable_compilation_cache() == "/elsewhere"
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
    path = pathlib.Path(compat.DEFAULT_CACHE_DIR)
    assert path.parent == root
    assert f"{path.name}/" in (root / ".gitignore").read_text().split()
