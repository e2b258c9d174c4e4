"""Main-path Pallas kernels compile for a TPU v5e at gemma2-2b widths.

Every kernel the serving path runs is lowered and compiled ahead of time
for a described (not attached) ``v5e:2x2`` topology by the TPU compiler
installed with JAX: what Mosaic refuses here (untileable block shapes, more
scoped VMEM than the kernel may use, a kernel that cannot be partitioned)
would fail the first chip run. Nothing executes, so these tests say nothing
about results or times. The topology is described inside a fixture, never
at import, and the persistent compilation cache is off around the compiles
(entries written for a described chip cannot be read back).

Widths are gemma2-2b's (``configs/gemma2_2b.py``): 8 query heads, 4 KV
heads, head_dim 256, bf16 activations, softcap 50, local window 4096.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import plan as plan_lib
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.paged_decode_attention import (
    paged_flash_decode, paged_latent_decode,
)
from repro.kernels.paged_prefill_attention import (
    paged_flash_prefill, paged_latent_prefill,
)

HQ, HKV, D = 8, 4, 256
SOFTCAP, WINDOW = 50.0, 4096
BATCH, PAGE, MAX_PAGES, NUM_PAGES = 8, 16, 64, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def v5e_kind(monkeypatch):
    """Plans for backend "tpu" priced as the v5e the topology describes."""
    monkeypatch.setattr(plan_lib, "tpu_device_kind", lambda: "TPU v5 lite")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("seq,window", [
    (512, None), (512, WINDOW), (8192, None), (8192, WINDOW), (16384, None),
])
def test_flash_forward_compiles_at_prefill_buckets(one_chip, v5e_kind,
                                                   seq, window):
    """The prefill plan's schedule compiles at every bucket, resident K/V
    included: the VMEM budget and the compiler limit come from the chip."""
    plan = plan_lib.plan_attention(
        (1, HQ, HKV, seq, seq, D), backend="tpu", window=window,
    )
    assert plan.impl == "pallas" and not plan.interpret
    assert plan.mapping.kv_resident  # 4*S*D*2 <= 48 MiB up to S = 24576

    def fwd(q, k, v):
        return flash_attention_fwd(
            q, k, v, mapping=plan.mapping, softcap=SOFTCAP, window=window,
        )

    _compile(fwd, _sds(one_chip, (1, HQ, seq, D)),
             _sds(one_chip, (1, HKV, seq, D)), _sds(one_chip, (1, HKV, seq, D)))


@pytest.mark.parametrize("resident", [True, False])
def test_flash_forward_and_backward_compile(one_chip, v5e_kind, resident):
    """The forward's (bm, 1) lse blocks feed the backward kernels."""
    plan = plan_lib.plan_attention((1, HQ, HKV, 512, 512, D), backend="tpu")
    mapping = dataclasses.replace(plan.mapping, kv_resident=resident)

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, softcap=SOFTCAP, mapping=mapping,
                                plan=plan)
        return jnp.sum(o.astype(jnp.float32))

    args = (_sds(one_chip, (1, HQ, 512, D)), _sds(one_chip, (1, HKV, 512, D)),
            _sds(one_chip, (1, HKV, 512, D)))
    _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)


def _paged_args(sharding, kv_dtype):
    pages = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    args = [
        _sds(sharding, (BATCH, HQ, D)),
        _sds(sharding, (HKV, NUM_PAGES, PAGE, D), pages),
        _sds(sharding, (HKV, NUM_PAGES, PAGE, D), pages),
        _sds(sharding, (BATCH, MAX_PAGES), jnp.int32),
        _sds(sharding, (BATCH,), jnp.int32),
    ]
    if kv_dtype == "int8":
        args += [_sds(sharding, (HKV, NUM_PAGES), jnp.float32)] * 2
    return args


@pytest.mark.parametrize("num_splits", [1, 4])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles(one_chip, num_splits, kv_dtype):
    def decode(q, kp, vp, pt, ln, ks=None, vs=None):
        return paged_flash_decode(
            q, kp, vp, pt, ln, softcap=SOFTCAP, window=WINDOW,
            num_splits=num_splits, k_scales=ks, v_scales=vs,
        )

    _compile(decode, *_paged_args(one_chip, kv_dtype))


#: The benchmark cells' decode shapes (``bench/``): rows, query heads, KV
#: heads, table pages, pool pages, window; head 128, pages of 16.
CELL_DECODE = {
    "deepseek-llm-7b.session": (16, 32, 32, 256, 16 * 256 + 1 + 256, None),
    "mistral-7b.chat": (32, 32, 8, 120, 32 * 120 + 1 + 256, 4096),
}


@pytest.mark.parametrize("cell", sorted(CELL_DECODE))
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles_at_cell_shapes(one_chip, v5e_kind, cell,
                                              kv_dtype):
    """The decode plan's block walk at both cells' widths: a (head, page)
    scale table of the session pool would not fit SMEM whole."""
    b, hq, hkv, mp, num_pages, window = CELL_DECODE[cell]
    plan = plan_lib.plan_attention(
        (b, hq, hkv, 1, mp * PAGE, 128), phase=plan_lib.DECODE,
        kv_layout=plan_lib.PAGED, page_size=PAGE, window=window,
        backend="tpu", kv_dtype="int8" if kv_dtype == "int8" else "fp32",
    )
    pages = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    args = [
        _sds(one_chip, (b, hq, 128)),
        _sds(one_chip, (hkv, num_pages, PAGE, 128), pages),
        _sds(one_chip, (hkv, num_pages, PAGE, 128), pages),
        _sds(one_chip, (b, mp), jnp.int32),
        _sds(one_chip, (b,), jnp.int32),
    ]
    if kv_dtype == "int8":
        args += [_sds(one_chip, (hkv, num_pages), jnp.float32)] * 2

    def decode(q, kp, vp, pt, ln, ks=None, vs=None):
        return paged_flash_decode(
            q, kp, vp, pt, ln, window=window, num_splits=plan.num_splits,
            pages_per_block=plan.pages_per_block, k_scales=ks, v_scales=vs,
        )

    _compile(decode, *args)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_prefill_compiles(one_chip, kv_dtype):
    tail, prefix_pages = 128, 8
    pages = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    args = [
        _sds(one_chip, (2, HQ, tail, D)),
        _sds(one_chip, (HKV, NUM_PAGES, PAGE, D), pages),
        _sds(one_chip, (HKV, NUM_PAGES, PAGE, D), pages),
        _sds(one_chip, (2, prefix_pages), jnp.int32),
        _sds(one_chip, (2, HKV, tail, D)),
        _sds(one_chip, (2, HKV, tail, D)),
        _sds(one_chip, (2,), jnp.int32),
        _sds(one_chip, (2,), jnp.int32),
    ]
    if kv_dtype == "int8":
        args += [_sds(one_chip, (HKV, NUM_PAGES), jnp.float32)] * 2

    def prefill(q, kp, vp, pt, kt, vt, pl_, tl, ks=None, vs=None):
        return paged_flash_prefill(
            q, kp, vp, pt, kt, vt, pl_, tl, softcap=SOFTCAP, window=WINDOW,
            k_scales=ks, v_scales=vs,
        )

    _compile(prefill, *args)


#: The latent cell's shapes (``deepseek-v2-lite.session-wide``): 64 rows,
#: 16 query heads on one latent pool of 576-wide rows (lane-padded to 640),
#: 512 of them the values, pages of 16, 6144-token contexts.
LATENT = dict(rows=64, heads=16, width=640, v_dim=512, pages=384,
              pool=64 * 384 + 1 + 256)


@pytest.mark.parametrize("num_splits", [1, 2])
def test_latent_decode_compiles_at_cell_shapes(one_chip, v5e_kind, num_splits):
    c = LATENT
    plan = plan_lib.plan_attention(
        (c["rows"], c["heads"], 1, 1, c["pages"] * PAGE, c["width"]),
        phase=plan_lib.DECODE, kv_layout=plan_lib.PAGED, page_size=PAGE,
        backend="tpu", latent=True,
    )
    assert plan.impl == "pallas"

    def decode(q, pool, pt, ln):
        return paged_latent_decode(
            q, pool, pt, ln, v_dim=c["v_dim"], scale=0.1,
            num_splits=num_splits, pages_per_block=plan.pages_per_block)

    compiled = _compile(
        decode, _sds(one_chip, (c["rows"], c["heads"], c["width"])),
        _sds(one_chip, (1, c["pool"], PAGE, c["width"])),
        _sds(one_chip, (c["rows"], c["pages"]), jnp.int32),
        _sds(one_chip, (c["rows"],), jnp.int32))
    assert "paged_latent_decode" in compiled.as_text()


@pytest.mark.parametrize("tail,prefix_pages", [(1024, 512), (6144, 0)])
def test_latent_prefill_compiles_at_cell_shapes(one_chip, tail, prefix_pages):
    """An extend over the largest prefix bucket, and a fresh history."""
    c = LATENT
    q = _sds(one_chip, (1, tail, c["heads"], c["width"]))
    lat = _sds(one_chip, (1, tail, c["width"]))
    lens = [_sds(one_chip, (1,), jnp.int32)] * 2
    if prefix_pages:
        def prefill(q, pool, pt, lat, pl_, tl):
            return paged_latent_prefill(q, pool, pt, lat, pl_, tl,
                                        v_dim=c["v_dim"], scale=0.1)

        args = [q, _sds(one_chip, (1, c["pool"], PAGE, c["width"])),
                _sds(one_chip, (1, prefix_pages), jnp.int32), lat, *lens]
    else:
        def prefill(q, lat, pl_, tl):
            return paged_latent_prefill(q, None, None, lat, pl_, tl,
                                        v_dim=c["v_dim"], scale=0.1)

        args = [q, lat, *lens]
    compiled = _compile(prefill, *args)
    assert "paged_latent_prefill" in compiled.as_text()


@pytest.mark.parametrize("num_splits", [1, 4])
def test_dense_decode_compiles(one_chip, num_splits):
    """Dense flash-decode: ``lengths`` rides scalar prefetch into SMEM."""
    smax = 2048

    def decode(q, k, v, ln):
        return flash_decode(q, k, v, ln, softcap=SOFTCAP, window=WINDOW,
                            chunk=512, num_splits=num_splits)

    _compile(decode, _sds(one_chip, (BATCH, HQ, D)),
             _sds(one_chip, (BATCH, HKV, smax, D)),
             _sds(one_chip, (BATCH, HKV, smax, D)),
             _sds(one_chip, (BATCH,), jnp.int32))


def test_paged_decode_compiles_head_parallel_on_four_chips(topo, v5e_kind):
    """On a 4-chip serving mesh the kernel runs under shard_map, one KV
    head per chip, with the pool head-sharded as the paged backend places
    it: GSPMD alone cannot partition a Mosaic kernel."""
    mesh = Mesh(topo.devices, ("model",))
    rep = NamedSharding(mesh, P())
    heads = NamedSharding(mesh, P("model"))
    plan = plan_lib.plan_attention(
        (BATCH, HQ, HKV, 1, MAX_PAGES * PAGE, D), phase=plan_lib.DECODE,
        kv_layout=plan_lib.PAGED, page_size=PAGE, window=WINDOW,
        backend="tpu", num_devices=4,
    )
    assert plan.impl == "pallas" and not plan.interpret

    def decode(q, kp, vp, pt, ln):
        with ops.kernel_mesh(mesh):
            return ops.paged_decode_attention(
                q, kp, vp, pt, ln, softcap=SOFTCAP, window=WINDOW, plan=plan,
            )

    compiled = _compile(
        decode, _sds(rep, (BATCH, HQ, D)),
        _sds(heads, (HKV, NUM_PAGES, PAGE, D)),
        _sds(heads, (HKV, NUM_PAGES, PAGE, D)),
        _sds(rep, (BATCH, MAX_PAGES), jnp.int32),
        _sds(rep, (BATCH,), jnp.int32),
    )
    assert "all-gather" in compiled.as_text()
