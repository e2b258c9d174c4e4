"""The attention-plan layer: resolution, caching, and call-site hygiene.

Covers the PR-3 acceptance criteria directly:
  * ``plan_attention`` is the single resolver for every phase; the legacy
    entry points (``ops.resolve_mapping`` / ``ops.resolve_kv_layout``) are
    thin wrappers over it,
  * the plan LRU cache keys on **backend + interpret flag** as well as
    shape (the PR-1 resolver silently shared entries across backends in
    tests that flip ``JAX_PLATFORMS``),
  * grep enforcement: no dispatch site threads ``mapping_name`` /
    ``q_offset`` out-of-band or hand-rolls a ``MappingConfig`` past the
    plan layer.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.kernels import ops
from repro.kernels import plan as plan_lib
from repro.kernels.flash_attention import (
    HEAD_FIRST,
    PAPER_MAPPINGS,
    MappingConfig,
)


SHAPE = (2, 8, 2, 2048, 2048, 64)


@pytest.fixture
def v5e_kind(monkeypatch):
    """Price "tpu" plans as the v5e its device kind names, on a host
    that has no TPU attached."""
    monkeypatch.setattr(plan_lib, "tpu_device_kind", lambda: "TPU v5 lite")


# --- resolution ---------------------------------------------------------------


def test_plan_phases_resolve_distinct_impls():
    prefill = plan_lib.plan_attention(SHAPE, backend="cpu")
    decode = plan_lib.plan_attention(
        (2, 8, 2, 1, 2048, 64), phase=plan_lib.DECODE, backend="cpu"
    )
    extend = plan_lib.plan_attention(
        (1, 8, 2, 32, 96, 64), phase=plan_lib.EXTEND,
        kv_layout=plan_lib.PAGED, page_size=16, prefix_pages=4, backend="cpu",
    )
    assert prefill.impl == "xla_flash"
    assert decode.impl == "xla" and decode.chunk is not None
    # The headline: paged extend is the Pallas kernel on EVERY backend (no
    # gather fallback); CPU hosts run it in interpret mode.
    assert extend.impl == "pallas" and extend.interpret
    assert extend.prefix_capacity == 64
    # Dense extend stays the legacy XLA q_offset oracle (pallas cannot
    # carry the offset).
    dense_ext = plan_lib.plan_attention(
        (1, 8, 2, 32, 96, 64), phase=plan_lib.EXTEND, backend="cpu",
    )
    assert dense_ext.impl == "xla_flash"
    # An explicitly pinned compiled CPU impl never lands on the
    # interpreter: paged extend coerces it to the compiled gather oracle.
    pinned = plan_lib.plan_attention(
        (1, 8, 2, 32, 96, 64), phase=plan_lib.EXTEND,
        kv_layout=plan_lib.PAGED, page_size=16, prefix_pages=4,
        backend="cpu", impl="xla_flash",
    )
    assert pinned.impl == "xla"


def test_plan_on_tpu_backend_targets_mosaic(v5e_kind):
    p = plan_lib.plan_attention(SHAPE, backend="tpu")
    assert p.impl == "pallas" and not p.interpret


def test_plan_cache_keys_on_backend_and_interpret(v5e_kind):
    """Same shape, different backend / interpret flag -> distinct entries;
    identical key -> the same LRU object."""
    cpu = plan_lib.plan_attention(SHAPE, backend="cpu")
    tpu = plan_lib.plan_attention(SHAPE, backend="tpu")
    assert cpu is not tpu
    assert (cpu.backend, cpu.impl) != (tpu.backend, tpu.impl)
    forced = plan_lib.plan_attention(SHAPE, backend="tpu", interpret=True)
    assert forced is not tpu and forced.interpret
    again = plan_lib.plan_attention(SHAPE, backend="cpu")
    assert again is cpu
    hash(cpu)  # usable as a jit-closure constant / custom_vjp nondiff arg


def test_topology_is_keyed_on_device_kind(monkeypatch):
    """A TPU is priced as the chip its device kind names; an unknown kind
    raises instead of borrowing another chip's figures; CPU hosts use the
    named rehearsal topology."""
    from repro.core import numa

    monkeypatch.setattr(plan_lib, "tpu_device_kind", lambda: "TPU v5 lite")
    assert plan_lib.topology_for("tpu") is numa.TPU_V5E
    budget, limit = plan_lib.vmem_limits("tpu")
    assert limit == numa.TPU_V5E.cache_bytes * 3 // 4 and budget == limit // 2
    monkeypatch.setattr(plan_lib, "tpu_device_kind", lambda: "TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        plan_lib.topology_for("tpu")
    assert plan_lib.topology_for("cpu") is numa.CPU_REHEARSAL
    assert numa.CPU_REHEARSAL.name == "cpu_rehearsal"


def test_record_plans_sees_cache_hits():
    """record_plans collects every plan resolved inside it, including
    LRU hits, and nothing after the block closes."""
    plan_lib.plan_attention(SHAPE, backend="cpu")  # warm the cache
    with plan_lib.record_plans() as plans:
        a = plan_lib.plan_attention(SHAPE, backend="cpu")
        b = plan_lib.plan_attention(
            (2, 8, 2, 1, 2048, 64), phase=plan_lib.DECODE, backend="cpu"
        )
    plan_lib.plan_attention(SHAPE, backend="cpu")
    assert plans == [a, b]


def test_plan_decode_chunk_prefers_capacity_divisor():
    # 2048 divides by the resolver's block_n (128) -> chunk 128, no pad.
    even = plan_lib.plan_attention(
        (2, 8, 2, 1, 2048, 64), phase=plan_lib.DECODE, backend="cpu"
    )
    assert even.chunk and 2048 % even.chunk == 0
    # An odd capacity picks the largest sublane-multiple divisor.
    odd = plan_lib.plan_attention(
        (2, 8, 2, 1, 2000, 64), phase=plan_lib.DECODE, backend="cpu"
    )
    assert odd.chunk and 2000 % odd.chunk == 0 and odd.chunk % 8 == 0


def test_plan_pinned_mapping_and_bad_names():
    p = plan_lib.plan_attention(
        SHAPE, backend="cpu", mapping_name="naive_block_first"
    )
    assert p.mapping is PAPER_MAPPINGS["naive_block_first"]
    with pytest.raises(KeyError):
        plan_lib.plan_attention(SHAPE, backend="cpu", mapping_name="nope")
    with pytest.raises(ValueError):
        plan_lib.plan_attention(SHAPE, phase="warmup")
    with pytest.raises(ValueError):
        plan_lib.plan_attention(SHAPE, kv_layout=plan_lib.PAGED)  # no page_size


def test_plan_for_config_reads_policy():
    cfg = registry.get_smoke_config("llama3-8b")
    shape = (1, cfg.n_heads, cfg.n_kv_heads, 64, 64, cfg.head_dim)
    p = plan_lib.plan_for_config(cfg, shape)
    assert p.mapping.order == HEAD_FIRST
    pinned = plan_lib.with_mapping(cfg, "swizzled_block_first")
    p2 = plan_lib.plan_for_config(pinned, shape)
    assert p2.mapping is PAPER_MAPPINGS["swizzled_block_first"]
    with pytest.raises(KeyError):
        plan_lib.with_mapping(cfg, "not_a_mapping")


# --- thin wrappers ------------------------------------------------------------


def test_resolve_mapping_is_a_thin_wrapper():
    mc = ops.resolve_mapping(SHAPE)
    assert mc is plan_lib.plan_attention(SHAPE).mapping
    dec = ops.resolve_mapping((2, 8, 2, 1, 2048, 64), decode=True)
    assert dec is plan_lib.plan_attention(
        (2, 8, 2, 1, 2048, 64), phase=plan_lib.DECODE
    ).mapping


def test_resolve_kv_layout_is_a_thin_wrapper():
    shape = (6, 32, 8, 512, 128)
    assert ops.resolve_kv_layout(shape, capacity=2048, page_size=16) == \
        plan_lib.resolve_kv_layout(shape, capacity=2048, page_size=16)


def test_flash_attention_executes_a_plan():
    """An explicitly resolved plan drives ops.flash_attention and matches
    the oracle (the pallas route, interpret mode)."""
    from repro.kernels import ref

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    plan = plan_lib.plan_attention(
        (1, 4, 2, 256, 256, 64), impl="pallas", dtype_bytes=4
    )
    o = ops.flash_attention(q, k, v, causal=True, plan=plan)
    o_ref = ref.attention(q, k, v, causal=True)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5


def test_explicit_mapping_and_paged_extend_skip_scoring():
    """A caller-decided MappingConfig (plan_for_mapping) and a paged
    extend plan (whose kernel takes no mapping) must not pay the
    12-candidate scoring sweep."""
    before = plan_lib._score_mapping.cache_info().misses
    p = plan_lib.plan_for_mapping(
        MappingConfig(block_m=256), impl="pallas", backend="cpu"
    )
    assert p.impl == "pallas" and p.interpret
    assert p.mapping.block_m == 256
    ext = plan_lib.plan_attention(
        (1, 8, 2, 32, 32 * 16 + 32, 64), phase=plan_lib.EXTEND,
        kv_layout=plan_lib.PAGED, page_size=16, prefix_pages=32,
        backend="cpu",
    )
    assert ext.impl == "pallas"
    assert plan_lib._score_mapping.cache_info().misses == before


def test_prefill_rejects_dense_prefix_caches():
    """A dense (non-paged) prefix cache in prefill mode must raise, not
    silently drop the prefix (the dense prefix_kv route is gone)."""
    import numpy as np

    from repro.models import transformer

    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    dense = transformer.init_caches(params, cfg, batch=1, cache_len=32)
    tokens = jnp.asarray(np.arange(1, 17)[None])
    with pytest.raises(ValueError, match="paged"):
        transformer.prefill(
            params, cfg, tokens, cache_len=16, prefix_caches=dense,
            page_table=jnp.zeros((1, 2), jnp.int32),
            prefix_len=jnp.asarray([16], jnp.int32),
        )


def test_perf_model_scores_plans():
    """perf_model.estimate_attention_plan dispatches on the plan's
    phase/layout, and the paged extend kernel models cheaper than the
    gather route it replaced (prefix bytes read once, not thrice)."""
    from repro.core import numa, perf_model

    shape_e = (1, 32, 8, 64, 512 + 64, 128)
    pe = plan_lib.plan_attention(
        shape_e, phase=plan_lib.EXTEND, kv_layout=plan_lib.PAGED,
        page_size=16, prefix_pages=32, backend="gpu",
    )
    paged = perf_model.estimate_attention_plan(pe, shape_e, topo=numa.MI300X)
    gather = perf_model.estimate_extend_prefill(
        batch=1, num_q_heads=32, num_kv_heads=8, prefix_len=512, tail_len=64,
        page_size=16, head_dim=128, dtype_bytes=2, topo=numa.MI300X,
        gather=True,
    )
    assert paged.layout == "extend:paged" and gather.layout == "extend:gather"
    assert paged.hbm_bytes < gather.hbm_bytes
    assert paged.time <= gather.time
    # Reuse ranks the kernel above the gather route (fraction of logical
    # per-q-head prefix reads served without a fetch).
    assert paged.reuse_rate > gather.reuse_rate

    shape_d = (8, 32, 8, 1, 2048, 128)
    pd = plan_lib.plan_attention(shape_d, phase=plan_lib.DECODE, backend="gpu")
    assert perf_model.estimate_attention_plan(
        pd, shape_d, topo=numa.MI300X
    ).layout == "dense"
    pdp = plan_lib.plan_attention(
        shape_d, phase=plan_lib.DECODE, kv_layout=plan_lib.PAGED,
        page_size=16, backend="gpu",
    )
    assert perf_model.estimate_attention_plan(
        pdp, shape_d, topo=numa.MI300X
    ).layout.startswith("paged:")

    shape_p = (8, 32, 8, 4096, 4096, 128)
    pp = plan_lib.plan_attention(shape_p, backend="gpu")
    assert perf_model.estimate_attention_plan(
        pp, shape_p, topo=numa.MI300X
    ).time > 0


def test_extend_route_is_scored_per_shape():
    """PR-4 satellite: the paged-extend impl is chosen by
    perf_model.estimate_extend_prefill, and each route wins somewhere.

    Low occupancy (one MQA request: B x Hkv = 1 of MI300X's 8 domains,
    long tail) -> the gather route's dense flash regains the idle domains
    and beats the kernel despite 3x prefix traffic. High occupancy
    (batched GQA, long prefix, short tail) -> the kernel's once-per-page
    reads win. A pinned impl skips the scoring entirely."""
    from repro.core import numa, perf_model

    gather_shape = (1, 8, 1, 512, 512 + 16, 64)
    gp = plan_lib.plan_attention(
        gather_shape, phase=plan_lib.EXTEND, kv_layout=plan_lib.PAGED,
        page_size=16, prefix_pages=1, backend="gpu",
    )
    assert gp.impl == "xla"
    paged_shape = (8, 32, 8, 64, 2048 + 64, 128)
    pp = plan_lib.plan_attention(
        paged_shape, phase=plan_lib.EXTEND, kv_layout=plan_lib.PAGED,
        page_size=16, prefix_pages=128, backend="gpu",
    )
    assert pp.impl == "pallas"
    # The choices agree with the estimates they claim to come from.
    for shape, plan in ((gather_shape, gp), (paged_shape, pp)):
        b, hq, hkv, sq, skv, hd = shape
        kw = dict(batch=b, num_q_heads=hq, num_kv_heads=hkv,
                  prefix_len=skv - sq, tail_len=sq, page_size=16,
                  head_dim=hd, dtype_bytes=2, topo=numa.MI300X)
        paged_t = perf_model.estimate_extend_prefill(gather=False, **kw).time
        gather_t = perf_model.estimate_extend_prefill(gather=True, **kw).time
        assert (plan.impl == "pallas") == (paged_t <= gather_t), shape
    # Pinned impls are never re-routed.
    pinned = plan_lib.plan_attention(
        gather_shape, phase=plan_lib.EXTEND, kv_layout=plan_lib.PAGED,
        page_size=16, prefix_pages=1, backend="gpu", impl="pallas",
    )
    assert pinned.impl == "pallas"


# --- grep enforcement ---------------------------------------------------------


def test_no_out_of_band_schedule_threading():
    """The four former dispatch sites consume AttentionPlans: none of them
    may thread ``q_offset`` / ``mapping_name`` by hand, look up
    ``PAPER_MAPPINGS``, or hand-roll a ``MappingConfig`` past the plan
    layer. (kernels/ops.py keeps ``q_offset`` only as the oracle/fallback
    argument of ``flash_attention``; the plan layer itself is the one
    reader of the config policy.) Single implementation: the linter's
    ``plan-dispatch-only`` rule."""
    from repro.analysis import run_rules

    assert run_rules(rules=["plan-dispatch-only"]) == []


def test_engine_resolves_schedules_through_plans():
    """Both facade backends' advertised mapping comes from the plan layer
    and honors a pinned override."""
    from repro.models import transformer
    from repro.serving import LLMEngine

    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=2,
                    cache_len=64, prompt_buckets=(16,))
    assert eng.mapping is plan_lib.plan_for_config(
        cfg, (2, cfg.n_heads, cfg.n_kv_heads, 64, 64, cfg.head_dim)
    ).mapping
    pinned = LLMEngine(cfg, params, kv_layout="dense", max_batch=2,
                       cache_len=64, prompt_buckets=(16,),
                       mapping="naive_head_first")
    assert pinned.mapping is PAPER_MAPPINGS["naive_head_first"]
    with pytest.raises(KeyError):
        LLMEngine(cfg, params, kv_layout="dense", max_batch=2, cache_len=64,
                  prompt_buckets=(16,), mapping="bogus")
    paged = LLMEngine(cfg, params, kv_layout="paged", num_pages=32,
                      page_size=16, max_batch=2, max_pages_per_seq=4,
                      prompt_buckets=(16, 32))
    assert paged.mapping is plan_lib.plan_for_config(
        cfg, (2, cfg.n_heads, cfg.n_kv_heads, 1, 64, cfg.head_dim),
        phase=plan_lib.DECODE, kv_layout=plan_lib.PAGED, page_size=16,
    ).mapping


def test_paged_decode_block_comes_from_the_shape():
    """``pages_per_block`` is a function of the decode shape alone: the
    target block worth of pages, clamped to the table (a short table is one
    block; a long one need not be a multiple of it, the tail block's slots
    past the table are never read), to one block per split, and halved
    under the VMEM budget."""
    ps, d = 16, 128
    target = plan_lib.PAGED_DECODE_BLOCK_TOKENS // ps

    def decode_plan(b, hq, hkv, max_pages, **kw):
        return plan_lib.plan_attention(
            (b, hq, hkv, 1, max_pages * ps, d), phase=plan_lib.DECODE,
            kv_layout=plan_lib.PAGED, page_size=ps, backend="cpu", **kw)

    # The two benchmark cells' decode shapes: 256 and 120 pages of 16.
    session, chat = decode_plan(16, 32, 32, 256), decode_plan(32, 32, 8, 120)
    assert session.pages_per_block == chat.pages_per_block == target
    assert 120 % chat.pages_per_block  # a dynamic tail, not a divisor
    # Rows and heads do not enter; a short table is one block.
    assert decode_plan(1, 8, 2, 256).pages_per_block == target
    assert decode_plan(4, 8, 2, 6).pages_per_block == 6
    # Under a small VMEM budget the block halves until it fits.
    budget = plan_lib.paged_decode_vmem_bytes(4 * ps, d, 2)
    small = decode_plan(16, 32, 32, 256, vmem_budget_bytes=budget)
    assert small.pages_per_block == 4
    assert plan_lib.paged_decode_vmem_bytes(
        small.pages_per_block * ps, d, 2) <= budget
    # Every split-K range keeps a block; dense and prefill plans carry 1.
    assert plan_lib.decode_pages_per_block(
        256, ps, d, 2, num_splits=32, vmem_budget_bytes=1 << 30) == 8
    assert plan_lib.plan_attention(
        (2, 8, 2, 1, 2048, 64), phase=plan_lib.DECODE,
        backend="cpu").pages_per_block == 1


def test_engine_stats_report_the_decode_block():
    """``decode_plan()`` records what the paged decode kernel runs with,
    and ``stats()`` reports it beside the split count."""
    from repro.models import transformer
    from repro.serving import LLMEngine

    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    paged = LLMEngine(cfg, params, kv_layout="paged", num_pages=32,
                      page_size=16, max_batch=2, max_pages_per_seq=4,
                      prompt_buckets=(16, 32))
    plan = paged.backend.decode_plan()
    assert plan.pages_per_block == 4   # the whole 4-page table
    stats = paged.stats()
    assert (stats.pages_per_block, stats.decode_splits) == (
        plan.pages_per_block, plan.num_splits)
    assert "decode blocks of 4 pages x 1 splits" in stats.summary()
    dense = LLMEngine(cfg, params, kv_layout="dense", max_batch=2,
                      cache_len=64, prompt_buckets=(16,))
    assert dense.stats().pages_per_block == 0
