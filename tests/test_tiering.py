"""Million-token KV (PR 10): quantized pages + device↔host KV tiering.

Three layers of acceptance:

  * **kernel parity** — int8/fp8 pools through the Pallas decode/prefill
    kernels (interpret mode) match the quantized oracle to float tolerance
    and the fp32 oracle to quantization tolerance, across GQA/MQA shapes,
    non-page-multiple lengths, length-0 rows, and split-K;
  * **serving bit-match** — a tiered engine (device pool too small, host
    tier behind it) produces byte-identical greedy tokens to the untiered
    engine, through demote→promote round trips and preemption/resume, and
    closes leak-free. Every engine in this module runs under BOTH shadow
    sanitizers (``tests/conftest.py``): page-pool lifecycle + tier
    residency checking on every operation;
  * **accounting** — pool bytes are exact (int8 ≤ 0.55x fp32 with scale
    metadata included), the host store's LRU/counters behave, the tiered
    cache simulator and perf-model link pricing agree on structure.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.pool_sanitizer import (
    DoubleDemoteError,
    PromoteAfterFreeError,
    StaleDeviceReadError,
    attach_tier,
)
from repro.cache import quant
from repro.cache.tier import HostPageStore
from repro.configs import registry
from repro.core import cache_sim, numa, perf_model
from repro.kernels import ref
from repro.kernels.paged_decode_attention import paged_flash_decode
from repro.kernels.paged_prefill_attention import paged_flash_prefill
from repro.models import transformer
from repro.serving import LLMEngine, Request, SamplingParams
from repro.serving.scheduler import SchedulerStats

from paged_cases import mk_edge_batch

#: Worst-case |dequant(quant(x)) - x| through attention, per format.
QTOL = {"int8": 0.03, "fp8": 0.12}


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_caches():
    # The pinned jaxlib's CPU JIT segfaults in backend_compile once a
    # single process accumulates a full tier-1 suite's worth of compiled
    # executables; this module (last alphabetically, compile-heavy: many
    # short-lived engines) is where it lands. Dropping the executable
    # caches up front restores the standalone-run compile budget.
    jax.clear_caches()
    yield


@pytest.fixture(scope="module")
def llama():
    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# --- kernel parity: quantized pools vs oracles --------------------------------


def mk_paged(b, hq, hkv, d, ps, max_pages, seed=0, shared_pages=0):
    rng = np.random.default_rng(seed)
    num_pages = 1 + shared_pages + b * max_pages
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, hq, d), jnp.float32)
    k_pages = jax.random.normal(keys[1], (hkv, num_pages, ps, d))
    v_pages = jax.random.normal(keys[2], (hkv, num_pages, ps, d))
    avail = list(rng.permutation(np.arange(1 + shared_pages, num_pages)))
    pt = np.zeros((b, max_pages), np.int32)
    lengths = np.zeros((b,), np.int32)
    for i in range(b):
        # Deliberately non-page-multiple lengths (never aligned unless
        # the draw happens to be).
        lengths[i] = rng.integers(max(shared_pages * ps, 1),
                                  max_pages * ps + 1)
        live = -(-int(lengths[i]) // ps)
        row = list(range(1, 1 + min(shared_pages, live)))
        row += [avail.pop() for _ in range(live - len(row))]
        pt[i, :live] = row
    return q, k_pages, v_pages, jnp.asarray(pt), jnp.asarray(lengths)


@pytest.mark.parametrize("b,hq,hkv,d", [
    (2, 8, 2, 64),       # GQA
    (2, 4, 1, 64),       # MQA
    (1, 25, 5, 64),      # odd group
])
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_decode_parity(b, hq, hkv, d, kv_dtype):
    q, kp, vp, pt, lengths = mk_paged(b, hq, hkv, d, ps=16, max_pages=6,
                                      shared_pages=2)
    kq, ksc = quant.quantize_pages(kp, kv_dtype)
    vq, vsc = quant.quantize_pages(vp, kv_dtype)
    o = paged_flash_decode(q, kq, vq, pt, lengths,
                           k_scales=ksc, v_scales=vsc, interpret=True)
    # Kernel in-VMEM dequant == oracle gather-then-dequant, to float eps.
    o_qref = ref.paged_decode_attention(q, kq, vq, pt, lengths,
                                        k_scales=ksc, v_scales=vsc)
    assert jnp.max(jnp.abs(o - o_qref)) < 2e-5
    # And the whole quantized path tracks the fp32 oracle within the
    # format's quantization budget.
    o_fp32 = ref.paged_decode_attention(q, kp, vp, pt, lengths)
    assert jnp.max(jnp.abs(o - o_fp32)) < QTOL[kv_dtype]


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_decode_split_k_parity(kv_dtype):
    q, kp, vp, pt, lengths = mk_paged(2, 8, 2, 64, ps=16, max_pages=8,
                                      seed=5)
    kq, ksc = quant.quantize_pages(kp, kv_dtype)
    vq, vsc = quant.quantize_pages(vp, kv_dtype)
    o1 = paged_flash_decode(q, kq, vq, pt, lengths,
                            k_scales=ksc, v_scales=vsc, interpret=True)
    o4 = paged_flash_decode(q, kq, vq, pt, lengths,
                            k_scales=ksc, v_scales=vsc, num_splits=4,
                            interpret=True)
    assert jnp.max(jnp.abs(o1 - o4)) < 2e-5


@pytest.mark.parametrize("pages_per_block", [1, 3, 8])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 1), (4, 4)])
@pytest.mark.parametrize("window", [None, 37])
def test_quantized_block_walk_parity(pages_per_block, hq, hkv, window):
    """The block-walk edge cases over int8 pools: each page of a block is
    dequantized with its own (head, page) scale."""
    q, kp, vp, pt, lengths = mk_edge_batch(hq, hkv, 32, pages_per_block)
    kq, ksc = quant.quantize_pages(kp, "int8")
    vq, vsc = quant.quantize_pages(vp, "int8")
    o = paged_flash_decode(q, kq, vq, pt, lengths, window=window,
                           pages_per_block=pages_per_block,
                           k_scales=ksc, v_scales=vsc, interpret=True)
    o_qref = ref.paged_decode_attention(q, kq, vq, pt, lengths, window=window,
                                        k_scales=ksc, v_scales=vsc)
    assert jnp.max(jnp.abs(o - o_qref)) < 2e-5
    assert jnp.max(jnp.abs(o[0])) == 0.0


def test_scales_both_or_neither_everywhere():
    """One-sided scales would silently attend over raw codes — every
    dispatch target (kernel AND oracle, decode AND prefill) must refuse."""
    q, kp, vp, pt, lengths = mk_paged(1, 4, 2, 32, ps=8, max_pages=2)
    kq, ksc = quant.quantize_pages(kp, "int8")
    with pytest.raises(ValueError, match="together"):
        paged_flash_decode(q, kq, kq, pt, lengths, k_scales=ksc,
                           interpret=True)
    with pytest.raises(ValueError, match="together"):
        ref.paged_decode_attention(q, kq, kq, pt, lengths, v_scales=ksc)
    tail = jnp.zeros((1, 2, 8, 32), jnp.float32)
    qp = jnp.zeros((1, 4, 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="together"):
        paged_flash_prefill(qp, kq, kq, pt, tail, tail,
                            jnp.asarray([8]), jnp.asarray([8]),
                            k_scales=ksc, interpret=True)
    with pytest.raises(ValueError, match="together"):
        ref.paged_prefill_attention(qp, kq, kq, pt, tail, tail,
                                    jnp.asarray([8]), jnp.asarray([8]),
                                    v_scales=ksc)


def test_quantized_decode_length_zero_row():
    q, kp, vp, pt, lengths = mk_paged(3, 8, 2, 64, ps=16, max_pages=4,
                                      seed=3)
    lengths = lengths.at[1].set(0)
    kq, ksc = quant.quantize_pages(kp, "int8")
    vq, vsc = quant.quantize_pages(vp, "int8")
    o = paged_flash_decode(q, kq, vq, pt, lengths,
                           k_scales=ksc, v_scales=vsc, interpret=True)
    assert jnp.max(jnp.abs(o[1])) == 0.0
    o_fp32 = ref.paged_decode_attention(q, kp, vp, pt, lengths)
    assert jnp.max(jnp.abs(o - o_fp32)) < QTOL["int8"]


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_prefill_parity(kv_dtype):
    """Extend prefill over quantized prefix pages + fp32 dense tail:
    kernel vs quantized oracle (float eps) vs fp32 oracle (format
    budget); non-page-multiple prefixes; rows past tail_len exact zero."""
    b, hq, hkv, d, ps, mp, st = 2, 8, 2, 64, 16, 4, 24
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(keys[0], (b, hq, st, d), jnp.float32)
    kp = jax.random.normal(keys[1], (hkv, 1 + b * mp, ps, d))
    vp = jax.random.normal(keys[2], (hkv, 1 + b * mp, ps, d))
    k_tail = jax.random.normal(keys[3], (b, hkv, st, d), jnp.float32)
    v_tail = jax.random.normal(keys[4], (b, hkv, st, d), jnp.float32)
    pt = jnp.asarray(
        1 + np.arange(b * mp).reshape(b, mp), jnp.int32)
    prefix_len = jnp.asarray([37, 64], jnp.int32)   # non-multiple + full
    tail_len = jnp.asarray([st, st - 5], jnp.int32)  # one short row
    kq, ksc = quant.quantize_pages(kp, kv_dtype)
    vq, vsc = quant.quantize_pages(vp, kv_dtype)
    o = paged_flash_prefill(q, kq, vq, pt, k_tail, v_tail,
                            prefix_len, tail_len,
                            k_scales=ksc, v_scales=vsc, interpret=True)
    o_qref = ref.paged_prefill_attention(q, kq, vq, pt, k_tail, v_tail,
                                         prefix_len, tail_len,
                                         k_scales=ksc, v_scales=vsc)
    assert jnp.max(jnp.abs(o - o_qref)) < 2e-5
    o_fp32 = ref.paged_prefill_attention(q, kp, vp, pt, k_tail, v_tail,
                                         prefix_len, tail_len)
    assert jnp.max(jnp.abs(o - o_fp32)) < QTOL[kv_dtype]
    assert jnp.max(jnp.abs(o[1, :, st - 5:])) == 0.0


def test_append_rows_rescale_keeps_history():
    """Rescale-on-append: a loud new token widens its page's scale and
    shrinks the existing codes — history dequantizes to the same values
    within one extra quantization step."""
    hkv, P, ps, d = 2, 3, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (hkv, P, ps, d))
    pages, scales = quant.quantize_pages(x, "int8")
    before = quant.dequantize_pages(pages, scales)
    loud = 50.0 * jax.random.normal(jax.random.PRNGKey(1), (hkv, 1, d))
    pages, scales = quant.append_rows(
        pages, scales, loud, jnp.asarray([1], jnp.int32),
        jnp.asarray([3], jnp.int32), "int8")
    after = quant.dequantize_pages(pages, scales)
    # The appended row round-trips at the widened scale...
    err_new = jnp.max(jnp.abs(after[:, 1, 3] - loud[:, 0]))
    assert err_new < float(jnp.max(jnp.abs(loud))) / 127.0 * 1.01
    # ...untouched pages are bit-identical...
    assert jnp.array_equal(after[:, 0], before[:, 0])
    assert jnp.array_equal(after[:, 2], before[:, 2])
    # ...and the rescaled page's other rows stay within the new step.
    step = float(jnp.max(scales[:, 1]))
    rest = jnp.delete(jnp.arange(ps), 3)
    assert float(jnp.max(jnp.abs(
        after[:, 1, rest] - before[:, 1, rest]))) <= step * 1.01


# --- accounting ---------------------------------------------------------------


def test_int8_pool_bytes_under_055x_fp32(llama):
    cfg, params = llama
    engines = {}
    for kv_dtype in ("fp32", "int8"):
        e = LLMEngine(cfg, params, kv_layout="paged", num_pages=32,
                      page_size=8, kv_dtype=kv_dtype)
        engines[kv_dtype] = e.backend.kv_pool_bytes()
        # Accounting must be exact: 2 pools x layers x heads x
        # (page payload + one fp32 scale per (head, page)).
        itemsize = quant.kv_itemsize(kv_dtype)
        scale = 4 if kv_dtype != "fp32" else 0
        expect = (2 * cfg.n_layers * cfg.n_kv_heads
                  * (8 * cfg.head_dim * itemsize + scale) * 32)
        assert engines[kv_dtype] == expect, kv_dtype
        e.close()
    ratio = engines["int8"] / engines["fp32"]
    assert ratio <= 0.55, ratio


def test_host_store_lru_and_counters():
    store = HostPageStore(capacity_bytes=4 * 100, page_nbytes=100)
    assert store.capacity_pages == 4
    keys = [bytes([i]) for i in range(5)]
    for h in keys[:4]:
        assert store.admit(h, {"page": h})
    assert store.bytes_resident == 400 and store.free_slots == 0
    # Chain lookup stops at the first miss and MRU-refreshes hits.
    assert store.lookup_chain(keys[:3] + [b"missing"]) == keys[:3]
    # Admitting a 5th evicts the LRU (keys[3]: the lookup refreshed 0-2).
    assert store.admit(keys[4], {})
    assert keys[3] not in store and keys[0] in store
    assert store.evictions == 1
    # take consumes; discard drops without a promotion count.
    store.take(keys[0])
    assert keys[0] not in store and store.promotions == 1
    assert store.discard(keys[1]) and not store.discard(keys[1])
    assert store.promotions == 1
    with pytest.raises(KeyError):
        store.take(keys[3])
    c = store.counters()
    assert c["demotions"] == 5.0 and c["hits"] == 3.0
    assert store.drain() == len(store._lru) or store.drain() == 0
    assert store.bytes_resident == 0


def test_host_store_zero_capacity_disables():
    store = HostPageStore(capacity_bytes=10, page_nbytes=100)
    assert not store.admit(b"h", {})
    assert store.bytes_resident == 0


def test_estimate_tier_transfer_pricing():
    t0 = perf_model.estimate_tier_transfer(0)
    assert t0 == pytest.approx(perf_model.HOST_SYNC_OVERHEAD_S)
    t1 = perf_model.estimate_tier_transfer(1 << 20)
    assert t1 > t0
    assert t1 == pytest.approx(
        perf_model.HOST_SYNC_OVERHEAD_S + (1 << 20) / perf_model.HOST_LINK_BW)
    # A page transfer beats re-prefilling anything non-trivial, but not a
    # recompute cheaper than the sync overhead itself.
    assert perf_model.tier_transfer_beats_recompute(1 << 16, 5e-3)
    assert not perf_model.tier_transfer_beats_recompute(1 << 16, 1e-6)


def test_simulate_tiered_decode_accounting():
    # 2-page device LRU, 2-page host: read 3 pages round-robin twice.
    # First pass: 3 recomputes + 1 demotion (A evicted when C fills).
    # Second pass: A promotes from host, B/C churn likewise.
    trace = ["A", "B", "C", "A", "B", "C"]
    r = cache_sim.simulate_tiered_decode(
        trace, page_bytes=1000, device_pages=2, host_pages=2,
        topo=numa.MI300X, recompute_s_per_page=1e-3)
    assert r.device_hits == 0
    assert r.recomputes == 3 and r.promotions == 3
    assert r.demotions == 4  # every device eviction before the last two
    assert r.link_bytes == (r.promotions + r.demotions) * 1000
    assert r.hbm_bytes == 6 * 1000
    assert r.rescue_rate == pytest.approx(0.5)
    assert r.elapsed > 0
    # A device pool that fits the working set: all hits after cold start.
    r2 = cache_sim.simulate_tiered_decode(
        trace, page_bytes=1000, device_pages=3, host_pages=2,
        topo=numa.MI300X, recompute_s_per_page=1e-3)
    assert r2.device_hits == 3 and r2.demotions == 0
    assert r2.elapsed < r.elapsed


def test_scales_shard_with_their_pages():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    from repro.distributed import sharding as sharding_lib

    caches = {
        "scanned": ({"attn": {
            "k_pages": jax.ShapeDtypeStruct((3, 4, 16, 8, 16), jnp.int8),
            "k_scales": jax.ShapeDtypeStruct((3, 4, 16), jnp.float32),
        }},),
        "rem": ({"attn": {
            "v_pages": jax.ShapeDtypeStruct((4, 16, 8, 16), jnp.int8),
            "v_scales": jax.ShapeDtypeStruct((4, 16), jnp.float32),
        }},),
    }
    specs = sharding_lib.paged_cache_specs(mesh, caches)

    def axes(spec, rank):
        # normalize: PartitionSpec trims trailing Nones, == doesn't.
        t = tuple(spec) + (None,) * (rank - len(tuple(spec)))
        return t

    sc = specs["scanned"][0]["attn"]
    assert axes(sc["k_pages"], 5) == (None, "model", None, None, None)
    assert axes(sc["k_scales"], 3) == (None, "model", None)
    rm = specs["rem"][0]["attn"]
    assert axes(rm["v_pages"], 4) == ("model", None, None, None)
    assert axes(rm["v_scales"], 2) == ("model", None)


def test_scheduler_stats_summary_includes_tier_line():
    s = SchedulerStats(kv_layout="paged", kv_dtype="int8",
                       demoted_pages=7, promoted_pages=3,
                       host_bytes_resident=4096)
    text = s.summary()
    assert "int8" in text and "7 demoted" in text and "3 promoted" in text
    assert "tier" not in SchedulerStats().summary()


# --- residency sanitizer ------------------------------------------------------


def test_shadow_tier_catches_residency_violations():
    store = HostPageStore(capacity_bytes=10 * 64, page_nbytes=64)
    shadow = attach_tier(store)
    try:
        store.admit(b"a", {"k": 1})
        with pytest.raises(DoubleDemoteError):
            store.admit(b"a", {"k": 1})
        store.take(b"a")
        with pytest.raises(PromoteAfterFreeError):
            store.take(b"a")
        # LRU overflow is a legal transition: the shadow mirrors it.
        tiny = HostPageStore(capacity_bytes=64, page_nbytes=64)
        sh2 = attach_tier(tiny)
        try:
            tiny.admit(b"x", {})
            tiny.admit(b"y", {})       # evicts x host-side
            tiny.admit(b"x", {})       # NOT a double demote: x was evicted
        finally:
            sh2.detach()
    finally:
        shadow.detach()


def test_shadow_tier_catches_stale_device_read():
    from repro.cache.pool import PagePool
    from repro.cache.prefix import PrefixCache

    pool = PagePool(num_pages=8, page_size=4)
    prefix = PrefixCache(pool)
    store = HostPageStore(capacity_bytes=10 * 64, page_nbytes=64)
    shadow = attach_tier(store, prefix)
    try:
        seq = pool.allocate_sequence(8)
        prefix.insert([b"h1", b"h2"], seq.pages[:2])
        store.admit(b"h2", {"payload": 2})   # demoted, device copy stale
        with pytest.raises(StaleDeviceReadError):
            prefix.lookup([b"h1", b"h2"])
        with pytest.raises(StaleDeviceReadError):
            prefix.insert([b"h2"], [seq.pages[1]])
        # discard clears host residency; the device side is legal again.
        store.discard(b"h2")
        prefix.insert([b"h2"], [seq.pages[1]])
        assert prefix.lookup([b"h1", b"h2"]) == list(seq.pages[:2])
        pool.release(seq)
        prefix.evict(10)
    finally:
        shadow.detach()


# --- serving: tiered bit-match, promotion, preemption, in-flight --------------


def _greedy(engine, prompts, n_new, uid0=0):
    reqs = [Request(uid0 + i, p, SamplingParams(max_tokens=n_new))
            for i, p in enumerate(prompts)]
    outs = engine.generate(reqs)
    return {o.uid - uid0: [int(t) for t in o.tokens] for o in outs}


def test_tiered_demote_promote_bit_match(llama):
    """The full round trip: serve P, pressure its pages host-side, serve
    P again — the promoted pages must reproduce the untiered tokens
    bit-for-bit, with real demotions AND promotions counted."""
    cfg, params = llama
    rng = np.random.default_rng(0)
    p_shared = rng.integers(1, cfg.vocab, size=33)
    fillers = [rng.integers(1, cfg.vocab, size=40 + 8 * i) for i in range(3)]

    ref_eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=96,
                        page_size=8)
    want = _greedy(ref_eng, [p_shared], 6)[0]
    ref_eng.close()

    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=20,
                    page_size=8, host_pool_bytes=1 << 20)
    first = _greedy(eng, [p_shared], 6, uid0=0)[0]
    _greedy(eng, fillers, 4, uid0=100)          # pressure: demote P's pages
    st = eng.backend.prefix_stats()
    assert st["demoted_pages"] > 0, st
    again = _greedy(eng, [p_shared], 6, uid0=200)[0]
    st = eng.backend.prefix_stats()
    assert st["promoted_pages"] > 0, st
    assert first == want and again == want
    assert eng.stats().demoted_pages == int(st["demoted_pages"])
    eng.close()   # leak-free or RefcountLeakError


def test_tiered_preemption_resume_bit_match(llama):
    """Preemption under decode pressure with the host tier on: resumed
    sequences replay through promoted/recomputed prefixes and still
    bit-match the pressure-free engine."""
    cfg, params = llama
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab, size=24) for _ in range(3)]

    big = LLMEngine(cfg, params, kv_layout="paged", num_pages=96,
                    page_size=8, max_batch=3)
    want = _greedy(big, prompts, 24)
    big.close()

    small = LLMEngine(cfg, params, kv_layout="paged", num_pages=14,
                      page_size=8, max_batch=3, host_pool_bytes=1 << 20)
    got = _greedy(small, prompts, 24)
    assert small.backend.stats["preemptions"] > 0
    assert got == want
    small.close()


def test_host_pool_requires_prefix_sharing(llama):
    cfg, params = llama
    with pytest.raises(ValueError, match="prefix_sharing"):
        LLMEngine(cfg, params, kv_layout="paged", prefix_sharing=False,
                  host_pool_bytes=1 << 20)
    with pytest.raises(ValueError, match="paged"):
        LLMEngine(cfg, params, kv_layout="dense", kv_dtype="int8")


def test_int8_greedy_matches_fp32_on_smoke_shapes(llama):
    """The CI acceptance shape: seed-0 prompts, 8 new tokens — int8
    quantization noise must not flip any greedy argmax here."""
    cfg, params = llama
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 400, size=L) for L in (8, 17, 25, 33)]
    a = LLMEngine(cfg, params, kv_layout="paged", num_pages=64, page_size=8)
    want = _greedy(a, prompts, 8)
    a.close()
    b = LLMEngine(cfg, params, kv_layout="paged", num_pages=64, page_size=8,
                  kv_dtype="int8")
    assert _greedy(b, prompts, 8) == want
    assert b.backend.prefix_stats()["kv_dtype"] == "int8"
    b.close()


def test_inflight_prefix_match_same_flush(llama):
    """Two same-prefix requests admitted in ONE flush share the pages the
    first is about to write (vLLM-style in-flight matching) instead of
    prefilling twice — and still both produce the reference tokens."""
    cfg, params = llama
    rng = np.random.default_rng(2)
    shared = rng.integers(1, cfg.vocab, size=17)   # 2 full pages + 1

    ref_eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=64,
                        page_size=8)
    want = _greedy(ref_eng, [shared], 5)[0]
    ref_eng.close()

    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=64,
                    page_size=8, max_batch=4)
    for i in range(3):
        eng.add_request(prompt=np.array(shared),
                        sampling=SamplingParams(max_tokens=5), uid=i)
    outs = []
    while len(outs) < 3:
        outs.extend(o for o in eng.step() if o.finished)
    assert eng.backend.stats["inflight_pages_reused"] > 0
    for o in outs:
        assert [int(t) for t in o.tokens] == want
    eng.close()


def test_stream_push_iterator(llama):
    """The async push surface: two concurrent streams over one engine,
    each sees exactly its own increments (with detokenized text), and the
    reassembled tokens match generate()."""
    cfg, params = llama
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab, size=n) for n in (12, 20)]

    ref_eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=64,
                        page_size=8)
    want = _greedy(ref_eng, prompts, 6)
    ref_eng.close()

    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=64,
                    page_size=8,
                    detokenizer=lambda toks: ",".join(
                        str(int(t)) for t in toks))

    async def consume(i):
        toks, texts = [], []
        async for out in eng.stream(prompt=np.array(prompts[i]),
                                    sampling=SamplingParams(max_tokens=6)):
            toks.extend(int(t) for t in out.new_tokens)
            texts.append(out.text)
        return toks, texts

    async def both():
        return await asyncio.gather(consume(0), consume(1))

    (t0, x0), (t1, x1) = asyncio.run(both())
    assert t0 == want[0] and t1 == want[1]
    # text is the detokenized increment, present on every emission
    assert all(x is not None for x in x0 + x1)
    assert ",".join(str(t) for t in t0) == ",".join(x for x in x0 if x)
    assert eng._stream_q == {}   # buffers torn down
    eng.close()
