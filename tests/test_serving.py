"""Serving facade tests: LLMEngine == direct greedy decode, both layouts.

PR-5 acceptance criteria covered here:
  * one public entry point (``LLMEngine(cfg, params, kv_layout=...)``)
    serves mixed batches through both backends with greedy outputs
    bit-matching the pre-refactor engines' oracle (direct greedy decode),
    including across preemption/resume;
  * ``step()`` streams incremental ``RequestOutput``s with correct
    ``finish_reason``s;
  * ``kv_layout="auto"`` resolves through the plan layer and falls back
    to dense for models the paged subsystem cannot hold;
  * the deprecated ``ServingEngine`` / ``PagedServingEngine`` shims stay
    drop-in, and nothing outside ``src/repro/serving/`` constructs them
    (grep-enforced, pattern of ``tests/test_attention_plan.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.pool import OutOfPages
from repro.configs import registry
from repro.models import transformer
from repro.serving import LLMEngine, Request, RequestOutput, SamplingParams


@pytest.fixture(scope="module")
def llama():
    cfg = registry.get_smoke_config("llama3-8b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def direct_greedy(cfg, params, prompt, n_new, cache_len=256):
    lg, caches = transformer.prefill(
        params, cfg, jnp.asarray(prompt)[None], cache_len=cache_len
    )
    toks, lengths = [], jnp.array([len(prompt)], jnp.int32)
    nxt = int(jnp.argmax(lg[0]))
    for _ in range(n_new):
        toks.append(nxt)
        lengths = lengths + 1
        lg, caches = transformer.decode_step(
            params, cfg, jnp.asarray([nxt]), caches, lengths
        )
        nxt = int(jnp.argmax(lg[0]))
    return toks


def toks_of(out: RequestOutput):
    return [int(t) for t in out.tokens]


# --- dense backend ------------------------------------------------------------


def test_continuous_batching_matches_direct(llama):
    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=3,
                    cache_len=256, prompt_buckets=(32, 64))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 400, size=(L,)) for L in (8, 20, 33, 11, 40)]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    results = eng.generate(reqs)
    assert len(results) == len(reqs)
    for r in results:
        assert r.finished and r.finish_reason == "length"
        assert toks_of(r) == direct_greedy(cfg, params, prompts[r.uid], 5), r.uid


def test_slot_reuse(llama):
    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=1,
                    cache_len=128, prompt_buckets=(16,))
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i, prompt=rng.integers(1, 400, size=(10,)),
                    max_new_tokens=3) for i in range(4)]
    results = eng.generate(reqs)
    assert sorted(r.uid for r in results) == [0, 1, 2, 3]


def test_stop_token_terminates_with_reason(llama):
    cfg, params = llama
    prompt = np.random.default_rng(2).integers(1, 400, size=(12,))
    ref_toks = direct_greedy(cfg, params, prompt, 8, cache_len=128)
    # A stop token that first appears at position i > 0 (greedy decode may
    # repeat tokens, so pick one with no earlier occurrence).
    i = next(k for k in range(1, 8) if ref_toks[k] not in ref_toks[:k])
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=1,
                    cache_len=128, prompt_buckets=(16,))
    res = eng.generate([Request(uid=0, prompt=prompt, max_new_tokens=8,
                                eos_id=int(ref_toks[i]))])
    # Stopped right after emitting the stop token (which is included).
    assert [int(t) for t in res[0].tokens] == ref_toks[: i + 1]
    assert res[0].finish_reason == "stop"
    # A stop token as the very FIRST generated token must terminate too
    # (the pre-facade engines only checked decode-sampled tokens).
    res0 = eng.generate([Request(uid=1, prompt=prompt, max_new_tokens=8,
                                 eos_id=int(ref_toks[0]))])
    assert [int(t) for t in res0[0].tokens] == [ref_toks[0]]
    assert res0[0].finish_reason == "stop"


def test_streaming_deltas_reassemble(llama):
    """step() emits disjoint new_tokens whose concatenation equals the
    final output, and the last delta carries the finish_reason."""
    cfg, params = llama
    rng = np.random.default_rng(20)
    prompts = [rng.integers(1, 400, size=(L,)) for L in (8, 14)]
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=2,
                    cache_len=128, prompt_buckets=(16,))
    for i, p in enumerate(prompts):
        eng.add_request(Request(uid=i, prompt=p, max_new_tokens=4))
    streams = {0: [], 1: []}
    finals = {}
    for _ in range(20):
        for out in eng.step():
            streams[out.uid].extend(int(t) for t in out.new_tokens)
            if out.finished:
                finals[out.uid] = out
        if not eng.backend.active.any() and not eng.scheduler.has_work():
            break
    assert sorted(finals) == [0, 1]
    for uid, out in finals.items():
        assert out.finish_reason == "length"
        assert streams[uid] == toks_of(out)  # deltas reassemble exactly
        assert streams[uid] == direct_greedy(cfg, params, prompts[uid], 4)


def test_mixed_sampling_batch_one_engine(llama):
    """The acceptance-criteria batch: different sampling params,
    priorities and lengths in one engine — greedy rows bit-match the
    oracle, stochastic rows are reproducible solo (per-request keys)."""
    cfg, params = llama
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 400, size=(L,)) for L in (8, 20, 13, 30)]
    mk = [
        SamplingParams(max_tokens=5),
        SamplingParams(temperature=0.9, top_k=25, max_tokens=4, seed=3),
        SamplingParams(temperature=1.1, top_p=0.8, max_tokens=6, seed=4),
        SamplingParams(max_tokens=3, seed=9),
    ]
    reqs = [Request(uid=i, prompt=p, sampling=s, priority=i % 2)
            for i, (p, s) in enumerate(zip(prompts, mk))]
    for layout, kw in (
        ("dense", dict(cache_len=256, prompt_buckets=(32, 64))),
        ("paged", dict(num_pages=96, page_size=16, max_pages_per_seq=8,
                       prompt_buckets=(16, 32, 64))),
    ):
        eng = LLMEngine(cfg, params, kv_layout=layout, max_batch=3, **kw)
        results = {r.uid: r for r in eng.generate([r.clone() for r in reqs])}
        assert sorted(results) == [0, 1, 2, 3]
        for uid in (0, 3):  # greedy rows == oracle regardless of batchmates
            want = direct_greedy(cfg, params, prompts[uid],
                                 mk[uid].max_tokens)
            assert toks_of(results[uid]) == want, (layout, uid)
        for uid in (1, 2):  # stochastic rows reproduce solo (same seed)
            solo = LLMEngine(cfg, params, kv_layout=layout, max_batch=1, **kw)
            (ref,) = solo.generate([reqs[uid].clone()])
            assert toks_of(results[uid]) == toks_of(ref), (layout, uid)


def test_kv_layout_auto_resolves_through_plan_layer(llama):
    cfg, params = llama
    eng = LLMEngine(cfg, params, max_batch=2, num_pages=32, page_size=16,
                    max_pages_per_seq=4, prompt_buckets=(16, 32))
    # The analytic NUMA decode model prefers the paged pool over streaming
    # full dense stripes for an attention-only model.
    assert eng.kv_layout == "paged"
    # The zero-knob constructor (the README example) must be valid: the
    # default per-sequence page cap clamps to what the pool can hold.
    eng_default = LLMEngine(cfg, params)
    assert eng_default.kv_layout == "paged"
    assert eng_default.backend.max_pages_per_seq <= \
        eng_default.backend.pool.num_pages - 1
    # Models the paged subsystem cannot hold fall back to dense.
    mcfg = registry.get_smoke_config("musicgen-medium")
    mparams = transformer.init_model(jax.random.PRNGKey(0), mcfg)
    meng = LLMEngine(mcfg, mparams, max_batch=2, cache_len=64,
                     prompt_buckets=(16,))
    assert meng.kv_layout == "dense"
    with pytest.raises(ValueError, match="single-codebook"):
        LLMEngine(mcfg, mparams, kv_layout="paged", max_batch=2)
    with pytest.raises(ValueError, match="kv_layout"):
        LLMEngine(cfg, params, kv_layout="sparse")


def test_multi_codebook_serving(llama):
    """MusicGen-style (S, K) prompts serve through the facade (dense
    fallback) with (K,) token outputs."""
    mcfg = registry.get_smoke_config("musicgen-medium")
    mparams = transformer.init_model(jax.random.PRNGKey(0), mcfg)
    rng = np.random.default_rng(22)
    eng = LLMEngine(mcfg, mparams, max_batch=2, cache_len=64,
                    prompt_buckets=(16,))
    res = eng.generate([
        Request(uid=0, prompt=rng.integers(1, 200, size=(8, 4)),
                max_new_tokens=3),
        Request(uid=1, prompt=rng.integers(1, 200, size=(6, 4)),
                sampling=SamplingParams(temperature=0.7, max_tokens=3,
                                        seed=1)),
    ])
    assert sorted(r.uid for r in res) == [0, 1]
    for r in res:
        assert all(np.asarray(t).shape == (4,) for t in r.tokens), r.uid


# --- paged backend ------------------------------------------------------------


def test_paged_matches_direct_with_prefix_sharing(llama):
    """Requests sharing a system prompt: pages are reused (hit rate > 0),
    only tails are prefilled, and every output still equals the dense
    direct greedy decode."""
    cfg, params = llama
    rng = np.random.default_rng(3)
    system = rng.integers(1, 400, size=(32,))
    prompts = [np.concatenate([system, rng.integers(1, 400, size=(L,))])
               for L in (5, 18, 2)]
    prompts.append(rng.integers(1, 400, size=(9,)))  # unshared
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=64,
                    page_size=16, max_batch=3, max_pages_per_seq=8,
                    prompt_buckets=(16, 32, 64))
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    results = eng.generate(reqs)
    assert len(results) == len(reqs)
    for r in results:
        want = direct_greedy(cfg, params, prompts[r.uid], 4)
        assert toks_of(r) == want, r.uid
    stats = eng.stats()
    assert stats.prefix_hit_rate > 0
    assert eng.backend.stats["pages_reused"] >= 2 * 2  # 2-page prefix, 2 reusers
    # all sequence pages released; only prefix-cache pages remain in use
    assert eng.backend.pool.used_pages == len(eng.backend.prefix)
    # Admission pricing (quote) is a pure peek: no LRU refresh, no
    # phantom hit-rate queries, however often the scheduler re-prices.
    before = eng.backend.prefix.stats()
    for _ in range(3):
        eng.backend.quote(Request(uid=99, prompt=prompts[0],
                                  max_new_tokens=2))
    assert eng.backend.prefix.stats() == before


def test_close_proves_zero_leak_teardown(llama):
    """`close()` mid-flight releases live rows, drains the prefix cache,
    and `PagePool.check_leaks()` certifies every page returned — the
    teardown path is the leak detector, not a best-effort cleanup."""
    cfg, params = llama
    rng = np.random.default_rng(11)
    system = rng.integers(1, 400, size=(32,))
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=64,
                    page_size=16, max_batch=3, max_pages_per_seq=8,
                    prompt_buckets=(16, 64))
    for i in range(3):
        tail = rng.integers(1, 400, size=(6 + i,))
        eng.add_request(Request(uid=i, prompt=np.concatenate([system, tail]),
                                max_new_tokens=32))
    for _ in range(4):   # partway through decode: rows + prefix pages live
        eng.step()
    assert eng.backend.pool.used_pages > 0
    assert eng.backend.check_leaks() == {}      # live refs fully explained
    eng.close()
    assert eng.backend.pool.used_pages == 0     # rows AND prefix drained
    assert eng.backend.pool.check_leaks() == {}


def test_paged_release_of_empty_row_raises(llama):
    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=32,
                    page_size=16, max_batch=2, max_pages_per_seq=8,
                    prompt_buckets=(16,))
    from repro.cache.pool import SequenceReleasedError
    with pytest.raises(SequenceReleasedError):
        eng.backend.release(0)   # row holds no sequence


def test_paged_preemption_under_page_pressure(llama):
    """A pool too small for all concurrent sequences preempts the lowest
    priority one, requeues it, and still completes everything exactly —
    the bit-match-across-preemption acceptance check."""
    cfg, params = llama
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 400, size=(20,)) for _ in range(3)]
    # 9 usable pages; each sequence grows to 4 pages (20 + 30 tokens).
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=10,
                    page_size=16, max_batch=3, max_pages_per_seq=4,
                    prompt_buckets=(16, 32))
    reqs = [Request(uid=i, prompt=p, max_new_tokens=30, priority=i)
            for i, p in enumerate(prompts)]
    results = eng.generate(reqs)
    assert sorted(r.uid for r in results) == [0, 1, 2]
    stats = eng.stats()
    assert stats.preemptions >= 1
    # The victim had decoded tokens before eviction and they were replayed
    # (restart-from-scratch would leave this at 0).
    assert stats.resumed_tokens > 0
    for r in results:
        want = direct_greedy(cfg, params, prompts[r.uid], 30)
        assert toks_of(r) == want, r.uid


def test_paged_prefix_reuse_survives_eviction_pressure(llama):
    """Admission that must evict prefix-cache pages to fit may never
    recycle the very pages it is about to reuse: here the request matches
    page 1 of a cached 3-page prefix while eviction frees pages 2-3, and
    the decoded output must still be exact."""
    cfg, params = llama
    rng = np.random.default_rng(6)
    prompt_a = rng.integers(1, 400, size=(48,))
    prompt_b = np.concatenate([prompt_a[:16], rng.integers(1, 400, size=(48,))])
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=6,
                    page_size=16, max_batch=1, max_pages_per_seq=5,
                    prompt_buckets=(16, 32, 48, 64))
    reqs = [Request(uid=0, prompt=prompt_a, max_new_tokens=16),
            Request(uid=1, prompt=prompt_b, max_new_tokens=16)]
    results = eng.generate(reqs)
    assert sorted(r.uid for r in results) == [0, 1]
    assert eng.backend.stats["pages_reused"] >= 1
    assert eng.backend.stats["prefix_evictions"] >= 2
    for r in results:
        want = direct_greedy(cfg, params, reqs[r.uid].prompt, 16)
        assert toks_of(r) == want, r.uid


def test_paged_prefill_compile_cache_is_log_bounded(llama):
    """Diverse live prefix lengths must NOT mint one tail-prefill
    compilation each: the jit key buckets prefix pages to powers of two,
    so the cache stays O(log smax) while outputs remain exact."""
    cfg, params = llama
    rng = np.random.default_rng(7)
    base = rng.integers(1, 400, size=(96,))  # 6 full pages once published
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=64,
                    page_size=16, max_batch=2, max_pages_per_seq=10,
                    prompt_buckets=(16, 32, 64, 96))
    prompts = [base]
    for i in range(1, 7):
        prompts.append(
            np.concatenate([base[: 16 * i], rng.integers(1, 400, size=(8,))])
        )
    reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts)]
    results = eng.generate(reqs)
    assert len(results) == len(reqs)
    for r in results:
        want = direct_greedy(cfg, params, prompts[r.uid], 3)
        assert toks_of(r) == want, r.uid
    backend = eng.backend
    assert backend.stats["extend_prefills"] >= 5
    prefix_keys = {k[1] for k in backend._prefill_p if k[1] > 0}
    assert all(p & (p - 1) == 0 for p in prefix_keys), prefix_keys
    import math

    assert len(prefix_keys) <= \
        math.ceil(math.log2(backend.max_pages_per_seq)) + 1, prefix_keys


def test_paged_resume_truncates_oversized_replay(llama):
    """A resumed request whose prompt+generated replay tail exceeds every
    prefill bucket must shed replayed tokens until the tail fits (they are
    regenerated by decode) instead of raising mid-run."""
    cfg, params = llama
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, 400, size=(30,))
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=32,
                    page_size=16, max_batch=2, max_pages_per_seq=5,
                    prompt_buckets=(16, 32))
    backend = eng.backend
    req = Request(uid=0, prompt=prompt, max_new_tokens=45)
    # Seed the prefix cache with the prompt's full page, as a prior
    # admission would have.
    rec = backend.try_admit(req)
    assert rec is not None
    eng._flush([rec])
    assert backend._preempt_one(protect=-1)
    assert eng.scheduler.num_waiting == 1  # requeued for resume
    # Resume with a 40-token replay: tail 30+40-16 = 54 exceeds bucket 32,
    # so the engine must keep only the 18 replayed tokens that fit
    # (30+18-16 = 32) and re-decode the rest.
    fake = [int(t) for t in rng.integers(1, 400, size=(40,))]
    rec = backend.try_admit(req, resume_tokens=fake)
    assert rec is not None
    row = rec["row"]
    assert backend.out[row] == fake[:18]
    assert backend.lengths[row] == 30 + 18
    assert backend.stats["resumed_tokens"] == 18


def test_paged_batched_prefills_bit_exact(llama):
    """Batched prefill flushing: ready requests sharing a jit bucket ride
    one tail-prefill launch — fewer launches, identical tokens vs the
    one-launch-per-request oracle, and still equal to direct greedy."""
    cfg, params = llama
    rng = np.random.default_rng(10)
    system = rng.integers(1, 400, size=(32,))
    prompts = []
    for i in range(6):
        tail = rng.integers(1, 400, size=(int(rng.integers(2, 14)),))
        prompts.append(np.concatenate([system, tail]) if i % 3 else tail)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    kw = dict(kv_layout="paged", num_pages=96, page_size=16, max_batch=4,
              max_pages_per_seq=8, prompt_buckets=(16, 32, 64))

    batched = LLMEngine(cfg, params, batch_prefills=True, **kw)
    res_b = batched.generate([r.clone() for r in reqs])
    serial = LLMEngine(cfg, params, batch_prefills=False, **kw)
    res_s = serial.generate([r.clone() for r in reqs])

    toks_b = {r.uid: toks_of(r) for r in res_b}
    toks_s = {r.uid: toks_of(r) for r in res_s}
    assert toks_b == toks_s  # bit-exact across the two flush modes
    for uid, toks in toks_b.items():
        assert toks == direct_greedy(cfg, params, prompts[uid], 4), uid
    assert batched.backend.stats["batched_prefills"] > 0
    assert batched.backend.stats["prefill_launches"] < \
        serial.backend.stats["prefill_launches"]
    assert serial.backend.stats["batched_prefills"] == 0


def test_paged_batched_extend_rows_share_one_launch(llama):
    """Several requests matching the same cached prefix (same tail bucket
    and page bucket) must extend in ONE launch with per-row prefix
    lengths."""
    cfg, params = llama
    rng = np.random.default_rng(11)
    base = rng.integers(1, 400, size=(32,))
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=96,
                    page_size=16, max_batch=4, max_pages_per_seq=8,
                    prompt_buckets=(16, 32, 64))
    # Publish the prefix first (its own flush), then three same-bucket
    # extenders arrive together.
    eng.generate([Request(uid=0, prompt=base, max_new_tokens=2)])
    backend = eng.backend
    launches_before = backend.stats["prefill_launches"]
    tails = [rng.integers(1, 400, size=(6 + i,)) for i in range(3)]
    reqs = [Request(uid=10 + i, prompt=np.concatenate([base, t]),
                    max_new_tokens=3) for i, t in enumerate(tails)]
    results = eng.generate(reqs)
    assert len(results) == 3
    assert backend.stats["extend_prefills"] >= 3
    assert backend.stats["prefill_launches"] == launches_before + 1
    assert backend.stats["batched_prefills"] >= 1
    # A (bucket, pages, rows=3) jit key exists — the kernel consumed (B,)
    # prefix/tail lengths in one call.
    assert any(k[2] == 3 and k[1] > 0 for k in backend._prefill_p), \
        sorted(backend._prefill_p)
    for r in results:
        want = direct_greedy(
            cfg, params, np.concatenate([base, tails[r.uid - 10]]), 3
        )
        assert toks_of(r) == want, r.uid


def test_paged_rejects_unservable_request_at_add(llama):
    """prompt + max_tokens that cannot fit max_pages_per_seq must fail at
    add_request, not crash mid-decode."""
    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=16,
                    page_size=16, max_batch=2, max_pages_per_seq=4,
                    prompt_buckets=(16, 32))
    bad = Request(uid=0, prompt=np.arange(1, 17), max_new_tokens=60)
    with pytest.raises(ValueError, match="outgrow"):
        eng.add_request(bad)
    assert eng.backend.pool.used_pages == 0  # nothing leaked
    assert eng.scheduler.num_waiting == 0    # nothing queued
    # Without prefix sharing, a prompt no bucket holds can never be
    # served either: rejected at add_request, not mid-run.
    eng2 = LLMEngine(cfg, params, kv_layout="paged", num_pages=16,
                     page_size=16, max_batch=2, max_pages_per_seq=8,
                     prompt_buckets=(16, 32), prefix_sharing=False)
    with pytest.raises(ValueError, match="exceeds buckets"):
        eng2.add_request(Request(uid=0, prompt=np.arange(1, 49),
                                 max_new_tokens=3))
    # Passing both a Request and loose keywords (incl. priority) errors.
    with pytest.raises(ValueError, match="either"):
        eng2.add_request(Request(uid=1, prompt=np.arange(1, 9),
                                 max_new_tokens=2), priority=5)


def test_poison_request_flushes_good_rows_and_is_ejected(llama):
    """A request whose tail overflows every prefill bucket only surfaces
    at admission time. It must (a) not strand same-round good rows
    unprefilled — the flush runs before the error propagates — and (b) be
    ejected from the queue so later steps are not wedged."""
    cfg, params = llama
    rng = np.random.default_rng(12)
    good = Request(uid=0, prompt=rng.integers(1, 400, size=(10,)),
                   max_new_tokens=3)
    # Fits pages (48 + 3 tokens < 5 pages) but no 48-token tail bucket.
    bad = Request(uid=1, prompt=rng.integers(1, 400, size=(48,)),
                  max_new_tokens=3)
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=64,
                    page_size=16, max_batch=2, max_pages_per_seq=5,
                    prompt_buckets=(16, 32))
    with pytest.raises(ValueError, match="exceeds buckets"):
        eng.generate([good, bad])
    row = int(np.flatnonzero(eng.backend.active)[0])
    assert row in eng._pending  # good row's prefill was flushed + sampled
    assert eng.scheduler.num_waiting == 0  # poison request ejected
    res = eng.generate([])  # drain the good request to completion
    assert toks_of(res[0]) == direct_greedy(cfg, params, good.prompt, 3)


def test_paged_pool_must_hold_one_max_sequence(llama):
    """A pool smaller than one max-size sequence would hit OutOfPages
    mid-decode with nothing to preempt; reject at construction."""
    cfg, params = llama
    with pytest.raises(ValueError, match="cannot hold"):
        LLMEngine(cfg, params, kv_layout="paged", num_pages=4, page_size=16,
                  max_batch=1, max_pages_per_seq=4, prompt_buckets=(16,))


def test_paged_admission_is_page_governed(llama):
    """With rows to spare but pages for only one sequence at a time, the
    scheduler serializes admission instead of overcommitting."""
    cfg, params = llama
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 400, size=(30,)) for _ in range(2)]
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=6,
                    page_size=16, max_batch=4, max_pages_per_seq=3,
                    prompt_buckets=(16, 32), prefix_sharing=False,
                    reserve_pages=1)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=14)
            for i, p in enumerate(prompts)]
    results = eng.generate(reqs)
    assert sorted(r.uid for r in results) == [0, 1]
    for r in results:
        want = direct_greedy(cfg, params, prompts[r.uid], 14)
        assert toks_of(r) == want, r.uid


def test_generate_raises_when_nothing_can_fit(llama):
    """A request that passes per-request validation but can never be
    admitted (pages + decode headroom exceed the whole pool) must raise
    OutOfPages from generate — carrying the outputs that already finished
    this call, not discarding them."""
    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=10,
                    page_size=16, max_batch=2, max_pages_per_seq=8,
                    prompt_buckets=(16, 32, 64), reserve_pages=6)
    good = Request(uid=0, prompt=np.arange(1, 11) % 400, max_new_tokens=3)
    # 4 prompt pages + 6 reserve > 9 usable: the scheduler's page budget
    # can never clear it.
    big = Request(uid=1, prompt=np.arange(1, 65) % 400, max_new_tokens=3)
    with pytest.raises(OutOfPages) as ei:
        eng.generate([good, big])
    (done,) = ei.value.completed  # the finished request survives the error
    assert done.uid == 0 and done.finish_reason == "length"
    assert toks_of(done) == direct_greedy(cfg, params, good.prompt, 3)


# --- deprecated shims ---------------------------------------------------------


def test_deprecated_shims_are_drop_in(llama):
    """Old constructor surface + run() still work (with a
    DeprecationWarning) and produce exactly the facade's outputs."""
    from repro.serving import PagedServingEngine, Result, ServingEngine

    cfg, params = llama
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, 400, size=(L,)) for L in (8, 20)]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4, temperature=0.0)
            for i, p in enumerate(prompts)]

    with pytest.warns(DeprecationWarning):
        dense = ServingEngine(cfg, params, num_slots=2, cache_len=128,
                              prompt_buckets=(32,))
    res = dense.run([r.clone() for r in reqs])
    assert all(isinstance(r, Result) for r in res)
    for r in res:
        assert [int(t) for t in r.tokens] == \
            direct_greedy(cfg, params, prompts[r.uid], 4), r.uid

    with pytest.warns(DeprecationWarning):
        paged = PagedServingEngine(cfg, params, num_pages=32, page_size=16,
                                   max_batch=2, max_pages_per_seq=4,
                                   prompt_buckets=(16, 32))
    res_p = paged.run([r.clone() for r in reqs])
    for r in res_p:
        assert [int(t) for t in r.tokens] == \
            direct_greedy(cfg, params, prompts[r.uid], 4), r.uid
    # Thin delegation: legacy introspection still reachable.
    assert paged.pool.used_pages == len(paged.prefix)
    assert paged.prefix_stats()["prefill_launches"] >= 2
    # Hand-driven submit()+step() loops still populate .results.
    manual = Request(uid=9, prompt=prompts[0], max_new_tokens=2)
    assert paged.submit(manual)
    for _ in range(5):
        paged.step()
    assert any(r.uid == 9 for r in paged.results)
    with pytest.raises(KeyError), pytest.warns(DeprecationWarning):
        ServingEngine(cfg, params, num_slots=1, cache_len=64,
                      prompt_buckets=(16,), mapping="bogus")


def test_no_legacy_engine_construction_outside_serving():
    """The deprecated engine classes may only be constructed inside
    ``src/repro/serving/`` — and this test file, which tests the shims
    themselves. Everything else goes through ``LLMEngine``. Single
    implementation: the linter's ``no-legacy-engine-construction`` rule."""
    from repro.analysis import run_rules

    assert run_rules(rules=["no-legacy-engine-construction"]) == []


# --- telemetry (PR 7) ---------------------------------------------------------


def test_disabled_telemetry_shares_null_instruments(llama):
    """The telemetry-off contract: an un-instrumented engine threads the
    module-level no-op singletons — no span or metric objects exist per
    step, and nothing is recorded."""
    from repro.obs import NULL_TELEMETRY
    from repro.obs.metrics import NULL_COUNTER, NULL_HISTOGRAM
    from repro.obs.tracing import NULL_SPAN

    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=2,
                    cache_len=128, prompt_buckets=(16,))
    assert eng.telemetry is NULL_TELEMETRY
    assert eng._m_steps is NULL_COUNTER
    assert eng._h_decode is NULL_HISTOGRAM
    assert eng._tr.span("step") is eng._tr.span("decode") is NULL_SPAN
    assert eng.backend.tracer is NULL_TELEMETRY.tracer
    rng = np.random.default_rng(0)
    eng.generate([Request(uid=0, prompt=rng.integers(1, 400, size=(8,)),
                          max_new_tokens=3)])
    assert NULL_COUNTER.value == 0.0
    assert NULL_HISTOGRAM.count == 0
    assert eng.telemetry.tracer.spans == []
    assert eng.telemetry.drift.num_samples == 0


def test_telemetry_records_lifecycle_spans_and_drift(llama):
    from repro.obs import Telemetry

    cfg, params = llama
    tel = Telemetry.create()
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=2,
                    cache_len=128, prompt_buckets=(16,), telemetry=tel)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(1, 400, size=(8,)),
                    max_new_tokens=4) for i in range(2)]
    results = eng.generate(reqs)
    assert len(results) == 2

    snap = tel.metrics.snapshot()
    assert snap["serving_requests_total"]["value"] == 2.0
    assert snap["serving_finished_total"]["value"] == 2.0
    total = sum(len(r.tokens) for r in results)
    assert snap["serving_tokens_total"]["value"] == float(total)
    assert snap["serving_steps_total"]["value"] > 0
    assert snap["serving_decode_step_seconds"]["count"] > 0

    span_names = {s.name for s in tel.tracer.spans}
    assert {"step", "schedule", "flush", "decode"} <= span_names
    for uid in (0, 1):
        events = [e for e, _, _ in tel.tracer.request_lifecycle(uid)]
        assert events[0] == "arrival" and events[-1] == "finish"
        assert "admitted" in events and "first_token" in events
        lat = tel.tracer.request_latencies()[uid]
        assert lat["ttft"] is not None and lat["ttft"] >= 0
        assert lat["e2e"] is not None and lat["e2e"] >= lat["ttft"]
        # max_new_tokens=4 -> first token + 3 inter-token intervals
        assert len(lat["itl"]) == 3

    assert tel.drift.num_samples > 0
    report = tel.drift.report(eng.drift_model_fn())
    assert report.rows and report.worst_ratio() is not None


def test_telemetry_counts_preemptions(llama):
    """Preempt/resume lifecycles reach the tracer and the counter (the
    page-pressure trace from test_paged_preemption_under_page_pressure,
    instrumented)."""
    from repro.obs import Telemetry

    cfg, params = llama
    tel = Telemetry.create()
    eng = LLMEngine(cfg, params, kv_layout="paged", num_pages=17,
                    page_size=16, max_batch=2, max_pages_per_seq=16,
                    prompt_buckets=(16, 32), prefix_sharing=False,
                    telemetry=tel)
    rng = np.random.default_rng(3)
    reqs = [
        Request(uid=0, prompt=rng.integers(1, 400, size=(16,)),
                max_new_tokens=40, priority=1),
        Request(uid=1, prompt=rng.integers(1, 400, size=(16,)),
                max_new_tokens=8),
    ]
    results = eng.generate(reqs)
    assert len(results) == 2
    stats = eng.stats()
    if stats.preemptions:  # page pressure fired
        snap = tel.metrics.snapshot()
        assert snap["serving_preemptions_total"]["value"] == \
            float(stats.preemptions)
        preempted = [
            uid for uid in (0, 1)
            if any(e == "preempt"
                   for e, _, _ in tel.tracer.request_lifecycle(uid))
        ]
        assert preempted, "preemption happened but no lifecycle event"
        for uid in preempted:
            events = [e for e, _, _ in tel.tracer.request_lifecycle(uid)]
            assert "resume" in events, events
            assert tel.tracer.request_latencies()[uid]["preemptions"] >= 1


def test_stats_split_measured_vs_modeled(llama):
    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=2,
                    cache_len=128, prompt_buckets=(16,))
    rng = np.random.default_rng(4)
    eng.generate([Request(uid=0, prompt=rng.integers(1, 400, size=(8,)),
                          max_new_tokens=4)])
    stats = eng.stats()
    assert stats.tokens_per_s > 0
    assert stats.measured_tok_s > 0
    assert stats.decode_elapsed_s > 0
    # Decode-phase wall time is a subset of total engine wall time, so
    # the decode-normalized rate can only be faster.
    assert stats.decode_elapsed_s <= stats.elapsed_s
    assert stats.measured_tok_s >= stats.tokens_per_s
    assert stats.modeled_tok_s > 0
    assert "measured decode" in stats.summary()


def test_modeled_tok_s_near_zero_model_reports_zero(llama):
    """The PR-7 satellite fix: a denormal decode_time_model result used
    to print as 10^15 modeled tok/s; safe_rate reports 0.0 (unknown)."""
    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=1,
                    cache_len=128, prompt_buckets=(16,))
    eng.backend.decode_time_model = lambda batch, mean_len=None: 1e-12
    stats = eng.stats()
    assert stats.modeled_tok_s == 0.0
    # And zero elapsed/decode time reports 0.0 rates, not a blow-up.
    assert stats.tokens_per_s == 0.0
    assert stats.measured_tok_s == 0.0


def test_dense_prefix_hit_rate_is_none_not_zero(llama):
    """Dense engines have no prefix cache: stats must say "n/a" (None),
    never a fake 0.0 that reads as a cold cache (PR 7 satellite)."""
    from repro.obs import Telemetry

    cfg, params = llama
    eng = LLMEngine(cfg, params, kv_layout="dense", max_batch=1,
                    cache_len=128, prompt_buckets=(16,))
    ps = eng.backend.prefix_stats()
    assert ps["prefix_hit_rate"] is None
    assert ps["prefix_lookup_queries"] == 0.0
    assert eng.stats().prefix_hit_rate is None
    assert "prefix hit n/a" in eng.stats().summary()

    # Paged engines report a real float (0.0 means "never shared").
    tel = Telemetry.create()
    peng = LLMEngine(cfg, params, kv_layout="paged", num_pages=96,
                     page_size=16, max_batch=2, max_pages_per_seq=8,
                     prompt_buckets=(16, 32), telemetry=tel)
    pps = peng.backend.prefix_stats()
    assert pps["prefix_hit_rate"] == 0.0
    assert peng.stats().prefix_hit_rate == 0.0
    assert {"prefix_lookup_hits", "prefix_lookup_queries",
            "prefix_evictions"} <= set(pps)
