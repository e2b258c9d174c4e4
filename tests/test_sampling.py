"""On-device batched sampler edge cases (PR-5 satellite).

temperature=0 == argmax exactly; top-k=1 == greedy; top-p keeps the
smallest sorted-mass set (boundary token included); per-request seeds are
independent of batch composition; multi-codebook shapes sample one token
per codebook with codebook-distinct streams.
"""

import numpy as np
import pytest

from repro.serving.sampling import sample_tokens


def _params(b, temperature=1.0, top_k=0, top_p=1.0, seed=0, pos=0):
    return (
        np.full((b,), temperature, np.float32),
        np.full((b,), top_k, np.int32),
        np.full((b,), top_p, np.float32),
        np.full((b,), seed, np.int32),
        np.full((b,), pos, np.int32),
    )


def test_temperature_zero_is_exact_argmax():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(5, 97)).astype(np.float32)
    out = np.asarray(sample_tokens(logits, *_params(5, temperature=0.0)))
    assert out.dtype == np.int32
    np.testing.assert_array_equal(out, np.argmax(logits, axis=-1))
    # ...even with adversarial top-k/top-p settings in the same call.
    t, k, p, s, c = _params(5, temperature=0.0, top_k=1, top_p=0.1)
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, t, k, p, s, c)),
        np.argmax(logits, axis=-1),
    )


def test_top_k_one_equals_greedy():
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(4, 64)).astype(np.float32)
    for pos in range(5):  # any stream position
        out = np.asarray(sample_tokens(
            logits, *_params(4, temperature=1.3, top_k=1, pos=pos)
        ))
        np.testing.assert_array_equal(out, np.argmax(logits, axis=-1))


def test_top_k_support_is_the_k_largest():
    rng = np.random.default_rng(2)
    logits = rng.normal(size=(1, 32)).astype(np.float32)
    topk = set(np.argsort(logits[0])[-5:])
    seen = set()
    for pos in range(200):
        out = np.asarray(sample_tokens(
            logits, *_params(1, temperature=2.0, top_k=5, pos=pos)
        ))
        seen.add(int(out[0]))
    assert seen <= topk
    assert len(seen) > 1  # actually stochastic


def test_top_p_mass_boundary():
    """probs (0.5, 0.25, 0.15, 0.10): top_p keeps the smallest sorted set
    whose mass reaches p — {0} at 0.4 (the top token always survives),
    {0,1} at 0.6 (mass before token 1 is 0.5 < 0.6; before token 2 it is
    0.75 >= 0.6), {0,1,2} at 0.8."""
    logits = np.log(np.array([[0.5, 0.25, 0.15, 0.10]], np.float32))
    for top_p, want in ((0.4, {0}), (0.6, {0, 1}), (0.8, {0, 1, 2}),
                        (1.0, {0, 1, 2, 3})):
        seen = set()
        for pos in range(300):
            out = np.asarray(sample_tokens(
                logits, *_params(1, temperature=1.0, top_p=top_p, pos=pos)
            ))
            seen.add(int(out[0]))
        assert seen <= want, (top_p, seen)
        if len(want) > 1:
            assert len(seen) > 1, (top_p, seen)


def test_top_p_ties_at_cutoff_are_kept():
    """Tokens tied with the boundary probability all stay in the nucleus
    (value-threshold semantics): probs (0.5, 0.25, 0.125, 0.125) at
    top_p=0.8 keep token 3 because it ties token 2's cutoff prob."""
    logits = np.log(np.array([[0.5, 0.25, 0.125, 0.125]], np.float32))
    seen = set()
    for pos in range(400):
        out = np.asarray(sample_tokens(
            logits, *_params(1, temperature=1.0, top_p=0.8, pos=pos)
        ))
        seen.add(int(out[0]))
    assert seen == {0, 1, 2, 3}


def test_per_request_seeds_independent_within_batch():
    """Same logits in every row: equal seeds produce identical streams
    regardless of row position; a different seed diverges."""
    rng = np.random.default_rng(3)
    row = rng.normal(size=(128,)).astype(np.float32)
    logits = np.stack([row, row, row])
    t = np.full((3,), 1.0, np.float32)
    k = np.zeros((3,), np.int32)
    p = np.ones((3,), np.float32)
    seeds = np.asarray([7, 7, 9], np.int32)
    streams = {0: [], 1: [], 2: []}
    for pos in range(40):
        out = np.asarray(sample_tokens(
            logits, t, k, p, seeds, np.full((3,), pos, np.int32)
        ))
        for r in range(3):
            streams[r].append(int(out[r]))
    assert streams[0] == streams[1]   # same seed, different rows
    assert streams[0] != streams[2]   # different seed diverges


def test_seed_stream_independent_of_batch_size():
    """A request's stream depends only on (seed, position, logits) — not
    on how many rows share the tick (reproducible across batch
    compositions, the resume-after-preemption guarantee)."""
    rng = np.random.default_rng(4)
    row = rng.normal(size=(64,)).astype(np.float32)
    solo = [int(np.asarray(sample_tokens(
        row[None], *_params(1, temperature=0.9, seed=5, pos=pos)))[0])
        for pos in range(10)]
    other = rng.normal(size=(3, 64)).astype(np.float32)
    batched = []
    for pos in range(10):
        logits = np.concatenate([other[:2], row[None], other[2:]])
        t = np.asarray([0.0, 1.5, 0.9, 2.0], np.float32)
        k = np.zeros((4,), np.int32)
        p = np.ones((4,), np.float32)
        s = np.asarray([1, 2, 5, 3], np.int32)
        c = np.full((4,), pos, np.int32)
        batched.append(int(np.asarray(sample_tokens(logits, t, k, p, s, c))[2]))
    assert batched == solo


def test_multi_codebook_shapes_and_streams():
    """(B, K, V) logits -> (B, K) tokens; greedy matches per-codebook
    argmax exactly (musicgen shapes); stochastic codebooks draw from
    distinct streams."""
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(2, 4, 48)).astype(np.float32)
    out = np.asarray(sample_tokens(logits, *_params(2, temperature=0.0)))
    assert out.shape == (2, 4)
    np.testing.assert_array_equal(out, np.argmax(logits, axis=-1))
    # Identical logits in every codebook: the per-codebook fold_in must
    # still decorrelate the draws (not 4 copies of one sample).
    same = np.broadcast_to(logits[:1, :1], (1, 4, 48)).copy()
    draws = set()
    for pos in range(50):
        out = np.asarray(sample_tokens(
            same, *_params(1, temperature=1.5, pos=pos)
        ))
        draws.add(tuple(out[0].tolist()))
        assert out.shape == (1, 4)
    assert any(len(set(d)) > 1 for d in draws)


def test_param_validation():
    from repro.serving.request import SamplingParams

    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(max_tokens=0)
    sp = SamplingParams(stop_token_ids=[3, np.int64(5)])
    assert sp.stop_token_ids == (3, 5)


def test_legacy_request_kwargs_build_sampling_params():
    from repro.serving.request import Request

    r = Request(uid=1, prompt=np.arange(4), max_new_tokens=7, eos_id=2,
                temperature=0.5)
    assert r.sampling.max_tokens == 7 == r.max_new_tokens
    assert r.sampling.stop_token_ids == (2,) and r.eos_id == 2
    assert r.sampling.temperature == 0.5 == r.temperature
    with pytest.raises(ValueError, match="not both"):
        from repro.serving.request import SamplingParams

        Request(uid=1, prompt=np.arange(4), sampling=SamplingParams(),
                max_new_tokens=3)


def test_row_counts_share_a_program_per_power_of_two():
    """Row counts pad to a power of two: counts 1..8 compile four
    programs (1, 2, 4, 8), and every row's token is the one it gets
    alone, sampled or greedy."""
    from repro.serving.sampling import _sample_batch

    rng = np.random.default_rng(6)
    logits = rng.normal(size=(8, 40)).astype(np.float32)
    t = np.asarray([0.0, 1.1, 0.7, 0.0, 1.9, 0.5, 0.0, 1.3], np.float32)
    k = np.zeros((8,), np.int32)
    p = np.full((8,), 0.9, np.float32)
    s = np.arange(8, dtype=np.int32) + 11
    c = np.full((8,), 3, np.int32)
    solo = [int(np.asarray(sample_tokens(
        logits[i:i + 1], t[i:i + 1], k[:1], p[:1], s[i:i + 1], c[:1]))[0])
        for i in range(8)]
    before = _sample_batch._cache_size()
    for n in range(1, 9):
        out = np.asarray(sample_tokens(logits[:n], t[:n], k[:n], p[:n],
                                       s[:n], c[:n]))
        assert out.shape == (n,)
        assert out.tolist() == solo[:n]
    assert _sample_batch._cache_size() - before <= 3   # 2, 4, 8 are new
