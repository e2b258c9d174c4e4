"""The generator: deterministic per seed, lengths inside their clips, and
the same work for every seed."""

import numpy as np
import pytest

from bench import traffic

OPEN = {
    "loop": "open", "rate": 3.0, "max_context": 2048,
    "system_prompts": {"count": 4, "length": 512, "weights": [0.4, 0.3, 0.2, 0.1], "share": 0.8},
    "user": {"median": 192, "sigma": 0.8, "min": 16, "max": 1024},
    "output": {"median": 96, "sigma": 0.7, "min": 16, "max": 384},
    "greedy_share": 0.25, "temperature": 0.7, "top_p": 0.95,
}
CLOSED = {
    "loop": "closed", "sessions": 16, "max_context": 4096, "stagger_s": 2.0,
    "history": {"median": 2048, "sigma": 0.25, "min": 1536, "max": 3072},
    "message": {"median": 96, "sigma": 0.6, "min": 32, "max": 256},
    "reply": {"median": 128, "sigma": 0.4, "min": 64, "max": 256},
    "greedy_share": 1.0, "temperature": 0.0, "top_p": 1.0,
}
BIG_SEED = 2**31 + 12345


def _sig(reqs):
    return [(r.key, r.due, r.max_tokens, r.temperature, r.seed, r.prompt.tobytes())
            for r in reqs]


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_open_loop_is_deterministic_per_seed(seed):
    a = traffic.open_requests(OPEN, 32000, seed, 20.0)
    b = traffic.open_requests(OPEN, 32000, seed, 20.0)
    assert _sig(a) == _sig(b)
    c = traffic.open_requests(OPEN, 32000, seed + 1, 20.0)
    assert _sig(a) != _sig(c)


def test_open_loop_keeps_clips_and_the_same_work_for_every_seed():
    runs = [traffic.open_requests(OPEN, 32000, s, 20.0) for s in (1, 2, BIG_SEED)]
    for reqs in runs:
        assert len(reqs) == 60
        assert all(0.0 <= r.due <= 20.0 for r in reqs)
        assert [r.due for r in reqs] == sorted(r.due for r in reqs)
        for r in reqs:
            assert 16 <= r.max_tokens <= 384
            assert 16 <= len(r.prompt) <= 512 + 1024
            assert len(r.prompt) + r.max_tokens <= OPEN["max_context"]
            assert r.prompt.min() >= 1 and r.prompt.max() < 32000
        assert sum(r.greedy for r in reqs) == 15
    outs = [sorted(r.max_tokens for r in reqs) for reqs in runs]
    assert outs[0] == outs[1] == outs[2]
    lens = [sorted(len(r.prompt) for r in reqs) for reqs in runs]
    assert lens[0] == lens[1] == lens[2]
    gaps = [sorted(np.diff([0.0] + [r.due for r in reqs] + [20.0])) for reqs in runs]
    np.testing.assert_allclose(gaps[0], gaps[1])
    np.testing.assert_allclose(gaps[0], gaps[2])
    assert [r.due for r in runs[0]] != [r.due for r in runs[1]]


def test_arrival_gaps_are_exponential_quantiles_that_fill_the_window():
    due = traffic.arrivals(120, 50.0, BIG_SEED)
    gaps = np.diff(np.concatenate([[0.0], due, [50.0]]))
    assert len(due) == 120 and 0.0 < due[0] and due[-1] < 50.0
    assert np.all(gaps > 0) and gaps.sum() == pytest.approx(50.0)
    # Exponential: the median gap is ln 2 of the mean, the largest about
    # ln(2(n + 1)) of it.
    mean = 50.0 / 121
    assert np.median(gaps) == pytest.approx(mean * np.log(2), rel=0.02)
    assert gaps.max() == pytest.approx(mean * np.log(2 * 121), rel=0.02)


def test_system_prompts_are_shared_and_page_aligned():
    reqs = traffic.open_requests(OPEN, 32000, 5, 20.0)
    heads = {}
    for r in reqs:
        if len(r.prompt) > 512:
            heads.setdefault(r.prompt[:512].tobytes(), 0)
            heads[r.prompt[:512].tobytes()] += 1
    # 80% of 60 requests behind 4 prompts weighted 0.4/0.3/0.2/0.1.
    assert sorted(heads.values(), reverse=True)[:4] == [19, 14, 10, 5]


def test_sessions_keep_clips_and_restart_before_the_context_fills():
    sess = traffic.sessions(CLOSED, 102400, BIG_SEED)
    hist = sorted(len(s.history) for s in sess)
    assert hist == sorted(len(s.history) for s in traffic.sessions(CLOSED, 102400, 7))
    assert all(1536 <= n <= 3072 for n in hist)
    s = sess[0]
    first = s.next_request()
    assert np.array_equal(first.prompt[: len(s.history)], s.history)
    assert 32 <= len(first.prompt) - len(s.history) <= 256
    restarted = False
    for _ in range(40):
        reply = np.arange(first.max_tokens, dtype=np.int32) + 1
        nxt = s.next_request(reply)
        assert 64 <= nxt.max_tokens <= 256
        assert len(nxt.prompt) + nxt.max_tokens <= CLOSED["max_context"]
        grown = np.concatenate([first.prompt, reply])
        if len(nxt.prompt) > len(grown) and np.array_equal(nxt.prompt[: len(grown)], grown):
            pass
        else:
            restarted = True
        first = nxt
    assert restarted


def test_first_turns_are_staggered_over_the_window_start():
    due = traffic.first_turn_due(CLOSED, 3)
    assert sorted(due) == sorted(traffic.first_turn_due(CLOSED, 4))
    assert all(0.0 < d < 2.0 for d in due)


def test_reachable_prefills_cover_the_mixes():
    assert traffic.reachable_prefills(OPEN, 16) == [
        ((0, 0), (16, 1024)), ((512, 512), (16, 1024)), ((0, 0), (528, 1536))]
    setup, restart, turn = traffic.reachable_prefills(CLOSED, 16)
    assert setup == ((0, 0), (1536, 3072))
    assert restart == ((0, 0), (1568, 3328))
    assert turn == ((1568, 3840), (96, 527))
