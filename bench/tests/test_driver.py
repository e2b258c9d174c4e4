"""Due-time TTFT, TPOT and window tok/s on a fake clock."""

import numpy as np
import pytest

from bench import driver
from bench.traffic import Req


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class FakeEngine:
    """Admits everything; each step takes ``step_s`` (``stall_s`` for the
    step that starts in ``stall_at``) and gives every live request
    ``per_step`` tokens, the first of them one step after admission."""

    def __init__(self, clock, step_s=0.1, per_step=4, stall_at=None, stall_s=0.0):
        self.clock, self.step_s, self.per_step = clock, step_s, per_step
        self.stall_at, self.stall_s = stall_at, stall_s
        self.live = {}
        self.next_uid = 0
        self.t0 = clock.t

    def submit(self, req):
        uid = self.next_uid
        self.next_uid += 1
        self.live[uid] = req.max_tokens
        return uid

    def busy(self):
        return bool(self.live)

    def step(self):
        now = self.clock.t - self.t0
        stall = self.stall_at is not None and self.stall_at[0] <= now < self.stall_at[1]
        self.clock.t += self.stall_s if stall else self.step_s
        out = []
        for uid in list(self.live):
            n = min(self.per_step, self.live[uid])
            self.live[uid] -= n
            done = self.live[uid] == 0
            if done:
                del self.live[uid]
            out.append((uid, list(range(n)), done))
        return out


def _reqs(dues, max_tokens=8):
    return [Req(key=f"r{i}", prompt=np.ones(4, np.int32), max_tokens=max_tokens,
                temperature=0.0, top_p=1.0, seed=0, due=d)
            for i, d in enumerate(dues)]


def _run(engine, clock, reqs, seconds):
    return driver.run_window(engine, seconds=seconds, open_reqs=reqs,
                             clock=clock, sleep=clock.sleep)


def test_ttft_counts_from_the_due_time_and_tpot_over_the_tokens():
    clock = Clock()
    w = _run(FakeEngine(clock), clock, _reqs([0.05, 0.5]), 2.0)
    first, second = w.records
    # Due at 0.05, submitted at 0.05 (the loop slept to it), first tokens
    # returned when the step that ran from 0.05 ends at 0.15.
    assert first.first == pytest.approx(0.15)
    ttft = driver.ttft_values(w)
    assert ttft == pytest.approx([0.10, 0.10])
    # 8 tokens in two steps of 0.1 s: (0.25 - 0.15) / 7.
    assert driver.tpot_values(w) == pytest.approx([0.1 / 7, 0.1 / 7])
    assert driver.output_tok_s(w) == pytest.approx(16 / 2.0)
    e2e = driver.end_to_end(w)
    assert e2e["ttft_p50_ms"] == pytest.approx(100.0)


def test_a_stalled_step_shows_in_the_ttft_tail():
    dues = [0.1 * i for i in range(1, 20)]
    clock = Clock()
    calm = _run(FakeEngine(clock), clock, _reqs(dues, 4), 4.0)
    clock = Clock()
    stalled = _run(FakeEngine(clock, stall_at=(0.95, 1.05), stall_s=1.0),
                   clock, _reqs(dues, 4), 4.0)
    # Requests due during the 1 s stall wait for it: their clock started at
    # their due time, not when the loop got round to submitting them.
    assert driver.end_to_end(calm)["ttft_p90_ms"] < 150
    assert driver.end_to_end(stalled)["ttft_p90_ms"] > 700
    assert max(stalled.lateness) > 0.8


def test_a_request_with_no_first_token_enters_at_its_wait_so_far():
    clock = Clock()
    w = _run(FakeEngine(clock, step_s=0.3), clock, _reqs([0.9]), 1.0)
    # Its first step ends at 1.2, after the close at 1.0.
    assert w.records[0].first is None
    assert driver.ttft_values(w) == pytest.approx([0.1])
    assert driver.tpot_values(w) == []
    assert driver.output_tok_s(w) == 0.0


def test_requests_due_after_the_close_are_not_attempted():
    clock = Clock()
    w = _run(FakeEngine(clock), clock, _reqs([0.1, 5.0]), 1.0)
    assert [r.due for r in w.records] == [0.1]


def test_closed_loop_sends_the_next_turn_when_the_reply_completes():
    class S:
        def __init__(self):
            self.turns = 0

        def next_request(self, reply=()):
            self.turns += 1
            return _reqs([None], 4)[0]

    clock = Clock()
    s = S()
    w = driver.run_window(FakeEngine(clock), seconds=1.0, sessions=[s],
                          session_due=[0.0], clock=clock, sleep=clock.sleep)
    # Each turn takes one step of 0.1 s; the next is due when it returns.
    assert s.turns >= 9
    assert w.records[1].due == pytest.approx(w.records[0].first)
