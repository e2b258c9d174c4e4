"""The readers of the engine's spans, events and named programs: the value
computed by hand on a made-up window, and no value where the program gives
nothing to read, as on the recorded trace of the program before its spans
and programs were named."""

import pathlib
import time

import jax
import pytest

from bench import context, driver, registry, run, trace_programs, trace_reduce
from bench.tests import tiny
from bench.trace_reduce import Event, Trace
from repro.obs.tracing import SpanRecord

DATA = pathlib.Path(__file__).parent / "data" / "session.xplane.pb.gz"
NEW = ("first_token_hold_p50_ms", "decode_scan_device_ms",
       "decode_scan_copy_share", "host_gap_per_sync_ms")
MS = 1_000_000  # ns
DEV = "/device:TPU:0"
T0 = 100.0      # the tracer's clock at the window's start


def _ctx(spans, events, trace, records=(), steps=()):
    return context.Ctx(
        window=driver.Window(seconds=1.0, records=list(records),
                             steps=list(steps), lateness=[]),
        t0=T0, max_batch=4, page_size=16, num_pages=64, shape=None, peak={},
        spans=spans, events=events, prefix_before={}, prefix_after={},
        pool_used=[], admissions=[], trace=trace)


def _ev(name, a, b):
    return Event(name, a * MS, b * MS)


@pytest.fixture
def made_up(monkeypatch):
    """A 1 s window: two decode scans of 4 ticks (200 ms each, 50 + 20 ms
    of copies), a prefill, and the engine's spans on the host."""
    modules = {DEV: [_ev("jit_decode_scan", 100, 300), _ev("jit_prefill", 320, 400),
                     _ev("jit_decode_scan", 500, 700)]}
    monkeypatch.setattr(trace_programs, "of", lambda t: modules)
    trace = Trace(
        window=(0, 1000 * MS),
        devices={DEV: [_ev("copy.1", 100, 150), _ev("fusion.2", 150, 300),
                       _ev("copy.5", 320, 400), _ev("copy", 500, 520),
                       _ev("copy-done", 520, 530),
                       _ev("paged_flash_decode.3", 530, 700)]},
        host=[_ev("bench.window", 0, 1000), _ev("bench.step", 55, 755),
              _ev("step", 60, 750), _ev("flush", 300, 420),
              _ev("decode", 430, 740), _ev("decode.sync", 440, 720),
              _ev("np.asarray(jax.Array)", 445, 715)],
    )
    spans = [SpanRecord("decode", T0 + 0.43, T0 + 0.74, 1,
                        {"batch": 2, "steps": 4, "ticks": 4}, 3, 0),
             SpanRecord("decode", T0 + 0.1, T0 + 0.3, 1,
                        {"batch": 2, "steps": 4, "ticks": 4}, 1, 0)]
    events = {
        1: [("arrival", T0 + 0.1, {}), ("first_sampled", T0 + 0.2, {}),
            ("first_token", T0 + 0.5, {})],
        2: [("first_sampled", T0 + 0.3, {}), ("first_token", T0 + 0.4, {})],
        # Preempted before its first token was returned: sampled again.
        3: [("first_sampled", T0 + 0.1, {}), ("preempt", T0 + 0.2, {}),
            ("first_sampled", T0 + 0.6, {}), ("first_token", T0 + 0.9, {})],
        # Returned after the window closed; sampled before it opened.
        4: [("first_sampled", T0 + 0.95, {}), ("first_token", T0 + 1.2, {})],
        5: [("first_token", T0 + 0.05, {})],
    }
    # Two steps that each returned 4 tokens to requests 1 and 2: 8 ticks.
    records = [driver.Record(req=None, due=0.0, uid=u, prompt_len=32)
               for u in (1, 2)]
    steps = [{"t0": 0.1, "t1": 0.3, "new": {1: 4, 2: 4}},
             {"t0": 0.43, "t1": 0.74, "new": {1: 4, 2: 4}}]
    return _ctx(spans, events, trace, records, steps)


def test_readers_on_a_made_up_window(made_up):
    read = {m: registry.load_reader(m)(made_up) for m in NEW}
    # Holds 300, 100 and 300 ms.
    assert read["first_token_hold_p50_ms"] == pytest.approx(300.0)
    # 400 ms of scans over 8 ticks.
    assert read["decode_scan_device_ms"] == pytest.approx(50.0)
    # ``copy`` ops inside the scans (not the prefill's, not copy-done).
    assert read["decode_scan_copy_share"] == pytest.approx(70 / 400)
    # Idle 300-320 ms under flush and 400-500 under decode.sync (the
    # asarray inside it is looked through); 0-100 and 700-1000 lie
    # outside the engine's spans. Two syncs.
    assert read["host_gap_per_sync_ms"] == pytest.approx(60.0)


def test_readers_give_nothing_without_the_programs_spans(monkeypatch):
    # The program before its spans were annotated and its programs named:
    # the decode scan was ``jit_run``, no ``first_sampled``, no ``ticks``.
    trace = trace_reduce.read(DATA)
    _, modules = trace_programs.read(DATA)
    monkeypatch.setattr(trace_programs, "of", lambda t: modules if t else {})
    spans = [SpanRecord("decode", T0, T0 + 0.1, 1, {"batch": 2, "steps": 4})]
    events = {1: [("arrival", T0, {}), ("admitted", T0 + 0.01, {"row": 0}),
                  ("first_token", T0 + 0.2, {})]}
    ctx = _ctx(spans, events, trace)
    for m in NEW:
        assert registry.load_reader(m)(ctx) is None, m
    # No trace at all (an untraced run).
    ctx = _ctx(spans, events, None)
    for m in NEW:
        assert registry.load_reader(m)(ctx) is None, m


class NoCompiles:
    n, seconds, cache_hits = 0, 0.0, 0


def test_traced_run_reports_the_first_token_hold(tmp_path, monkeypatch):
    # The harness end to end on the CPU: the engine's events give the hold;
    # the CPU has no TPU plane, so the device-trace readers give nothing.
    monkeypatch.setattr(run.work, "peaks", lambda kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    root = tiny.make_root(tmp_path)
    d = jax.devices()[0]
    out = run.run_cell(
        registry.load_cell("tiny.chat", root), seed=2**31 + 303, seconds=3.0,
        trace=True, root=root,
        device={"platform": d.platform, "kind": d.device_kind, "count": 1},
        t_start=time.perf_counter(), compiles=NoCompiles())
    m = out.result["metrics"]
    assert m["first_token_hold_p50_ms"]["value"] > 0
    assert not {"decode_scan_device_ms", "decode_scan_copy_share",
                "host_gap_per_sync_ms"} & set(m)
