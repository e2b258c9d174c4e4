"""Tier-1 tests for the PR-7 telemetry subsystem (``repro.obs``).

Covers the acceptance bar from the issue: exact quantiles on known
distributions and bucket-boundary edges, merge associativity, Prometheus
rendering, the zero-alloc null path, span nesting, monotone request
lifecycles, Chrome trace JSON round-tripping, and the drift collector's
near-zero-model discipline.
"""

import json

import pytest

from repro.obs import NULL_TELEMETRY, Telemetry
from repro.obs.drift import DriftCollector, NullDriftCollector, context_bucket
from repro.obs.metrics import (
    NULL_COUNTER,
    NULL_GAUGE,
    NULL_HISTOGRAM,
    Counter,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    write_json_artifact,
)
from repro.obs.tracing import NULL_SPAN, NullTracer, Tracer


class FakeClock:
    """Deterministic monotone clock for tracer tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt
        return self.t


# -- histogram ----------------------------------------------------------------


def test_histogram_exact_quantiles_uniform():
    h = Histogram("h", boundaries=tuple(float(b) for b in range(1, 101)))
    for v in range(1, 101):  # uniform 1..100, one per bucket
        h.observe(float(v))
    # With one observation per unit bucket, quantiles are exact to within
    # one bucket width.
    assert h.quantile(0.5) == pytest.approx(50.0, abs=1.0)
    assert h.quantile(0.9) == pytest.approx(90.0, abs=1.0)
    assert h.quantile(0.99) == pytest.approx(99.0, abs=1.0)
    assert h.quantile(0.0) == pytest.approx(h.min)
    assert h.quantile(1.0) == pytest.approx(h.max)
    assert h.mean == pytest.approx(50.5)


def test_histogram_single_value_is_exact():
    h = Histogram("h")
    for _ in range(7):
        h.observe(0.42)
    # min == max clamps interpolation: every quantile is the value itself.
    for q in (0.0, 0.5, 0.9, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(0.42)


def test_histogram_bucket_boundary_edges():
    h = Histogram("h", boundaries=(1.0, 2.0, 5.0))
    h.observe(1.0)   # exactly on a boundary: le="1" bucket (v <= le)
    h.observe(2.0)
    h.observe(7.0)   # overflow
    snap = h.snapshot()
    assert snap["buckets"][repr(1.0)] == 1
    assert snap["buckets"][repr(2.0)] == 2
    assert snap["buckets"][repr(5.0)] == 2
    assert snap["buckets"]["+Inf"] == 3
    assert snap["min"] == 1.0 and snap["max"] == 7.0


def test_histogram_overflow_clamps_to_observed_max():
    h = Histogram("h", boundaries=(1.0,))
    h.observe(50.0)
    h.observe(100.0)
    assert h.quantile(1.0) == pytest.approx(100.0)
    assert 50.0 <= h.quantile(0.5) <= 100.0


def test_histogram_merge_matches_union_and_is_associative():
    bs = (0.01, 0.1, 1.0, 10.0)
    data = ([0.005, 0.05, 0.5], [5.0, 50.0, 0.02], [0.3, 0.09])

    def build(vals):
        h = Histogram("h", boundaries=bs)
        for v in vals:
            h.observe(v)
        return h

    union = build([v for vs in data for v in vs])
    a_bc = build(data[0]).merge(build(data[1]).merge(build(data[2])))
    ab_c = build(data[0]).merge(build(data[1])).merge(build(data[2]))
    for merged in (a_bc, ab_c):
        assert merged.counts == union.counts
        assert merged.count == union.count
        assert merged.sum == pytest.approx(union.sum)
        assert merged.min == union.min and merged.max == union.max


def test_histogram_merge_requires_matching_boundaries():
    with pytest.raises(ValueError, match="boundary mismatch"):
        Histogram("a", boundaries=(1.0,)).merge(
            Histogram("b", boundaries=(2.0,)))


def test_histogram_rejects_bad_inputs():
    with pytest.raises(ValueError):
        Histogram("h", boundaries=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("h").quantile(1.5)
    assert Histogram("h").quantile(0.5) == 0.0  # empty


# -- registry -----------------------------------------------------------------


def test_registry_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    c1 = reg.counter("requests", "help text")
    c2 = reg.counter("requests")
    assert c1 is c2
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("requests")


def test_counter_monotone():
    c = Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == pytest.approx(3.5)
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_reset_preserves_instrument_identity():
    reg = MetricsRegistry()
    c = reg.counter("c")
    h = reg.histogram("h")
    c.inc(5)
    h.observe(0.5)
    reg.reset()
    assert c is reg.counter("c")
    assert c.value == 0.0
    assert h.count == 0
    c.inc()  # the pre-bound reference still records
    assert reg.snapshot()["c"]["value"] == 1.0


def test_prometheus_rendering():
    reg = MetricsRegistry()
    reg.counter("serving_steps_total", "engine ticks").inc(3)
    reg.gauge("serving_running").set(2)
    reg.histogram("step_seconds", boundaries=(0.1, 1.0)).observe(0.05)
    text = reg.render_prometheus()
    assert "# HELP serving_steps_total engine ticks" in text
    assert "# TYPE serving_steps_total counter" in text
    assert "serving_steps_total 3" in text
    assert "serving_running 2" in text
    assert 'step_seconds_bucket{le="0.1"} 1' in text
    assert 'step_seconds_bucket{le="+Inf"} 1' in text
    assert "step_seconds_count 1" in text


def test_null_registry_shares_singletons():
    reg = NullRegistry()
    assert reg.counter("a") is reg.counter("b") is NULL_COUNTER
    assert reg.gauge("a") is NULL_GAUGE
    assert reg.histogram("a") is NULL_HISTOGRAM
    NULL_COUNTER.inc()
    NULL_GAUGE.set(9)
    NULL_HISTOGRAM.observe(1.0)
    assert NULL_COUNTER.value == 0.0
    assert NULL_GAUGE.value == 0.0
    assert NULL_HISTOGRAM.count == 0
    assert reg.snapshot() == {}
    assert reg.render_prometheus() == ""
    assert not reg.enabled


def test_write_json_artifact_envelope(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc(2)
    path = write_json_artifact(
        "probe", {"k": "v"}, metrics=reg, dirpath=str(tmp_path), kind="test",
    )
    doc = json.loads(open(path).read())
    assert doc["schema"] == "repro.obs/v1"
    assert doc["name"] == "probe" and doc["kind"] == "test"
    assert doc["payload"] == {"k": "v"}
    assert doc["metrics"]["n"]["value"] == 2.0


# -- tracer -------------------------------------------------------------------


def test_spans_nest_positionally():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("step"):
        clk.tick()
        with tr.span("schedule"):
            clk.tick()
        with tr.span("decode", batch=3):
            clk.tick(2.0)
        clk.tick()
    by_name = {s.name: s for s in tr.spans}
    assert by_name["step"].depth == 0
    assert by_name["schedule"].depth == 1
    assert by_name["decode"].depth == 1
    assert by_name["decode"].args == {"batch": 3}
    # Children close before the parent and lie inside its interval.
    assert tr.spans[-1].name == "step"
    for child in ("schedule", "decode"):
        assert by_name["step"].t0 <= by_name[child].t0
        assert by_name[child].t1 <= by_name["step"].t1
    assert by_name["decode"].duration == pytest.approx(2.0)
    # Each span names the one open around it.
    assert by_name["step"].parent is None
    assert by_name["schedule"].parent == by_name["step"].sid
    assert by_name["decode"].parent == by_name["step"].sid
    assert len({s.sid for s in tr.spans}) == 3


def test_request_lifecycle_monotone_and_latencies():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.request_event(7, "arrival")
    clk.tick(2.0)
    tr.request_event(7, "admitted")
    clk.tick(1.0)
    tr.request_event(7, "first_token")
    tr.request_event(7, "tokens", n=1)
    clk.tick(0.5)
    tr.request_event(7, "tokens", n=1)
    clk.tick(1.0)
    tr.request_event(7, "tokens", n=2)  # a 2-token tick amortizes
    tr.request_event(7, "finish", reason="length")
    events = tr.request_lifecycle(7)
    times = [t for _, t, _ in events]
    assert times == sorted(times), "lifecycle must be monotone"
    assert [e for e, _, _ in events][0] == "arrival"
    assert [e for e, _, _ in events][-1] == "finish"
    lat = tr.request_latencies()[7]
    assert lat["queue"] == pytest.approx(2.0)
    assert lat["ttft"] == pytest.approx(3.0)
    assert lat["e2e"] == pytest.approx(4.5)
    # itl: 0.5 then two amortized 0.5s from the 1.0s 2-token emission.
    assert lat["itl"] == pytest.approx([0.5, 0.5, 0.5])
    assert lat["preemptions"] == 0


def test_request_latencies_partial_lifecycle():
    tr = Tracer(clock=FakeClock())
    tr.request_event(1, "arrival")
    lat = tr.request_latencies()[1]
    assert lat["ttft"] is None and lat["e2e"] is None
    assert lat["itl"] == []


def test_chrome_trace_round_trips():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("step"):
        clk.tick()
        with tr.span("decode", batch=2):
            clk.tick()
    tr.request_event(0, "arrival")
    clk.tick()
    tr.request_event(0, "first_token")
    tr.request_event(0, "finish", reason="stop")
    doc = json.loads(json.dumps(tr.to_chrome_trace()))
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "i", "b", "e"} <= phases
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"step", "decode"}
    by_name = {e["name"]: e for e in xs}
    assert by_name["decode"]["args"]["batch"] == 2
    assert by_name["step"]["args"]["parent"] is None
    assert by_name["decode"]["args"]["parent"] == by_name["step"]["args"]["sid"]
    for e in evs:
        if e["ph"] in ("X", "i", "b", "e"):
            assert e["ts"] >= 0  # all timestamps rebased to trace start
    b = next(e for e in evs if e["ph"] == "b")
    e_ = next(e for e in evs if e["ph"] == "e")
    assert b["id"] == e_["id"] == 0
    assert b["tid"] == e_["tid"] == 1  # request uid+1 track
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert "repro.serving.LLMEngine" in names
    assert "request 0" in names


def test_chrome_trace_writes_file(tmp_path):
    tr = Tracer(clock=FakeClock())
    with tr.span("step"):
        pass
    path = tr.write_chrome_trace(str(tmp_path / "sub" / "trace.json"))
    doc = json.loads(open(path).read())
    assert doc["traceEvents"]


def test_tracer_reset_drops_records():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("warmup"):
        clk.tick()
    tr.request_event(0, "arrival")
    tr.reset()
    assert tr.spans == [] and tr.requests == {}
    clk.tick()
    with tr.span("measured"):
        clk.tick()
    # Post-reset spans rebase on the reset time, not the construction time.
    assert tr.to_chrome_trace()["traceEvents"][-1]["ts"] >= 0


def test_null_tracer_shares_span():
    tr = NullTracer()
    assert tr.span("a") is tr.span("b") is NULL_SPAN
    with tr.span("a"):
        pass
    tr.request_event(1, "arrival")
    tr.request_event(1, "first_sampled")
    assert tr.spans == [] and tr.requests == {}


# -- drift --------------------------------------------------------------------


def test_context_bucket_powers_of_two():
    assert context_bucket(0) == 1
    assert context_bucket(1) == 1
    assert context_bucket(3) == 4
    assert context_bucket(4) == 4
    assert context_bucket(5.7) == 8
    assert context_bucket(1000) == 1024


def test_drift_report_ratio_and_cells():
    d = DriftCollector()
    for _ in range(10):
        d.record(batch=2, mean_len=30, seconds=1e-3)
    d.record(batch=4, mean_len=100, seconds=2e-3)
    assert d.num_samples == 11
    report = d.report(lambda batch, mean_len: 1e-4 * batch)
    rows = {(r["batch"], r["ctx_bucket"]): r for r in report.rows}
    assert set(rows) == {(2, 32), (4, 128)}
    r2 = rows[(2, 32)]
    assert r2["samples"] == 10
    assert r2["measured_p50_s"] == pytest.approx(1e-3)
    assert r2["ratio"] == pytest.approx(5.0)
    assert report.worst_ratio() == pytest.approx(rows[(4, 128)]["ratio"])
    assert "Drift" in report.render()


def test_drift_near_zero_model_reports_none_not_inf():
    d = DriftCollector()
    d.record(batch=1, mean_len=8, seconds=1e-3)
    report = d.report(lambda batch, mean_len: 0.0)
    assert report.rows[0]["ratio"] is None
    assert report.worst_ratio() is None
    assert "n/a" in report.render()


def test_drift_reset_and_null():
    d = DriftCollector()
    d.record(1, 8, 1e-3)
    d.reset()
    assert d.num_samples == 0
    assert d.report(lambda b, m: 1.0).rows == []
    n = NullDriftCollector()
    n.record(1, 8, 1e-3)
    assert n.num_samples == 0
    assert not n.enabled
    assert "no decode samples" in n.report(lambda b, m: 1.0).render()


# -- the bundle ---------------------------------------------------------------


def test_telemetry_bundle_and_null():
    tel = Telemetry.create()
    assert tel.enabled
    tel.metrics.counter("c").inc()
    with tel.tracer.span("s"):
        pass
    tel.drift.record(1, 8, 1e-3)
    tel.reset()
    assert tel.metrics.snapshot()["c"]["value"] == 0.0
    assert tel.tracer.spans == []
    assert tel.drift.num_samples == 0

    assert Telemetry.disabled() is NULL_TELEMETRY
    assert not NULL_TELEMETRY.enabled
    assert NULL_TELEMETRY.metrics.counter("x") is NULL_COUNTER
    assert NULL_TELEMETRY.tracer.span("x") is NULL_SPAN
    NULL_TELEMETRY.reset()  # no-op, must not raise
