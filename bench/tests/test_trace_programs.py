"""The device programs of a trace and idle time under named host events,
checked on the short TPU v5e traces of ``test_trace_reduce.py``: one of the
program before its device programs were named, and one after."""

import gzip
import pathlib
import time

import pytest

from bench import trace_programs, trace_reduce

DATA = pathlib.Path(__file__).parent / "data" / "session.xplane.pb.gz"
NAMED = pathlib.Path(__file__).parent / "data" / "session_named.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.read(DATA)


@pytest.fixture(scope="module")
def modules(trace):
    window, modules = trace_programs.read(DATA)
    assert window == trace.window
    return modules


def test_program_executions_are_named_without_fingerprints(modules):
    names = {e.name for e in modules["/device:TPU:0"]}
    assert {"jit_run", "jit_f", "jit__lambda", "jit_convert_element_type"} <= names
    assert not any("(" in n for n in names)


def test_ops_inside_a_program(trace, modules):
    # By hand on this trace: the decode scan (``jit_run`` at the time) ran
    # 1743.2 ms, 346.1 ms of it in ``copy`` ops; the tail scatter (a lambda)
    # is nearly all copy.
    assert trace_programs.module_s(trace, modules, "jit_run") == pytest.approx(1.7432, abs=5e-4)
    copy = trace_programs.module_ops_s(trace, modules, "jit_run", r"copy[.\d]*$")
    assert copy == pytest.approx(0.3461, abs=5e-4)
    lam = trace_programs.module_s(trace, modules, "jit__lambda")
    assert trace_programs.module_ops_s(trace, modules, "jit__lambda", r"copy") > 0.99 * lam
    assert trace_programs.module_s(trace, modules, "jit_no_such_program") == 0.0


def test_idle_within_named_host_events(trace):
    idle = trace.window_s - trace_reduce.busy_s(trace)
    for names in ({"bench.step"}, {"bench.step", "bench.sleep"}, set()):
        by_name, rest = trace_programs.idle_within(trace, names)
        assert set(by_name) <= names
        assert sum(by_name.values()) + rest == pytest.approx(idle, rel=1e-6, abs=1e-9)
    by_name, _ = trace_programs.idle_within(trace, {"bench.step"})
    assert by_name["bench.step"] > 0


def test_executions_are_found_by_the_window(tmp_path, monkeypatch, trace):
    # As a traced run leaves its profile: the newest ``bench-trace-*``
    # directory of the temp dir, with another run's profile beside it.
    monkeypatch.setattr(trace_programs.tempfile, "tempdir", str(tmp_path))
    for d, src in (("bench-trace-a", NAMED), ("bench-trace-b", DATA)):
        out = tmp_path / d / "plugins" / "profile" / "run"
        out.mkdir(parents=True)
        (out / "host.xplane.pb").write_bytes(gzip.decompress(src.read_bytes()))
    monkeypatch.setattr(trace_programs, "_seen", {})
    names = {e.name for e in trace_programs.of(trace)["/device:TPU:0"]}
    assert "jit_run" in names and "jit_decode_scan" not in names
    named = trace_reduce.read(NAMED)
    names = {e.name for e in trace_programs.of(named)["/device:TPU:0"]}
    assert "jit_decode_scan" in names and "jit_run" not in names
    other = trace_reduce.Trace(window=(0, 1), devices=trace.devices, host=[])
    assert trace_programs.of(other) == {}
    assert trace_programs.of(None) == {}


def test_engine_programs_and_spans_on_a_chip_trace():
    # Recorded on a TPU v5e the same way, once the engine's programs were
    # named and its spans were profiler annotations.
    t = trace_reduce.read(NAMED)
    _, modules = trace_programs.read(NAMED)
    programs = {e.name for e in modules["/device:TPU:0"]}
    assert {"jit_decode_scan", "jit_extend", "jit_scatter_tail"} <= programs
    assert not {"jit_run", "jit_f", "jit__lambda"} & programs
    spans = {h.name for h in t.host}
    assert {"step", "schedule", "flush", "prefill", "sample_first", "decode",
            "decode.launch", "decode.sync", "decode.replay"} <= spans
    scan = trace_programs.module_s(t, modules, "jit_decode_scan")
    assert 0 < trace_programs.module_ops_s(t, modules, "jit_decode_scan", r"copy[.\d]*$") < scan


def test_another_runs_unfinished_profile_is_passed_over(tmp_path, monkeypatch, trace):
    # A traced run beside this one is still writing its profile: the
    # newest directory holds a cut-off file, which ``of`` cannot parse.
    monkeypatch.setattr(trace_programs.tempfile, "tempdir", str(tmp_path))
    whole = gzip.decompress(DATA.read_bytes())
    for d, data in (("bench-trace-mine", whole), ("bench-trace-other", whole[:4096])):
        out = tmp_path / d / "plugins" / "profile" / "run"
        out.mkdir(parents=True)
        (out / "host.xplane.pb").write_bytes(data)
        time.sleep(0.05)
    with pytest.raises(RuntimeError):
        trace_programs.read(tmp_path / "bench-trace-other" / "plugins" / "profile"
                            / "run" / "host.xplane.pb")
    monkeypatch.setattr(trace_programs, "_seen", {})
    names = {e.name for e in trace_programs.of(trace)["/device:TPU:0"]}
    assert "jit_run" in names
