"""The trace reduction, checked on a short trace recorded on a TPU v5e by
``bench/run.py --workload deepseek-llm-7b.session --trace 1`` (gzipped)."""

import pathlib

import numpy as np
import pytest

from bench import trace_reduce

DATA = pathlib.Path(__file__).parent / "data" / "session.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.read(DATA)


def test_window_and_device_planes(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert 1.0 < trace.window_s < 10.0
    names = {h.name for h in trace.host}
    assert {"bench.window", "bench.step"} <= names


def test_busy_is_the_union_of_op_intervals(trace):
    # An independent count: mark every microsecond some op covers.
    lo, hi = trace.window
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for e in trace.devices["/device:TPU:0"]:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            grid[(s - lo) // 1000:(t - lo + 999) // 1000] = True
    want = grid.sum() * 1e-6
    got = trace_reduce.busy_s(trace)
    assert got == pytest.approx(want, rel=0.02, abs=2e-3)
    assert 0.0 < got <= trace.window_s


def test_kernel_time_by_name(trace):
    ev = trace.devices["/device:TPU:0"]
    lo, hi = trace.window
    want = sum(min(e.end, hi) - max(e.start, lo) for e in ev
               if e.name.startswith("paged_flash_decode") and e.end > lo and e.start < hi) * 1e-9
    got = trace_reduce.kernel_s(trace, r"paged_flash_decode")
    assert got == pytest.approx(want)
    assert got > 0
    assert trace_reduce.kernel_s(trace, r"no_such_kernel_name") == 0.0
    # A fusion that reads the kernel's output names it only as an operand.
    assert trace_reduce.op_name(
        "%slice_reduce_fusion.3 = bf16[16,32,128] fusion(bf16[16,32,8,128] "
        "%paged_flash_decode.7), kind=kLoop") == "slice_reduce_fusion.3"
    top = trace_reduce.top_ops(trace)
    assert len(top) <= 10 and all(s > 0 for _, s in top)
    assert sum(s for _, s in top) <= trace_reduce.busy_s(trace) * 1.0001


def test_idle_gaps_are_attributed_to_host_events(trace):
    gaps = trace_reduce.idle_gaps(trace, n=1000)
    idle = trace.window_s - trace_reduce.busy_s(trace)
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6, abs=1e-9)
    assert all(s > 0 for _, s in gaps)
    assert len(trace_reduce.idle_gaps(trace)) <= 10
