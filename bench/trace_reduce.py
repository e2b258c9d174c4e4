"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time
and idle gaps attributed to what the host was doing.

The measured window is the host annotation ``bench.window`` around the
serve loop, on the host thread that ran it; device operations are
the events of the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane, on the
same clock. Busy time is the union of their intervals inside the window,
averaged over the chips. An op event is named by its HLO instruction
(``%paged_flash_decode.7 = bf16[...] custom-call(...)``); it is matched to a
kernel by a regular expression over the instruction's own name only, never
its operands, so a Pallas kernel is found by the ``name`` it was given and a
fusion that consumes its output is not.
"""

from __future__ import annotations

import dataclasses
import gzip
import pathlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str           # the HLO instruction's name, or the host event's
    start: int          # ns
    end: int            # ns


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    devices: Dict[str, List[Event]]     # plane name -> ops, sorted by start
    host: List[Event]                   # python-thread host events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def op_name(text: str) -> str:
    """``%name.3 = f32[..] fusion(..)`` -> ``name.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda t: t) -> List[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(name(e.name), start, start + int(e.duration_ns)))
    out.sort(key=lambda e: e.start)
    return out


def read(path: pathlib.Path) -> Trace:
    """Read an ``.xplane.pb`` (or a gzipped one)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line, op_name)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                ev = _events(line)
                if any(e.name == WINDOW for e in ev):
                    host = ev
    wins = [e for e in host if e.name == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    w = wins[0]
    return Trace(window=(w.start, w.end), devices=devices, host=host)


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clipped(ev: Sequence[Event], window: Tuple[int, int]) -> List[Tuple[int, int]]:
    lo, hi = window
    return [(max(e.start, lo), min(e.end, hi)) for e in ev
            if e.end > lo and e.start < hi]


def busy_intervals(trace: Trace, device: str) -> List[Tuple[int, int]]:
    return _union(_clipped(trace.devices[device], trace.window))


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    tot = 0
    for dev in trace.devices:
        tot += sum(e - s for s, e in busy_intervals(trace, dev))
    return tot * 1e-9 / len(trace.devices)


def kernel_s(trace: Trace, pattern: str) -> float:
    """Device seconds of ops whose name matches ``pattern``, inside the
    window, averaged over the chips."""
    rx = re.compile(pattern)
    if not trace.devices:
        return 0.0
    tot = 0
    for ev in trace.devices.values():
        hits = [e for e in ev if rx.match(e.name)]
        tot += sum(e - s for s, e in _clipped(hits, trace.window))
    return tot * 1e-9 / len(trace.devices)


def _leaves(ev: List[Event]) -> List[Event]:
    """Ops that contain no other op on their line (a while loop's own event
    spans its body's ops; counting both would count the body twice)."""
    out = []
    for i, e in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt.start >= e.start and nxt.end <= e.end \
                and (nxt.start, nxt.end) != (e.start, e.end):
            continue
        out.append(e)
    return out


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` device ops that took most time in the window, by name with
    trailing instance numbers dropped, in seconds averaged over chips."""
    tot: Dict[str, int] = {}
    for ev in trace.devices.values():
        for e in _leaves(ev):
            s, t = max(e.start, trace.window[0]), min(e.end, trace.window[1])
            if t > s:
                key = re.sub(r"[.\d]+$", "", e.name) or e.name
                tot[key] = tot.get(key, 0) + (t - s)
    k = max(len(trace.devices), 1)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / k] for name, ns in rows]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Idle time of the first chip inside the window, grouped by the
    innermost host event running at each gap's midpoint (``host idle`` when
    none is), the ``n`` largest groups in seconds."""
    if not trace.devices:
        return []
    dev = sorted(trace.devices)[0]
    busy = busy_intervals(trace, dev)
    lo, hi = trace.window
    gaps = []
    cur = lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    # Host events of one thread nest, so the open ones at any instant form a
    # stack whose top is the innermost; sweep the gaps in time order.
    host = [h for h in trace.host if h.name != WINDOW]
    stack: List[Event] = []
    i = 0
    tot: Dict[str, int] = {}
    for s, e in gaps:
        mid = (s + e) // 2
        while i < len(host) and host[i].start <= mid:
            while stack and stack[-1].end <= host[i].start:
                stack.pop()
            stack.append(host[i])
            i += 1
        while stack and stack[-1].end < mid:
            stack.pop()
        inner: Optional[Event] = stack[-1] if stack else None
        key = inner.name if inner is not None else "host idle"
        tot[key] = tot.get(key, 0) + (e - s)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in rows]


def find_xplane(log_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]
