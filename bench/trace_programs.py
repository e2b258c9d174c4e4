"""The device programs of a traced window, and idle time under named host
events.

``trace_reduce`` reads a trace's ``XLA Ops`` line. The ``XLA Modules`` line
of the same ``/device:TPU:<n>`` planes holds each execution of a jitted
program, named ``jit_<function>(<fingerprint>)``; here the fingerprint is
dropped, and the ops of an execution are those inside its interval. A
traced run (``run.py``) profiles into a ``bench-trace-*`` directory of the
temp dir and keeps it until its metrics are read, so a reader finds the
executions of the window it was handed with ``of(ctx.trace)``.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import pathlib
import re
import tempfile
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce
from bench.trace_reduce import Event, Trace

MODULES_LINE = "XLA Modules"
TRACE_DIRS = "bench-trace-*"

Modules = Dict[str, List[Event]]    # plane name -> executions, sorted by start

_seen: Dict[Tuple[int, int], Modules] = {}


def module_name(text: str) -> str:
    """``jit_run(12454953062740391241)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", text)


def read(path: pathlib.Path) -> Tuple[Optional[Tuple[int, int]], Modules]:
    """The ``bench.window`` interval (None without one) and each chip's
    program executions of an ``.xplane.pb`` (or a gzipped one)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    window = None
    modules: Modules = {}
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = trace_reduce._events(line, module_name)
        elif plane.name.startswith("/host:CPU") and window is None:
            window = _window(plane)
    return window, modules


def _window(plane) -> Optional[Tuple[int, int]]:
    for line in plane.lines:
        for e in line.events:
            if e.name == trace_reduce.WINDOW:
                start = int(e.start_ns)
                return start, start + int(e.duration_ns)
    return None


def of(trace: Optional[Trace]) -> Modules:
    """The program executions of the traced run ``trace`` was read from: the
    newest profile under the temp dir's ``bench-trace-*`` directories whose
    window is ``trace``'s. Empty when there is none."""
    if trace is None:
        return {}
    if trace.window not in _seen:
        found: Modules = {}
        dirs = []
        for d in pathlib.Path(tempfile.gettempdir()).glob(TRACE_DIRS):
            try:
                dirs.append((d.stat().st_mtime, d))
            except FileNotFoundError:   # another run's, removed meanwhile
                continue
        for _, d in sorted(dirs, reverse=True):
            try:
                files = sorted(d.rglob("*.xplane.pb"))
                if not files:
                    continue
                window, modules = read(files[-1])
            except (OSError, RuntimeError):
                # Another run's profile, still being written or removed:
                # this run's own was complete before its readers ran.
                continue
            if window == trace.window:
                found = modules
                break
        _seen.clear()
        _seen[trace.window] = found
    return _seen[trace.window]


def module_s(trace: Trace, modules: Modules, module: str) -> float:
    """Device seconds of the executions of program ``module``
    (``jit_<name>``) inside the window, averaged over the chips."""
    if not trace.devices:
        return 0.0
    tot = 0
    for ev in modules.values():
        hits = [e for e in ev if e.name == module]
        tot += sum(e - s for s, e in trace_reduce._clipped(hits, trace.window))
    return tot * 1e-9 / len(trace.devices)


def module_ops_s(trace: Trace, modules: Modules, module: str, pattern: str) -> float:
    """Device seconds of the leaf ops whose name matches ``pattern`` that ran
    inside executions of program ``module``, inside the window, averaged
    over the chips."""
    rx = re.compile(pattern)
    if not trace.devices:
        return 0.0
    w0, w1 = trace.window
    tot = 0
    for dev, ops in trace.devices.items():
        # Executions on one chip do not overlap: the one an op belongs to is
        # the last to start before it.
        runs = [(e.start, e.end) for e in modules.get(dev, []) if e.name == module]
        starts = [s for s, _ in runs]
        for e in trace_reduce._leaves(ops):
            if not rx.match(e.name):
                continue
            k = bisect.bisect_right(starts, e.start) - 1
            if k >= 0:
                lo, hi = runs[k]
                tot += max(min(e.end, hi, w1) - max(e.start, lo, w0), 0)
    return tot * 1e-9 / len(trace.devices)


def idle_within(trace: Trace, names) -> Tuple[Dict[str, float], float]:
    """Idle time of the first chip inside the window put down to the
    innermost open host event whose name is in ``names`` (events of other
    names are looked through): seconds per name, and the seconds under no
    such event. The two add up to the window less its busy time."""
    names = set(names)
    only = dataclasses.replace(trace, host=[h for h in trace.host if h.name in names])
    by_name = dict(trace_reduce.idle_gaps(only, n=len(names) + 1))
    rest = by_name.pop("host idle", 0.0)
    return by_name, rest
