"""Engine: device idle time inside the engine's ``step`` span per decode
sync. Each idle gap of the chip counts when the profiler's host trace
holds a ``step`` annotation open at its midpoint; the sum, divided by the
``decode`` spans. The child spans (``flush``, ``decode.sync``, ...) lie
inside ``step`` and split this time in the breakdown's ``idle_gaps``.
Moves ``tpot_p90_ms``."""

from bench import trace_programs

ENGINE_SPANS = ("step",)


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    syncs = len(ctx.span_list("decode"))
    if syncs == 0 or not any(h.name in ENGINE_SPANS for h in ctx.trace.host):
        return None
    by_span, _ = trace_programs.idle_within(ctx.trace, ENGINE_SPANS)
    return 1e3 * sum(by_span.values()) / syncs
