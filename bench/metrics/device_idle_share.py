"""Device: share of the traced window in which no operation ran on the
chip. Moves ``tpot_p90_ms``."""

from bench import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 1.0 - trace_reduce.busy_s(ctx.trace) / ctx.trace.window_s
