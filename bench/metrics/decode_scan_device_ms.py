"""Engine: device time of the fused decode scan (the program
``jit_decode_scan``) per decode tick run, the ticks counted as
``decode_tick_ms`` counts them. What ``decode_tick_ms`` holds less the
host's share of each sync. Moves ``tpot_p90_ms``."""

from bench import trace_programs


def read(ctx):
    modules = trace_programs.of(ctx.trace)
    if not modules:
        return None
    ticks = len(ctx.ticks())
    spent = trace_programs.module_s(ctx.trace, modules, "jit_decode_scan")
    if ticks == 0 or spent <= 0:
        return None
    return 1e3 * spent / ticks
