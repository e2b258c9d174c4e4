"""Engine: share of the fused decode scan's device time (the program
``jit_decode_scan``, built and donated in ``serving/backends.py``) spent in
XLA ``copy`` ops, the ``copy`` row of the breakdown (copies of the KV pool
the scan carries). Moves ``tpot_p90_ms``."""

from bench import trace_programs

MODULE = "jit_decode_scan"


def read(ctx):
    modules = trace_programs.of(ctx.trace)
    if not modules:
        return None
    spent = trace_programs.module_s(ctx.trace, modules, MODULE)
    if spent <= 0:
        return None
    return trace_programs.module_ops_s(ctx.trace, modules, MODULE, r"copy[.\d]*$") / spent
