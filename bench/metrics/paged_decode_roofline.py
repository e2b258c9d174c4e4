"""Kernels: least time of the decode attention the window needed (live KV
tokens, window-capped, from ``work.py``) over the device time of the ops
named ``paged_flash_decode*``, in percent. Moves ``tpot_p90_ms``."""

from bench import trace_reduce, work


def read(ctx):
    if ctx.trace is None:
        return None
    spent = trace_reduce.kernel_s(ctx.trace, r"paged_flash_decode")
    if spent <= 0:
        return None
    return 100.0 * work.decode_least_seconds(ctx.shape, ctx.ticks(), ctx.peak) / spent
