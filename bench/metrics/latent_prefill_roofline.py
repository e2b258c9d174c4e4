"""Kernels: least time of the latent prefill and extend attention the
window needed (real tails over their cached prefixes, from the family's
``work.Shape``) over the device time of the ops named
``paged_latent_prefill*``, in percent. Moves ``ttft_p50_ms``."""

from bench import trace_reduce, work


def read(ctx):
    if ctx.trace is None:
        return None
    spent = trace_reduce.kernel_s(ctx.trace, r"paged_latent_prefill")
    if spent <= 0 or not ctx.prefills():
        return None
    return 100.0 * work.prefill_least_seconds(ctx.shape, ctx.prefills(), ctx.peak) / spent
