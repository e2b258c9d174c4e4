"""Kernels: least time of the latent decode attention the window needed
(one read of each live token's latent row a layer, the queries and outputs,
and ``2 * heads * (latent + rank)`` FLOPs a token pair, from the family's
``work.Shape``) over the device time of the ops named
``paged_latent_decode*``, in percent. Moves ``tpot_p90_ms``."""

from bench import trace_reduce, work


def read(ctx):
    if ctx.trace is None:
        return None
    spent = trace_reduce.kernel_s(ctx.trace, r"paged_latent_decode")
    if spent <= 0:
        return None
    return 100.0 * work.decode_least_seconds(ctx.shape, ctx.ticks(), ctx.peak) / spent
