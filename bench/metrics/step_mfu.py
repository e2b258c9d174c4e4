"""Model step: FLOPs the model needed for the prompt and output tokens the
window processed (``work.model_flops``) over the traced window times the
chip's bf16 peak, in percent. Moves ``tpot_p90_ms``."""

from bench import work


def read(ctx):
    if ctx.trace is None:
        return None
    flops = work.model_flops(ctx.shape, ctx.prefills(), ctx.ticks())
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["bf16_flops"])
