"""Engine: time in the ``decode`` span per decode tick run. The span ends
once the scan's tokens are on the host, so it holds the device time of the
fused ticks. Moves ``tpot_p90_ms``."""


def read(ctx):
    spans = ctx.span_list("decode")
    ticks = len(ctx.ticks())
    if not spans or ticks == 0:
        return None
    return 1e3 * sum(s.duration for s in spans) / ticks
