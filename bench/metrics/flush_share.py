"""Engine: share of the window spent in the ``flush`` span (prefills and the
first-token sample, synced to the host). Moves ``ttft_p50_ms``."""


def read(ctx):
    spans = ctx.span_list("flush")
    if not spans:
        return None
    return sum(s.duration for s in spans) / ctx.window.seconds
