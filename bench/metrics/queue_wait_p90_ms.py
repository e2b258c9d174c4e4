"""Scheduler: 90th percentile of due time -> first ``admitted`` event, over
every request due in the window (one never admitted enters at its wait so
far). Moves ``ttft_p90_ms``."""

import numpy as np


def read(ctx):
    w = ctx.window
    waits = []
    for r in w.records:
        if r.due > w.seconds:
            continue
        t = w.seconds
        for name, when, _ in ctx.events.get(r.uid, ()):
            if name == "admitted":
                t = min(when - ctx.t0, w.seconds)
                break
        waits.append(t - r.due)
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 90))
