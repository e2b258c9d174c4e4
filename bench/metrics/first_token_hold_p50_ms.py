"""Engine: how long a request's first token waits on the host before it is
returned. The engine records ``first_sampled`` once the token is sampled at
its prefill and ``first_token`` when a step returns it, after the next
decode scan; the median over requests with both events in the window, of
``first_token`` less the last ``first_sampled`` before it. Moves
``ttft_p50_ms``."""

import numpy as np


def read(ctx):
    close = ctx.t0 + ctx.window.seconds
    holds = []
    for events in ctx.events.values():
        sampled = None
        for name, t, _ in events:
            if t > close:
                break
            if name == "first_sampled":
                sampled = t
            elif name == "first_token":
                if sampled is not None:
                    holds.append(t - sampled)
                break
    if not holds:
        return None
    return 1e3 * float(np.median(holds))
