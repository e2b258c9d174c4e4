"""Cache: prompt pages served from the prefix cache over prompt pages
admitted in the window (``prefix_stats`` deltas). Moves ``ttft_p50_ms``."""


def read(ctx):
    a, b = ctx.prefix_after, ctx.prefix_before
    total = a["prompt_pages"] - b["prompt_pages"]
    if total <= 0:
        return None
    return (a["pages_reused"] - b["pages_reused"]) / total
