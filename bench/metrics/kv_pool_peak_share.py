"""Cache: the most pool pages held (by live sequences or the prefix cache)
after any step, over the usable pool. Moves ``output_tok_s``."""


def read(ctx):
    return max(ctx.pool_used) if ctx.pool_used else None
