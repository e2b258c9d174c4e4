"""Scheduler: mean decode batch (the ``decode`` span's ``batch``) over
``max_batch``. Moves ``output_tok_s``."""


def read(ctx):
    spans = ctx.span_list("decode")
    if not spans:
        return None
    return sum(s.args["batch"] for s in spans) / len(spans) / ctx.max_batch
