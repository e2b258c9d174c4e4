"""Model step: routed slots that landed on this chip's held experts, per
expert layer per decode tick, over the window's live ticks: the program's
count, which the fused decode scan returns and the engine records on each
``decode`` span (``held_slots`` a live tick, over ``moe_layers`` layers).
Moves ``tpot_p90_ms``. None where the program records no such count."""


def read(ctx):
    slots = ticks = 0
    for s in ctx.span_list("decode"):
        held = s.args.get("held_slots")
        if held is None:
            continue
        slots += sum(held)
        ticks += len(held) * s.args["moe_layers"]
    if ticks == 0:
        return None
    return slots / ticks
