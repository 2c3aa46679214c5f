"""Share of decode-pool slot steps that carried a live request, over the
traced window: the delta of the engine's ``active_slot_steps`` over the delta
of its ``decode_slot_steps``."""


def read(ctx):
    c0, c1 = ctx["counters"]
    slots = c1["decode_slot_steps"] - c0["decode_slot_steps"]
    if slots <= 0:
        return None
    return (c1["active_slot_steps"] - c0["active_slot_steps"]) / slots
