"""Model operations of the real work done in the traced window, over the
window and the chip's peak bf16 rate. Real work only: each prompt admitted
in the window (its tokens through every layer, causal attention, the head
once) and each token decoded in it at its own context; no padding rows, no
idle pool slots, no noise repeats."""
import flops


def read(ctx):
    d, t0, t1 = ctx["dims"], ctx["t0"], ctx["t1"]
    work = 0.0
    for t in ctx["drive"]["tracks"]:
        L = t.req.prompt.size
        if t.admitted is not None and t0 <= t.admitted and t.times and t.times[0] <= t1:
            work += flops.prompt_flops(d, L)
        for i, x in enumerate(t.times[1:], start=1):
            if t0 <= x <= t1:
                work += flops.token_flops(d, L + i, head=True)
    span = t1 - t0
    return 100.0 * work / span / ctx["peak"]["bf16_flops"] if span > 0 else None
