"""Share of the fused analog kernel's roofline, from the trace.

The least time of each kernel call is the larger of its operations over the
chip's peak bf16 rate and its bytes over the peak HBM bandwidth
(``flops.kernel_call_cost``: 2 m k n operations whatever K, and x, w and y at
bfloat16 size, w read once). Summed over the prefill dispatches of the pumps
wholly inside the traced window, it is divided by the device time of the
kernel's operations inside those pumps."""
import flops
import readers
import trace_reduce


def read(ctx):
    d, pk = ctx["dims"], ctx["peak"]
    pumps = {a: b for a, b, adm in readers.pumps_in_window(ctx) if adm}
    least = 0.0
    for t0, bb, sb, _real in ctx["drive"]["prefills"]:
        if t0 in pumps:
            for _, k, n in flops.matmul_shapes(d):
                f, by = flops.kernel_call_cost(bb * sb, k, n)
                least += max(f / pk["bf16_flops"], by / pk["hbm_bytes_s"]) * d["n_layers"]
    kev = readers.kernel_events(ctx)
    ns = sum(trace_reduce.busy(kev, readers.to_trace(ctx, a), readers.to_trace(ctx, b))
             for a, b in pumps.items())
    if ns <= 0 or least <= 0:
        return None
    return 100.0 * least / (ns / 1e9)
