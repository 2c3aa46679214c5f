"""Device time of one decode step, from the trace: the device's busy time
inside the pumps of the traced window that admitted nothing (each runs one
decode executable per pool with live slots), over the decode launches they
made."""
import readers


def read(ctx):
    busy, steps = 0, 0
    for a, b, admitted in readers.pumps_in_window(ctx):
        if not admitted:
            busy += readers.busy_in(ctx, a, b)
            steps += 1
    return busy / steps / 1e6 if steps else None
