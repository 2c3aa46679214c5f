"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"]) if s["window_s"] > 0 else None
