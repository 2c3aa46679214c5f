"""Median wait of a request in the scheduler's queue, host clock: from when
it was due to the start of the ``pump_step`` that admitted it into a decode
slot (or finished it at its first token). Over every request sent in the
window."""
import numpy as np


def read(ctx):
    waits = [(t.admitted - t.due) * 1e3 for t in ctx["drive"]["tracks"]
             if t.admitted is not None]
    return float(np.percentile(waits, 50)) if waits else None
