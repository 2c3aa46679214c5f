"""Shared arithmetic of the per-layer metric readers (``metrics/``).

A reader gets ``ctx``: the reduced trace (``trace_reduce.load``), its
``summary``, the traced window's host-clock bounds ``t0``/``t1`` and the
offset from the host clock to the trace's (``offset_ns``), the engine
counters at both bounds, what the harness saw (``drive``), the model's
sizes (``dims``), one chip's peaks (``peak``), and the cell's ``chips`` and
their ``device_ids`` (the trace holds those devices' planes only; a rate of
the whole cell divides by ``chips`` times a chip's peak).
"""
from __future__ import annotations

from typing import List, Tuple

import trace_reduce

#: the fused analog kernel runs as a Mosaic custom call; its operation name
#: (the HLO instruction) carries the call's target
KERNEL_MARKS = ('custom_call_target="tpu_custom_call"',)


def device(ctx) -> dict:
    return next(iter(ctx["trace"]["devices"].values()))


def to_trace(ctx, t: float) -> int:
    return int(t * 1e9) + ctx["offset_ns"]


def pumps_in_window(ctx) -> List[Tuple[float, float, bool]]:
    """(start, end, admitted) of each pump wholly inside the traced window;
    ``admitted``: the pump admitted at least one request (so it ran
    prefill and insert executables before its decode step)."""
    starts = {p[0] for p in ctx["drive"]["prefills"]}
    return [(a, b, a in starts) for a, b in ctx["drive"]["pumps"]
            if a >= ctx["t0"] and b <= ctx["t1"]]


def busy_in(ctx, a: float, b: float, events=None) -> int:
    ev = device(ctx)["ops"] if events is None else events
    return trace_reduce.busy(ev, to_trace(ctx, a), to_trace(ctx, b))


def is_kernel(name: str) -> bool:
    return any(m in name for m in KERNEL_MARKS)


def kernel_events(ctx):
    return [e for e in device(ctx)["ops"] if is_kernel(e[0])]


def prefill_us_per_tok(ctx):
    """Device time of prefill per real (unpadded) prompt token: the device's
    busy time inside the traced pumps that admitted requests, less the decode
    steps they also ran (at the mean device time of a decode-only pump), over
    the real prompt tokens those pumps took in."""
    pumps = pumps_in_window(ctx)
    dec = [busy_in(ctx, a, b) for a, b, adm in pumps if not adm]
    if not dec:
        return None
    step = sum(dec) / len(dec)
    busy, tokens = 0.0, 0
    starts = {a for a, _, adm in pumps if adm}
    for a, b, adm in pumps:
        if adm:
            busy += busy_in(ctx, a, b) - step
    for t0, _bb, _sb, real in ctx["drive"]["prefills"]:
        if t0 in starts:
            tokens += real
    return busy / tokens / 1e3 if tokens else None
