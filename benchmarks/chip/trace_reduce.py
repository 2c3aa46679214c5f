"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps, in plain
tuples of (name, start_ns, duration_ns):

* for each device plane (``/device:TPU:<n>``), the events of its ``XLA Ops``
  line, one per operation run on the device, named by its HLO instruction;
  given the cell's device ids, only the planes of those devices (the
  profiler records every chip of the host, and a chip the cell does not use
  would dilute the busy share averaged over the planes);
* the host spans the harness opens with ``jax.profiler.TraceAnnotation``
  (``HOST_SPANS``), from every host thread.

The reductions below are plain functions of those tuples, so a test can
check them against counts written by hand:

* ``busy`` is the union of the operation intervals clipped to the window;
  the idle share is ``1 - busy / window``;
* ``op_sums`` adds each operation name's device time inside the window
  (the breakdown names an operation by its HLO instruction name and leaves
  out control-flow operations, whose events span the operations inside);
* ``idle_gaps`` lists the gaps in the union, and ``attribute`` splits each
  gap among the host spans open during it, by overlap; what no span covers
  is ``unattributed``.

On a v5e host the device's and the host's clocks in one trace agree to about
a quarter of a millisecond, which bounds how finely a gap is attributed.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]  # (name, start_ns, duration_ns)

HOST_SPANS = ("submit", "pump_step", "bookkeeping", "wait_arrival")
WINDOW_SPAN = "traced_window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
#: instruction-name prefixes of control flow (a layer scan is a ``while``)
CONTAINERS = ("%while", "%conditional", "%call")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, device_ids: Optional[Iterable[int]] = None) -> dict:
    """Device and host events of one trace, as plain tuples; with
    ``device_ids``, only those devices' planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    keep = None if device_ids is None else {int(i) for i in device_ids}
    devices, host, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            if keep is not None and int(plane.name[len(DEVICE_PLANE):]) not in keep:
                continue
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
            devices[plane.name] = dict(ops=ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, int(e.start_ns), int(e.duration_ns)))
                    elif e.name == WINDOW_SPAN:
                        window = (int(e.start_ns), int(e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return dict(devices=devices, host=sorted(host, key=lambda e: e[1]), window=window)


def clip(events: Iterable[Event], ws: int, we: int) -> List[Tuple[int, int]]:
    out = []
    for _, s, d in events:
        a, b = max(s, ws), min(s + d, we)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def busy(events: Iterable[Event], ws: int, we: int) -> int:
    return sum(b - a for a, b in union(clip(events, ws, we)))


def idle_gaps(events: Iterable[Event], ws: int, we: int) -> List[Tuple[int, int]]:
    gaps, t = [], ws
    for a, b in union(clip(events, ws, we)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if we > t:
        gaps.append((t, we))
    return gaps


def op_sums(events: Iterable[Event], ws: int, we: int) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for name, s, d in events:
        a, b = max(s, ws), min(s + d, we)
        if b > a:
            out[name] = out.get(name, 0) + (b - a)
    return out


def attribute(gaps: List[Tuple[int, int]], spans: List[Event]) -> Dict[str, int]:
    """Idle nanoseconds by the host span open during them."""
    out: Dict[str, int] = {}
    for ga, gb in gaps:
        covered = 0
        for name, s, d in spans:
            a, b = max(ga, s), min(gb, s + d)
            if b > a:
                out[name] = out.get(name, 0) + (b - a)
                covered += b - a
        rest = (gb - ga) - covered
        if rest > 0:
            out["unattributed"] = out.get("unattributed", 0) + rest
    return out


def short(name: str) -> str:
    """An operation's HLO instruction name: the text before `` = ``."""
    return name.split(" = ", 1)[0]


def is_container(name: str) -> bool:
    """Control-flow operations whose events span the operations inside them."""
    return short(name).startswith(CONTAINERS)


def top(d: Dict[str, int], n: int = 10) -> List[list]:
    """The ``n`` largest entries as [name, seconds]."""
    return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def summary(tr: dict) -> dict:
    """Window, busy time and breakdown, averaged over the devices kept."""
    ws, we = tr["window"]
    devs = list(tr["devices"].values())
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    busy_ns = sum(busy(d["ops"], ws, we) for d in devs) / len(devs)
    ops: Dict[str, int] = {}
    idle: Dict[str, int] = {}
    for d in devs:
        leaves = [e for e in d["ops"] if not is_container(e[0])]
        for k, v in op_sums(leaves, ws, we).items():
            k = short(k)
            ops[k] = ops.get(k, 0) + v / len(devs)
        for k, v in attribute(idle_gaps(d["ops"], ws, we), tr["host"]).items():
            idle[k] = idle.get(k, 0) + v / len(devs)
    return dict(window_s=(we - ws) / 1e9, busy_s=busy_ns / 1e9,
                device_ops=top(ops), idle_gaps=top(idle), op_ns=ops)
