"""The serving engine's own spans and the device's executables, from a trace.

``trace_reduce.load`` keeps the device's operations and the harness's spans.
``load`` here reads the same ``.xplane.pb`` for what the program adds, and
returns ``trace_reduce.load``'s dict with two more things in it:

* ``spans``: the engine's spans (names beginning ``engine.``, see
  ``src/repro/serving/trace.py``) from every host thread, as
  (name, start_ns, duration_ns, metadata) with the metadata as a dict;
* ``modules`` in each device's dict: the events of its ``XLA Modules`` line,
  one per executable run, as (name, start_ns, duration_ns). A module is
  named ``jit_<function>(<fingerprint>)``: the engine's three executables
  are ``jit_prefill``, ``jit_decode`` and ``jit_insert``;
* ``run_starts`` in each device's dict and ``launches``: the start of each
  executable run on the device and of its ``DoEnqueueProgram`` on the host,
  by the ``run_id`` both carry.

What ``trace_reduce.load`` returns is left as it is, so every reading made
from it reads the same from this dict.

The readings below are plain functions of that dict. Each returns None where
the trace holds nothing for it to read: a program that opens no engine span,
or whose executables carry other names.

* ``pump_idle_ms``: device idle (gaps in the union of ``XLA Ops``) inside
  the ``engine.pump`` spans wholly in the window, per such pump;
* ``decode_exe_ms``: mean device time of the ``jit_decode`` runs wholly in
  the window;
* ``prefill_exe_us_per_tok``: device time of the ``jit_prefill`` runs over
  the real prompt tokens of the ``engine.prefill`` spans that launched them.
  The engine never has two prefills in flight (it waits for each one's first
  tokens before the next ``engine.prefill`` opens), so a run pairs with the
  last ``engine.prefill`` span that starts before the run ends. (Not before
  it starts: the device's clock in a trace reads behind the host's, see
  ``device_lag_ns``, so a launch span can start after its run seems to.)
  Only pairs wholly in the window count;
* ``admit_wait_ms_p50``: median over requests whose ``engine.submit`` and
  admitting ``engine.prefill`` spans both lie wholly in the window of the
  time from the start of the one to the start of the other, matched by uid;
* ``device_lag_ns``: how far the device's clock in the trace reads behind
  the host's, the least shift that puts no run before the host enqueued it
  (on a v5e 0.3-1.3 ms: as long as the host's steps between two device
  programs, so attributing idle time to host spans needs it);
* ``idle_by_span``: the device's idle time inside each innermost engine
  span, that is each span's own share of the idle with the spans nested in
  it taken out, with the device's events moved by a given lag;
* ``module_times``: device time and runs of each executable in the window.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import trace_reduce

PREFIX = "engine."
SUBMIT = "engine.submit"
PUMP = "engine.pump"
SCHEDULE = "engine.schedule"
PREFILL = "engine.prefill"
PREFILL_WAIT = "engine.prefill_wait"
INSERT = "engine.insert"
DECODE = "engine.decode"
DECODE_WAIT = "engine.decode_wait"
RETIRE = "engine.retire"
COMPILE = "engine.compile"
SPANS = (SUBMIT, PUMP, SCHEDULE, PREFILL, PREFILL_WAIT, INSERT, DECODE,
         DECODE_WAIT, RETIRE, COMPILE)

MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
PREFILL_MODULE = "jit_prefill"
DECODE_MODULE = "jit_decode"
INSERT_MODULE = "jit_insert"

Span = Tuple[str, int, int, dict]  # (name, start_ns, duration_ns, metadata)


def load(path: str) -> dict:
    """``trace_reduce.load(path)`` with the engine's spans and each device's
    executable runs added."""
    from jax.profiler import ProfileData

    tr = trace_reduce.load(path)
    spans: List[Span] = []
    launches: Dict[int, int] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name in tr["devices"]:
            dev = tr["devices"][plane.name]
            dev.update(modules=[], run_starts={})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        dev["modules"].append((e.name, int(e.start_ns), int(e.duration_ns)))
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            dev["run_starts"][int(run)] = int(e.start_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name, int(e.start_ns), int(e.duration_ns),
                                      dict(e.stats)))
                    elif e.name == ENQUEUE:
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            launches.setdefault(int(run), int(e.start_ns))
    tr["spans"] = sorted(spans, key=lambda s: (s[1], -s[2]))
    tr["launches"] = launches
    return tr


def module_name(name: str) -> str:
    """``jit_decode(123...)`` -> ``jit_decode``."""
    return name.split("(", 1)[0]


def uids(meta: dict) -> List[int]:
    """The uids of an ``engine.prefill`` span (one reads back as a number)."""
    return [int(u) for u in str(meta.get("uids", "")).split()]


def _device(tr) -> dict:
    return next(iter(tr["devices"].values()))


def _inside(s: int, d: int, ws: int, we: int) -> bool:
    return s >= ws and s + d <= we


def spans_in(tr, name: str) -> List[Span]:
    """Spans named ``name`` wholly inside the window."""
    ws, we = tr["window"]
    return [s for s in tr.get("spans", ()) if s[0] == name and _inside(s[1], s[2], ws, we)]


def runs_in(tr, module: str) -> List[trace_reduce.Event]:
    """Runs of the executable ``module`` wholly inside the window."""
    ws, we = tr["window"]
    return [e for e in _device(tr).get("modules", ())
            if module_name(e[0]) == module and _inside(e[1], e[2], ws, we)]


def pump_idle_ms(tr) -> Optional[float]:
    pumps = spans_in(tr, PUMP)
    if not pumps:
        return None
    gaps = trace_reduce.idle_gaps(_device(tr)["ops"], *tr["window"])
    idle = trace_reduce.attribute(gaps, [(PUMP, s, d) for _, s, d, _ in pumps]).get(PUMP, 0)
    return idle / len(pumps) / 1e6


def decode_exe_ms(tr) -> Optional[float]:
    runs = runs_in(tr, DECODE_MODULE)
    return sum(d for _, _, d in runs) / len(runs) / 1e6 if runs else None


def prefill_pairs(tr) -> List[Tuple[Span, trace_reduce.Event]]:
    """(``engine.prefill`` span, the ``jit_prefill`` run it launched), for
    the pairs wholly inside the window."""
    ws, we = tr["window"]
    launches = [s for s in tr.get("spans", ()) if s[0] == PREFILL]
    runs = sorted((e for e in _device(tr).get("modules", ())
                   if module_name(e[0]) == PREFILL_MODULE), key=lambda e: e[1])
    pairs, i = [], -1
    for run in runs:
        while i + 1 < len(launches) and launches[i + 1][1] < run[1] + run[2]:
            i += 1
        if i >= 0:
            sp = launches[i]
            if sp[1] >= ws and run[1] + run[2] <= we:
                pairs.append((sp, run))
    return pairs


def prefill_exe_us_per_tok(tr) -> Optional[float]:
    pairs = prefill_pairs(tr)
    tokens = sum(int(sp[3].get("tokens", 0)) for sp, _ in pairs)
    if not tokens:
        return None
    return sum(run[2] for _, run in pairs) / tokens / 1e3


def admit_waits_ms(tr) -> List[float]:
    submitted = {}
    for s in spans_in(tr, SUBMIT):
        submitted.setdefault(int(s[3].get("uid", -1)), s[1])
    waits, seen = [], set()
    for s in spans_in(tr, PREFILL):
        for uid in uids(s[3]):
            if uid in submitted and uid not in seen:
                seen.add(uid)
                waits.append((s[1] - submitted[uid]) / 1e6)
    return waits


def admit_wait_ms_p50(tr) -> Optional[float]:
    waits = admit_waits_ms(tr)
    return float(np.percentile(waits, 50)) if waits else None


def innermost(spans: List[Span]) -> List[trace_reduce.Event]:
    """The time of each span less that of the spans nested in it, as
    (name, start_ns, duration_ns) pieces. Spans of one thread nest, so the
    pieces do not overlap."""
    points = sorted({p for _, s, d, _ in spans for p in (s, s + d)})
    starts: Dict[int, List[Span]] = {}
    for sp in spans:
        starts.setdefault(sp[1], []).append(sp)
    pieces, stack = [], []
    for a, b in zip(points, points[1:]):
        stack = [sp for sp in stack if sp[1] + sp[2] > a]
        stack += sorted((sp for sp in starts.get(a, ()) if sp[2] > 0), key=lambda sp: -sp[2])
        if stack:
            pieces.append((stack[-1][0], a, b - a))
    return pieces


def device_lag_ns(tr) -> Optional[int]:
    launches = tr.get("launches", {})
    lags = [launches[run] - s for run, s in _device(tr).get("run_starts", {}).items()
            if run in launches]
    return max(lags) if lags else None


def idle_by_span(tr, lag_ns: int = 0) -> Dict[str, int]:
    """Idle nanoseconds of the device in the window by the innermost engine
    span open during them, the device's events moved ``lag_ns`` later;
    ``outside`` where no span is."""
    ws, we = tr["window"]
    ops = [(name, s + lag_ns, d) for name, s, d in _device(tr)["ops"]]
    gaps = trace_reduce.idle_gaps(ops, ws, we)
    out = trace_reduce.attribute(gaps, innermost(list(tr.get("spans", ()))))
    if "unattributed" in out:
        out["outside"] = out.pop("unattributed")
    return out


def module_times(tr) -> Dict[str, Tuple[int, int]]:
    """(runs, device ns) of each executable, clipped to the window."""
    ws, we = tr["window"]
    out: Dict[str, Tuple[int, int]] = {}
    for name, s, d in _device(tr).get("modules", ()):
        a, b = max(s, ws), min(s + d, we)
        if b > a:
            n, t = out.get(module_name(name), (0, 0))
            out[module_name(name)] = (n + 1, t + b - a)
    return out
