"""The serving engine's spans on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``: while a profiler records
(``jax.profiler.start_trace``) it lands in the trace's host plane, on the
same clock as the device's events, so a gap in the device's work can be
read against what the engine's host code was doing in it. With no profiler
recording a span costs one C++ check and a constructor call (about half a
microsecond), and its metadata is not built at all.

One pump of the continuous engine (``ServingEngine._pump_once``) nests as::

    engine.pump
      engine.schedule       expiry, governor, free slots, pop_admissible
      engine.prefill        per admitted group: bucket, pad, keys, launch
        engine.compile      only when the executable is not cached yet
      engine.prefill_wait   the first tokens back on the host
      engine.insert         the cache rows scattered into the pool
      engine.decode         per pool: the host-to-device copies, launch
      engine.decode_wait    the decoded tokens back on the host
      engine.retire         the per-slot bookkeeping

and ``engine.submit`` wraps the scheduler insert of ``submit``.
``engine.attach_mesh`` wraps the weights' placement on a mesh, with the
leaves ``kept`` as they lay, the leaves ``relaid`` and the
``weight_bytes_per_chip`` in its metadata. The spans of
one request share its uid: ``engine.submit`` carries ``uid``, and
``engine.prefill`` the space-separated ``uids`` of its rows: the profiler's
metadata encoding cuts a value at a comma, and all that follows at a ``#``
(``text`` strips both). A numeric metadata value reads back from the trace
as a number, so a single uid reads back as an int.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

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

#: set-up, outside any pump (so not among ``SPANS``)
ATTACH_MESH = "engine.attach_mesh"


def span(name: str, **meta) -> TraceAnnotation:
    """The span ``name``. ``meta`` is attached only while a profiler
    records; a value that is callable is called then, so metadata that
    costs anything to build (a uid list) is built only for a trace."""
    if meta and TraceAnnotation.is_enabled():
        return TraceAnnotation(
            name, **{k: v() if callable(v) else v for k, v in meta.items()})
    return TraceAnnotation(name)


def text(value) -> str:
    """``value`` as metadata text that reads back whole from a trace."""
    return str(value).replace(",", "").replace("#", "")
