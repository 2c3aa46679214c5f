"""The serving engine's span helper: metadata is built only while a profiler
records, and metadata text reads back whole from a trace."""
import glob
import os

import jax

from repro.serving import trace


def test_span_metadata_is_lazy_without_a_profiler():
    calls = []

    def uids():
        calls.append(1)
        return "1 2"

    with trace.span(trace.PREFILL, bb=2, uids=uids):
        pass
    assert calls == []


def test_span_metadata_reads_back_under_the_profiler(tmp_path):
    from jax.profiler import ProfileData

    key = ("prefill", 4, 512, (1, "front#8,rest2"))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span(trace.COMPILE, key=lambda: trace.text(key)):
            pass
        with trace.span(trace.PREFILL, tier="k1", bb=2, uids=lambda: "3 5 9"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    got = {e.name: dict(e.stats) for p in ProfileData.from_file(path).planes
           for line in p.lines for e in line.events if e.name.startswith("engine.")}
    assert got[trace.COMPILE] == {"key": "('prefill' 4 512 (1 'front8rest2'))"}
    assert got[trace.PREFILL] == {"tier": "k1", "bb": 2, "uids": "3 5 9"}


def test_span_names_are_distinct_and_not_the_harness_names():
    assert len(set(trace.SPANS)) == len(trace.SPANS)
    harness = {"submit", "pump_step", "bookkeeping", "wait_arrival", "traced_window"}
    assert all(n.startswith("engine.") and n not in harness for n in trace.SPANS)


def test_attach_mesh_span_reads_back(tmp_path):
    """``engine.attach_mesh`` carries the leaves kept and re-laid and the
    weight bytes a chip; on a one-device mesh every leaf lies as it was."""
    from jax.profiler import ProfileData

    from repro.launch.mesh import make_mesh_for_devices
    from repro.models import init_params
    from repro.serving import ServingEngine
    from test_serving import MODEL

    params = init_params(jax.random.PRNGKey(0), MODEL)
    engine = ServingEngine(params, MODEL, max_gen=2, seq_buckets=(16,))
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.attach_mesh(make_mesh_for_devices(1))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    (got,) = [dict(e.stats) for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events if e.name == trace.ATTACH_MESH]
    leaves = jax.tree.leaves(params)
    whole = sum(a.nbytes for a in leaves)
    assert got == {"kept": len(leaves), "relaid": 0, "weight_bytes_per_chip": whole}
    assert engine.weight_bytes_per_chip == whole
