"""Record the small trace ``data/engine.xplane.pb`` that test_bench_engine_trace.py reads.

Run on a TPU host: a tiny continuous serving engine is warmed untraced, then
serves three requests to the end inside a ``traced_window`` span, with the
harness's ``submit`` and ``pump_step`` spans around the engine's calls, as
``harness.py`` opens them. The requests make two prefill groups (two rows at
seq bucket 16, one at 32) in one pump and one decode pool.

The engine is digital and its source locations hold one frame: an analog
tier's noise makes its executables about six times larger, and the trace
stores each operation's name and source stack. Two things no reader reads
are left out of the file (``lean``): the ``/host:metadata`` plane, which
holds the executables' HLO protos, and the stats of each plane's event
metadata (an operation's shapes, source lines and cost estimates). The
events' own stats stay.

    python benchmarks/chip/tests/make_engine_trace_fixture.py [output path]
"""
import glob
import os
import shutil
import sys
import tempfile

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(HERE))), "src"))

from repro.models import init_params  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

MODEL = ModelConfig(
    name="trace-fixture", family="dense", n_layers=1, d_model=32, n_heads=2,
    n_kv_heads=1, d_ff=64, vocab_size=128, attn_q_chunk=16, attn_kv_chunk=16,
    loss_chunk=32, dtype="float32",
)
PROMPT_LENGTHS = (5, 9, 20)
MAX_NEW = 3
LEFT_OUT = ("/host:metadata",)


def serve(eng, rng, annotate):
    for length in PROMPT_LENGTHS:
        with annotate("submit"):
            eng.submit(rng.integers(0, MODEL.vocab_size, length), max_new_tokens=MAX_NEW,
                       key=rng.integers(0, 2**32, 2, dtype=np.uint64).astype(np.uint32))
    while eng.n_in_flight:
        with annotate("pump_step"):
            eng.pump_step(force=True)


def _varint(b: bytes, i: int):
    n = shift = 0
    while True:
        c = b[i]
        i += 1
        n |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return n, i


def _varint_bytes(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _fields(b: bytes):
    """(field number, payload of a length-delimited field or None, raw bytes)
    of each field of a protobuf message."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        wire = key & 7
        val = None
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(b, i)
            val, i = b[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, val, b[start:i]


def _field(number: int, payload: bytes) -> bytes:
    return _varint_bytes(number << 3 | 2) + _varint_bytes(len(payload)) + payload


def _lean_plane(plane: bytes) -> bytes:
    """An XPlane whose event metadata (field 4: map entries, the value in
    field 2) keep no stats (XEventMetadata field 5)."""
    def lean_entry(entry):
        return b"".join(
            _field(2, b"".join(r for f, _, r in _fields(v) if f != 5)) if f == 2 else raw
            for f, v, raw in _fields(entry))
    return b"".join(_field(4, lean_entry(v)) if f == 4 else raw
                    for f, v, raw in _fields(plane))


def lean(space: bytes) -> bytes:
    """An XSpace (field 1: each XPlane, whose field 2 is its name) without
    the planes in ``LEFT_OUT`` and without event metadata stats."""
    def name(plane):
        return next((v.decode() for f, v, _ in _fields(plane) if f == 2), "")
    return b"".join(
        raw if f != 1 else b"" if name(v) in LEFT_OUT else _field(1, _lean_plane(v))
        for f, v, raw in _fields(space))


def main():
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    eng = ServingEngine(
        init_params(jax.random.PRNGKey(0), MODEL), MODEL, max_gen=4, max_batch=2,
        batch_buckets=(1, 2), seq_buckets=(16, 32), max_wait=0.0,
        continuous=True, pool_slots=4,
    )
    rng = np.random.default_rng(0)
    serve(eng, rng, lambda name: jax.profiler.TraceAnnotation("warm_" + name))
    misses = eng.cache_stats()["misses"]
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("traced_window"):
        serve(eng, rng, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    assert eng.cache_stats()["misses"] == misses, "a compile in the window"
    (src,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data", "engine.xplane.pb")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(src, "rb") as f:
        data = f.read()
    with open(out, "wb") as f:
        f.write(lean(data))
    shutil.rmtree(d)
    print(f"{out}: {os.path.getsize(out)} bytes ({len(data)} recorded)")


if __name__ == "__main__":
    main()
