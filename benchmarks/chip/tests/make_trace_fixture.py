"""Record the small trace ``data/small.xplane.pb`` that test_bench_trace.py reads.

Run on a TPU host: three jitted programs, the host idling under the
harness's span names between them, all inside a ``traced_window`` span.

    python benchmarks/chip/tests/make_trace_fixture.py [output path]
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready(f(x))
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("traced_window"):
        for name in ("pump_step", "submit", "pump_step"):
            with jax.profiler.TraceAnnotation(name):
                jax.block_until_ready(f(x))
            with jax.profiler.TraceAnnotation("wait_arrival"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data", "small.xplane.pb")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
