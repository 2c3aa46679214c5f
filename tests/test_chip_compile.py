"""The fused analog-matmul kernel compiles for a TPU v5e, at real widths.

Interpret mode (every other kernel test) never meets the chip's kernel
compiler, which refuses things the interpreter runs: a uint32 -> float32
cast, unaligned slices, too much fast memory. These tests compile the
kernel for a *described* v5e chip -- the TPU compiler is installed, no chip
is attached -- at granite-3-8b widths (d_model 4096, d_ff 12800), and check
that the compiled program holds the kernel (``tpu_custom_call``), so it did
not fall back to interpret mode. Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.analog_matmul import analog_matmul_raw


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compilation_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without a chip: keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compilation_cache):
    return SingleDeviceSharding(topo.devices[0])


#: (m, k, n, noise kind, K, vmapped request batch or None)
CASES = {
    "output_K1": (256, 4096, 4096, "output", 1, None),
    "output_K4_ffn": (256, 4096, 12800, "output", 4, None),
    "weight_K1": (256, 4096, 4096, "weight", 1, None),
    "output_K4_per_request": (256, 4096, 4096, "output", 4, 4),
    # the docs cell's prefill: noise slices drawn across 8 and 25 k-steps
    "output_K8_docs_ffn": (512, 4096, 12800, "output", 8, 4),
    "output_K8_docs_down": (512, 12800, 4096, "output", 8, 4),
}


@pytest.mark.parametrize("case", CASES)
def test_fused_kernel_compiles_for_v5e(case, one_chip):
    m, k, n, kind, n_repeats, batch = CASES[case]

    def kern(x, w, rs, cs, wq, sc, seed):
        return analog_matmul_raw(
            x, w, rs, cs, wq, sc, seed,
            noise_kind=kind, n_repeats=n_repeats, interpret=False,
        )

    if batch is not None:
        # the serving path: one noise stream per request, via jax.vmap
        def fn(x, w, rs, cs, wq, sc, seed):
            return jax.vmap(
                lambda xr, rr, sr: kern(xr, w, rr, cs, wq, sc, sr)
            )(x, rs, seed)
    else:
        fn = kern
    lead = () if batch is None else (batch,)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(fn).lower(
        spec(lead + (m, k), jnp.bfloat16),
        spec((k, n), jnp.bfloat16),
        spec(lead + (m, 1), jnp.float32),
        spec((1, n), jnp.float32),
        spec((3, n), jnp.float32),
        spec((1, 8), jnp.float32),
        spec(lead + (1, 4), jnp.uint32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
