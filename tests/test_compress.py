"""Gradient compression: int8 bounds, error feedback, compressed psum."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim.compress import (
    ef_compress,
    ef_int8_roundtrip,
    int8_dequantize,
    int8_quantize,
)


@settings(max_examples=30, deadline=None)
@given(scale=st.floats(min_value=1e-4, max_value=1e4), seed=st.integers(0, 2**31 - 1))
def test_int8_roundtrip_error_bound(scale, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=128) * scale, jnp.float32)
    q, s = int8_quantize(x)
    err = jnp.abs(int8_dequantize(q, s) - x)
    assert float(err.max()) <= float(s) / 2 + 1e-7 * scale


def test_ef_bias_vanishes():
    """With error feedback, the TIME-AVERAGED compressed gradient converges
    to the true gradient (compression bias is eliminated)."""
    key = jax.random.PRNGKey(0)
    g_true = {"w": jax.random.normal(key, (64,)) * 1e-3}
    # a tiny constant gradient that int8 alone would mangle badly
    err = None
    acc = jnp.zeros((64,))
    n = 200
    for i in range(n):
        g_c, err = ef_compress(g_true, err)
        acc = acc + g_c["w"]
    mean = acc / n
    rel = float(jnp.linalg.norm(mean - g_true["w"]) / jnp.linalg.norm(g_true["w"]))
    assert rel < 0.05

    # without EF the bias persists for adversarial values
    x = {"w": jnp.full((64,), 1.0).at[0].set(300.0)}  # scale -> 300/127
    plain = ef_int8_roundtrip(x)["w"]
    assert float(jnp.abs(plain[1:] - 1.0).max()) > 0.1


def test_roundtrip_preserves_dtype_and_shape():
    g = {"a": jnp.ones((3, 5), jnp.bfloat16), "b": jnp.ones((7,), jnp.float32)}
    out = ef_int8_roundtrip(g)
    assert out["a"].shape == (3, 5) and out["a"].dtype == jnp.bfloat16
    assert out["b"].dtype == jnp.float32


def test_compressed_psum_multidevice_subprocess():
    """compressed_psum on an 8-device CPU mesh approximates the exact psum."""
    import subprocess, sys, textwrap

    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum
        mesh = jax.make_mesh((8,), ("d",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        f = jax.shard_map(lambda v: compressed_psum(v[0], "d")[None],
                          mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))
        got = np.asarray(f(x))
        want = np.asarray(jnp.sum(x, axis=0))
        # mean-scale reconstruction: ~1 int8 step of error per participant
        rel = np.abs(got - want[None]).max() / np.abs(want).max()
        assert rel < 0.15, rel
        corr = np.corrcoef(got[0], want)[0, 1]
        assert corr > 0.999, corr
        print("OK", rel)
        """
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
