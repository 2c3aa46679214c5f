"""Fused K-repeat dynamic precision (paper §IV) + backend dispatch.

Covers the acceptance criteria of the fused-execution refactor:
  * kernel vs pure-jnp oracle agreement for every noise kind at K in
    {1, 4, 16}, including non-multiple-of-128 shapes (K-tail masking);
  * bit-exact repeat-averaged draws: tiled windows of the averaged noise
    reproduce the full-array draw exactly (the kernel/oracle contract);
  * fused K-repeat variance matches the explicit O(K) time-averaging oracle;
  * AnalogHook reaches the Pallas kernel under backend="pallas";
  * the analytic HBM traffic of the fused form is independent of K;
  * output noise drawn in row slices across the k-steps is bit-identical
    to the oracle's whole-array draw, and the kernel records its plan.
"""
import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AnalogConfig, SiteQuant
from repro.core.analog import analog_dot
from repro.core.redundant import (
    spatial_averaged_dot_explicit,
    time_averaged_dot,
    time_averaged_dot_explicit,
)
from repro.kernels import analog_matmul, analog_matmul_reference
from repro.kernels.analog_matmul import (
    DEFAULT_BLOCK,
    NOISE_PLANS,
    analog_matmul_raw,
    noise_plan,
)
from repro.kernels.dispatch import resolve_backend
from repro.kernels.prng import repeat_averaged_gaussian_tile, repeat_key
from repro.models.hooks import AnalogHook
from repro.quant import calibrate_minmax

KEY = jax.random.PRNGKey(23)

# deliberately ragged: exercises the K-tail masking and M/N block padding
SHAPES = [(96, 200, 72), (17, 130, 33)]


def _setup(m, k, n):
    x = jax.random.normal(KEY, (m, k))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n)) * 0.2
    sq = SiteQuant(
        wqp=calibrate_minmax(w, channel_axis=1),
        xqp=calibrate_minmax(x),
        oqp=calibrate_minmax(x @ w),
    )
    return x, w, sq


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("k_rep", [1, 4, 16])
@pytest.mark.parametrize(
    "cfg,e",
    [
        (AnalogConfig.shot(), 10.0),
        (AnalogConfig.thermal(0.01), 4.0),
        (AnalogConfig.weight(0.1), 5.0),
        (AnalogConfig(mode="analog"), 1.0),
    ],
    ids=["shot", "thermal", "weight", "none"],
)
def test_fused_kernel_matches_oracle(shape, k_rep, cfg, e):
    m, k, n = shape
    x, w, sq = _setup(m, k, n)
    yk = analog_matmul(
        x, w, energy=jnp.asarray(e), key=KEY, cfg=cfg, sq=sq,
        n_repeats=k_rep, block=(32, 32, 64),
    )
    yr = analog_matmul_reference(
        x, w, energy=jnp.asarray(e), key=KEY, cfg=cfg, sq=sq, n_repeats=k_rep
    )
    scale = float(jnp.abs(yr).max()) + 1e-6
    atol = 3e-5 * scale
    if cfg.out_bits is not None and sq.oqp is not None:
        # tiled f32 accumulation can flip a rounding boundary by one bin
        atol = max(atol, float(sq.oqp.delta) * 1.01)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=atol, rtol=1e-4)


def test_repeat_averaged_draws_bit_exact_under_tiling():
    """The repeat-averaged gaussian is a pure function of global indices:
    any tiled window must equal the corresponding slice of the full draw
    BIT-exactly — this is what makes kernel and oracle agree for any
    BlockSpec at any K."""
    k0, k1 = jnp.uint32(5), jnp.uint32(9)
    for k_rep in (1, 4, 16):
        full = repeat_averaged_gaussian_tile(k0, k1, 0, 0, (48, 40), k_rep)
        sub = repeat_averaged_gaussian_tile(k0, k1, 16, 8, (16, 16), k_rep)
        np.testing.assert_array_equal(
            np.asarray(full[16:32, 8:24]), np.asarray(sub)
        )


def test_repeat_streams_identity_and_decorrelation():
    """r=0 leaves the stream untouched (K=1 == single draw, bit-for-bit);
    r>0 streams are decorrelated."""
    k0, k1 = jnp.uint32(3), jnp.uint32(7)
    assert int(repeat_key(k1, 0)) == int(k1)
    g1 = repeat_averaged_gaussian_tile(k0, k1, 0, 0, (64, 64), 1).reshape(-1)
    from repro.kernels.prng import gaussian_tile

    g_single = gaussian_tile(k0, k1, 0, 0, (64, 64)).reshape(-1)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g_single))
    g_r1 = gaussian_tile(k0, repeat_key(k1, 1), 0, 0, (64, 64)).reshape(-1)
    corr = float(jnp.corrcoef(jnp.stack([g_single, g_r1]))[0, 1])
    assert abs(corr) < 0.05


@pytest.mark.parametrize(
    "cfg,e",
    [(AnalogConfig.shot(), 2.0), (AnalogConfig.weight(0.1), 1.0)],
    ids=["shot", "weight"],
)
def test_fused_variance_matches_explicit_oracle(cfg, e):
    """Fused K-repeat (kernel path) noise variance == the explicit O(K)
    time-averaging oracle's, within statistical tolerance."""
    x = jax.random.normal(KEY, (16, 64))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 24)) * 0.2
    clean = x @ w
    k_rep = 4

    def std(fn, n=160):
        ys = jax.vmap(fn)(jax.random.split(KEY, n))
        return float(jnp.std(ys - jnp.mean(ys, axis=0)[None]))

    s_fused = std(
        lambda k: analog_matmul(
            x, w, energy=jnp.asarray(e), key=k, cfg=cfg,
            n_repeats=k_rep, block=(16, 16, 32),
        )
    )
    s_explicit = std(
        lambda k: time_averaged_dot_explicit(
            x, w, cfg=cfg, base_energy=jnp.asarray(e), key=k, k_repeats=k_rep
        )
    )
    assert s_fused == pytest.approx(s_explicit, rel=0.15)
    # and both sit at 1/sqrt(K) of the single draw
    s_one = std(
        lambda k: analog_dot(x, w, cfg=cfg, energy=jnp.asarray(e), key=k)
    )
    assert s_one / s_fused == pytest.approx(np.sqrt(k_rep), rel=0.2)


def test_fused_path_matches_spatial_oracle_variance():
    cfg = AnalogConfig.weight(0.1, out_bits=None, weight_bits=None, act_bits=None)
    x = jax.random.normal(KEY, (8, 48))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (48, 16)) * 0.2

    def std(fn, n=160):
        ys = jax.vmap(fn)(jax.random.split(KEY, n))
        return float(jnp.std(ys - jnp.mean(ys, axis=0)[None]))

    s_fused = std(
        lambda k: time_averaged_dot(
            x, w, cfg=cfg, base_energy=jnp.asarray(1.0), key=k, k_repeats=4
        )
    )
    s_spatial = std(
        lambda k: spatial_averaged_dot_explicit(
            x, w, cfg=cfg, base_energy=jnp.asarray(1.0), key=k, k_repeats=4
        )
    )
    assert s_fused == pytest.approx(s_spatial, rel=0.2)


# ---------------------------------------------------------------------------
# backend dispatch
# ---------------------------------------------------------------------------


def test_resolve_backend_rules():
    big, small = (256, 256), (256, 8)
    w_big = (256, 256)
    assert resolve_backend(AnalogConfig.shot(backend="pallas"), big, w_big) == "pallas"
    assert resolve_backend(AnalogConfig.shot(backend="jnp"), big, w_big) == "jnp"
    assert resolve_backend(AnalogConfig.shot(use_kernel=True), big, w_big) == "pallas"
    assert resolve_backend(AnalogConfig(), big, w_big) == "jnp"  # digital
    if jax.default_backend() != "tpu":
        # auto never picks interpret-mode Pallas off-TPU
        assert resolve_backend(AnalogConfig.shot(), big, w_big) == "jnp"
    with pytest.raises(ValueError):
        AnalogConfig.shot(backend="cuda")


def test_analog_hook_reaches_pallas_kernel(monkeypatch):
    """AnalogHook.__call__ and .batched execute the fused Pallas kernel
    under backend="pallas" — the model hot path actually reaches
    analog_matmul_raw."""
    from repro.kernels import ops as kernel_ops

    calls = []
    real = kernel_ops.analog_matmul_raw

    def spy(*args, **kwargs):
        calls.append(kwargs.get("n_repeats"))
        return real(*args, **kwargs)

    monkeypatch.setattr(kernel_ops, "analog_matmul_raw", spy)
    cfg = AnalogConfig.shot(backend="pallas")
    x = jax.random.normal(KEY, (16, 32))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (32, 8)) * 0.2
    hook = AnalogHook(cfg=cfg, energies={"q": jnp.asarray(8.0)}, key=KEY, n_repeats=4)
    y = hook("q", x, w)
    assert y.shape == (16, 8)
    assert calls == [4]

    xb = jax.random.normal(KEY, (2, 16, 32))
    wb = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 32, 8)) * 0.2
    yb = hook.batched("q", xb, wb)
    assert yb.shape == (2, 16, 8)
    assert len(calls) == 2  # one more trace through the kernel


def test_fused_jnp_equivalence_high_energy():
    """The jnp fallback implements n_repeats=K as a single draw at K*E:
    same distribution as the kernel's in-register average."""
    cfg = AnalogConfig.shot(backend="jnp")
    x = jax.random.normal(KEY, (16, 64))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 24)) * 0.2

    def std(fn, n=160):
        ys = jax.vmap(fn)(jax.random.split(KEY, n))
        return float(jnp.std(ys - jnp.mean(ys, axis=0)[None]))

    s_rep = std(
        lambda k: analog_dot(x, w, cfg=cfg, energy=jnp.asarray(2.0), key=k, n_repeats=8)
    )
    s_one = std(lambda k: analog_dot(x, w, cfg=cfg, energy=jnp.asarray(16.0), key=k))
    assert s_rep == pytest.approx(s_one, rel=0.15)


def test_analytic_traffic_fused_independent_of_k():
    """Acceptance criterion: fused HBM traffic is the same for every K while
    the unfused form scales ~K-fold."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.kernel_bench import analytic_traffic

    m, k, n = 512, 512, 512
    t1 = analytic_traffic(m, k, n, 1)
    t16 = analytic_traffic(m, k, n, 16)
    assert t1["hbm_bytes_fused"] == t16["hbm_bytes_fused"]
    ratio = t16["hbm_bytes_unfused"] / t1["hbm_bytes_unfused"]
    assert ratio == pytest.approx(16.0, rel=0.1)


# ---------------------------------------------------------------------------
# output noise drawn across the k-steps
# ---------------------------------------------------------------------------

#: (m, k, n, block, plan): two row and two column tiles each, so the row
#: and column tile offsets of the counters are exercised.
SPREAD_CASES = {
    "nk1_finish": (128, 96, 80, (64, 40, 128), "finish"),
    "nk3_exact": (144, 96, 80, (72, 40, 32), (24, 3)),  # 72 / 3 = 24 rows
    "nk3_rounded": (128, 96, 80, (64, 40, 32), (24, 3)),  # 22 -> 24, tail 16
    "nk25_rounded": (512, 200, 80, (256, 40, 8), (16, 16)),  # 11 -> 16, 9 idle
}


@pytest.mark.parametrize("k_rep", [1, 8])
@pytest.mark.parametrize("case", SPREAD_CASES)
def test_spread_output_noise_bit_exact(case, k_rep):
    """Thermal noise with x = 0 leaves y = col_scale * xi exactly, so the
    kernel's noise, drawn slice by slice into its scratch, must equal the
    oracle's whole-array draw bit for bit, at a nonzero column origin.
    Both sides are compiled: run op by op, the oracle rounds each repeat's
    add on its own, where compiled code may contract the multiply-adds of
    the repeat average (the kernel always runs compiled)."""
    m, k, n, block, plan = SPREAD_CASES[case]
    assert noise_plan(m, k, n, block) == plan
    cfg = AnalogConfig.thermal(0.01, weight_bits=None, act_bits=None, out_bits=None)
    # the activation range sets the thermal scale; x itself is zero
    sq = SiteQuant(xqp=calibrate_minmax(jax.random.normal(KEY, (m, k))))
    x = jnp.zeros((m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n)) * 0.2
    kw = dict(energy=jnp.asarray(4.0), cfg=cfg, sq=sq, n_repeats=k_rep, offsets=(0, 40))

    yk = jax.jit(lambda x, w, key: analog_matmul(x, w, key=key, block=block, **kw))(x, w, KEY)
    yr = jax.jit(lambda x, w, key: analog_matmul_reference(x, w, key=key, **kw))(x, w, KEY)
    assert float(jnp.abs(yr).min()) > 0  # every element holds noise
    np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))


def _trace_kernel(m, k, n, noise_kind="output", n_repeats=8):
    f32 = jnp.float32
    args = [
        jax.ShapeDtypeStruct(s, d)
        for s, d in [((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16), ((m, 1), f32),
                     ((1, n), f32), ((3, n), f32), ((1, 8), f32), ((1, 4), jnp.uint32)]
    ]
    jax.eval_shape(
        lambda *a: analog_matmul_raw(*a, noise_kind=noise_kind, n_repeats=n_repeats),
        *args,
    )


def test_noise_plan_and_counter():
    """The docs cell's prefill shapes spread their noise (gate/up: 32 rows
    on each of 8 k-steps; down: 16 rows on 16 of 25); one k-step draws at
    the finish; weight noise records no output-noise plan. Each traced
    kernel counts once under its plan."""
    assert noise_plan(512, 4096, 12800, DEFAULT_BLOCK) == (32, 8)
    assert noise_plan(512, 12800, 4096, DEFAULT_BLOCK) == (16, 16)
    assert noise_plan(512, 512, 4096, DEFAULT_BLOCK) == "finish"
    assert noise_plan(512, 300, 4096, DEFAULT_BLOCK) == "finish"
    assert noise_plan(4, 4096, 4096, DEFAULT_BLOCK) == (4, 1)  # c never above bm
    assert noise_plan(512, 4096, 12800, DEFAULT_BLOCK, "weight") is None

    before = collections.Counter(NOISE_PLANS)
    _trace_kernel(512, 4096, 12800)
    _trace_kernel(512, 4096, 12800)
    _trace_kernel(512, 12800, 4096)
    _trace_kernel(512, 512, 4096)
    _trace_kernel(512, 4096, 12800, noise_kind="weight")
    assert NOISE_PLANS - before == {(32, 8): 2, (16, 16): 1, "finish": 1}
