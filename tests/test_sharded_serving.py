"""Tensor-parallel serving goldens: the tile-salted noise contract.

Each TP shard of the column-parallel analog matmul salts its counter-based
noise stream with its *global* tile coordinates, so shard (i, j) draws
exactly the (i, j) tile of the unsharded stream — sharding can never change
which noise a request sees. Pinned here at three levels:

  * unit: ``kernels/prng.gaussian_tile`` at offset (r0, c0) is bit-exactly
    the [r0:, c0:] slice of the offset-(0, 0) draw (single and K-repeat
    streams), and column shards partition the full draw.
  * golden: a mesh-attached ``ServingEngine`` serves bit-identical tokens
    to the single-device oracle, per family (dense + griffin, the stateful
    rung) and under a *non-uniform* per-layer ``PrecisionProfile``.
  * sharded weights: a continuous engine handed weights drawn in their
    shards (``tree_shardings(..., DEFAULT_RULES)``, as the chip benchmark
    draws them) keeps them sharded — every matrix of the layer stack split
    by its columns, ``wo`` and ``w_down`` re-laid so — and serves the
    one-device engine's tokens and logits bit for bit at K=1, K=8 and a
    profile tier, with no analog dot falling back to the whole product and
    nothing compiled for a second request.

The mesh cases run in a child process on four virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``: a process that has
started JAX cannot be given more), which prints what it saw as one JSON
line; the tests check it.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import AnalogConfig, PrecisionProfile
from repro.kernels import prng
from repro.launch.mesh import make_mesh_for_devices
from repro.models import init_energy_tree, init_params
from repro.models.config import ModelConfig
from repro.serving import ServingEngine
from test_serving import ENERGY_AJ, SB

KEY = jax.random.PRNGKey(0)
HERE = os.path.dirname(os.path.abspath(__file__))

DENSE = ModelConfig(
    name="shard-dense", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv_heads=1, d_ff=64, vocab_size=128, attn_q_chunk=16, attn_kv_chunk=16,
    loss_chunk=32, dtype="float32",
)
GRIFFIN = ModelConfig(
    name="shard-griffin", family="griffin", n_layers=3, d_model=32, n_heads=2,
    n_kv_heads=1, head_dim=16, d_ff=64, vocab_size=128, rnn_width=32,
    conv_width=4, local_window=8, attn_q_chunk=16, attn_kv_chunk=16,
    loss_chunk=32, dtype="float32",
)
#: every matrix of the layer stack and the embedding split four ways by the
#: tensor-parallel rules (2 KV heads of 64: K and V have 128 columns). XLA's
#: CPU dot rounds some narrow products differently whole and in column
#: shards; at these widths and 16-row prompts each analog dot agrees
TP = ModelConfig(
    name="shard-tp", family="dense", n_layers=2, d_model=128, n_heads=4,
    n_kv_heads=2, head_dim=64, d_ff=256, vocab_size=256, attn_q_chunk=16,
    attn_kv_chunk=16, loss_chunk=32, dtype="float32",
)
TP_SB = 16

#: non-uniform per-layer repeat profiles (n_layers entries each)
PROFILES = {"shard-dense": (2, 1), "shard-griffin": (2, 1, 1), "shard-tp": (2, 1)}
#: the tiers of the sharded-weights case: uniform K=1, K=8 and a profile
TP_TIERS = {"k1": 1, "k8": 8, "profile": "nu"}
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


# --------------------------------------------------------------------------
# unit: tile-coordinate noise salt
# --------------------------------------------------------------------------


def test_tile_salt_shard_equals_slice():
    """Shard (i, j) of the sharded draw IS slice (i, j) of the unsharded
    draw — ``gaussian_tile`` is a pure function of global element indices."""
    k0, k1 = np.uint32(0xA5A5_A5A5), np.uint32(0x1234)
    full = np.asarray(prng.gaussian_tile(k0, k1, 0, 0, (16, 24)))
    for r0, c0, m, n in [(0, 0, 16, 24), (4, 8, 8, 8), (12, 16, 4, 8)]:
        tile = np.asarray(prng.gaussian_tile(k0, k1, r0, c0, (m, n)))
        np.testing.assert_array_equal(tile, full[r0 : r0 + m, c0 : c0 + n])
    # the K-repeat averaged stream tiles the same way (fused kernel path)
    full_k = np.asarray(
        prng.repeat_averaged_gaussian_tile(k0, k1, 0, 0, (16, 24), 3)
    )
    tile_k = np.asarray(
        prng.repeat_averaged_gaussian_tile(k0, k1, 4, 8, (8, 8), 3)
    )
    np.testing.assert_array_equal(tile_k, full_k[4:12, 8:16])
    # column shards partition the full draw exactly — the TP contract:
    # shard j at col0 = j * n_local reconstructs the unsharded stream
    shards = [
        np.asarray(prng.gaussian_tile(k0, k1, 0, j * 12, (16, 12)))
        for j in range(2)
    ]
    np.testing.assert_array_equal(np.concatenate(shards, axis=1), full)
    # and the weight-noise stream (its own salted key) tiles identically
    wk0 = k0 ^ np.uint32(prng.WEIGHT_STREAM_SALT)
    w_full = np.asarray(prng.gaussian_tile(wk0, k1, 0, 0, (8, 16)))
    w_tile = np.asarray(prng.gaussian_tile(wk0, k1, 0, 8, (8, 8)))
    np.testing.assert_array_equal(w_tile, w_full[:, 8:16])


# --------------------------------------------------------------------------
# the mesh cases, read from the child process
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def seen():
    src = os.path.join(os.path.dirname(HERE), "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([src, os.environ.get("PYTHONPATH", "")])}
    r = subprocess.run([sys.executable, os.path.abspath(__file__)], cwd=HERE,
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("family", ["dense", "griffin"])
def test_sharded_tokens_match_unsharded_oracle(seen, family):
    oracle, sharded = seen["golden"][family]
    assert oracle and sharded == oracle


def test_mesh_weights_stay_sharded(seen):
    w = seen["weights"]
    # every matrix of the layer stack, and the embedding, a quarter a device
    for name in MATRICES + ("embed",):
        assert w["quarter"][name], name
    # the rules split wo and w_down by rows; the engine re-lays them by columns
    for name in MATRICES:
        assert w["spec"][name] == [None, None, "model"], name
    assert w["spec"]["embed"] == ["model", None]
    assert sorted(w["relaid"]) == ["w_down", "wo"]
    # the sharded draw's other leaves are the engine's, not copies
    assert w["kept_same_buffers"]
    assert w["bytes_per_chip"] == w["bytes_expected"]


@pytest.mark.parametrize("tier", sorted(TP_TIERS))
def test_mesh_serves_one_devices_tokens_and_logits(seen, tier):
    one, four = seen["tiers"][tier]["one"], seen["tiers"][tier]["four"]
    assert one["tokens"] and four["tokens"] == one["tokens"]
    # prefill's last position, then one decode step through the cache. On
    # the CPU the whole sharded program fuses otherwise than one device's
    # and the logits differ in the last bits (about 1e-6; weights held whole
    # on every device read the same), so they agree to float32 rounding here
    np.testing.assert_allclose(four["logits"], one["logits"], rtol=0, atol=1e-5)


def test_no_sharded_dot_falls_back(seen):
    dots = seen["sharded_dots"]
    assert dots["fallback"] == []
    # q, k, v, o, gate, up, down: each traced column-parallel
    assert {s.split("_", 1)[1] for s in dots["column"]} == {
        "q", "k", "v", "o", "gate", "up", "out"}


def test_warm_mesh_engine_compiles_nothing_new(seen):
    warm = seen["warm"]
    assert warm["traces_before"] > 0
    assert warm["traces_after"] == warm["traces_before"]
    assert warm["misses_after"] == warm["misses_before"]
    assert warm["tokens"] == seen["tiers"]["k1"]["four"]["tokens"][0]


# -- the child process -----------------------------------------------------------


def _serve_tokens(cfg, env, mesh):
    """Serve a fixed trace (uniform K=2 + the non-uniform profile tier,
    explicit per-request noise keys) and return uid -> tokens."""
    profile = PrecisionProfile(PROFILES[cfg.name], name="nu")
    eng = ServingEngine(
        env["params"], cfg, analog_cfg=AnalogConfig.shot(backend="tile"),
        energies=env["energies"], max_gen=4, max_batch=2, max_wait=0.0,
        batch_buckets=(1, 2), seq_buckets=(SB,), k_ladder=(1, 2),
        profiles=[profile], mesh=mesh,
    )
    rng = np.random.default_rng(7)
    out = {}
    for i, tier in enumerate([2, "nu", "nu", 2]):
        prompt = rng.integers(0, cfg.vocab_size, 6 + 3 * i).astype(np.int32)
        uid = eng.submit(
            prompt, tier=tier, max_new_tokens=3,
            key=jax.random.fold_in(KEY, 100 + i),
        )
        out[i] = uid
    results = eng.flush()
    return {i: np.asarray(results[uid]).tolist() for i, uid in out.items()}


def _tp_engine(params, mesh):
    return ServingEngine(
        params, TP, analog_cfg=AnalogConfig.shot(backend="tile"),
        energies=init_energy_tree(TP, ENERGY_AJ), max_gen=4, max_batch=2,
        max_wait=0.0, batch_buckets=(2,), seq_buckets=(TP_SB,),
        continuous=True, pool_slots=2, k_ladder=(1, 8),
        profiles=[PrecisionProfile(PROFILES[TP.name], name="nu")], mesh=mesh,
    )


def _tp_prompts():
    rng = np.random.default_rng(11)
    return [rng.integers(0, TP.vocab_size, n).astype(np.int32) for n in (13, 9)]


def _serve_continuous(eng, tier, prompts):
    uids = [eng.submit(p, tier=tier, max_new_tokens=4, key=jax.random.fold_in(KEY, 200 + i))
            for i, p in enumerate(prompts)]
    done = {}
    while eng.n_in_flight:
        done.update(eng.pump_step(force=True))
    return [np.asarray(done[u]).tolist() for u in uids]


def _logits(eng, tier, prompt):
    """The tier's logits at the prompt's last position and at the next one
    (one decode step through the prefill's cache), from the engine's own
    weights and noise spec, under its mesh."""
    import jax.numpy as jnp

    from repro.models import lm

    t = eng.tiers.get(tier)
    tokens = np.zeros((1, TP_SB), np.int32)
    tokens[0, :prompt.size] = prompt
    lengths = jnp.asarray([prompt.size], jnp.int32)
    keys = jax.random.key_data(jax.random.fold_in(KEY, 300))[None]

    def f(params, tokens, lengths, keys):
        cache, h = lm.prefill(params, {"tokens": tokens}, TP, analog=t.analog_spec(keys),
                              cache_len=TP_SB + 4, lengths=lengths)
        first = lm.logits_last(params, h, TP)
        tok = jnp.argmax(first[:, 0, 0], axis=-1).astype(jnp.int32)
        nxt, _ = lm.decode_step(params, cache, {"tokens": tok[:, None]}, lengths, TP,
                                analog=t.analog_spec(keys, pos=lengths), lengths=lengths)
        return first, nxt

    with eng._mesh_ctx():
        first, nxt = jax.jit(f)(eng.params, jnp.asarray(tokens), lengths, keys)
    return [np.asarray(first).ravel().tolist(), np.asarray(nxt).ravel().tolist()]


def _name(path) -> str:
    return str(path[-1].key)


def child() -> dict:
    from repro.core.analog import SHARDED_DOTS
    from repro.models import lm, sharding

    out = {"golden": {}}
    for cfg in (DENSE, GRIFFIN):
        env = dict(params=init_params(KEY, cfg), energies=init_energy_tree(cfg, ENERGY_AJ))
        mesh = make_mesh_for_devices(4, model_parallel=4)
        out["golden"][cfg.family] = [_serve_tokens(cfg, env, None),
                                     _serve_tokens(cfg, env, mesh)]

    mesh = make_mesh_for_devices(4, model_parallel=4)
    rules = sharding.tree_shardings(lm.param_axes(TP), lm.param_specs(TP), mesh,
                                    sharding.DEFAULT_RULES)
    drawn = jax.jit(lm.init_params, static_argnums=1, out_shardings=rules)(KEY, TP)
    whole = jax.device_put(drawn, jax.devices()[0])
    SHARDED_DOTS.clear()
    four, one = _tp_engine(drawn, mesh), _tp_engine(whole, None)

    given = dict(jax.tree_util.tree_flatten_with_path(drawn)[0])
    held = jax.tree_util.tree_flatten_with_path(four.params)[0]
    quarter, spec, relaid, same = {}, {}, [], True
    for path, a in held:
        n = _name(path)
        quarter[n] = all(4 * s.data.size == a.size for s in a.addressable_shards)
        spec[n] = list(a.sharding.spec) + [None] * (a.ndim - len(a.sharding.spec))
        if given[path] is a:
            continue
        if given[path].sharding.is_equivalent_to(a.sharding, a.ndim):
            same = False  # a copy of a leaf that already lay right
        relaid.append(n)
    expected = sum(a.nbytes // (4 if quarter[_name(p)] else 1) for p, a in held)
    out["weights"] = dict(quarter=quarter, spec=spec, relaid=relaid, kept_same_buffers=same,
                          bytes_per_chip=four.weight_bytes_per_chip,
                          bytes_expected=int(expected))

    prompts = _tp_prompts()
    out["tiers"] = {}
    for name, tier in TP_TIERS.items():
        out["tiers"][name] = {
            side: dict(tokens=_serve_continuous(eng, tier, prompts),
                       logits=_logits(eng, tier, prompts[0]))
            for side, eng in (("one", one), ("four", four))}
    st = four.cache_stats()
    warm = dict(traces_before=four.trace_count, misses_before=st["misses"])
    warm["tokens"] = _serve_continuous(four, 1, prompts[:1])[0]
    warm.update(traces_after=four.trace_count, misses_after=four.cache_stats()["misses"])
    out["warm"] = warm
    out["sharded_dots"] = dict(
        column=sorted({k[1] for k in SHARDED_DOTS if k[0] == "column"}),
        fallback=sorted(f"{k[1]}: {k[2]}" for k in SHARDED_DOTS if k[0] == "fallback"))
    return out


if __name__ == "__main__":
    print(json.dumps(child()))
