"""A cell of four chips, driven end to end on four virtual CPU devices.

Each test starts this file as a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (a process that has
started JAX cannot be given more devices), which runs ``run.run`` on
``data/tiny.json`` as a cell of ``chips: 4`` and prints what it read as one
JSON line; the test checks it. The harness builds the program's
tensor-parallel mesh over the four devices, draws the weights in their
shards, hands the mesh to the engine, and runs the reference on it.

``tiny.json`` has 2 KV heads of 32: its K and V projections (64 columns)
still split four ways by size, and the leaves the rules leave whole (norm
scales) are accepted as such.
"""
import argparse
import contextlib
import io
import json
import os
import subprocess
import sys

import pytest

import bench_paths  # noqa: F401
import run

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
LIMIT = 0.06  # as test_bench_correct.py: sound runs read about 0.01, fp8 about 0.2
SEED = 2**31 + 41
#: every request sent when the window opens, none after: both runs of a
#: comparison serve the same requests, whatever the host's speed
CLOSED = {"loop": "closed", "closed": {"outstanding": 6, "requests": 6}}


def _child(case: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(bench_paths.ROOT, "src")}
    r = subprocess.run([sys.executable, os.path.abspath(__file__), case],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=bench_paths.ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_four_chip_cell_is_sharded_and_correct():
    out = _child("auto")
    assert out["devices"] == 4 and out["mesh"] == {"data": 1, "model": 4}
    assert out["engine_mesh"] == out["mesh"]
    # each stacked weight and the embedding split four ways; every other
    # leaf (norm scales) is one the rules replicate, whole on each chip
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "embed", "lm_head"):
        assert out["quarter"][name], name
    assert set(out["whole"]) == {"ln1_0", "ln2_0", "final_ln"}
    assert all(out["replicated_by_rule"][n] for n in out["whole"])
    assert out["on_cell_devices"]
    assert out["draw_equal"] and out["threefry_partitionable"]
    # the reference draws the same values, laid out as the program's
    assert out["ref_draw_equal"] and out["ref_layout_equal"]
    res = out["result"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert res["compared"]["logit_gap_max"]["value"] <= LIMIT
    assert res["device"]["count"] == 4
    # the CPU reports no memory: no peak, one entry per chip
    assert res["device"]["memory_peak_bytes"] is None
    assert res["device"]["memory_peak_bytes_by_chip"] == [None] * 4
    # on the mesh "auto" draws the counter stream at every shape; on one
    # chip the random stream below the fused kernel's size
    assert out["streams"] == {"mesh_decode": "counter", "mesh_prefill_cpu": "counter",
                              "one_chip_decode": "random"}


def test_four_chips_serve_one_chips_tokens():
    """Under the tile oracle (the counter stream on both sides) a four-chip
    run and a one-chip run of one seed serve the same tokens. Under "auto"
    on the CPU they would not: one device draws the random stream there."""
    out = _child("tile")
    one, four = out["one"], out["four"]
    assert one["count"] == 1 and four["count"] == 4
    assert one["correct"] and four["correct"]
    assert one["tokens"] and one["tokens"] == four["tokens"]
    assert abs(one["gap"] - four["gap"]) <= 1e-5


@pytest.mark.parametrize("stats, want", [
    ([None, None], (None, [None, None])),
    ([{}, {"bytes_in_use": 3}], (None, [None, None])),
    ([{"peak_bytes_in_use": 5}, None, {"peak_bytes_in_use": 9}, {"bytes_in_use": 1}],
     (9, [5, None, 9, None])),
])
def test_memory_peaks_over_reporting_chips(stats, want):
    class Dev:
        def __init__(self, s):
            self.s = s

        def memory_stats(self):
            return self.s

    assert run.memory_peaks([Dev(s) for s in stats]) == want


# -- the child process ---------------------------------------------------------


def _spec(chips: int, backend: str, mix: dict):
    import jax

    import peaks

    with open(os.path.join(DATA, "tiny.json")) as f:
        cfg = json.load(f)
    cfg["analog"]["backend"] = backend
    ref = run.load_module(os.path.join(bench_paths.CHIP, "reference", "dense_analog.py"),
                          "ref_dense_analog")
    peaks.PEAKS.setdefault(jax.devices()[0].device_kind,
                           dict(bf16_flops=1e12, hbm_bytes_s=1e11, hbm_bytes=1e10, source="test"))
    return dict(cell=dict(chips=chips), cfg=cfg, mix=mix, ref=ref,
                end_to_end=[dict(name="out_tok_s", unit="tokens/s")], per_layer=[],
                limits={"logit_gap_max": {"limit": LIMIT}})


def _mix(**over) -> dict:
    with open(os.path.join(DATA, "tiny_mix.json")) as f:
        return {**json.load(f), **over}


def _run(spec, seed: int, seen: dict) -> dict:
    import jax

    import harness

    build, drive = harness.Cell.build, harness.Cell.drive

    def on_build(cell):
        build(cell)
        seen["cell"] = cell

    def on_drive(cell, *a, **k):
        out = drive(cell, *a, **k)
        seen["tokens"] = {str(t.req.index): t.tokens.tolist()
                          for t in out["tracks"] if t.tokens is not None}
        return out

    harness.Cell.build, harness.Cell.drive = on_build, on_drive
    buf = io.StringIO()
    try:
        args = argparse.Namespace(workload="tiny", seed=seed, seconds=0.5, trace=0,
                                  control=None, keep_trace=None)
        with contextlib.redirect_stdout(buf):
            assert run.run(args, spec, jax.devices(), harness.CompileCounter()) == 0
    finally:
        harness.Cell.build, harness.Cell.drive = build, drive
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _leaves(tree) -> dict:
    import jax

    return {str(p[-1].key): v for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def child_auto() -> dict:
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec

    import harness
    from repro.models import lm

    spec = _spec(4, "auto", _mix())
    ref = spec["ref"]
    seen = {}

    build = harness.Cell.build

    def check_weights(cell):
        # read the sharded draw right after it is made, before the engine
        # replicates its own copy
        build(cell)
        mcfg = cell.engine.model_cfg
        want = harness.weight_shardings(mcfg, cell.mesh)
        one = jax.jit(lm.init_params, static_argnums=1)(harness.weight_key(cell.seed), mcfg)
        got, rule, ones = _leaves(cell.params), _leaves(want), _leaves(one)
        seen["quarter"] = {n: all(4 * s.data.size <= a.size for s in a.addressable_shards)
                           for n, a in got.items()}
        seen["whole"] = sorted(n for n, q in seen["quarter"].items() if not q)
        seen["replicated_by_rule"] = {n: rule[n].spec == PartitionSpec(*[None] * got[n].ndim)
                                      for n in seen["whole"]}
        seen["on_cell_devices"] = all(a.sharding.device_set == set(cell.devices)
                                      for a in got.values())
        seen["draw_equal"] = all(np.array_equal(np.asarray(got[n]), np.asarray(ones[n]))
                                 for n in got)
        key = harness.weight_key(cell.seed)
        rw = _leaves(ref.init_weights(key, spec["cfg"], mesh=cell.mesh))
        rone = _leaves(ref.init_weights(key, spec["cfg"]))
        seen["ref_draw_equal"] = set(rw) == set(rone) and all(
            np.array_equal(np.asarray(rw[n]), np.asarray(rone[n])) for n in rw)
        seen["ref_layout_equal"] = set(rw) == set(got) and all(
            rw[n].sharding.spec == got[n].sharding.spec for n in rw)
        seen["mesh"] = dict(cell.mesh.shape)
        seen["engine_mesh"] = dict(cell.engine.mesh.shape)

    harness.Cell.build = check_weights
    try:
        res = _run(spec, SEED, seen)
    finally:
        harness.Cell.build = build
    return dict(
        devices=len(jax.devices()), result=res,
        threefry_partitionable=bool(jax.config.jax_threefry_partitionable),
        streams=dict(mesh_decode=ref.stream_for("tpu", "auto", 1, 4096, 4096, 4),
                     mesh_prefill_cpu=ref.stream_for("cpu", "auto", 512, 4096, 4096, 4),
                     one_chip_decode=ref.stream_for("tpu", "auto", 1, 4096, 4096)),
        **{k: v for k, v in seen.items() if k not in ("cell", "tokens")})


def child_tile() -> dict:
    out = {}
    for name, chips in (("one", 1), ("four", 4)):
        seen = {}
        res = _run(_spec(chips, "tile", _mix(**CLOSED)), SEED, seen)
        out[name] = dict(count=res["device"]["count"], correct=res["correct"],
                         gap=res["compared"]["logit_gap_max"]["value"],
                         tokens=seen["tokens"])
    return out


if __name__ == "__main__":
    print(json.dumps({"auto": child_auto, "tile": child_tile}[sys.argv[1]]()))
