#!/usr/bin/env python3
"""Smoke test of the analog serving engine on a TPU, at published widths.

One chip (the default): granite-3-8b at its published widths (d_model 4096,
32 heads with 8 KV heads, head_dim 128, d_ff 12800, vocab 49155), cut to 16
of its 40 layers, with random weights drawn from ``--seed``. Four prompts of
128-512 tokens are served in each of three tiers -- uniform K=1 and K=8
shot-noise analog, and int8 weight-only digital -- through the continuous
``ServingEngine``, 16 new tokens per request. The run fails unless

  * every request returns all 16 tokens and no executable errored,
  * each analog tier's prefill program holds a ``tpu_custom_call`` (the
    fused Pallas kernel ran compiled, not in interpret mode),
  * serving the same traffic again returns the same tokens, and
  * a request served solo returns the tokens it got inside the batch.

``--chips 4`` runs only the tensor-parallel path: the weights are drawn in
their shards on a 1x4 ("data", "model") mesh (the tensor-parallel rules, as
the chip benchmark draws them), the K=1 and K=8 requests of the two
512-bucket prompts are served unsharded on device 0 from a whole copy, then
on the mesh from the shards, and the run fails unless the tokens are
identical, any analog dot fell back to the whole product, or the mesh
engine holds a weight whole. Both runs pin the analog
backend to the Pallas kernel: under "auto" a decode matmul resolves to the
jnp path unsharded but to the tile oracle under a mesh, and those draw
different noise streams by design (kernels/dispatch.py).

Earlier lines report set-up facts (device, compile and serve seconds, peak
device memory); the last line of stdout is one JSON object naming the
device. Without a TPU the script exits non-zero and prints no such line.

Run:  python chip_smoke.py [--chips 4] [--seed 0]
"""
import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import numpy as np  # noqa: E402

LAYERS = 16  # of granite-3-8b's 40: the depth cut that fits one 16 GB chip
PROMPT_LENS = (512, 384, 200, 128)  # seq buckets 512, 512, 256, 128
SEQ_BUCKETS = (128, 256, 512)
NEW_TOKENS = 16
ENERGY_AJ = 10.0  # per-MAC energy at K=1


def fail(msg: str) -> None:
    print(f"chip_smoke FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def model_config():
    from repro.configs import granite_3_8b

    return dataclasses.replace(granite_3_8b.CONFIG, n_layers=LAYERS)


def reckon_bytes(cfg) -> tuple:
    """(bf16 weight bytes, int8-tier copy bytes), from shapes alone."""
    import jax

    from repro.models import lm
    from repro.quant.weights import quantize_params

    specs = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.PRNGKey(0))
    qspecs = jax.eval_shape(quantize_params, specs)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    return nbytes(specs), nbytes(qspecs)


def make_traffic(cfg, seed: int):
    """Prompts and per-request PRNG keys, drawn from ``seed``."""
    import jax

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32) for n in PROMPT_LENS]
    base = jax.random.PRNGKey(seed + 1)
    keys = [jax.random.fold_in(base, i) for i in range(len(prompts))]
    return prompts, keys


def make_engine(params, cfg, *, backend="auto", mesh=None):
    from repro.core import AnalogConfig
    from repro.models import lm
    from repro.serving import ServingEngine

    return ServingEngine(
        params, cfg,
        analog_cfg=AnalogConfig.shot(backend=backend),
        energies=lm.init_energy_tree(cfg, ENERGY_AJ),
        max_gen=NEW_TOKENS, max_batch=4, batch_buckets=(1, 2, 4),
        seq_buckets=SEQ_BUCKETS, continuous=True, pool_slots=4, mesh=mesh,
    )


def serve(engine, tiers, prompts, keys) -> dict:
    """Submit every prompt in every tier, drain, and check each result is
    a full row of tokens. Returns {(tier, prompt index): tokens}."""
    uids = {}
    for tier in tiers:
        for i, (prompt, key) in enumerate(zip(prompts, keys)):
            uids[(tier, i)] = engine.submit(
                prompt, tier=tier, max_new_tokens=NEW_TOKENS, key=key
            )
    results = engine.flush()
    out = {}
    for tk, uid in uids.items():
        res = results.get(uid)
        check(isinstance(res, np.ndarray), f"request {tk}: {res!r}")
        check(res.dtype == np.int32 and res.shape == (NEW_TOKENS,),
              f"request {tk}: {res.dtype} {res.shape}, want int32 ({NEW_TOKENS},)")
        out[tk] = res
    s = engine.stats
    check(s["exe_errors"] == 0 and s["exe_faults"] == 0,
          f"exe_errors={s['exe_errors']} exe_faults={s['exe_faults']}")
    return out


def peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def run_one_chip(cfg, seed: int) -> None:
    import jax

    from repro.models import lm
    from repro.serving import Int8DigitalTier

    t0 = time.perf_counter()
    params = jax.jit(lm.init_params, static_argnums=1)(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    print(f"weights initialized from seed {seed} in {time.perf_counter() - t0:.1f} s")

    engine = make_engine(params, cfg)
    int8 = engine.register_tier(Int8DigitalTier())
    tiers = (1, 8, int8)
    prompts, keys = make_traffic(cfg, seed)

    t0 = time.perf_counter()
    cold = serve(engine, tiers, prompts, keys)
    cold_s = time.perf_counter() - t0
    compile_s = engine.cache_stats()["compile_s"]
    t0 = time.perf_counter()
    warm = serve(engine, tiers, prompts, keys)
    warm_s = time.perf_counter() - t0
    n_tok = sum(r.size for r in warm.values())
    print(f"served {len(warm)} requests x {NEW_TOKENS} tokens in tiers {tiers}: "
          f"first pass {cold_s:.1f} s ({compile_s:.1f} s compiling "
          f"{engine.cache_stats()['entries']} executables), warm pass "
          f"{warm_s:.3f} s for {n_tok} tokens")
    check(all(np.array_equal(cold[k], warm[k]) for k in cold),
          "the warm pass returned different tokens from the first")

    for tier in (1, 8):
        exe = engine.exe_cache.lookup(engine.tiers.exe_key(
            "prefill", tier, 2, max(SEQ_BUCKETS), engine.pool_cache_len))
        check(exe is not None, f"no K={tier} prefill executable at (2, 512)")
        n_calls = exe.as_text().count("tpu_custom_call")
        print(f"K={tier} prefill program: {n_calls} tpu_custom_call site(s)")
        check(n_calls > 0, f"K={tier} prefill ran no compiled Pallas kernel")
    from repro.kernels.analog_matmul import NOISE_PLANS

    print("output-noise plans of the traced kernels ((rows, steps) or finish): "
          + ", ".join(f"{plan}: {n}" for plan, n in sorted(NOISE_PLANS.items(), key=str)))

    uid = engine.submit(prompts[0], tier=1, max_new_tokens=NEW_TOKENS, key=keys[0])
    solo = engine.flush()[uid]
    same = np.array_equal(solo, warm[(1, 0)])
    print(f"solo vs batched (K=1, {PROMPT_LENS[0]}-token prompt): "
          f"{'identical' if same else 'DIFFERENT'}")
    check(same, f"solo {solo.tolist()} != batched {warm[(1, 0)].tolist()}")

    for tier in (1, 8):
        agree = np.mean([np.mean(warm[(tier, i)] == warm[(int8, i)])
                         for i in range(len(prompts))])
        print(f"token agreement K={tier} analog vs int8: {agree:.3f}")
    print(f"peak_bytes_in_use: {peak_gb(jax.devices()[0])}")


def placement(tree) -> str:
    import jax

    devs = set()
    for leaf in jax.tree.leaves(tree):
        devs |= {d.id for d in leaf.sharding.device_set}
    return "devices " + ",".join(str(d) for d in sorted(devs))


def run_four_chips(cfg, seed: int) -> None:
    import jax

    from repro.core.analog import SHARDED_DOTS
    from repro.launch.mesh import make_mesh_for_devices
    from repro.models import lm, sharding

    mesh = make_mesh_for_devices(4, model_parallel=4)
    dev0 = jax.devices()[0]
    t0 = time.perf_counter()
    params = jax.jit(
        lm.init_params, static_argnums=1,
        out_shardings=sharding.tree_shardings(
            lm.param_axes(cfg), lm.param_specs(cfg), mesh, sharding.DEFAULT_RULES),
    )(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    print(f"weights initialized in their shards from seed {seed} in "
          f"{time.perf_counter() - t0:.1f} s; mesh {dict(mesh.shape)} over "
          f"devices {[d.id for d in mesh.devices.flat]}")
    # the two prompts of the top seq bucket: one prefill shape per tier
    prompts, keys = (v[:2] for v in make_traffic(cfg, seed))
    tiers = (1, 8)

    out = {}
    for name, build in (
        ("unsharded", lambda: make_engine(jax.device_put(params, dev0), cfg, backend="pallas")),
        ("sharded", lambda: make_engine(params, cfg, backend="pallas", mesh=mesh)),
    ):
        engine = build()
        t0 = time.perf_counter()
        out[name] = serve(engine, tiers, prompts, keys)
        pools = {t: p.cache for t, p in engine.pools.items()}
        print(f"{name}: served {len(out[name])} requests in "
              f"{time.perf_counter() - t0:.1f} s "
              f"({engine.cache_stats()['compile_s']:.1f} s compiling); weights "
              f"on {placement(engine.params)}, {engine.weight_bytes_per_chip / 1e9:.3f} GB "
              f"a chip; pool caches on {placement(pools)}")
        if engine.mesh is not None:
            whole = [jax.tree_util.keystr(p) for p, a in
                     jax.tree_util.tree_flatten_with_path(engine.params)[0]
                     if a.ndim == 3 and a.sharding.is_fully_replicated]
            check(not whole, f"the mesh engine holds {whole} whole")
        del engine, pools
    fallbacks = {k: n for k, n in SHARDED_DOTS.items() if k[0] == "fallback"}
    print(f"sharded dots: {sum(n for k, n in SHARDED_DOTS.items() if k[0] == 'column')} "
          f"column-parallel, {sum(fallbacks.values())} fallbacks")
    check(not fallbacks, f"analog dots fell back to the whole product: {fallbacks}")
    n_same = 0
    for k, a in out["unsharded"].items():
        b = out["sharded"][k]
        same = np.array_equal(a, b)
        n_same += same
        print(f"  request (K={k[0]}, prompt {k[1]}): "
              + ("identical" if same else f"first differs at token {int(np.argmax(a != b))}"))
    print(f"sharded vs unsharded tokens: {n_same}/{len(out['unsharded'])} "
          "requests identical")
    check(n_same == len(out["unsharded"]), "sharded tokens differ from unsharded")
    for d in jax.devices():
        print(f"device {d.id} peak_bytes_in_use: {peak_gb(d)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # run from a checkout: never pick up another installed copy of the code
    check(os.path.isdir(os.path.join(ROOT, "src", "repro")),
          f"no src/repro next to {__file__}: run from a checkout of the repo")

    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"JAX found no TPU (platform {devices[0].platform!r})")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}")
    print(f"device_kind: {devices[0].device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache_dir}")

    cfg = model_config()
    w_bytes, q_bytes = reckon_bytes(cfg)
    print(f"config: {cfg.name} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, head_dim {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); depth cut 40 -> "
          f"{cfg.n_layers} layers; reckoned {w_bytes / 1e9:.2f} GB bf16 weights "
          f"+ {q_bytes / 1e9:.2f} GB int8-tier copy")

    if args.chips == 4:
        run_four_chips(cfg, args.seed)
    else:
        run_one_chip(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
