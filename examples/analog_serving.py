"""Analog serving: the paper's deployment scenario as a serving client.

Two modes:

  default   — side-by-side digital vs analog generation on one batch: every
              matmul runs the analog path under shot noise with per-site
              energies; reports token agreement and optical energy/token.

  --traffic — replays a synthetic *mixed-precision* load through the
              bucket-batched serving engine (repro.serving): requests with
              random prompt lengths, heterogeneous decode budgets, and
              dynamic-precision tiers (K = 1/2/4 analog repeats) are
              tier-grouped, padded into power-of-two buckets, and served
              through AOT-compiled executables. Prints per-tier
              token/energy accounting and the executable-cache hit/miss
              counters (steady state re-traces nothing). Add --continuous
              to decode through persistent per-tier slot pools (in-flight
              admission, early retirement) instead of run-to-completion
              batches.

              admission, early retirement) instead of run-to-completion
              batches. Add --slo SECONDS to attach the SLA-aware precision
              governor: every request carries a latency SLO and a random
              accuracy floor, the middle of the replay arrives as a 3x
              burst, and the governor demotes/promotes precision tiers
              against live queue pressure (policy events are printed).
              Add --dashboard to attach the streaming MetricsFeed
              (serving/monitor.py) and render a compact per-tier dashboard
              — tokens/s, queue depth, pool occupancy — from the sampled
              ring after the replay (samples also stream to a JSONL file).

  --cluster — replicated serving: 3 engine replicas behind the
              ClusterRouter (repro.serving.cluster), replica 0 crashes
              mid-burst, the heartbeat detector declares it dead, and the
              request journal re-dispatches its queued + in-flight work to
              the survivors — re-served streams verified bit-identical to
              the prefixes already emitted (per-request PRNG keys make
              tokens replica-independent), nothing lost, nothing
              re-emitted.

Run:  PYTHONPATH=src python examples/analog_serving.py [--energy 10.0]
      PYTHONPATH=src python examples/analog_serving.py --traffic \
          [--requests 24] [--gen 8] [--continuous] [--slo 2.0] [--dashboard]
      PYTHONPATH=src python examples/analog_serving.py --cluster \
          [--requests 24] [--gen 8]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PHOTON_ENERGY_AJ, AnalogConfig, total_energy
from repro.models import (
    AnalogSpec,
    decode_step,
    energy_macs,
    init_energy_tree,
    init_params,
    prefill,
)
from repro.models.config import ModelConfig
from repro.data.pipeline import TokenTaskConfig, markov_batch
from repro.serving import (
    ClusterRouter,
    MetricsFeed,
    PolicyConfig,
    ReplicaCrash,
    ServingEngine,
    TierSpec,
    TimedOut,
)
from repro.runtime.compile_cache import enable_compile_cache

CFG = ModelConfig(
    name="serve-demo", family="dense", n_layers=4, d_model=256, n_heads=8,
    n_kv_heads=4, d_ff=1024, vocab_size=4096, attn_q_chunk=128,
    attn_kv_chunk=128, loss_chunk=128, dtype="float32",
)


def _trained_params():
    """Briefly pre-train on the Markov task (cached under /tmp)."""
    import os
    import tempfile

    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import TrainConfig
    from repro.runtime.driver import DriverConfig, TrainDriver

    data = TokenTaskConfig(vocab_size=CFG.vocab_size, seq_len=128, global_batch=8, seed=7)
    ckpt = os.path.join(tempfile.gettempdir(), "repro_serve_demo")
    driver = TrainDriver(
        CFG, data, make_local_mesh(), ckpt_dir=ckpt,
        train_cfg=TrainConfig(lr=1e-3, opt_state_dtype="float32"),
        driver_cfg=DriverConfig(max_steps=80, ckpt_every=40, ckpt_async=False),
    )
    out = driver.run()
    if out["metrics"]:  # empty when a cached checkpoint already hit max_steps
        print(f"pre-trained to loss {out['metrics'][-1]['loss']:.3f}")
    else:
        print("restored pre-trained checkpoint")
    return out["state"]["params"]


def _tier_agreement(params, energies, ks):
    """Greedy-token-agreement accuracy stand-in per uniform-K tier: the
    metadata the governor's demotion floors are enforced against."""
    from repro.core import PrecisionProfile
    from repro.models import lm

    key = jax.random.PRNGKey(5)
    toks = jax.random.randint(key, (2, 32), 0, CFG.vocab_size)
    head = params["embed"].T if CFG.tie_embeddings else params["lm_head"]

    def greedy(analog):
        h, _ = lm.forward_hidden(
            params, {"tokens": toks}, CFG, mode="train", analog=analog
        )
        return np.asarray(jnp.argmax(jnp.matmul(h, head), axis=-1))

    ref = greedy(None)
    out = {}
    for k in ks:
        spec = AnalogSpec(
            cfg=AnalogConfig.shot(), energies=energies, key=key,
            profile=PrecisionProfile.uniform(k, CFG.n_layers),
        )
        out[k] = float((greedy(spec) == ref).mean())
    return out


def run_traffic(args, params):
    """Replay a mixed-precision load through the serving engine."""
    tiers, weights = (1, 2, 4), (0.5, 0.3, 0.2)
    profiles = []
    if args.profile:
        from repro.serving import PrecisionProfile

        schedule = tuple(int(k) for k in args.profile.split(","))
        profiles = [PrecisionProfile(schedule, name="cli")]
        # route a slice of traffic to the per-layer profile tier
        tiers, weights = (1, 2, 4, "cli"), (0.4, 0.25, 0.15, 0.2)
    energies = init_energy_tree(CFG, args.energy)
    policy, accs = None, {}
    if args.slo is not None:
        accs = _tier_agreement(params, energies, (1, 2, 4))
        print(f"tier agreement vs digital: "
              + ", ".join(f"K={k}: {a:.3f}" for k, a in sorted(accs.items())))
        policy = PolicyConfig(
            tiers=tuple(TierSpec(k, accs[k]) for k in (1, 2, 4)),
            demote_at=1.0, promote_at=0.25, shed_at=6.0, min_dwell=2,
        )
    seq_buckets = [32]
    while seq_buckets[-1] < args.prompt_len:
        seq_buckets.append(seq_buckets[-1] * 2)
    feed = None
    if args.dashboard:
        import os
        import tempfile

        feed = MetricsFeed(
            capacity=4096,
            jsonl_path=os.path.join(tempfile.gettempdir(),
                                    "repro_serving_metrics.jsonl"),
        )
    engine = ServingEngine(
        params, CFG, analog_cfg=AnalogConfig.shot(backend=args.backend),
        energies=energies, max_gen=args.gen, max_batch=8, max_wait=0.5,
        batch_buckets=(1, 2, 4, 8), seq_buckets=tuple(seq_buckets),
        profiles=profiles, continuous=args.continuous, policy=policy,
        metrics=feed,
    )
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        length = int(rng.integers(8, args.prompt_len + 1))
        k = rng.choice(np.asarray(tiers, dtype=object), p=weights)
        # heterogeneous decode budgets: where continuous batching pays off
        # (run-to-completion decodes every row to the batch max)
        gen = int(rng.choice([max(1, args.gen // 8), max(1, args.gen // 2), args.gen]))
        reqs.append((rng.integers(0, CFG.vocab_size, length),
                     k if isinstance(k, str) else int(k), gen))

    t0 = time.perf_counter()
    uid_tier, results = {}, {}
    t = 0.0
    for i, (prompt, k, gen) in enumerate(reqs):
        tier_kw = {"profile": k} if isinstance(k, str) else {"n_repeats": k}
        slo_kw = {}
        if args.slo is not None:
            # the middle third of the replay arrives as a 3x burst; each
            # request carries the SLO and a random accuracy floor
            t += 1e-3 / 3 if args.requests // 3 <= i < 2 * args.requests // 3 else 1e-3
            floor = (None, accs[2], accs[4])[rng.choice(3, p=(0.5, 0.3, 0.2))]
            slo_kw = {"target_latency": args.slo, "accuracy_floor": floor}
        else:
            t = i * 1e-3
        uid = engine.submit(prompt, max_new_tokens=gen, now=t, **tier_kw, **slo_kw)
        uid_tier[uid] = k
        results.update(engine.poll(now=t))
    while engine.n_in_flight:  # drain on the virtual clock (governor live)
        t += 1e-2
        results.update(
            engine.pump_step(now=t) if args.continuous else engine.poll(now=t)
        )
    wall = time.perf_counter() - t0
    timed_out = {u for u, r in results.items() if isinstance(r, TimedOut)}
    results = {u: r for u, r in results.items() if u not in timed_out}

    total_toks = sum(len(v) for v in results.values())
    print(f"replayed {args.requests} requests ({total_toks} tokens) "
          f"in {wall:.2f}s -> {total_toks / wall:.1f} tok/s "
          f"[backend={args.backend}]")
    for k in tiers:
        uids = [u for u, t in uid_tier.items() if t == k]
        toks = sum(len(results[u]) for u in uids if u in results)
        # true per-tier spend: sum_l K_l * E_l * MACs_l (lm_head is digital)
        e_tok = engine.tier_energy_per_token(k)
        label = f"K={k}" if not isinstance(k, str) else (
            f"profile {k}={list(engine.profiles[k].repeats)}"
        )
        print(f"  tier {label}: {len(uids):>3} requests, {toks:>4} tokens, "
              f"{e_tok / 1e6:.3f} pJ/token "
              f"({e_tok / PHOTON_ENERGY_AJ:.2e} photons)")
    cs = engine.cache_stats()
    print(f"executables: {cs['entries']} compiled ({cs['compile_s']:.1f}s), "
          f"{cs['hits']} hits / {cs['misses']} misses; batches="
          f"{engine.stats['batches']} padded_rows={engine.stats['padded_rows']}")
    if args.continuous:
        s = engine.stats
        active = s["active_slot_steps"] / max(1, s["decode_slot_steps"])
        print(f"continuous: {len(engine.pools)} tier pool(s) x "
              f"{engine.pool_slots} slots, {s['admitted']} admitted / "
              f"{s['retired']} retired in-flight, {s['decode_steps']} pool "
              f"steps ({s['decode_slot_steps']} row-slots, "
              f"{active:.0%} occupancy)")
    if engine.governor is not None:
        gov, s = engine.governor, engine.stats
        served = {}  # tokens by the tier each request was SERVED at
        for uid, toks in results.items():
            tier = engine.served_tiers.get(uid, uid_tier[uid])
            served[tier] = served.get(tier, 0) + len(toks)
        total = sum(served.values())
        blended = sum(
            n * engine.tier_energy_per_token(tier) for tier, n in served.items()
        ) / max(1, total)
        print(f"governor: mode={gov.mode} demoted={s['demoted']} "
              f"promoted_back={s['promoted_back']} shed={s['shed']} "
              f"timed_out={len(timed_out)} "
              f"transitions={s['policy_transitions']}")
        print(f"  served tier mix {dict(sorted(served.items(), key=str))} -> "
              f"blended {blended / 1e6:.3f} pJ/token")
        for e in gov.events:
            print(f"  [{e.kind:>8}] policy step {e.step} pressure="
                  f"{e.pressure:.2f} queue={e.queue_depth} moved={e.moved} "
                  f"{e.detail}")
    if feed is not None:
        _render_dashboard(feed, engine)
    sample = results[min(results)]
    print("sample tokens:", sample[:12].tolist())


def run_cluster(args, params):
    """Replicated serving demo: 3 data-parallel replicas behind a
    ClusterRouter, with replica 0 crashing mid-burst. The router's health
    detector discovers the death through the stalled MetricsFeed
    heartbeat, journal replay re-dispatches the orphaned requests to the
    survivors, and — because every request carries its own stacked PRNG
    key — the re-served streams are verified bit-identical against the
    prefixes the dead replica had already emitted (deduped, never
    re-emitted)."""
    energies = init_energy_tree(CFG, args.energy)
    seq_buckets = [32]
    while seq_buckets[-1] < args.prompt_len:
        seq_buckets.append(seq_buckets[-1] * 2)

    def make_engine():
        return ServingEngine(
            params, CFG, analog_cfg=AnalogConfig.shot(backend=args.backend),
            energies=energies, max_gen=args.gen, max_batch=4, max_wait=0.0,
            batch_buckets=(1, 2, 4), seq_buckets=tuple(seq_buckets),
            continuous=True, pool_slots=4, k_ladder=(1, 2, 4),
        )

    # the crash lands on round 1, while replica 0 still holds its share of
    # the up-front burst: queued rows re-dispatch, decoding rows re-serve
    cluster = ClusterRouter(
        [make_engine() for _ in range(3)], seed=0,
        suspect_after=2, dead_after=4, backoff_rounds=1, backoff_jitter=0,
        faults=(ReplicaCrash(replica=0, at=1),),
    )
    rng = np.random.default_rng(0)
    reqs = [
        (rng.integers(0, CFG.vocab_size, int(rng.integers(8, args.prompt_len + 1))),
         int(rng.choice((1, 2, 4), p=(0.5, 0.3, 0.2))),
         int(rng.choice([max(1, args.gen // 8), max(1, args.gen // 2)])))
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    results, t, submitted = {}, 0.0, 0
    # half the burst lands up front, the rest trickles in 2 per round —
    # the crash at round 4 hits with queued AND decoding work on replica 0
    for prompt, k, gen in reqs[: len(reqs) // 2]:
        cluster.submit(prompt, tier=k, max_new_tokens=gen, now=t)
        submitted += 1
    while cluster.n_in_flight or submitted < len(reqs):
        t += 1e-2
        for prompt, k, gen in reqs[submitted:submitted + 2]:
            cluster.submit(prompt, tier=k, max_new_tokens=gen, now=t)
            submitted += 1
        results.update(cluster.pump_step(now=t))
    wall = time.perf_counter() - t0

    s = cluster.stats
    total_toks = sum(len(v) for v in results.values())
    print(f"cluster: 3 replicas, crash injected at round 1; replayed "
          f"{len(reqs)} requests ({total_toks} tokens) in {wall:.2f}s")
    print(f"health: {cluster.health}")
    for ev in cluster.events:
        if ev["kind"] in ("crash_injected", "health", "failover"):
            desc = {
                "crash_injected": f"replica {ev.get('replica')} crashed",
                "health": (f"replica {ev.get('replica')} "
                           f"{ev.get('frm')} -> {ev.get('to')}: "
                           f"{ev.get('detail')}"),
                "failover": (f"replica {ev.get('replica')} orphaned "
                             f"{len(ev.get('uids', ()))} request(s); "
                             f"re-dispatch at round {ev.get('retry_round')}"),
            }[ev["kind"]]
            print(f"  [round {ev.get('round'):>3}] {desc}")
    print(f"failover: {s['failed_over']} orphaned, {s['redispatched']} "
          f"re-dispatched, {s['dedup_tokens']} already-streamed tokens "
          f"verified + deduped, {s['prefix_mismatches']} prefix mismatches")
    per = cluster.replica_stats()
    print(f"{'replica':>8} {'state':>8} {'heartbeat':>10} {'requests':>9} "
          f"{'tokens':>7}")
    for r in per:
        print(f"{r['replica_id']:>8} {r['state']:>8} "
              f"{r['heartbeat_step']:>10} {r['requests']:>9} "
              f"{r['tokens_generated']:>7}")
    lost = len(reqs) - len(results)
    assert lost == 0 and s["prefix_mismatches"] == 0, (
        f"failover contract broken: lost={lost} "
        f"mismatches={s['prefix_mismatches']}"
    )
    print(f"zero lost requests; every re-served stream bit-identical. "
          f"delivered={s['delivered']} failed={s['failed']}")


def _sparkline(values, width=48):
    """Unicode mini-chart of a numeric series (None plotted as 0)."""
    vals = [0.0 if v is None else float(v) for v in values]
    if len(vals) > width:  # downsample: mean over equal chunks
        step = len(vals) / width
        vals = [
            float(np.mean(vals[int(i * step):max(int(i * step) + 1,
                                                 int((i + 1) * step))]))
            for i in range(width)
        ]
    blocks = " .:-=+*#%@"
    hi = max(vals) or 1.0
    return "".join(blocks[min(len(blocks) - 1,
                              int(v / hi * (len(blocks) - 1)))] for v in vals)


def _render_dashboard(feed, engine):
    """Compact per-tier dashboard rendered from the MetricsFeed ring:
    token throughput per tier over pump steps, queue depth, and pool
    occupancy — the same samples the JSONL sink streams for offline
    dashboards."""
    samples = feed.samples()
    if not samples:
        print("dashboard: no samples recorded")
        return
    print(f"--- dashboard ({len(samples)} retained samples, "
          f"jsonl: {feed.jsonl_path}) ---")
    deltas = feed.tier_series("tokens_delta")
    for tier in sorted(deltas, key=str):
        series = deltas[tier]
        total = samples[-1]["tiers"][tier]["tokens"]
        e = samples[-1]["tiers"][tier]["energy_per_token_aj"]
        e_txt = "n/a" if e is None else f"{e / 1e6:.3f} pJ/tok"
        print(f"  tier {tier:>8} |{_sparkline(series)}| "
              f"{total:>5} tokens, {e_txt}")
    print(f"  queue depth   |{_sparkline([s['queue_depth'] for s in samples])}| "
          f"peak {max(s['queue_depth'] for s in samples)}")
    occ = [s["occupancy"] for s in samples]
    if any(occ):
        print(f"  pool occupancy|{_sparkline(occ)}| "
              f"peak {max(occ):.0%}")
    feed.close()


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--energy", type=float, default=10.0, help="aJ per MAC")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--backend", default="auto", choices=["auto", "pallas", "jnp"],
                    help="matmul backend (pallas = fused kernel; interpret on CPU)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="dynamic-precision K: repeat each analog op K times "
                         "and average (fused in-kernel on pallas)")
    ap.add_argument("--traffic", action="store_true",
                    help="replay a mixed-precision load through the "
                         "bucket-batched serving engine")
    ap.add_argument("--continuous", action="store_true",
                    help="decode through persistent per-tier slot pools "
                         "(in-flight admission + early retirement) instead "
                         "of run-to-completion batches (--traffic mode)")
    ap.add_argument("--requests", type=int, default=24,
                    help="number of requests in --traffic mode")
    ap.add_argument("--slo", type=float, default=None,
                    help="per-request latency SLO in virtual seconds: attach "
                         "the SLA-aware precision governor, replay the middle "
                         "third as a 3x burst, and print policy events "
                         "(--traffic mode)")
    ap.add_argument("--profile", default=None,
                    help="comma-separated per-layer K schedule (e.g. 4,2,1,1)"
                         " served as its own precision tier in --traffic mode")
    ap.add_argument("--cluster", action="store_true",
                    help="replicated serving demo: 3 engine replicas behind "
                         "the ClusterRouter, replica 0 crashes mid-burst, "
                         "health-checked failover re-dispatches its requests "
                         "bit-identically to the survivors")
    ap.add_argument("--dashboard", action="store_true",
                    help="attach the streaming MetricsFeed and render a "
                         "compact per-tier dashboard (tokens/s, queue depth, "
                         "pool occupancy) after the replay; samples are also "
                         "streamed to a JSONL file (--traffic mode)")
    args = ap.parse_args()

    if args.cluster:
        run_cluster(args, _trained_params())
        return
    if args.traffic:
        run_traffic(args, _trained_params())
        return

    key = jax.random.PRNGKey(0)
    params = _trained_params()  # untrained logits are near-ties: noise flips argmax
    data = TokenTaskConfig(vocab_size=CFG.vocab_size, seq_len=args.prompt_len,
                           global_batch=args.batch, seed=11)
    prompts = jnp.asarray(markov_batch(data, 0)["tokens"])

    energies = init_energy_tree(CFG, args.energy)
    analog = AnalogSpec(
        cfg=AnalogConfig.shot(backend=args.backend), energies=energies, key=key,
        n_repeats=args.repeats,
    )
    cache_len = args.prompt_len + args.gen

    # --- analog and digital generations side by side ------------------------
    outs = {}
    for mode, aspec in (("digital", None), ("analog", analog)):
        cache, h_last = prefill(params, {"tokens": prompts}, CFG,
                                analog=aspec, cache_len=cache_len)
        from repro.models import lm
        logits = lm.logits_last(params, h_last, CFG)
        toks = []
        tok = jnp.argmax(logits[:, 0, 0], axis=-1)[:, None]
        step_fn = jax.jit(
            lambda p, c, t, pos: decode_step(p, c, {"tokens": t}, pos, CFG, analog=aspec)
        )
        for i in range(args.gen):
            toks.append(tok)
            logits, cache = step_fn(params, cache, tok, args.prompt_len + i)
            tok = jnp.argmax(logits[:, 0, 0], axis=-1)[:, None]
        outs[mode] = jnp.concatenate(toks, axis=1)

    agree = float(jnp.mean(outs["digital"] == outs["analog"]))
    macs = energy_macs(CFG, 1)  # per generated token
    e_tot = float(total_energy(energies, macs)) * args.repeats
    print(f"generated {args.gen} tokens x {args.batch} sequences "
          f"[backend={args.backend}, K={args.repeats}]")
    print(f"digital vs analog token agreement: {agree:.1%} at {args.energy} aJ/MAC")
    print(f"optical energy per generated token: {e_tot/1e6:.3f} pJ "
          f"({e_tot / PHOTON_ENERGY_AJ:.2e} photons)")
    print("sample (digital):", outs["digital"][0, :12].tolist())
    print("sample (analog): ", outs["analog"][0, :12].tolist())


if __name__ == "__main__":
    main()
