"""Drive the serving engine for one cell: set-up, warm-up, window, drain.

The engine is used through its public entry points only: ``submit`` and
``pump_step`` in continuous mode. The harness reads, and never writes, the
engine's counters (``stats``, ``cache_stats()``) and its pool records (the
tokens each slot has emitted) to stamp token times on the host clock right
after each ``pump_step`` returns: the engine reads every token back to the
host inside ``pump_step``, so a stamp comes after the device finished.

A cell of more than one chip runs on the first ``chips`` devices, as the
program's tensor-parallel mesh (``repro.launch.mesh``: "data" x "model", 1 x
chips) that is handed to the engine. Its weights are drawn on that mesh in
the shards the program's tensor-parallel rules give them
(``sharding.DEFAULT_RULES``), so no chip ever holds them whole at set-up.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

import traffic as traffic_lib

#: engine counters whose deltas the metrics read
COUNTERS = ("decode_steps", "decode_slot_steps", "active_slot_steps",
            "tokens_generated", "admitted", "exe_errors")


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Track:
    """What the host saw of one request."""

    req: traffic_lib.Request
    uid: int
    due: float  # absolute host time the request was due (or was sent, closed loop)
    admitted: Optional[float] = None  # start of the pump that admitted it
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[np.ndarray] = None
    failure: Optional[str] = None


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits and misses."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        return dict(compiles=self.compiles, compile_s=self.compile_s,
                    cache_hits=self.hits, cache_misses=self.misses)


def weight_key(seed: int):
    """Raw uint32 PRNG key of the weights, drawn from the seed."""
    import jax.numpy as jnp

    words = np.random.default_rng((seed, 0x57)).integers(0, 2**32, 2, dtype=np.uint64)
    return jnp.asarray(words.astype(np.uint32))


def tier_repeats(spec: dict, n_layers: int) -> List[int]:
    """The K of each layer for one tier of a mix."""
    if "k" in spec:
        return [int(spec["k"])] * n_layers
    reps = [int(k) for count, k in spec["profile"] for _ in range(int(count))]
    if len(reps) != n_layers:
        raise ValueError(f"profile covers {len(reps)} layers, the model has {n_layers}")
    return reps


def tp_mesh(devices):
    """The program's tensor-parallel mesh over exactly ``devices``, the first
    of those JAX sees."""
    from repro.launch.mesh import make_mesh_for_devices

    mesh = make_mesh_for_devices(len(devices), model_parallel=len(devices))
    if set(mesh.devices.flat) != set(devices):
        raise ValueError(f"the mesh holds {mesh.devices.flat}, the cell {devices}")
    return mesh


def weight_shardings(mcfg, mesh):
    """Each weight's layout on ``mesh`` under the tensor-parallel rules."""
    from repro.models import lm, sharding

    return sharding.tree_shardings(lm.param_axes(mcfg), lm.param_specs(mcfg), mesh,
                                   sharding.DEFAULT_RULES)


class Cell:
    """One configuration served under one traffic mix, on ``devices``."""

    def __init__(self, cfg: dict, mix: dict, ref, seed: int, devices):
        self.cfg, self.mix, self.ref, self.seed = cfg, mix, ref, seed
        self.devices = list(devices)
        self.dims = ref.dims(cfg)
        self.mesh = None
        self.engine = None
        self.params = None
        self.tier_ids: Dict[str, object] = {}
        self.facts: Dict[str, float] = {}

    # -- set-up ---------------------------------------------------------------

    def build(self) -> None:
        import jax

        from repro.core import AnalogConfig
        from repro.core.profile import PrecisionProfile
        from repro.models import lm
        from repro.models.config import ModelConfig
        from repro.serving import ServingEngine

        a, e = self.cfg["analog"], self.mix["engine"]
        if a["noise"] != "shot":
            raise ValueError(f"unsupported noise {a['noise']!r}")
        mcfg = ModelConfig(**self.ref.program_kwargs(self.cfg))
        if len(self.devices) > 1:
            self.mesh = tp_mesh(self.devices)
            init = jax.jit(lm.init_params, static_argnums=1,
                           out_shardings=weight_shardings(mcfg, self.mesh))
        else:
            init = jax.jit(lm.init_params, static_argnums=1)
        t0 = time.perf_counter()
        self.params = init(weight_key(self.seed), mcfg)
        jax.block_until_ready(self.params)
        self.facts["weight_init_s"] = time.perf_counter() - t0
        self.engine = ServingEngine(
            self.params, mcfg,
            analog_cfg=AnalogConfig.shot(backend=a["backend"]),
            energies=lm.init_energy_tree(mcfg, float(a["energy_aj_per_mac"])),
            max_gen=int(e["max_gen"]), max_batch=max(e["batch_buckets"]),
            batch_buckets=tuple(e["batch_buckets"]), seq_buckets=tuple(e["seq_buckets"]),
            max_wait=float(e["max_wait"]), continuous=True, pool_slots=int(e["pool_slots"]),
            mesh=self.mesh,
        )
        for name, spec in self.mix["tiers"].items():
            if "k" in spec:
                self.tier_ids[name] = int(spec["k"])
            else:
                prof = PrecisionProfile(
                    tuple(tier_repeats(spec, self.dims["n_layers"])), name=name)
                self.tier_ids[name] = self.engine.register_profile(prof)

    def warm(self) -> None:
        """Run every (tier, batch bucket, seq bucket) the ladder can hit:
        each prefill, insert and decode executable compiles and runs once."""
        e = self.mix["engine"]
        eng = self.engine
        rng = np.random.default_rng((self.seed, 0x3A))
        t0 = time.perf_counter()
        for tier in self.tier_ids.values():
            for sb in e["seq_buckets"]:
                for bb in e["batch_buckets"]:
                    for _ in range(bb):
                        toks = rng.integers(0, self.dims["vocab"], sb).astype(np.int32)
                        key = rng.integers(0, 2**32, 2, dtype=np.uint64).astype(np.uint32)
                        eng.submit(toks, tier=tier, max_new_tokens=2, key=key)
                    eng.pump_step(force=True)
                    while eng.n_in_flight:
                        eng.pump_step(force=True)
        self.facts["warm_s"] = time.perf_counter() - t0

    # -- the window -------------------------------------------------------------

    def counters(self) -> dict:
        s = self.engine.stats
        return {k: int(s[k]) for k in COUNTERS}

    def drive(self, requests, seconds: float, *, on_pump=None, drain_s: float = 120.0):
        """Serve ``requests`` for ``seconds``, then drain what is in flight.

        ``on_pump(now, t_open)`` is called before each pump (the traced run
        starts and stops its trace there). Returns a dict of what the host
        saw: the tracks, the window's open and close and the drain's end,
        generator lateness, the pumps and the prefill dispatches observed
        (pump start, batch bucket, seq bucket, real prompt tokens)."""
        eng, mix = self.engine, self.mix
        e = mix["engine"]
        closed = mix["loop"] == "closed"
        tracks: Dict[int, Track] = {}
        order: List[Track] = []
        seen: Dict[int, int] = {}
        prefills = []  # (t_start, bb, sb, real_tokens)
        pumps = []  # (t_start, t_end)
        lateness = []
        nxt = 0

        def submit(req, due):
            nonlocal nxt
            with annotate("submit"):
                uid = eng.submit(req.prompt, tier=self.tier_ids[req.tier],
                                 max_new_tokens=req.max_new_tokens, key=req.key)
            t = Track(req, uid, due)
            tracks[uid] = t
            order.append(t)
            lateness.append(time.perf_counter() - due)
            nxt += 1

        def stamp(results, t0, t1):
            admitted = []  # first seen this pump: admitted by it
            for pool in eng.pools.values():
                for s in pool.active_slots():
                    rec = pool.record(s)
                    uid = rec.request.uid
                    n_new = len(rec.emitted) - seen.get(uid, 0)
                    if uid not in seen:
                        admitted.append(uid)
                    if n_new > 0:
                        tracks[uid].times.extend([t1] * n_new)
                        seen[uid] = len(rec.emitted)
            for uid, res in results.items():
                t = tracks[uid]
                prev = seen.get(uid)
                if prev is None:
                    admitted.append(uid)
                if isinstance(res, np.ndarray):
                    t.tokens = res
                    t.times.extend([t1] * (res.size - (prev or 0)))
                else:
                    t.failure = repr(res)
                seen[uid] = t.tokens.size if t.tokens is not None else 0
            groups: Dict[tuple, List[int]] = {}
            for uid in admitted:
                t = tracks[uid]
                t.admitted = t0
                sb = traffic_lib.bucket(t.req.prompt.size, e["seq_buckets"])
                groups.setdefault((t.req.tier, sb), []).append(t.req.prompt.size)
            mb = max(e["batch_buckets"])
            for (tier, sb), lens in groups.items():
                for i in range(0, len(lens), mb):
                    chunk = lens[i:i + mb]
                    bb = traffic_lib.bucket(len(chunk), e["batch_buckets"])
                    prefills.append((t0, bb, sb, int(sum(chunk))))

        t_open = time.perf_counter()
        t_close = t_open + seconds
        if closed:
            for _ in range(int(mix["closed"]["outstanding"])):
                submit(requests[nxt], time.perf_counter())
        while True:
            now = time.perf_counter()
            if now >= t_close:
                break
            if not closed:
                while nxt < len(requests) and t_open + requests[nxt].due <= now:
                    submit(requests[nxt], t_open + requests[nxt].due)
            if eng.n_in_flight == 0:
                if closed:
                    break  # the list ran dry: reported by the caller
                wake = t_open + requests[nxt].due if nxt < len(requests) else t_close
                with annotate("wait_arrival"):
                    time.sleep(max(0.0, min(wake, t_close) - time.perf_counter()))
                continue
            if on_pump is not None:
                on_pump(now, t_open)
            t0 = time.perf_counter()
            with annotate("pump_step"):
                results = eng.pump_step()
            t1 = time.perf_counter()
            pumps.append((t0, t1))
            with annotate("bookkeeping"):
                stamp(results, t0, t1)
                if closed:
                    for uid in results:
                        if nxt < len(requests) and time.perf_counter() < t_close:
                            submit(requests[nxt], time.perf_counter())
        if on_pump is not None:
            on_pump(time.perf_counter(), t_open)
        ran_dry = closed and nxt >= len(requests)
        drain_end = time.perf_counter() + drain_s
        while eng.n_in_flight and time.perf_counter() < drain_end:
            t0 = time.perf_counter()
            results = eng.pump_step()
            t1 = time.perf_counter()
            stamp(results, t0, t1)
        t_end = time.perf_counter()
        return dict(tracks=order, t_open=t_open, t_close=t_close, t_end=t_end,
                    lateness=lateness, prefills=prefills, pumps=pumps, ran_dry=ran_dry,
                    drained=eng.n_in_flight == 0)

    def release(self) -> None:
        """Free the program's device state (before the reference runs)."""
        self.engine = None
        self.params = None
        gc.collect()
