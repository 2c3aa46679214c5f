"""The serving engine's spans and named executables as the benchmark reads
them (``engine_trace.py``): a tiny engine traced on the CPU, the readings
against events written by hand, and the existing metrics unmoved by them."""
import os
import types

import jax
import numpy as np
import pytest

import bench_paths
import engine_trace as et
import run
import trace_reduce

ROOT = bench_paths.ROOT

# -- a tiny engine under the profiler (CPU) ------------------------------------

#: (prompt length, tier K). Tier 1 admits a bb 2 x sb 16 group and a bb 1 x
#: sb 32 group; tier 2 a bb 1 x sb 16 group. Budgets differ, so pools retire
#: rows at different pumps.
REQUESTS = ((5, 1, 3), (9, 1, 4), (25, 1, 2), (12, 2, 3))


def _engine():
    from repro.core import AnalogConfig
    from repro.models import init_energy_tree, init_params
    from repro.models.config import ModelConfig
    from repro.serving import ServingEngine

    cfg = ModelConfig(
        name="trace-test", family="dense", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=1, d_ff=64, vocab_size=128, attn_q_chunk=16, attn_kv_chunk=16,
        loss_chunk=32, dtype="float32")
    return ServingEngine(
        init_params(jax.random.PRNGKey(0), cfg), cfg, analog_cfg=AnalogConfig.shot(),
        energies=init_energy_tree(cfg, 20.0), max_gen=4, max_batch=2,
        batch_buckets=(1, 2), seq_buckets=(16, 32), max_wait=0.0,
        continuous=True, pool_slots=4)


def _serve(eng, annotate):
    """Submit every request and pump to the end: {uid: (length, tier)} in
    the order sent, and the tokens of each in that order."""
    rng = np.random.default_rng(7)
    sent, out = {}, {}
    for i, (length, k, gen) in enumerate(REQUESTS):
        with annotate("submit"):
            uid = eng.submit(rng.integers(0, 128, length), tier=k, max_new_tokens=gen,
                             key=jax.random.fold_in(jax.random.PRNGKey(11), i))
        sent[uid] = (length, k)
    while eng.n_in_flight:
        with annotate("pump_step"):
            out.update(eng.pump_step(force=True))
    return sent, [out[uid] for uid in sent]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A cold engine serves the requests under the profiler, then serves them
    again with the profiler off."""
    eng = _engine()
    d = str(tmp_path_factory.mktemp("engine_trace"))
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation("traced_window"):
            sent, on = _serve(eng, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    misses = eng.cache_stats()["misses"]
    _, off = _serve(eng, lambda name: jax.profiler.TraceAnnotation(name))
    tr = et.load(trace_reduce.find_xplane(d))
    return dict(eng=eng, tr=tr, sent=sent, on=on, off=off, misses=misses)


def _within(outer, s):
    return outer[1] <= s[1] and s[1] + s[2] <= outer[1] + outer[2]


def test_one_pump_span_per_pump_step(traced):
    tr = traced["tr"]
    steps = [h for h in tr["host"] if h[0] == "pump_step"]
    pumps = [s for s in tr["spans"] if s[0] == et.PUMP]
    assert len(steps) == len(pumps) >= 3
    for step in steps:
        assert sum(_within(step, p) for p in pumps) == 1


def test_spans_nest_in_order_inside_each_pump(traced):
    tr = traced["tr"]
    phases = (et.SCHEDULE, et.PREFILL, et.PREFILL_WAIT, et.INSERT,
              et.DECODE, et.DECODE_WAIT, et.RETIRE)
    admitted_in = []
    for pump in (s for s in tr["spans"] if s[0] == et.PUMP):
        names = [s[0] for s in tr["spans"] if s[0] in phases and _within(pump, s)]
        assert names[0] == et.SCHEDULE and et.SCHEDULE not in names[1:]
        rest = names[1:]
        n_admit = rest.count(et.PREFILL)
        assert rest[:3 * n_admit] == [et.PREFILL, et.PREFILL_WAIT, et.INSERT] * n_admit
        n_pools = (len(rest) - 3 * n_admit) // 3
        assert n_pools >= 1
        assert rest[3 * n_admit:] == [et.DECODE, et.DECODE_WAIT, et.RETIRE] * n_pools
        admitted_in.append(n_admit)
    assert admitted_in[0] == 3 and not any(admitted_in[1:])


def test_prefill_spans_name_their_requests(traced):
    tr, sent = traced["tr"], traced["sent"]
    prefills = [s for s in tr["spans"] if s[0] == et.PREFILL]
    seen = []
    for _, _, _, meta in prefills:
        ids = et.uids(meta)
        seen += ids
        lengths = [sent[u][0] for u in ids]
        assert meta["tokens"] == sum(lengths)
        assert {str(sent[u][1]) for u in ids} == {str(meta["tier"])}
        assert meta["bb"] == len(ids) and meta["sb"] == (16 if max(lengths) <= 16 else 32)
    assert sorted(seen) == sorted(sent)
    assert any(len(et.uids(m)) == 2 for *_, m in prefills)
    submits = {int(m["uid"]) for n, *_, m in tr["spans"] if n == et.SUBMIT}
    assert submits == set(sent)


def test_cold_buckets_show_one_compile_each(traced):
    tr = traced["tr"]
    compiles = [s for s in tr["spans"] if s[0] == et.COMPILE]
    assert len(compiles) == traced["misses"]
    phase_of = {et.PREFILL: "prefill", et.INSERT: "insert", et.DECODE: "decode"}
    for span in (s for s in tr["spans"] if s[0] in phase_of):
        inner = [c for c in compiles if _within(span, c)]
        assert len(inner) <= 1
        for c in inner:
            assert c[3]["key"].startswith(f"('{phase_of[span[0]]}'")
    # every prefill of the cold engine is a new bucket; the three groups
    # share two insert shapes; each tier's pool compiles one decode
    assert [sum(_within(p, c) for c in compiles)
            for p in tr["spans"] if p[0] == et.PREFILL] == [1, 1, 1]
    assert sum(_within(p, c) for c in compiles for p in tr["spans"] if p[0] == et.INSERT) == 2
    assert sum(_within(p, c) for c in compiles for p in tr["spans"] if p[0] == et.DECODE) == 2


def test_tokens_identical_with_the_profiler_on_and_off(traced):
    assert len(traced["on"]) == len(traced["off"]) == len(REQUESTS)
    for on, off in zip(traced["on"], traced["off"]):
        np.testing.assert_array_equal(on, off)


def test_executables_are_named_modules(traced):
    eng = traced["eng"]
    cl = eng.pool_cache_len
    exes = {et.PREFILL_MODULE: eng.tiers.exe_key("prefill", 1, 2, 16, cl),
            et.DECODE_MODULE: eng.tiers.exe_key("decode", 1, 4, cl),
            et.INSERT_MODULE: eng.tiers.exe_key("insert", None, 4, cl, 2)}
    for module, key in exes.items():
        text = eng.exe_cache.lookup(key).as_text()
        assert text.split(",", 1)[0] == f"HloModule {module}"


def test_span_names_match_the_program():
    from repro.serving import trace

    assert et.SPANS == trace.SPANS
    for name in ("SUBMIT", "PUMP", "SCHEDULE", "PREFILL", "PREFILL_WAIT", "INSERT",
                 "DECODE", "DECODE_WAIT", "RETIRE", "COMPILE"):
        assert getattr(et, name) == getattr(trace, name)
    assert all(n.startswith(et.PREFIX) for n in trace.SPANS)


# -- the readings against events written by hand --------------------------------

WINDOW = (1_000, 101_000)
#: device runs (module, start, duration) in ns; each is one operation too
RUNS = [("jit_decode", 500, 400),        # before the window
        ("jit_prefill", 11_000, 6_000),  # pump A's prefill
        ("jit_insert", 18_300, 100),
        ("jit_decode", 18_600, 10_400),
        ("jit_prefill", 40_100, 4_000),  # pump B's prefill; reads before its launch span
        ("jit_insert", 45_200, 100),
        ("jit_decode", 45_500, 14_000),
        ("jit_prefill", 95_400, 7_600)]  # pump C's prefill, cut by the window's end


def _span(name, a, b, **meta):
    return (name, a, b - a, meta)


SPANS = sorted([
    _span(et.SUBMIT, 500, 510, uid=3),  # before the window
    _span(et.SUBMIT, 2_000, 2_010, uid=5),
    _span(et.SUBMIT, 2_500, 2_510, uid=9),
    _span(et.SUBMIT, 30_000, 30_010, uid=7),
    # pump A admits uids 3, 5 and 9
    _span(et.PUMP, 10_000, 30_000),
    _span(et.SCHEDULE, 10_000, 10_500),
    _span(et.PREFILL, 10_500, 11_000, tier=1, bb=4, sb=16, tokens=30, uids="3 5 9"),
    _span(et.PREFILL_WAIT, 11_000, 18_000),
    _span(et.INSERT, 18_000, 18_200, bb=4),
    _span(et.DECODE, 18_200, 18_500, tier=1, slots=8, active=3),
    _span(et.DECODE_WAIT, 18_500, 29_500),
    _span(et.RETIRE, 29_500, 30_000),
    # pump B admits uid 7 (one uid reads back as a number)
    _span(et.PUMP, 40_000, 60_000),
    _span(et.SCHEDULE, 40_000, 40_200),
    _span(et.PREFILL, 40_200, 40_600, tier=1, bb=1, sb=16, tokens=12, uids=7),
    _span(et.PREFILL_WAIT, 40_600, 45_000),
    _span(et.INSERT, 45_000, 45_100, bb=1),
    _span(et.DECODE, 45_100, 45_400, tier=1, slots=8, active=4),
    _span(et.DECODE_WAIT, 45_400, 59_800),
    _span(et.RETIRE, 59_800, 60_000),
    # pump C runs past the window's end
    _span(et.PUMP, 95_000, 105_000),
    _span(et.SCHEDULE, 95_000, 95_100),
    _span(et.PREFILL, 95_100, 95_300, tier=1, bb=1, sb=32, tokens=20, uids=11),
    _span(et.PREFILL_WAIT, 95_300, 104_000),
], key=lambda s: (s[1], -s[2]))


def _trace(runs=RUNS, spans=SPANS):
    ops = [(f"%fusion.{i} = ...", s, d) for i, (_, s, d) in enumerate(runs)]
    modules = [(f"{m}({i})", s, d) for i, (m, s, d) in enumerate(runs)]
    return dict(devices={"/device:TPU:0": dict(ops=ops, modules=modules)},
                host=[], window=WINDOW, spans=list(spans))


def test_pump_idle_per_whole_pump():
    # A: 20000 - (6000 + 100 + 10400); B: 20000 - (4000 + 100 + 14000); C cut
    assert et.pump_idle_ms(_trace()) == pytest.approx((3_500 + 1_900) / 2 / 1e6)


def test_decode_exe_mean_in_window():
    assert et.decode_exe_ms(_trace()) == pytest.approx((10_400 + 14_000) / 2 / 1e6)


def test_prefill_runs_pair_with_their_launch():
    pairs = et.prefill_pairs(_trace())
    assert [(sp[1], run[1]) for sp, run in pairs] == [(10_500, 11_000), (40_200, 40_100)]
    # pump C's run ends past the window: left out with its 20 tokens
    assert et.prefill_exe_us_per_tok(_trace()) == pytest.approx((6_000 + 4_000) / 42 / 1e3)


def test_admit_wait_matched_by_uid():
    # uid 3 was submitted before the window; 11 has no submit span
    assert sorted(et.admit_waits_ms(_trace())) == pytest.approx(
        [x / 1e6 for x in (8_000, 8_500, 10_200)])
    assert et.admit_wait_ms_p50(_trace()) == pytest.approx(8_500 / 1e6)


def test_idle_by_innermost_span():
    idle = et.idle_by_span(_trace())
    assert idle == {et.SUBMIT: 30, et.SCHEDULE: 700, et.PREFILL: 700,
                    et.PREFILL_WAIT: 2_000, et.INSERT: 300, et.DECODE: 400,
                    et.DECODE_WAIT: 1_000, et.RETIRE: 700, "outside": 53_970}
    tr = _trace()
    busy = trace_reduce.busy(tr["devices"]["/device:TPU:0"]["ops"], *WINDOW)
    assert sum(idle.values()) == WINDOW[1] - WINDOW[0] - busy


def test_module_times_clip_to_window():
    assert et.module_times(_trace()) == {"jit_prefill": (3, 6_000 + 4_000 + 5_600),
                                         "jit_insert": (2, 200),
                                         "jit_decode": (2, 24_400)}


def test_a_program_without_spans_reads_nothing():
    """The parent's program: no engine spans, every executable ``jit_fn``."""
    tr = _trace(runs=[("jit_fn", s, d) for _, s, d in RUNS], spans=())
    for read in (et.pump_idle_ms, et.decode_exe_ms, et.prefill_exe_us_per_tok,
                 et.admit_wait_ms_p50):
        assert read(tr) is None
    assert et.idle_by_span(tr) == {"outside": 59_800}


def _track(L, due, admitted, times):
    req = types.SimpleNamespace(prompt=np.zeros(L, np.int32))
    return types.SimpleNamespace(req=req, due=due, admitted=admitted, times=times)


def test_existing_metrics_read_the_same_with_spans_and_modules():
    """Every per-layer metric of BENCHMARK.json and the summary, on one trace
    with and without what ``engine_trace.load`` adds."""
    ops = [("%fusion.1 = ...", 10_000_001_000, 20_000_000),
           ('%k = f32 custom-call(...), custom_call_target="tpu_custom_call"',
            10_030_001_000, 40_000_000),
           ("%fusion.2 = ...", 10_200_001_000, 10_000_000),
           ("%fusion.3 = ...", 10_400_001_000, 30_000_000)]
    host = [("pump_step", 10_000_001_000, 100_000_000), ("bookkeeping", 10_100_001_000, 5)]

    def ctx(extra):
        dev = dict(ops=ops, **({"modules": [("jit_decode(1)", 10_200_001_000, 10_000_000)]}
                               if extra else {}))
        tr = dict(devices={"/device:TPU:0": dev}, host=host,
                  window=(10_000_001_000, 11_000_001_000))
        if extra:
            tr["spans"] = [(et.PUMP, 10_000_001_100, 99_000_000, {})]
        drive = dict(pumps=[(10.0, 10.1), (10.2, 10.3), (10.4, 10.5), (11.5, 11.6)],
                     prefills=[(10.0, 2, 16, 20)],
                     tracks=[_track(8, 9.95, 10.0, [10.1, 10.3, 10.5]),
                             _track(12, 9.9, 10.0, [10.1, 10.3])])
        return dict(trace=tr, summary=trace_reduce.summary(tr), t0=10.0, t1=11.0,
                    offset_ns=1000, drive=drive,
                    counters=(dict(decode_slot_steps=100, active_slot_steps=40),
                              dict(decode_slot_steps=164, active_slot_steps=88)),
                    dims=dict(n_layers=1, d_model=4, n_heads=2, n_kv_heads=1, head_dim=2,
                              d_ff=8, vocab=10, mlp="swiglu"),
                    peak=dict(bf16_flops=197e12, hbm_bytes_s=819e9))

    plain, more = ctx(False), ctx(True)
    assert plain["summary"] == more["summary"]
    spec = run.load_cell("granite8b-chat-k1", root=ROOT)
    names = {m["name"] for m in spec["bench"]["per_layer"]}
    assert len(names) == 8
    for name in sorted(names):
        read = run.load_module(os.path.join(bench_paths.CHIP, "metrics", f"{name}.py"),
                               "m_" + name.replace(".", "_")).read
        assert read(plain) == read(more), name


def test_device_lag_and_idle_moved_by_it():
    # the device's run 1 reads 500 ns before the host enqueued it, run 2 200 ns
    tr = dict(window=(0, 100), launches={1: 1_500, 2: 5_200, 3: 9_000},
              devices={"/device:TPU:0": dict(ops=[("%fusion = ...", 15, 30)],
                                             run_starts={1: 1_000, 2: 5_000})},
              spans=[_span(et.DECODE, 10, 20), _span(et.DECODE_WAIT, 20, 60)])
    assert et.device_lag_ns(tr) == 500
    assert et.device_lag_ns(_trace()) is None
    # the decode program reads [15, 45) and ran [25, 55): moved by 10 ns, the
    # idle before it falls in the launch span, not in the wait for its tokens
    assert et.idle_by_span(tr) == {et.DECODE: 5, et.DECODE_WAIT: 15, "outside": 50}
    assert et.idle_by_span(tr, 10) == {et.DECODE: 10, et.DECODE_WAIT: 10, "outside": 50}


ENGINE_FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                              "engine.xplane.pb")


def test_recorded_engine_trace():
    """A v5e trace (make_engine_trace_fixture.py): three requests submitted,
    then two pumps; the first prefills a bb 2 x sb 16 group (uids 3 and 4)
    and a bb 1 x sb 32 group (uid 5), inserts both and decodes once, the
    second decodes once. Counts read by hand off its events."""
    tr = et.load(ENGINE_FIXTURE)
    assert list(tr["devices"]) == ["/device:TPU:0"]
    assert [h[0] for h in tr["host"]] == ["submit"] * 3 + ["pump_step"] * 2
    assert [s[0] for s in tr["spans"]] == (
        [et.SUBMIT] * 3
        + [et.PUMP, et.SCHEDULE] + [et.PREFILL, et.PREFILL_WAIT, et.INSERT] * 2
        + [et.DECODE, et.DECODE_WAIT, et.RETIRE]
        + [et.PUMP, et.SCHEDULE, et.DECODE, et.DECODE_WAIT, et.RETIRE])
    assert [s[3]["uid"] for s in tr["spans"] if s[0] == et.SUBMIT] == [3, 4, 5]
    assert [s[3] for s in tr["spans"] if s[0] == et.PREFILL] == [
        {"tier": 1, "bb": 2, "sb": 16, "tokens": 14, "uids": "3 4"},
        {"tier": 1, "bb": 1, "sb": 32, "tokens": 20, "uids": 5}]
    assert [s[3] for s in tr["spans"] if s[0] == et.DECODE] == [
        {"tier": 1, "slots": 4, "active": 3}] * 2
    mods = tr["devices"]["/device:TPU:0"]["modules"]
    engine_runs = [(et.module_name(n), d) for n, _, d in mods
                   if et.module_name(n) in (et.PREFILL_MODULE, et.DECODE_MODULE, et.INSERT_MODULE)]
    assert engine_runs == [("jit_prefill", 7_656), ("jit_insert", 2_180),
                           ("jit_prefill", 14_406), ("jit_insert", 3_294),
                           ("jit_decode", 7_793), ("jit_decode", 7_945)]
    pairs = et.prefill_pairs(tr)
    assert [(sp[3]["tokens"], run[2]) for sp, run in pairs] == [(14, 7_656), (20, 14_406)]
    assert et.prefill_exe_us_per_tok(tr) == pytest.approx((7_656 + 14_406) / 34 / 1e3)
    assert et.decode_exe_ms(tr) == pytest.approx((7_793 + 7_945) / 2 / 1e6)
    # the two pumps last 12_762_069 and 2_639_400 ns; the device is busy 42_980 of them
    assert et.pump_idle_ms(tr) == pytest.approx((12_762_069 + 2_639_400 - 42_980) / 2 / 1e6)
    # each request from its engine.submit to the start of its engine.prefill
    assert et.admit_waits_ms(tr) == pytest.approx([0.266571, 0.136311, 5.631919])
    # the device's clock reads 1.317 ms behind the host's: every run of the
    # trace starts at least that long before its DoEnqueueProgram would allow
    lag = et.device_lag_ns(tr)
    assert lag == 1_316_967
    runs = tr["devices"]["/device:TPU:0"]["run_starts"]
    assert len(runs) == len(mods) and all(tr["launches"][r] <= s + lag for r, s in runs.items())
    # what trace_reduce.load reads is left as it was
    plain = trace_reduce.load(ENGINE_FIXTURE)
    for k in ("modules", "run_starts"):
        tr["devices"]["/device:TPU:0"].pop(k)
    assert {k: v for k, v in tr.items() if k not in ("spans", "launches")} == plain
