"""Continuous batching invariants: the slot allocator can never alias two
requests, slot-aware admission respects per-tier free-slot accounting and
deadlines over partial pools, pooled decode retires rows the step they
finish (budget or stop id), and — the acceptance contract — a request's
tokens through a persistent decode pool are bit-identical to its solo run,
for every served family, regardless of slot index, admission step, or
pool neighbors."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import AnalogConfig
from repro.models import init_energy_tree, init_params, lm
from repro.serving import (
    DecodePool,
    ExecutableBuildError,
    ExecutableCache,
    ExecutionTier,
    FaultPlan,
    PrecisionProfile,
    Request,
    RequestFailure,
    ServingEngine,
    SlotAllocator,
    TierScheduler,
    TransientExecutableFault,
)
from test_serving import ENERGY_AJ, FAMILY_CONFIGS, SB, _solo_tokens

KEY = jax.random.PRNGKey(0)


def _requests(n=3, lens=(7, 19, 28), gens=(2, 5, 8), vocab=128, seed=3):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, L) for L in lens[:n]]
    keys = [jax.random.fold_in(jax.random.PRNGKey(5), i) for i in range(n)]
    return prompts, list(gens[:n]), keys


def _continuous_engine(params, cfg, *, pool_slots=2, analog=False, **kw):
    extra = {}
    if analog:
        extra = dict(
            analog_cfg=AnalogConfig.shot(),
            energies=init_energy_tree(cfg, ENERGY_AJ),
        )
    return ServingEngine(
        params, cfg, max_gen=8, max_batch=4, max_wait=1.0,
        batch_buckets=(1, 2, 4), seq_buckets=(SB,),
        continuous=True, pool_slots=pool_slots, **extra, **kw,
    )


# --------------------------------------------------------------------------
# slot allocator: no double assignment, no aliasing across retire->admit
# --------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(n_slots=st.integers(1, 8), seed=st.integers(0, 2**16))
def test_slot_allocator_property(n_slots, seed):
    """Random take/release traffic: a slot is never handed out while held
    (no double assignment), releases only succeed on held slots, and the
    free+held partition always covers exactly the pool."""
    rng = np.random.default_rng(seed)
    alloc = SlotAllocator(n_slots)
    held = {}  # slot -> owning uid
    uid = 0
    for _ in range(200):
        if rng.random() < 0.55 and alloc.n_free:
            k = int(rng.integers(1, alloc.n_free + 1))
            got = alloc.take(k)
            assert len(got) == len(set(got)) == k
            assert not set(got) & set(held)  # never double-assigned
            for s in got:
                assert 0 <= s < n_slots
                held[s] = uid
                uid += 1
        elif held:
            s = int(rng.choice(sorted(held)))
            alloc.release(s)
            del held[s]
        assert alloc.n_free + len(held) == n_slots
        assert alloc.held() == set(held)
    with pytest.raises(ValueError):
        alloc.take(alloc.n_free + 1)
    if held:
        s = next(iter(held))
        alloc.release(s)
        with pytest.raises(ValueError, match="not held"):
            alloc.release(s)  # double release


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_pool_reuse_never_aliases_rows_or_keys(seed):
    """Retire->admit slot reuse through the DecodePool host state: an
    activated slot always carries its OWN request's token/position/length/
    key row, never a previous or concurrent occupant's."""
    rng = np.random.default_rng(seed)
    slots = 4
    pool = DecodePool(
        tier=1, slots=slots, cache_len=40, key_shape=(2,),
        key_dtype=np.uint32, cache=None,
    )
    uid = 0
    live = {}  # slot -> uid
    for _ in range(60):
        if rng.random() < 0.5 and pool.n_free:
            (s,) = pool.take(1)
            req = Request(
                uid=uid, tokens=np.arange(1 + uid % 7, dtype=np.int32),
                max_new_tokens=4,
            )
            pool.activate(s, req, first_token=100 + uid, key_row=[uid, uid ^ 0xFF])
            live[s] = uid
            uid += 1
        elif live:
            s = int(rng.choice(sorted(live)))
            rec = pool.retire(s)
            assert rec.request.uid == live.pop(s)
        # every live slot still holds exactly its own request's row state
        assert set(pool.active_slots()) == set(live)
        for s, u in live.items():
            assert pool.record(s).request.uid == u
            assert pool.tok[s] == 100 + u
            assert pool.lengths[s] == pool.record(s).request.prompt_len
            np.testing.assert_array_equal(pool.keys[s], [u, u ^ 0xFF])
        for s in range(slots):  # freed rows are inert length-0 pad rows
            if s not in live:
                assert pool.lengths[s] == 0
        assert len(set(live.values())) == len(live)  # no uid in two slots


# --------------------------------------------------------------------------
# scheduler: slot-aware admission
# --------------------------------------------------------------------------


def _req(uid, length, k, arrival):
    return Request(uid=uid, tokens=np.zeros(length, np.int32), n_repeats=k,
                   arrival=arrival)


def test_pop_admissible_caps_at_free_slots():
    sch = TierScheduler(max_batch=4, max_wait=10.0, seq_buckets=(32,))
    for uid in range(6):
        sch.submit(_req(uid, 8, 1, arrival=0.0))
    free = {1: 3}
    batches = sch.pop_admissible(0.0, free, force=True)
    assert [[r.uid for r in b] for b in batches] == [[0, 1, 2]]
    assert free[1] == 0 and sch.n_pending == 3
    assert sch.pop_admissible(0.0, {1: 0}, force=True) == []  # pool full
    # freed slots admit the FIFO remainder, max_batch still caps one wave
    batches = sch.pop_admissible(0.0, {1: 6}, force=True)
    assert [[r.uid for r in b] for b in batches] == [[3, 4, 5]]
    assert sch.n_pending == 0


def test_pop_admissible_deadline_over_partial_pool():
    sch = TierScheduler(max_batch=4, max_wait=5.0, seq_buckets=(32,))
    for uid in range(2):
        sch.submit(_req(uid, 8, 1, arrival=0.0))
    # not full, not aged: stays queued even though slots are free
    assert sch.pop_admissible(4.9, {1: 4}) == []
    # aged past max_wait with ONE free slot: admit what fits now, keep FIFO
    batches = sch.pop_admissible(5.0, {1: 1})
    assert [[r.uid for r in b] for b in batches] == [[0]]
    assert sch.n_pending == 1
    assert sch.pending_tiers() == {1}


def test_pop_admissible_shares_tier_slots_across_seq_buckets():
    """Two seq-bucket groups of one tier draw from the same pool: the free
    accounting spans groups, submission order first."""
    sch = TierScheduler(max_batch=4, max_wait=10.0, seq_buckets=(16, 32))
    sch.submit(_req(0, 8, 1, arrival=0.0))
    sch.submit(_req(1, 8, 1, arrival=0.0))
    sch.submit(_req(2, 30, 1, arrival=0.0))
    sch.submit(_req(3, 30, 1, arrival=0.0))
    free = {1: 3}
    batches = sch.pop_admissible(0.0, free, force=True)
    assert [[r.uid for r in b] for b in batches] == [[0, 1], [2]]
    assert free[1] == 0 and sch.n_pending == 1


# --------------------------------------------------------------------------
# pooled decode == solo run, per family (the acceptance contract)
# --------------------------------------------------------------------------

POOLED_FAMILIES = ["dense", "windowed", "griffin", "xlstm"]


@pytest.mark.parametrize("family", POOLED_FAMILIES)
def test_family_pooled_vs_solo(family):
    """3 requests with heterogeneous budgets through a 2-slot pool: the
    third is admitted mid-flight into a retired slot, yet every request's
    tokens equal its solo unpadded run (slot index, admission step, and
    neighbors are invisible)."""
    cfg = FAMILY_CONFIGS[family]
    params = init_params(KEY, cfg)
    prompts, gens, _ = _requests(vocab=cfg.vocab_size)
    eng = _continuous_engine(params, cfg, pool_slots=2)
    uids = [
        eng.submit(p, max_new_tokens=g, now=0.0) for p, g in zip(prompts, gens)
    ]
    pooled = eng.flush()
    assert eng.stats["admitted"] == 3 and eng.stats["retired"] == 3
    for uid, p, g in zip(uids, prompts, gens):
        np.testing.assert_array_equal(pooled[uid], _solo_tokens(params, cfg, p, g))


@pytest.mark.parametrize("family", ["dense", "griffin"])
def test_family_analog_pooled_matches_sync_and_solo(family):
    """Analog serving: pooled tokens == the batch-synchronous engine ==
    a solo run through the pool itself (per-request noise keys make pool
    occupancy and decode discipline invisible to the numerics)."""
    cfg = FAMILY_CONFIGS[family]
    params = init_params(KEY, cfg)
    energies = init_energy_tree(cfg, ENERGY_AJ)
    prompts, gens, keys = _requests(vocab=cfg.vocab_size)
    pooled_eng = _continuous_engine(params, cfg, pool_slots=2, analog=True)
    uids = [
        pooled_eng.submit(p, n_repeats=2, max_new_tokens=g, key=k, now=0.0)
        for p, g, k in zip(prompts, gens, keys)
    ]
    pooled = pooled_eng.flush()

    sync_eng = ServingEngine(
        params, cfg, analog_cfg=AnalogConfig.shot(), energies=energies,
        max_gen=8, max_batch=4, max_wait=1.0, batch_buckets=(1, 2, 4),
        seq_buckets=(SB,),
    )
    sync_uids = [
        sync_eng.submit(p, n_repeats=2, max_new_tokens=g, key=k, now=0.0)
        for p, g, k in zip(prompts, gens, keys)
    ]
    sync = sync_eng.flush()
    for pu, su in zip(uids, sync_uids):
        np.testing.assert_array_equal(pooled[pu], sync[su])

    # solo through the SAME pool (lands in slot 0, no neighbors)
    for pu, p, g, k in zip(uids, prompts, gens, keys):
        solo_uid = pooled_eng.submit(
            p, n_repeats=2, max_new_tokens=g, key=k, now=0.0
        )
        np.testing.assert_array_equal(pooled_eng.flush()[solo_uid], pooled[pu])


def test_profile_tier_pools_and_uniform_coexist():
    """A per-layer profile tier gets its own pool next to the uniform-K
    pool; both serve retrace-free on replay and match the batch-synchronous
    engine bit-for-bit."""
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)
    profile = PrecisionProfile((2, 1), name="lop")
    prompts, gens, keys = _requests(vocab=cfg.vocab_size)
    tiers = [{"profile": profile}, {"n_repeats": 2}, {"profile": "lop"}]

    def run(continuous):
        eng = _continuous_engine(
            params, cfg, pool_slots=2, analog=True, profiles=[profile],
        ) if continuous else ServingEngine(
            params, cfg, analog_cfg=AnalogConfig.shot(),
            energies=init_energy_tree(cfg, ENERGY_AJ), max_gen=8, max_batch=4,
            max_wait=1.0, batch_buckets=(1, 2, 4), seq_buckets=(SB,),
            profiles=[profile],
        )
        out = []
        for replay in range(2):
            uids = [
                eng.submit(p, max_new_tokens=g, key=k, now=0.0, **tier)
                for p, g, k, tier in zip(prompts, gens, keys, tiers)
            ]
            if replay == 1:
                eng.exe_cache.reset_stats()
                traces = eng.trace_count
            done = eng.flush()
            out = [done[u] for u in uids]
        assert eng.exe_cache.stats()["misses"] == 0  # steady replay: all hits
        assert eng.trace_count == traces
        return out, eng

    pooled, eng = run(continuous=True)
    assert set(eng.pools) == {"lop", 2}  # one persistent pool per tier
    sync, _ = run(continuous=False)
    for a, b in zip(pooled, sync):
        np.testing.assert_array_equal(a, b)


def test_pool_cache_len_override_and_fit_check():
    """An explicit pool_cache_len sizes the pools below the seq ladder's
    worst case; requests that can't fit a slot are rejected at submit, and
    fitting traffic still matches its solo run."""
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)
    prompts, _, _ = _requests(vocab=cfg.vocab_size)
    with pytest.raises(ValueError, match="pool_cache_len"):
        _continuous_engine(params, cfg, pool_cache_len=SB)  # <= min bucket
    eng = _continuous_engine(params, cfg, pool_cache_len=SB + 4)
    assert eng.pool_cache_len == SB + 4
    with pytest.raises(ValueError, match="decode"):
        eng.submit(prompts[0], max_new_tokens=8, now=0.0)  # 32+8 > 36
    uid = eng.submit(prompts[0], max_new_tokens=4, now=0.0)  # 32+4 fits
    np.testing.assert_array_equal(
        eng.flush()[uid], _solo_tokens(params, cfg, prompts[0], 4)
    )


def test_moe_continuous_rejected():
    """MoE keeps the batch-synchronous path: expert noise is batch-level,
    so in-flight admission would change a request's noise mid-stream."""
    cfg = FAMILY_CONFIGS["moe"]
    params = init_params(KEY, cfg)
    with pytest.raises(ValueError, match="moe"):
        ServingEngine(params, cfg, continuous=True)


# --------------------------------------------------------------------------
# early retirement: stop tokens and budgets, both decode disciplines
# --------------------------------------------------------------------------


@pytest.mark.parametrize("continuous", [False, True])
def test_stop_tokens_retire_early(continuous):
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)
    prompts, _, _ = _requests(vocab=cfg.vocab_size)
    full = _solo_tokens(params, cfg, prompts[2], 8)
    stop = int(full[3])
    kw = dict(continuous=True, pool_slots=4) if continuous else {}
    eng = ServingEngine(
        params, cfg, max_gen=8, max_batch=4, max_wait=1.0,
        batch_buckets=(1, 2, 4), seq_buckets=(SB,), **kw,
    )
    tokens_before = eng.stats["tokens_generated"]
    u_stop = eng.submit(prompts[2], max_new_tokens=8, stop_tokens=(stop,), now=0.0)
    u_free = eng.submit(prompts[2], max_new_tokens=8, now=0.0)
    out = eng.flush()
    # the stop id is the LAST emitted token; the no-stop twin runs out its
    # budget untouched (batch-mates don't inherit each other's stops)
    np.testing.assert_array_equal(out[u_stop], full[:4])
    np.testing.assert_array_equal(out[u_free], full)
    assert eng.stats["tokens_generated"] - tokens_before == 4 + 8


def test_stop_token_at_first_token_and_budget_one():
    """A stop id emitted at prefill (or a 1-token budget) finishes the
    request without ever occupying a decode slot."""
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)
    prompts, _, _ = _requests(vocab=cfg.vocab_size)
    first = int(_solo_tokens(params, cfg, prompts[0], 1)[0])
    eng = _continuous_engine(params, cfg, pool_slots=2)
    u0 = eng.submit(prompts[0], max_new_tokens=8, stop_tokens=(first,), now=0.0)
    u1 = eng.submit(prompts[1], max_new_tokens=1, now=0.0)
    out = eng.flush()
    np.testing.assert_array_equal(out[u0], [first])
    assert out[u1].shape == (1,)
    assert eng.stats["decode_steps"] == 0  # nothing ever decoded
    assert all(p.n_active == 0 for p in eng.pools.values())


def test_legacy_batch_early_exit_on_all_stopped():
    """Batch-synchronous EOS: once every row has hit its budget or stop id,
    the batch stops decoding (no more wasted steps) and tokens_generated
    counts actual emissions."""
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)
    prompts, _, _ = _requests(vocab=cfg.vocab_size)
    refs = [_solo_tokens(params, cfg, p, 8) for p in prompts[:2]]
    stops = [int(refs[0][1]), int(refs[1][2])]
    eng = ServingEngine(
        params, cfg, max_gen=8, max_batch=4, max_wait=1.0,
        batch_buckets=(1, 2, 4), seq_buckets=(SB,),
    )
    uids = [
        eng.submit(p, max_new_tokens=8, stop_tokens=(s,), now=0.0)
        for p, s in zip(prompts[:2], stops)
    ]
    out = eng.flush()
    np.testing.assert_array_equal(out[uids[0]], refs[0][:2])
    np.testing.assert_array_equal(out[uids[1]], refs[1][:3])
    assert eng.stats["decode_steps"] == 2  # stopped at the slowest row, not 7
    assert eng.stats["tokens_generated"] == 2 + 3


# --------------------------------------------------------------------------
# throughput structure, pump_step API, cache insert, LRU
# --------------------------------------------------------------------------


def test_continuous_uses_fewer_decode_row_slots_and_stays_compiled():
    """Heterogeneous budgets: the pool dispatches strictly less decode work
    (row-slots) than run-to-completion batching of the same traffic, with
    identical outputs and zero steady-state retraces on replay."""
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(9)
    lens = rng.integers(4, SB + 1, 8)
    gens = [2, 2, 8, 2, 4, 2, 8, 2]  # one batch would decode 8 steps for all
    prompts = [rng.integers(0, cfg.vocab_size, L) for L in lens]
    keys = [jax.random.fold_in(jax.random.PRNGKey(11), i) for i in range(8)]

    outputs, slot_steps = {}, {}
    for mode, continuous in (("sync", False), ("continuous", True)):
        eng = ServingEngine(
            params, cfg, max_gen=8, max_batch=8, max_wait=1.0,
            batch_buckets=(1, 2, 4, 8), seq_buckets=(SB,),
            continuous=continuous, pool_slots=4,
        )
        for replay in range(2):
            if replay == 1:
                eng.exe_cache.reset_stats()
                traces = eng.trace_count
                before = eng.stats["decode_slot_steps"]
            uids = [
                eng.submit(p, max_new_tokens=g, key=k, now=0.0)
                for p, g, k in zip(prompts, gens, keys)
            ]
            done = eng.flush()
            outputs.setdefault(mode, [done[u] for u in uids])
        slot_steps[mode] = eng.stats["decode_slot_steps"] - before
        assert eng.exe_cache.stats()["misses"] == 0, mode
        assert eng.trace_count == traces, mode
    for a, b in zip(outputs["sync"], outputs["continuous"]):
        np.testing.assert_array_equal(a, b)
    assert slot_steps["continuous"] < slot_steps["sync"], slot_steps


def test_pump_step_drains_incrementally():
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)
    prompts, gens, _ = _requests(vocab=cfg.vocab_size)
    eng = _continuous_engine(params, cfg, pool_slots=2)
    with pytest.raises(ValueError, match="continuous"):
        ServingEngine(
            params, cfg, max_gen=8, batch_buckets=(1, 2), seq_buckets=(SB,)
        ).pump_step()
    uids = [
        eng.submit(p, max_new_tokens=g, now=0.0) for p, g in zip(prompts, gens)
    ]
    assert eng.n_in_flight == 3
    results, steps = {}, 0
    while eng.n_in_flight:
        results.update(eng.pump_step(now=1.0, force=True))
        steps += 1
        assert steps < 50
    assert set(results) == set(uids)
    assert steps > 1  # finished across iterations, not one run-to-completion
    for uid, p, g in zip(uids, prompts, gens):
        np.testing.assert_array_equal(results[uid], _solo_tokens(params, cfg, p, g))


def test_scatter_cache_rows_places_and_drops():
    cfg = FAMILY_CONFIGS["dense"]
    slots, bb, cache_len = 4, 2, 12
    dst = lm.init_cache(cfg, slots, cache_len)
    src = jax.tree.map(
        lambda a: jax.numpy.ones_like(a), lm.init_cache(cfg, bb, cache_len)
    )
    out = lm.scatter_cache_rows(cfg, dst, src, np.asarray([2, slots], np.int32))
    for leaf in jax.tree.leaves(out):
        leaf = np.asarray(leaf)  # (g, per, batch, s, kh, hd): batch axis 2
        assert (leaf[:, :, 2] == 1).all()  # row 0 of src landed in slot 2
        mask = np.ones(slots, bool)
        mask[2] = False
        assert (leaf[:, :, mask] == 0).all()  # oob row dropped, rest untouched


def test_executable_cache_lru_eviction():
    cache = ExecutableCache(max_entries=2)
    built = []

    def make(name):
        def build():
            built.append(name)
            return name

        return build

    assert cache.get("a", make("a")) == "a"
    assert cache.get("b", make("b")) == "b"
    assert cache.get("a", make("a")) == "a"  # hit refreshes "a"
    assert cache.get("c", make("c")) == "c"  # evicts LRU "b"
    assert "b" not in cache and "a" in cache and "c" in cache
    stats = cache.stats()
    assert stats["evictions"] == 1 and stats["entries"] == 2
    assert stats["max_entries"] == 2
    assert cache.get("b", make("b")) == "b"  # re-compiles: a fresh miss
    assert built == ["a", "b", "c", "b"]
    assert cache.stats()["evictions"] == 2  # "a" fell out when "b" returned
    with pytest.raises(ValueError):
        ExecutableCache(max_entries=0)
    # default stays unbounded
    unbounded = ExecutableCache()
    for i in range(10):
        unbounded.get(i, make(i))
    assert len(unbounded) == 10 and unbounded.stats()["evictions"] == 0


# --------------------------------------------------------------------------
# fault hygiene: random faults + deadlines never leak or alias slots
# --------------------------------------------------------------------------

_FAULT_ENG = []  # lazy singleton: examples share executables, not state


def _fault_engine():
    if not _FAULT_ENG:
        cfg = FAMILY_CONFIGS["dense"]
        params = init_params(KEY, cfg)
        # constructed WITH a (empty) plan so the cache fault guard is armed;
        # each example swaps in its own plan, then clears it
        _FAULT_ENG.append(
            _continuous_engine(params, cfg, pool_slots=2,
                               fault_plan=FaultPlan())
        )
    return _FAULT_ENG[0]


class _GenericExeFaultPlan(FaultPlan):
    """A plan whose scheduled executable faults raise a *generic*
    ``RuntimeError`` instead of :class:`TransientExecutableFault` — the
    unplanned mid-pump crash (driver bug, OOM, cosmic ray in the host
    code) that the engine's containment must treat like any other
    executable failure: the fault fires pre-dispatch, so no donated
    buffer is consumed, no pool slot leaks or aliases, and the affected
    requests retire-or-requeue exactly once."""

    def check_executable(self, key) -> None:
        try:
            super().check_executable(key)
        except TransientExecutableFault as e:
            raise RuntimeError(
                f"unplanned executable crash: {e.phase} call #{e.call_index}"
            ) from None


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_faulted_pool_accounting_property(seed):
    """Random stalls, executable faults (transient AND generic unplanned
    exceptions), poisoned rows, and tight deadlines over continuous
    traffic: every submitted uid resolves exactly once (tokens or a
    structured RequestFailure), nothing hangs, and after the drain every
    pool's slots are fully free with the scheduler empty — faults may
    fail requests but can never leak or alias a slot."""
    rng = np.random.default_rng(seed)
    eng = _fault_engine()
    cfg = eng.model_cfg
    c0 = eng._fault_clock  # plans are scheduled relative to the live clock
    # half the examples raise generic exceptions at the same injection
    # points: containment must not depend on the fault's type
    plan_cls = _GenericExeFaultPlan if rng.random() < 0.5 else FaultPlan
    errs0 = eng.stats["exe_errors"]
    plan = plan_cls(
        seed=seed,
        stall_steps=tuple(c0 + int(o) for o in rng.integers(0, 14, 3)),
        exe_faults=tuple(
            ("decode", int(n)) for n in rng.choice(12, 2, replace=False)
        ) + ((("prefill", int(rng.integers(0, 3))),) if rng.random() < 0.5
             else ()),
        poison={(c0 + int(rng.integers(0, 10)), int(rng.integers(0, 2))): -7}
        if rng.random() < 0.5 else (),
    )
    eng.fault_plan = plan
    try:
        n = int(rng.integers(2, 5))
        uids = []
        for i in range(n):
            prompt = rng.integers(0, cfg.vocab_size, int(rng.integers(1, SB)))
            deadline = float(rng.uniform(0.002, 0.02)) if rng.random() < 0.4 \
                else None
            uids.append(eng.submit(
                prompt, max_new_tokens=int(rng.integers(1, 9)),
                now=0.0, deadline=deadline,
            ))
        results, t, steps = {}, 0.0, 0
        while eng.n_in_flight:
            t += 1e-3
            for uid, res in eng.pump_step(now=t, force=True).items():
                assert uid not in results  # resolved at most once
                results[uid] = res
            steps += 1
            assert steps < 500, "faulted drain hung"
    finally:
        eng.fault_plan = FaultPlan()  # disarm for the next example
    assert set(results) == set(uids)  # every uid resolved exactly once
    # generic exceptions route through the containment path, not retries
    exe_fired = sum(1 for e in plan.log if e["site"] == "executable")
    if plan_cls is _GenericExeFaultPlan and exe_fired:
        assert eng.stats["exe_errors"] >= errs0 + 1
    for res in results.values():
        if isinstance(res, RequestFailure):
            assert res.detail and not res.ok
        else:
            assert isinstance(res, np.ndarray) and res.dtype == np.int32
    # slot hygiene: nothing leaked, nothing half-held, scheduler empty
    assert eng.scheduler.n_pending == 0 and eng.n_in_flight == 0
    for pool in eng.pools.values():
        assert pool.n_active == 0
        assert pool.allocator.n_free == pool.slots
        assert not pool.allocator.held()
        assert (np.asarray(pool.lengths) == 0).all()  # all rows inert


@pytest.mark.parametrize("continuous", [False, True])
def test_compile_error_propagates_out_of_engine(continuous, monkeypatch):
    """A program the compiler refuses is not a transient fault: retrying
    cannot help, so the build error leaves the engine (under both decode
    disciplines) instead of turning into retried, then Failed, requests
    that a calling script would count as served."""
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)

    def refuse(self, *shape):
        raise NotImplementedError("Unsupported cast: uint32 -> float32")

    monkeypatch.setattr(ExecutionTier, "build_prefill", refuse)
    eng = ServingEngine(
        params, cfg, max_gen=4, batch_buckets=(1, 2), seq_buckets=(SB,),
        continuous=continuous,
    )
    eng.submit(np.arange(5), max_new_tokens=2)
    with pytest.raises(ExecutableBuildError, match="Unsupported cast"):
        eng.flush()
    for stat in ("exe_errors", "exe_faults", "retried", "failed"):
        assert eng.stats[stat] == 0, stat


@pytest.mark.parametrize("phase", ["decode", "insert"])
def test_consumed_donated_cache_is_renewed(phase, monkeypatch):
    """An executable that raises after consuming its donated pool cache
    (a device-side failure; the CPU ignores donation, so the test deletes
    the buffer itself) leaves the pool no state to read: the engine gives
    the pool a fresh cache and retries every row that lost its state, and
    the retried requests still return their solo tokens."""
    cfg = FAMILY_CONFIGS["dense"]
    params = init_params(KEY, cfg)
    eng = _continuous_engine(params, cfg, pool_slots=2)
    get = eng.exe_cache.get
    fired = []

    def consuming_get(key, build):
        exe = get(key, build)
        if key[0] != phase:
            return exe

        def call(*args):
            if not fired:
                fired.append(key)
                donated = args[1] if phase == "decode" else args[0]
                for a in jax.tree.leaves(donated):
                    a.delete()
                raise RuntimeError("device fault after donation")
            return exe(*args)

        return call

    monkeypatch.setattr(eng.exe_cache, "get", consuming_get)
    prompts, gens, _ = _requests()
    uids = [eng.submit(p, max_new_tokens=g) for p, g in zip(prompts, gens)]
    results = eng.flush()
    assert fired and eng.stats["exe_errors"] == 1
    assert eng.stats["failed"] == 0
    for uid, p, g in zip(uids, prompts, gens):
        np.testing.assert_array_equal(
            results[uid], _solo_tokens(params, cfg, p, g)
        )
