"""Bucket-batched analog serving engine.

Takes a queue of heterogeneous generation requests — varying prompt length,
batch arrival pattern, and precision tier (``n_repeats`` = the paper's
dynamic-precision K) — and serves them through the fused analog path:

  submit -> TierScheduler groups same-K requests         (scheduler.py)
         -> pad into a power-of-two (batch, seq) bucket  (bucketing.py)
         -> AOT executable per (bucket, K, backend)      (cache.py)
         -> prefill once, then bucketed decode steps     (models/lm.py)

Two decode disciplines share that pipeline:

  batch-synchronous (default) — a dispatched batch decodes to completion:
      ``max(max_new_tokens)`` steps for every row. Simple, but a 4-token
      request co-batched with a 64-token one pays 16x its own decode work,
      finished rows keep burning analog energy, and nothing new is admitted
      until the batch drains.

  continuous (``continuous=True``) — each tier owns a persistent
      **decode slot pool** (pool.py): a fixed ``(slots, cache_len)`` cache
      that decodes every step under an active-slot mask, *retires* a row
      the step it hits its token budget or emits a stop id, and *admits*
      freshly prefilled requests into the freed slots mid-flight — the
      prefill runs at the pool's cache length and its cache rows are
      scattered in under jit (``lm.scatter_cache_rows``), no retrace, no
      host round-trip of the cache. Decode slots stay saturated with real
      work, which is the throughput headline of every production serving
      stack.

Correctness contract: every request is served with its *own* PRNG key
stacked into the batch (per-request noise streams, see AnalogHook), its own
true prompt length (per-row decode positions), and greedy sampling — so its
tokens are bit-identical to running it alone at the same seq bucket,
regardless of batch-mates, batch padding, decode discipline, slot index, or
admission step. The engine's batching is a pure throughput optimization,
not a numerics change. (Inactive pool slots are exactly length-0
batch-padding rows; a noise stream depends only on the request key, layer,
site, and token position — never on where the row sits.)

Every model family rides this contract via length-aware prefill/decode
(``lengths`` threaded through ``models/lm.py``) — with one exception:
**MoE stays batch-synchronous.** Its expert capacity buffers mix requests
inside one matmul, so analog expert sites draw a *batch-level* noise stream
(``AnalogHook.batched``); under in-flight admission that stream would
change mid-request every time a neighbor retired or arrived. Rather than
silently weakening MoE's (already batch-level) reproducibility story,
``continuous=True`` is rejected for the moe family — serve it with the
batch-synchronous engine, whose noise is reproducible per batch
composition. (Re-folding ``collapse_keys(valid=active)`` per step is the
documented alternative if mid-request noise drift is ever acceptable.)

Execution tiers can never share a batch (or a pool): what a tier computes
is static in the fused kernel (baked into the trace), which is exactly why
the tier scheduler exists. A tier is an *execution configuration*
(serving/tiers.py): the uniform analog ``n_repeats=K``, a registered
per-layer ``PrecisionProfile`` (the paper's learned per-layer precision,
§V-VI — profile batches run the segmented layer scan, their executables
are cache-keyed on the profile's repeat tuple, and their energy/token is
the true ``sum_l K_l * E_l * MACs_l``), or a registered custom tier such
as the weight-only ``Int8DigitalTier`` — all three are implementations of
one ``ExecutionTier`` interface resolved through the engine-owned
``TierRegistry``, so analog and digital traffic serve side by side in one
engine with per-tier executables, params, energy models, and degradation
ladders.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.analog import AnalogConfig, raw_key
from repro.core.profile import PrecisionProfile
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving import trace
from repro.serving.bucketing import (
    DEFAULT_BATCH_BUCKETS,
    DEFAULT_SEQ_BUCKETS,
    bucket_shape,
    next_bucket,
    pad_to_bucket,
    pool_shape,
)
from repro.serving.cache import (
    ExecutableBuildError,
    ExecutableCache,
    mesh_fingerprint,
    spec_tree,
)
from repro.serving.faults import (
    BoundedLog,
    FaultPlan,
    QueueFull,
    TransientExecutableFault,
)
from repro.serving.policy import PolicyConfig, PrecisionGovernor
from repro.serving.pool import DecodePool
from repro.serving.scheduler import Request, TierScheduler
from repro.serving.tiers import ExecutionTier, TierRegistry

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class RequestFailure:
    """Structured non-success result: a request the engine gave up on.

    Successes stay plain ``np.ndarray`` token rows; a failed or timed-out
    request resolves (exactly once, in the same results dict) to one of
    these instead — no hang, no exception swallowing a batch, no leaked
    slot. ``tokens`` carries whatever was generated before the failure
    (a timeout mid-decode keeps its partial output, a queue timeout is
    empty); partial tokens are a *prefix* of the fault-free output — the
    bit-identity contract holds for every token actually emitted.
    """

    uid: int
    tokens: np.ndarray  # tokens emitted before the failure (maybe empty)
    detail: str
    retries: int = 0

    @property
    def ok(self) -> bool:
        return False


@dataclasses.dataclass(frozen=True)
class TimedOut(RequestFailure):
    """The request's deadline passed while it was queued or decoding."""


@dataclasses.dataclass(frozen=True)
class Failed(RequestFailure):
    """The request hit an injected/transient fault and ran out of retries."""


#: what poll()/flush() map a uid to: a token row, or a structured failure
RequestResult = Union[np.ndarray, RequestFailure]


def _bytes_a_chip(leaves, shardings) -> int:
    """Bytes one chip holds of ``leaves`` laid out by ``shardings``."""
    return sum(
        int(np.prod(sh.shard_shape(a.shape))) * a.dtype.itemsize
        for a, sh in zip(leaves, shardings)
    )


class ServingEngine:
    """Serves mixed-precision generation traffic over a frozen analog model.

    ``analog_cfg=None`` serves the digital model (same batching machinery,
    no noise). ``energies`` is an ``init_energy_tree``-shaped allocation —
    per-site energy at K=1; a tier's total spend is ``K * energy`` (uniform)
    or ``sum_l K_l * E_l * MACs_l`` for a per-layer profile tier
    (``profiles`` / ``register_profile`` / ``submit(profile=...)``).

    ``analog_cfg`` and ``energies`` are FROZEN for the engine's lifetime:
    they are baked into every compiled executable as trace-time constants
    (the cache key doesn't cover them), so mutation would silently serve
    stale energies from warm buckets. ``energies`` is a read-only property;
    a recalibrated allocation means a new engine. ``params`` are runtime
    arguments and may be swapped freely.

    ``continuous=True`` switches decode to persistent per-tier slot pools
    (see the module docstring): ``pool_slots`` sizes each pool (default:
    the largest batch bucket), and the pool cache length defaults to
    ``max(seq_buckets) + max_gen`` so any admissible request fits any slot.
    Every pool step attends over the full pool cache, so SIZE THE SEQ
    LADDER (or pass ``pool_cache_len``) TO YOUR TRAFFIC: with the default
    1024-top ladder, short-prompt traffic would decode against a ~1056-slot
    cache each step and hand the throughput win back. A smaller
    ``pool_cache_len`` is enforced at submit — a request whose seq bucket
    plus decode budget can't fit a slot is rejected with the resize advice
    (pool-shape *ladders* are future work, see ROADMAP). ``max_entries``
    optionally LRU-bounds the executable cache — pool shapes multiply the
    key space, so long-lived multi-tier engines may want a cap (default
    unbounded).
    """

    def __init__(
        self,
        params,
        model_cfg: ModelConfig,
        *,
        analog_cfg: Optional[AnalogConfig] = None,
        energies=None,
        max_gen: int = 32,
        max_batch: int = 8,
        max_wait: float = 0.05,
        batch_buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS,
        seq_buckets: Sequence[int] = DEFAULT_SEQ_BUCKETS,
        pad_id: int = 0,
        seed: int = 0,
        profiles: Optional[Sequence[PrecisionProfile]] = None,
        continuous: bool = False,
        pool_slots: Optional[int] = None,
        pool_cache_len: Optional[int] = None,
        max_entries: Optional[int] = None,
        max_queue: Optional[int] = None,
        fault_plan: Optional[FaultPlan] = None,
        max_retries: int = 1,
        k_ladder: Sequence[int] = (1, 2, 4, 8),
        fault_log_maxlen: Optional[int] = 4096,
        policy: Optional[PolicyConfig] = None,
        metrics=None,
        mesh=None,
    ):
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if not k_ladder or any(int(k) < 1 for k in k_ladder):
            raise ValueError(f"k_ladder must be positive Ks, got {k_ladder}")
        if analog_cfg is not None and energies is None:
            raise ValueError("analog serving requires an energy tree")
        if continuous and model_cfg.family == "moe":
            raise ValueError(
                "continuous batching is unavailable for the moe family: "
                "analog expert sites draw a batch-level noise stream "
                "(capacity buffers mix requests), so in-flight admission/"
                "retirement would change a request's noise mid-stream; "
                "serve MoE batch-synchronously (continuous=False)"
            )
        self.params = params
        self.model_cfg = model_cfg
        self.analog_cfg = analog_cfg
        self._energies = energies
        #: the tier registry (serving/tiers.py): the ONE component that
        #: maps tier ids — uniform K ints, profile names, custom digital
        #: tier ids — to ExecutionTier objects (executable factory, cache
        #: identity, params, energy model, degradation ladder). Add-only,
        #: like the profile store it subsumes.
        self.tiers = TierRegistry(self)
        for p in profiles or ():
            self.register_profile(p)
        self.max_gen = max_gen
        self.batch_buckets = tuple(batch_buckets)
        self.seq_buckets = tuple(seq_buckets)
        self.pad_id = pad_id
        self.scheduler = TierScheduler(
            max_batch=min(max_batch, max(batch_buckets)),
            max_wait=max_wait,
            seq_buckets=seq_buckets,
            max_queue=max_queue,
        )
        #: injection schedule (serving/faults.py); clearing it to None
        #: mid-run models repaired hardware — every site (including the
        #: cache's executable guard, which reads it dynamically) goes quiet
        self.fault_plan = fault_plan
        self.max_retries = int(max_retries)
        self.k_ladder = tuple(sorted({int(k) for k in k_ladder}))

        def _exe_guard(key):
            if self.fault_plan is not None:
                self.fault_plan.check_executable(key)

        self.exe_cache = ExecutableCache(
            max_entries=max_entries,
            fault_hook=_exe_guard if fault_plan is not None else None,
        )
        self.continuous = bool(continuous)
        self.pool_slots, self.pool_cache_len = pool_shape(
            pool_slots if pool_slots is not None else max(batch_buckets),
            seq_buckets,
            max_gen,
        )
        if pool_cache_len is not None:
            # explicit pool sizing for traffic shorter than the seq ladder's
            # top: requests that can't fit a slot are rejected at submit
            if pool_cache_len <= min(seq_buckets):
                raise ValueError(
                    f"pool_cache_len={pool_cache_len} can't hold even a "
                    f"minimum-bucket prompt ({min(seq_buckets)}) plus one "
                    "generated token"
                )
            self.pool_cache_len = int(pool_cache_len)
        #: tier -> persistent DecodePool, created lazily at first admission
        self._pools: Dict[object, DecodePool] = {}
        #: attached device mesh (tensor-parallel serving) + its AOT-key
        #: fingerprint; () unmeshed so legacy cache keys are unchanged
        self._mesh = None
        self._mesh_key: tuple = ()
        self._base_key = raw_key(jax.random.PRNGKey(seed))
        self._param_specs = spec_tree(params)
        self._uid = 0
        self._clock: Optional[str] = None  # "real" | "virtual", set on first use
        self._traces = 0  # incremented at trace time inside the step fns
        #: realized noise-std drift factor: 1.0 is nominal (bit-identical to
        #: an engine without the knob — the executables divide energies by
        #: scale**2 as a runtime operand, and x/1.0 is IEEE-exact)
        self._noise_scale = 1.0
        #: drift response: when set, newly submitted uniform-K requests are
        #: promoted one rung up the k_ladder until recalibrate() clears it
        self._promoted = False
        #: monotone per-decode-step-attempt counter — the fault plan's clock
        #: (advances on stalled steps too, so schedules can't wedge a drain)
        self._fault_clock = 0
        self.stats = {
            "requests": 0,
            "batches": 0,
            "tokens_generated": 0,
            "padded_rows": 0,
            "decode_steps": 0,
            # decode work actually dispatched, in row-slots (steps x batch
            # rows, or steps x pool slots): the structural quantity
            # continuous batching shrinks on heterogeneous traffic
            "decode_slot_steps": 0,
            # of those, row-slots that carried a live request (pool only)
            "active_slot_steps": 0,
            "admitted": 0,  # requests admitted into a pool slot
            "retired": 0,  # pool retirements (budget hit or stop id)
            # fault tolerance: structured-failure and degradation counters
            "timed_out": 0,  # requests retired past their deadline
            "failed": 0,  # requests that exhausted fault retries
            "retried": 0,  # fault-triggered resubmissions
            "stalled_steps": 0,  # pool decode steps lost to injected stalls
            "exe_faults": 0,  # transient executable failures absorbed
            "exe_errors": 0,  # unexpected executable exceptions contained
            "poisoned_rows": 0,  # corrupted decode rows detected + retired
            "cancelled": 0,  # requests withdrawn via cancel()
            "promotions": 0,  # drift-response tier promotions activated
            # SLA policy (serving/policy.py) + bounded-log accounting
            "shed": 0,  # submissions rejected by the governor's last rung
            "demoted": 0,  # queued requests retiered down under pressure
            "promoted_back": 0,  # queued requests restored after the drain
            "policy_transitions": 0,  # governor mode flips (dwell-gated)
            "dropped_events": 0,  # fault_log entries evicted by the bound
            # per-tier realized work: tier -> generated tokens / decode
            # steps dispatched (the energy-attribution surface: multiply by
            # tier_energy_per_token for realized spend)
            "tier_tokens": {},
            "tier_decode_steps": {},
        }
        #: engine-side record of every fault consequence and policy action:
        #: which uids were retried/failed/timed out/retiered, and every
        #: drift response — the bench and tests derive the affected-request
        #: set from this. Ring-bounded (``fault_log_maxlen``): evictions
        #: are counted in stats["dropped_events"], never silently lost.
        self.fault_log: List[dict] = BoundedLog(
            maxlen=fault_log_maxlen, on_drop=self._note_dropped_events
        )
        #: uid -> tier the request was actually dispatched at (set when it
        #: enters a prefill batch; governor demotions land *before*
        #: dispatch, so this is the ground truth for accuracy-floor audits
        #: and the bench's realized accuracy proxy). A fault retry that
        #: re-dispatches at a promoted tier overwrites its entry.
        self.served_tiers: Dict[int, object] = {}
        #: streaming observability feed (monitor.MetricsFeed or anything
        #: with a ``record(engine, now=...)`` method): sampled once per
        #: pump/poll round — the per-tier time-series surface
        self.metrics = metrics
        #: SLA-aware precision governor (None without a policy config)
        self.governor: Optional[PrecisionGovernor] = None
        if policy is not None:
            self.governor = PrecisionGovernor(self, policy)
        if mesh is not None:
            self.attach_mesh(mesh)

    def _note_dropped_events(self, n: int) -> None:
        """BoundedLog eviction hook: surface ring-buffer drops as a stat."""
        self.stats["dropped_events"] += n

    def _bump_tier(self, stat: str, tier, n: int) -> None:
        """Accumulate per-tier realized work (tokens / decode steps)."""
        d = self.stats[stat]
        d[tier] = d.get(tier, 0) + n

    # -- request intake ------------------------------------------------------

    def _now(self, now: Optional[float], phase: str) -> float:
        """Resolve a timestamp, pinning the engine to one clock domain.

        Deadlines compare submit arrivals against poll times, so mixing the
        real clock (``now=None``) with caller-supplied virtual times would
        silently dispatch everything immediately (or never) — rejected
        instead. A fully drained engine (no pending requests) holds no
        timestamps to compare against, so it may re-pin to the other clock:
        a finished virtual-time replay can be reused live, and vice versa.
        """
        mode = "real" if now is None else "virtual"
        if self._clock is None or (
            self._clock != mode and self.scheduler.n_pending == 0
        ):
            self._clock = mode
        elif self._clock != mode:
            raise ValueError(
                f"{phase}() used the {mode} clock but this engine is on the "
                f"{self._clock} clock with requests pending; pass `now` "
                f"consistently (or never), or drain before switching"
            )
        return time.monotonic() if now is None else now

    def register_profile(self, profile: PrecisionProfile) -> str:
        """Register a per-layer repeat schedule as a servable tier.

        Validates the schedule against the model's layer layout. The registry
        is add-only: re-registering a name with a *different* schedule is
        rejected (profiles are baked into executable cache keys, so renaming
        a schedule in place would silently serve the old trace). Returns the
        tier id (the profile's name) for ``submit(profile=...)``.
        """
        return self.tiers.register_profile(profile)

    def register_tier(self, tier: ExecutionTier):
        """Register a custom execution tier (e.g. ``Int8DigitalTier``) as
        a servable tier id for ``submit(tier=...)`` — the plug point for
        execution domains beyond analog K-repeats. Add-only, same AOT
        contract as profiles. Returns the tier id."""
        return self.tiers.register(tier)

    def submit(
        self,
        tokens,
        *,
        n_repeats: int = 1,
        profile=None,
        tier=None,
        max_new_tokens: Optional[int] = None,
        stop_tokens: Sequence[int] = (),
        key: Optional[Array] = None,
        now: Optional[float] = None,
        deadline: Optional[float] = None,
        target_latency: Optional[float] = None,
        accuracy_floor: Optional[float] = None,
        max_degradation: Optional[float] = None,
    ) -> int:
        """Enqueue one request; returns its uid (results key in poll()).

        ``profile`` selects a per-layer precision tier: a registered tier id
        or a ``PrecisionProfile`` (auto-registered). Mutually exclusive with
        ``n_repeats``; a *uniform* profile degenerates to the equivalent
        ``n_repeats=K`` tier (identical trace, shared executables, shared
        batches). Digital engines ignore both — K is a no-op without noise.

        ``tier`` is the general form: any registered tier id (a uniform K
        int, a profile name, or a custom tier id such as the int8 digital
        tier's — see ``register_tier``), a ``PrecisionProfile``, or an
        ``ExecutionTier`` instance (auto-registered). Mutually exclusive
        with the two legacy knobs above; unlike them it is honored on
        digital engines too (an explicitly requested digital tier is not
        an analog precision knob to coalesce away).

        ``stop_tokens``: EOS-style ids. Greedy decode finishes the request
        the step it emits one (the stop id is included as the last output
        token); without any, the request runs its full ``max_new_tokens``.

        ``deadline``: absolute timestamp (same clock domain as ``now``)
        past which the request is retired with a structured ``TimedOut``
        result — empty if still queued, the partial output if mid-decode.
        Deadlines are enforced on clocked ``poll``/``pump_step`` calls;
        ``flush()`` drains everything and checks none (like ``max_wait``).

        SLO fields (the precision governor's inputs, serving/policy.py):
        ``target_latency`` is a *relative* latency target in seconds from
        arrival — it defaults ``deadline`` to ``arrival + target_latency``
        when no explicit deadline is given, and feeds the governor's
        deadline-headroom urgency signal. ``accuracy_floor`` bounds how far
        the governor may demote this request under overload (the minimum
        acceptable tier accuracy); ``max_degradation`` expresses the same
        floor relative to the *requested* tier's measured accuracy
        (``floor = acc(requested tier) - max_degradation``, the paper's
        degradation form — requires a governor whose table prices the
        requested tier). Without a governor the floors are inert metadata
        and ``target_latency`` still arms the deadline.

        Raises :class:`~repro.serving.faults.QueueFull` when the scheduler
        queue is at its ``max_queue`` high-water mark (backpressure), when
        the governor is **shedding** (the policy's last rung: every queued
        request is already at its accuracy floor and pressure is still
        above the shed threshold), and
        ``ValueError`` for requests the engine could never serve: an empty
        prompt, a prompt longer than the largest seq bucket, or a
        ``max_new_tokens`` outside ``[1, max_gen]`` (the decode budget is
        part of every compiled cache length — silently clamping it would
        return fewer tokens than asked for). ``max_new_tokens=None`` (the
        default) requests the full ``max_gen`` budget.
        """
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            raise ValueError(
                "empty prompt: a request must carry at least one token "
                "(there is no position to continue generation from)"
            )
        if tokens.size > max(self.seq_buckets):
            raise ValueError(
                f"prompt of {tokens.size} tokens exceeds the largest seq "
                f"bucket ({max(self.seq_buckets)}); extend seq_buckets or "
                "truncate the prompt"
            )
        if max_new_tokens is None:
            max_new_tokens = self.max_gen  # default: the full decode budget
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if max_new_tokens > self.max_gen:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} exceeds this engine's "
                f"decode budget max_gen={self.max_gen} (cache lengths are "
                "compiled around it); raise max_gen or lower the request"
            )
        if n_repeats < 1:
            raise ValueError(f"n_repeats must be >= 1, got {n_repeats}")
        if target_latency is not None and target_latency <= 0.0:
            raise ValueError(
                f"target_latency must be > 0 seconds, got {target_latency}"
            )
        if accuracy_floor is not None and max_degradation is not None:
            raise ValueError(
                "pass either accuracy_floor or max_degradation, not both: "
                "max_degradation is the floor expressed relative to the "
                "requested tier's accuracy"
            )
        if max_degradation is not None:
            if max_degradation < 0.0:
                raise ValueError(
                    f"max_degradation must be >= 0, got {max_degradation}"
                )
            if self.governor is None:
                raise ValueError(
                    "max_degradation needs a policy governor: the floor is "
                    "relative to the requested tier's measured accuracy, "
                    "which lives in the governor's tier table (pass "
                    "accuracy_floor for an absolute bound instead)"
                )
        if self.continuous:
            # a pool slot must hold the prompt's seq bucket + decode budget
            sb = next_bucket(tokens.size, self.seq_buckets)
            budget = int(max_new_tokens)
            if sb + budget > self.pool_cache_len:
                raise ValueError(
                    f"request needs {sb} (seq bucket) + {budget} (decode "
                    f"budget) cache slots but the decode pools hold "
                    f"{self.pool_cache_len}; raise pool_cache_len or size "
                    "seq_buckets/max_gen to the traffic"
                )
        stop_tokens = tuple(int(t) for t in stop_tokens)
        if tier is not None:
            if profile is not None or n_repeats != 1:
                raise ValueError(
                    "pass either tier, or the legacy n_repeats/profile "
                    "knobs, not both: tier is the general form of the "
                    "same dial"
                )
            tier_id = self.tiers.resolve(tier)
        elif profile is not None:
            if n_repeats != 1:
                raise ValueError(
                    "pass either n_repeats or profile, not both: a profile "
                    "is the per-layer form of the same knob"
                )
            # a uniform coalesced profile degenerates to its bare-K tier id
            # (coalesce=False is the unrolled test oracle — its trace is
            # deliberately distinct, so it stays a profile tier)
            tier_id = self.tiers.resolve_profile(profile)
        else:
            tier_id = int(n_repeats)
        if max_degradation is not None:
            # the paper's degradation form: floor relative to the requested
            # tier's measured accuracy (raises if the tier is unpriced)
            accuracy_floor = (
                self.governor.tier_accuracy(tier_id) - float(max_degradation)
            )
        if self.governor is not None and self.governor.shedding:
            # the policy's last rung: demotion headroom is exhausted, so new
            # traffic is rejected instead of queued past every deadline
            self.stats["shed"] += 1
            self.fault_log.append({
                "kind": "shed", "clock": self._fault_clock,
                "queue_depth": self.scheduler.n_pending,
            })
            raise QueueFull(
                f"precision governor is shedding load: every queued request "
                f"is already at its accuracy floor and pressure is still "
                f"above the shed threshold ({self.scheduler.n_pending} "
                "pending); retry after the queue drains"
            )
        uid = self._uid
        self._uid += 1
        if key is None:
            key = jax.random.fold_in(self._base_key, uid)
        if tier is None and self.analog_cfg is None:
            # digital serving: K/profile are analog precision no-ops, don't
            # split batches on them (explicit tier= requests keep their tier)
            tier_id = self.tiers.base_id
        elif self._promoted:
            # drift response: serve new traffic one rung up its tier's own
            # ladder until recalibration clears the event (queued/in-flight
            # requests keep their tier — their noise keys already bind them;
            # profile and drift-exempt digital tiers pass through unchanged)
            tier_id = self.tiers.drift_promote(tier_id)
        arrival = self._now(now, "submit")
        if deadline is None and target_latency is not None:
            # the SLO arms the deadline: a missed latency target surfaces as
            # a structured TimedOut (which the governor's job is to prevent)
            deadline = arrival + float(target_latency)
        req = Request(
            uid=uid,
            tokens=tokens,
            max_new_tokens=int(max_new_tokens),
            key=raw_key(key),
            arrival=arrival,
            stop_tokens=stop_tokens,
            deadline=deadline,
            target_latency=(
                None if target_latency is None else float(target_latency)
            ),
            accuracy_floor=(
                None if accuracy_floor is None else float(accuracy_floor)
            ),
        )
        req.retier(tier_id)
        with trace.span(trace.SUBMIT, uid=uid):
            self.scheduler.submit(req)
        self.stats["requests"] += 1
        return uid

    def poll(self, now: Optional[float] = None) -> Dict[int, RequestResult]:
        """Serve every request that is ready at ``now``; returns finished
        uids (token rows, or structured ``TimedOut``/``Failed`` values).
        Batch-synchronous: runs each ready batch to completion. Continuous:
        admits ready requests into pool slots and pumps masked decode steps
        — re-admitting as retirements free slots — until the pools drain
        and nothing else is deadline-ready. Requests requeued by a
        transient fault are reserved within the same call when ready."""
        now = self._now(now, "poll")
        if self.continuous:
            return self._pump(now, force=False)
        results: Dict[int, RequestResult] = self._expire_queued(now)
        if self.governor is not None:
            self.governor.step(now)
        # loop: a faulted batch requeues its requests (aged arrivals stay
        # deadline-ready), so one poll drains everything ready at `now`
        while True:
            batches = self.scheduler.pop_ready(now)
            if not batches:
                break
            for reqs in batches:
                results.update(self._run_batch(reqs))
        if self.metrics is not None:
            self.metrics.record(self, now=now)
        return results

    def cancel(self, uid: int) -> bool:
        """Withdraw a submitted request before it finishes.

        A queued request leaves the scheduler; a pooled request retires
        immediately (its slot frees for admission on the very next pump
        round) and its partial tokens are discarded. Per-request noise
        keys make this safe mid-batch: batch-mates' token streams never
        depended on the cancelled row. Returns ``False`` when the uid is
        unknown or already finished — the caller (e.g. a cluster router
        cancelling a hedged-dispatch loser) treats that as "the result
        already shipped" and dedupes it instead.
        """
        if self.scheduler.cancel(uid) is not None:
            self.stats["cancelled"] += 1
            self.fault_log.append(
                {"kind": "cancel", "where": "queue", "uids": [uid]}
            )
            return True
        for pool in self._pools.values():
            for s in pool.active_slots():
                if pool.record(s).request.uid == uid:
                    pool.retire(s)
                    self.stats["retired"] += 1
                    self.stats["cancelled"] += 1
                    self.fault_log.append(
                        {"kind": "cancel", "where": "pool", "uids": [uid]}
                    )
                    return True
        return False

    def flush(self) -> Dict[int, RequestResult]:
        """Drain the queue regardless of deadlines (end of replay/shutdown)."""
        if self.continuous:
            return self._pump(None, force=True)
        results: Dict[int, RequestResult] = {}
        while self.scheduler.n_pending:  # fault retries re-enter the queue
            for reqs in self.scheduler.flush():
                results.update(self._run_batch(reqs))
        return results

    # -- graceful degradation ------------------------------------------------

    def _expire_queued(self, now: Optional[float]) -> Dict[int, RequestResult]:
        """Retire queued requests whose deadline passed (clocked calls only)."""
        out: Dict[int, RequestResult] = {}
        if now is None:
            return out
        for r in self.scheduler.pop_expired(now):
            out[r.uid] = TimedOut(
                uid=r.uid, tokens=np.zeros((0,), np.int32), retries=r.retries,
                detail=f"deadline {r.deadline:g} passed at {now:g} in queue",
            )
            self.stats["timed_out"] += 1
            self.fault_log.append(
                {"kind": "timeout", "where": "queue", "uids": [r.uid]}
            )
        return out

    def _expire_pooled(self, now: Optional[float]) -> Dict[int, RequestResult]:
        """Retire pooled requests past deadline; partial tokens are kept
        (a prefix of the fault-free output) and slots free immediately."""
        out: Dict[int, RequestResult] = {}
        if now is None:
            return out
        for pool in self._pools.values():
            for s in pool.expired(now):
                rec = pool.retire(s)
                r = rec.request
                out[r.uid] = TimedOut(
                    uid=r.uid,
                    tokens=np.asarray(rec.emitted, np.int32),
                    retries=r.retries,
                    detail=(
                        f"deadline {r.deadline:g} passed at {now:g} after "
                        f"{len(rec.emitted)} tokens"
                    ),
                )
                self.stats["timed_out"] += 1
                self.stats["retired"] += 1
                self.fault_log.append(
                    {"kind": "timeout", "where": "pool", "uids": [r.uid]}
                )
        return out

    def _fault_requeue(
        self, reqs: List[Request], kind: str, detail: str
    ) -> Dict[int, RequestResult]:
        """Handle requests whose batch hit a transient fault: one bounded
        retry from scratch at the tier's own *promoted* rung — uniform K
        goes one rung up the ladder (noise/sqrt(K) buys margin against
        whatever corrupted the batch), a profile tier promotes to a
        registered higher-accuracy tier or a per-layer re-trim, digital
        tiers retry in place (repeats buy nothing without noise) — else
        a structured ``Failed``. Partial output is discarded: a faulted
        batch's tokens are not trustworthy."""
        out: Dict[int, RequestResult] = {}
        entry = {
            "kind": kind, "clock": self._fault_clock, "detail": detail,
            "uids": [r.uid for r in reqs], "retried": [], "failed": [],
            "promoted": {},
        }
        for r in reqs:
            if r.retries < self.max_retries:
                r2 = dataclasses.replace(r, retries=r.retries + 1)
                r2.retier(self.tiers.get(r.tier).promote())
                # force: an internal requeue must never bounce off QueueFull
                self.scheduler.submit(r2, force=True)
                self.stats["retried"] += 1
                entry["retried"].append(r.uid)
                entry["promoted"][r.uid] = r2.tier
            else:
                out[r.uid] = Failed(
                    uid=r.uid, tokens=np.zeros((0,), np.int32),
                    detail=detail, retries=r.retries,
                )
                self.stats["failed"] += 1
                entry["failed"].append(r.uid)
        self.fault_log.append(entry)
        return out

    def set_noise_scale(self, scale: float) -> None:
        """Set the realized noise-std drift factor (1.0 = nominal). The
        scale is a *runtime operand* of every compiled executable — no
        retrace, and 1.0 is bit-identical to an engine without the knob."""
        if scale <= 0.0:
            raise ValueError(f"noise scale must be > 0, got {scale}")
        self._noise_scale = float(scale)

    @property
    def noise_scale(self) -> float:
        return self._noise_scale

    @property
    def promoted(self) -> bool:
        """True while the drift response is promoting new uniform-K traffic."""
        return self._promoted

    def promote_tiers(self, event=None) -> None:
        """Drift response: until :meth:`recalibrate`, newly submitted
        uniform-K requests serve one rung up the ``k_ladder`` (extra
        repeats buy back the drifted noise floor at higher energy; the
        ladder top is the calibrated bound). Typically driven by a
        ``NoiseDriftWatchdog`` event; idempotent."""
        if not self._promoted:
            self.stats["promotions"] += 1
        self._promoted = True
        self.fault_log.append(
            {"kind": "drift_promotion", "clock": self._fault_clock,
             "event": event if event is None else dataclasses.asdict(event),
             # attribution: registered tiers the response does NOT touch
             # (digital executions don't share the analog array's physics)
             "exempt_tiers": self.tiers.drift_exempt_ids()}
        )

    def recalibrate(self, *, noise_scale: float = 1.0) -> None:
        """The recalibration hook: clear the drift response and pin the
        realized noise scale (1.0 after physical recalibration; the
        measured residual factor if the hardware can only partially
        correct). New submissions return to their requested tiers."""
        self._promoted = False
        self.set_noise_scale(noise_scale)
        self.fault_log.append(
            {"kind": "recalibrated", "clock": self._fault_clock,
             "noise_scale": float(noise_scale)}
        )

    def _sync_noise_scale(self) -> None:
        """Pull the fault plan's drift factor at the current fault clock."""
        if self.fault_plan is not None and self.fault_plan.drift is not None:
            self._noise_scale = self.fault_plan.noise_scale_at(self._fault_clock)

    def _scale_arr(self) -> Array:
        return jnp.asarray(self._noise_scale, jnp.float32)

    # -- mesh attach / resize ------------------------------------------------

    @property
    def mesh(self):
        """The attached device mesh (None = single-device serving)."""
        return self._mesh

    @property
    def mesh_key(self) -> tuple:
        """The mesh fingerprint appended to every AOT cache key (() unmeshed)."""
        return self._mesh_key

    def attach_mesh(self, mesh) -> None:
        """Attach (or resize to) a device mesh for tensor-parallel serving.

        The weights stay sharded: each leaf is laid out by
        ``sharding.serving_spec`` — every analog weight of the layer stack
        split by its columns over the mesh's "model" axis, the embedding,
        head and norm scales as ``DEFAULT_RULES`` gives them. A leaf that
        already lies so (drawn in its shards, as ``tree_shardings(...,
        DEFAULT_RULES)`` draws all but ``wo`` and ``w_down``) is kept as
        given; any other is re-laid, one leaf at a time, so the move holds at
        most one leaf's old and new shards at once. The AOT argument specs
        carry each leaf's sharding, and ``analog_dot``'s shard_map takes
        each chip's column shard as it lies, salting its counter-based noise
        on global tile coordinates, so a mesh engine's tokens are
        bit-identical to the single-device oracle. Everything else at the
        jit boundary — decode-pool caches, batch inputs, outputs — stays
        *replicated* (``SERVING_RULES``). Cache keys carry the mesh
        fingerprint and the weights' layout: a resize compiles fresh entries
        once, then serves at a 100% hit rate again (and a resize back to a
        previous mesh re-hits its warm entries).

        Resizing requires a drained engine (no queued or pooled requests):
        live decode state is pinned to the old mesh's devices. Pools are
        dropped and lazily rebuilt replicated on the new mesh — empty pools
        hold no request state, so nothing is lost. ``attach_mesh(None)``
        detaches (back to single-device serving).
        """
        if self.n_in_flight:
            raise ValueError(
                f"cannot attach/resize a mesh with {self.n_in_flight} "
                "requests in flight (their decode state is pinned to the "
                "current devices); drain with flush() first"
            )
        self._mesh = mesh
        self._pools.clear()  # rebuilt lazily, replicated on the new mesh
        if mesh is None:
            self._param_specs = spec_tree(self.params)
            self._mesh_key = ()
            return
        from repro.models import sharding as shardlib

        layout = shardlib.serving_shardings(
            lm.param_axes(self.model_cfg), self.params, mesh
        )
        self.params = self._lay_out_weights(layout)
        self._param_specs = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            self.params, layout,
        )
        self._mesh_key = mesh_fingerprint(
            mesh, [sh.spec for sh in jax.tree.leaves(layout)]
        )

    def _lay_out_weights(self, layout):
        """The weights placed by ``layout`` (a tree of shardings): each leaf
        that lies so already kept, each other re-laid and waited for before
        the next (``attach_mesh``)."""
        leaves, treedef = jax.tree.flatten(self.params)
        targets = treedef.flatten_up_to(layout)
        keep = [
            isinstance(a, jax.Array) and a.sharding.is_equivalent_to(sh, a.ndim)
            for a, sh in zip(leaves, targets)
        ]
        out = []
        with trace.span(trace.ATTACH_MESH, kept=sum(keep), relaid=len(keep) - sum(keep),
                        weight_bytes_per_chip=_bytes_a_chip(leaves, targets)):
            for a, sh, k in zip(leaves, targets, keep):
                if not k:
                    a = jax.block_until_ready(jax.device_put(a, sh))
                out.append(a)
        return treedef.unflatten(out)

    @property
    def weight_bytes_per_chip(self) -> int:
        """Bytes of weights each chip holds: one shard of every leaf (the
        whole leaf where it is not split, and every leaf unmeshed)."""
        leaves = jax.tree.leaves(self.params)
        return _bytes_a_chip(leaves, [a.sharding for a in leaves])

    def _replicated_sharding(self):
        """NamedSharding(mesh, P()) when a mesh is attached, else None."""
        if self._mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self._mesh, PartitionSpec())

    def _replicate(self, tree):
        """device_put a tree replicated onto the attached mesh (identity
        unmeshed) — run once at attach/pool-build time, never per dispatch."""
        sh = self._replicated_sharding()
        if sh is None:
            return tree
        return jax.device_put(tree, sh)

    def _mesh_ctx(self):
        """Ambient-mesh context the tier builders lower under: the attached
        mesh with every activation axis replicated (``SERVING_RULES``),
        which is what routes analog matmuls through the tensor-parallel
        shard_map at trace time. A no-op context unmeshed."""
        if self._mesh is None:
            return contextlib.nullcontext()
        from repro.models import sharding as shardlib

        return shardlib.use_mesh(self._mesh, shardlib.SERVING_RULES)

    # -- execution -----------------------------------------------------------
    # the executable builders and cache-key identity live on the tiers
    # themselves (serving/tiers.py): the engine only composes
    # ``tiers.exe_key(phase, tier, *shape)`` with ``tier.build_*`` and
    # dispatches ``tier.params`` — it never inspects what kind of tier it
    # is holding (the lint test in tests/test_tiers.py keeps it that way)

    def _keys_spec(self, bb: int) -> jax.ShapeDtypeStruct:
        """Spec for a stacked raw-key batch, sized from the actual key impl
        (threefry keys are 2 uint32 words; other impls differ)."""
        sh = self._replicated_sharding()
        if sh is None:
            return jax.ShapeDtypeStruct(
                (bb,) + self._base_key.shape, self._base_key.dtype
            )
        return jax.ShapeDtypeStruct(
            (bb,) + self._base_key.shape, self._base_key.dtype, sharding=sh
        )

    def _batch_keys(self, reqs: List[Request], bb: int) -> Array:
        rows = [r.key for r in reqs]
        # batch-padding rows get a fixed key; their outputs are discarded,
        # per-request streams keep them from touching real rows, and the
        # batch-level MoE expert fold excludes length-0 rows entirely
        # (collapse_keys valid mask), so the pad count never changes noise
        rows += [raw_key(jax.random.PRNGKey(0))] * (bb - len(reqs))
        return jnp.stack([jnp.asarray(k, self._base_key.dtype) for k in rows])

    def _prefill_batch(self, reqs: List[Request], cache_len: Optional[int] = None):
        """Shared prefill dispatch: pad into a bucket, run the AOT prefill
        at ``cache_len`` (default: the batch-synchronous ``sb + max_gen``;
        continuous admission passes the pool's cache length), returning
        ((bucket, cache_len), cache, first tokens). The tokens stay a
        device array — only callers that need host values (admission
        bookkeeping, stop-id checks) should materialize them, so the
        batch-synchronous path keeps enqueueing work without a sync."""
        tier = reqs[0].tier
        assert all(r.tier == tier for r in reqs), "mixed-tier batch"
        for r in reqs:  # dispatch point: the tier is now bound (see ctor)
            self.served_tiers[r.uid] = tier
        t = self.tiers.get(tier)
        bb, sb = bucket_shape(
            len(reqs), max(r.prompt_len for r in reqs),
            batch_buckets=self.batch_buckets, seq_buckets=self.seq_buckets,
        )
        if cache_len is None:
            cache_len = sb + self.max_gen
        with trace.span(
            trace.PREFILL, tier=lambda: trace.text(tier), bb=bb, sb=sb,
            tokens=lambda: sum(r.prompt_len for r in reqs),
            uids=lambda: " ".join(str(r.uid) for r in reqs),
        ):
            tokens_np, lengths_np = pad_to_bucket(
                [r.tokens for r in reqs], (bb, sb), pad_id=self.pad_id
            )
            keys = self._batch_keys(reqs, bb)
            prefill_exe = self.exe_cache.get(
                self.tiers.exe_key("prefill", tier, bb, sb, cache_len),
                lambda: t.build_prefill(bb, sb, cache_len),
            )
            self._sync_noise_scale()
            cache, tok = prefill_exe(
                t.params, jnp.asarray(tokens_np), jnp.asarray(lengths_np), keys,
                self._scale_arr(),
            )
        self.stats["batches"] += 1
        self.stats["padded_rows"] += bb - len(reqs)
        return (bb, sb, cache_len), keys, cache, tok

    # -- batch-synchronous execution ----------------------------------------

    def _run_batch(self, reqs: List[Request]) -> Dict[int, RequestResult]:
        tier = reqs[0].tier
        exec_tier = self.tiers.get(tier)
        try:
            (bb, _sb, cache_len), keys, cache, tok = self._prefill_batch(reqs)
        except TransientExecutableFault as f:
            self.stats["exe_faults"] += 1
            return self._fault_requeue(reqs, "exe_fault", str(f))
        except ExecutableBuildError:
            raise  # a program the compiler refuses fails on every retry
        except Exception as e:  # noqa: BLE001 - serving must not crash
            # an executable raising anything else mid-batch is contained
            # the same way: the batch retires into the bounded-retry path
            # (structured Failed once retries exhaust), never a crashed
            # serving loop with requests stranded in limbo
            self.stats["exe_errors"] += 1
            return self._fault_requeue(reqs, "exe_error", repr(e))
        lengths = jnp.asarray([r.prompt_len for r in reqs] + [0] * (bb - len(reqs)),
                              jnp.int32)
        toks = [tok]
        stop_sets = [r.stop_set for r in reqs]
        has_stops = any(stop_sets)
        n_steps = max(r.max_new_tokens for r in reqs) - 1
        if has_stops:  # host read only when EOS is in play
            tok0 = np.asarray(tok)
            emitted = [1] * len(reqs)
            done = [
                emitted[i] >= r.max_new_tokens or int(tok0[i]) in stop_sets[i]
                for i, r in enumerate(reqs)
            ]
        steps_run = 0
        if n_steps > 0:  # single-token batches never need the decode exe
            decode_exe = self.exe_cache.get(
                self.tiers.exe_key("decode", tier, bb, cache_len),
                lambda: exec_tier.build_decode(bb, cache_len),
            )
        for t in range(n_steps):
            if has_stops and all(done):
                break  # EOS early exit: every real row hit budget or stop id
            pos = lengths + t
            self._fault_clock += 1
            self._sync_noise_scale()
            try:
                tok, cache = decode_exe(
                    exec_tier.params, cache, tok[:, None], pos, lengths, keys,
                    self._scale_arr(),
                )
            except TransientExecutableFault as f:
                # pre-dispatch guard: the donated cache was not consumed,
                # but a faulted batch's partial tokens are discarded — the
                # whole batch retries from scratch (or fails, bounded)
                self.stats["exe_faults"] += 1
                self.stats["decode_steps"] += steps_run
                self.stats["decode_slot_steps"] += steps_run * bb
                return self._fault_requeue(reqs, "exe_fault", str(f))
            except Exception as e:  # noqa: BLE001 - serving must not crash
                self.stats["exe_errors"] += 1
                self.stats["decode_steps"] += steps_run
                self.stats["decode_slot_steps"] += steps_run * bb
                return self._fault_requeue(reqs, "exe_error", repr(e))
            toks.append(tok)
            steps_run += 1
            if has_stops:  # per-step host read only when EOS is in play
                tok_np = np.asarray(tok)
                for i, r in enumerate(reqs):
                    if not done[i]:
                        emitted[i] += 1
                        done[i] = (
                            emitted[i] >= r.max_new_tokens
                            or int(tok_np[i]) in stop_sets[i]
                        )

        seq = np.stack([np.asarray(t) for t in toks], axis=1)  # (bb, steps+1)
        out: Dict[int, np.ndarray] = {}
        for i, r in enumerate(reqs):
            row = seq[i, : min(r.max_new_tokens, seq.shape[1])]
            if stop_sets[i]:
                hits = np.flatnonzero(np.isin(row, list(stop_sets[i])))
                if hits.size:  # the stop id is the last emitted token
                    row = row[: hits[0] + 1]
            out[r.uid] = row.copy()
            self.stats["tokens_generated"] += int(row.size)
            self._bump_tier("tier_tokens", tier, int(row.size))
        self.stats["decode_steps"] += steps_run
        self.stats["decode_slot_steps"] += steps_run * bb
        self._bump_tier("tier_decode_steps", tier, steps_run)
        return out

    # -- continuous execution: persistent per-tier decode slot pools ---------

    def _pool(self, tier) -> DecodePool:
        pool = self._pools.get(tier)
        if pool is None:
            pool = DecodePool(
                tier=tier,
                slots=self.pool_slots,
                cache_len=self.pool_cache_len,
                key_shape=self._base_key.shape,
                key_dtype=self._base_key.dtype,
                cache=lm.init_cache(
                    self.model_cfg, self.pool_slots, self.pool_cache_len
                ),
                exec_tier=self.tiers.get(tier),
            )
            # mesh serving: the pool cache lives replicated on every shard
            # from birth, so the first donated decode/insert call already
            # matches its executable's pinned input sharding
            pool.place_cache(self._replicate)
            self._pools[tier] = pool
        return pool

    def _renew_donated_cache(
        self, pool: DecodePool, detail: str
    ) -> Dict[int, RequestResult]:
        """After a decode or insert executable raised: if the call had
        already consumed the pool's donated cache, every active row lost
        its state. Those rows retire into the bounded-retry path and the
        pool gets a fresh cache, so no later call reads a deleted buffer."""
        if not any(a.is_deleted() for a in jax.tree.leaves(pool.cache)):
            return {}
        reqs = [pool.retire(s).request for s in pool.active_slots()]
        self.stats["retired"] += len(reqs)
        pool.cache = lm.init_cache(self.model_cfg, pool.slots, pool.cache_len)
        pool.place_cache(self._replicate)
        return self._fault_requeue(reqs, "exe_error", detail)

    @property
    def n_in_flight(self) -> int:
        """Requests submitted but not yet finished: queued + pooled."""
        return self.scheduler.n_pending + sum(
            p.n_active for p in self._pools.values()
        )

    def pump_step(
        self, now: Optional[float] = None, *, force: bool = False
    ) -> Dict[int, RequestResult]:
        """One continuous-scheduling iteration (the unit real serving loops
        and latency measurements want): admit deadline-ready requests into
        free slots (all pending requests when ``force``), then run ONE
        masked decode step across every pool with active slots. Returns the
        requests finished this iteration."""
        if not self.continuous:
            raise ValueError("pump_step() requires continuous=True")
        now = self._now(now, "poll")
        results, _ = self._pump_once(now, force)
        return results

    def _pump(self, now: Optional[float], force: bool) -> Dict[int, RequestResult]:
        results: Dict[int, RequestResult] = {}
        while True:
            step_results, progressed = self._pump_once(now, force)
            results.update(step_results)
            if not progressed:
                return results

    def _pump_once(self, now, force):
        """(finished requests, progressed) for one admit-then-decode round.

        Admission runs before decode (prefill-first: freed slots refill as
        eagerly as the scheduler's readiness rule allows — ``max_wait`` is
        the prefill/decode interleave knob), then every pool with active
        slots takes exactly one masked decode step. ``progressed`` is False
        only when nothing was admitted and no slot decoded: the caller's
        drain loop is done. Deadline expiry runs first on clocked calls
        (``now=None`` flush drains everything and times out nothing).
        """
        results: Dict[int, RequestResult] = {}
        with trace.span(trace.PUMP):
            with trace.span(trace.SCHEDULE):
                results.update(self._expire_queued(now))
                results.update(self._expire_pooled(now))
                progressed = bool(results)
                if self.governor is not None and not force:
                    # one policy step per pump round: demotions land *before*
                    # admission, so retiered requests prefill into their new
                    # tier's pool this very round (flush keeps requests
                    # as-submitted)
                    self.governor.step(now)
                free = {}
                for tier in self.scheduler.pending_tiers():
                    pool = self._pools.get(tier)
                    free[tier] = pool.n_free if pool is not None else self.pool_slots
                admissible = self.scheduler.pop_admissible(now, free, force=force)
            for reqs in admissible:
                results.update(self._admit(reqs))
                progressed = True
            for pool in self._pools.values():
                if pool.n_active:
                    results.update(self._pool_step(pool))
                    progressed = True
            if self.metrics is not None:
                # one observability sample per pump round: the feed's time base
                self.metrics.record(self, now=now)
        return results, progressed

    def _admit(self, reqs: List[Request]) -> Dict[int, RequestResult]:
        """Prefill a ready group at the pool's cache length and scatter it
        into free slots. Requests that finish at their first token (1-token
        budget, or the first token is a stop id) complete here and never
        occupy a decode slot. A transient executable fault at either
        dispatch requeues the whole admission wave (taken slots released;
        the pre-dispatch guard left the pool cache intact)."""
        pool = self._pool(reqs[0].tier)
        assert len(reqs) <= pool.n_free, "scheduler admitted beyond free slots"
        try:
            (bb, _sb, _cl), _keys, src_cache, tok0 = self._prefill_batch(
                reqs, pool.cache_len
            )
        except TransientExecutableFault as f:
            self.stats["exe_faults"] += 1
            return self._fault_requeue(reqs, "exe_fault", str(f))
        except ExecutableBuildError:
            raise  # a program the compiler refuses fails on every retry
        except Exception as e:  # noqa: BLE001 - serving must not crash
            # exception safety at admission: no slot was taken yet, so an
            # executable raising anything mid-pump leaks nothing — the
            # wave retires into the bounded-retry path exactly once
            self.stats["exe_errors"] += 1
            return self._fault_requeue(reqs, "exe_error", repr(e))
        with trace.span(trace.PREFILL_WAIT):
            tok0 = np.asarray(tok0)  # admission bookkeeping needs host values
        slots = pool.take(len(reqs))
        # prefill batch-padding rows aim past the pool: dropped by the scatter
        slot_ids = np.full((bb,), pool.slots, np.int32)
        slot_ids[: len(reqs)] = slots
        # tier-free key: the cache layout is parameter- and noise-free, so
        # one insert executable is shared across every tier's pool shape
        with trace.span(trace.INSERT, bb=bb):
            insert_exe = self.exe_cache.get(
                self.tiers.exe_key("insert", None, pool.slots, pool.cache_len, bb),
                lambda: pool.exec_tier.build_insert(pool.slots, pool.cache_len, bb),
            )
            try:
                pool.cache = insert_exe(pool.cache, src_cache, jnp.asarray(slot_ids))
            except TransientExecutableFault as f:
                for s in slots:
                    pool.release(s)
                self.stats["exe_faults"] += 1
                return self._fault_requeue(reqs, "exe_fault", str(f))
            except Exception as e:  # noqa: BLE001 - serving must not crash
                # taken slots are released before the requeue: a raising
                # insert neither leaks nor aliases pool slots
                for s in slots:
                    pool.release(s)
                self.stats["exe_errors"] += 1
                out = self._renew_donated_cache(pool, repr(e))
                out.update(self._fault_requeue(reqs, "exe_error", repr(e)))
                return out
        self.stats["admitted"] += len(reqs)
        out: Dict[int, np.ndarray] = {}
        for i, (r, s) in enumerate(zip(reqs, slots)):
            t0 = int(tok0[i])
            if r.max_new_tokens == 1 or t0 in r.stop_set:
                pool.release(s)
                out[r.uid] = np.asarray([t0], np.int32)
                self.stats["tokens_generated"] += 1
                self._bump_tier("tier_tokens", r.tier, 1)
                self.stats["retired"] += 1
            else:
                pool.activate(s, r, t0, r.key)
        return out

    def _pool_step(self, pool: DecodePool) -> Dict[int, RequestResult]:
        """One masked decode step over a whole pool: inactive slots are
        length-0 rows (inert), active rows decode at their own position
        under their own key, and rows that hit their budget or emit a stop
        id retire immediately — the freed slots are admission targets on the
        very next pump iteration.

        Fault sites live here too (injected by the engine's FaultPlan): a
        *stalled* step skips the dispatch (the latency cost of a wedged
        batch, charged to the fault clock so schedules can't stall a drain
        forever), a *transient executable fault* retires every active row
        into the bounded-retry path (pre-dispatch: the donated cache
        survives), and a *poisoned row* — any emitted token outside the
        vocab — retires just that row the step it appears (per-request
        noise keys keep batch-mates bit-identical through all of it).
        """
        plan = self.fault_plan
        clock = self._fault_clock
        self._fault_clock += 1
        if plan is not None and plan.stalled(clock):
            self.stats["stalled_steps"] += 1
            self.fault_log.append(
                {"kind": "stall", "clock": clock, "tier": pool.tier,
                 "uids": [pool.record(s).request.uid
                          for s in pool.active_slots()]}
            )
            return {}
        # the pool carries its ExecutionTier object (the registry is
        # add-only, so the reference can't drift from it)
        t = pool.exec_tier
        with trace.span(trace.DECODE, tier=lambda: trace.text(pool.tier),
                        slots=pool.slots, active=pool.n_active):
            decode_exe = self.exe_cache.get(
                self.tiers.exe_key("decode", pool.tier, pool.slots, pool.cache_len),
                lambda: t.build_decode(pool.slots, pool.cache_len),
            )
            self._sync_noise_scale()
            try:
                tok, pool.cache = decode_exe(
                    t.params,
                    pool.cache,
                    jnp.asarray(pool.tok[:, None]),
                    jnp.asarray(pool.pos),
                    jnp.asarray(pool.lengths),
                    jnp.asarray(pool.keys),
                    self._scale_arr(),
                )
            except TransientExecutableFault as f:
                self.stats["exe_faults"] += 1
                out: Dict[int, RequestResult] = {}
                reqs = []
                for s in pool.active_slots():
                    rec = pool.retire(s)
                    self.stats["retired"] += 1
                    reqs.append(rec.request)
                out.update(self._fault_requeue(reqs, "exe_fault", str(f)))
                return out
            except Exception as e:  # noqa: BLE001 - serving must not crash
                # same containment for an executable raising anything else:
                # every active row retires (slots freed, never aliased) and
                # re-enters through the bounded-retry path exactly once
                self.stats["exe_errors"] += 1
                out: Dict[int, RequestResult] = {}
                reqs = []
                for s in pool.active_slots():
                    rec = pool.retire(s)
                    self.stats["retired"] += 1
                    reqs.append(rec.request)
                out.update(self._fault_requeue(reqs, "exe_error", repr(e)))
                out.update(self._renew_donated_cache(pool, repr(e)))
                return out
        with trace.span(trace.DECODE_WAIT):
            tok_np = np.asarray(tok)
        with trace.span(trace.RETIRE):
            if plan is not None and plan.poison_map:
                tok_np = tok_np.copy()  # device views are read-only
                plan.poison_rows(clock, tok_np)  # detected below by value
            self.stats["decode_steps"] += 1
            self.stats["decode_slot_steps"] += pool.slots
            self.stats["active_slot_steps"] += pool.n_active
            self._bump_tier("tier_decode_steps", pool.tier, 1)
            out: Dict[int, RequestResult] = {}
            poisoned_reqs: List[Request] = []
            vocab = self.model_cfg.vocab_size
            for s in pool.active_slots():
                t = int(tok_np[s])
                if not 0 <= t < vocab:
                    # corrupted readout: retire the row alone; its batch-mates'
                    # noise streams never depended on it
                    rec = pool.retire(s)
                    self.stats["poisoned_rows"] += 1
                    self.stats["retired"] += 1
                    poisoned_reqs.append(rec.request)
                    continue
                rec = pool.record(s)
                rec.emitted.append(t)
                pool.tok[s] = t
                pool.pos[s] += 1
                if rec.done:
                    pool.retire(s)
                    out[rec.request.uid] = np.asarray(rec.emitted, np.int32)
                    self.stats["tokens_generated"] += len(rec.emitted)
                    self._bump_tier("tier_tokens", pool.tier, len(rec.emitted))
                    self.stats["retired"] += 1
            for r in poisoned_reqs:
                out.update(self._fault_requeue([r], "poison", "out-of-vocab token"))
        return out

    # -- introspection -------------------------------------------------------

    @property
    def energies(self):
        """The frozen energy allocation (baked into compiled executables)."""
        return self._energies

    def effective_energies(self):
        """The energy tree the hardware is *actually* delivering right now:
        registered energies divided by the realized drift factor squared
        (std ~ 1/sqrt(E)). At the nominal scale 1.0 this is the registered
        tree bit-for-bit."""
        if self._energies is None:
            raise ValueError("digital engine: no energy tree")
        s = self._noise_scale
        if s == 1.0:
            return self._energies
        return jax.tree.map(lambda e: e / (s * s), self._energies)

    def probe_apply(self):
        """``(energies, tokens, key) -> final hidden states`` over the live
        model — the calibrate-machinery apply fn the drift watchdog probes
        through. Cached on the engine (one object) so the probe's jitted
        executable compiles once; energies are runtime arguments, so
        probing at drifted energies never retraces."""
        if self.analog_cfg is None:
            raise ValueError("digital engine: nothing to probe for drift")
        fn = getattr(self, "_probe_apply_fn", None)
        if fn is None:
            params, cfg, a_cfg = self.params, self.model_cfg, self.analog_cfg

            def fn(energies, tokens, key):
                spec = lm.AnalogSpec(cfg=a_cfg, energies=energies, key=key)
                h, _ = lm.forward_hidden(
                    params, {"tokens": tokens}, cfg, mode="train", analog=spec
                )
                return h

            self._probe_apply_fn = fn
        return fn

    def probe_reference(self, tokens) -> Array:
        """Clean (digital) hidden states for a probe batch — the zero-noise
        reference the watchdog measures residual RMS against."""
        h, _ = lm.forward_hidden(
            self.params, {"tokens": jnp.asarray(tokens, jnp.int32)},
            self.model_cfg, mode="train", analog=None,
        )
        return h

    @property
    def profiles(self) -> Dict[str, PrecisionProfile]:
        """The registered per-layer precision tiers (read-only copy)."""
        return self.tiers.profiles

    @property
    def pools(self) -> Dict[object, DecodePool]:
        """The live per-tier decode pools (continuous mode; read-only copy)."""
        return dict(self._pools)

    def tier_energy_per_token(self, tier) -> float:
        """Honest energy per generated token of a tier (aJ), from the
        tier's OWN cost model: analog tiers report the true ``sum_l K_l *
        E_l * MACs_l`` over the frozen per-site energies (uniform K is the
        degenerate profile — same formula, every K_l = K), digital tiers
        report ``aj_per_mac * MACs/token`` from their per-MAC digital cost
        constant — never the analog energy tree.

        ``tier``: any registered tier id (uniform K int, profile name,
        custom tier id) or an ad-hoc ``PrecisionProfile``.
        """
        if isinstance(tier, PrecisionProfile):
            if self._energies is None:
                raise ValueError("digital engine: no energy tree to account")
            return lm.profile_token_energy(self.model_cfg, self._energies, tier)
        return float(self.tiers.get(tier).energy_per_token())

    @property
    def trace_count(self) -> int:
        """Number of jax traces performed (== executable-cache misses)."""
        return self._traces

    def cache_stats(self) -> dict:
        return self.exe_cache.stats()
