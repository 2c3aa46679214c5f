"""Pluggable execution tiers: one interface, three execution domains.

The serving stack used to know exactly two kinds of "tier": a uniform
analog repeat count K (an ``int``) and a named per-layer repeat profile
(a ``str``).  Every consumer — AOT cache keys, slot pools, the SLA
governor, fault-retry promotion, energy accounting — branched on which
kind it was holding, and the digital path hid behind a ``("digital",)``
sentinel baked into the executable keys.  This module replaces all of
that with a single abstraction:

``ExecutionTier``
    *identity*   — ``tier_id`` (the scheduler-facing id) and
    ``cache_key()`` (the executable-identity suffix: everything that
    changes the trace must be in it, nothing else may be).
    *execution*  — an AOT executable factory (``build_prefill`` /
    ``build_decode`` / ``build_insert``) plus the parameter tree those
    executables consume (``params`` / ``param_specs``; the int8 tier
    substitutes a quantized tree here).
    *economics*  — ``energy_per_token()``, an honest per-token cost:
    analog tiers price through the calibrated per-site energy tree,
    digital tiers through a per-MAC digital cost constant — never each
    other's.
    *health*     — ``accuracy`` floor metadata (the governor's ladder
    coordinate), ``drift_exempt`` (digital executions don't ride the
    analog noise-drift watchdog), and the ``promote()`` /
    ``drift_promote()`` degradation ladder used by fault retries and the
    drift response.

``TierRegistry``
    owned by the engine; the only component that maps tier ids to tier
    objects.  Uniform-K tiers materialize lazily (any ``int`` is
    servable on an analog engine), profiles register by name (add-only,
    frozen), and custom tiers — e.g. :class:`Int8DigitalTier` — plug in
    via :meth:`register`.  Everything else in ``serving/`` asks the
    registry; a lint test (``tests/test_tiers.py``) keeps the old
    branches from creeping back.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.energy import (
    DIGITAL_BF16_AJ_PER_MAC,
    DIGITAL_INT8_AJ_PER_MAC,
    total_macs,
)
from ..core.profile import PrecisionProfile
from ..models import lm
from ..quant.weights import quantize_params
from .cache import aot_compile, spec_tree

__all__ = [
    "AnalogProfileTier",
    "DigitalTier",
    "ExecutionTier",
    "Int8DigitalTier",
    "TierRegistry",
    "UniformKTier",
]


def _next_rung(k: int, ladder: Tuple[int, ...]) -> int:
    """Smallest ladder rung strictly above ``k`` (saturates at the top:
    the calibrated bound — promotion never invents an uncalibrated K)."""
    for rung in ladder:
        if rung > k:
            return rung
    return k


class ExecutionTier:
    """One servable execution configuration. Subclass and register.

    A tier is bound to exactly one engine (the registry binds it at
    registration); binding gives it access to the model config, the
    live parameter tree, and the engine's retrace audit counter. The
    base class owns the three AOT executable builders — subclasses
    customize them entirely through :meth:`analog_spec` (the noise
    model traced into the executables) and :attr:`params` /
    :attr:`param_specs` (the weight tree they consume).
    """

    #: digital executions don't share the analog array's physics: the
    #: noise-drift watchdog and the drift promotion response skip them
    drift_exempt = False

    def __init__(self, tier_id, *, accuracy: Optional[float] = None):
        self.tier_id = tier_id
        self.accuracy = None if accuracy is None else float(accuracy)
        self._engine = None

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"{type(self).__name__}({self.tier_id!r})"

    # -- binding -------------------------------------------------------------

    def _bind(self, engine) -> None:
        if self._engine is not None and self._engine is not engine:
            raise ValueError(
                f"tier {self.tier_id!r} is already bound to another engine"
            )
        self._engine = engine

    @property
    def engine(self):
        if self._engine is None:
            raise ValueError(
                f"tier {self.tier_id!r} is not registered with an engine"
            )
        return self._engine

    # -- mesh-aware lowering helpers -----------------------------------------

    def _sds(self, shape, dtype):
        """ShapeDtypeStruct pinned replicated on the engine's mesh (plain
        spec when unmeshed — the legacy lowering, byte-identical)."""
        sh = self.engine._replicated_sharding()
        if sh is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def _pin(self, specs):
        """Pin a tree of specs (an eval_shape'd cache) replicated. The
        weights' specs (``param_specs``) carry their own shardings."""
        sh = self.engine._replicated_sharding()
        if sh is None:
            return specs
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            specs,
        )

    # -- identity ------------------------------------------------------------

    def cache_key(self) -> tuple:
        """Executable-identity suffix appended to every AOT cache key.

        Must capture everything that changes the traced computation
        (repeat schedule, backend, noise kind, numeric format) and
        nothing that doesn't — two tiers with equal ``cache_key()``
        share warm executables by construction."""
        raise NotImplementedError

    # -- execution -----------------------------------------------------------

    @property
    def params(self):
        """The parameter tree this tier's executables consume."""
        return self.engine.params

    @property
    def param_specs(self):
        return self.engine._param_specs

    def analog_spec(self, keys, pos=None, noise_scale=None):
        """AnalogSpec traced into this tier's executables (None =
        noiseless digital execution). ``keys`` are the stacked
        per-request raw keys, folded with the decode position so every
        generated token draws fresh noise; ``noise_scale`` is the
        *traced* drift operand (runtime value, never a compile
        constant)."""
        return None

    def build_prefill(self, bb: int, sb: int, cache_len: int):
        eng = self.engine
        cfg = eng.model_cfg

        # the function's name names the HLO module (``jit_prefill``; likewise
        # ``jit_decode`` and ``jit_insert``), which a device trace shows
        def prefill(params, tokens, lengths, keys, noise_scale):
            eng._traces += 1  # runs at trace time only: the retrace audit
            analog = self.analog_spec(keys, noise_scale=noise_scale)
            cache, h_last = lm.prefill(
                params, {"tokens": tokens}, cfg,
                analog=analog, cache_len=cache_len, lengths=lengths,
            )
            logits = lm.logits_last(params, h_last, cfg)
            tok = jnp.argmax(logits[:, 0, 0], axis=-1).astype(jnp.int32)
            return cache, tok

        i32 = jnp.int32
        with eng._mesh_ctx():
            return aot_compile(
                prefill,
                self.param_specs,
                self._sds((bb, sb), i32),
                self._sds((bb,), i32),
                eng._keys_spec(bb),
                self._sds((), jnp.float32),
                out_shardings=eng._replicated_sharding(),
            )

    def build_decode(self, bb: int, cache_len: int):
        eng = self.engine
        cfg = eng.model_cfg

        def decode(params, cache, tok, pos, lengths, keys, noise_scale):
            eng._traces += 1
            analog = self.analog_spec(keys, pos=pos, noise_scale=noise_scale)
            logits, new_cache = lm.decode_step(
                params, cache, {"tokens": tok}, pos, cfg, analog=analog,
                lengths=lengths,
            )
            nxt = jnp.argmax(logits[:, 0, 0], axis=-1).astype(jnp.int32)
            return nxt, new_cache

        i32 = jnp.int32
        cache_specs = jax.eval_shape(lambda: lm.init_cache(cfg, bb, cache_len))
        with eng._mesh_ctx():
            return aot_compile(
                decode,
                self.param_specs,
                self._pin(cache_specs),
                self._sds((bb, 1), i32),
                self._sds((bb,), i32),
                self._sds((bb,), i32),
                eng._keys_spec(bb),
                self._sds((), jnp.float32),
                donate_argnums=(1,),
                out_shardings=eng._replicated_sharding(),
            )

    def build_insert(self, slots: int, cache_len: int, bb: int):
        """Admission scatter: prefilled cache rows (batch ``bb``) into
        the pool cache (batch ``slots``) at per-row slot ids, under jit.
        Rows pointed at slot id ``slots`` (prefill batch padding) are
        dropped. The cache layout is parameter- and noise-free, so the
        insert executable is shared across every tier (the registry
        keys it without a tier suffix)."""
        eng = self.engine
        cfg = eng.model_cfg

        def insert(pool_cache, src_cache, slot_ids):
            eng._traces += 1
            return lm.scatter_cache_rows(cfg, pool_cache, src_cache, slot_ids)

        pool_specs = jax.eval_shape(lambda: lm.init_cache(cfg, slots, cache_len))
        src_specs = jax.eval_shape(lambda: lm.init_cache(cfg, bb, cache_len))
        with eng._mesh_ctx():
            return aot_compile(
                insert,
                self._pin(pool_specs),
                self._pin(src_specs),
                self._sds((bb,), jnp.int32),
                donate_argnums=(0,),
                out_shardings=eng._replicated_sharding(),
            )

    # -- economics -----------------------------------------------------------

    def energy_per_token(self) -> float:
        """Honest energy per generated token in aJ, from this tier's own
        cost model (analog energy tree or digital per-MAC constant)."""
        raise NotImplementedError

    # -- degradation ladder --------------------------------------------------

    def promote(self):
        """Tier id a bounded-retry fault promotes this tier's requests
        to (more repeats buy margin against whatever made the first
        attempt fail). Returning ``self.tier_id`` means "retry at the
        same tier" — the digital default, where repeats buy nothing."""
        return self.tier_id

    def drift_promote(self):
        """Tier id new submissions serve at while the engine's drift
        response is active (see ``ServingEngine.promote_tiers``)."""
        return self.tier_id


class UniformKTier(ExecutionTier):
    """The paper's uniform dynamic-precision dial: every analog matmul
    runs K repeated evaluations (noise/sqrt(K) at K x energy). The id
    is the bare ``int`` K, which is also the legacy wire format —
    ``submit(n_repeats=K)`` resolves here."""

    def __init__(self, k: int, *, accuracy: Optional[float] = None):
        if k < 1:
            raise ValueError(f"n_repeats must be >= 1, got {k}")
        super().__init__(int(k), accuracy=accuracy)
        self.k = int(k)

    def cache_key(self) -> tuple:
        cfg = self.engine.analog_cfg
        return (self.k, cfg.backend, cfg.noise.kind)

    def analog_spec(self, keys, pos=None, noise_scale=None):
        eng = self.engine
        k = keys if pos is None else jax.vmap(jax.random.fold_in)(keys, pos)
        return lm.AnalogSpec(
            cfg=eng.analog_cfg, energies=eng._energies, key=k,
            n_repeats=self.k, profile=None, noise_scale=noise_scale,
        )

    def energy_per_token(self) -> float:
        eng = self.engine
        profile = PrecisionProfile.uniform(self.k, eng.model_cfg.n_layers)
        return lm.profile_token_energy(eng.model_cfg, eng._energies, profile)

    def promote(self):
        return _next_rung(self.k, self.engine.k_ladder)

    # drift response: one rung up the calibrated ladder, same as retries
    drift_promote = promote


class AnalogProfileTier(ExecutionTier):
    """A named per-layer repeat schedule (the paper's learned profile).
    The id is the profile name; the repeat tuple is frozen at
    registration (add-only), so the executable identity can't drift."""

    def __init__(self, profile: PrecisionProfile):
        super().__init__(profile.name, accuracy=profile.accuracy)
        self.profile = profile

    def cache_key(self) -> tuple:
        cfg = self.engine.analog_cfg
        if cfg is None:
            # profiles are registrable on digital engines for API parity
            # but never served there (submit coalesces to the base tier)
            return ("digital", "bf16")
        # uniform+coalesce profiles share the bare-K element with
        # UniformKTier on purpose: equal schedule => shared executables
        return (self.profile.cache_key(), cfg.backend, cfg.noise.kind)

    def analog_spec(self, keys, pos=None, noise_scale=None):
        eng = self.engine
        if eng.analog_cfg is None:
            return None
        k = keys if pos is None else jax.vmap(jax.random.fold_in)(keys, pos)
        return lm.AnalogSpec(
            cfg=eng.analog_cfg, energies=eng._energies, key=k,
            n_repeats=1, profile=self.profile, noise_scale=noise_scale,
        )

    def energy_per_token(self) -> float:
        eng = self.engine
        if eng._energies is None:
            raise ValueError("digital engine: no energy tree to account")
        return lm.profile_token_energy(eng.model_cfg, eng._energies, self.profile)

    def promote(self):
        """Fault promotion for a non-uniform schedule: prefer the
        smallest *registered* strictly-higher-accuracy tier (its
        executables are already warm), else re-trim the whole profile
        one ladder rung up per layer — never a silent collapse to
        uniform K."""
        eng = self.engine
        if self.accuracy is not None:
            best = None
            for cand in eng.tiers.registered():
                if cand is self or cand.accuracy is None:
                    continue
                if cand.accuracy > self.accuracy and (
                    best is None or cand.accuracy < best.accuracy
                ):
                    best = cand
            if best is not None:
                return best.tier_id
        ladder = eng.k_ladder
        reps = tuple(_next_rung(k, ladder) for k in self.profile.repeats)
        if reps == self.profile.repeats:
            return self.tier_id  # already at the calibrated top everywhere
        retrim = PrecisionProfile(reps, name=f"{self.profile.name}+retrim")
        return eng.tiers.register_profile(retrim)


class DigitalTier(ExecutionTier):
    """Noiseless digital execution of the engine's parameter tree.

    This is both the implicit tier of a digital engine (no analog
    config; the registry creates one as the base tier) and a
    registrable escape hatch on analog engines: an always-exact tier
    the governor can demote to across domains. Accuracy defaults to
    1.0 — digital *is* the reference the analog agreement proxy is
    measured against. Energy prices through a per-MAC digital cost
    constant when one is supplied; without one there is nothing honest
    to report and :meth:`energy_per_token` refuses."""

    drift_exempt = True

    def __init__(
        self,
        tier_id="bf16",
        *,
        aj_per_mac: Optional[float] = DIGITAL_BF16_AJ_PER_MAC,
        accuracy: Optional[float] = 1.0,
    ):
        super().__init__(tier_id, accuracy=accuracy)
        self.aj_per_mac = None if aj_per_mac is None else float(aj_per_mac)
        self._macs_per_token = None

    def cache_key(self) -> tuple:
        return ("digital", "bf16")

    def energy_per_token(self) -> float:
        if self.aj_per_mac is None:
            raise ValueError("digital engine: no energy tree to account")
        if self._macs_per_token is None:
            self._macs_per_token = float(
                total_macs(lm.energy_macs(self.engine.model_cfg, 1))
            )
        return self.aj_per_mac * self._macs_per_token


class Int8DigitalTier(DigitalTier):
    """Weight-only int8 digital execution (``quant/weights.py``).

    The executables consume a quantized parameter tree (int8 q +
    per-output-channel f32 scale, dequantized per layer-slice inside
    the model's scan — see ``lm._maybe_dequant``), re-quantized lazily
    whenever the engine's live tree is swapped. Energy prices through
    the int8 per-MAC digital constant, NOT the analog energy tree;
    accuracy defaults to 1.0 (greedy-decode agreement with the bf16
    reference is near-exact at 8 bits — pass a measured value to be
    stricter)."""

    def __init__(
        self,
        tier_id="int8",
        *,
        aj_per_mac: Optional[float] = DIGITAL_INT8_AJ_PER_MAC,
        accuracy: Optional[float] = 1.0,
    ):
        super().__init__(tier_id, aj_per_mac=aj_per_mac, accuracy=accuracy)
        self._src = None
        self._qparams = None
        self._qspecs = None

    def cache_key(self) -> tuple:
        return ("digital", "int8")

    @property
    def params(self):
        src = self.engine.params
        if self._qparams is None or self._src is not src:
            self._qparams = quantize_params(src)
            self._qspecs = spec_tree(
                self._qparams, shardings=self.engine.mesh is not None
            )
            self._src = src
        return self._qparams

    @property
    def param_specs(self):
        self.params  # materialize (and track engine param swaps)
        return self._qspecs


class TierRegistry:
    """Engine-owned map from tier ids to :class:`ExecutionTier`s.

    Add-only, like the profile store it subsumes: executables compiled
    against a tier id must stay valid for the engine's lifetime.
    Uniform-K tiers materialize lazily (any positive ``int`` is a valid
    analog tier); named tiers — profiles and custom/digital tiers —
    must be registered first. On a digital engine every numeric tier
    resolves to the single base :class:`DigitalTier` (K is a no-op
    without noise), which is how heterogeneous-K traffic coalesces
    into shared batches there."""

    def __init__(self, engine):
        self._engine = engine
        self._tiers: Dict[object, ExecutionTier] = {}
        self._profiles: Dict[str, PrecisionProfile] = {}
        self.base_id = 1
        if engine.analog_cfg is None:
            base = DigitalTier(tier_id=self.base_id, aj_per_mac=None)
            base._bind(engine)
            self._tiers[self.base_id] = base

    # -- registration --------------------------------------------------------

    def register(self, tier: ExecutionTier):
        """Register a custom tier under its ``tier_id``. Idempotent for
        the same object; re-registering a taken id is an error (the
        AOT contract: ids are frozen to their executables)."""
        if not isinstance(tier, ExecutionTier):
            raise TypeError(f"expected an ExecutionTier, got {type(tier)!r}")
        prev = self._tiers.get(tier.tier_id)
        if prev is tier:
            return tier.tier_id
        if prev is not None:
            raise ValueError(
                f"tier id {tier.tier_id!r} is frozen to an already-registered "
                "tier; pick a new id (executables compiled against it must "
                "stay valid)"
            )
        tier._bind(self._engine)
        self._tiers[tier.tier_id] = tier
        return tier.tier_id

    def register_profile(self, profile: PrecisionProfile) -> str:
        """Register (or re-confirm) a per-layer repeat profile under its
        name. Validates the schedule against the model; idempotent for
        an identical schedule, an error for a conflicting one."""
        eng = self._engine
        lm.profile_rows(eng.model_cfg, profile)  # layer-count validation
        prev = self._profiles.get(profile.name)
        if prev is not None:
            if prev.cache_key() != profile.cache_key():
                raise ValueError(
                    f"profile name {profile.name!r} is frozen to a different "
                    "repeat schedule; profiles are add-only (executables "
                    "compiled against the name must stay valid)"
                )
            return profile.name
        if profile.name in self._tiers:
            raise ValueError(
                f"tier id {profile.name!r} is frozen to an already-registered "
                "non-profile tier; pick a new profile name"
            )
        self._profiles[profile.name] = profile
        tier = AnalogProfileTier(profile)
        tier._bind(eng)
        self._tiers[profile.name] = tier
        return profile.name

    # -- resolution ----------------------------------------------------------

    def get(self, tier_id) -> ExecutionTier:
        """The tier serving ``tier_id``; lazily materializes uniform-K
        tiers on analog engines, raises for unknown named tiers."""
        tier = self._tiers.get(tier_id)
        if tier is not None:
            return tier
        if isinstance(tier_id, (int,)) and not isinstance(tier_id, bool):
            eng = self._engine
            if eng.analog_cfg is None:
                return self._tiers[self.base_id]  # K is a no-op without noise
            tier = UniformKTier(tier_id)
            tier._bind(eng)
            self._tiers[tier_id] = tier
            return tier
        raise ValueError(
            f"unknown profile {tier_id!r}; register_profile() it first"
        )

    def resolve(self, tier):
        """Normalize a submit-time ``tier=`` argument to a tier id:
        accepts a registered id, a bare uniform K, a PrecisionProfile,
        or an ExecutionTier instance (auto-registered)."""
        if isinstance(tier, ExecutionTier):
            if self._tiers.get(tier.tier_id) is not tier:
                self.register(tier)
            return tier.tier_id
        if isinstance(tier, PrecisionProfile):
            return self.resolve_profile(tier)
        self.get(tier)  # existence check (materializes uniform Ks)
        return tier

    def resolve_profile(self, profile):
        """Normalize a submit-time ``profile=`` argument to a tier id.
        A degenerate uniform+coalesce profile resolves to its bare K so
        it shares batches and executables with ``n_repeats=K`` traffic."""
        if isinstance(profile, PrecisionProfile):
            pid = self.register_profile(profile)
        else:
            pid = str(profile)
            if pid not in self._profiles:
                raise ValueError(
                    f"unknown profile {pid!r}; register_profile() it first "
                    "(or pass the PrecisionProfile itself)"
                )
        p = self._profiles[pid]
        if p.is_uniform and p.coalesce:
            return int(p.repeats[0])
        return pid

    # -- executable identity -------------------------------------------------

    def exe_key(self, phase: str, tier_id, *shape) -> tuple:
        """The full AOT cache key for one executable: phase + static
        shape + the engine's mesh fingerprint + the tier's identity
        suffix. ``tier_id=None`` builds a tier-free key (the admission
        insert, shared across tiers). The mesh fingerprint is ``()``
        unmeshed (legacy keys unchanged); on a mesh-attached engine it
        makes resharding compile fresh executables while a reshard back
        to a previous mesh hits that mesh's still-warm entries."""
        base = (phase,) + tuple(shape) + self._engine.mesh_key
        if tier_id is None:
            return base
        return base + self.get(tier_id).cache_key()

    # -- introspection -------------------------------------------------------

    @property
    def profiles(self) -> Dict[str, PrecisionProfile]:
        """Registered profiles by name (a copy; the registry is add-only)."""
        return dict(self._profiles)

    def registered(self) -> List[ExecutionTier]:
        """Every explicitly-known tier (registration order)."""
        return list(self._tiers.values())

    def ladder(self) -> List[ExecutionTier]:
        """Registered tiers with accuracy metadata, floor-ordered
        (ascending accuracy): the governor's demotion ladder spans
        analog and digital domains in one ordering."""
        tiers = [t for t in self._tiers.values() if t.accuracy is not None]
        return sorted(tiers, key=lambda t: (t.accuracy, str(t.tier_id)))

    def drift_exempt_ids(self) -> List[object]:
        return [t.tier_id for t in self._tiers.values() if t.drift_exempt]

    def drift_promote(self, tier_id):
        """Tier id a new submission serves at under the active drift
        response (digital tiers and profiles pass through unchanged)."""
        return self.get(tier_id).drift_promote()

    def __contains__(self, tier_id) -> bool:
        return tier_id in self._tiers

    def __len__(self) -> int:
        return len(self._tiers)
