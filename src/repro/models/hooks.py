"""Matmul hooks: the seam where the paper's analog execution plugs into
every model. Digital training uses the default hook; analog serving and
Eq.-14 calibration pass an AnalogHook carrying per-site energies.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.analog import (
    AnalogConfig,
    analog_dot,
    collapse_keys,
    fold_key,
    site_key,
)

Array = jax.Array


class MatmulHook:
    """Digital execution: plain matmuls (bf16/f32 per model dtype)."""

    def __call__(self, site: str, x: Array, w: Array) -> Array:
        return jnp.matmul(x, w.astype(x.dtype))

    def batched(self, site: str, x: Array, w: Array) -> Array:
        """Expert-batched matmul: (E, ..., K) @ (E, K, M)."""
        return jnp.einsum("e...k,ekm->e...m", x, w.astype(x.dtype))


@dataclasses.dataclass
class AnalogHook(MatmulHook):
    """Analog execution with per-site energies (paper §IV-V).

    ``energies`` maps site name -> scalar / (M,) per-channel / (E,) or (E, M)
    for expert-batched sites. All leaves are for the *current layer* (callers
    slice stacked (L, ...) energy trees inside their layer scan).

    ``key`` may be a single PRNG key or a *stacked* (B, ...) array of
    per-request keys (one per batch row, the serving engine's noise
    isolation): every site then draws an independent stream per row, so a
    request's output is invariant to what else shares its batch. For
    expert-batched sites, stacked keys are XOR-folded into one batch-level
    stream (``collapse_keys``) — MoE capacity buffers mix tokens from
    different requests inside one matmul, so per-request noise isolation is
    physically meaningless there and analog MoE serving is reproducible
    per batch composition rather than per request.

    Execution routes through the backend dispatch in ``analog_dot``: under
    ``cfg.backend = "pallas"`` (or "auto" on TPU with large enough shapes)
    every site runs the fused Pallas kernel — quant, matmul, K-repeat noise
    averaging and requant in one pass. ``n_repeats`` is the serving-time
    dynamic-precision knob: K repeats at the per-site energies, averaged
    in-register by the kernel (noise / sqrt(K) at zero extra HBM traffic).
    K is static in the trace, so per-layer K schedules (PrecisionProfile)
    reach this hook as one segment-constant int per layer — the layer scan in
    ``models/lm.py`` is segmented into same-K runs rather than threading a
    traced repeat array through here.

    ``valid`` (B,) bool marks the *real* rows of a stacked-key bucket batch
    (False = batch-padding row, length 0). It only affects expert-batched
    sites: pad rows fold the XOR identity into the batch-level stream, so
    the same real traffic draws the same expert noise at any pad count.

    ``noise_scale`` models hardware noise drift: a (traced) scalar factor
    multiplying the effective noise std at *every* site. All three noise
    models have std proportional to ``1/sqrt(E)`` (core/noise.py
    Eqs. 9-11), so scaling the std by ``d`` is realized exactly as serving
    at energies ``E / d**2`` — a runtime value on both backends (energy is
    a fused-kernel operand), which is what lets the serving engine drift
    the noise floor without retracing. ``None`` (the default) is the
    bit-identical nominal path.
    """

    cfg: AnalogConfig
    energies: Dict[str, Array]
    key: jax.Array
    n_repeats: int = 1
    valid: Optional[Array] = None
    noise_scale: Optional[Array] = None

    def _site_energy(self, site: str) -> Array:
        e = self.energies[site]
        if self.noise_scale is not None:
            # std ~ 1/sqrt(E): a noise-std drift factor d IS E -> E / d^2
            e = e / jnp.square(self.noise_scale)
        return e

    def __call__(self, site: str, x: Array, w: Array) -> Array:
        e = self._site_energy(site)
        k = site_key(self.key, site)
        y = analog_dot(
            x, w, cfg=self.cfg, energy=e, key=k, n_repeats=self.n_repeats, site=site
        )
        return y.astype(x.dtype)

    def batched(self, site: str, x: Array, w: Array) -> Array:
        # expert buffers mix requests: one batch-level stream (pad rows inert)
        key = collapse_keys(self.key, self.valid)
        e = self._site_energy(site)
        n_e = w.shape[0]
        e = jnp.broadcast_to(jnp.atleast_1d(e), (n_e,) + jnp.shape(e)[1:])
        keys = jax.random.split(site_key(key, site), n_e)

        def one(xe, we, ee, ke):
            return analog_dot(
                xe, we, cfg=self.cfg, energy=ee, key=ke, n_repeats=self.n_repeats,
                site=site,
            )

        y = jax.vmap(one)(x, w, e, keys)
        return y.astype(x.dtype)


@dataclasses.dataclass
class PrefixHook(MatmulHook):
    """Namespaces an inner hook's site names (repeated sublayers per group)."""

    inner: MatmulHook
    prefix: str

    def __call__(self, site: str, x: Array, w: Array) -> Array:
        return self.inner(f"{self.prefix}{site}", x, w)

    def batched(self, site: str, x: Array, w: Array) -> Array:
        return self.inner.batched(f"{self.prefix}{site}", x, w)


def hook_for_layer(
    analog_cfg: Optional[AnalogConfig],
    layer_energies: Optional[Dict[str, Array]],
    key: Optional[jax.Array],
    layer_idx,
    *,
    n_repeats: int = 1,
    valid: Optional[Array] = None,
    noise_scale: Optional[Array] = None,
) -> MatmulHook:
    """Hook for one layer: ``n_repeats`` is that layer's K (a static int —
    per-layer schedules arrive pre-sliced from the segmented scan), ``valid``
    the bucket batch's real-row mask, ``noise_scale`` the drift factor on
    every site's noise std (see AnalogHook)."""
    if analog_cfg is None or layer_energies is None:
        return MatmulHook()
    lk = fold_key(key, layer_idx)
    return AnalogHook(
        cfg=analog_cfg, energies=layer_energies, key=lk, n_repeats=n_repeats,
        valid=valid, noise_scale=noise_scale,
    )
