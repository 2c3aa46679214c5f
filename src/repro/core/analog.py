"""The analog matmul execution primitive (paper §II-C, §IV).

``analog_dot`` is the single choke-point through which every matmul in every
model runs. In ``digital`` mode it performs (optionally fake-quantized)
ordinary matmuls; in ``analog`` mode it simulates the noisy accelerator:

    quantize inputs/weights  ->  MAC array (x @ w)  ->  physical noise
    scaled by 1/sqrt(E)      ->  requantize output to 8 bits

Per the paper's Appendix A:
  * thermal/weight noise: digital 8-bit I/O (per-channel weights, per-tensor
    activations, percentile clipping for thermal), output requantized to 8b.
  * shot noise: continuous-valued inputs and weights (neuromorphic regime).

Energies may be scalar (per-layer) or per-output-channel vectors (§V).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import noise as noise_lib
from repro.core.noise import NoiseSpec
from repro.kernels.dispatch import TP_AXIS, fused_dot, resolve_backend, tile_dot
from repro.quant.affine import QuantParams, fake_quant

Array = jax.Array

PER_LAYER = "per_layer"
PER_CHANNEL = "per_channel"

#: analog dots traced under a tensor-parallel mesh (trace time, like the
#: fused kernel's ``NOISE_PLANS``): ``("column", site)`` for each
#: column-parallel instantiation, ``("fallback", site, reason)`` for each
#: that fell back to the whole product on every chip. ``site`` is the hook's
#: site name (None for a caller that names none).
SHARDED_DOTS: collections.Counter = collections.Counter()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AnalogConfig:
    """Static configuration of the simulated analog accelerator."""

    mode: str = dataclasses.field(metadata=dict(static=True), default="digital")
    noise: NoiseSpec = NoiseSpec()
    granularity: str = dataclasses.field(metadata=dict(static=True), default=PER_LAYER)
    weight_bits: Optional[float] = dataclasses.field(metadata=dict(static=True), default=8.0)
    act_bits: Optional[float] = dataclasses.field(metadata=dict(static=True), default=8.0)
    out_bits: Optional[float] = dataclasses.field(metadata=dict(static=True), default=8.0)
    #: snap energies to integer multiples of a quantum (photons / K repeats).
    discrete_energy: bool = dataclasses.field(metadata=dict(static=True), default=False)
    energy_quantum: float = dataclasses.field(
        metadata=dict(static=True), default=noise_lib.PHOTON_ENERGY_AJ
    )
    #: execution backend: "auto" picks the fused Pallas kernel when shape /
    #: platform permit (see kernels/dispatch.py), "pallas"/"jnp"/"tile"
    #: force a path ("tile" = the pure-jnp oracle with Pallas-identical
    #: counter-based noise — the stream tensor-parallel shards slice).
    backend: str = dataclasses.field(metadata=dict(static=True), default="auto")
    #: legacy alias for backend="pallas" (kept for existing configs/tests).
    use_kernel: bool = dataclasses.field(metadata=dict(static=True), default=False)

    def __post_init__(self):
        if self.mode not in ("digital", "analog"):
            raise ValueError(f"bad mode {self.mode!r}")
        if self.granularity not in (PER_LAYER, PER_CHANNEL):
            raise ValueError(f"bad granularity {self.granularity!r}")
        if self.backend not in ("auto", "pallas", "jnp", "tile"):
            raise ValueError(f"bad backend {self.backend!r}")

    @classmethod
    def shot(cls, **kw) -> "AnalogConfig":
        """Shot-noise configuration: continuous I/O (paper §VI-A)."""
        kw.setdefault("noise", NoiseSpec(kind=noise_lib.SHOT))
        return cls(
            mode="analog", weight_bits=None, act_bits=None, out_bits=None, **kw
        )

    @classmethod
    def thermal(cls, sigma_t: float = 0.01, **kw) -> "AnalogConfig":
        kw.setdefault("noise", NoiseSpec(kind=noise_lib.THERMAL, sigma=sigma_t))
        return cls(mode="analog", **kw)

    @classmethod
    def weight(cls, sigma_w: float = 0.1, **kw) -> "AnalogConfig":
        kw.setdefault("noise", NoiseSpec(kind=noise_lib.WEIGHT, sigma=sigma_w))
        return cls(mode="analog", **kw)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SiteQuant:
    """Calibrated quantizers for one matmul site.

    ``wqp``: per-channel weight quantizer (ranges shaped (1, M)).
    ``xqp``: per-tensor activation quantizer (scalar ranges).
    ``oqp``: per-tensor output quantizer (layer l+1 range, scalar).
    """

    wqp: Optional[QuantParams] = None
    xqp: Optional[QuantParams] = None
    oqp: Optional[QuantParams] = None


def key_batch(key: Optional[jax.Array]) -> Optional[int]:
    """Leading batch size of a *stacked* key array, or None for a single key.

    A stacked key carries one independent PRNG stream per request row (the
    serving engine's per-request noise isolation): raw uint32 keys stack to
    (B, 2), typed keys to (B,). Every fold/draw maps over the leading axis.
    """
    if key is None:
        return None
    typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
    base_ndim = 0 if typed else 1
    if key.ndim == base_ndim:
        return None
    if key.ndim == base_ndim + 1:
        return key.shape[0]
    raise ValueError(f"bad key shape {key.shape}")


def fold_key(key: jax.Array, data) -> jax.Array:
    """``jax.random.fold_in`` that maps over stacked per-request keys."""
    if key_batch(key) is None:
        return jax.random.fold_in(key, data)
    return jax.vmap(lambda k: jax.random.fold_in(k, data))(key)


def raw_key(key: jax.Array) -> jax.Array:
    """Normalize a (possibly typed) PRNG key to raw uint32 data — the
    stackable, ShapeDtypeStruct-able form the serving engine traffics in."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def collapse_keys(key: jax.Array, valid: Optional[jax.Array] = None) -> jax.Array:
    """XOR-fold a stacked (B, ...) key array into ONE batch-level raw key.

    Expert-batched MoE matmuls mix tokens from every request in shared
    capacity buffers, so per-request noise streams are physically meaningless
    there; those sites instead draw a single stream from this batch-level
    key. Deterministic and order-invariant in the batch, but (necessarily)
    dependent on the set of *real* keys sharing the batch. Single keys pass
    through unchanged.

    ``valid`` (B,) bool: rows marked False — batch-padding rows in a bucket
    batch — fold the XOR identity (0) instead of their key, so the collapsed
    key depends only on the real requests. Without this, identical real
    traffic served at different batch-pad counts would XOR in a different
    number of pad keys and draw different expert noise.
    """
    if key_batch(key) is None:
        return key
    raw = raw_key(key)
    if valid is not None:
        mask = jnp.reshape(valid, (raw.shape[0],) + (1,) * (raw.ndim - 1))
        raw = jnp.where(mask, raw, jnp.zeros_like(raw))
    return jax.lax.reduce(raw, raw.dtype.type(0), jax.lax.bitwise_xor, (0,))


def site_key(key: jax.Array, site: str) -> jax.Array:
    """Deterministic per-site RNG stream derived from a stable name hash.

    Stacked per-request keys fold elementwise: every request keeps its own
    stream for the site."""
    h = int.from_bytes(hashlib.blake2s(site.encode(), digest_size=4).digest(), "little")
    return fold_key(key, h)


def _w_range(sq: SiteQuant, w: Array) -> Array:
    """Per-output-channel weight range (1, M) or from data if uncalibrated."""
    if sq is not None and sq.wqp is not None:
        return (sq.wqp.x_max - sq.wqp.x_min).astype(jnp.float32)
    lo = jnp.min(w, axis=0, keepdims=True)
    hi = jnp.max(w, axis=0, keepdims=True)
    return (hi - lo).astype(jnp.float32)


def _x_range(sq: SiteQuant, x: Array) -> Array:
    if sq is not None and sq.xqp is not None:
        return (sq.xqp.x_max - sq.xqp.x_min).astype(jnp.float32)
    return (jnp.max(x) - jnp.min(x)).astype(jnp.float32)


def _column_parallel_misfit(x, w, *, cfg, energy, sq, tp: int) -> Optional[str]:
    """Why an analog dot cannot run column-parallel over ``tp`` chips, or
    None where it can."""
    if sq is not None:
        return "calibrated quantizers"
    if w.ndim != 2:
        return "not a matrix"
    if w.shape[1] % tp != 0:
        return "columns do not divide"
    if jnp.ndim(energy) != 0:
        return "per-channel energy"  # its columns would need co-sharding
    if resolve_backend(cfg, x.shape, (w.shape[0], w.shape[1] // tp)) not in ("tile", "pallas"):
        return "backend not tiling-invariant"
    return None


def _maybe_sharded_analog_dot(
    x: Array,
    w: Array,
    *,
    cfg: AnalogConfig,
    energy: Array,
    key: jax.Array,
    sq: Optional[SiteQuant],
    n_repeats: int,
    site: Optional[str],
) -> Optional[Array]:
    """Column-parallel analog matmul through shard_map, or None to fall back.

    Each tensor-parallel shard holds columns ``[r * n_local, (r+1) * n_local)``
    of the weight and draws its noise with the matching global column offset,
    so (Threefry being counter-based) it computes exactly its tile of the
    unsharded "tile"/Pallas stream — the gathered output is bit-identical to
    the single-device oracle at every K and per-layer profile. Only the
    output N dim is sharded (the contracting dim stays whole: no psum, no
    cross-device rounding) and the gather back to replicated is pure data
    movement, so bit-identity is exact, not approximate.

    ``w`` is taken as each chip holds it: a mesh engine lays every analog
    weight out by its columns (``sharding.serving_spec``), so a chip's shard
    is its ``in_specs`` block and nothing is sliced. A weight that arrives
    whole is sliced to its blocks, at the cost of reading it whole.

    Returns None without a tensor-parallel mesh. Under one it falls back
    (None, counted in ``SHARDED_DOTS`` with the reason) when the resolved
    backend is not tiling-invariant ("jnp"), or when the operands don't fit
    the column-parallel contract (calibrated quantizers, per-channel
    energies, N not divisible by the shard count).
    """
    from repro.kernels.dispatch import active_mesh

    mesh = active_mesh()
    if mesh is None:
        return None
    tp = int(dict(mesh.shape).get(TP_AXIS, 1))
    if tp <= 1:
        return None
    misfit = _column_parallel_misfit(x, w, cfg=cfg, energy=energy, sq=sq, tp=tp)
    if misfit is not None:
        SHARDED_DOTS["fallback", site, misfit] += 1
        return None
    SHARDED_DOTS["column", site] += 1
    n_local = w.shape[1] // tp
    backend = resolve_backend(cfg, x.shape, (w.shape[0], n_local))

    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels import ops as kernel_ops

    kb = key_batch(key)
    if kb is not None and (x.ndim < 2 or x.shape[0] != kb):
        raise ValueError(
            f"stacked key batch {kb} does not match x leading dim {x.shape}"
        )
    kraw = raw_key(key)
    e_arr = jnp.asarray(energy, jnp.float32)
    mm = kernel_ops.analog_matmul if backend == "pallas" else (
        kernel_ops.analog_matmul_reference
    )

    def shard(xs, ws, ks, es):
        col0 = jax.lax.axis_index(TP_AXIS) * n_local

        def one(xr, kr):
            return mm(
                xr, ws, energy=es, key=kr, cfg=cfg, sq=None,
                n_repeats=n_repeats, offsets=(0, col0),
            )

        if kb is None:
            return one(xs, ks)
        return jax.vmap(one)(xs, ks)

    out_spec = P(*([None] * (x.ndim - 1)), TP_AXIS)
    y = jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(), P(None, TP_AXIS), P(), P()),
        out_specs=out_spec,
        check_vma=False,
    )(x, w, kraw, e_arr)
    # Gather the column shards back to replicated: everything outside
    # analog_dot (residual adds, caches, AOT argument shardings) stays
    # replicated, which is what lets executables survive mesh resize.
    return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P()))


def analog_dot(
    x: Array,
    w: Array,
    *,
    cfg: AnalogConfig,
    energy: Optional[Array] = None,
    key: Optional[jax.Array] = None,
    sq: Optional[SiteQuant] = None,
    precision=None,
    n_repeats: int = 1,
    site: Optional[str] = None,
) -> Array:
    """Noisy (or digital) matmul ``(..., K) @ (K, M) -> (..., M)``.

    ``energy``: scalar (per-layer) or (M,) per-channel energy/MAC; required in
    analog mode. ``key``: PRNG key for the noise draw; required in analog mode.
    ``n_repeats``: static K-repeat redundancy (paper §IV): run the op K times
    at ``energy`` each and average. On the Pallas backend the repeats are
    averaged in-register inside the fused kernel (one matmul pass, one x/w
    HBM read); on the jnp path the statistically identical single draw at
    ``K * energy`` is used. Total energy spent is ``K * energy`` either way.
    ``site`` names the call in ``SHARDED_DOTS`` only.
    """
    if x.shape[-1] != w.shape[0]:
        raise ValueError(f"contract mismatch {x.shape} @ {w.shape}")
    if n_repeats < 1:
        raise ValueError(f"n_repeats must be >= 1, got {n_repeats}")
    if cfg.mode == "analog" and energy is not None and key is not None:
        # Tensor-parallel path: under an active mesh with a model axis > 1,
        # run the matmul column-sharded through shard_map — checked before
        # the stacked-key vmap so ONE shard_map wraps the whole batch.
        y = _maybe_sharded_analog_dot(
            x, w, cfg=cfg, energy=energy, key=key, sq=sq, n_repeats=n_repeats,
            site=site,
        )
        if y is not None:
            return y
    return _local_analog_dot(
        x, w, cfg=cfg, energy=energy, key=key, sq=sq, precision=precision,
        n_repeats=n_repeats,
    )


def _local_analog_dot(x, w, *, cfg, energy, key, sq, precision, n_repeats):
    """``analog_dot`` on the whole weight, on each device that runs it."""
    kb = key_batch(key)
    if kb is not None:
        # Stacked per-request keys: one independent noise stream per leading
        # row. Each row's draw is identical to running that row alone, so a
        # request's output never depends on what else shares its batch (the
        # serving engine's batching-invariance contract).
        if x.ndim < 2 or x.shape[0] != kb:
            raise ValueError(
                f"stacked key batch {kb} does not match x leading dim {x.shape}"
            )
        return jax.vmap(
            lambda xr, kr: _local_analog_dot(
                xr, w, cfg=cfg, energy=energy, key=kr, sq=sq,
                precision=precision, n_repeats=n_repeats,
            )
        )(x, key)
    k_dim, m_dim = w.shape
    compute_dtype = jnp.float32 if cfg.mode == "analog" else x.dtype

    if cfg.mode == "digital":
        if cfg.weight_bits is not None and sq is not None and sq.wqp is not None:
            w = fake_quant(w, sq.wqp)
        if cfg.act_bits is not None and sq is not None and sq.xqp is not None:
            x = fake_quant(x, sq.xqp)
        y = jnp.matmul(x, w.astype(x.dtype), precision=precision)
        if cfg.out_bits is not None and sq is not None and sq.oqp is not None:
            y = fake_quant(y, sq.oqp)
        return y

    if energy is None or key is None:
        raise ValueError("analog mode requires energy and key")
    backend = resolve_backend(cfg, x.shape, w.shape)
    if backend == "pallas":
        return fused_dot(
            x, w, cfg=cfg, energy=energy, key=key, sq=sq, n_repeats=n_repeats
        )
    if backend == "tile":
        return tile_dot(
            x, w, cfg=cfg, energy=energy, key=key, sq=sq, n_repeats=n_repeats
        )

    x = x.astype(compute_dtype)
    w = w.astype(compute_dtype)
    energy = jnp.asarray(energy, jnp.float32)
    if cfg.discrete_energy:
        from repro.quant.affine import ste_snap_levels

        energy = ste_snap_levels(energy, cfg.energy_quantum)
    if n_repeats > 1:
        # K repeats at E averaged == one draw at K*E (noise in quadrature);
        # the explicit-K oracle forms live in core/redundant.py.
        energy = energy * n_repeats

    # --- input/weight quantization (digital-I/O architectures) -------------
    if cfg.weight_bits is not None and sq is not None and sq.wqp is not None:
        w_q = fake_quant(w, sq.wqp)
    else:
        w_q = w
    if cfg.act_bits is not None and sq is not None and sq.xqp is not None:
        x_q = fake_quant(x, sq.xqp)
    else:
        x_q = x

    kind = cfg.noise.kind
    if kind == noise_lib.WEIGHT:
        w_rng = _w_range(sq, w_q)  # (1, M)
        w_noisy = noise_lib.perturb_weights(key, w_q, w_rng, cfg.noise.sigma, energy)
        y = jnp.matmul(x_q, w_noisy, precision=precision)
    elif kind == noise_lib.THERMAL:
        y = jnp.matmul(x_q, w_q, precision=precision)
        std = noise_lib.thermal_noise_std(
            k_dim, _w_range(sq, w_q), _x_range(sq, x_q), cfg.noise.sigma, energy
        )
        y = y + noise_lib.sample_output_noise(key, y.shape, std)
    elif kind == noise_lib.SHOT:
        y = jnp.matmul(x_q, w_q, precision=precision)
        # eps-safe norms: ||.|| has a NaN gradient at exactly zero, and MoE
        # capacity padding produces all-zero input rows
        w_col = jnp.sqrt(jnp.sum(w_q * w_q, axis=0, keepdims=True) + 1e-20)
        x_row = jnp.sqrt(jnp.sum(x_q * x_q, axis=-1, keepdims=True) + 1e-20)
        std = noise_lib.shot_noise_std(
            w_col, x_row, k_dim, energy, cfg.noise.photon_energy_aj
        )
        y = y + noise_lib.sample_output_noise(key, y.shape, std)
    elif kind == noise_lib.NONE:
        y = jnp.matmul(x_q, w_q, precision=precision)
    else:  # pragma: no cover - NoiseSpec validates kinds
        raise ValueError(kind)

    # --- output requantization (paper App. A: requantize to 8 bits) --------
    if cfg.out_bits is not None and sq is not None and sq.oqp is not None:
        y = fake_quant(y, sq.oqp)
    return y


def analog_conv2d(
    x: Array,
    kernel: Array,
    *,
    cfg: AnalogConfig,
    stride: int = 1,
    padding: str = "SAME",
    energy: Optional[Array] = None,
    key: Optional[jax.Array] = None,
    sq: Optional[SiteQuant] = None,
) -> Array:
    """Convolution as an im2col matmul (paper §II-A, [25]) through analog_dot.

    ``x``: (B, H, W, Cin); ``kernel``: (kh, kw, Cin, Cout).
    """
    kh, kw, cin, cout = kernel.shape
    patches = jax.lax.conv_general_dilated_patches(
        x.astype(jnp.float32),
        filter_shape=(kh, kw),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (B, Ho, Wo, kh*kw*cin) with feature order (cin, kh, kw)
    w_mat = jnp.transpose(kernel, (2, 0, 1, 3)).reshape(kh * kw * cin, cout)
    return analog_dot(patches, w_mat, cfg=cfg, energy=energy, key=key, sq=sq)
