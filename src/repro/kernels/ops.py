"""Public jit'd wrappers around the fused analog-matmul kernel.

``prepare_operands`` maps the high-level (AnalogConfig, SiteQuant, energy,
key) description onto the kernel's raw operands — precomputed noise scale
vectors, per-channel quantizer vectors, scalar pack, PRNG seed — so the same
preparation feeds both the Pallas kernel and the pure-jnp oracle.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import noise as noise_lib
from repro.kernels import prng
from repro.kernels.analog_matmul import DEFAULT_BLOCK, analog_matmul_raw
from repro.kernels.ref import analog_matmul_ref_raw

Array = jax.Array


def _ranges(sq, w, x) -> Tuple[Array, Array]:
    if sq is not None and sq.wqp is not None:
        w_rng = (sq.wqp.x_max - sq.wqp.x_min).astype(jnp.float32).reshape(1, -1)
    else:
        w_rng = (jnp.max(w, axis=0) - jnp.min(w, axis=0)).reshape(1, -1)
    if sq is not None and sq.xqp is not None:
        x_rng = (sq.xqp.x_max - sq.xqp.x_min).astype(jnp.float32)
    else:
        x_rng = jnp.max(x) - jnp.min(x)
    return w_rng, jnp.asarray(x_rng, jnp.float32)


def _norm(v: Array, axis: int) -> Array:
    """L2 norm along ``axis`` (kept, size 1), summed in a fixed order.

    ``jnp.sum`` lowers to a reduction whose order the TPU compiler picks
    by shape, layout and fusion, so a tensor-parallel shard's column slice
    or a differently fused operand rounds differently from the unsharded
    call. A pairwise tree of elementwise adds (zero-padded to a power of
    two, which is exact) cannot be reordered: each norm depends only on its
    own row or column. The noise scales of sharded and unsharded calls are
    then bit-identical."""
    v = v.astype(jnp.float32)
    v = v * v
    n = v.shape[axis]
    width = 1 << (n - 1).bit_length()
    if width != n:
        pad = [(0, 0)] * v.ndim
        pad[axis] = (0, width - n)
        v = jnp.pad(v, pad)
    while width > 1:
        width //= 2
        v = (jax.lax.slice_in_dim(v, 0, width, axis=axis)
             + jax.lax.slice_in_dim(v, width, 2 * width, axis=axis))
    return jnp.sqrt(v)


def prepare_operands(
    x2d: Array, w: Array, *, energy, key, cfg, sq=None, offsets=(0, 0)
) -> dict:
    """Compute raw kernel operands from the analog execution description.

    ``offsets = (row0, col0)`` is the global tile origin of this call's
    operands in the unsharded problem: a tensor-parallel shard holding
    columns ``[col0, col0 + n)`` of the full weight passes its column offset
    so the counter-based noise it draws is exactly its tile of the global
    stream (the whole-array call at ``(0, 0)`` is unchanged). Offsets may be
    traced values (e.g. ``axis_index * n_local`` inside ``shard_map``).
    """
    m, k = x2d.shape
    _, n = w.shape
    energy = jnp.asarray(energy, jnp.float32)
    if cfg.discrete_energy:
        from repro.quant.affine import ste_snap_levels

        energy = ste_snap_levels(energy, cfg.energy_quantum)
    e_col = jnp.broadcast_to(energy.reshape(1, -1), (1, n))

    kind = cfg.noise.kind
    ones_row = jnp.ones((m, 1), jnp.float32)
    if kind == noise_lib.THERMAL:
        w_rng, x_rng = _ranges(sq, w, x2d)
        col = noise_lib.thermal_noise_std(k, w_rng, x_rng, cfg.noise.sigma, e_col)
        row = ones_row
        noise_kind = "output"
    elif kind == noise_lib.SHOT:
        photons = e_col / cfg.noise.photon_energy_aj
        col = _norm(w, axis=0) / jnp.sqrt(jnp.float32(k) * photons)
        row = _norm(x2d, axis=-1)
        noise_kind = "output"
    elif kind == noise_lib.WEIGHT:
        w_rng, _ = _ranges(sq, w, x2d)
        col = noise_lib.weight_noise_std(w_rng, cfg.noise.sigma, e_col)
        row = ones_row
        noise_kind = "weight"
    else:
        col = jnp.zeros((1, n), jnp.float32)
        row = ones_row
        noise_kind = "none"

    quant_w = cfg.weight_bits is not None and sq is not None and sq.wqp is not None
    quant_x = cfg.act_bits is not None and sq is not None and sq.xqp is not None
    quant_out = cfg.out_bits is not None and sq is not None and sq.oqp is not None

    if quant_w:
        wd = jnp.broadcast_to(sq.wqp.delta.reshape(1, -1), (1, n))
        wz = jnp.broadcast_to(sq.wqp.zero_point.reshape(1, -1), (1, n))
        wb = jnp.broadcast_to(jnp.reshape(sq.wqp.n_bins, (1, 1)), (1, n))
        wq = jnp.concatenate([wd, wz, wb], axis=0)
    else:
        wq = jnp.ones((3, n), jnp.float32)

    def _sq_scalars(qp):
        if qp is None:
            return jnp.ones(()), jnp.zeros(()), jnp.ones(())
        return (
            jnp.reshape(qp.delta, ()),
            jnp.reshape(qp.zero_point, ()),
            jnp.reshape(qp.n_bins, ()),
        )

    xd, xz, xb = _sq_scalars(sq.xqp if (quant_x and sq) else None)
    od, oz, ob = _sq_scalars(sq.oqp if (quant_out and sq) else None)
    scalars = jnp.stack([xd, xz, xb, od, oz, ob, jnp.zeros(()), jnp.zeros(())]).reshape(1, 8)

    k0, k1 = prng.key_to_words(key)
    row0 = jnp.asarray(offsets[0], jnp.int32).astype(jnp.uint32).reshape(())
    col0 = jnp.asarray(offsets[1], jnp.int32).astype(jnp.uint32).reshape(())
    seed = jnp.stack([k0, k1, row0, col0]).reshape(1, 4)

    return dict(
        x=x2d,
        w=w,
        row_scale=row,
        col_scale=col,
        wq=wq,
        scalars=scalars,
        seed=seed,
        noise_kind=noise_kind,
        quant_x=quant_x,
        quant_w=quant_w,
        quant_out=quant_out,
    )


def analog_matmul(
    x: Array,
    w: Array,
    *,
    energy,
    key,
    cfg,
    sq=None,
    n_repeats: int = 1,
    block: tuple = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
    offsets=(0, 0),
) -> Array:
    """Fused analog matmul for arbitrary batch dims: (..., K) @ (K, N).

    ``n_repeats``: static K-repeat redundancy (paper §IV) fused into the
    kernel — one matmul pass whose noise is the in-register average of K
    independent draws at the given (base) energy. ``offsets``: global
    (row0, col0) tile origin for tensor-parallel shards (see
    ``prepare_operands``).
    """
    batch_shape = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    ops = prepare_operands(
        x2d, w, energy=energy, key=key, cfg=cfg, sq=sq, offsets=offsets
    )
    kind = ops.pop("noise_kind")
    qx, qw, qo = ops.pop("quant_x"), ops.pop("quant_w"), ops.pop("quant_out")
    y = analog_matmul_raw(
        ops["x"],
        ops["w"],
        ops["row_scale"],
        ops["col_scale"],
        ops["wq"],
        ops["scalars"],
        ops["seed"],
        noise_kind=kind,
        quant_x=qx,
        quant_w=qw,
        quant_out=qo,
        n_repeats=n_repeats,
        block=block,
        interpret=interpret,
    )
    return y.reshape(*batch_shape, w.shape[1])


def analog_matmul_reference(
    x: Array, w: Array, *, energy, key, cfg, sq=None, n_repeats: int = 1, offsets=(0, 0)
) -> Array:
    """Oracle with identical noise draws (pure jnp, no Pallas)."""
    batch_shape = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    ops = prepare_operands(
        x2d, w, energy=energy, key=key, cfg=cfg, sq=sq, offsets=offsets
    )
    kind = ops.pop("noise_kind")
    qx, qw, qo = ops.pop("quant_x"), ops.pop("quant_w"), ops.pop("quant_out")
    y = analog_matmul_ref_raw(
        ops["x"],
        ops["w"],
        ops["row_scale"],
        ops["col_scale"],
        ops["wq"],
        ops["scalars"],
        ops["seed"],
        noise_kind=kind,
        quant_x=qx,
        quant_w=qw,
        quant_out=qo,
        n_repeats=n_repeats,
    )
    return y.reshape(*batch_shape, w.shape[1])
