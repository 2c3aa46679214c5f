"""Counter-based PRNG (Threefry-2x32, 20 rounds) + Box-Muller gaussians.

Pure ``jnp`` uint32 arithmetic, so the SAME code traces both inside Pallas
kernel bodies (register-resident noise generation — no HBM traffic for the
noise tensor) and in the pure-jnp oracle (`kernels/ref.py`), giving bit-exact
kernel-vs-reference parity.

Counter convention: one gaussian per output element, counter words =
(global_row_index, global_col_index), key words = derived from the JAX PRNG
key (+ a salt to decorrelate weight-noise draws from output-noise draws).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA  # python int: jnp constants can't be closure-captured in Pallas
#: salt xored into the key for the weight-noise stream.
WEIGHT_STREAM_SALT = 0x9E3779B9
#: multiplier folded into the key word per repeat index (K-repeat averaging).
REPEAT_STREAM_MULT = 0x85EBCA6B


def _rotl(x: Array, d: int) -> Array:
    return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))


def _rounds(x0: Array, x1: Array, rots) -> tuple[Array, Array]:
    for d in rots:
        x0 = x0 + x1
        x1 = _rotl(x1, d)
        x1 = x1 ^ x0
    return x0, x1


def threefry2x32(k0: Array, k1: Array, c0: Array, c1: Array) -> tuple[Array, Array]:
    """Full 20-round Threefry-2x32. All args uint32, broadcastable."""
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(c0, jnp.uint32)
    x1 = jnp.asarray(c1, jnp.uint32)
    ks2 = k0 ^ k1 ^ jnp.uint32(_PARITY)

    x0 = x0 + k0
    x1 = x1 + k1
    x0, x1 = _rounds(x0, x1, _ROT_A)
    x0 = x0 + k1
    x1 = x1 + ks2 + jnp.uint32(1)
    x0, x1 = _rounds(x0, x1, _ROT_B)
    x0 = x0 + ks2
    x1 = x1 + k0 + jnp.uint32(2)
    x0, x1 = _rounds(x0, x1, _ROT_A)
    x0 = x0 + k0
    x1 = x1 + k1 + jnp.uint32(3)
    x0, x1 = _rounds(x0, x1, _ROT_B)
    x0 = x0 + k1
    x1 = x1 + ks2 + jnp.uint32(4)
    x0, x1 = _rounds(x0, x1, _ROT_A)
    x0 = x0 + ks2
    x1 = x1 + k0 + jnp.uint32(5)
    return x0, x1


def _top24_float(bits: Array) -> Array:
    """High 24 bits of uint32 words as exact float32 integers.

    Converted through int32: Mosaic (the TPU kernel compiler) has no
    uint32 -> float32 cast, and the shifted values are below 2^24, so the
    int32 bitcast and the float conversion are both exact."""
    shifted = bits >> jnp.uint32(8)
    return jax.lax.bitcast_convert_type(shifted, jnp.int32).astype(jnp.float32)


def bits_to_unit_open(bits: Array) -> Array:
    """uint32 -> float32 in (0, 1]: 1 - (bits >> 8) * 2^-24."""
    return jnp.float32(1.0) - _top24_float(bits) * jnp.float32(2.0**-24)


def bits_to_unit_halfopen(bits: Array) -> Array:
    """uint32 -> float32 in [0, 1)."""
    return _top24_float(bits) * jnp.float32(2.0**-24)


def counter_gaussian(k0: Array, k1: Array, c0: Array, c1: Array) -> Array:
    """One standard gaussian per (c0, c1) counter pair via Box-Muller."""
    b0, b1 = threefry2x32(k0, k1, c0, c1)
    u1 = bits_to_unit_open(b0)  # (0, 1] so log() is finite
    u2 = bits_to_unit_halfopen(b1)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(u1))
    theta = jnp.float32(2.0 * 3.14159265358979) * u2
    return r * jnp.cos(theta)


def gaussian_tile(
    k0: Array, k1: Array, row0: Array, col0: Array, shape: tuple[int, int]
) -> Array:
    """Gaussian tile for global element indices [row0:row0+m, col0:col0+n).

    Pure function of the *global* indices — independent of how the output is
    tiled, which is what makes kernel and oracle agree for any BlockSpec.
    """
    m, n = shape
    r0 = jnp.asarray(row0, jnp.int32).astype(jnp.uint32)
    c0 = jnp.asarray(col0, jnp.int32).astype(jnp.uint32)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (m, n), 0) + r0
    cols = jax.lax.broadcasted_iota(jnp.uint32, (m, n), 1) + c0
    return counter_gaussian(k0, k1, rows, cols)


def repeat_key(k1: Array, r: int) -> Array:
    """Second key word for repeat stream ``r`` of a K-repeat averaged op.

    ``r`` is a static Python int. ``r = 0`` returns ``k1`` unchanged, so the
    K=1 stream coincides bit-for-bit with the single-draw stream.
    """
    return jnp.asarray(k1, jnp.uint32) ^ jnp.uint32((r * REPEAT_STREAM_MULT) & 0xFFFFFFFF)


def repeat_averaged_gaussian_tile(
    k0: Array,
    k1: Array,
    row0: Array,
    col0: Array,
    shape: tuple[int, int],
    n_repeats: int,
) -> Array:
    """Mean of ``n_repeats`` independent gaussian tiles, one per repeat stream.

    This is the in-register noise of the fused dynamic-precision kernel
    (paper §IV: repeat the analog op K times and average -> std / sqrt(K)).
    The sequential accumulation order (r = 0..K-1) and the final
    ``float32(1/K)`` scale are part of the contract: the Pallas kernel and the
    pure-jnp oracle both call this function, which is what makes their
    repeat-averaged draws bit-exact for any output tiling.
    """
    xi = gaussian_tile(k0, k1, row0, col0, shape)
    for r in range(1, n_repeats):
        xi = xi + gaussian_tile(k0, repeat_key(k1, r), row0, col0, shape)
    if n_repeats > 1:
        xi = xi * jnp.float32(1.0 / n_repeats)
    return xi


def key_to_words(key: jax.Array) -> tuple[Array, Array]:
    """JAX PRNG key (typed or raw uint32 pair) -> two uint32 key words."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = key
    data = data.reshape(-1).astype(jnp.uint32)
    if data.size == 1:
        return jnp.uint32(0), data[0]
    return data[0], data[1]
