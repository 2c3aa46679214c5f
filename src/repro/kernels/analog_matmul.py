"""Fused analog-matmul Pallas TPU kernel.

One kernel fuses the entire simulated analog pipeline of paper §IV:

    fake-quant(x)  ->  fake-quant(w) per-channel  ->  [weight-read noise]
    ->  MXU matmul accumulate (f32)  ->  [output noise, std = row x col]
    ->  affine requantization of the output

Noise is generated *inside* the kernel from a counter-based Threefry PRNG
keyed on global element indices — the (M, N) gaussian tensor never exists in
HBM. Block sizes are MXU-aligned (multiples of 128) and sized so the working
set (x, w, out tiles) fits VMEM.

Noise kinds (static):
  * "output": additive gaussian with std[i, j] = row_scale[i] * col_scale[j].
    Covers thermal (row=1) and shot (row=||x_i||) — scales precomputed in
    ops.py from the calibrated ranges / energies.
  * "weight": per-weight gaussian with std[j] = wnoise_scale[j] (Eq. 10),
    drawn per (k, j) — identical draw for every row-tile i, as in a single
    physical read of the crossbar.
  * "none": plain (optionally quantized) matmul.

Dynamic precision (static ``n_repeats``): the paper's K-repeat redundancy
(§IV, Fig. 3) — run the analog op K times at base energy and average — is
fused into the kernel. Because the matmul is linear in its operands, the
average of K noisy products equals the clean product plus the *averaged*
noise, so the kernel draws K independent gaussian tiles per output/weight
tile (salted by repeat index), averages them in-register, and applies them
in a SINGLE matmul pass: one x/w HBM read and one y write regardless of K.
The K-fold tiled operands of the explicit form never exist.

Output noise is drawn alongside the matmul, not after it: with several
k-steps per output tile, each step draws one row slice of the tile's
repeat-averaged noise into a VMEM scratch (``noise_plan``), in the same
straight-line block as that step's matmul, so the VPU's Threefry work and the
MXU's accumulation can be scheduled together. The finish step only reads the
scratch. Every element's draw is the same call with the same counters, so the
output is bit-identical to drawing the whole tile at the finish.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import prng

Array = jax.Array

DEFAULT_BLOCK = (256, 256, 512)  # (bm, bn, bk)

#: kernel instantiations (trace time) by ``noise_plan``: "finish" or
#: (rows per slice, steps that draw). Output-noise calls only.
NOISE_PLANS: collections.Counter = collections.Counter()


def _clip_block(block: tuple, m: int, k: int, n: int) -> tuple:
    bm, bn, bk = block
    return min(bm, m), min(bn, n), min(bk, k)


def noise_plan(m: int, k: int, n: int, block: tuple = DEFAULT_BLOCK,
               noise_kind: str = "output"):
    """Where an (m, k) @ (k, n) call draws each output tile's noise.

    None without output noise. "finish" with one k-step: the whole tile is
    drawn after its only matmul. Otherwise ``(c, steps)``: k-step ``tk <
    steps`` draws tile rows ``[tk*c, min(tk*c + c, bm))``, with ``c`` the
    tile's rows over the k-steps rounded up to the 8-row sublane tiling,
    and the later steps draw nothing.
    """
    if noise_kind != "output":
        return None
    bm, _, bk = _clip_block(block, m, k, n)
    nk = pl.cdiv(k, bk)
    if nk == 1:
        return "finish"
    c = min(pl.cdiv(pl.cdiv(bm, nk), 8) * 8, bm)
    return c, pl.cdiv(bm, c)


def _fake_quant(v: Array, delta: Array, zp: Array, bins: Array) -> Array:
    """Affine fake-quant; delta/zp/bins broadcast (scalars or per-channel)."""
    code = jnp.round(v / delta) + zp
    code = jnp.clip(code, 0.0, bins)
    return (code - zp) * delta


def _kernel(
    x_ref,
    w_ref,
    rs_ref,
    cs_ref,
    wq_ref,
    sc_ref,
    seed_ref,
    out_ref,
    *scratch,
    noise_kind: str,
    plan,
    nk: int,
    block: tuple,
    k_total: int,
    quant_x: bool,
    quant_w: bool,
    quant_out: bool,
    n_repeats: int,
):
    bm, bn, bk = block
    ti = pl.program_id(0)
    tj = pl.program_id(1)
    tk = pl.program_id(2)
    sc = sc_ref[...]  # (1, 8) f32 scalars
    seed = seed_ref[...]  # (1, 4) uint32: key words + global tile origin
    k0, k1 = seed[0, 0], seed[0, 1]
    # Global origin of this call's operands in the unsharded problem: a
    # tensor-parallel shard offsets its noise counters so it draws exactly
    # its tile of the global stream ((0, 0) for whole-array calls).
    row0, col0 = seed[0, 2], seed[0, 3]

    def output_noise(r0, rows):
        # K repeat draws averaged in-register for tile rows [r0, r0 + rows):
        # one matmul pass, zero extra HBM traffic for the dynamic-precision
        # redundancy.
        return prng.repeat_averaged_gaussian_tile(
            k0,
            k1,
            row0 + jnp.asarray(ti * bm + r0, jnp.uint32),
            col0 + jnp.asarray(tj * bn, jnp.uint32),
            (rows, bn),
            n_repeats,
        )

    @pl.when(tk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def accumulate():
        xb = x_ref[...].astype(jnp.float32)
        wb = w_ref[...].astype(jnp.float32)

        if k_total % bk != 0:
            # Mask the K-tail: out-of-bounds block regions are undefined (NaN
            # in interpret mode) and must not feed the accumulation.
            k_idx = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1) + tk * bk
            xb = jnp.where(k_idx < k_total, xb, 0.0)
            wk_idx = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0) + tk * bk
            wb = jnp.where(wk_idx < k_total, wb, 0.0)

        if quant_x:
            xb = _fake_quant(xb, sc[0, 0], sc[0, 1], sc[0, 2])
        if quant_w:
            wd = wq_ref[0:1, :]  # (1, bn) per-channel delta
            wz = wq_ref[1:2, :]
            wbins = wq_ref[2:3, :]
            wb = _fake_quant(wb, wd, wz, wbins)
        if noise_kind == "weight":
            # std per column lives in cs; counter = (global k, global j); the
            # salt decorrelates this stream from the output-noise stream.
            # With n_repeats > 1 the K independent device reads are averaged
            # here in VMEM -- the (K*k, N) tiled weight array never exists.
            xi = prng.repeat_averaged_gaussian_tile(
                k0 ^ jnp.uint32(prng.WEIGHT_STREAM_SALT),
                k1,
                jnp.asarray(tk * bk, jnp.uint32),
                col0 + jnp.asarray(tj * bn, jnp.uint32),
                (bk, bn),
                n_repeats,
            )
            wb = wb + cs_ref[...] * xi

        out_ref[...] += jnp.dot(xb, wb, preferred_element_type=jnp.float32)

    if isinstance(plan, tuple):
        # Each step's noise slice is drawn in the same block as its matmul:
        # a branch around the draw alone would give it a block of its own,
        # which the compiler does not interleave with the matmul. So the
        # matmul is emitted once per run of steps that draw alike.
        (noise_ref,) = scratch
        c, steps = plan
        tail = bm - (steps - 1) * c

        def draw(rows):
            start = tk * c
            if c % 8 == 0:
                start = pl.multiple_of(start, 8)
            noise_ref[pl.ds(start, rows), :] = output_noise(tk * c, rows)

        full = steps if tail == c else steps - 1
        runs = [(0, full, c), (full, steps, tail), (steps, nk, 0)]
        runs = [r for r in runs if r[0] < r[1]]
        for lo, hi, rows in runs:
            def step(rows=rows):
                if rows:
                    draw(rows)
                accumulate()

            if len(runs) == 1:
                step()
            else:
                pl.when((tk >= lo) & (tk < hi))(step)
    else:
        accumulate()

    @pl.when(tk == nk - 1)
    def _finish():
        y = out_ref[...]
        if plan == "finish":
            y = y + rs_ref[...] * cs_ref[...] * output_noise(0, bm)
        elif isinstance(plan, tuple):
            y = y + rs_ref[...] * cs_ref[...] * noise_ref[...]
        if quant_out:
            y = _fake_quant(y, sc[0, 3], sc[0, 4], sc[0, 5])
        out_ref[...] = y


def analog_matmul_raw(
    x: Array,
    w: Array,
    row_scale: Array,
    col_scale: Array,
    wq: Array,
    scalars: Array,
    seed: Array,
    *,
    noise_kind: str = "output",
    quant_x: bool = False,
    quant_w: bool = False,
    quant_out: bool = False,
    n_repeats: int = 1,
    block: tuple = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
) -> Array:
    """Low-level entry: shapes (M,K) @ (K,N) -> (M,N).

    row_scale: (M, 1) f32; col_scale: (1, N) f32; wq: (3, N) f32 rows =
    (delta, zp, bins); scalars: (1, 8) f32 = (xd, xz, xbins, od, oz, obins,
    0, 0); seed: (1, 4) uint32 = (k0, k1, row0, col0) — key words plus the
    global tile origin of this call in the unsharded problem (tensor-parallel
    shards offset their noise counters; whole-array calls pass (0, 0)).
    ``n_repeats`` (static): average K independent noise draws in-register —
    the fused form of the paper's K-repeat redundancy, with noise std scaled
    by 1/sqrt(K).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert n_repeats >= 1, n_repeats
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bm, bn, bk = _clip_block(block, m, k, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    plan = noise_plan(m, k, n, block, noise_kind)
    if plan is not None:
        NOISE_PLANS[plan] += 1
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)] if isinstance(plan, tuple) else []

    kern = functools.partial(
        _kernel,
        noise_kind=noise_kind,
        plan=plan,
        nk=grid[2],
        block=(bm, bn, bk),
        k_total=k,
        quant_x=quant_x,
        quant_w=quant_w,
        quant_out=quant_out,
        n_repeats=n_repeats,
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((3, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, 8), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, 4), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(
        x.astype(jnp.float32),
        w.astype(jnp.float32),
        row_scale.astype(jnp.float32),
        col_scale.astype(jnp.float32),
        wq.astype(jnp.float32),
        scalars.astype(jnp.float32),
        seed.astype(jnp.uint32),
    )
