"""Microbenchmark: fused K-repeat analog matmul vs the unfused composition.

Sweeps (shape x K) over the dynamic-precision repeat count K (paper §IV).
Three execution forms per cell:

  explicit — ``time_averaged_dot_explicit``: K full analog matmuls + K
             HBM-resident (M, N) noise tensors, then a mean. What the
             simulation cost USED to be.
  fused    — the model hot path: one ``analog_dot`` with ``n_repeats=K``
             (on CPU the jnp single-draw-at-K*E equivalent; on TPU the
             fused Pallas kernel).
  kernel   — the Pallas kernel itself. On CPU this runs in interpret mode
             (a correctness vehicle, not a timing proxy for TPU), so it is
             timed with few iters and reported separately.

ANALYTIC HBM traffic per cell (f32 bytes; the fusion argument on TPU):

  unfused: per draw — read x, w; write y; write+read noise; read+write y
           (add); read+write y (requant) = xw + 6*|y| touches, times K
           draws, plus the K-way mean ((K+1)*|y|).
  fused:   read x, w once; write y once — noise generated and averaged
           in-register, INDEPENDENT of K.

Persisted via ``cache_json`` (itself atomic) and summarized into the
repo-root ``BENCH_kernel.json`` through ``atomic_write_json`` with a
``run_provenance()`` block — the artifact carries the commit/jax stack
that produced it, and a crash mid-write never truncates the previous
record. ``--smoke`` runs a tiny sweep for CI.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import atomic_write_json, cache_json, run_provenance

TRAJECTORY_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_kernel.json",
)
from repro.core import AnalogConfig, analog_dot
from repro.core.redundant import time_averaged_dot_explicit
from repro.kernels import analog_matmul
from repro.runtime.compile_cache import enable_compile_cache

SHAPES = [(256, 256, 256), (512, 512, 512), (384, 640, 512)]
K_REPEATS = [1, 4, 16]
SMOKE_SHAPES = [(128, 128, 128)]
SMOKE_K_REPEATS = [1, 4]


def analytic_traffic(m: int, k: int, n: int, k_repeats: int) -> dict:
    """Analytic HBM byte counts (f32) for the unfused vs fused K-repeat op."""
    bytes_xw = (m * k + k * n) * 4
    bytes_y = m * n * 4
    unfused = k_repeats * (bytes_xw + 6 * bytes_y) + (k_repeats + 1) * bytes_y
    fused = bytes_xw + bytes_y  # one x/w read + one y write, regardless of K
    return {
        "hbm_bytes_unfused": unfused,
        "hbm_bytes_fused": fused,
        "hbm_traffic_saving_x": unfused / fused,
    }


def _time(fn, *args, iters=20):
    fn(*args).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _sweep(shapes, k_repeats, iters, kernel_iters):
    key = jax.random.PRNGKey(0)
    cfg = AnalogConfig.shot()
    e = jnp.asarray(10.0)
    rows = []
    for m, k, n in shapes:
        x = jax.random.normal(key, (m, k))
        w = jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.1
        t_plain = _time(jax.jit(lambda a, b: a @ b), x, w, iters=iters)
        for r in k_repeats:
            explicit = jax.jit(
                lambda a, b, kk, r=r: time_averaged_dot_explicit(
                    a, b, cfg=cfg, base_energy=e, key=kk, k_repeats=r
                )
            )
            fused = jax.jit(
                lambda a, b, kk, r=r: analog_dot(
                    a, b, cfg=cfg, energy=e, key=kk, n_repeats=r
                )
            )
            row = {
                "shape": [m, k, n],
                "k_repeats": r,
                "plain_matmul_us": t_plain,
                "explicit_us": _time(explicit, x, w, key, iters=iters),
                "fused_us": _time(fused, x, w, key, iters=iters),
                **analytic_traffic(m, k, n, r),
            }
            row["speedup_x"] = row["explicit_us"] / row["fused_us"]
            row["analog_overhead_x"] = row["fused_us"] / t_plain
            # interpret-mode kernel timing is K-independent noise on CPU:
            # record it once per shape, not per K
            if kernel_iters and r == k_repeats[0]:
                kern = jax.jit(
                    lambda a, b, kk, r=r: analog_matmul(
                        a, b, energy=e, key=kk, cfg=cfg, n_repeats=r,
                        block=(min(256, m), min(256, n), min(256, k)),
                    )
                )
                row["kernel_interpret_us"] = _time(kern, x, w, key, iters=kernel_iters)
            rows.append(row)
    # headline rows for the CSV trajectory: the biggest (MACs) shape, with
    # analog_overhead_x defined at K=1 (fused single draw vs plain matmul,
    # the pre-sweep definition) and speedup/saving at the largest K.
    big = max(rows, key=lambda r: (r["shape"][0] * r["shape"][1] * r["shape"][2], r["k_repeats"]))
    base = next(
        r for r in rows if r["shape"] == big["shape"] and r["k_repeats"] == k_repeats[0]
    )
    return {
        "backend": jax.default_backend(),
        "provenance": run_provenance(),
        "rows": rows,
        "analog_overhead_x": base["analog_overhead_x"],
        "hbm_traffic_saving_x": big["hbm_traffic_saving_x"],
        "speedup_x": big["speedup_x"],
    }


# "_sweep" cache names: the pre-sweep "kernel_bench" JSON had a different
# (flat) schema; a fresh name keeps stale caches from crashing the readers.
@cache_json("kernel_bench_sweep")
def kernel_bench():
    return _sweep(SHAPES, K_REPEATS, iters=20, kernel_iters=2)


@cache_json("kernel_bench_sweep_smoke")
def kernel_bench_smoke():
    return _sweep(SMOKE_SHAPES, SMOKE_K_REPEATS, iters=3, kernel_iters=1)


def _print_table(out):
    hdr = (
        f"{'shape':>16} {'K':>3} {'explicit_us':>12} {'fused_us':>10} "
        f"{'speedup':>8} {'unfused_MB':>11} {'fused_MB':>9} {'saving':>7}"
    )
    print(f"backend={out['backend']}")
    print(hdr)
    for r in out["rows"]:
        print(
            f"{'x'.join(map(str, r['shape'])):>16} {r['k_repeats']:>3} "
            f"{r['explicit_us']:>12.1f} {r['fused_us']:>10.1f} "
            f"{r['speedup_x']:>7.1f}x {r['hbm_bytes_unfused'] / 1e6:>10.2f} "
            f"{r['hbm_bytes_fused'] / 1e6:>8.2f} {r['hbm_traffic_saving_x']:>6.1f}x"
        )


def _write_trajectory(out, smoke: bool) -> str:
    """Atomic repo-root summary: headline numbers + provenance, never the
    full row dump (that lives in the artifacts/paper cache)."""
    record = {
        "bench": "kernel_bench",
        "smoke": smoke,
        "backend": out["backend"],
        "provenance": out.get("provenance", run_provenance()),
        "n_rows": len(out["rows"]),
        "analog_overhead_x": out["analog_overhead_x"],
        "hbm_traffic_saving_x": out["hbm_traffic_saving_x"],
        "speedup_x": out["speedup_x"],
    }
    return atomic_write_json(TRAJECTORY_PATH, record)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny sweep for CI")
    ap.add_argument("--force", action="store_true", help="ignore cached JSON")
    args = ap.parse_args()
    fn = kernel_bench_smoke if args.smoke else kernel_bench
    out = fn(force=args.force)
    _print_table(out)
    print(f"trajectory -> {_write_trajectory(out, args.smoke)}")


if __name__ == "__main__":
    main()
