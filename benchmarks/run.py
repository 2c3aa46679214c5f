"""Benchmark harness entry point: one benchmark per paper table/figure plus
the kernel microbench and the roofline report.

Prints ``name,us_per_call,derived`` CSV per benchmark (us_per_call = wall
time of the benchmark computation; derived = its headline number). Results
are cached under benchmarks/artifacts/paper; pass --force to recompute.
"""
import argparse
import json
import sys
import time


def _row(name, us, derived):
    print(f"{name},{us:.0f},{derived}")
    sys.stdout.flush()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated benchmark names")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import kernel_bench as kb
    from benchmarks import paper_tables as pt
    from benchmarks import roofline_report as rr
    from benchmarks import serving_bench as sb

    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")

    def run(name, fn, derive):
        if only and name not in only:
            return
        t0 = time.perf_counter()
        out = fn(force=args.force)
        us = (time.perf_counter() - t0) * 1e6
        _row(name, us, derive(out))

    run("table1_noise_bits", pt.table1,
        lambda o: "max|noisy-lowbit|=%.3f" % max(
            abs(r["noisy_acc"] - r["lowbit_acc"]) for r in o["rows"] if r["avg_bits"]
        ))
    run("table2_min_energy", pt.table2,
        lambda o: "improvements=" + ";".join(
            f"{m}/{n}:{o[m][n]['improvement_pct']:.0f}%"
            for m in ("cnn", "mlp") for n in ("shot", "thermal", "weight")
        ))
    run("table3_dynamic_bits", pt.table3,
        lambda o: "dyn-uni acc gain=" + ";".join(
            f"{r['target_e_per_mac']}:{r['dynamic']['acc']-r['uniform']['acc']:+.3f}"
            for r in o["rows"]
        ))
    run("table4_bert_shot", pt.table4,
        lambda o: f"bert uniform {o['uniform_aj_per_mac']['min_e_per_mac']:.3f} -> "
                  f"dynamic {o['dynamic_aj_per_mac']['min_e_per_mac']:.3f} aJ/MAC "
                  f"({o['improvement_pct']:.0f}%)")
    run("table5_profile_vs_uniform", pt.table5_profile,
        lambda o: f"profile K={list(o['profile']['repeats'].values())} "
                  f"{o['profile']['e_per_mac_aj']:.3f} aJ/MAC, "
                  f"saves {o['improvement_pct_vs_cheapest_uniform']:.0f}% vs "
                  f"cheapest feasible uniform"
                  if o["improvement_pct_vs_cheapest_uniform"] is not None
                  else "no feasible uniform K")
    run("fig4_energy_curve", pt.fig4,
        lambda o: "monotone_acc=" + str(all(
            o["curve"][i]["dynamic_acc"] <= o["curve"][i + 1]["dynamic_acc"] + 0.05
            for i in range(len(o["curve"]) - 1)
        )))
    run("fig6_energy_allocations", pt.fig6,
        lambda o: "allocs=" + ";".join(
            f"{k}:{v:.3f}" for k, v in o["allocations_aj_per_mac"].items()
        ))
    run("kernel_bench", kb.kernel_bench,
        lambda o: f"fused_speedup={o['speedup_x']:.2f}x "
                  f"analog_overhead={o['analog_overhead_x']:.2f}x "
                  f"hbm_saving={o['hbm_traffic_saving_x']:.2f}x")
    run("serving_bench", sb.serving_bench,
        lambda o: f"engine={o['engine']['tokens_per_s']:.0f}tok/s "
                  f"naive={o['naive']['tokens_per_s']:.0f}tok/s "
                  f"speedup={o['throughput_speedup_x']:.2f}x "
                  f"hit_rate={o['steady_hit_rate']:.0%} "
                  f"retraces={o['engine']['steady_retraces']}")

    if only is None or "roofline" in only:
        t0 = time.perf_counter()
        rows = rr.load_cells()
        s = rr.summary(rows)
        rr.main()
        _row("roofline_report", (time.perf_counter() - t0) * 1e6,
             f"cells_ok={s['cells_ok']} fits={s['fits']}/{s['cells_ok']} "
             f"dominant={s['dominant_histogram']}")


if __name__ == '__main__':
    main()
