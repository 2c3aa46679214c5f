"""Where the device idles inside the serving engine, from a kept trace.

    JAX_PLATFORMS=cpu python benchmarks/chip/tools/idle_by_span.py <trace dir or .xplane.pb>

The trace is one that ``run.py --trace 1 --keep-trace <dir>`` kept. Prints,
for the traced window (``engine_trace.py`` defines each reading):

* the device's idle time inside each innermost ``engine.*`` span, in all
  and per pump: each span's own share of the idle, the spans nested in it
  taken out, with the device's events moved by the lag of its clock behind
  the host's (``engine_trace.device_lag_ns``; the JSON line also holds the
  split without the move);
* the device time of each executable (``jit_prefill``, ``jit_decode``,
  ``jit_insert``): runs, total and mean;
* the engine's readings: idle per pump, decode and prefill device time, the
  wait from submit to prefill; and beside them the harness's breakdown of
  the idle by its own spans, which the pumps' idle should come near;

then all of it as one JSON line.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import engine_trace  # noqa: E402
import trace_reduce  # noqa: E402


def _ms(ns: dict) -> dict:
    return {k: v / 1e6 for k, v in sorted(ns.items(), key=lambda kv: -kv[1])}


def report(tr) -> dict:
    pumps = len(engine_trace.spans_in(tr, engine_trace.PUMP))
    summ = trace_reduce.summary(tr)
    lag = engine_trace.device_lag_ns(tr)
    idle = engine_trace.idle_by_span(tr, lag or 0)
    mods = engine_trace.module_times(tr)
    pump_idle = engine_trace.pump_idle_ms(tr)
    ws, we = tr["window"]
    steps = [d for name, s, d in tr["host"] if name == "pump_step" and s >= ws and s + d <= we]
    return dict(
        window_s=summ["window_s"], busy_s=summ["busy_s"], pumps=pumps,
        device_lag_ms=lag / 1e6 if lag is not None else None,
        idle_by_span_ms=_ms(idle),
        idle_by_span_unaligned_ms=_ms(engine_trace.idle_by_span(tr)),
        modules={k: dict(runs=n, total_ms=t / 1e6, mean_ms=t / n / 1e6)
                 for k, (n, t) in sorted(mods.items())},
        pump_idle_ms=pump_idle,
        pump_idle_total_ms=pump_idle * pumps if pump_idle is not None else None,
        decode_exe_ms=engine_trace.decode_exe_ms(tr),
        prefill_exe_us_per_tok=engine_trace.prefill_exe_us_per_tok(tr),
        prefill_pairs=len(engine_trace.prefill_pairs(tr)),
        admit_wait_ms_p50=engine_trace.admit_wait_ms_p50(tr),
        admit_waits=len(engine_trace.admit_waits_ms(tr)),
        harness_idle_ms={k: v * 1e3 for k, v in summ["idle_gaps"]},
        pump_steps=len(steps),
        pump_step_ms_mean=sum(steps) / len(steps) / 1e6 if steps else None,
    )


def main(path):
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    r = report(engine_trace.load(path))
    print(f"window {r['window_s']:.4f} s, busy {r['busy_s']:.4f} s, "
          f"{r['pumps']} engine.pump spans wholly inside, device lag {r['device_lag_ms']} ms")
    print("device idle by innermost engine span, the lag taken out: total ms, per pump ms")
    for k, v in r["idle_by_span_ms"].items():
        print(f"  {k:22s} {v:10.3f} {v / r['pumps'] if r['pumps'] else float('nan'):10.4f}")
    print("device time by executable: runs, total ms, mean ms")
    for k, m in r["modules"].items():
        print(f"  {k:22s} {m['runs']:6d} {m['total_ms']:10.3f} {m['mean_ms']:10.4f}")
    for k in ("pump_idle_ms", "pump_idle_total_ms", "decode_exe_ms", "prefill_exe_us_per_tok",
              "prefill_pairs", "admit_wait_ms_p50", "admit_waits", "pump_steps",
              "pump_step_ms_mean"):
        print(f"{k}: {r[k]}")
    print("harness idle by its spans (ms): "
          + ", ".join(f"{k} {v:.3f}" for k, v in r["harness_idle_ms"].items()))
    print(json.dumps(r))


if __name__ == "__main__":
    main(sys.argv[1])
