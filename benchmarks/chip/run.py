#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of the
checkout. It names a configuration (``configs/<name>.json``, whose
``reference`` names its plain reference in ``reference/``) and a traffic mix
(``traffic/<name>.json``). In order, the run:

1. keeps JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
2. checks that JAX sees a TPU with as many chips as the cell asks for, and
   otherwise exits non-zero and prints no result;
3. makes the weights on the device from the seed, under ``jit``; a cell of
   ``chips`` > 1 runs on the first ``chips`` devices as one tensor-parallel
   mesh, its weights drawn in their shards (``harness.py``);
4. warms every executable the mix's bucket ladder can reach, and no other;
5. serves the mix for ``--seconds`` through the engine's ``submit`` and
   ``pump_step``, then drains what is in flight;
6. frees the program and compares a sample of what it served with the plain
   reference (``correctness.py``), on the cell's mesh where it has one;
7. prints set-up facts and the compared numbers on standard error, and one
   JSON line as the last line of standard output.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces a
part of the window with the profiler and reports the cell's per-layer
metrics (``metrics/<name>.py``), the device's busy and window seconds and
the trace's breakdown.

Options the benchmark's own runs do not use: ``--control <dtype>`` puts the
control of the comparison in the program's place: the reference computed at
that lower precision gives the number compared, so a run with the control
reads not correct (the program's own reading is still printed beside it);
``--keep-trace <dir>`` keeps the traced run's profiler output
(``tools/dump_trace.py`` prints its structure). ``tools/sweep.py`` serves an
open-loop mix at several rates, to find the highest the cell sustains.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)


def fail(msg: str, code: int = 2) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT, here: str = HERE) -> dict:
    """The cell's entry, configuration, mix, reference and metric lists."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    with open(os.path.join(here, "configs", f"{w['config']}.json")) as f:
        cfg = json.load(f)
    import traffic as traffic_lib

    mix = traffic_lib.load_mix(w["traffic"], here)
    ref = load_module(os.path.join(here, "reference", f"{cfg['reference']}.py"),
                      f"reference_{cfg['reference']}")

    def applies(m):
        return workload in m.get("workloads", [workload])

    limits_path = os.path.join(here, "limits", f"{workload}.json")
    limits = {}
    if os.path.exists(limits_path):
        with open(limits_path) as f:
            limits = json.load(f)
    return dict(bench=bench, cell=w, cfg=cfg, mix=mix, ref=ref, limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default) of all values."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(drive: dict) -> dict:
    """Every end-to-end metric the harness can read from one window.

    ``out_tok_s`` is every token of every request sent in the window, over
    the window's open to the end of the drain that follows its close: all
    the work that was sent and all the time it took. Counted only up to the
    close, the rate moved in steps of a whole pump's tokens, by where the
    close fell in a pump."""
    ttft, itl = [], []
    out_tokens = 0
    for t in drive["tracks"]:
        if t.times:
            ttft.append((t.times[0] - t.due) * 1e3)
            itl.extend((b - a) * 1e3 for a, b in zip(t.times, t.times[1:]))
        else:
            ttft.append(float("inf"))  # missing: failed, or never served
        out_tokens += len(t.times)
    out = dict(out_tok_s=out_tokens / (drive["t_end"] - drive["t_open"]))
    if ttft:
        out["ttft_p95_ms"] = percentile(ttft, 95)
    if itl:
        out["itl_p99_ms"] = percentile(itl, 99)
    # a tail over a failed request is infinite: left out (the run is not correct)
    return {k: v for k, v in out.items() if math.isfinite(v)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="compare the reference at this lower dtype in the program's place")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's profiler output to this directory")
    args = ap.parse_args(argv)
    return run(args, *start(args.workload))


def start(workload: str):
    """Load the cell, keep JAX's cache in the checkout, and find the chips;
    exits non-zero, printing no result, where they are not there."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no program (src/repro) in {ROOT}")
    try:
        spec = load_cell(workload)
    except (OSError, KeyError, ValueError) as e:
        fail(str(e))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import harness

    counter = harness.CompileCounter()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no accelerator: {e}", 3)
    chips = int(spec["cell"]["chips"])
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r})", 3)
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devices)}", 3)
    return spec, devices, counter


def run(args, spec, devices, counter) -> int:
    import gc

    import correctness
    import harness
    import peaks as peaks_lib
    import traffic as traffic_lib

    mix = spec["mix"]
    cfg, ref = spec["cfg"], spec["ref"]
    chips = int(spec["cell"]["chips"])
    devices = devices[:chips]
    dev = devices[0]
    peak = peaks_lib.peaks(dev.device_kind)
    cell = harness.Cell(cfg, mix, ref, args.seed, devices)
    requests = traffic_lib.generate(mix, args.seed, args.seconds, ref.dims(cfg)["vocab"])
    cell.build()
    cell.warm()
    # set-up leaves millions of objects (JAX's caches); frozen, the window's
    # collections no longer walk them
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    at_setup = counter.snapshot()
    exe = cell.engine.cache_stats()

    tracer = Tracer(cell, args.seconds, args.keep_trace) if args.trace else None
    c0 = cell.counters()
    drive = cell.drive(requests, args.seconds, on_pump=tracer.on_pump if tracer else None)
    c1 = cell.counters()
    at_window = counter.snapshot()
    mem_peak, mem_by_chip = memory_peaks(devices)
    e2e = end_to_end(drive)

    lat = [x * 1e3 for x in drive["lateness"]]
    facts = dict(
        setup_s=setup_s, weight_init_s=cell.facts["weight_init_s"], warm_s=cell.facts["warm_s"],
        executables=exe["entries"], engine_compile_s=exe["compile_s"],
        xla_compiles=at_setup["compiles"], xla_compile_s=at_setup["compile_s"],
        persistent_cache_hits=at_setup["cache_hits"],
        persistent_cache_misses=at_setup["cache_misses"],
        compiles_in_window=at_window["compiles"] - at_setup["compiles"],
        memory_peak_bytes=mem_peak, memory_peak_bytes_by_chip=mem_by_chip,
        generator_late_ms_p50=percentile(lat, 50) if lat else None,
        generator_late_ms_max=max(lat) if lat else None,
        requests_sent=len(drive["tracks"]), drained=drive["drained"],
        ran_dry=drive["ran_dry"],
        **backlog(drive),
        **{f"delta_{k}": c1[k] - c0[k] for k in ("decode_steps", "tokens_generated",
                                                  "admitted", "exe_errors")},
    )
    for k, v in facts.items():
        print(f"fact {k}: {v}", file=sys.stderr)
    for k, v in e2e.items():
        print(f"metric {k}: {v}", file=sys.stderr)

    per_layer = {}
    breakdown = None
    device_info = dict(platform=dev.platform, kind=dev.device_kind, count=chips,
                       memory_peak_bytes=mem_peak, memory_peak_bytes_by_chip=mem_by_chip)
    if tracer is not None:
        summ, ctx = tracer.reduce(cell, drive, spec, peak)
        print(f"fact trace_planes: {sorted(ctx['trace']['devices'])}", file=sys.stderr)
        device_info.update(busy_s=summ["busy_s"], window_s=summ["window_s"])
        breakdown = dict(device_ops=summ["device_ops"], idle_gaps=summ["idle_gaps"])
        for m in spec["per_layer"]:
            mod = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                              "metric_" + m["name"].replace(".", "_"))
            v = mod.read(ctx)
            if v is not None:
                per_layer[m["name"]] = dict(value=float(v), unit=m["unit"])
                print(f"metric {m['name']}: {v}", file=sys.stderr)

    attempted = drive["tracks"]
    failed = [t for t in attempted if t.failure is not None or t.tokens is None]
    picked = correctness.sample(drive["tracks"], args.seed,
                                int(mix["check"]["tokens"]), int(mix["check"]["max_requests"]))
    n_tracks = len(drive["tracks"])
    gc.unfreeze()  # what set-up made may be freed again: the program's device state
    cell.release()
    del drive
    gc.collect()
    readings = correctness.compare(ref, cfg, mix, args.seed, picked,
                                   platform=dev.platform, control=args.control,
                                   mesh=cell.mesh)
    for k, v in readings.items():
        print(f"check {k}: {v}", file=sys.stderr)
    limit = spec["limits"].get("logit_gap_max", {}).get("limit")
    # with --control, the control stands in the program's place
    gap = readings["control_gap_max" if args.control else "logit_gap_max"]
    compared = {"logit_gap_max": dict(value=gap, limit=limit)}
    correct = (not failed and bool(picked) and limit is not None
               and gap <= limit and n_tracks > 0)

    if args.trace:
        metrics = per_layer
    else:
        metrics = {m["name"]: dict(value=e2e[m["name"]], unit=m["unit"])
                   for m in spec["end_to_end"] if m["name"] in e2e}
        metrics["setup_s"] = dict(value=setup_s, unit="s")
    result = dict(correct=bool(correct), attempted=len(attempted), failed=len(failed),
                  metrics=metrics, device=device_info)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def memory_peaks(devices):
    """(the fullest chip's peak bytes, each chip's peak): over the chips that
    report one; None where none does (the CPU reports no memory stats)."""
    by_chip = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    known = [b for b in by_chip if b is not None]
    return (max(known) if known else None), by_chip


def backlog(drive: dict) -> dict:
    """Whether the queue grew over the window: requests sent but not yet
    admitted when it closed, and the median time to first token of the
    first and the second half of the requests sent."""
    tracks = drive["tracks"]
    waiting = sum(1 for t in tracks if t.admitted is None or t.admitted > drive["t_close"])
    ttft = [(t.times[0] - t.due) * 1e3 if t.times else float("inf") for t in tracks]
    h = len(ttft) // 2
    return dict(waiting_at_close=waiting, sent=len(tracks),
                ttft_p50_first_half=percentile(ttft[:h], 50) if h else None,
                ttft_p50_second_half=percentile(ttft[h:], 50) if h else None)


class Tracer:
    """Traces a part of the window, centred in it, with the profiler."""

    LENGTH_S = 8.0

    def __init__(self, cell, seconds: float, keep=None):
        self.length = min(self.LENGTH_S, seconds)
        self.offset = (seconds - self.length) / 2
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state = "before"
        self.t0 = self.t1 = None
        self.span = None
        self.c0 = self.c1 = None
        self.cell = cell
        self.keep = keep

    def on_pump(self, now, t_open):
        import jax

        if self.state == "before" and now >= t_open + self.offset:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("traced_window")
            self.span.__enter__()
            self.t0 = time.perf_counter()
            self.c0 = self.cell.counters()
            self.state = "on"
        elif self.state == "on" and now >= self.t0 + self.length:
            self.t1 = time.perf_counter()
            self.c1 = self.cell.counters()
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self, cell, drive, spec, peak):
        import trace_reduce

        if self.state == "on":
            self.on_pump(float("inf"), 0.0)
        ids = [d.id for d in cell.devices]
        tr = trace_reduce.load(trace_reduce.find_xplane(self.dir), ids)
        if self.keep:
            shutil.copytree(self.dir, self.keep, dirs_exist_ok=True)
        shutil.rmtree(self.dir, ignore_errors=True)
        summ = trace_reduce.summary(tr)
        ctx = dict(trace=tr, summary=summ, t0=self.t0, t1=self.t1, drive=drive,
                   counters=(self.c0, self.c1),
                   dims=spec["ref"].dims(spec["cfg"]), peak=peak, mix=cell.mix,
                   chips=len(ids), device_ids=ids,
                   offset_ns=tr["window"][0] - int(self.t0 * 1e9))
        return summ, ctx


if __name__ == "__main__":
    sys.exit(main())
