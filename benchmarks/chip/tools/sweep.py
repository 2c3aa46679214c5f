"""Serve an open-loop cell at several arrival rates in one process, to find
the highest rate it sustains: the rate whose traffic file the cell keeps is
about four fifths of that.

    python benchmarks/chip/tools/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,3,4

Set-up is the run's own (``run.start``, then the cell's weights and warm-up);
then one window per rate, each with the mix's lengths at that rate. Each
window prints one ``sweep`` line on standard error: the end-to-end metrics,
whether the queue grew (requests waiting at the close; the median time to
first token of the first and the second half of the requests), and the
spread of the gaps between tokens. No reference runs.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402


def gap_shape(drive: dict) -> dict:
    """Quantiles of every gap between tokens, and the share of gaps over
    twice the median (gaps that hold a prefill)."""
    gaps = [(b - a) * 1e3 for t in drive["tracks"] for a, b in zip(t.times, t.times[1:])]
    if not gaps:
        return {}
    med = run.percentile(gaps, 50)
    out = {f"itl_p{q}_ms": run.percentile(gaps, q) for q in (50, 90, 95, 97, 99)}
    out["gaps"] = len(gaps)
    out["long_gap_share"] = sum(g > 2 * med for g in gaps) / len(gaps)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated arrivals per second")
    args = ap.parse_args(argv)
    spec, devices, _ = run.start(args.workload)

    import harness
    import traffic as traffic_lib

    mix, cfg, ref = spec["mix"], spec["cfg"], spec["ref"]
    if mix["loop"] != "open":
        run.fail("the sweep is for open-loop mixes")
    cell = harness.Cell(cfg, mix, ref, args.seed, devices[:int(spec["cell"]["chips"])])
    cell.build()
    cell.warm()
    for rate in [float(x) for x in args.rates.split(",")]:
        cell.mix = dict(mix, rate_per_s=rate)
        reqs = traffic_lib.generate(cell.mix, args.seed, args.seconds, ref.dims(cfg)["vocab"])
        drive = cell.drive(reqs, args.seconds)
        out = {"rate": rate, **run.end_to_end(drive), **run.backlog(drive),
               **gap_shape(drive)}
        print(f"sweep {json.dumps(out)}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
