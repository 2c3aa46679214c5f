"""Print the structure of a profiler trace: planes, lines, event names and
stats, for reading one trace by hand before writing code against it.

    JAX_PLATFORMS=cpu python benchmarks/chip/tools/dump_trace.py <trace dir or .xplane.pb>
"""
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_reduce  # noqa: E402


def main(path):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            tot = collections.Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
            print(f"  LINE {line.name!r}: {len(evs)} events, {len(names)} names")
            if plane.name.startswith("/host") and len(names) > 40:
                keep = [n for n in names if n in trace_reduce.HOST_SPANS + (trace_reduce.WINDOW_SPAN,)]
            else:
                keep = [n for n, _ in tot.most_common(25)]
            for n in keep:
                ex = next(e for e in evs if e.name == n)
                stats = {k: (v if len(str(v)) < 120 else str(v)[:120]) for k, v in ex.stats}
                print(f"    {n!r}: n={names[n]} total_ms={tot[n] / 1e6:.3f} first=({ex.start_ns}, {ex.duration_ns}) stats={stats}")


if __name__ == "__main__":
    main(sys.argv[1])
