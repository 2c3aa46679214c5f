"""Share of the device's busy time spent in collective operations, from the
trace: on the cell's first plane, the union of the intervals of the `XLA
Ops` that are collectives, over the union of all `XLA Ops` intervals, both
clipped to the traced window. A collective is an operation whose HLO name
is an all-gather, all-reduce, reduce-scatter, all-to-all or
collective-permute, their ``-start``/``-done`` halves, or a fusion that XLA
names after one: on a tensor-parallel mesh, the gathers that put each
column-parallel analog dot's shards back together."""
import readers
import trace_reduce

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def is_collective(name: str) -> bool:
    op = trace_reduce.short(name)
    return not trace_reduce.is_container(name) and any(c in op for c in COLLECTIVES)


def read(ctx):
    ws, we = ctx["trace"]["window"]
    ops = readers.device(ctx)["ops"]
    busy = trace_reduce.busy(ops, ws, we)
    if busy <= 0:
        return None
    return 100.0 * trace_reduce.busy([e for e in ops if is_collective(e[0])], ws, we) / busy
