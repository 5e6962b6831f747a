"""Device milliseconds per training step in collective ops (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute; their async
`-start` / `-done` halves too) during which no other op runs on that chip:
the union of the collectives' intervals less what the other ops' intervals
cover, on chip 0, over the traced steps.  A program on one chip has no such
op and reads nothing."""
from benchmark.metrics._common import TRAIN_PROGRAM
from benchmark.reduce import xplane

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def is_collective(text: str) -> bool:
    op = xplane.parse_op(text)
    return op["opcode"].startswith(COLLECTIVES) \
        or op["name"].lstrip("%").startswith(COLLECTIVES)


def exposed_ns(events) -> float:
    """ns inside the collectives' intervals that no other op covers."""
    coll = xplane.merged_intervals(e for e in events if is_collective(e[0]))
    rest = xplane.merged_intervals(
        e for e in events if not is_collective(e[0]))
    total = 0.0
    for a, b in coll:
        covered = sum(min(b, d) - max(a, c) for c, d in rest
                      if min(b, d) > max(a, c))
        total += (b - a) - covered
    return total


def read(ctx):
    win, trace = ctx["window"], ctx["trace"]
    if win.get("kind") != "train_job" or trace is None:
        return None
    runs = xplane.module_runs(trace, TRAIN_PROGRAM)
    if not runs:
        return None
    found, total = False, 0.0
    for _, s, d in runs:
        events = xplane.ops_within(trace, s, s + d)
        found = found or any(is_collective(e[0]) for e in events)
        total += exposed_ns(events)
    return total / 1e6 / len(runs) if found else None
