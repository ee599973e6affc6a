"""A statistic of a field the harness recorded for every call of the
window: a per-call number (``seconds``: wall of the call, host clock
from before it to after ``block_until_ready`` of its solution) or a
per-source list the API returned (``iters``, ``true_res``), taken over
every source of every call."""

import statistics

STATS = {"median": statistics.median, "mean": statistics.fmean, "max": max}


def read(ctx, field, stat="median"):
    vals = []
    for call in ctx["calls"]:
        v = call.get(field, ())
        vals += list(v) if isinstance(v, (list, tuple)) else [v]
    return STATS[stat](vals) if vals else None
