"""What the program's build accounting recorded (``quda_tpu/obs/
build.py``: one record per program and stage that jax traced, lowered
and compiled or fetched, with the API call it was built under), summed
over the records of the process's solve calls.

``calls``: ``first`` is the process's first solve call, the harness's
warm-up; ``later`` every solve call after it, the window's: a program
built there is built again on every call or was not warmed up.
``stage``: one of trace, lower, compile (all where not given).
``programs``: only those programs; with ``others`` every program BUT
those (the eager operations between the cached programs).  ``count``:
``records`` or ``programs`` gives how many build events, or distinct
programs, in place of their seconds (a record stands for itself and
the ``repeats`` the program folded into it: the same program traced
again under the same spans in a later call).  A trace nested in another
program's trace is part of that one and never counted.

None where the program keeps no such records (a parent commit), or none
under a solve call; a sum of seconds with nothing to sum is None too,
a count is 0."""

import importlib

SOLVE_APIS = ("invert_quda", "invert_multi_src_quda")


def read(ctx, calls, stage=None, programs=None, others=False, count=None):
    try:
        build = importlib.import_module("quda_tpu.obs.build")
    except ImportError:
        return None
    solve = [r for r in build.snapshot()
             if r["api"] in SOLVE_APIS and r["inside"] is None]
    if not solve:
        return None
    recs = [r for r in solve
            if (r["ordinal"] == 1) == (calls == "first")
            and (stage is None or r["stage"] == stage)
            and (programs is None
                 or (r["program"] in programs) != bool(others))]
    if count == "records":
        return sum(1 + r.get("repeats", 0) for r in recs)
    if count == "programs":
        return len({r["program"] for r in recs})
    return sum(r["seconds"] for r in recs) if recs else None
