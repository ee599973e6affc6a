"""``program_build`` for the records under ONE named API span: what the
program's build accounting (``quda_tpu/obs/build.py``) recorded under
the calls of ``api`` (``program_build`` reads ``invert_quda`` and
``invert_multi_src_quda`` only), summed over the process's first such
call (``calls`` = ``first``, the harness's warm-up) or over every later
one (``later``: the window's; a program built there is built again on
every call or was not warmed up).  ``stage``: one of trace, lower,
compile (all where not given).  ``programs``: only those programs.
``count`` = ``records`` gives how many build events in place of their
seconds (a record stands for itself and its ``repeats``).  A trace
nested in another program's trace is part of that one and never
counted.

None where the program keeps no such records or none under a call of
that API; a sum of seconds with nothing to sum is None too, a count is
0."""

import importlib


def read(ctx, api, calls, stage=None, programs=None, count=None):
    try:
        build = importlib.import_module("quda_tpu.obs.build")
    except ImportError:
        return None
    mine = [r for r in build.snapshot()
            if r["api"] == api and r["inside"] is None]
    if not mine:
        return None
    recs = [r for r in mine
            if (r["ordinal"] == 1) == (calls == "first")
            and (stage is None or r["stage"] == stage)
            and (programs is None or r["program"] in programs)]
    if count == "records":
        return sum(1 + r.get("repeats", 0) for r in recs)
    return sum(r["seconds"] for r in recs) if recs else None
