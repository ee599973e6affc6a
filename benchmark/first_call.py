#!/usr/bin/env python3
"""One cell's set-up in one process, then what it built, by program.

    python3 benchmark/first_call.py --workload <cell> --seed <n> [--top 20]
                                    [--calls 1] [--records <file>]

Does what ``run.py`` does up to the end of its warm-up call (seeded data
on the device, the entry point's own initialisation, one call of exactly
the window's call) and prints, as its last line, one JSON object from
the program's build accounting (``quda_tpu/obs/build.py``):

* ``rows``: the ``--top`` heaviest (program, causing span path, API
  span, ordinal) with their seconds by stage (trace, lower, compile),
  how often the program was built and what the persistent cache
  answered, and ``rest``: everything else as one row;
* ``first_call``: the wall of the warm-up call; the benchmark's own
  ``first_call_*`` metrics of the records under it, each through the
  reader and the arguments its ``per_layer/*.json`` names (seconds by
  stage, of the solve and exit programs, of the eager remainder with
  its number of distinct programs), so this table and a ``--trace 1``
  line cannot differ; the seconds by span path; what is left of the
  wall after everything built: execution and Python;
* ``other_apis``: seconds by API span of everything built outside the
  first solve call (the loads; ``none``: outside any API span);
* ``later_calls`` (with ``--calls`` above 1: that many calls in all, a
  new source each): the rows of what was built under the solve calls
  after the first, and ``window_programs_built`` as the benchmark
  counts them.

``--records`` also writes every record as it was kept, one JSON object
a line.  The table of PERF.md section 5 is this line, one run a cell.  Fails
like ``run.py`` where jax's first device is not a TPU (``--rehearse``:
a tiny lattice on the CPU, never a measurement).
"""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as harness                      # noqa: E402
from benchmark.readers import program_build               # noqa: E402

METRICS = ("first_call_trace_s", "first_call_lower_s",
           "first_call_compile_s", "first_call_solve_program_s",
           "first_call_exit_program_s", "first_call_eager_s",
           "first_call_eager_programs", "window_programs_built")


def metric(name):
    """A per-layer metric of the build records, as ``run.py`` reads it."""
    spec = harness.load_json(harness.HERE, "per_layer", name + ".json")
    assert spec["reader"] == "program_build", spec
    return program_build.read({}, **spec["args"])


def table(records, first_call_s, top):
    from quda_tpu.obs import build
    rows = build.by_program(records)
    rest = {"programs": len(rows[top:]),
            **{k: sum(r[k] for r in rows[top:])
               for k in ("trace", "lower", "compile", "seconds", "builds")}}
    by_path, other, later = {}, {}, []
    for r in records:
        if r["inside"] is not None:
            continue
        if r["api"] not in program_build.SOLVE_APIS:
            other[r["api"]] = other.get(r["api"], 0.0) + r["seconds"]
        elif r["ordinal"] == 1:
            by_path[r["path"]] = by_path.get(r["path"], 0.0) + r["seconds"]
        else:
            later.append(r)
    values = {m: metric(m) for m in METRICS}
    return {
        "later_calls": build.by_program(later),
        "window_programs_built": values.pop("window_programs_built"),
        "rows": rows[:top], "rest": rest,
        "first_call": {
            "wall_s": first_call_s, **values,
            "left_s": first_call_s - (program_build.read({}, calls="first")
                                      or 0.0),
            "by_path": dict(sorted(by_path.items(), key=lambda kv: -kv[1]))},
        "other_apis": other}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--calls", type=int, default=1)
    ap.add_argument("--records", help="write the raw records here, JSONL")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny lattice on the CPU; never a measurement")
    args = ap.parse_args(argv)
    _, cell, config, traffic, lattice = harness.load_cell(args.workload,
                                                          args.rehearse)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != cell["chips"]):
        sys.stderr.write(f"first_call.py: cell {cell['name']} needs "
                         f"{cell['chips']} TPU chip(s); jax reports "
                         f"{device}. No result.\n")
        return 1
    data = importlib.import_module(f"{harness.PKG}.data")
    entry = harness.module("entry", config["entry"])
    links = data.links_for(args.seed, traffic, lattice)
    state = entry.open(config, traffic,
                       data.to_canonical_gauge(links, lattice))
    walls = []
    for i in range(args.calls):
        src = data.to_canonical_spinors(
            data.gaussian_sources(data.key_of(args.seed, 999 + i), lattice,
                                  int(config["sources_per_call"])), lattice)
        src.block_until_ready()
        t0 = time.perf_counter()
        x, info = entry.call(state, src)
        x.block_until_ready()
        walls.append(time.perf_counter() - t0)
    first_call_s = walls[0]
    from quda_tpu.obs import build
    records = build.snapshot()
    if args.records:
        with open(args.records, "w") as fh:
            fh.writelines(json.dumps(r) + "\n" for r in records)
    out = {"workload": cell["name"], "seed": args.seed, "device": device,
           "rehearse": args.rehearse, "iters": info["iters"],
           "call_walls_s": walls,
           **table(records, first_call_s, args.top)}
    entry.close(state)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
