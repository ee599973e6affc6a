#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (import, seeded data made on the device, the entry point's own
initialisation, one warm-up call of exactly the window's call), then a
closed loop of calls for ``--seconds``, then the comparison with the
plain reference.  The last line of stdout is the result object.  The
harness knows no cell, configuration, traffic mix or metric by name: it
finds each in the file ``BENCHMARK.json`` names (README.md).

Fails (non-zero, no result line) when jax's first device is not a TPU or
the chips differ from what the cell asks for.  ``--rehearse`` is the
benchmark's own switch for a tiny lattice on the CPU: it prints the CPU
device line and is never a measurement.
"""

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import random            # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PKG = os.path.basename(HERE)
TRACE_CALLS = 2          # calls of the window a --trace 1 run profiles


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def by_name(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def module(kind, name):
    return importlib.import_module(f"{PKG}.{kind}.{name}")


def load_cell(workload, rehearse=False):
    """(bench, cell, config, traffic, lattice) of one cell; ``lattice``
    in array order (T, Z, Y, X) — the files give extents as (x, y, z, t)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], workload, "workload")
    config = load_json(
        ROOT, by_name(bench["configs"], cell["config"], "config")["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    lattice = tuple(reversed(config["rehearse_lattice"] if rehearse
                             else config["lattice"]))
    return bench, cell, config, traffic, lattice


def folded_links(reference, config, links):
    """The reference's links with the configuration's t boundary."""
    return reference.fold_boundary(
        links, config["gauge_param"]["t_boundary"] == "antiperiodic")


def say(**kw):
    print(json.dumps(kw), flush=True)


def metric_values(bench, section, cell, ctx):
    """The cell's metrics of one section of BENCHMARK.json, each through
    the reader its own file names; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in bench[section]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        spec = load_json(HERE, section, m["name"] + ".json")
        value = module("readers", spec["reader"]).read(
            ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny lattice on the CPU; never a measurement")
    args = ap.parse_args(argv)

    bench, cell, config, traffic, lattice = load_cell(args.workload,
                                                      args.rehearse)
    nx = lattice[3]
    n_src = int(config["sources_per_call"])

    # the compile cache at a fixed path inside the checkout, whatever the
    # machine set: the program takes the directory this variable names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.path.insert(0, ROOT)
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != cell["chips"]):
        sys.stderr.write(f"run.py: cell {cell['name']} needs "
                         f"{cell['chips']} TPU chip(s); jax reports "
                         f"{device}. No result.\n")
        return 1
    say(phase="device", device=device, rehearse=args.rehearse,
        lattice=list(lattice), jax=jax.__version__)

    data = importlib.import_module(f"{PKG}.data")
    reference = module("reference", config["reference"])
    correct = importlib.import_module(f"{PKG}.correct")
    entry = module("entry", config["entry"])

    def links_of_seed():
        return data.links_for(args.seed, traffic, lattice)

    def sources_of_call(i):
        """Call i's sources in the reference layout (i = -1: warm-up)."""
        return data.gaussian_sources(data.key_of(args.seed, 1000 + i),
                                     lattice, n_src)

    def timed_call(i):
        src = data.to_canonical_spinors(sources_of_call(i), lattice)
        src.block_until_ready()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_call"):
            x, info = entry.call(state, src)
            x.block_until_ready()
        secs = time.perf_counter() - t0
        kept = data.from_canonical_spinors(x)   # compact: 35 MB a source
        return secs, info, kept

    def sample(label, i, kept, info):
        return {"label": label, "sources": sources_of_call(i),
                "solutions": kept, "true_res": info["true_res"]}

    # ---- set-up ----------------------------------------------------------
    links = links_of_seed()
    state = entry.open(config, traffic,
                       data.to_canonical_gauge(links, lattice))
    first_call_s, warm_info, warm_kept = timed_call(-1)
    say(phase="warm-up", seconds=first_call_s, **warm_info)
    # the reference's time is not set-up: its clock is stopped here
    t_ref0 = time.perf_counter()
    ref_links = folded_links(reference, config, links)
    warm_check = correct.compare(
        reference, ref_links, float(traffic["kappa"]), nx,
        [sample("warm-up", -1, warm_kept, warm_info)], traffic)
    del links, ref_links, warm_kept
    t_ref = time.perf_counter() - t_ref0

    # ---- the window ------------------------------------------------------
    pick = random.Random(args.seed)
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracing, traced = False, 0
    calls, kept_sample, kept_last = [], None, None
    counters0 = entry.counters()
    t_win0 = time.perf_counter()
    setup_s = t_win0 - T_START - t_ref
    while (time.perf_counter() - t_win0 < args.seconds
           or (args.trace and traced < 1)):
        i = len(calls)
        if args.trace and i == 1:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        secs, info, kept = timed_call(i)
        calls.append({"call": i, "seconds": secs, **info})
        say(phase="call", **calls[-1])
        kept_last = (i, kept, info)
        if pick.random() < 1.0 / (i + 1):     # reservoir of one
            kept_sample = kept_last
        if tracing:
            traced += 1
            if traced == TRACE_CALLS:
                jax.profiler.stop_trace()
                tracing = False
    if tracing:
        jax.profiler.stop_trace()
    window_s = time.perf_counter() - t_win0
    counters1 = entry.counters()
    mem = [d.memory_stats() or {} for d in devs]
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)

    # ---- correctness, after the window ------------------------------------
    samples = [sample("last call", *kept_last)]
    if kept_sample[0] != kept_last[0]:
        samples.insert(0, sample(f"sampled call {kept_sample[0]}",
                                 *kept_sample))
    ref_links = folded_links(reference, config, links_of_seed())
    t_ref0 = time.perf_counter()
    check = correct.compare(reference, ref_links, float(traffic["kappa"]),
                            nx, samples, traffic)
    say(phase="reference", seconds_after_window=time.perf_counter()
        - t_ref0, seconds_in_setup=t_ref, sources_checked=check["checked"]
        + warm_check["checked"])

    res_bound = float(traffic["res_bound"])
    good = [bool(c) and r <= res_bound for call in calls
            for c, r in zip(call["converged"], call["true_res"])]
    attempted, failed = len(good), len(good) - sum(good)
    ok = bool(check["correct"] and warm_check["correct"] and failed == 0)

    # ---- metrics -----------------------------------------------------------
    reduced = None
    if args.trace:
        tr = importlib.import_module(f"{PKG}.trace_reduce")
        xplane = tr.find_xplane(trace_dir)
        reduced = tr.reduce(tr.load_xplane(xplane))
        say(phase="trace", file=os.path.relpath(xplane, ROOT),
            bytes=os.path.getsize(xplane), calls_traced=traced,
            found_device_ops=reduced is not None)
    ctx = {"calls": calls, "window_s": window_s, "chips": cell["chips"],
           "good_sources": sum(good), "setup_s": setup_s,
           "first_call_s": first_call_s, "counters0": counters0,
           "counters1": counters1, "memory": mem, "trace": reduced,
           "config": config, "traffic": traffic, "lattice": lattice,
           "device_kind": device["kind"], "package": PKG}
    say(phase="window", calls=len(calls), window_s=window_s,
        setup_s=setup_s, attempted=attempted, failed=failed)
    metrics = metric_values(
        bench, "per_layer" if args.trace else "end_to_end", cell, ctx)
    device["memory_peak_bytes"] = int(peak)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    entry.close(state)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
