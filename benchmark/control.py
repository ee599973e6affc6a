#!/usr/bin/env python3
"""Sound runs and the lower-precision control, seed after seed, in one
process (set-up is long; the chip is held once).

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --control 3

For each seed: new links and sources as a run makes them, ONE call of
the cell's entry point at the cell's own size (the timed path), and the
numbers ``correct.compare`` would compare.  For the first ``--control``
seeds also the control: the plain reference put in the program's place
and computed one precision down (``control_precision`` of the
configuration: every field stored in bfloat16), on the call's first
``--control-sources`` sources, with its own claimed residual in the
API's place.  The limits in the traffic files were set from these
readings (PERF.md section 2); ``tests/test_correct.py`` keeps the control
at a size a test run can hold.  ``--kappa`` overrides the traffic's (the
sweep that fixed it).  Not a measurement of speed.
"""

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
PKG = os.path.basename(HERE)


def numbers(check):
    return {n["name"]: n["value"] for n in check["numbers"]}


def one_seed(run, cell_name, seed, rehearse=False, kappa=None, control=0,
             control_maxiter=20000, out=print):
    """{"program": {...}, "control": {...}|None} for one seed."""
    import jax.numpy as jnp
    _, _, config, traffic, lattice = run.load_cell(cell_name, rehearse)
    if kappa is not None:
        traffic["kappa"] = kappa
    nx = lattice[3]
    data = importlib.import_module(f"{PKG}.data")
    correct = importlib.import_module(f"{PKG}.correct")
    reference = run.module("reference", config["reference"])
    entry = run.module("entry", config["entry"])
    n_src = int(config["sources_per_call"])

    links = data.links_for(seed, traffic, lattice)
    state = entry.open(config, traffic,
                       data.to_canonical_gauge(links, lattice))
    ref_links = run.folded_links(reference, config, links)
    b = data.gaussian_sources(data.key_of(seed, 1000), lattice, n_src)
    t0 = time.perf_counter()
    x, info = entry.call(state, data.to_canonical_spinors(b, lattice))
    x.block_until_ready()
    secs = time.perf_counter() - t0
    quiet = lambda *_: None
    check = correct.compare(
        reference, ref_links, float(traffic["kappa"]), nx,
        [{"label": "program", "sources": b,
          "solutions": data.from_canonical_spinors(x),
          "true_res": info["true_res"]}], traffic, out=quiet)
    row = {"seed": seed, "kappa": traffic["kappa"],
           "program": {"seconds": secs, "iters": info["iters"],
                       "converged": info["converged"],
                       "correct": check["correct"], **numbers(check)},
           "control": None}
    del x
    if control:
        store = config["control_precision"]
        k = float(traffic["kappa"])
        xs, claimed, iters = [], [], []
        t0 = time.perf_counter()
        for i in range(min(control, n_src)):
            xc, it = reference.solve_normal(
                ref_links, b[i], k, nx,
                float(config["invert_param"]["tol"]), control_maxiter,
                store=store)
            r = b[i] - reference.apply_m(ref_links, xc, k, nx, store=store)
            claimed.append(float(jnp.sqrt(jnp.sum(jnp.abs(r) ** 2)
                                          / jnp.sum(jnp.abs(b[i]) ** 2))))
            xs.append(xc)
            iters.append(int(it))
        cc = correct.compare(
            reference, ref_links, k, nx,
            [{"label": "control", "sources": b, "solutions": jnp.stack(xs),
              "true_res": claimed}], traffic, out=quiet)
        row["control"] = {"store": store, "iters": iters,
                          "seconds": time.perf_counter() - t0,
                          "correct": cc["correct"], **numbers(cc)}
    out(json.dumps(row))
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", type=int, default=0,
                    help="run the control on this many leading seeds")
    ap.add_argument("--control-sources", type=int, default=1)
    ap.add_argument("--kappa", default=None,
                    help="comma-separated; each is run on every seed")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax
    run = importlib.import_module(f"{PKG}.run")
    d0 = jax.devices()[0]
    if not args.rehearse and d0.platform != "tpu":
        sys.stderr.write("control.py: jax's first device is not a TPU\n")
        return 1
    print(json.dumps({"device": {"platform": d0.platform,
                                 "kind": d0.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    rows = []
    kappas = ([float(k) for k in args.kappa.split(",")] if args.kappa
              else [None])
    for kappa in kappas:
        for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
            rows.append(one_seed(
                run, args.workload, seed, rehearse=args.rehearse,
                kappa=kappa,
                control=args.control_sources if n < args.control else 0))
            sys.stdout.flush()
    prog = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows if r["control"]]
    summary = {"workload": args.workload, "seeds": len(rows),
               "program_all_correct": all(p["correct"] for p in prog),
               "program_res_max": max(p["res_max"] for p in prog),
               "program_agree_max": max(p["agree_max"] for p in prog),
               "program_iters": [min(min(p["iters"]) for p in prog),
                                 max(max(p["iters"]) for p in prog)]}
    if ctrl:
        summary.update(control_all_failed=not any(c["correct"]
                                                  for c in ctrl),
                       control_res_min=min(c["res_max"] for c in ctrl),
                       control_agree_min=min(c["agree_max"] for c in ctrl))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
