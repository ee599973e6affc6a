#!/usr/bin/env python3
"""Chip smoke: the Wilson solve path, once, on a real TPU.

Drives the entry points a user calls — ``invert_quda``,
``invert_multi_src_quda`` and ``SolveService`` — at 24^4 (the volume
every bench row in the repo is written for), checks every answer with
the complex64 ``DiracWilson`` operator ON THE DEVICE, and proves the
solves ran the compiled pallas kernels (``tpu_custom_call`` in the
compiled operator, no failed tuner candidate), not a stand-in.

    python chip_smoke.py              # one chip: phases A-F
    python chip_smoke.py --chips 4    # four chips: split-grid vs batched
                                      # (+ the sharded eo operator), only

ONE process, which imports jax once and holds the chip throughout; no
child process, no re-exec, no platform override.  x64 stays OFF (the
jax default): under x64 Mosaic refuses every pallas kernel.  Data comes
from ``--seed``; nothing is read from the repo's records.  Exits
non-zero — printing why — when jax's first device is not a TPU, when a
phase raises, or when a check fails; no phase is allowed to fail and
let the run go on.  Every line of stdout is one JSON object; the LAST
is ``{"ok": true, "device": {...}}`` with the device as jax reports it.
"""

import argparse
import json
import math
import os
import sys
import time

DEADLINE_S = {1: 1100, 4: 540}   # hard wall per invocation (seconds)
L = 24                    # lattice extent (T=Z=Y=X)
KAPPA = 0.124             # as __graft_entry__.py
TOL = 1e-6
RES_BOUND = 5e-6          # true residual ||b - Mx||/||b|| accepted
AGREE_BOUND = 1e-4        # relative agreement between two solve routes


class SmokeFailure(RuntimeError):
    pass


def emit(**kw):
    """One JSON line per phase, stamped with device 0's HBM use."""
    import jax
    st = jax.devices()[0].memory_stats() or {}
    kw["hbm_gib"] = {k: round(st[k] / 2 ** 30, 3) for k in
                     ("bytes_in_use", "peak_bytes_in_use") if k in st}
    print(json.dumps(kw), flush=True)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def arm_deadline(seconds):
    """A wedged device call never returns to Python: a daemon timer
    prints why and hard-exits NON-ZERO, so the run stays inside its
    time limit and leaves nothing running."""
    import threading

    def fire():
        sys.stderr.write(f"chip_smoke: FAILED — still running after "
                         f"{seconds} s (a phase hung); no result\n")
        sys.stderr.flush()
        os._exit(2)
    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()


def _child_pids():
    """PIDs whose parent is this process (Linux /proc scan)."""
    me, kids = os.getpid(), []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as fh:
                # pid (comm) state ppid ...; comm may contain spaces
                ppid = int(fh.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        if ppid == me:
            kids.append(int(name))
    return kids


def make_data(seed, n_src):
    """Seeded random SU(3) links (made on the device, handed to the API
    as the host array a user would pass) + ``n_src`` Gaussian sources."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.geometry import LatticeGeometry
    geom = LatticeGeometry((L, L, L, L))
    gauge = np.asarray(GaugeField.random(jax.random.PRNGKey(seed), geom,
                                         dtype=jnp.complex64).data)
    rng = np.random.default_rng(seed + 1)
    shp = (n_src, L, L, L, L, 4, 3)
    srcs = (rng.standard_normal(shp, np.float32)
            + 1j * rng.standard_normal(shp, np.float32)).astype(np.complex64)
    return geom, gauge, srcs


def gauge_param():
    from quda_tpu.interfaces.params import GaugeParam
    return GaugeParam(X=(L, L, L, L), t_boundary="antiperiodic",
                      cuda_prec="single")


def invert_param():
    """A fresh InvertParam per call (the API writes results into it)."""
    from quda_tpu.interfaces.params import InvertParam
    return InvertParam(dslash_type="wilson", inv_type="cg",
                       solve_type="normop-pc", kappa=KAPPA, tol=TOL,
                       maxiter=2000, cuda_prec="single",
                       cuda_prec_sloppy="auto")


def make_checker(gauge, geom):
    """rel(b, x) = ||b - M x|| / ||b|| with the complex64 full-lattice
    operator on the default device — independent of the pair / pallas
    path under test (and the proof that complex executes here).

    Applied EAGERLY, op by op, as the API's own epilogue does: fused
    into ONE jitted program the canonical-layout operator asks for
    13.35 GB of temporaries at 24^4 (its (...,3,3)/(...,4,3) einsum
    intermediates tile-pad ~57x) and no longer loads next to eight
    resident sources (RESOURCE_EXHAUSTED on the v5e, PR 22)."""
    import jax.numpy as jnp

    from quda_tpu.models.wilson import DiracWilson
    d = DiracWilson(jnp.asarray(gauge, jnp.complex64), geom, KAPPA,
                    antiperiodic_t=True)

    def run(b, x):
        b = jnp.asarray(b, jnp.complex64)
        x = jnp.asarray(x, jnp.complex64)
        check(x.shape == b.shape, f"solution shape {x.shape} != {b.shape}")
        r = b - d.M(x)
        v = float(jnp.sqrt(jnp.sum(jnp.abs(r) ** 2)
                           / jnp.sum(jnp.abs(b) ** 2)))
        check(math.isfinite(v), "non-finite residual from the complex check")
        return v
    return run


def rel_diff(a, b):
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.complex64)
    b = jnp.asarray(b, jnp.complex64)
    return float(jnp.sqrt(jnp.sum(jnp.abs(a - b) ** 2)
                          / jnp.sum(jnp.abs(b) ** 2)))


# -- phase A ----------------------------------------------------------------

def phase_device(chips):
    import importlib.metadata as md

    import jax
    import jaxlib
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: phase A failed — jax's first device is "
            f"platform {d0.platform!r} ({d0.device_kind!r}), not a TPU; "
            "this program measures the chip and never falls back\n")
        sys.exit(1)
    from quda_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()   # before the first compile
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "unknown"
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    emit(phase="A", device=device, jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         x64=bool(jax.config.jax_enable_x64),
         matmul_precision=jax.config.jax_default_matmul_precision,
         compile_cache_dir=cache_dir,
         jax_compilation_cache_dir=jax.config.jax_compilation_cache_dir)
    check(not jax.config.jax_enable_x64,
          "jax_enable_x64 is on: Mosaic refuses the pallas kernels")
    check(len(devs) == chips,
          f"--chips {chips} but jax sees {len(devs)} device(s)")
    return device


# -- phase B ----------------------------------------------------------------

def phase_invert(api, gauge, b, checker):
    """init -> load gauge -> invert_quda twice (cold incl. compile, warm)."""
    import numpy as np
    api.init_quda()
    api.load_gauge_quda(gauge, gauge_param())
    runs = []
    x = None
    for which in ("cold", "warm"):
        p = invert_param()
        t0 = time.perf_counter()
        x = api.invert_quda(b, p)
        x.block_until_ready()
        secs = time.perf_counter() - t0
        runs.append({"call": which, "seconds": secs,
                     "iterations": p.iter_count, "true_res": p.true_res,
                     "converged": bool(p.converged)})
        check(p.converged and np.isfinite(p.true_res),
              f"invert_quda ({which}) did not converge: {runs[-1]}")
    res = checker(b, x)
    emit(phase="B", api="invert_quda", lattice=[L] * 4, kappa=KAPPA,
         tol=TOL, runs=runs, checked_res=res, bound=RES_BOUND,
         check="||b - M x||/||b||, complex64 DiracWilson.M on the device")
    check(res <= RES_BOUND, f"invert_quda residual {res:.3e} > {RES_BOUND}")
    return x


def mdagm_with_links_as_args(op):
    """``op.MdagM_pairs`` as fn(links, v) + the resident link arrays: a
    jit that merely closes over ``op`` bakes ~0.5 GB of links into the
    executable as constants (slow to compile, too big to cache)."""
    import copy
    links = (op.gauge_eo_pp, op._u_bw)

    def fn(links, v):
        o = copy.copy(op)
        o.gauge_eo_pp, o._u_bw = links
        return o.MdagM_pairs(v)
    return fn, links


# -- phase C ----------------------------------------------------------------

def phase_kernels(api):
    """The solve in B ran the compiled pallas kernels, not a stand-in."""
    import jax

    from quda_tpu.utils import tune as qtune
    on_tpu = jax.default_backend() == "tpu"
    ip = invert_param()
    check(api._pallas_enabled(on_tpu) and api._packed_enabled(on_tpu),
          "the packed pallas route is not enabled on this backend")
    sloppy = api._resolve_sloppy(ip)       # "half" (bf16) on a TPU
    ws = api._WilsonPairsSolve(
        api._resident_wilson(ip, (api._pair_store(sloppy),)), ip.kappa)
    check(not ws.op._pallas_interpret,
          "the solve operator is in pallas INTERPRET mode")
    found = {}
    for name, op in (("precise_f32", ws.op),
                     ("sloppy_" + sloppy, ws.sloppy(sloppy))):
        x = jax.ShapeDtypeStruct((4, 3, 2, L, L, L * L // 2),
                                 op.store_dtype)
        fn, links = mdagm_with_links_as_args(op)
        txt = jax.jit(fn).lower(links, x).compile().as_text()
        found[name] = txt.count("tpu_custom_call")
        check(found[name] > 0,
              f"no tpu_custom_call in the compiled {name} MdagM_pairs")
    failed = list(qtune._failed)
    emit(phase="C", solve_form=api._solve_form(ws),
         precision_form=ws.op._precision_form,
         tpu_custom_call_mentions=found,
         tuner_failed_candidates=failed,
         tuner_winners={k: v.get("param")
                        for k, v in qtune.cache_snapshot().items()})
    check(not failed, f"tuner candidates failed: {failed}")
    return ws


# -- phase D ----------------------------------------------------------------

def phase_multi_src(api, srcs, checker, x_single=None, label="D"):
    import numpy as np
    p = invert_param()
    t0 = time.perf_counter()
    X = api.invert_multi_src_quda(srcs, p)
    X.block_until_ready()
    secs = time.perf_counter() - t0
    res = [checker(srcs[i], X[i]) for i in range(len(srcs))]
    out = dict(phase=label, api="invert_multi_src_quda", n_src=len(srcs),
               seconds=secs, iterations=list(p.iter_count_multi),
               true_res_multi=list(p.true_res_multi), checked_res=res,
               bound=RES_BOUND)
    if x_single is not None:
        out["agree_with_invert_quda"] = rel_diff(X[0], x_single)
    emit(**out)
    check(all(np.isfinite(r) and r <= RES_BOUND
              for r in list(p.true_res_multi) + res),
          f"multi-source residual above {RES_BOUND}: {out}")
    if x_single is not None:
        check(out["agree_with_invert_quda"] <= AGREE_BOUND,
              f"source 0 disagrees with invert_quda: "
              f"{out['agree_with_invert_quda']:.3e}")
    return X


# -- phase E ----------------------------------------------------------------

def phase_service(gauge, srcs, checker):
    import threading

    from quda_tpu.serve import SolveService
    gp, ip = gauge_param(), invert_param()
    kids0 = _child_pids()
    # a 100 ms coalescing window: the six requests below land in it
    svc = SolveService(batch_window_ms=100.0).start()
    try:
        svc.load_gauge("g0", gauge, gp)
        t0 = time.perf_counter()
        tickets = [svc.submit(srcs[i], ip, "g0") for i in range(len(srcs))]
        outs = [t.result(timeout=900) for t in tickets]
        secs = time.perf_counter() - t0
        worker = [t.name for t in threading.enumerate()
                  if t.name == "quda-serve"]
        kids = sorted(set(_child_pids()) - set(kids0))
    finally:
        svc.stop()
    res = [checker(srcs[i], o.x) if o.x is not None else float("nan")
           for i, o in enumerate(outs)]
    emit(phase="E", api="SolveService", requests=len(outs),
         statuses=[o.status for o in outs],
         batch_sizes=[o.batch_size for o in outs],
         iterations=[o.iter_count for o in outs],
         true_res=[o.true_res for o in outs], checked_res=res,
         seconds_per_request=[o.secs for o in outs], seconds_total=secs,
         errors=[o.error for o in outs if o.error],
         worker_threads=worker, child_processes=kids, bound=RES_BOUND)
    check(all(o.status == "converged" for o in outs),
          f"service statuses: {[o.status for o in outs]}")
    check(all(r <= RES_BOUND for r in res),
          f"service residual above {RES_BOUND}: {res}")
    check(worker == ["quda-serve"] and not kids,
          f"service must be one in-process thread: {worker}, {kids}")


# -- phase F ----------------------------------------------------------------

def phase_timing(api, ws, device, n_apply=200, reps=3):
    """One honest timing, printed, not judged: seconds per MdagM_pairs
    from a jitted fori_loop ended by block_until_ready()."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quda_tpu.obs import roofline as orf
    mdagm, links = mdagm_with_links_as_args(ws.op)
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (4, 3, 2, L, L, L * L // 2), jnp.float32)

    @jax.jit
    def power(links, x):
        # a few normalised applications: the growth factor that keeps
        # the timed chain finite without a reduction inside it
        def body(_, c):
            v, _ = c
            y = mdagm(links, v)
            n = jnp.sqrt(jnp.sum(y * y))
            return y / n, n / jnp.sqrt(jnp.sum(v * v))
        return jax.lax.fori_loop(0, 20, body, (x, jnp.float32(1.0)))

    @jax.jit
    def chain(links, x, scale):
        return jax.lax.fori_loop(
            0, n_apply, lambda _, v: mdagm(links, v) * scale, x)

    x, lam = power(links, x)
    scale = 1.0 / lam
    chain(links, x, scale).block_until_ready()   # compile + warm
    secs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        y = chain(links, x, scale)
        y.block_until_ready()
        secs.append(time.perf_counter() - t0)
    check(bool(jnp.all(jnp.isfinite(y))), "timed chain went non-finite")
    form = api._solve_form(ws)
    # MdagM = 2 PC M applies = 4 dslash invocations over volume/2 sites
    row = orf.attribute(form, L ** 4 // 2, 2 * n_apply,
                        float(np.median(secs)),
                        flops_per_site=ws.flops_per_site_M(),
                        dslash_per_apply=2.0, device_kind=device["kind"])
    peak = orf.DEVICE_PEAKS[device["kind"]]["gbps"]
    emit(phase="F", what="MdagM_pairs f32, jitted fori_loop, "
         "block_until_ready", n_apply=n_apply, loop_seconds=secs,
         seconds_per_MdagM=float(np.median(secs)) / n_apply,
         traffic_model=form, bytes_per_site_per_dslash=row["bytes_per_site"],
         implied_gbps=row["gbps"], implied_gflops=row["gflops"],
         published_peak_gbps=peak, pct_published_peak_bw=row["pct_peak_bw"],
         suspect=bool(row["gbps"] > peak), judged=False)


# -- four chips --------------------------------------------------------------

def _per_device(x):
    import jax
    return {"sharding": str(x.sharding),
            "shard_devices": sorted(s.device.id
                                    for s in x.addressable_shards),
            "bytes_in_use": {d.id: (d.memory_stats() or {}).get(
                "bytes_in_use") for d in jax.devices()}}


def phase_four_chips(api, geom, gauge, srcs, checker):
    """(a) split-grid multi-source solve on 4 devices, (b) the same
    sources through the one-device batched-pairs route, (c) the
    t-sharded eo Wilson operator against the single-device application
    (last: its halo-policy race runs kernels new to hardware)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quda_tpu.parallel.split import multi_src_route
    api.init_quda()
    api.load_gauge_quda(gauge, gauge_param())

    # (a) the route the API takes when it sees >1 device
    check("QUDA_TPU_MULTI_SRC_SPLIT" not in os.environ,
          "QUDA_TPU_MULTI_SRC_SPLIT is pinned from outside")
    route, mesh, _ = multi_src_route(len(srcs))
    check(route == "split" and mesh is not None,
          f"4 sources on {len(jax.devices())} devices routed {route!r}")
    p = invert_param()
    t0 = time.perf_counter()
    Xs = api.invert_multi_src_quda(srcs, p)
    Xs.block_until_ready()
    secs = time.perf_counter() - t0
    place = _per_device(Xs)
    res = [checker(srcs[i], Xs[i]) for i in range(len(srcs))]
    emit(phase="4a", route=route, mesh=dict(mesh.shape), seconds=secs,
         iterations=list(p.iter_count_multi),
         true_res_multi=list(p.true_res_multi), checked_res=res,
         bound=RES_BOUND, **place)
    check(place["shard_devices"] == sorted(d.id for d in jax.devices()),
          f"solution shards live on {place['shard_devices']}, not on "
          "every device")
    check(all(v for v in place["bytes_in_use"].values()),
          f"a device holds nothing: {place['bytes_in_use']}")
    check(all(r <= RES_BOUND for r in list(p.true_res_multi) + res),
          f"split-grid residual above {RES_BOUND}")

    # (b) what it is compared with: the batched-pairs route, one device
    os.environ["QUDA_TPU_MULTI_SRC_SPLIT"] = "0"
    try:
        check(multi_src_route(len(srcs), split_mode="0")[0] == "batched",
              "QUDA_TPU_MULTI_SRC_SPLIT=0 did not select the batched route")
        Xb = phase_multi_src(api, srcs, checker, label="4b")
    finally:
        del os.environ["QUDA_TPU_MULTI_SRC_SPLIT"]
    agree = [rel_diff(Xs[i], Xb[i]) for i in range(len(srcs))]
    emit(phase="4ab", split_vs_batched_rel_diff=agree, bound=AGREE_BOUND)
    check(all(a <= AGREE_BOUND for a in agree),
          f"split and batched solutions disagree: {agree}")

    # (c) the sharded eo operator under the default policy
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.parallel.mesh import make_lattice_mesh
    from quda_tpu.utils import tune as qtune
    on_tpu = jax.default_backend() == "tpu"
    dpk = DiracWilsonPC(jnp.asarray(gauge, jnp.complex64), geom,
                        KAPPA).packed()
    kw = dict(use_pallas=True, pallas_interpret=not on_tpu)
    one = dpk.pairs(jnp.float32, **kw)
    # t-only decomposition: a (2,2,1,1) grid leaves local Z=12, which
    # has no legal z-block under the default 6 MB VMEM budget at 24^4
    # (_pick_bz raises; found by compiling for the described chip)
    lat_mesh = make_lattice_mesh(grid=(4, 1, 1, 1))
    shd = dpk.pairs(jnp.float32, mesh=lat_mesh, **kw)
    be, bo = even_odd_split(jnp.asarray(srcs[0], jnp.complex64), geom)
    ref = jax.jit(one.MdagM_pairs)(one.prepare_pairs(be, bo))
    out = jax.jit(shd.MdagM_pairs)(shd.prepare_pairs(be, bo))
    out.block_until_ready()
    diff = float(jnp.max(jnp.abs(np.asarray(out) - np.asarray(ref)))
                 / jnp.max(jnp.abs(ref)))
    failed = list(qtune._failed)
    emit(phase="4c", mesh=dict(lat_mesh.shape),
         sharded_policy=str(shd._sharded_policy),
         max_abs_diff_rel=diff, bound=1e-5, out_sharding=str(out.sharding),
         tuner_failed_candidates=failed)
    check(np.isfinite(diff) and diff <= 1e-5,
          f"sharded MdagM_pairs differs from one device: {diff:.3e}")
    check(not failed, f"tuner candidates failed: {failed}")
    api.end_quda()


# -- driver -----------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    arm_deadline(DEADLINE_S[args.chips])
    device = phase_device(args.chips)                        # A
    from quda_tpu.interfaces import quda_api as api
    if args.chips == 4:
        geom, gauge, srcs = make_data(args.seed, 4)
        phase_four_chips(api, geom, gauge, srcs,
                         make_checker(gauge, geom))
    else:
        geom, gauge, srcs = make_data(args.seed, 8)
        checker = make_checker(gauge, geom)
        x = phase_invert(api, gauge, srcs[0], checker)       # B
        ws = phase_kernels(api)                              # C
        phase_multi_src(api, srcs, checker, x_single=x)      # D
        phase_service(gauge, srcs[:6], checker)              # E
        phase_timing(api, ws, device)                        # F
        api.end_quda()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
