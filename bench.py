"""Headline benchmark: Wilson dslash GFLOPS on one chip.

Prints ONE JSON line, e.g.:
  {"metric": "wilson_dslash_gflops_chip", "value": N, "unit": "GFLOPS",
   "vs_baseline": N, "platform": "tpu", "lattice": [24,24,24,24],
   "path": "pallas_packed", "correctness_rel_err": E, "method": {...},
   "paths": {...per-path GFLOPS...}}

Baseline: 1400 GFLOPS — the order of public A100 single-precision Wilson
dslash results (BASELINE.md: target is "within 2x of A100", so
vs_baseline >= 0.5 meets the target).

Flop model: 1320 flops/site (Dslash::flops(), reference include/dslash.h:475).

Measurement method:
  * The headline paths are the all-f32 pair-form stencils (the honest
    "single precision" numbers to compare against GPU f32 dslash
    results); the complex64 canonical stencil is timed alongside.
  * ONE process touches jax.  Without QUDA_TPU_BENCH_CPU=1 the run
    FAILS (non-zero exit) when jax's default backend is not an
    accelerator — a CPU timing is never recorded under a chip banner,
    and there is no probe child, retry loop or re-exec onto the CPU.
  * The per-application time is the MARGINAL cost between two chain
    lengths, (t(n2)-t(n1))/(n2-n1), so fixed per-call overhead cancels;
    every timed call ends in a host fetch of an f32 scalar checksum.
  * Inputs are varied per repetition (an eps scalar folded into the
    chain).
  * Correctness is asserted in-run: the pair path on this backend is
    compared against the complex stencil on the CPU backend at 8^4 and
    the relative error is reported in the JSON line.
  * The deadline watchdog (QUDA_TPU_BENCH_DEADLINE_S) prints the partial
    record and exits NON-ZERO: a wedged run is a failed run.

Paths benchmarked (best f32 path wins; bf16-storage sloppy reported too):
  xla_pairs     — packed pair-form (4,3,2,T,Z,YX) f32 stencil
                  (ops/wilson_packed.dslash_packed_pairs)
  pallas_packed — hand-blocked pallas kernel, grid (T, Z/BZ)
                  (ops/wilson_pallas_packed); TPU only
  pallas_bf16 / xla_pairs_bf16 — same with bf16 storage (f32 compute):
                  the half-precision sloppy-operator number
  xla_canonical — complex (T,Z,Y,X,4,3) roll+einsum stencil
"""

from __future__ import annotations

import json
import math
import os
import sys
import threading
import time

BASELINE_GFLOPS = 1400.0


# -- roofline / noise gating ------------------------------------------------
# Round 5 recorded physically impossible rows into the measurement log
# (triple_update_norm2 at 1.27e11 GFLOPS / secs 0.0, xpay_redot at
# 31.8 TB/s — measurements_tpu.log), and the mg suite silently fell back
# to CPU under a TPU banner.  Every recorded row now passes ``gate_row``:
# a marginal-seconds floor, a per-suite roofline bound, and a
# platform==banner assertion.  Rejections are printed LOUDLY into the log
# (an error row), never silently recorded as data.  The bounds are pure
# numbers unit-tested in tests/test_bench_gate.py.

MIN_MARGINAL_SECS = 1e-6      # below this a marginal is noise, not data

SUITE_ROOFLINES = {
    # {"gflops", "gbps"} upper bounds per suite, deliberately generous
    # (~10x the best credible chip measurement) — they reject the
    # impossible, not the surprising:
    #  * dslash/solver: best measured 5,673 GFLOPS (PERF.md round 5); an
    #    order of magnitude above sits far past the v5p VPU envelope for
    #    a stencil, and effective bandwidth beyond ~25 TB/s exceeds even
    #    the VMEM-resident regime (<= 23 TB/s measured).
    #  * blas: bandwidth-bound bundles at ~0.67 flops/byte against the
    #    same <= 23 TB/s VMEM ceiling -> < 16 TFLOPS real.
    "dslash": {"gflops": 60.0e3, "gbps": 25.0e3},
    "solver": {"gflops": 60.0e3, "gbps": 25.0e3},
    "blas": {"gflops": 30.0e3, "gbps": 25.0e3},
    "mg": {"gflops": 60.0e3, "gbps": 25.0e3},
    "gauge": {"gflops": 60.0e3, "gbps": 25.0e3},
}
_DEFAULT_ROOFLINE = {"gflops": 60.0e3, "gbps": 25.0e3}


def gate_row(suite: str, row: dict, banner_platform: str = None):
    """(ok, reason) for a measurement row.

    Pure function (no jax) so the round-5 failure modes are unit-testable:
    rejects rows whose platform does not match the banner they would be
    recorded under, rows with a ~zero/negative time, and rows whose
    gflops/gbps exceed the per-suite roofline bound."""
    if banner_platform is not None and row.get("platform") != banner_platform:
        return False, (f"platform mismatch: row measured on "
                       f"{row.get('platform')!r} cannot be recorded "
                       f"under a {banner_platform!r} banner")
    secs = row.get("secs_per_call", row.get("secs"))
    if secs is not None and not (isinstance(secs, (int, float))
                                 and math.isfinite(secs)
                                 and secs > MIN_MARGINAL_SECS):
        return False, (f"secs={secs!r} at/below the {MIN_MARGINAL_SECS:g}s "
                       "floor: a zero/negative marginal is noise, not a "
                       "measurement")
    if row.get("converged") is False:
        return False, ("unconverged solve: the row carries "
                       "converged=False — a timing whose solve missed "
                       "tol is not recordable throughput (quda_tpu/"
                       "robust unconverged-flag contract)")
    lim = SUITE_ROOFLINES.get(suite, _DEFAULT_ROOFLINE)
    for key, unit in (("gflops", "GFLOPS"), ("gbps", "GB/s")):
        v = row.get(key)
        if v is None:
            continue
        if not (isinstance(v, (int, float)) and math.isfinite(v)
                and v >= 0):
            return False, f"{key}={v!r} is not a finite throughput"
        if v > lim[key]:
            return False, (f"{key}={v:g} exceeds the {suite} roofline "
                           f"bound {lim[key]:g} {unit} — physically "
                           "impossible; rejected")
    return True, ""


# Rows accepted by record_row in this process, in order — the compare
# gate's "current run" input (bench_suite --compare).  Rejected rows are
# kept too so the gate summary can say how many died at the gate.
_RECORDED_ROWS: list = []
_REJECTED_ROWS: list = []


def recorded_rows() -> list:
    """(suite, row) pairs accepted by record_row this process."""
    return list(_RECORDED_ROWS)


def rejected_rows() -> list:
    """(suite, row, reason) triples refused by record_row this process."""
    return list(_REJECTED_ROWS)


def reset_recorded_rows():
    _RECORDED_ROWS.clear()
    _REJECTED_ROWS.clear()


def _mirror_row_event(name: str, suite: str, row: dict, **extra):
    """Mirror a bench row into the obs trace stream (bench_suite
    --trace) so the chrome artifact carries the measurements next to
    the spans/tuner events; scalars only, and never let observability
    break a measurement run."""
    try:
        from quda_tpu.obs import trace as _otr
        if _otr.enabled():
            # row keys that collide with event()'s own parameters are
            # prefixed
            taken = ("name", "cat", "suite") + tuple(extra)
            fields = {("row_" + k if k in taken else k): v
                      for k, v in row.items()
                      if isinstance(v, (str, int, float, bool))
                      or v is None}
            _otr.event(name, cat="bench", suite=suite, **fields,
                       **extra)
    except Exception:
        pass


def record_row(suite: str, row: dict, banner_platform: str = None,
               log=None):
    """Print ``row`` as one JSON line iff it passes ``gate_row``;
    otherwise print a loud rejection row so the failure lands IN the log
    instead of being silently recorded as data.  Returns True iff the
    row was recorded."""
    if log is None:
        log = lambda s: print(s, flush=True)
    ok, reason = gate_row(suite, row, banner_platform)
    if ok:
        log(json.dumps(dict({"suite": suite}, **row)))
        _RECORDED_ROWS.append((suite, dict(row)))
        _mirror_row_event("bench_row", suite, row)
    else:
        log(json.dumps({"suite": suite, "name": row.get("name"),
                        "rejected": reason,
                        "platform": row.get("platform")}))
        _REJECTED_ROWS.append((suite, dict(row), reason))
        # rejections mirror too (bench_row_rejected): a gate failure
        # must be visible in the chrome artifact, not just the text log
        _mirror_row_event("bench_row_rejected", suite, row,
                          rejected=reason)
    return ok


# The record is built INCREMENTALLY (skeleton first, each timed path folded
# in as it completes) so that the deadline watchdog below can always emit a
# parseable line before it fails the run.
_RECORD: dict = {}
_DONE = threading.Event()


def _arm_deadline(seconds: float):
    """Watchdog thread: on expiry, print the record accumulated so far and
    hard-exit NON-ZERO (a run that hit its deadline failed; the partial
    record is evidence, not a result).  A thread (not SIGALRM) because
    the failure mode being defended against is the main thread wedged
    inside a backend call that never returns to the bytecode loop."""
    if seconds <= 0:
        return None

    def fire():
        if _DONE.is_set():
            return
        # snapshot before serializing: the main thread may be mutating the
        # record concurrently, and ANY exception here must still reach the
        # os._exit
        out = ('{"metric": "wilson_dslash_gflops_chip", "value": 0.0, '
               '"unit": "GFLOPS", "vs_baseline": 0.0, '
               '"error": "deadline hit; record serialization failed"}')
        import copy
        for _ in range(3):
            try:
                rec = copy.deepcopy(_RECORD)
                rec.setdefault("note", "deadline hit; partial record")
                out = json.dumps(rec)
                break
            except Exception:
                continue
        try:
            print(out, flush=True)
        finally:
            os._exit(3)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def _conf(name):
    """Benchmark knobs go through the central registry
    (quda_tpu.utils.config) — one source of truth for defaults/docs."""
    from quda_tpu.utils import config as qconf
    return qconf.get(name, fresh=True)


def _backend(force_cpu: bool, **who) -> str:
    """The backend THIS process runs its timings on (the only process
    that touches jax).  No accelerator and no explicit request for the
    CPU (QUDA_TPU_BENCH_CPU) is a failure — an error row tagged with
    ``who`` and a non-zero exit — never a silent fallback."""
    import jax
    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.default_backend()
    if platform == "cpu" and not force_cpu:
        print(json.dumps({
            **who,
            "error": "jax's default backend is 'cpu': no accelerator; "
                     "set QUDA_TPU_BENCH_CPU=1 to benchmark the CPU"}),
              flush=True)
        sys.exit(1)
    return platform


def _fetch(x) -> float:
    """Host-fetch an f32 scalar — the execution barrier of every timed
    call (the value also feeds the checksum)."""
    import numpy as np
    return float(np.asarray(x))


def _time_marginal(make_chain, args, n1: int, n2: int, reps: int):
    """Marginal per-application seconds between chain lengths n1 < n2.

    make_chain(n) -> jitted f(*args, eps) returning an f32 scalar.
    Returns (seconds_per_apply, checksum).

    A marginal that is not clearly positive means the measurement is
    NOISE (a contended host can inflate the short-chain total past the
    long one — observed 2026-07-31: blas rows claiming 0.0 s/call and
    1e11 "GFLOPS" while another process shared the chip).  On a
    degenerate marginal BOTH chains are re-measured, keeping the min of
    each (the consistent estimator); if the marginal is still
    indistinguishable from zero the result is NaN so no caller can
    mistake it for a throughput."""
    import jax.numpy as jnp

    totals = {}
    checksum = None

    def measure(n):
        f = make_chain(n)
        nonlocal checksum
        checksum = _fetch(f(*args, jnp.float32(0.01)))  # compile + warm
        best = float("inf")
        for i in range(reps):
            eps = jnp.float32(0.01 + 1e-4 * (i + 1))
            t0 = time.perf_counter()
            checksum = _fetch(f(*args, eps))
            best = min(best, time.perf_counter() - t0)
        return best

    for n in (n1, n2):
        totals[n] = measure(n)
    if totals[n2] - totals[n1] <= 0.02 * totals[n1]:
        # degenerate marginal — usually a contention spike inflating the
        # SHORT chain's best.  Re-measure BOTH chains and keep the min
        # (the consistent estimator); never keep a slower sample.
        for n in (n1, n2):
            totals[n] = min(totals[n], measure(n))
    sec = (totals[n2] - totals[n1]) / (n2 - n1)
    if sec <= 0.02 * totals[n1] / (n2 - n1):
        return float("nan"), checksum
    return sec, checksum


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp

    platform = _backend(_conf("QUDA_TPU_BENCH_CPU"),
                        metric="wilson_dslash_gflops_chip")

    # Skeleton record + deadline watchdog BEFORE any device work.
    _RECORD.update({
        "metric": "wilson_dslash_gflops_chip", "value": 0.0,
        "unit": "GFLOPS", "vs_baseline": 0.0, "platform": platform,
        "path": "none", "paths": {},
    })
    deadline = _arm_deadline(float(_conf("QUDA_TPU_BENCH_DEADLINE_S")))

    from quda_tpu.ops import wilson as wops
    from quda_tpu.ops import wilson_packed as wpk

    L = _conf("QUDA_TPU_BENCH_L") or (24 if platform != "cpu" else 8)
    T = Z = Y = X = L
    rng = np.random.default_rng(0)

    # Build fields on the host; antiperiodic-t phases folded into the
    # links like the solve path.
    gauge = (rng.standard_normal((4, T, Z, Y, X, 3, 3))
             + 1j * rng.standard_normal((4, T, Z, Y, X, 3, 3))
             ).astype(np.complex64) * 0.3
    gauge[3, -1] *= -1.0
    psi = (rng.standard_normal((T, Z, Y, X, 4, 3))
           + 1j * rng.standard_normal((T, Z, Y, X, 4, 3))
           ).astype(np.complex64)
    gp = np.transpose(gauge, (0, 5, 6, 1, 2, 3, 4)).reshape(
        4, 3, 3, T, Z, Y * X)
    pp = np.transpose(psi, (4, 5, 0, 1, 2, 3)).reshape(4, 3, T, Z, Y * X)
    g_pairs = np.stack([gp.real, gp.imag], axis=3).astype(np.float32)
    p_pairs = np.stack([pp.real, pp.imag], axis=2).astype(np.float32)

    g_d = jax.device_put(jnp.asarray(g_pairs))
    p_d = jax.device_put(jnp.asarray(p_pairs))
    g_d.block_until_ready(), p_d.block_until_ready()

    # ---- correctness gate: pair path on this backend vs complex stencil
    # on the CPU backend, at 8^4 ------------------------------------------
    Lc = 8
    gs = gauge[:, :Lc, :Lc, :Lc, :Lc]
    ps = psi[:Lc, :Lc, :Lc, :Lc]
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = np.asarray(jax.jit(wops.dslash_full)(
            jax.device_put(gs, cpu), jax.device_put(ps, cpu)))
    refp = np.transpose(ref, (4, 5, 0, 1, 2, 3)).reshape(
        4, 3, Lc, Lc, Lc * Lc)
    gps = np.transpose(gs, (0, 5, 6, 1, 2, 3, 4)).reshape(
        4, 3, 3, Lc, Lc, Lc * Lc)
    pps = np.transpose(ps, (4, 5, 0, 1, 2, 3)).reshape(
        4, 3, Lc, Lc, Lc * Lc)
    gsd = jax.device_put(jnp.asarray(
        np.stack([gps.real, gps.imag], axis=3).astype(np.float32)))
    psd = jax.device_put(jnp.asarray(
        np.stack([pps.real, pps.imag], axis=2).astype(np.float32)))
    out_h = np.asarray(jax.jit(
        lambda g, p: wpk.dslash_packed_pairs(g, p, Lc, Lc))(gsd, psd))
    got = out_h[:, :, 0] + 1j * out_h[:, :, 1]
    rel_err = float(np.max(np.abs(got - refp)) / np.max(np.abs(refp)))
    if rel_err > 1e-4:
        _DONE.set()
        _RECORD["error"] = f"correctness gate failed: {rel_err}"
        print(json.dumps(_RECORD))
        return
    _RECORD["correctness_rel_err"] = rel_err
    _RECORD["lattice"] = [L, L, L, L]

    # ---- timed paths -----------------------------------------------------
    # chain spread sets the timing SNR: the marginal difference must be
    # large against per-call dispatch noise, so the long chain is ~200
    # applications.
    n1 = _conf("QUDA_TPU_BENCH_N1")
    n2 = _conf("QUDA_TPU_BENCH_N2")
    reps = _conf("QUDA_TPU_BENCH_REPS")
    flops = 1320 * (L ** 4)

    def chain_of(fn):
        def make(n):
            @jax.jit
            def f(g, p, eps):
                def body(v, _):
                    o = fn(g, v) * 0.125 + eps * v
                    return o.astype(p.dtype), None
                out, _ = jax.lax.scan(body, p, None, length=n)
                return jnp.sum(out.astype(jnp.float32) ** 2)
            return f
        return make

    paths = _RECORD["paths"]
    secs = {}

    def _refresh_headline():
        # fold the best f32 path into the record after EVERY measurement,
        # so a deadline fire mid-run still reports what has been measured
        f32 = {k: v for k, v in secs.items() if "bf16" not in k}
        if f32:
            best = min(f32, key=f32.get)
            _RECORD["path"] = best
            _RECORD["value"] = round(flops / f32[best] / 1e9, 1)
            _RECORD["vs_baseline"] = round(
                _RECORD["value"] / BASELINE_GFLOPS, 3)

    def run_path(name, fn, args):
        try:
            s, _ = _time_marginal(chain_of(fn), args, n1, n2, reps)
            ok, reason = gate_row("dslash", {
                "name": name, "secs_per_call": s,
                "gflops": flops / s / 1e9 if s and s > 0 else float("nan"),
                "platform": platform})
            if not (s > 0):              # NaN marginal — noise, not data
                paths[name + "_error"] = ("non-positive marginal "
                                          "(contended host?)")
            elif not ok:                 # roofline-gated: impossible rate
                paths[name + "_error"] = reason
            else:
                secs[name] = s
                paths[name] = round(flops / s / 1e9, 1)
        except Exception as e:
            paths[name + "_error"] = str(e)[:160]
        _refresh_headline()

    if platform != "tpu":
        run_path("xla_pairs",
                 lambda g, v: wpk.dslash_packed_pairs(g, v, X, Y),
                 (g_d, p_d))

    pallas_rel_err = None
    if platform == "tpu":
        # most-important-first: if the deadline watchdog fires mid-run,
        # the f32 pallas kernel must already be in the record; stencil
        # + bf16 variants follow
        from quda_tpu.ops import wilson_pallas_packed as wpp
        # gate the pallas kernel ON DEVICE against the (CPU-gated) pair
        # stencil at the headline size — this exercises the multi-z-block
        # splice configuration the headline number is measured with
        try:
            # pre-shifted backward gauge: computed once per gauge load in
            # real use, so keep the rolls OUT of the timed chain (inside
            # the scan body XLA re-rolls the whole field per application)
            gbw = jax.jit(lambda g: wpp.backward_gauge(g, X))(g_d)
            gbw.block_until_ready()

            @jax.jit
            def _gate(g, p):
                # gate the EXACT timed variant (explicit gauge_bw)
                a = wpp.dslash_pallas_packed(g, p, X, gauge_bw=gbw)
                b = wpk.dslash_packed_pairs(g, p, X, Y)
                return (jnp.max(jnp.abs(a - b)), jnp.max(jnp.abs(b)))
            d, m = _gate(g_d, p_d)
            pallas_rel_err = _fetch(d) / _fetch(m)
            if pallas_rel_err < 1e-4:
                run_path("pallas_packed",
                         lambda g, v: wpp.dslash_pallas_packed(
                             g, v, X, gauge_bw=gbw),
                         (g_d, p_d))
            else:
                paths["pallas_packed_error"] = (
                    f"gate failed: rel err {pallas_rel_err:.3e}")
        except Exception as e:
            paths["pallas_packed_error"] = str(e)[:160]
        # f32 stencil next: if the pallas gate failed, the record still
        # gets a headline-eligible f32 number before the bf16 variants
        run_path("xla_pairs",
                 lambda g, v: wpk.dslash_packed_pairs(g, v, X, Y),
                 (g_d, p_d))
        # bf16-storage sloppy variants (f32 compute) — the half-precision
        # operator number; pallas reads bf16 blocks if given bf16 arrays
        g_bf = g_d.astype(jnp.bfloat16)
        p_bf = p_d.astype(jnp.bfloat16)
        g_bf.block_until_ready(), p_bf.block_until_ready()
        gbw_bf = jax.jit(lambda g: wpp.backward_gauge(g, X))(g_bf)
        gbw_bf.block_until_ready()
        run_path("pallas_bf16",
                 lambda g, v: wpp.dslash_pallas_packed(
                     g, v, X, gauge_bw=gbw_bf),
                 (g_bf, p_bf))
        run_path("xla_pairs_bf16",
                 lambda g, v: wpk.dslash_packed_pairs(g, v, X, Y,
                                                      out_dtype=jnp.bfloat16),
                 (g_bf, p_bf))
        # multi-RHS amortization (the round-7 tentpole): 8 RHS streamed
        # through one gauge-tile fetch per (t, z-block).  NOT headline-
        # eligible (the headline is per-application single-RHS); the
        # aggregate and per-RHS rates land in "paths" through the same
        # roofline gate.  Gate: lane 0 of the batch must BIT-match the
        # single-RHS v2 kernel (same kernel body by construction).
        try:
            p8 = jnp.stack([jnp.roll(p_d, i, axis=-1) for i in range(8)])
            p8.block_until_ready()

            @jax.jit
            def _gate_mrhs(g, pb):
                a = wpp.dslash_pallas_packed_mrhs(g, pb, X, gauge_bw=gbw)
                b = wpp.dslash_pallas_packed(g, pb[0], X, gauge_bw=gbw)
                return (jnp.max(jnp.abs(a[0] - b)), jnp.max(jnp.abs(b)))
            dm, mm = _gate_mrhs(g_d, p8)
            mrhs_rel = _fetch(dm) / _fetch(mm)
            if mrhs_rel < 1e-6:
                s8, _ = _time_marginal(
                    chain_of(lambda g, v: wpp.dslash_pallas_packed_mrhs(
                        g, v, X, gauge_bw=gbw)), (g_d, p8), n1, n2, reps)
                row = {"name": "pallas_mrhs_n8", "secs_per_call": s8,
                       "gflops": (8 * flops / s8 / 1e9
                                  if s8 and s8 > 0 else float("nan")),
                       "platform": platform}
                ok, reason = gate_row("dslash", row)
                if not (s8 > 0):
                    paths["pallas_mrhs_n8_error"] = (
                        "non-positive marginal (contended host?)")
                elif not ok:
                    paths["pallas_mrhs_n8_error"] = reason
                else:
                    paths["pallas_mrhs_n8"] = round(8 * flops / s8 / 1e9,
                                                    1)
                    paths["pallas_mrhs_n8_per_rhs"] = round(
                        flops / s8 / 1e9, 1)
            else:
                paths["pallas_mrhs_n8_error"] = (
                    f"gate failed: rel err {mrhs_rel:.3e}")
        except Exception as e:
            paths["pallas_mrhs_n8_error"] = str(e)[:160]
        _refresh_headline()

    # complex64 canonical stencil (timed on every backend)
    gauge_d = jax.device_put(jnp.asarray(gauge))
    psi_d = jax.device_put(jnp.asarray(psi))

    def canon(g, v):
        return wops.dslash_full(g, v)

    def make_canon(n):
        @jax.jit
        def f(g, p, eps):
            def body(v, _):
                return canon(g, v) * 0.125 + eps * v, None
            out, _ = jax.lax.scan(body, p, None, length=n)
            return jnp.sum(jnp.real(out * jnp.conj(out)))
        return f
    try:
        s, _ = _time_marginal(make_canon, (gauge_d, psi_d), n1, n2,
                              reps)
        ok, reason = gate_row("dslash", {
            "name": "xla_canonical", "secs_per_call": s,
            "gflops": flops / s / 1e9 if s and s > 0 else float("nan"),
            "platform": platform})
        if not (s > 0):          # NaN marginal — noise, not data
            paths["xla_canonical_error"] = ("non-positive marginal "
                                            "(contended host?)")
        elif not ok:
            paths["xla_canonical_error"] = reason
        else:
            secs["xla_canonical"] = s
            paths["xla_canonical"] = round(flops / s / 1e9, 1)
    except Exception as e:
        paths["xla_canonical_error"] = str(e)[:160]
    _refresh_headline()

    # headline (best f32 path; bf16 storage reported but not headline) has
    # been folded in by _refresh_headline after each path
    _RECORD["pallas_vs_xla_rel_err"] = pallas_rel_err
    _RECORD["method"] = {
        "timing": "marginal cost between scan chains",
        "chains": [n1, n2],
        "reps": reps,
        "execution_barrier": "host fetch of f32 checksum",
        "inputs_varied_per_rep": True,
    }
    _DONE.set()
    if deadline is not None:
        deadline.cancel()
    print(json.dumps(_RECORD))


if __name__ == "__main__":
    main()
