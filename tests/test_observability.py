"""Observability layer tests: span tracer, convergence recording,
roofline attribution, and the end_quda telemetry flush.

Covers the obs/ subsystem contract: chrome-trace JSON schema validity
and span nesting, per-iteration residual capture on a real Wilson CG
solve (history length == reported iters at cadence 1), the
counters-off zero-overhead path, roofline row arithmetic against a
hand-computed fixture, the bench-row achieved-GFLOPS round-trip, the
TimeProfile double-start fix, and the init/end_quda artifact flush."""

import json
import math
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.obs import convergence as oconv
from quda_tpu.obs import roofline as orf
from quda_tpu.obs import trace as otr
from quda_tpu.utils import config as qconf
from quda_tpu.utils.timer import TimeProfile


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Every test starts and ends with tracing off, empty roofline rows
    and a fresh config cache (tests mutate os.environ)."""
    otr.stop(flush_files=False)
    orf.reset()
    qconf.reset_cache()
    yield
    otr.stop(flush_files=False)
    orf.reset()
    qconf.reset_cache()


# -- span tracer ------------------------------------------------------------

@pytest.mark.parametrize("do_not_profile", [False, True])
def test_spans_buffer_nothing_when_off(monkeypatch, do_not_profile):
    """With no session open nothing is buffered: event() is a
    single-global-load early return, and a span is a profiler
    annotation of its name (tests/test_build_accounting.py) or, under
    QUDA_TPU_DO_NOT_PROFILE, the module's no-op singleton (no
    allocation)."""
    if do_not_profile:
        monkeypatch.setenv("QUDA_TPU_DO_NOT_PROFILE", "1")
    else:
        monkeypatch.delenv("QUDA_TPU_DO_NOT_PROFILE", raising=False)
    qconf.reset_cache()
    assert not otr.enabled()
    if do_not_profile:
        assert otr.span("a") is otr.span("b", cat="x", k=1) is otr._NOOP
    with otr.span("nested") as s:
        assert (s is otr._NOOP) is do_not_profile
        s.set(anything="ignored: no session records it")
    otr.event("dropped", value=1)         # must not raise, must not buffer
    assert otr._session is None


def test_span_nesting_and_chrome_schema(tmp_path):
    otr.start(str(tmp_path))
    with otr.span("outer", cat="api", who="test"):
        with otr.span("middle", cat="compute"):
            with otr.span("inner", cat="solver"):
                time.sleep(0.002)
    otr.event("marker", cat="event", value=42)
    paths = otr.stop()
    doc = json.load(open(paths["chrome"]))
    evs = doc["traceEvents"]
    assert isinstance(evs, list)
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == 3
    for e in spans:
        for key in ("name", "cat", "ts", "dur", "pid", "tid", "args"):
            assert key in e
        assert e["dur"] >= 0
    # three genuinely NESTED spans: depths 1..3 and time containment
    by_depth = {e["args"]["depth"]: e for e in spans}
    assert set(by_depth) == {1, 2, 3}
    for d in (2, 3):
        inner, outer = by_depth[d], by_depth[d - 1]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] \
            + 1e-3
    # instant events carry their fields; the JSONL stream parses
    marks = [e for e in evs if e["ph"] == "i"]
    assert marks and marks[0]["args"]["value"] == 42
    lines = [json.loads(ln) for ln in open(paths["jsonl"])]
    assert {ln["kind"] for ln in lines} == {"span", "event"}


def test_trace_event_cap(tmp_path):
    os.environ["QUDA_TPU_TRACE_EVENTS_MAX"] = "5"
    qconf.reset_cache()
    try:
        otr.start(str(tmp_path))
        for i in range(10):
            otr.event("e", i=i)
        paths = otr.stop()
        doc = json.load(open(paths["chrome"]))
        assert len(doc["traceEvents"]) == 5
        assert doc["otherData"]["dropped_events"] == 5
    finally:
        del os.environ["QUDA_TPU_TRACE_EVENTS_MAX"]


# -- TimeProfile double-start (satellite fix) -------------------------------

def test_timer_nested_same_category():
    prof = TimeProfile("nested")
    prof.start("compute")
    time.sleep(0.01)
    prof.start("compute")          # nested same-category span
    time.sleep(0.01)
    prof.stop("compute")           # closes the INNER interval
    prof.stop("compute")           # closes the OUTER interval
    assert prof.count["compute"] == 2
    # the outer interval covers both sleeps: total >= 0.01 + 0.02
    assert prof.seconds["compute"] >= 0.025
    # unmatched stop stays a no-op
    prof.stop("compute")
    assert prof.count["compute"] == 2


# -- convergence recording: solver-level ------------------------------------

def _diag_system(n=96, lo=0.5, hi=2.0, dtype=jnp.float32):
    d = jnp.linspace(lo, hi, n).astype(dtype)
    b = jnp.ones((n,), dtype)
    return (lambda v: d * v), b


def test_fused_cg_history_cadence1():
    from quda_tpu.solvers.fused_iter import fused_cg
    mv, b = _diag_system()
    res = fused_cg(mv, b, tol=1e-6, maxiter=200, check_every=1,
                   record=True)
    it = int(res.iters)
    hist = np.asarray(res.history)
    valid = hist[~np.isnan(hist)]
    assert len(valid) == it
    rec = oconv.harvest("cg", res, tol=1e-6, b2=float(jnp.sum(b * b)))
    assert rec.cadence == 1
    assert len(rec.history) == it
    assert rec.history[-1]["relres"] <= 1e-6
    # off path: no history in the result
    res_off = fused_cg(mv, b, tol=1e-6, maxiter=200, check_every=1)
    assert res_off.history is None
    assert oconv.harvest("cg", res_off, tol=1e-6, b2=1.0) is None


def test_fused_cg_history_cadence_gaps():
    from quda_tpu.solvers.fused_iter import fused_cg
    mv, b = _diag_system()
    res = fused_cg(mv, b, tol=1e-8, maxiter=200, check_every=3,
                   record=True)
    it = int(res.iters)
    assert it % 3 == 0
    rec = oconv.harvest("cg", res, tol=1e-8, b2=float(jnp.sum(b * b)))
    assert rec.cadence == 3
    assert [e["iter"] for e in rec.history] == \
        [3 * (i + 1) for i in range(len(rec.history))]
    assert rec.events and rec.events[0]["type"] == "check_cadence"
    assert rec.events[0]["every"] == 3


def test_cg_reliable_history_and_events():
    from quda_tpu.solvers.mixed import cg_reliable, dtype_codec
    n = 96
    d = jnp.linspace(0.5, 2.0, n).astype(jnp.float64)
    b = jnp.ones((n,), jnp.complex128)
    mv = lambda v: d * v
    d_lo = d.astype(jnp.complex64)
    mv_lo = lambda v: (d_lo * v).astype(jnp.complex64)
    res = cg_reliable(mv, mv_lo, b, sloppy_dtype=jnp.complex64,
                      tol=1e-8, maxiter=200, record=True)
    rec = oconv.harvest("cg-reliable", res, tol=1e-8,
                        b2=float(jnp.sum(jnp.abs(b) ** 2)))
    assert len(rec.history) == int(res.iters)
    assert any(e["type"] == "reliable_update" for e in rec.events)


def test_multishift_history_lanes():
    from quda_tpu.solvers.multishift import multishift_cg
    mv, b = _diag_system()
    shifts = (0.0, 0.3, 1.1)
    res = multishift_cg(mv, b, shifts, tol=1e-6, maxiter=200,
                        record=True)
    rec = oconv.harvest("multi-shift-cg", res, tol=1e-6,
                        b2=float(jnp.sum(b * b)))
    assert len(rec.history) == int(res.iters)
    assert set(rec.lanes) == {"shift0", "shift1", "shift2"}
    conv_events = [e for e in rec.events if e["type"] == "shift_converged"]
    assert len(conv_events) == len(shifts)
    # larger shifts converge no later than the base system
    its = {e["shift"]: e["iter"] for e in conv_events}
    assert its[2] <= its[0]


def test_bicgstab_history():
    from quda_tpu.solvers.bicgstab import bicgstab
    mv, b = _diag_system(dtype=jnp.float64)
    res = bicgstab(mv, b, tol=1e-8, maxiter=200, record=True)
    rec = oconv.harvest("bicgstab", res, tol=1e-8,
                        b2=float(jnp.sum(b * b)))
    assert len(rec.history) == int(res.iters)
    assert rec.history[-1]["r2"] == pytest.approx(float(res.r2))


def test_batched_cg_pairs_history_lanes():
    from quda_tpu.solvers.block import batched_cg_pairs
    n, nrhs = 96, 3
    d = jnp.linspace(0.5, 2.0, n).astype(jnp.float32)
    B = jnp.stack([jnp.ones((n,)), 2.0 * jnp.ones((n,)),
                   0.5 * jnp.ones((n,))]).astype(jnp.float32)
    res = batched_cg_pairs(lambda V: d[None] * V, B, tol=1e-6,
                           maxiter=200, check_every=1, record=True)
    rec = oconv.harvest("batched-cg-pairs", res, tol=1e-6,
                        b2=float(jnp.max(jnp.sum(B * B, axis=1))))
    assert rec.lanes is not None and len(rec.lanes) == nrhs
    worst = int(np.max(np.asarray(res.iters)))
    assert len(rec.history) == worst


# -- roofline ---------------------------------------------------------------

def test_roofline_achieved_fixture():
    # hand fixture: 1e9 flops + 2e9 bytes in 0.5 s -> 2 GFLOPS, 4 GB/s
    th = orf.achieved(1e9, 2e9, 0.5)
    assert th == {"gflops": 2.0, "gbps": 4.0}
    assert orf.achieved(1e9, 1e9, 0.0) == {"gflops": 0.0, "gbps": 0.0}


def test_roofline_attribute_wilson_v2_fixture():
    # 16^4 PC Wilson v2: sites = vol/2, 100 applies, 0.1 s (hand math)
    vol = 16 ** 4
    sites = vol // 2
    row = orf.attribute("wilson_v2", sites, 100, 0.1)
    flops = 1320 * sites * 100
    bts = 1152 * sites * 100
    assert row["gflops"] == round(flops / 0.1 / 1e9, 2)
    assert row["gbps"] == round(bts / 0.1 / 1e9, 2)
    # percent-of-peak is against the PUBLISHED peaks of the device
    # present: none on the CPU, the v5e table row when named, and an
    # unknown TPU kind is an error (never a default)
    assert row["pct_peak_gflops"] is None and row["pct_peak_bw"] is None
    row = orf.attribute("wilson_v2", sites, 100, 0.1,
                        device_kind="TPU v5 lite")
    assert orf.DEVICE_PEAKS["TPU v5 lite"]["gbps"] == 819.0
    assert row["pct_peak_gflops"] == round(
        100.0 * row["gflops"] / 197000.0, 2)
    assert row["pct_peak_bw"] == round(100.0 * row["gbps"] / 819.0, 2)
    with pytest.raises(KeyError, match="no published peaks"):
        orf.attribute("wilson_v2", sites, 100, 0.1,
                      device_kind="TPU v99")


def test_roofline_mrhs_model_amortises_gauge():
    # the full-Z route's traffic model: per-RHS bytes 288 + 576/N
    # (two time-slices a step, psi read twice; at N=8, 8 x 360 = the
    # 2,880 B a site of PERF.md section 5)
    _, b1 = orf.model("wilson_mrhs", nrhs=1)
    _, b8 = orf.model("wilson_mrhs", nrhs=8)
    assert b1 == pytest.approx(864.0)
    assert b8 == pytest.approx(360.0)
    # generic form carries no traffic model -> no bandwidth claim
    row = orf.attribute("generic", 100, 1, 1.0, flops_per_site=10)
    assert row["gbps"] is None and row["pct_peak_bw"] is None


def test_bench_row_roundtrips_through_roofline():
    """A gated bench row's achieved-GFLOPS column must equal the
    obs/roofline arithmetic for the same (flops, secs) — the bench
    harness consumes the shared helper instead of private math."""
    from bench import record_row
    flops, bytes_, secs = 1320 * 8 ** 4, 1152 * 8 ** 4, 0.0123
    th = orf.achieved(flops, bytes_, secs)
    rows = []
    ok = record_row("dslash", {
        "name": "fixture", "gflops": th["gflops"], "gbps": th["gbps"],
        "secs_per_call": secs, "platform": "cpu", "lattice": [8] * 4},
        banner_platform="cpu", log=rows.append)
    assert ok
    row = json.loads(rows[0])
    assert row["gflops"] == round(flops / secs / 1e9, 2)
    assert row["gbps"] == round(bytes_ / secs / 1e9, 2)


def test_gated_bench_row_mirrors_into_trace(tmp_path):
    """With a trace session active, every gated bench row lands in the
    JSONL stream as a bench_row event (the --trace artifact contract)."""
    from bench import record_row
    otr.start(str(tmp_path))
    record_row("blas", {"name": "fixture", "gflops": 1.0, "gbps": 2.0,
                        "secs_per_call": 0.01, "platform": "cpu",
                        "lattice": [4] * 4},
               banner_platform="cpu", log=lambda s: None)
    paths = otr.stop()
    lines = [json.loads(ln) for ln in open(paths["jsonl"])]
    rows = [ln for ln in lines if ln.get("name") == "bench_row"]
    assert rows and rows[0]["row_name"] == "fixture"
    assert rows[0]["gflops"] == 1.0


def test_rejected_bench_row_mirrors_into_trace(tmp_path):
    """Gate failures are visible in the chrome artifact too: a refused
    row lands in the stream as bench_row_rejected carrying the gate's
    reason, not just in the text log."""
    from bench import record_row
    otr.start(str(tmp_path))
    ok = record_row("blas", {"name": "bad_row", "gflops": 1.27e11,
                             "secs_per_call": 1e-4, "platform": "tpu",
                             "lattice": [4] * 4},
                    banner_platform="tpu", log=lambda s: None)
    assert not ok
    paths = otr.stop()
    lines = [json.loads(ln) for ln in open(paths["jsonl"])]
    rej = [ln for ln in lines if ln.get("name") == "bench_row_rejected"]
    assert rej and rej[0]["row_name"] == "bad_row"
    assert "roofline" in rej[0]["rejected"]
    assert not [ln for ln in lines if ln.get("name") == "bench_row"]


def test_harvest_handles_dict_and_lane_histories():
    # synthetic results exercise the harvest shapes without a solver
    fake = types.SimpleNamespace(
        iters=jnp.int32(4), converged=jnp.bool_(True),
        history=np.array([4.0, 2.0, 1.0, 0.5, np.nan, np.nan]))
    rec = oconv.harvest("s", fake, tol=1e-3, b2=16.0)
    assert [e["iter"] for e in rec.history] == [1, 2, 3, 4]
    assert rec.history[0]["relres"] == pytest.approx(0.5)
    # dump is valid JSON
    class _Buf:
        s = ""
    import io
    buf = io.StringIO()
    json.dump({"ok": True}, buf)  # sanity that json module is importable
    d = {"r2": np.array([4.0, 1.0, np.nan]),
         "reliable": np.array([False, True, False])}
    fake2 = types.SimpleNamespace(iters=jnp.int32(2),
                                  converged=jnp.bool_(True), history=d)
    rec2 = oconv.harvest("s", fake2, tol=1e-3, b2=4.0)
    assert [e["type"] for e in rec2.events] == ["reliable_update"]
    assert rec2.events[0]["iter"] == 2


def test_roofline_dslash_per_apply_scales_bytes_only():
    """A PC M runs two dslash invocations per apply: the traffic side
    must double (dslash_per_apply=2) while caller-supplied flops stay
    per-apply — the units fix for the BW column."""
    sites, applies, secs = 8 ** 4 // 2, 100, 0.1
    base = orf.attribute("wilson_v2", sites, applies, secs,
                         flops_per_site=2 * 1320 + 48)
    pc = orf.attribute("wilson_v2", sites, applies, secs,
                       flops_per_site=2 * 1320 + 48,
                       dslash_per_apply=2.0)
    assert pc["gflops"] == base["gflops"]
    assert pc["gbps"] == pytest.approx(2.0 * base["gbps"])
    assert pc["gbps"] == round(
        1152 * sites * applies * 2.0 / secs / 1e9, 2)
    assert pc["dslash_per_apply"] == 2.0


def test_harvest_per_lane_b2_normalization():
    """2-D (per-RHS) histories: every lane's relres is judged against
    its OWN |b_i|^2, and the headline is the worst RELATIVE lane per
    slot — not the biggest raw r2."""
    # lane 0: huge rhs, converging well; lane 1: tiny rhs, stalled
    a = np.array([[100.0, 0.04],
                  [1.0, 0.04],
                  [np.nan, np.nan]])
    fake = types.SimpleNamespace(
        iters=jnp.asarray([2, 2], jnp.int32),
        converged=jnp.asarray([True, False]), history=a)
    rec = oconv.harvest("s", fake, tol=1e-3, b2=np.array([1e4, 0.04]))
    assert rec.lanes["rhs0"][0]["relres"] == pytest.approx(0.1)
    assert rec.lanes["rhs1"][0]["relres"] == pytest.approx(1.0)
    # slot 0: lane 1 (relres 1.0) is worse than lane 0 (0.1) despite
    # lane 0's raw r2 being 2500x larger
    assert rec.history[0]["r2"] == pytest.approx(0.04)
    assert rec.history[0]["relres"] == pytest.approx(1.0)
    assert rec.history[1]["relres"] == pytest.approx(1.0)


def test_solve_form_labels_recon12():
    """Roofline form labels must carry reconstruct-12 (the compressed
    link arrays move 2*96 B/site less than recon-18; labeling an r12
    run 'wilson_v2' overstates achieved BW ~20%).  Detection is by the
    resident link shape (rows kept), not the env knob."""
    from quda_tpu.interfaces.quda_api import _solve_form

    class _FakeWilsonOp:
        use_pallas = True
        _mesh = None

    op18, op12 = _FakeWilsonOp(), _FakeWilsonOp()
    op18.gauge_eo_pp = (np.zeros((4, 3, 3, 2, 2, 2, 4), np.float32),)
    op12.gauge_eo_pp = (np.zeros((4, 2, 3, 2, 2, 2, 4), np.float32),)
    assert _solve_form(op18) == "wilson_v2"
    assert _solve_form(op12) == "wilson_v2_r12"
    # every r12 label resolves to a model with the subtracted traffic
    assert orf.model("wilson_v2_r12")[1] == 960
    assert orf.model("wilson_sharded_v2_r12")[1] == 960


def test_publish_multishift_sloppy_stage_tol():
    """The dtype-sloppy multishift route records only the shared-Krylov
    stage at a clamped tolerance: the published record must carry THAT
    tol and a stage marker, not param.tol (which nothing was judged
    against)."""
    from quda_tpu.interfaces.quda_api import _publish_multishift

    class _P:
        tol = 1e-10
        res_history = ()
        events = ()

    fake = types.SimpleNamespace(
        iters=jnp.int32(3), converged=jnp.asarray([True]),
        history=np.array([1e-2, 1e-4, 1e-9, np.nan]))
    p = _P()
    _publish_multishift(fake, jnp.ones(4, jnp.float32), p, tol=1e-4,
                        stage_note="sloppy stage")
    assert p.res_history and len(p.res_history) == 3
    assert p.events[0] == {"type": "stage", "note": "sloppy stage"}
    # judged at the clamped tol -> no spurious 'unconverged' event
    assert not any(e["type"] == "unconverged" for e in p.events)


def test_harvest_dict_history_b2_override():
    """A solver that recorded a DIFFERENT system than the caller's rhs
    (cg_reliable_df's normal-equation curve) ships its own b2 in the
    history dict, which harvest must prefer."""
    d = {"r2": np.array([25.0, 1.0, np.nan]),
         "reliable": np.array([False, False, False]),
         "b2": 100.0}
    fake = types.SimpleNamespace(iters=jnp.int32(2),
                                 converged=jnp.bool_(True), history=d)
    rec = oconv.harvest("s", fake, tol=1e-3, b2=1.0)  # caller's wrong b2
    assert rec.b2 == pytest.approx(100.0)
    assert rec.history[0]["relres"] == pytest.approx(0.5)
    assert rec.history[1]["relres"] == pytest.approx(0.1)


# -- end-to-end: traced Wilson CG solve + shutdown flush --------------------

def _unit_gauge(L):
    return np.broadcast_to(np.eye(3, dtype=np.complex64),
                           (4, L, L, L, L, 3, 3)).copy()


def test_traced_invert_quda_acceptance(tmp_path, monkeypatch):
    """The acceptance path: QUDA_TPU_TRACE=1 + resource path ->
    one Wilson CG invert_quda produces a loadable chrome trace with
    >= 3 nested spans, a JSONL stream whose residual-event count
    matches InvertParam.iter_count, and the end_quda summary tsv."""
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.interfaces.quda_api import (end_quda, init_quda,
                                              invert_quda,
                                              load_gauge_quda)
    monkeypatch.setenv("QUDA_TPU_TRACE", "1")
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    qconf.reset_cache()
    init_quda()
    L = 4
    load_gauge_quda(_unit_gauge(L), GaugeParam(X=(L,) * 4,
                                               cuda_prec="single"))
    rng = np.random.default_rng(0)
    b = (rng.standard_normal((L, L, L, L, 4, 3))
         + 1j * rng.standard_normal((L, L, L, L, 4, 3))
         ).astype(np.complex64)
    p = InvertParam(dslash_type="wilson", inv_type="cg",
                    solve_type="normop-pc", kappa=0.12, tol=1e-6,
                    maxiter=300, cuda_prec="single")
    invert_quda(b, p)
    assert p.iter_count > 2
    # per-iteration history surfaced on the param (cadence 1)
    assert len(p.res_history) == p.iter_count
    assert p.res_history[-1]["relres"] <= 1e-5
    end_quda()

    # chrome trace: loads, >= 3 nested spans
    doc = json.load(open(tmp_path / "trace.json"))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    depths = {e["args"]["depth"] for e in spans}
    assert {1, 2, 3} <= depths
    names = {e["name"] for e in spans}
    assert {"invert_quda", "setup", "compute", "epilogue",
            "solve:cg"} <= names
    # JSONL stream: residual events match the reported iteration count
    lines = [json.loads(ln) for ln in open(tmp_path /
                                           "trace_events.jsonl")]
    res_events = [ln for ln in lines if ln.get("name") == "residual"]
    assert len(res_events) == p.iter_count
    assert [e["iter"] for e in res_events] == \
        list(range(1, p.iter_count + 1))
    # roofline attribution rode along
    assert [ln for ln in lines if ln.get("name") == "roofline"]
    # end_quda summary tsv artifacts under the resource path
    assert (tmp_path / "profile.tsv").exists()
    prof = open(tmp_path / "profile.tsv").read()
    assert "invert_quda" in prof and "compute" in prof
    assert (tmp_path / "roofline.tsv").exists()


def test_untraced_invert_runs_no_recording_code(monkeypatch):
    """Counters-off zero-overhead: with tracing off the solve path must
    never construct a real span or touch the convergence recorder —
    enforced by making both paths raise if entered."""
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.interfaces.quda_api import (end_quda, init_quda,
                                              invert_quda,
                                              load_gauge_quda)
    monkeypatch.delenv("QUDA_TPU_TRACE", raising=False)
    qconf.reset_cache()

    def _boom(*a, **kw):
        raise AssertionError("recording code ran with tracing off")

    monkeypatch.setattr(otr._Span, "__enter__", _boom)
    monkeypatch.setattr(oconv, "harvest", _boom)
    monkeypatch.setattr(orf, "record", _boom)
    init_quda()
    L = 4
    load_gauge_quda(_unit_gauge(L), GaugeParam(X=(L,) * 4,
                                               cuda_prec="single"))
    rng = np.random.default_rng(1)
    b = (rng.standard_normal((L, L, L, L, 4, 3))
         + 1j * rng.standard_normal((L, L, L, L, 4, 3))
         ).astype(np.complex64)
    p = InvertParam(dslash_type="wilson", inv_type="cg",
                    solve_type="normop-pc", kappa=0.12, tol=1e-6,
                    maxiter=300, cuda_prec="single")
    invert_quda(b, p)
    assert p.res_history == () and p.events == ()
    end_quda()


def test_end_quda_flushes_monitor_and_profiles(tmp_path, monkeypatch):
    """Satellite: init_quda starts the monitor, end_quda stops it and
    writes monitor.tsv + profile.tsv under the resource path."""
    from quda_tpu.interfaces.quda_api import end_quda, init_quda
    from quda_tpu.utils.timer import get_profile
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    monkeypatch.setenv("QUDA_TPU_ENABLE_MONITOR", "1")
    monkeypatch.setenv("QUDA_TPU_MONITOR_PERIOD", "0.01")
    qconf.reset_cache()
    init_quda()
    prof = get_profile("flush_check")
    prof.start("compute")
    time.sleep(0.05)
    prof.stop("compute")
    orf.record("wilson_v2", 8 ** 4 // 2, 10, 0.01, label="flush_check")
    end_quda()
    assert (tmp_path / "monitor.tsv").exists()
    body = open(tmp_path / "monitor.tsv").read().strip().splitlines()
    assert body[0].startswith("time\t") and len(body) >= 2
    assert (tmp_path / "profile.tsv").exists()
    assert "flush_check" in open(tmp_path / "profile.tsv").read()
    # accumulated roofline rows are dumped AND cleared: a later
    # init/end cycle in the same process must not re-dump them
    assert "flush_check" in open(tmp_path / "roofline.tsv").read()
    assert orf.rows() == []


def test_tuner_emits_candidate_trace_events(tmp_path):
    from quda_tpu.utils import tune
    otr.start(str(tmp_path))
    x = jnp.ones((16, 16))
    slow = jax.jit(lambda a: (a @ a) @ (a @ a))
    fast = jax.jit(lambda a: a + 1.0)
    key_aux = "obs_test"
    tune.tune("obs_dummy", (16, 16), {"slow": slow, "fast": fast}, (x,),
              aux=key_aux)
    # second call hits the cache -> audited as a cached decision
    tune.tune("obs_dummy", (16, 16), {"slow": slow, "fast": fast}, (x,),
              aux=key_aux)
    paths = otr.stop()
    lines = [json.loads(ln) for ln in open(paths["jsonl"])]
    names = [ln["name"] for ln in lines]
    assert names.count("tune_candidate") == 2
    assert "tune_winner" in names
    assert "tune_cached" in names
    winner = next(ln for ln in lines if ln["name"] == "tune_winner")
    assert winner["param"] == "fast"
