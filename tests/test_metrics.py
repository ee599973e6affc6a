"""Serving-metrics tests: counter/gauge/histogram registry, HBM field
ledger, compile/cache accounting, fleet report, and the end_quda
artifact contract.

Covers the ISSUE-12 acceptance path (QUDA_TPU_METRICS=1 + one Wilson CG
solve + one staggered multi-src solve -> metrics.prom / metrics.tsv /
fleet_report.txt with solve counters by family+status, a non-empty HBM
ledger with high-water, >=1 compile event per distinct operator form,
and tuner warm-cache hit/miss counters), the off-path zero-overhead pin
(raising stubs, mirroring test_observability.py), the all-device
monitor snapshot, and the exception-safe end_quda epilogue."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.obs import memory as omem
from quda_tpu.obs import metrics as omet
from quda_tpu.obs import report as orep
from quda_tpu.obs import schema as osch
from quda_tpu.obs import trace as otr
from quda_tpu.utils import config as qconf


@pytest.fixture(autouse=True)
def _metrics_isolation():
    """Every test starts and ends with no metrics session, an empty
    ledger, no trace session, and a fresh config cache."""
    omet.stop(flush_files=False)
    omem.reset()
    otr.stop(flush_files=False)
    qconf.reset_cache()
    yield
    omet.stop(flush_files=False)
    omem.reset()
    otr.stop(flush_files=False)
    qconf.reset_cache()


# -- registry units ---------------------------------------------------------

def test_registry_counter_gauge_histogram(tmp_path):
    omet.start(str(tmp_path))
    omet.inc("solves_total", api="invert_quda", family="wilson",
             status="converged")
    omet.inc("solves_total", 2.0, api="invert_quda", family="wilson",
             status="converged")
    omet.set_gauge("hbm_family_bytes", 1024, family="gauge")
    omet.observe("solve_seconds", 0.05, api="invert_quda",
                 family="wilson")
    omet.observe("solve_seconds", 30.0, api="invert_quda",
                 family="wilson")
    snap = omet.snapshot()
    (_, labels), v = next(iter(snap["counters"].items()))
    assert v == 3.0
    assert dict(labels)["status"] == "converged"
    h = next(iter(snap["histograms"].values()))
    assert h["n"] == 2 and h["sum"] == pytest.approx(30.05)
    # prometheus rendering: HELP/TYPE lines + the cumulative buckets
    prom = omet.render_prometheus(snap)
    assert "# TYPE quda_tpu_solves_total counter" in prom
    assert ('quda_tpu_solves_total{api="invert_quda",family="wilson",'
            'status="converged"} 3') in prom
    assert 'quda_tpu_solve_seconds_bucket' in prom
    assert 'le="+Inf"} 2' in prom
    tsv = omet.render_tsv(snap)
    assert "solves_total\tcounter" in tsv


def test_export_keeps_full_precision_on_large_values(tmp_path):
    """'%g'-style rendering truncates at 6 significant digits — a
    session's iteration counters and byte gauges exceed 1e6 routinely,
    and a rounded counter reads as zero/negative under rate()."""
    omet.start(str(tmp_path))
    omet.inc("solve_iterations_total", 1234567, api="a", family="b")
    omet.set_gauge("hbm_family_bytes", 66977792, family="gauge")
    prom = omet.render_prometheus()
    assert "} 1234567" in prom and "} 66977792" in prom
    tsv = omet.render_tsv()
    assert "\t1234567" in tsv and "\t66977792" in tsv


def test_stop_clears_session_even_when_flush_raises(tmp_path,
                                                    monkeypatch):
    """A failed flush (unwritable resource path) must not leak the
    stale registry into the next session."""
    omet.start(str(tmp_path / "no" / "such"))
    monkeypatch.setattr(omet, "flush",
                        lambda: (_ for _ in ()).throw(OSError("ro")))
    with pytest.raises(OSError):
        omet.stop()
    assert not omet.enabled()


def test_registry_rejects_unregistered_and_mistyped_names(tmp_path):
    omet.start(str(tmp_path))
    with pytest.raises(KeyError, match="unregistered metric"):
        omet.inc("no_such_metric_total")
    with pytest.raises(TypeError, match="registered as counter"):
        omet.set_gauge("solves_total", 1.0)


def test_noop_when_off():
    """Off means off: recording calls return after one global load and
    never construct a registry."""
    assert not omet.enabled()
    omet.inc("solves_total", api="a", family="b", status="c")
    omet.set_gauge("hbm_family_bytes", 1, family="gauge")
    omet.observe("solve_seconds", 1.0, api="a", family="b")
    assert not omet.record_execution("a", "f", (4, 4, 4, 4), "single",
                                     "cg", 0.1)
    assert omet._session is None
    assert omet.snapshot() == {"counters": {}, "gauges": {},
                               "histograms": {}}


def test_record_execution_first_vs_warm(tmp_path):
    omet.start(str(tmp_path))
    otr.start(str(tmp_path))
    first = omet.record_execution("invert_quda", "wilson_v2",
                                  (8, 8, 8, 8), "single", "cg", 1.5)
    again = omet.record_execution("invert_quda", "wilson_v2",
                                  (8, 8, 8, 8), "single", "cg", 0.01)
    other = omet.record_execution("invert_quda", "wilson_v2",
                                  (16, 8, 8, 8), "single", "cg", 1.2)
    assert first and other and not again
    snap = omet.snapshot()
    compiles = sum(v for (n, _), v in snap["counters"].items()
                   if n == "compiles_total")
    execs = sum(v for (n, _), v in snap["counters"].items()
                if n == "executions_total")
    assert compiles == 2 and execs == 3
    # first executions mirror as 'compile' trace events
    paths = otr.stop()
    lines = [json.loads(ln) for ln in open(paths["jsonl"])]
    assert len([ln for ln in lines if ln.get("name") == "compile"]) == 2


# -- HBM ledger units -------------------------------------------------------

def test_ledger_track_release_high_water(tmp_path):
    omet.start(str(tmp_path))
    a = np.zeros((8, 8), np.float32)
    b = np.zeros((16, 16), np.complex64)
    omem.track("gauge", "resident_gauge", a)
    omem.track("eig", "evecs", [b, b.copy()])
    assert omem.family_bytes() == {"gauge": a.nbytes,
                                   "eig": 2 * b.nbytes}
    # re-track replaces (resident mutation), high-water keeps the peak
    omem.track("eig", "evecs", b)
    assert omem.family_bytes()["eig"] == b.nbytes
    assert omem.high_water()["eig"] == 2 * b.nbytes
    assert omem.release("eig", "evecs")
    assert not omem.release("eig", "evecs")
    assert "eig" not in omem.family_bytes()
    snap = omet.snapshot()
    gauges = {(n, dict(lab).get("family")): v
              for (n, lab), v in snap["gauges"].items()}
    assert gauges[("hbm_family_bytes", "eig")] == 0
    assert gauges[("hbm_family_high_water_bytes", "eig")] == 2 * b.nbytes


def test_nbytes_of_walks_objects_and_cycles():
    class _Op:
        pass

    op = _Op()
    op.links = [np.zeros((4, 4), np.float32)] * 2  # same array twice
    op.meta = {"x": np.zeros((2,), np.float64), "n": 3}
    op.self_ref = op                                # cycle
    # the duplicate list entry is the SAME object -> counted once
    assert omem.nbytes_of(op) == 4 * 4 * 4 + 2 * 8


def test_device_snapshot_covers_all_local_devices():
    """Satellite: the monitor sampled only jax.local_devices()[0];
    device_snapshot must return one row per local device."""
    rows = omem.device_snapshot()
    assert len(rows) == len(jax.local_devices())
    assert all("bytes_in_use" in r and "device" in r for r in rows)


def test_monitor_samples_all_devices(tmp_path):
    from quda_tpu.utils.monitor import Monitor
    m = Monitor(period_s=0.01, path=str(tmp_path / "monitor.tsv"))
    with m:
        time.sleep(0.05)
    assert m.samples and all(
        s["n_devices"] == len(jax.local_devices()) for s in m.samples)
    header = open(tmp_path / "monitor.tsv").readline()
    assert header.startswith("time\t")
    assert "device_bytes_max" in header and "n_devices" in header


def test_vmem_audit_and_budget_report(tmp_path):
    omet.start(str(tmp_path))
    omem.vmem_audit("QUDA_TPU_PALLAS_VMEM_MB", 4 << 20, 6 << 20, bz=8)
    rows = omem.audit_vmem_budgets()
    by_knob = {r["knob"]: r for r in rows}
    assert by_knob["QUDA_TPU_PALLAS_VMEM_MB"]["double_buffer_ok"]
    assert by_knob["QUDA_TPU_PALLAS_VMEM_MB"]["last_bz"] == 8
    # the raised staggered default is flagged (not rejected)
    assert not by_knob["QUDA_TPU_PALLAS_VMEM_MB_STAGGERED"][
        "double_buffer_ok"]
    rep = orep.render()
    assert "QUDA_TPU_PALLAS_VMEM_MB_STAGGERED" in rep


def test_pick_bz_feeds_vmem_audit(tmp_path):
    from quda_tpu.ops.wilson_pallas_packed import _pick_bz
    omet.start(str(tmp_path))
    _pick_bz(8, 64)
    snap = omet.snapshot()
    gauges = {n: dict(lab) for (n, lab), _ in snap["gauges"].items()}
    assert gauges.get("vmem_block_bytes", {}).get("knob") == \
        "QUDA_TPU_PALLAS_VMEM_MB"
    assert "vmem_budget_bytes" in gauges


def test_solve_program_counts_one_miss_then_hits(tmp_path):
    """Three calls through the cached solve program (solvers/program.py;
    here on the cheap XLA pair stencil at 4^4, new links every call):
    ``solve_program_total`` reads one miss then two hits, and the solve
    span carries ``program`` likewise."""
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.interfaces.quda_api import _note_solve_program
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.solvers import program as sprog
    omet.start(str(tmp_path))
    otr.start(str(tmp_path))
    geom = LatticeGeometry((4, 4, 4, 4))
    rng = np.random.default_rng(5)
    for i in range(3):
        g = GaugeField.random(jax.random.PRNGKey(40 + i), geom)
        dpk = DiracWilsonPC(g.data.astype(jnp.complex64), geom,
                            0.11 + 0.005 * i).packed()
        hi, lo = dpk.pairs(jnp.float32), dpk.pairs(jnp.bfloat16)
        b = jnp.asarray(rng.standard_normal((4, 3, 2, 4, 4, 8)),
                        jnp.float32)
        with otr.span("solve:cg", cat="solver") as sp:
            # a delta of this test's own: delta is part of the key, and
            # a worker that ran test_solve_program.py first has traced
            # the 0.1 program at this shape already
            res, hit = sprog.cg_reliable(hi, lo, b, tol=1e-5, maxiter=200,
                                         delta=0.11)
            _note_solve_program(sp, "invert_quda", "wilson_xla", "cg",
                                hit)
        assert bool(res.converged)
        r = b - hi.MdagM_pairs(res.x)
        assert float(jnp.linalg.norm(r) / jnp.linalg.norm(b)) < 2e-5
    by_outcome = {dict(lab)["outcome"]: v
                  for (n, lab), v in omet.snapshot()["counters"].items()
                  if n == "solve_program_total"}
    assert by_outcome == {"miss": 1.0, "hit": 2.0}
    prom = omet.render_prometheus()
    assert ('quda_tpu_solve_program_total{api="invert_quda",'
            'form="wilson_xla",outcome="hit",solver="cg"} 2') in prom
    paths = otr.stop()
    spans = [json.loads(ln) for ln in open(paths["jsonl"])]
    assert [s["program"] for s in spans
            if s.get("name") == "solve:cg"] == ["miss", "hit", "hit"]


# -- acceptance: metrics-on session end to end ------------------------------

def _unit_gauge(L):
    return np.broadcast_to(np.eye(3, dtype=np.complex64),
                           (4, L, L, L, L, 3, 3)).copy()


def _wilson_param():
    from quda_tpu.interfaces.params import InvertParam
    return InvertParam(dslash_type="wilson", inv_type="cg",
                       solve_type="normop-pc", kappa=0.12, tol=1e-6,
                       maxiter=300, cuda_prec="single")


def test_metrics_acceptance_session(tmp_path, monkeypatch):
    """The ISSUE acceptance criterion: a QUDA_TPU_METRICS=1 CPU session
    running one Wilson CG solve + one staggered multi-src solve ends
    with metrics.prom/metrics.tsv and a fleet report carrying solve
    counters by family+status, a non-empty HBM ledger with high-water,
    >=1 compile per distinct operator form, and tuner warm-cache
    hit/miss counters."""
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.utils import tune
    monkeypatch.setenv("QUDA_TPU_METRICS", "1")
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    qconf.reset_cache()
    api.init_quda()
    L = 4
    api.load_gauge_quda(_unit_gauge(L), GaugeParam(X=(L,) * 4,
                                                   cuda_prec="single"))
    rng = np.random.default_rng(0)
    b = (rng.standard_normal((L, L, L, L, 4, 3))
         + 1j * rng.standard_normal((L, L, L, L, 4, 3))
         ).astype(np.complex64)
    api.invert_quda(b, _wilson_param())
    B = np.stack([(rng.standard_normal((L, L, L, L, 1, 3))
                   + 1j * rng.standard_normal((L, L, L, L, 1, 3))
                   ).astype(np.complex64) for _ in range(2)])
    ps = InvertParam(dslash_type="staggered", inv_type="cg", mass=0.1,
                     solve_type="normop-pc", tol=1e-6, maxiter=300,
                     cuda_prec="single")
    api.invert_multi_src_quda(B, ps)
    # one tuner race + one warm-cache hit inside the session
    x = jnp.ones((8, 8))
    f = jax.jit(lambda a: a + 1.0)
    tune.tune("metrics_acceptance", (8, 8), {"id": f}, (x,))
    tune.tune("metrics_acceptance", (8, 8), {"id": f}, (x,))
    api.end_quda()

    prom = open(tmp_path / "metrics.prom").read()
    # solve counters labeled by family and status
    assert ('quda_tpu_solves_total{api="invert_quda",family="wilson",'
            'status="converged"} 1') in prom
    assert 'family="staggered"' in prom
    # HBM ledger: resident gauge bytes + high-water gauges
    gauge_bytes = 4 * L ** 4 * 9 * 8
    assert (f'quda_tpu_hbm_family_bytes{{family="gauge"}} {gauge_bytes}'
            in prom)
    assert "quda_tpu_hbm_family_high_water_bytes" in prom
    # >= 1 compile per distinct operator form
    assert 'quda_tpu_compiles_total{api="invert_quda",form="wilson_xla"}' \
        in prom
    assert ('quda_tpu_compiles_total{api="invert_quda",'
            'form="staggered_xla"}') in prom
    # tuner warm-cache hit/miss counters
    assert 'quda_tpu_tune_cache_hits_total' in prom
    assert 'quda_tpu_tune_cache_misses_total' in prom

    assert (tmp_path / "metrics.tsv").exists()
    rep = open(tmp_path / "fleet_report.txt").read()
    assert "## Solves (by api / family / status)" in rep
    assert "wilson" in rep and "staggered" in rep
    assert "gauge/resident_gauge" in rep and "high-water" in rep
    assert "first-execution compiles: 2" in rep
    assert "tuner warm-cache: 1 hits / 1 misses" in rep
    # session closed: a second end-cycle ledger is empty
    assert omem.family_bytes() == {}


def test_transient_families_released_after_solve(tmp_path, monkeypatch):
    """Clover terms are rebuilt per _build_dirac and eig workspaces are
    handed to the caller — their ledger rows must NOT survive the API
    call as 'resident now' (stale rows overstate capacity on the exact
    surface the fleet reads), while the family high-water keeps the
    peak signal."""
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.interfaces import quda_api as api
    monkeypatch.setenv("QUDA_TPU_METRICS", "1")
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    qconf.reset_cache()
    api.init_quda()
    L = 4
    api.load_gauge_quda(_unit_gauge(L), GaugeParam(X=(L,) * 4,
                                                   cuda_prec="single"))
    rng = np.random.default_rng(3)
    b = (rng.standard_normal((L, L, L, L, 4, 3))
         + 1j * rng.standard_normal((L, L, L, L, 4, 3))
         ).astype(np.complex64)
    p = InvertParam(dslash_type="clover", inv_type="cg",
                    solve_type="normop-pc", kappa=0.12, csw=1.0,
                    tol=1e-5, maxiter=300, cuda_prec="single")
    api.invert_quda(b, p)
    assert "clover" not in omem.family_bytes()     # released at exit
    assert omem.high_water().get("clover", 0) > 0  # peak retained
    assert omem.family_bytes().get("gauge", 0) > 0  # resident stays
    api.end_quda()


def test_metrics_off_solve_never_touches_registry(monkeypatch):
    """Satellite: QUDA_TPU_METRICS=0 installs raising stubs on every
    registry recording method and the report renderer; a full Wilson CG
    solve completes without touching any of them (the obs zero-overhead
    pin, test_observability.py style) — and the compiled solve path has
    no metrics branch that could alter it."""
    from quda_tpu.interfaces.params import GaugeParam
    from quda_tpu.interfaces import quda_api as api
    monkeypatch.delenv("QUDA_TPU_METRICS", raising=False)
    qconf.reset_cache()

    def _boom(*a, **kw):
        raise AssertionError("metrics recording ran with metrics off")

    monkeypatch.setattr(omet._Registry, "inc", _boom)
    monkeypatch.setattr(omet._Registry, "set", _boom)
    monkeypatch.setattr(omet._Registry, "observe", _boom)
    monkeypatch.setattr(orep, "render", _boom)
    monkeypatch.setattr(omem, "sample", _boom)
    api.init_quda()
    L = 4
    api.load_gauge_quda(_unit_gauge(L), GaugeParam(X=(L,) * 4,
                                                   cuda_prec="single"))
    rng = np.random.default_rng(1)
    b = (rng.standard_normal((L, L, L, L, 4, 3))
         + 1j * rng.standard_normal((L, L, L, L, 4, 3))
         ).astype(np.complex64)
    p = _wilson_param()
    api.invert_quda(b, p)
    assert p.converged and p.true_res < 1e-5
    api.end_quda()


# -- end_quda exception-path artifact flush (satellite) ---------------------

def test_end_quda_flushes_artifacts_after_raising_solve(tmp_path,
                                                        monkeypatch):
    """A solve that raises must not cost the session its artifacts:
    end_quda still writes the trace + metrics exports that explain the
    crash."""
    from quda_tpu.interfaces.params import GaugeParam
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.utils.logging import QudaError
    monkeypatch.setenv("QUDA_TPU_METRICS", "1")
    monkeypatch.setenv("QUDA_TPU_TRACE", "1")
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    qconf.reset_cache()
    api.init_quda()
    L = 4
    api.load_gauge_quda(_unit_gauge(L), GaugeParam(X=(L,) * 4,
                                                   cuda_prec="single"))
    p = _wilson_param()
    p.inv_type = "no-such-solver"
    rng = np.random.default_rng(2)
    b = (rng.standard_normal((L, L, L, L, 4, 3))
         + 1j * rng.standard_normal((L, L, L, L, 4, 3))
         ).astype(np.complex64)
    with pytest.raises(QudaError):
        api.invert_quda(b, p)
    api.end_quda()
    assert (tmp_path / "trace.json").exists()
    assert (tmp_path / "metrics.prom").exists()
    assert (tmp_path / "fleet_report.txt").exists()


def test_end_quda_epilogue_survives_step_failure(tmp_path, monkeypatch):
    """A raising epilogue step (broken profile writer) must not eat the
    later flush steps: metrics/trace artifacts are still written and
    the first error re-raises AFTER the epilogue completes."""
    from quda_tpu.interfaces import quda_api as api
    import quda_tpu.utils.tune as qtune
    monkeypatch.setenv("QUDA_TPU_METRICS", "1")
    monkeypatch.setenv("QUDA_TPU_TRACE", "1")
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    qconf.reset_cache()
    api.init_quda()

    def _broken():
        raise OSError("disk full")

    monkeypatch.setattr(qtune, "save_profile", _broken)
    with pytest.raises(OSError, match="disk full"):
        api.end_quda()
    assert (tmp_path / "metrics.prom").exists()
    assert (tmp_path / "trace.json").exists()


# -- fleet report -----------------------------------------------------------

def test_report_renders_without_session():
    rep = orep.render()
    assert "(no API solves recorded)" in rep
    assert "(no resident fields tracked)" in rep


def test_report_retry_section(tmp_path):
    omet.start(str(tmp_path))
    omet.inc("solve_retries_total", api="invert_quda",
             reason="breakdown:nonfinite")
    omet.inc("solve_degraded_total", api="invert_quda")
    omet.inc("breakdowns_total", api="invert_quda",
             reason="nonfinite")
    rep = orep.render()
    assert "retry invert_quda [breakdown:nonfinite]: 1" in rep
    assert "degraded solves: 1; breakdown exits: 1" in rep


def test_schema_types_consistent():
    """Every schema metric is one of the three types; histogram bucket
    config is monotone."""
    for name, meta in osch.METRICS.items():
        assert meta["type"] in (osch.COUNTER, osch.GAUGE,
                                osch.HISTOGRAM), name
        assert meta["help"]
    assert list(omet.HIST_BUCKETS) == sorted(omet.HIST_BUCKETS)
