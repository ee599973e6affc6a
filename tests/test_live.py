"""Live telemetry plane tests (quda_tpu/obs/live.py): the ISSUE-19
acceptance drills.

CPU drills, all tier-1:

* mid-traffic scrape — a running SolveService answers all five
  endpoints while serving, and ``serve_requests_total`` advances
  between two /metrics scrapes with ZERO ``end_quda`` calls (the
  long-lived-worker contract the plane exists for);
* /readyz flips on gauge load and back off when the last gauge is
  evicted; /healthz exposes a dead worker behind a live socket;
* off means off — with QUDA_TPU_LIVE unset a raising stub on the
  session class proves no server is ever constructed, and the solves
  are bit-identical to a live-telemetry session's (same process, same
  compiled executable);
* concurrent scrape + solve — handler threads only read
  lock-consistent snapshots, so hammering /metrics //slo during
  active solves yields 200s throughout;
* request-id correlation — a fault-injected request's postmortem
  bundle ``manifest.json`` carries the exact ``request_id`` its
  SolveTicket reported;
* QUDA_TPU_SERVE_SLO_BUCKETS reshapes ``serve_request_seconds`` and
  the burn-rate math; the periodic flusher rewrites artifacts with the
  session still open.
"""

import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from quda_tpu.obs import live as olive
from quda_tpu.obs import memory as omem
from quda_tpu.obs import metrics as omet
from quda_tpu.obs import trace as otr
from quda_tpu.utils import config as qconf

L = 4


@pytest.fixture(autouse=True)
def _live_isolation(monkeypatch, tmp_path):
    """Fresh session per test under its own resource path; the live
    plane is torn down on both sides so a failed test can never leak a
    bound socket into its neighbor."""
    from quda_tpu.interfaces import quda_api as api
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    monkeypatch.setenv("QUDA_TPU_METRICS", "1")
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    monkeypatch.delenv("QUDA_TPU_LIVE", raising=False)
    monkeypatch.delenv("QUDA_TPU_LIVE_PORT", raising=False)
    monkeypatch.delenv("QUDA_TPU_METRICS_FLUSH_SEC", raising=False)
    olive.stop()
    # an earlier test of this worker may have left the API initialised
    # (test_eig's arpack bridge does): SolveService.start() then skips
    # init_quda, so no metrics session opens and /metrics reads empty
    api.end_quda()
    omet.stop(flush_files=False)
    omem.reset()
    otr.stop(flush_files=False)
    qconf.reset_cache()
    yield
    olive.stop()
    try:
        api.end_quda()
    except Exception:
        pass
    omet.stop(flush_files=False)
    omem.reset()
    otr.stop(flush_files=False)
    qconf.reset_cache()


def _unit_gauge():
    return np.broadcast_to(np.eye(3, dtype=np.complex64),
                           (4, L, L, L, L, 3, 3)).copy()


def _gauge_param():
    from quda_tpu.interfaces.params import GaugeParam
    return GaugeParam(X=(L,) * 4, cuda_prec="single")


def _wilson_param(**kw):
    from quda_tpu.interfaces.params import InvertParam
    args = dict(dslash_type="wilson", inv_type="cg",
                solve_type="normop-pc", kappa=0.12, tol=1e-6,
                maxiter=300, cuda_prec="single")
    args.update(kw)
    return InvertParam(**args)


def _sources(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((L, L, L, L, 4, 3))
             + 1j * rng.standard_normal((L, L, L, L, 4, 3))
             ).astype(np.complex64) for _ in range(n)]


def _get(path):
    """Scrape one endpoint off the bound live port; HTTP errors are
    payloads here, not exceptions (503 readyz IS the assertion)."""
    p = olive.port()
    assert p, "live telemetry plane is not bound"
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{p}{path}", timeout=30) as r:
            return r.status, r.headers.get("Content-Type", ""), \
                r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type", ""), \
            e.read().decode()


def _prom_value(body, name, **labels):
    """Sum a counter family out of Prometheus text (None when the
    family has no sample lines yet)."""
    tot, found = 0.0, False
    for line in body.splitlines():
        if not line.startswith(f"quda_tpu_{name}"):
            continue
        head, _, val = line.rpartition(" ")
        if all(f'{k}="{v}"' in head for k, v in labels.items()):
            tot += float(val)
            found = True
    return tot if found else None


def _service(monkeypatch, gauge=True):
    from quda_tpu.serve import SolveService
    monkeypatch.setenv("QUDA_TPU_LIVE", "1")
    qconf.reset_cache()
    svc = SolveService(batch_window_ms=0.0)
    if gauge:
        svc.load_gauge("cfg", _unit_gauge(), _gauge_param())
    return svc


# -- mid-traffic scrape: the acceptance drill ---------------------------------

def test_all_endpoints_answer_and_counters_advance_mid_traffic(
        monkeypatch):
    """Running service + QUDA_TPU_LIVE=1: every endpoint answers, and
    serve_requests_total advances between two /metrics scrapes with no
    end_quda in between (scrapes are idempotent reads of the live
    registry — NOT reset-on-read)."""
    svc = _service(monkeypatch).start()
    try:
        st, ct, body1 = _get("/metrics")
        assert st == 200 and ct.startswith("text/plain")
        before = _prom_value(body1, "serve_requests_total") or 0.0

        param = _wilson_param()
        for b in _sources(2, seed=3):
            out = svc.submit(b, param, "cfg").result(timeout=600)
            assert out.status == "converged"

        st, _, body2 = _get("/metrics")
        assert st == 200
        assert _prom_value(body2, "serve_requests_total") == before + 2
        # the scrape plane meters itself: scrape #1 landed in the
        # registry that scrape #2 reads
        assert _prom_value(body2, "live_scrapes_total",
                           endpoint="metrics", code="2xx") >= 1

        st, ct, hz = _get("/healthz")
        assert st == 200 and json.loads(hz)["worker_alive"]
        st, _, rz = _get("/readyz")
        assert st == 200 and json.loads(rz)["ready"]
        st, _, fleet = _get("/fleet")
        assert st == 200 and "Service" in fleet
        st, ct, slo = _get("/slo")
        assert st == 200 and ct.startswith("application/json")
        doc = json.loads(slo)
        assert doc["overall"]["n"] == 2
        st, _, nf = _get("/nope")
        assert st == 404 and "/metrics" in nf
    finally:
        svc.stop()


# -- readiness / liveness -----------------------------------------------------

def test_readyz_flips_on_gauge_load_and_eviction(monkeypatch):
    svc = _service(monkeypatch, gauge=False).start()
    try:
        st, _, body = _get("/readyz")
        assert st == 503
        assert json.loads(body)["checks"]["gauge_present"] is False

        svc.load_gauge("cfg", _unit_gauge(), _gauge_param())
        st, _, body = _get("/readyz")
        assert st == 200 and json.loads(body)["ready"]

        # evict the last gauge: registered host copies AND residency
        svc._gauges.clear()
        svc.residency.drop_all()
        st, _, body = _get("/readyz")
        assert st == 503
        assert json.loads(body)["checks"]["gauge_present"] is False
    finally:
        svc.stop()


def test_healthz_exposes_dead_worker_behind_live_socket(monkeypatch):
    """The zombie /healthz exists to catch: worker thread dead, HTTP
    socket still answering.  Must go 503, not 200."""
    svc = _service(monkeypatch).start()
    try:
        st, _, _ = _get("/healthz")
        assert st == 200
        svc._stop.set()
        svc._thread.join()           # worker exits on its idle poll
        st, _, body = _get("/healthz")
        doc = json.loads(body)
        assert st == 503
        assert doc["worker_alive"] is False and doc["stopped"] is False
    finally:
        svc.stop()


# -- off means off ------------------------------------------------------------

def test_live_off_never_constructs_server_and_solves_bit_identical(
        monkeypatch):
    """QUDA_TPU_LIVE unset: a raising stub on the session class proves
    init_quda + a full solve never construct a server/socket/thread;
    the same compiled executable then re-runs with the plane ON and
    the solutions are bit-identical (zero ops in compiled solves
    either way)."""
    from quda_tpu.interfaces import quda_api as api

    def _boom(*a, **k):
        raise AssertionError("live telemetry touched while off")

    src, param = _sources(1, seed=7)[0], _wilson_param()
    with monkeypatch.context() as m:
        m.setattr(olive._Live, "__init__", _boom)
        api.init_quda()
        api.load_gauge_quda(_unit_gauge(), _gauge_param())
        x_off = np.asarray(api.invert_quda(src, param))
        assert param.converged
        assert not olive.enabled() and olive.port() is None
    # same process, same executable — now with the plane up
    olive.start(port=0)
    assert olive.enabled() and olive.port()
    st, _, _ = _get("/metrics")
    assert st == 200
    x_on = np.asarray(api.invert_quda(src, param))
    np.testing.assert_array_equal(x_off, x_on)


# -- concurrency --------------------------------------------------------------

def test_concurrent_scrapes_during_active_solves(monkeypatch):
    """Handler threads hammer /metrics //slo while the worker solves;
    every scrape is a 200 (snapshots are lock-consistent, a scrape can
    never observe a half-written registry or kill the pool)."""
    svc = _service(monkeypatch).start()
    stop = threading.Event()
    statuses = []

    def _scraper():
        i = 0
        while not stop.is_set():
            st, _, _ = _get("/metrics" if i % 2 == 0 else "/slo")
            statuses.append(st)
            i += 1

    t = threading.Thread(target=_scraper, daemon=True)
    t.start()
    try:
        param = _wilson_param()
        for b in _sources(3, seed=5):
            out = svc.submit(b, param, "cfg").result(timeout=600)
            assert out.status == "converged"
    finally:
        stop.set()
        t.join(timeout=30)
        svc.stop()
    assert len(statuses) >= 2
    assert set(statuses) == {200}


# -- request-id correlation ---------------------------------------------------

def test_fault_injected_bundle_manifest_carries_request_id(
        monkeypatch):
    """The one-grep contract: a fault-injected request's postmortem
    bundle manifest.json carries the EXACT request_id its SolveTicket
    reported (minted at submit, threaded through the batch into the
    capture scope)."""
    from quda_tpu.robust import faultinject as finj
    monkeypatch.setenv("QUDA_TPU_POSTMORTEM", "1")
    monkeypatch.setenv("QUDA_TPU_ROBUST", "verify")
    monkeypatch.setenv("QUDA_TPU_FAULT", "residual:1e6")
    qconf.reset_cache()
    finj.reset()                  # re-parse the env spec (one-shot arms)
    svc = _service(monkeypatch)
    svc.start()
    try:
        tkt = svc.submit(_sources(1, seed=11)[0], _wilson_param(),
                         "cfg")
        assert tkt.request_id.startswith("rq-")
        out = tkt.result(timeout=600)
        assert out.status == "unverified"
        assert out.request_id == tkt.request_id

        rp = os.environ["QUDA_TPU_RESOURCE_PATH"]
        bundles = sorted(glob.glob(
            os.path.join(rp, "postmortems", "pm_*")))
        assert bundles, "verify_mismatch capture did not write"
        m = json.load(open(os.path.join(bundles[-1], "manifest.json")))
        assert m["request_id"] == tkt.request_id
        assert m["request_ids"] == [tkt.request_id]
    finally:
        svc.stop()
        finj.reset()


def test_request_ids_mint_unique_and_ride_outcomes(monkeypatch):
    svc = _service(monkeypatch).start()
    try:
        param = _wilson_param()
        tickets = [svc.submit(b, param, "cfg")
                   for b in _sources(3, seed=23)]
        rids = [t.request_id for t in tickets]
        assert len(set(rids)) == 3
        assert all(r.startswith(f"rq-{os.getpid()}-") for r in rids)
        for t in tickets:
            out = t.result(timeout=600)
            assert out.status == "converged"
            assert out.request_id == t.request_id
    finally:
        svc.stop()


# -- SLO buckets + burn rate --------------------------------------------------

def test_serve_slo_buckets_knob_reshapes_histogram_and_burn(
        monkeypatch):
    monkeypatch.setenv("QUDA_TPU_SERVE_SLO_BUCKETS", "0.05,0.25,1")
    monkeypatch.setenv("QUDA_TPU_SLO_TARGET_MS", "100")
    monkeypatch.setenv("QUDA_TPU_SLO_OBJECTIVE", "0.9")
    qconf.reset_cache()
    omet.start()
    for v in (0.01, 0.02, 0.5):
        omet.observe("serve_request_seconds", v, family="wilson")
    snap = omet.snapshot()
    (_, h), = [(k, h) for k, h in snap["histograms"].items()
               if k[0] == "serve_request_seconds"]
    assert h["buckets"] == (0.05, 0.25, 1.0)
    assert h["counts"] == [2, 0, 1, 0]
    prom = omet.render_prometheus(snap)
    assert 'le="0.05"' in prom

    # conservative grading: only buckets whose UPPER bound fits the
    # 100 ms target count as good → 2/3 compliant, 10% budget
    s = olive.slo_summary(snap)
    assert s["overall"]["n"] == 3 and s["overall"]["good"] == 2
    assert s["families"][0]["family"] == "wilson"
    assert abs(s["overall"]["burn_rate"] - (1 / 3) / 0.1) < 1e-3


def test_slo_summary_empty_is_compliant(monkeypatch):
    omet.start()
    s = olive.slo_summary()
    assert s["families"] == []
    assert s["overall"] == {"n": 0, "good": 0, "compliance": 1.0,
                            "burn_rate": 0.0}


def test_malformed_slo_buckets_falls_back(monkeypatch):
    monkeypatch.setenv("QUDA_TPU_SERVE_SLO_BUCKETS", "fast,slow")
    qconf.reset_cache()
    omet.start()
    omet.observe("serve_request_seconds", 0.1, family="wilson")
    (_, h), = [(k, h) for k, h in
               omet.snapshot()["histograms"].items()
               if k[0] == "serve_request_seconds"]
    assert h["buckets"] == omet.HIST_BUCKETS


# -- periodic exporter --------------------------------------------------------

def test_flush_now_writes_artifacts_without_end_quda(monkeypatch,
                                                     tmp_path):
    omet.start()
    omet.inc("live_flushes_total", )  # ensure family exists pre-flush
    olive.start(port=0, flush_sec=0.0)
    assert olive._session.flusher is None    # 0 = no periodic thread
    written = olive.flush_now()
    assert written["metrics"]["prom"]
    assert os.path.exists(written["metrics"]["prom"])
    body = open(written["metrics"]["prom"]).read()
    assert "quda_tpu_live_flushes_total" in body


def test_periodic_flusher_rewrites_on_interval(monkeypatch, tmp_path):
    monkeypatch.setenv("QUDA_TPU_METRICS_FLUSH_SEC", "0.05")
    omet.start()
    olive.start(port=0)
    assert olive._session.flusher is not None
    prom = os.path.join(tmp_path, "metrics.prom")

    def flushes():
        return sum(v for (n, _), v in omet.snapshot()["counters"].items()
                   if n == "live_flushes_total")
    # a window writes metrics.prom first and counts itself last, after
    # the flight and roofline legs: wait for the whole window
    deadline = time.time() + 15.0
    while time.time() < deadline and not (os.path.exists(prom)
                                          and flushes()):
        time.sleep(0.05)
    assert os.path.exists(prom), "flusher never wrote metrics.prom"
    from quda_tpu.obs import schema as osch
    assert flushes() >= 1
    assert osch.METRICS["live_flushes_total"]["type"] == osch.COUNTER


def test_live_off_scrape_helpers_noop(monkeypatch):
    assert not olive.enabled()
    assert olive.port() is None
    assert olive.flush_now() is None
    assert olive.stop() is None
    olive.attach(object())           # one global load, no throw
    olive.detach(object())
