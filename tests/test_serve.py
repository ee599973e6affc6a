"""Solve-service tests (quda_tpu/serve): the ISSUE-12 acceptance drills.

CPU drills, all tier-1:

* coalescing — k concurrent requests for one gauge served as ONE MRHS
  execution, pinned via ``executions_total``;
* residency — eviction honoring the HBM budget with the gauge family's
  high-water intact, and transparent reload of an evicted gauge;
* warm start — a second worker session reusing the persisted
  compilation cache + executable-key index records
  ``compiles_total == 0`` for already-keyed executables while
  ``executions_total`` advances;
* availability — a fault-injected (QUDA_TPU_FAULT) request lands as a
  degraded availability event on the ticket and in the counters, never
  a worker crash;
* the tier-1 smoke drill — N mixed-gauge requests, clean shutdown
  flushing artifacts through end_quda (fleet_report.txt Service
  section, artifacts manifest);
* batcher/residency units and the serve_* schema pins (the
  bidirectional AST lint in test_obs_schema_lint.py covers serve/
  automatically — the pins here assert the registrations the Service
  section keys on never rot).
"""

import json
import os
import queue as _queue

import jax
import numpy as np
import pytest

from quda_tpu.obs import memory as omem
from quda_tpu.obs import metrics as omet
from quda_tpu.obs import schema as osch
from quda_tpu.obs import trace as otr
from quda_tpu.serve import batcher
from quda_tpu.utils import config as qconf

L = 4


@pytest.fixture(autouse=True)
def _serve_isolation(monkeypatch, tmp_path):
    """Each test runs a fresh session under its own resource path with
    the packed MRHS route enabled (the batched-pairs pipeline is the
    coalescing target; off-TPU it runs the vmapped XLA form)."""
    from quda_tpu.interfaces import quda_api as api
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    monkeypatch.setenv("QUDA_TPU_METRICS", "1")
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    omet.stop(flush_files=False)
    otr.stop(flush_files=False)
    # a session another file's test left open on this xdist worker would
    # keep SolveService.start() from calling init_quda, and with it from
    # opening the metrics registry these tests read: end it here
    if api._ctx["initialized"]:
        api.end_quda()
    omem.reset()
    qconf.reset_cache()
    yield
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


def _counter(snap, name, **match):
    tot = 0.0
    for (n, labels), v in snap["counters"].items():
        lab = dict(labels)
        if n == name and all(lab.get(k) == str(v2)
                             for k, v2 in match.items()):
            tot += v
    return tot


# -- batcher units (pure logic, no jax) -------------------------------------

def test_batcher_groups_by_key_fifo_and_cap():
    pa, pb = _wilson_param(), _wilson_param(tol=1e-8)
    reqs = [batcher.SolveRequest(source=i, param=p, gauge_id=g)
            for i, (p, g) in enumerate(
                [(pa, "A"), (pa, "A"), (pb, "A"), (pa, "A"),
                 (pa, "B"), (pa, "A")])]
    groups = batcher.group(reqs, cap=3)
    shapes = [[r.source for r in g] for g in groups]
    # same (gauge, key) coalesces FIFO-stable; differing tol / gauge
    # split; the cap chunks
    assert shapes == [[0, 1, 3], [2], [4], [5]]


def test_batcher_multishift_never_batches():
    p = _wilson_param()
    p.num_offset = 2
    r1 = batcher.SolveRequest(source=0, param=p, gauge_id="A")
    r2 = batcher.SolveRequest(source=1, param=p, gauge_id="A")
    assert batcher.solve_key(r1) != batcher.solve_key(r2)
    assert [len(g) for g in batcher.group([r1, r2])] == [1, 1]


def test_batcher_key_covers_operator_fields_and_never_raises():
    """The solve key derives from EVERY non-result InvertParam field
    (an allowlist silently merges requests — and wrong-operator
    coalescing delivers the wrong solution as 'converged'), and an
    unhashable field value over-splits instead of killing the
    grouping."""
    pa = _wilson_param()
    pb = _wilson_param()
    pb.m5 = -1.0                      # operator-defining, non-listed
    ra = batcher.SolveRequest(source=0, param=pa, gauge_id="A")
    rb = batcher.SolveRequest(source=1, param=pb, gauge_id="A")
    assert batcher.solve_key(ra) != batcher.solve_key(rb)
    pc_ = _wilson_param()
    pc_.offset = np.array([0.05])     # unhashable; num_offset == 0
    rc = batcher.SolveRequest(source=2, param=pc_, gauge_id="A")
    assert batcher.solve_key(rc)      # no raise
    assert [len(g) for g in batcher.group([ra, rb, rc])] == [1, 1, 1]


def test_reregistered_gauge_is_not_served_stale():
    """load_gauge on an existing id must invalidate the cached device
    copy: the next request solves against the NEW configuration, not
    the stale one delivered as 'converged'."""
    import jax.numpy as jnp

    from quda_tpu.serve import SolveService
    svc = SolveService(batch_window_ms=0.0)
    svc.load_gauge("cfg", _unit_gauge(), _gauge_param())
    param = _wilson_param()
    svc.start()
    b = _sources(1, seed=23)[0]
    x1 = svc.submit(b, param, "cfg").result(timeout=600)
    svc.load_gauge("cfg", 0.8 * _unit_gauge(), _gauge_param())
    x2 = svc.submit(b, param, "cfg").result(timeout=600)
    assert x1.status == "converged" and x2.status == "converged"
    # different operator -> materially different solution
    rel = float(jnp.linalg.norm(jnp.ravel(x1.x - x2.x))
                / jnp.linalg.norm(jnp.ravel(x1.x)))
    assert rel > 1e-2, rel
    svc.stop()


def test_batcher_collect_drains_within_window():
    q = _queue.Queue()
    for i in range(5):
        q.put(i)
    out = batcher.collect(q, window_s=0.0)
    assert out == [0, 1, 2, 3, 4]       # already-queued items batch
    assert batcher.collect(q, window_s=0.0, poll_s=0.01) == []


def test_batcher_caps_respect_max_multi_rhs(monkeypatch):
    monkeypatch.setenv("QUDA_TPU_SERVE_MAX_BATCH", "64")
    monkeypatch.setenv("QUDA_TPU_MAX_MULTI_RHS", "4")
    qconf.reset_cache()
    assert batcher.max_batch() == 4


# -- schema pins (the Service report section keys on these) -----------------

def test_serve_schema_registrations():
    for name, kind in (
            ("serve_requests_total", osch.COUNTER),
            ("serve_batches_total", osch.COUNTER),
            ("serve_request_seconds", osch.HISTOGRAM),
            ("serve_queue_depth", osch.GAUGE),
            ("serve_gauge_hits_total", osch.COUNTER),
            ("serve_gauge_activations_total", osch.COUNTER),
            ("serve_gauge_evictions_total", osch.COUNTER),
            ("serve_availability_events_total", osch.COUNTER),
            ("serve_warm_keys", osch.GAUGE)):
        assert osch.METRICS[name]["type"] == kind, name
    for ev in ("serve_batch", "serve_gauge_evicted",
               "serve_availability", "serve_warm_start"):
        assert osch.TRACE_EVENTS[ev]["cat"] == "serve", ev


# -- coalescing: k requests -> ONE MRHS execution ---------------------------

def test_coalesced_requests_one_mrhs_execution():
    from quda_tpu.serve import SolveService
    svc = SolveService(batch_window_ms=100.0)
    svc.load_gauge("cfgA", _unit_gauge(), _gauge_param())
    param = _wilson_param()
    tickets = [svc.submit(b, param, "cfgA") for b in _sources(3)]
    svc.start()                      # pre-queued requests coalesce
    outs = [t.result(timeout=600) for t in tickets]
    for o in outs:
        assert o.status == "converged" and o.converged
        assert o.batch_size == 3
        assert o.true_res < 1e-6 * 100
        assert o.iter_count > 0
    snap = omet.snapshot()
    # THE pin: one batch, one compute-phase execution of the MRHS route
    assert _counter(snap, "executions_total",
                    api="invert_multi_src_quda") == 1
    assert _counter(snap, "serve_batches_total", size=3) == 1
    assert _counter(snap, "serve_requests_total",
                    status="converged") == 3
    svc.stop()


def test_mixed_gauge_smoke_drill(tmp_path):
    """Tier-1 smoke: N requests across two gauges, clean shutdown
    flushes artifacts through end_quda — the CI-shaped service drill."""
    from quda_tpu.serve import SolveService
    svc = SolveService(batch_window_ms=50.0)
    svc.load_gauge("cfgA", _unit_gauge(), _gauge_param())
    svc.load_gauge("cfgB", _unit_gauge(), _gauge_param())
    param = _wilson_param()
    srcs = _sources(4, seed=3)
    tickets = [svc.submit(srcs[0], param, "cfgA"),
               svc.submit(srcs[1], param, "cfgB"),
               svc.submit(srcs[2], param, "cfgA"),
               svc.submit(srcs[3], param, "cfgB")]
    svc.start()
    for t in tickets:
        assert t.result(timeout=600).status == "converged"
    svc.stop()                        # owns the session -> end_quda
    rep = open(tmp_path / "fleet_report.txt").read()
    assert "## Service (solve-service worker)" in rep
    assert "coalesced batches:" in rep
    assert "solve_seconds SLO" in rep
    assert "availability events: none" in rep
    assert "gauge cfgA:" in rep and "gauge cfgB:" in rep
    manifest = json.load(open(tmp_path / "artifacts_manifest.json"))
    arts = manifest.get("artifacts", manifest)
    assert any("fleet_report" in str(k) for k in arts)


# -- residency: ledger-driven HBM budget + LRU eviction ---------------------

def test_residency_eviction_honors_budget():
    from quda_tpu.serve import SolveService
    gauge_bytes = omem.nbytes_of(
        np.zeros((4, L, L, L, L, 3, 3), np.complex64))
    # room for 2 resident gauges, not 3
    budget_mb = (2 * gauge_bytes + gauge_bytes // 2) / 2 ** 20
    svc = SolveService(batch_window_ms=0.0, hbm_budget_mb=budget_mb)
    for gid in ("g0", "g1", "g2"):
        svc.load_gauge(gid, _unit_gauge(), _gauge_param())
    param = _wilson_param()
    svc.start()
    srcs = _sources(3, seed=5)
    for gid, b in zip(("g0", "g1", "g2"), srcs):
        assert svc.submit(b, param, gid).result(
            timeout=600).status == "converged"
    svc.drain(timeout=600)
    # the ledger's gauge family obeys the budget; somebody was evicted
    assert omem.family_bytes()["gauge"] <= int(budget_mb * 2 ** 20)
    assert len(svc.residency.resident_ids()) <= 2
    snap = omet.snapshot()
    assert _counter(snap, "serve_gauge_evictions_total") >= 1
    # family high-water keeps the peak signal (>= 2 gauges resident at
    # some point), untouched by eviction
    assert omem.high_water()["gauge"] >= 2 * gauge_bytes
    # an evicted gauge reloads transparently from the retained host
    # copy: g0 was the LRU victim, and still serves
    out = svc.submit(srcs[0], param, "g0").result(timeout=600)
    assert out.status == "converged"
    svc.stop()


def test_residency_activation_vs_hit_counters():
    from quda_tpu.serve import SolveService
    svc = SolveService(batch_window_ms=0.0)
    svc.load_gauge("gA", _unit_gauge(), _gauge_param())
    svc.load_gauge("gB", _unit_gauge(), _gauge_param())
    param = _wilson_param()
    svc.start()
    b = _sources(1, seed=7)[0]
    svc.submit(b, param, "gA").result(timeout=600)   # load (activation)
    svc.submit(b, param, "gA").result(timeout=600)   # hit
    svc.submit(b, param, "gB").result(timeout=600)   # load (activation)
    svc.submit(b, param, "gA").result(timeout=600)   # switch back
    snap = omet.snapshot()
    assert _counter(snap, "serve_gauge_hits_total", gauge="gA") == 1
    assert _counter(snap, "serve_gauge_activations_total",
                    gauge="gA") == 2
    assert _counter(snap, "serve_gauge_activations_total",
                    gauge="gB") == 1
    svc.stop()


def test_residency_stashes_restores_and_evicts_mg_per_gauge():
    """Round-15 headroom item: a resident MG hierarchy rides its gauge
    through the residency table — stashed on switch (ledger row moves
    hierarchy -> serve:<id>), restored warm on re-activation, and its
    ledger rows dropped when the gauge is evicted (a reload rebuilds
    lazily)."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.serve.residency import GaugeResidency
    api.init_quda()
    res = GaugeResidency()
    res.ensure_active("gA",
                      loader=lambda: (_unit_gauge(), _gauge_param()))

    class _FakeMG:                    # hierarchy stand-in with arrays
        def __init__(self):
            self.v = np.ones((64, 2), np.float32)

    mg_a = _FakeMG()
    api._install_resident_mg(mg_a)
    assert api.resident_mg_state() is mg_a
    mg_bytes = omem.family_bytes().get("mg", 0)
    assert mg_bytes > 0                          # one ledger row

    # switching gauges stashes the hierarchy next to its gauge
    res.ensure_active("gB",
                      loader=lambda: (_unit_gauge(), _gauge_param()))
    assert api.resident_mg_state() is None       # gB has no hierarchy
    assert omem.family_bytes().get("mg", 0) == mg_bytes  # row moved

    # re-activating gA restores the SAME warm hierarchy (no rebuild)
    assert res.ensure_active("gA") == "activated"
    assert api.resident_mg_state() is mg_a
    assert omem.family_bytes().get("mg", 0) == mg_bytes

    # evicting the gauge drops the hierarchy's ledger rows with it
    res.ensure_active("gB")
    assert res.evict("gA", budget_eviction=False)
    assert omem.family_bytes().get("mg", 0) == 0


def test_stale_hierarchy_is_dropped_not_restashed():
    """If the gauge mutates while active (epoch bump: smear/HMC), its
    hierarchy is retired by the epoch guard — the switch must DROP it
    (ledger row included), and a later re-activation must not restore
    it as valid (the silent wrong-preconditioner case)."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.serve.residency import GaugeResidency
    api.init_quda()
    res = GaugeResidency()
    res.ensure_active("gA",
                      loader=lambda: (_unit_gauge(), _gauge_param()))

    class _FakeMG:
        def __init__(self):
            self.v = np.ones((16,), np.float32)

    api._install_resident_mg(_FakeMG())
    api._ctx["gauge_epoch"] += 1          # the gauge mutated under us
    assert api.resident_mg_state() is None
    res.ensure_active("gB",
                      loader=lambda: (_unit_gauge(), _gauge_param()))
    assert omem.family_bytes().get("mg", 0) == 0     # dropped, not kept
    assert res.ensure_active("gA") == "activated"
    assert api.resident_mg_state() is None           # no stale restore


def test_budget_counts_stashed_hierarchies():
    """The HBM budget decision reads gauges + hierarchies: a stashed
    per-gauge hierarchy big enough to blow the budget evicts its (LRU)
    gauge even though the gauge family alone fits."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.serve.residency import GaugeResidency
    api.init_quda()
    res = GaugeResidency(budget_mb=0.5)      # two L=4 gauges fit easily
    res.ensure_active("gA",
                      loader=lambda: (_unit_gauge(), _gauge_param()))

    class _BigMG:
        def __init__(self):
            self.v = np.ones((1 << 20,), np.float32)     # 4 MB

    api._install_resident_mg(_BigMG())
    res.ensure_active("gB",
                      loader=lambda: (_unit_gauge(), _gauge_param()))
    # stash(gA + 4MB hierarchy) then load gB -> ensure_budget sees
    # resident_bytes > budget and evicts gA, hierarchy rows included
    assert "gA" not in res.resident_ids()
    assert omem.family_bytes().get("mg", 0) == 0
    assert res.resident_bytes() <= res.budget_bytes()


def test_resident_mg_state_never_serves_stale_hierarchy():
    """A gauge reload bumps the epoch: the old hierarchy must read as
    absent (a stale one silently degrades to a wrong preconditioner)."""
    from quda_tpu.interfaces import quda_api as api
    api.init_quda()
    api.load_gauge_quda(_unit_gauge(), _gauge_param())

    class _FakeMG:
        def __init__(self):
            self.v = np.ones((8,), np.float32)

    api._install_resident_mg(_FakeMG())
    assert api.resident_mg_state() is not None
    api.load_gauge_quda(_unit_gauge(), _gauge_param())   # epoch bump
    assert api.resident_mg_state() is None


# -- cross-process warm start ------------------------------------------------

# 73 s alone, 93 s in a full run (PR 25): two service sessions, each
# compiling its MRHS solve; L=4, 2 gauges, 2 batches already
@pytest.mark.slow
def test_acceptance_two_workers_warm_start(tmp_path, monkeypatch):
    """The ISSUE-12 acceptance drill end to end.  Worker session A
    serves coalesced MRHS batches against 2 resident gauges under a
    ledger-bounded residency budget and persists its executable-key
    index + tunecache + compilation cache; a fresh worker session B
    under the same resource path records compiles_total == 0 for the
    already-keyed (api, form, shape, dtype, solver) executables while
    executions_total advances, and its fleet_report.txt carries the
    Service section with batch/SLO/availability rows."""
    from quda_tpu.serve import SolveService
    # the cache is placed from OUTSIDE: with JAX_COMPILATION_CACHE_DIR
    # set, no code path may re-point jax_compilation_cache_dir
    cache_dir = tmp_path / "jax_compilation_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    jax_dir_before = jax.config.jax_compilation_cache_dir
    param = _wilson_param()
    gauge_bytes = omem.nbytes_of(
        np.zeros((4, L, L, L, L, 3, 3), np.complex64))
    budget_mb = 2.5 * gauge_bytes / 2 ** 20     # room for 2 residents

    svc = SolveService(batch_window_ms=100.0, hbm_budget_mb=budget_mb)
    svc.load_gauge("cfgA", _unit_gauge(), _gauge_param())
    svc.load_gauge("cfgB", _unit_gauge(), _gauge_param())
    srcs = _sources(4, seed=9)
    tickets = [svc.submit(srcs[0], param, "cfgA"),
               svc.submit(srcs[1], param, "cfgB"),
               svc.submit(srcs[2], param, "cfgA"),
               svc.submit(srcs[3], param, "cfgB")]
    svc.start()                       # pre-queued -> 2 batches of 2
    for t in tickets:
        out = t.result(timeout=600)
        assert out.status == "converged" and out.batch_size == 2
    snap_a = omet.snapshot()
    assert _counter(snap_a, "serve_batches_total", size=2) == 2
    # ledger-bounded residency: both gauges resident, budget honored
    assert omem.family_bytes()["gauge"] <= int(budget_mb * 2 ** 20)
    assert len(svc.residency.resident_ids()) == 2
    svc.stop()                        # persists executable_keys.json
    keys_file = tmp_path / "executable_keys.json"
    saved = json.load(open(keys_file))
    assert any(saved.values())
    # the warm start reports the externally placed cache directory and
    # set nothing in code (jax reads the variable itself at start-up;
    # without it the fixed <checkout>/.jax_cache is wired instead)
    assert svc.warm["cache_dir"] == str(cache_dir)
    assert jax.config.jax_compilation_cache_dir == jax_dir_before
    from quda_tpu.utils import compile_cache as qcc
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert qcc.compile_cache_dir() == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))

    # "worker process B": the metrics session (and its seen-key set)
    # is gone with end_quda above; a fresh service session under the
    # same resource path warm-starts from disk (in-process stand-in
    # for a second OS process — the seen-key registry and metrics
    # session it warm-starts are exactly the per-process state)
    assert not omet.enabled()
    qconf.reset_cache()
    svc_b = SolveService(batch_window_ms=100.0)
    svc_b.load_gauge("cfgA", _unit_gauge(), _gauge_param())
    svc_b.load_gauge("cfgB", _unit_gauge(), _gauge_param())
    tickets = [svc_b.submit(srcs[0], param, "cfgA"),
               svc_b.submit(srcs[1], param, "cfgB"),
               svc_b.submit(srcs[2], param, "cfgA"),
               svc_b.submit(srcs[3], param, "cfgB")]
    svc_b.start()
    assert svc_b.warm["keys_seeded"] >= 1
    for t in tickets:
        assert t.result(timeout=600).status == "converged"
    snap = omet.snapshot()
    # the acceptance instrument: zero compiles for the already-keyed
    # executables, executions advance
    assert _counter(snap, "compiles_total") == 0
    assert _counter(snap, "executions_total",
                    api="invert_multi_src_quda") == 2
    svc_b.stop()
    rep = open(tmp_path / "fleet_report.txt").read()
    assert "## Service (solve-service worker)" in rep
    assert "coalesced batches: n=2 x2" in rep
    assert "solve_seconds SLO [wilson]" in rep
    assert "availability events: none" in rep


# -- availability: faults become events, not crashes ------------------------

def test_fault_injected_request_is_availability_event(monkeypatch):
    """A fault-injected request (inflated verified residual under
    QUDA_TPU_ROBUST=verify) lands as an 'unverified' availability
    event on its ticket and in the counters; the worker survives and
    the next request (fault disarmed — one-shot) converges."""
    from quda_tpu.robust import faultinject as finj
    from quda_tpu.serve import SolveService
    monkeypatch.setenv("QUDA_TPU_ROBUST", "verify")
    monkeypatch.setenv("QUDA_TPU_FAULT", "residual:1e6")
    qconf.reset_cache()
    finj.reset()                  # re-parse the env spec (one-shot arms)
    svc = SolveService(batch_window_ms=0.0)
    svc.load_gauge("cfg", _unit_gauge(), _gauge_param())
    param = _wilson_param()
    svc.start()
    b = _sources(1, seed=11)[0]
    out = svc.submit(b, param, "cfg").result(timeout=600)
    assert out.status == "unverified" and not out.converged
    # worker alive; the one-shot fault disarmed
    out2 = svc.submit(b, param, "cfg").result(timeout=600)
    assert out2.status == "converged"
    snap = omet.snapshot()
    assert _counter(snap, "serve_availability_events_total",
                    kind="unverified") == 1
    svc.stop()
    finj.reset()


def test_multishift_singleton_routes_to_multishift_api():
    """A multishift request never batches (unique solve key) and must
    dispatch to invert_multishift_quda — not invert_quda, which
    refuses num_offset > 0.  The outcome's x is the stacked per-shift
    solution batch."""
    from quda_tpu.interfaces.params import InvertParam
    from quda_tpu.serve import SolveService
    shifts = (0.05, 0.1)
    p = InvertParam(dslash_type="wilson", kappa=0.12,
                    inv_type="multi-shift-cg", solve_type="normop-pc",
                    cuda_prec="single", cuda_prec_sloppy="single",
                    tol=1e-6, maxiter=500, num_offset=len(shifts),
                    offset=shifts)
    svc = SolveService(batch_window_ms=0.0)
    svc.load_gauge("cfg", _unit_gauge(), _gauge_param())
    svc.start()
    out = svc.submit(_sources(1, seed=19)[0], p, "cfg").result(
        timeout=600)
    assert out.status == "converged"
    assert out.batch_size == 1
    assert out.x.shape[0] == len(shifts)
    svc.stop()


def test_stop_serves_requests_stranded_by_shutdown_race():
    """A submit racing stop() can enqueue after the worker's final
    empty-queue check; stop() must serve the straggler on the calling
    thread so the ticket is delivered, never stranded (the delivery
    contract).  The race is forced deterministically: the worker is
    told to stop and joined while the service still looks running, the
    request lands in the dead worker's queue, then stop() runs."""
    from quda_tpu.serve import SolveService
    svc = SolveService(batch_window_ms=0.0)
    svc.load_gauge("cfg", _unit_gauge(), _gauge_param())
    svc.start()
    svc._stop.set()
    svc._thread.join()               # worker exits on its idle poll
    t = svc.submit(_sources(1, seed=17)[0], _wilson_param(), "cfg")
    assert not t.done()              # stranded: nobody is draining
    svc.stop()
    assert t.result(timeout=60).status == "converged"


def test_raising_request_fails_ticket_not_worker():
    """An execution that raises (unregistered gauge id reaching the
    residency manager) delivers status='failed' + error on the ticket
    and counts a 'failed' availability event; the worker keeps
    serving."""
    from quda_tpu.serve import SolveService
    svc = SolveService(batch_window_ms=0.0)
    svc.load_gauge("ok", _unit_gauge(), _gauge_param())
    param = _wilson_param()
    # sabotage BEFORE the worker starts (deterministic): registered at
    # submit time, vanished by execution time
    svc.load_gauge("ghost", _unit_gauge(), _gauge_param())
    t = svc.submit(_sources(1)[0], param, "ghost")
    svc._gauges.pop("ghost")
    svc.start()
    out = t.result(timeout=600)
    assert out.status == "failed" and out.error
    out2 = svc.submit(_sources(1, seed=13)[0], param, "ok").result(
        timeout=600)
    assert out2.status == "converged"
    snap = omet.snapshot()
    assert _counter(snap, "serve_availability_events_total",
                    kind="failed") == 1
    svc.stop()
