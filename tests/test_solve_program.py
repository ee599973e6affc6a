"""The cached solve program (solvers/program.py): the solver loop is
traced once per process per key, and everything that differs from call
to call is an operand.

Every count is read from the ``solve_program_total`` counter the
mechanism itself reports (obs/metrics).  4 x 8 x 2 x 4 (the smallest
lattice the packed pair kernels' full-Z route takes), interpret-mode
kernels, through ``invert_quda`` (mixed f32/bf16 reliable CG on the
Wilson packed pair operator) and ``invert_multi_src_quda`` (the f32
batched-pairs route).  A miss costs the CPU 40-60 s, whatever the
lattice: ten seconds of lowering for every interpreted kernel in the
program, and XLA's compile.  So the first call of each route is made
once per worker, in a fixture, and what does not need the kernels runs
on the XLA pair stencil: the comparison with the eager solver and the
operand's own tests at 4^4, and the batched route's flipped knobs
through the same API call with ``QUDA_TPU_PALLAS=0`` (a miss there is
XLA's 20 s and no kernel's lowering; ``invert_quda``'s Wilson route off
the kernels is the eager solver and reaches no program, so its flips
stay on the kernels).

Both routes solve on the resident Wilson pair operators
(``wilson_term_total``) and leave through the verified-exit program
(``solve_program_total`` with solver ``verified-exit``): their API
cases are here because they need the same warm-up (the program and the
term by themselves: tests/test_wilson_resident.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.obs import metrics as omet
from quda_tpu.obs import trace as otr
from quda_tpu.robust import faultinject as finj
from quda_tpu.solvers import program as sprog
from quda_tpu.utils import config as qconf
from tests.host_reference.wilson_ref import wilson_mat_ref

DIMS = (4, 2, 8, 4)                     # (x, y, z, t)
LAT = tuple(reversed(DIMS))             # array order (T, Z, Y, X)
HALF = LAT[:2] + (LAT[2] * LAT[3] // 2,)
KAPPA = 0.12
ROUTES = ("single", "multi")
KEY = {"single": dict(api="invert_quda", form="wilson_v2", solver="cg"),
       "multi": dict(api="invert_multi_src_quda",
                     form="wilson_batched_pairs",
                     solver="batched-cg-pairs")}


def _gauge(seed):
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.geometry import LatticeGeometry
    g = GaugeField.random(jax.random.PRNGKey(seed),
                          LatticeGeometry(DIMS))
    return np.asarray(g.data.astype(jnp.complex64))


def _sources(seed, n):
    rng = np.random.default_rng(seed)
    shape = (n,) + LAT + (4, 3)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _param(kappa=KAPPA):
    return InvertParam(dslash_type="wilson", inv_type="cg",
                       solve_type="normop-pc", kappa=kappa, tol=1e-6,
                       maxiter=500, cuda_prec="single",
                       cuda_prec_sloppy="half")


def _solve(route, seed, kappa=KAPPA):
    """One API call on sources drawn from ``seed``: (sources, solutions,
    param), each with a leading source axis."""
    p = _param(kappa)
    if route == "single":
        b = _sources(seed, 1)
        return b, np.asarray(api.invert_quda(b[0], p))[None], p
    b = _sources(seed, 2)
    return b, np.asarray(api.invert_multi_src_quda(b, p)), p


def _counts(route, solver=None):
    """(misses, hits) of the route's solve program so far, or of its
    program ``solver`` (the verified exit)."""
    want = dict(KEY[route], **({"solver": solver} if solver else {}))
    out = {"miss": 0, "hit": 0}
    for (name, labels), v in omet.snapshot()["counters"].items():
        lab = dict(labels)
        if name == "solve_program_total" and all(
                lab[k] == w for k, w in want.items()):
            out[lab["outcome"]] += int(v)
    return out["miss"], out["hit"]


def _delta(route, before, solver=None):
    m, h = _counts(route, solver)
    return m - before[0], h - before[1]


EXIT = "verified-exit"


def _term_counts():
    """{outcome: count} of the resident Wilson term so far."""
    out = {"built": 0, "reused": 0, "rebuilt": 0}
    for (name, labels), v in omet.snapshot()["counters"].items():
        if name == "wilson_term_total":
            out[dict(labels)["outcome"]] += int(v)
    return out


def _term_delta(before):
    return {k: v - before[k] for k, v in _term_counts().items()
            if v != before[k]}


def _host_residual(gauge, b, x, kappa):
    r = b - wilson_mat_ref(gauge.astype(np.complex128),
                           x.astype(np.complex128), kappa)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def _load(gauge):
    api.load_gauge_quda(gauge, GaugeParam(X=DIMS, cuda_prec="single"))


@pytest.fixture(scope="module")
def gauges():
    return {"A": _gauge(11), "B": _gauge(12)}


@pytest.fixture(scope="module")
def quda(gauges, tmp_path_factory):
    """init + resident gauge A + a metrics session, with the
    interpret-mode pallas pair route selected (the TPU default)."""
    mp = pytest.MonkeyPatch()
    mp.setenv("QUDA_TPU_PACKED", "1")
    mp.setenv("QUDA_TPU_PALLAS", "1")
    # conftest's eight virtual devices would take a batch of two to the
    # split-grid route: the batched route is the one-chip route
    mp.setenv("QUDA_TPU_MULTI_SRC_SPLIT", "0")
    for knob in ("QUDA_TPU_ROBUST", "QUDA_TPU_FAULT", "QUDA_TPU_TRACE",
                 "QUDA_TPU_CG_CHECK_EVERY"):
        mp.delenv(knob, raising=False)
    qconf.reset_cache()
    finj.reset()
    otr.stop(flush_files=False)
    api.init_quda()
    omet.start(str(tmp_path_factory.mktemp("solve_program")))
    _load(gauges["A"])
    yield
    omet.stop(flush_files=False)
    api.end_quda()
    mp.undo()
    qconf.reset_cache()


@pytest.fixture(scope="module")
def first_calls(quda):
    return {}


@pytest.fixture
def warm(route, first_calls):
    """The (misses, hits) that the worker's first call of ``route``
    added; made here, in set-up, once per worker.  Later calls with the
    same key must all be hits."""
    if route not in first_calls:
        before, before_exit = _counts(route), _counts(route, EXIT)
        _solve(route, seed=1)
        first_calls[route] = _delta(route, before)
        first_calls[route, EXIT] = _delta(route, before_exit, EXIT)
    return first_calls[route]


@pytest.fixture
def knobs(monkeypatch):
    """Set or clear knobs for one test; everything is put back (the
    fault registry disarmed, the kernel route's resident term built
    again if the test left the route) after it."""
    def set_(**env):
        for k, v in env.items():
            if v is None:
                monkeypatch.delenv(k, raising=False)
            else:
                monkeypatch.setenv(k, v)
        qconf.reset_cache()
        finj.reset()
    yield set_
    monkeypatch.undo()
    qconf.reset_cache()
    finj.reset()
    otr.stop(flush_files=False)
    api._resident_wilson(_param())


# (a), (d): sources and links are operands ------------------------------------

@pytest.mark.parametrize("route", ROUTES)
def test_new_source_and_new_gauge_reuse_the_program(route, warm, gauges):
    assert sum(warm) == 1         # at most the process's one trace
    before, before_exit = _counts(route), _counts(route, EXIT)
    b2, x2, p2 = _solve(route, seed=2)
    try:
        _load(gauges["B"])
        terms = _term_counts()
        b3, x3, p3 = _solve(route, seed=3)
    finally:
        _load(gauges["A"])
    assert _delta(route, before) == (0, 2)
    assert _delta(route, before_exit, EXIT) == (0, 2)
    # the resident term went with gauge A: the second call built its own
    assert _term_delta(terms) == {"built": 1}
    # each call returned the solution of ITS gauge, not of the links the
    # program was traced with or of a term that outlived its gauge
    for i in range(len(b2)):
        assert _host_residual(gauges["A"], b2[i], x2[i], KAPPA) < 5e-6
        assert _host_residual(gauges["B"], b3[i], x3[i], KAPPA) < 5e-6
    assert _host_residual(gauges["A"], b3[0], x3[0], KAPPA) > 1e-2
    assert p2.converged and p3.converged


# (b), (d): kappa is an operand ----------------------------------------------

@pytest.mark.parametrize("route", ROUTES)
def test_second_kappa_reuses_the_program(route, warm, gauges):
    before, before_exit = _counts(route), _counts(route, EXIT)
    api._resident_wilson(_param())  # KAPPA's, if the last test loaded anew
    terms = _term_counts()
    b, x, p = _solve(route, seed=4, kappa=0.105)
    assert _delta(route, before) == (0, 1)
    assert _delta(route, before_exit, EXIT) == (0, 1)
    assert _term_delta(terms) == {"reused": 1}      # kappa: a leaf
    assert p.converged
    assert _host_residual(gauges["A"], b[0], x[0], 0.105) < 5e-6
    assert _host_residual(gauges["A"], b[0], x[0], KAPPA) > 1e-3


def _mrhs_route_counts():
    """{(route, epilogue, reduce): traced MRHS kernels so far}."""
    out = {}
    for (name, labels), v in omet.snapshot()["counters"].items():
        if name == "wilson_mrhs_route_total":
            lab = dict(labels)
            out[lab["route"], lab["epilogue"], lab["reduce"]] = int(v)
    return out


def _mrhs_route_delta(before):
    after = _mrhs_route_counts()
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


@pytest.mark.parametrize("method,n", [("MdagM_pairs_mrhs", 5),
                                      ("MdagM_cg_step_pairs_mrhs", 6),
                                      ("program", 7)])
def test_batched_route_operator_combines_in_its_second_hops(quda, method,
                                                            n):
    """The batched route's resident operator hands its program ``Ap``
    and not the bare hop sums: tracing ``MdagM`` on a batch of a size
    this process has not traced traces two kernels, the first hop bare
    and the second with the combine epilogue
    (``wilson_mrhs_route_total``; the counter counts a kernel where it
    is traced, and the second ``M``'s hops are the first's traces);
    kappa is an operand of that epilogue, which is why the second kappa
    above is a hit.  That epilogue sums the squares of what it stores
    (``reduce="norm2"``, once per traced fused hop).  What the
    program's loop applies, ``MdagM_cg_step``, takes ``pAp`` from those
    sums and makes the LAST of its four hops (two bare: one trace, one
    combine, one residual) write ``r - alpha A p`` and sum it: one
    kernel more, the residual form, and that is all a whole solve
    program traces."""
    op = api._resident_wilson(_param())["ops"][jnp.dtype(jnp.float32)]
    op = op.with_kappa(KAPPA)
    before = _mrhs_route_counts()
    batch = jax.ShapeDtypeStruct((n, 4, 3, 2) + HALF, jnp.float32)
    lanes = jax.ShapeDtypeStruct((n,), jnp.float32)
    want = {("fullz", "none", "none"): 1, ("fullz", "combine", "norm2"): 1}
    if method == "MdagM_pairs_mrhs":
        out = jax.eval_shape(op.MdagM_pairs_mrhs, batch)
    elif method == "MdagM_cg_step_pairs_mrhs":
        out, *scalars = jax.eval_shape(op.MdagM_cg_step_pairs_mrhs, batch,
                                       batch, lanes)
        assert [(v.shape, v.dtype) for v in scalars] == [
            ((n,), jnp.float32)] * 3
        want["fullz", "residual", "norm2"] = 1
    else:
        key = (1, sprog._LoopKnobs(False, None, None, None), False)
        out = jax.eval_shape(
            lambda o, b: sprog._batched_cg_pairs_program(
                o, b, 1e-6, 500, key=key), op, batch).x
        want["fullz", "residual", "norm2"] = 1
    assert (out.shape, out.dtype) == (batch.shape, batch.dtype)
    assert _mrhs_route_delta(before) == want


@pytest.mark.parametrize("route", ["multi"])
def test_second_kappa_and_batch_are_a_hit_of_the_batched_program(
        route, warm):
    """``solvers/program.batched_cg_pairs`` itself, on the resident
    operator at another kappa and on another batch than the worker's
    first call of the route: a hit (``alpha`` and ``coeff`` are operands
    of the residual hop, not constants of the program), and the solution
    of THAT system."""
    op = api._resident_wilson(_param())["ops"][jnp.dtype(jnp.float32)]
    other = op.with_kappa(0.105)
    b = jnp.asarray(np.random.default_rng(21).standard_normal(
        (2, 4, 3, 2) + HALF), jnp.float32)
    before = _mrhs_route_counts()
    res, hit = sprog.batched_cg_pairs(other, b, tol=1e-6, maxiter=500)
    assert hit and _mrhs_route_delta(before) == {}
    assert bool(jnp.all(res.converged))
    for kappa, small in ((0.105, True), (KAPPA, False)):
        r = b - op.with_kappa(kappa).MdagM_pairs_mrhs(res.x)
        rel = float(jnp.sqrt(jnp.sum(r * r) / jnp.sum(b * b)))
        assert (rel < 5e-6) == small, (kappa, rel)


@pytest.mark.parametrize("route", ["multi"])
def test_kernel_pAp_stops_the_batched_solve_where_the_dot_does(
        route, warm, knobs, gauges):
    """The batched API call on the kernel route (``pAp`` out of the
    second hop's epilogue, ``r`` and ``|r|^2`` out of the last's)
    against the same call on the XLA stencil (``block.cg_step``: XLA's
    dot, update and sum over the batch): both converge to
    tol, source by source within two iterations of each other, and the
    kernel route's call is a hit of the program the worker's first call
    traced."""
    before = _counts(route)
    b, x, p = _solve(route, seed=12)
    assert _delta(route, before) == (0, 1) and p.converged
    iters = list(p.iter_count_multi)
    knobs(QUDA_TPU_PALLAS="0")
    _, x_dot, p_dot = _solve(route, seed=12)
    assert p_dot.converged
    assert all(abs(i - j) <= 2
               for i, j in zip(iters, p_dot.iter_count_multi))
    for i in range(len(b)):
        assert _host_residual(gauges["A"], b[i], x[i], KAPPA) < 5e-6
        assert _host_residual(gauges["A"], b[i], x_dot[i], KAPPA) < 5e-6


# the resident term and the verified exit, through the API -----------------

@pytest.mark.parametrize("route", ROUTES)
def test_load_then_two_solves_build_once_and_trace_the_exit_once(
        route, warm, first_calls, gauges):
    assert first_calls[route, EXIT] == (1, 0)
    _load(gauges["A"])
    assert api._ctx["wilson"] is None
    before_exit, terms = _counts(route, EXIT), _term_counts()
    for seed in (7, 8):
        b, x, p = _solve(route, seed=seed)
        assert p.converged
        for i in range(len(b)):
            assert _host_residual(gauges["A"], b[i], x[i], KAPPA) < 5e-6
    assert _term_delta(terms) == {"built": 1, "reused": 1}
    assert _delta(route, before_exit, EXIT) == (0, 2)


@pytest.mark.parametrize("route", ROUTES)
def test_free_gauge_then_a_new_one_solves_the_new_system(route, warm,
                                                         gauges):
    terms = _term_counts()
    try:
        api.free_gauge_quda()
        assert api._ctx["wilson"] is None
        _load(gauges["B"])
        b, x, p = _solve(route, seed=9)
    finally:
        _load(gauges["A"])
    assert _term_delta(terms) == {"built": 1}
    assert p.converged
    assert _host_residual(gauges["B"], b[0], x[0], KAPPA) < 5e-6
    assert _host_residual(gauges["A"], b[0], x[0], KAPPA) > 1e-2


@pytest.mark.parametrize("route", ROUTES)
def test_no_canonical_wilson_operator_is_built_on_the_route(
        route, warm, gauges, monkeypatch):
    from quda_tpu.models import wilson as mwil

    def refuse(self, *a, **k):
        raise AssertionError(f"{type(self).__name__} built on the route")
    for cls in (mwil.DiracWilson, mwil.DiracWilsonPC,
                mwil.DiracWilsonPCPacked):
        monkeypatch.setattr(cls, "__init__", refuse)
    _load(gauges["A"])          # the term too is built without them
    b, x, p = _solve(route, seed=10)
    monkeypatch.undo()
    assert p.converged
    assert _host_residual(gauges["A"], b[0], x[0], KAPPA) < 5e-6


@pytest.mark.parametrize("route", ROUTES)
def test_true_res_is_that_of_what_is_returned(route, warm, gauges):
    """A solve cut short returns a poor solution and says so: the
    reported residual is the one the host computes of the returned
    field, whatever the solver's recurrence believed."""
    p = _param()
    p.maxiter = 3
    b = _sources(11, 1 if route == "single" else 2)
    if route == "single":
        x = np.asarray(api.invert_quda(b[0], p))[None]
        reported = [p.true_res]
    else:
        x = np.asarray(api.invert_multi_src_quda(b, p))
        reported = p.true_res_multi
    assert not p.converged
    for i in range(len(b)):
        want = _host_residual(gauges["A"], b[i], x[i], KAPPA)
        assert want > 1e-3
        assert abs(reported[i] - want) < 1e-4 * want


# (c), (d): what changes the traced loop is in the key ---------------------

FLIPS = {
    # what the loop reads -> the environment that flips it ("record" is
    # a trace session, started in the test)
    "check_every": {"QUDA_TPU_CG_CHECK_EVERY": "2"},
    "robust": {"QUDA_TPU_ROBUST": "verify"},
    "fault": {"QUDA_TPU_FAULT": "dslash:3"},
    "robust+fault": {"QUDA_TPU_ROBUST": "verify",
                     "QUDA_TPU_FAULT": "dslash:3"},
    "record": {},
}


# Sentinel, fault and record are resolved by one function for both
# programs (program._loop_knobs), and a miss on the single-source route
# compiles twice as long: there the sentinel and the fault flip
# together, and one at a time on the batched route.  What is tested is
# the key, not a kernel: the batched route's calls run on the XLA pair
# stencil (the same API call reaches the same program with
# ``use_pallas`` False in the operator's signature; invert_quda's
# Wilson route off the kernels reaches no program).
@pytest.mark.parametrize("route,flip", [
    # 50 s each alone on the interpreted kernels: the single-source
    # program has no flip of its own (its codec is keyed by the storage
    # dtype alone since PR 45); its sentinel, fault and record stay in
    # tier 1 with the batched cases, and that no form knob moves its
    # key is tests/test_ks_resident.py's
    pytest.param("single", "robust+fault", marks=pytest.mark.slow),
    pytest.param("single", "record", marks=pytest.mark.slow),
    ("multi", "check_every"), ("multi", "robust"), ("multi", "fault"),
    ("multi", "record")])
def test_a_flipped_knob_is_a_miss_and_back_a_hit(route, flip, warm, knobs,
                                                 first_calls, tmp_path):
    env = FLIPS[flip]
    if route == "multi":
        knobs(QUDA_TPU_PALLAS="0")
        if "xla" not in first_calls:    # the worker's first call there
            _solve(route, seed=1)
            first_calls["xla"] = True
    before = _counts(route)
    knobs(**env)
    if flip == "record":
        otr.start(str(tmp_path))
    _, _, p = _solve(route, seed=5)
    assert _delta(route, before) == (1, 0), "a stale program served it"
    if "fault" in flip:
        # the program that was traced holds the fault: the solve broke
        assert finj.fired("dslash") and not p.converged
        if "robust" in flip:     # ... and its sentinel said so
            assert p.solve_status == "breakdown:nonfinite"
    elif flip == "record":
        assert len(p.res_history) > 0
        spans = [e for e in otr._session.jsonl
                 if e.get("kind") == "span"
                 and e["name"].startswith("solve:")]
        assert spans[-1]["program"] == "miss"
        otr.stop(flush_files=False)
    else:
        assert p.converged
    knobs(**{k: None for k in env})
    _, _, p = _solve(route, seed=6)
    assert _delta(route, before) == (1, 1)
    assert p.converged


# (e): the cached program is the eager solver ------------------------------

@pytest.mark.parametrize("route", ROUTES)
def test_cached_program_equals_the_eager_solver(route):
    """Same operators, same right-hand side, through the program and
    through the eager solver function (on the XLA pair stencil at 4^4:
    what is compared is the program, not the kernels)."""
    from quda_tpu.solvers import batched_cg_pairs, cg_reliable
    from quda_tpu.solvers.mixed import pair_inplace_codec
    dpk = _packed(3, KAPPA)
    hi, lo = dpk.pairs(jnp.float32), dpk.pairs(jnp.bfloat16)
    rng = np.random.default_rng(8)
    kw = dict(tol=1e-5, maxiter=300)
    if route == "single":
        b = jnp.asarray(rng.standard_normal((4, 3, 2, 4, 4, 8)),
                        jnp.float32)
        cached, _ = sprog.cg_reliable(hi, lo, b, delta=0.1, **kw)
        eager = cg_reliable(hi.MdagM_pairs, lo.MdagM_pairs, b, delta=0.1,
                            codec=pair_inplace_codec(jnp.bfloat16), **kw)
    else:
        b = jnp.asarray(rng.standard_normal((2, 4, 3, 2, 4, 4, 8)),
                        jnp.float32)
        cached, _ = sprog.batched_cg_pairs(hi, b, **kw)
        eager = batched_cg_pairs(hi.MdagM_pairs_mrhs, b, **kw)
    assert np.all(np.asarray(cached.converged))
    np.testing.assert_array_equal(np.asarray(cached.iters),
                                  np.asarray(eager.iters))
    np.testing.assert_allclose(np.asarray(cached.r2),
                               np.asarray(eager.r2), rtol=1e-3)
    np.testing.assert_allclose(np.asarray(cached.x), np.asarray(eager.x),
                               rtol=0, atol=1e-5 * float(
                                   jnp.max(jnp.abs(eager.x))))


def test_hermitian_batched_program_applies_m_once_an_iteration(
        monkeypatch):
    """The batched program on an operator that says it is ``hermitian``
    (the improved-staggered PC operator): ``M_pairs_mrhs`` in the loop,
    two hops an iteration and no normal equations, iteration for
    iteration the eager ``batched_cg_pairs`` on the same operator."""
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.staggered import (DiracStaggeredPC,
                                           DiracStaggeredPCPairs)
    from quda_tpu.solvers import batched_cg_pairs
    geom = LatticeGeometry((4,) * 4)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    fat = GaugeField.random(k1, geom).data.astype(jnp.complex64)
    lng = (0.1 * GaugeField.random(k2, geom).data).astype(jnp.complex64)
    op = DiracStaggeredPC(fat, geom, 0.1, improved=True,
                          long_links=lng).pairs(jnp.float32)
    assert op.hermitian and sprog.presents(op)
    # no step of its own: the program lifts M_pairs_mrhs (block.cg_step)
    assert not hasattr(op, "M_cg_step_pairs_mrhs")
    hops = []
    d_to = DiracStaggeredPCPairs._d_to_mrhs
    monkeypatch.setattr(
        DiracStaggeredPCPairs, "_d_to_mrhs",
        lambda self, *a, **k: hops.append(1) or d_to(self, *a, **k))
    b = jnp.asarray(np.random.default_rng(9).standard_normal(
        (3, 3, 2, 4, 4, 8)), jnp.float32)
    kw = dict(tol=1e-6, maxiter=500)
    cached, hit = sprog.batched_cg_pairs(op, b, **kw)
    assert not hit and len(hops) == 2       # one M in the traced loop
    _, hit = sprog.batched_cg_pairs(op.with_mass(0.2), 2.0 * b, **kw)
    assert hit and len(hops) == 2
    eager = batched_cg_pairs(op.M_pairs_mrhs, b, **kw)
    assert np.all(np.asarray(cached.converged))
    np.testing.assert_array_equal(np.asarray(cached.iters),
                                  np.asarray(eager.iters))
    np.testing.assert_allclose(np.asarray(cached.x), np.asarray(eager.x),
                               rtol=0, atol=1e-5 * float(
                                   jnp.max(jnp.abs(eager.x))))


# the operand itself ------------------------------------------------------

def _packed(seed, kappa, lat=4):
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.wilson import DiracWilsonPC
    geom = LatticeGeometry((lat,) * 4)
    g = GaugeField.random(jax.random.PRNGKey(seed), geom)
    return DiracWilsonPC(g.data.astype(jnp.complex64), geom,
                         kappa).packed()


@pytest.mark.parametrize("store,pallas,form", [
    (jnp.float32, True, None), (jnp.bfloat16, False, None),
    (jnp.float32, True, "r12f"), (jnp.bfloat16, True, "int8"),
    # the XLA stencil operators the batched route's flipped knobs run on
    (jnp.float32, False, None), (jnp.float32, False, "int8"),
    # the other storage forms a solve program may be handed
    (jnp.bfloat16, True, "fold"), (jnp.float32, True, "bzfull"),
    (jnp.float32, True, "r12")])
def test_operator_is_arrays_plus_a_small_static_key(store, pallas, form):
    kw = dict(use_pallas=pallas, pallas_interpret=True,
              precision_form=form)
    op = _packed(1, 0.12).pairs(store, **kw)
    leaves, treedef = jax.tree_util.tree_flatten(op)
    # every field-sized thing is a leaf (the links of both parities at
    # the least), the key holds no array, and an operator rebuilt on
    # other links with another kappa has the same key
    assert sum(getattr(x, "size", 1) for x in leaves) >= 36 * 4 ** 4
    sig = op.program_signature
    assert not any(isinstance(v, (jax.Array, np.ndarray)) for v in sig)
    other = _packed(2, 0.1).pairs(store, **kw)
    assert jax.tree_util.tree_structure(other) == treedef
    assert hash(other.program_signature) == hash(sig)
    # the round trip keeps everything the stencil dispatch reads
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.program_signature == sig
    for name in type(op)._PROGRAM_ARRAYS:
        was, now = getattr(op, name, None), getattr(back, name)
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: a is b, was, now))


def test_a_mesh_operator_does_not_present():
    from quda_tpu.parallel.mesh import make_lattice_mesh
    mesh = make_lattice_mesh(grid=(2, 1, 1, 1), n_src=1,
                             devices=jax.devices()[:2])
    op = _packed(1, 0.12, lat=8).pairs(
        jnp.float32, use_pallas=True, pallas_interpret=True, mesh=mesh,
        sharded_policy="xla_facefix")
    assert op.program_signature is None and not sprog.presents(op)
    with pytest.raises(TypeError, match="mesh"):
        jax.tree_util.tree_flatten(op)
    assert not sprog.presents(lambda v: v)
