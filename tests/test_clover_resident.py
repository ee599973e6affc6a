"""The resident clover term (``load_clover_quda``): built lattice-minor
once per (gauge, kappa*csw, matpc), kept in the API's context, solved
on through the cached solve program, and checked against three things
that do not share its code: the canonical construction
(ops/clover.clover_blocks / invert_clover), the canonical full operator
(models/clover.DiracClover) and the benchmark's plain reference
(benchmark/reference/clover.py).

CPU, seeded random SU(3) links, 4^4 and 8^4.  Every API case runs the
staged XLA form (QUDA_TPU_PACKED=1 alone); one case at 4^4 runs the
fused kernels interpreted.  Counts are read from the counters the
mechanism itself reports (``clover_term_total``,
``solve_program_total``: obs/metrics).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.spinor import even_odd_split
from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.models.clover import DiracClover, DiracCloverPC
from quda_tpu.obs import memory as omem
from quda_tpu.obs import metrics as omet
from quda_tpu.ops import clover as cl
from quda_tpu.ops import clover_packed as cpk
from quda_tpu.utils import config as qconf

KAPPA, CSW = 0.12, 1.0


def _geom(lat):
    return LatticeGeometry((lat,) * 4)


def _gauge(seed, lat):
    g = GaugeField.random(jax.random.PRNGKey(seed), _geom(lat))
    return g.data.astype(jnp.complex64)


def _unpack(blocks, half_shape):
    """(2,6,6,T,Z,Y*Xh) -> ops/clover's (T,Z,Y,Xh,2,6,6)."""
    T, Z, Y, Xh = half_shape
    return jnp.transpose(blocks.reshape(2, 6, 6, T, Z, Y, Xh),
                         (3, 4, 5, 6, 0, 1, 2))


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


# (a) the lattice-minor construction against the canonical one ---------------

@pytest.fixture(scope="module")
def terms():
    """{lat: (canonical (A_e, A_o), packed (A_p, A_q, A_q^-1) unpacked to
    the canonical layout)} at matpc even, built once per lattice."""
    out = {}

    def get(lat):
        if lat not in out:
            geom, g = _geom(lat), _gauge(3, lat)
            coeff = KAPPA * CSW / 2.0
            ref = even_odd_split(cl.clover_blocks(g, coeff), geom)
            half = geom.lattice_shape[:3] + (lat // 2,)
            got = tuple(_unpack(b, half) for b in
                        cpk.clover_term_packed(g, coeff,
                                               geom.lattice_shape, 0))
            out[lat] = (ref, got)
        return out[lat]
    return get


@pytest.mark.parametrize("lat", [4, 8])
@pytest.mark.parametrize("what", ["term", "inverse", "a_times_inverse"])
def test_lattice_minor_term_equals_the_canonical(terms, lat, what):
    (a_e, a_o), (a_p, a_q, ainv_q) = terms(lat)
    if what == "term":
        assert _rel(a_p, a_e) < 1e-5 and _rel(a_q, a_o) < 1e-5
    elif what == "inverse":
        assert _rel(ainv_q, cl.invert_clover(a_o)) < 1e-5
    else:
        one = jnp.einsum("...ij,...jk->...ik", a_o, ainv_q)
        eye = jnp.broadcast_to(jnp.eye(6, dtype=one.dtype), one.shape)
        assert float(jnp.max(jnp.abs(one - eye))) < 1e-5


def test_csw_zero_gives_the_identity():
    geom = _geom(4)
    blocks = cpk.clover_term_packed(_gauge(5, 4), 0.0,
                                    geom.lattice_shape, 1)
    eye = jnp.eye(6, dtype=jnp.complex64)[None, :, :, None, None, None]
    for b in blocks:
        assert float(jnp.max(jnp.abs(b - eye))) == 0.0


# (b) the API: one build, then reuse ------------------------------------------

L = 4


def _param(**kw):
    d = dict(dslash_type="clover", inv_type="cg", solve_type="normop-pc",
             kappa=KAPPA, csw=CSW, tol=1e-6, maxiter=500,
             cuda_prec="single", cuda_prec_sloppy="half")
    d.update(kw)
    return InvertParam(**d)


def _source(seed, lat=L):
    rng = np.random.default_rng(seed)
    shape = (lat,) * 4 + (4, 3)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _counts():
    """{(counter, outcome): count} of the two counters so far."""
    out = {}
    for (name, labels), v in omet.snapshot()["counters"].items():
        if name in ("clover_term_total", "solve_program_total"):
            key = (name, dict(labels)["outcome"])
            out[key] = out.get(key, 0) + int(v)
    return out


def _delta(before):
    now = _counts()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def _host_residual(gauge, b, x, kappa=KAPPA, csw=CSW, lat=L):
    d = DiracClover(jnp.asarray(gauge), _geom(lat), kappa, csw)
    return _rel(d.M(jnp.asarray(x)), jnp.asarray(b))


@pytest.fixture(scope="module")
def gauges():
    return {"A": np.asarray(_gauge(11, L)), "B": np.asarray(_gauge(12, L))}


def _load(gauge, lat=L):
    api.load_gauge_quda(gauge, GaugeParam(X=(lat,) * 4,
                                          cuda_prec="single"))


@pytest.fixture(scope="module")
def quda(gauges, tmp_path_factory):
    """init + resident gauge A + a metrics session on the packed pair
    route with the staged XLA stencil (no kernel is interpreted)."""
    mp = pytest.MonkeyPatch()
    mp.setenv("QUDA_TPU_PACKED", "1")
    for knob in ("QUDA_TPU_PALLAS", "QUDA_TPU_ROBUST", "QUDA_TPU_FAULT",
                 "QUDA_TPU_TRACE", "QUDA_TPU_CLOVER_FORM"):
        mp.delenv(knob, raising=False)
    qconf.reset_cache()
    api.init_quda()
    omet.start(str(tmp_path_factory.mktemp("clover_resident")))
    _load(gauges["A"])
    yield
    omet.stop(flush_files=False)
    api.end_quda()
    mp.undo()
    qconf.reset_cache()


@pytest.fixture(scope="module")
def warm(quda):
    """The worker's first clover solve, which traces the solve program:
    made once, in set-up, and its counts kept: {(counter, outcome): n}.
    Every later call with the same key must be a hit."""
    before = _counts()
    api.invert_quda(_source(0), _param())
    return _delta(before)


def test_load_then_two_solves_build_once_and_trace_once(warm, gauges):
    assert warm.get(("solve_program_total", "miss"), 0) == 1
    assert ("solve_program_total", "hit") not in warm
    api.free_clover_quda()
    before = _counts()
    api.load_clover_quda(_param())
    assert _delta(before) == {("clover_term_total", "built"): 1}
    rows = {(r["family"], r["field"]) for r in omem.ledger()}
    assert ("clover", "resident_clover") in rows
    before = _counts()
    for seed in (1, 2):
        b, p = _source(seed), _param()
        x = api.invert_quda(b, p)
        assert p.converged
        assert _host_residual(gauges["A"], b, x) < 5e-6
    assert _delta(before) == {("clover_term_total", "reused"): 2,
                              ("solve_program_total", "hit"): 2}
    # the term stays in the ledger after the calls (it is resident)
    assert ("clover", "resident_clover") in {
        (r["family"], r["field"]) for r in omem.ledger()}


def test_second_coefficient_rebuilds_and_solves_its_system(warm, gauges):
    api.load_clover_quda(_param())
    before = _counts()
    b, p = _source(3), _param(kappa=0.11, csw=1.3)
    x = api.invert_quda(b, p)
    d = _delta(before)
    assert d[("clover_term_total", "rebuilt")] == 1
    assert ("solve_program_total", "miss") not in d     # kappa: a leaf
    assert _host_residual(gauges["A"], b, x, 0.11, 1.3) < 5e-6
    assert _host_residual(gauges["A"], b, x) > 1e-3
    # the same kappa*csw under another kappa reuses the blocks
    before = _counts()
    b, p = _source(4), _param(kappa=0.13, csw=1.1)
    x = api.invert_quda(b, p)
    assert _delta(before)[("clover_term_total", "reused")] == 1
    assert _host_residual(gauges["A"], b, x, 0.13, 1.1) < 5e-6


def test_a_new_gauge_invalidates(warm, gauges):
    api.load_clover_quda(_param())
    try:
        _load(gauges["B"])
        assert api._ctx["clover"] is None
        assert ("clover", "resident_clover") not in {
            (r["family"], r["field"]) for r in omem.ledger()}
        before = _counts()
        b, p = _source(5), _param()
        x = api.invert_quda(b, p)
        assert _delta(before)[("clover_term_total", "built")] == 1
        assert _host_residual(gauges["B"], b, x) < 5e-6
        assert _host_residual(gauges["A"], b, x) > 1e-2
    finally:
        _load(gauges["A"])


def test_no_load_at_all_still_solves(warm, gauges):
    api.free_clover_quda()
    before = _counts()
    b, p = _source(6), _param()
    x = api.invert_quda(b, p)
    assert _delta(before)[("clover_term_total", "built")] == 1
    assert p.converged and _host_residual(gauges["A"], b, x) < 5e-6


def test_fused_kernels_interpreted_apply_the_resident_operator(
        quda, gauges, monkeypatch):
    """The one fused case: the resident f32 operator in the fused form
    (both epilogue kernels, interpreted) crosses a jit boundary as a
    pytree, kappa a traced leaf and -kappa^2 the kernel's SMEM operand,
    and is the canonical PC operator.  Form and route are part of the
    term's key, so the staged term of the other cases is replaced."""
    monkeypatch.setenv("QUDA_TPU_PALLAS", "1")
    monkeypatch.setenv("QUDA_TPU_CLOVER_FORM", "pallas")
    qconf.reset_cache()
    try:
        api.load_clover_quda(_param(cuda_prec_sloppy="single"))
        op = api._ctx["clover"]["ops"][jnp.dtype(jnp.float32)]
        assert op._op_form == "pallas" and op.use_pallas
        geom = _geom(L)
        dpc = DiracCloverPC(jnp.asarray(gauges["A"]), geom, 0.11, CSW / 0.11
                            * KAPPA)        # same kappa*csw, other kappa
        pe, _ = even_odd_split(jnp.asarray(_source(7)), geom)
        got = jax.jit(lambda o, v: o._from_pairs(
            o.M_pairs(o._to_pairs(v)), v.dtype))(op.with_kappa(0.11), pe)
        assert _rel(got, dpc.M(pe)) < 1e-5
    finally:
        monkeypatch.undo()
        qconf.reset_cache()
        api.free_clover_quda()


# (c) the system against the benchmark's plain reference ----------------------

@pytest.fixture(scope="module")
def bench():
    return {name: importlib.import_module(f"benchmark.{name}")
            for name in ("data", "reference.clover", "reference.wilson")}


@pytest.mark.parametrize("lat", [(4, 4, 4, 4), (4, 6, 2, 8)])
def test_program_operator_equals_the_plain_reference(bench, lat):
    data, ref = bench["data"], bench["reference.clover"]
    u = data.su3_field(data.key_of(2 ** 31 + 5, 0), (4,), lat, 0.7)
    psi = data.gaussian_sources(data.key_of(7, 1), lat, 2)
    d = DiracClover(data.to_canonical_gauge(u, lat),
                    LatticeGeometry(tuple(reversed(lat))), 0.32, ref.CSW,
                    antiperiodic_t=True)
    links = ref.fold_boundary(u, True)
    for i in range(2):
        prog = d.M(data.to_canonical_spinors(psi, lat)[i])
        mine = ref.apply_m(links, psi[i], 0.32, lat[3])
        mine = data.to_canonical_spinors(mine[None], lat)[0]
        assert _rel(prog, mine) <= 5e-6


def test_reference_without_the_term_is_the_wilson_reference(bench,
                                                            monkeypatch):
    data = bench["data"]
    ref, wil = bench["reference.clover"], bench["reference.wilson"]
    lat = (4, 4, 4, 4)
    u = data.su3_field(data.key_of(9, 0), (4,), lat, 0.7)
    psi = data.gaussian_sources(data.key_of(9, 1), lat, 1)[0]
    monkeypatch.setattr(ref, "CSW", 0.0)
    # CSW is read when apply_m is traced: trace it anew
    # (a new function: jit's cache is keyed on the function it wraps)
    fresh = jax.jit(lambda links, v: ref.apply_m.__wrapped__(
        links, v, 0.32, lat[3]))
    got = fresh(ref.fold_boundary(u, True), psi)
    want = wil.apply_m(wil.fold_boundary(u, True), psi, 0.32, lat[3])
    assert float(jnp.max(jnp.abs(got - want))) == 0.0


def test_api_solution_under_the_plain_reference(quda, bench):
    """invert_quda's answer on the benchmark's own links, judged by the
    benchmark's own operator (8^4: the configurations' rehearsal size)."""
    data, ref = bench["data"], bench["reference.clover"]
    lat = (8, 8, 8, 8)
    u = data.su3_field(data.key_of(101, 0), (4,), lat, 0.7)
    b = data.gaussian_sources(data.key_of(3, 1000), lat, 1)
    try:
        _load(data.to_canonical_gauge(u, lat), lat=8)
        p = _param(kappa=0.2, csw=ref.CSW, maxiter=2000)
        x = api.invert_quda(data.to_canonical_spinors(b, lat)[0], p)
        r = ref.rel_residual(ref.fold_boundary(u, True), 0.2, lat[3],
                             b[0], data.from_canonical_spinors(x[None])[0])
        assert p.converged and r <= 3e-5
        assert abs(p.true_res - r) / r < 0.1
    finally:
        _load(np.asarray(_gauge(11, L)))


# (d) the cached program is the eager solver ----------------------------------

def test_cached_program_equals_the_eager_solver_on_the_clover_operator():
    from quda_tpu.solvers import cg_reliable
    from quda_tpu.solvers import program as sprog
    from quda_tpu.solvers.mixed import pair_inplace_codec
    dpc = DiracCloverPC(_gauge(3, 4), _geom(4), KAPPA, CSW)
    hi, lo = dpc.pairs(jnp.float32), dpc.pairs(jnp.bfloat16)
    assert sprog.presents(hi, lo)
    leaves, treedef = jax.tree_util.tree_flatten(hi)
    assert not any(isinstance(v, (jax.Array, np.ndarray))
                   for v in hi.program_signature)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.program_signature == hi.program_signature
    b = jnp.asarray(np.random.default_rng(8).standard_normal(
        (4, 3, 2, 4, 4, 8)), jnp.float32)
    kw = dict(tol=1e-5, maxiter=300)
    cached, _ = sprog.cg_reliable(hi, lo, b, delta=0.1, **kw)
    eager = cg_reliable(hi.MdagM_pairs, lo.MdagM_pairs, b, delta=0.1,
                        codec=pair_inplace_codec(jnp.bfloat16), **kw)
    assert bool(cached.converged)
    assert int(cached.iters) == int(eager.iters)
    np.testing.assert_allclose(np.asarray(cached.r2),
                               np.asarray(eager.r2), rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(cached.x), np.asarray(eager.x), rtol=0,
        atol=1e-5 * float(jnp.max(jnp.abs(eager.x))))


@pytest.mark.parametrize("fault_k", [None, 7])
def test_solve_program_in_the_fused_form_takes_the_operators_step(
        quda, monkeypatch, fault_k):
    """The single-source solve program on the resident operators in the
    fused form (PR 50): traced, not compiled.  ``cg_reliable_loop``
    takes its step from the sloppy operator (``MdagM_cg_step_pairs``):
    of its two ``diag_hop`` calls an iteration the first is the
    ``norm2`` form (``pAp``), the second the ``residual`` form (the new
    ``r`` and ``|r|^2``), counted by ``clover_route_total`` where they
    are traced; the precise operator's ``MdagM_pairs`` (the reliable
    update and the exit: two traces) stays ``combine``.  With a dslash
    fault armed the program takes ``mixed.cg_step`` of ``MdagM_pairs``
    whatever the operand offers: ``combine`` only, and the one loop
    with its ``lax.cond`` where the operator's step runs in stretches.  The same program
    asked for again is the traced one and counts nothing."""
    from quda_tpu.solvers import mixed
    from quda_tpu.solvers import program as sprog
    monkeypatch.setenv("QUDA_TPU_PALLAS", "1")
    monkeypatch.setenv("QUDA_TPU_CLOVER_FORM", "pallas")
    qconf.reset_cache()
    steps = []
    generic = mixed.cg_step
    monkeypatch.setattr(mixed, "cg_step", lambda mv, codec, k=None: (
        steps.append(k), generic(mv, codec, k))[1])

    def counts():
        return {tuple(dict(l)[k] for k in ("form", "stage", "epilogue")):
                int(v) for (n, l), v in omet.snapshot()["counters"].items()
                if n == "clover_route_total"}
    try:
        api.load_clover_quda(_param())
        hi, lo = (api._ctx["clover"]["ops"][jnp.dtype(dt)]
                  for dt in (jnp.float32, jnp.bfloat16))
        assert hi._op_form == lo._op_form == "pallas"
        assert lo.MdagM_cg_step_pairs is not None
        b = jax.ShapeDtypeStruct((4, 3, 2, L, L, L * L // 2), jnp.float32)
        key = (0.1, mixed.pair_inplace_config(jnp.bfloat16),
               sprog._loop_knobs(False, 100)._replace(fault_k=fault_k),
               False)
        trace = lambda: sprog._cg_reliable_program.trace(
            hi, lo, b, 1e-6, 100, key=key)
        before = counts()
        loops = str(trace().jaxpr)
        first = counts()
        # the operator's step runs in stretches (a loop in a loop, no
        # conditional), the generic step in the one loop with the
        # reliable update's branch
        assert (loops.count(" while["), loops.count(" cond[")) == (
            (2, 0) if fault_k is None else (1, 1))
        lo_calls = ({("pallas", "diag_hop", "norm2"): 1,
                     ("pallas", "diag_hop", "residual"): 1}
                    if fault_k is None else
                    {("pallas", "diag_hop", "combine"): 2})
        want = {("pallas", "post", "none"): 2 + 4,
                ("pallas", "diag_hop", "combine"): 4}
        for k, v in lo_calls.items():
            want[k] = want.get(k, 0) + v
        assert {k: v - before.get(k, 0) for k, v in first.items()
                if v != before.get(k, 0)} == want
        assert steps == ([] if fault_k is None else [fault_k])
        trace()
        assert counts() == first
    finally:
        monkeypatch.undo()
        qconf.reset_cache()
        api.free_clover_quda()


# (e) the batched route: invert_multi_src_quda on the resident term -----------
# PR 46.  Three sources (N is not the point), 4^4, the staged XLA form;
# the three programs of the route are compiled once, by ``batch_warm``,
# and every case after it must be served by them (another gauge, kappa
# or csw is an operand).

N_SRC = 3


def _batch(seed, lat=L):
    return np.stack([_source(100 * seed + i, lat) for i in range(N_SRC)])


def _program_counts():
    """{(solver, outcome): n} of solve_program_total on the batched
    clover route."""
    out = {}
    for (name, labels), v in omet.snapshot()["counters"].items():
        lab = dict(labels)
        if (name == "solve_program_total"
                and lab["form"] == "clover_batched_pairs"):
            assert lab["api"] == "invert_multi_src_quda"
            out[lab["solver"], lab["outcome"]] = int(v)
    return out


def _route_counts():
    """{(form, stage, route, epilogue): n} of clover_mrhs_route_total."""
    return {tuple(dict(l)[k] for k in ("form", "stage", "route",
                                       "epilogue")): int(v)
            for (n, l), v in omet.snapshot()["counters"].items()
            if n == "clover_mrhs_route_total"}


@pytest.fixture(scope="module")
def batch_warm(warm):
    """The worker's first batched clover call (after the single-source
    ``warm``, so the term of gauge A is there): what it returned, the
    counters' change and the route's own program counts."""
    from quda_tpu.solvers import program as sprog
    api.load_clover_quda(_param())
    before, routes0 = _counts(), _route_counts()
    B, p = _batch(1), _param()
    n0 = sprog._traces[0]
    x = api.invert_multi_src_quda(B, p)
    routes = {k: v - routes0.get(k, 0) for k, v in _route_counts().items()}
    return {"B": B, "x": np.asarray(x), "param": p,
            "delta": _delta(before), "programs": _program_counts(),
            "routes": routes, "traced": sprog._traces[0] - n0}


def test_batch_first_call_builds_three_programs_on_the_resident_term(
        batch_warm, gauges):
    w = batch_warm
    assert w["delta"] == {("clover_term_total", "reused"): 1,
                          ("solve_program_total", "miss"): 3}
    assert w["programs"] == {("prepare", "miss"): 1,
                             ("batched-cg-pairs", "miss"): 1,
                             ("verified-exit", "miss"): 1}
    assert w["traced"] == 3
    # the staged form off the chip; an M counts both stages where it is
    # traced: Mdag of the entry, M and Mdag of the loop
    assert w["routes"] == {("xla", "post", "none", "none"): 3,
                           ("xla", "diag_hop", "none", "combine"): 3}
    p = w["param"]
    assert all(p.converged_multi) and len(p.true_res_multi) == N_SRC
    for i in range(N_SRC):
        r = _host_residual(gauges["A"], w["B"][i], w["x"][i])
        assert r < 5e-6
        assert abs(p.true_res_multi[i] - r) / r < 0.1


def test_batch_second_call_hits_and_another_csw_only_rebuilds_the_term(
        batch_warm, gauges):
    from quda_tpu.solvers import program as sprog
    api.load_clover_quda(_param())
    n0, progs0, before = sprog._traces[0], _program_counts(), _counts()
    B, p = _batch(2), _param()
    x = api.invert_multi_src_quda(B, p)
    assert _delta(before) == {("clover_term_total", "reused"): 1,
                              ("solve_program_total", "hit"): 3}
    hits = {k: v - progs0.get(k, 0) for k, v in _program_counts().items()}
    assert hits == {("prepare", "miss"): 0, ("batched-cg-pairs", "miss"): 0,
                    ("verified-exit", "miss"): 0, ("prepare", "hit"): 1,
                    ("batched-cg-pairs", "hit"): 1,
                    ("verified-exit", "hit"): 1}
    assert max(_host_residual(gauges["A"], B[i], x[i])
               for i in range(N_SRC)) < 5e-6
    # another kappa and csw: the term is rebuilt, the programs are not
    before = _counts()
    p = _param(kappa=0.11, csw=1.3)
    x = api.invert_multi_src_quda(B, p)
    assert _delta(before) == {("clover_term_total", "rebuilt"): 1,
                              ("solve_program_total", "hit"): 3}
    assert sprog._traces[0] == n0
    for i in range(N_SRC):
        assert _host_residual(gauges["A"], B[i], x[i], 0.11, 1.3) < 5e-6
        assert _host_residual(gauges["A"], B[i], x[i]) > 1e-3


@pytest.mark.parametrize("fault_k", [None, 7])
def test_batch_solve_program_in_the_fused_form_counts_the_fullz_route(
        batch_warm, monkeypatch, fault_k):
    """The batched solve program on the resident operator in the fused
    form (PR 47): traced, not compiled (an interpreted kernel costs ~20
    s a module and proves nothing about a counter).  Each fused MRHS
    call is counted where it is traced, by the route the call takes
    from its shapes (``fullz`` at the test lattice as at 24^4) and by
    its epilogue.  Since PR 48 the loop takes its step from the
    operator (``MdagM_cg_step_pairs_mrhs``): two ``post``, the first
    M's K2 call in the ``norm2`` form (``pAp``), the second's in the
    ``residual`` form (the new ``r`` and ``|r|^2``).  With a dslash
    fault armed the program takes ``block.cg_step`` of
    ``MdagM_pairs_mrhs`` whatever the operator offers (the fault
    corrupts ``A p``, which only that step has): ``combine`` only.
    The same program asked for again is the traced one and counts
    nothing."""
    from quda_tpu.solvers import block
    from quda_tpu.solvers import program as sprog
    from quda_tpu.solvers.fused_iter import _resolve_check_every
    monkeypatch.setenv("QUDA_TPU_PALLAS", "1")
    monkeypatch.setenv("QUDA_TPU_CLOVER_FORM", "pallas")
    qconf.reset_cache()
    steps = []
    generic = block.cg_step
    monkeypatch.setattr(block, "cg_step", lambda mv, k=None: (
        steps.append(k), generic(mv, k))[1])
    try:
        api.load_clover_quda(_param(cuda_prec_sloppy="single"))
        op = api._ctx["clover"]["ops"][jnp.dtype(jnp.float32)]
        assert op._mrhs_form() == "pallas"
        x = jax.ShapeDtypeStruct((N_SRC, 4, 3, 2, L, L, L * L // 2),
                                 jnp.float32)
        key = (_resolve_check_every(None),
               sprog._loop_knobs(False, 100)._replace(fault_k=fault_k),
               False)
        trace = lambda: sprog._batched_cg_pairs_program.trace(
            op, x, 1e-6, 100, key=key)
        before = _route_counts()
        trace()
        first = _route_counts()
        assert {k: v - before.get(k, 0) for k, v in first.items()
                if v != before.get(k, 0)} == ({
            ("pallas", "post", "fullz", "none"): 2,
            ("pallas", "diag_hop", "fullz", "norm2"): 1,
            ("pallas", "diag_hop", "fullz", "residual"): 1}
            if fault_k is None else {
            ("pallas", "post", "fullz", "none"): 2,
            ("pallas", "diag_hop", "fullz", "combine"): 2})
        assert steps == ([] if fault_k is None else [fault_k])
        trace()
        assert _route_counts() == first
    finally:
        monkeypatch.undo()
        qconf.reset_cache()
        api.load_clover_quda(_param())


def test_batch_equals_single_source_solves_on_the_same_term(batch_warm):
    api.load_clover_quda(_param())
    for i in range(N_SRC):
        x1 = api.invert_quda(batch_warm["B"][i], _param())
        assert _rel(jnp.asarray(batch_warm["x"][i]), x1) < 1e-5


def test_batch_under_the_plain_reference(batch_warm, bench):
    """Every source of a batched call on the benchmark's own links,
    judged by the benchmark's own operator: the residual and the API's
    own claim of it."""
    data, ref = bench["data"], bench["reference.clover"]
    lat = (L,) * 4
    u = data.su3_field(data.key_of(101, 0), (4,), lat, 0.7)
    b = data.gaussian_sources(data.key_of(5, 1000), lat, N_SRC)
    before = _counts()
    try:
        _load(data.to_canonical_gauge(u, lat))
        p = _param(kappa=0.2, csw=ref.CSW, maxiter=2000)
        x = api.invert_multi_src_quda(data.to_canonical_spinors(b, lat), p)
        got = data.from_canonical_spinors(x)
        links = ref.fold_boundary(u, True)
        for i in range(N_SRC):
            r = ref.rel_residual(links, 0.2, lat[3], b[i], got[i])
            assert p.converged_multi[i] and r <= 5e-6
            assert abs(p.true_res_multi[i] - r) / r < 0.1
        assert ("solve_program_total", "miss") not in _delta(before)
    finally:
        _load(np.asarray(_gauge(11, L)))


def _pc_pairs(op, x):
    """The pair-form PC solution inside canonical solution(s) ``x``."""
    half = lambda v: op._to_pairs(even_odd_split(v, op.geom)[op.matpc])
    return jax.vmap(half)(x) if x.ndim == 7 else half(x)


@pytest.mark.parametrize("n", [1, N_SRC])
def test_verified_exit_pairs_equals_the_eager_full_operator(batch_warm, n):
    """``DiracCloverPCPairs.verified_exit_pairs`` for both ranks against
    the single-source route's eager check (models/clover._full_m_pairs
    with the term's A_q) on the same solutions."""
    from quda_tpu.interfaces.quda_api import _CloverResidentSolve
    api.load_clover_quda(_param())
    d = _CloverResidentSolve(api._ctx["clover"], KAPPA)
    op = d.with_full_diag()
    B = jnp.asarray(batch_warm["B"][:n])
    X = jnp.asarray(batch_warm["x"][:n])
    if n == 1:
        B, X = B[0], X[0]
    shape = (n,) + (L,) * 4 + (4, 3)
    full = d.full()
    # the sound solutions, whose residuals are f32 rounding (two
    # evaluations of them agree to a part in a hundred), and the same
    # scaled by 1.01, whose residuals are not
    for scale, rtol in ((1.0, 1e-2), (1.01, 1e-5)):
        x_back, res = op.verified_exit_pairs(B, scale * _pc_pairs(op, X))
        if scale == 1.0:
            assert _rel(x_back, X) < 1e-6
        want = [_rel(full.M(jnp.asarray(x)), b) for x, b in
                zip(np.asarray(x_back).reshape(shape),
                    np.asarray(B).reshape(shape))]
        np.testing.assert_allclose(np.asarray(res).reshape(n), want,
                                   rtol=rtol)
        assert (scale == 1.0) == (max(want) < 5e-6)


def test_an_altered_solution_raises_its_own_residual_only(batch_warm):
    """The exit program's residual is per source: one PC solution of the
    batch scaled by 1.01 reads ~1e-2 there and leaves the others'."""
    from quda_tpu.interfaces.quda_api import _CloverResidentSolve
    from quda_tpu.solvers import program as sprog
    api.load_clover_quda(_param())
    op = _CloverResidentSolve(api._ctx["clover"], KAPPA).with_full_diag()
    B = jnp.asarray(batch_warm["B"])
    x_pp = _pc_pairs(op, jnp.asarray(batch_warm["x"]))
    (_, sound), hit = sprog.verified_exit(op, B, x_pp)
    assert hit
    (_, bad), hit = sprog.verified_exit(op, B, x_pp.at[1].multiply(1.01))
    assert hit
    sound, bad = np.asarray(sound), np.asarray(bad)
    np.testing.assert_allclose(
        sound, batch_warm["param"].true_res_multi, rtol=1e-3)
    assert bad[1] > 1e3 * sound[1] and bad[1] > 1e-3
    np.testing.assert_array_equal(bad[[0, 2]], sound[[0, 2]])
