"""``invert_multishift_quda(dslash_type="clover")`` on the resident clover
term (the RHMC's shifted solve of a Wilson-clover campaign): entry
(split, prepare, Mdag), the multi-shift CG on the normal equations and
the exit as cached programs (solvers/program.py), every shift verified
by the exit, held to the benchmark's plain reference
``benchmark/reference/clover_shifted.py`` shift by shift and to the
eager canonical branch the route replaces.

CPU, 4^4, the benchmark's own seeded hot links, kappa 0.32 and csw 1.0
(the cell's system) and THREE offsets: the number of shifts is not the
point here (the described-chip compile of tests/test_chip_compile.py
and benchmark/tests/test_clover_multishift.py hold fourteen, the latter
at 8^4).  Everything runs the XLA pair stencil, as
tests/test_clover_resident.py: what is compared is the route, the
programs and the exit, not the kernels (tests/test_clover_pallas.py
holds those; interpreted they compile ~20 s a kernel form).  One
module-scoped session and ONE solve serve the cases.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.spinor import even_odd_join, even_odd_split
from quda_tpu.interfaces import milc
from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.obs import build as obuild
from quda_tpu.obs import metrics as omet
from quda_tpu.robust import faultinject as finj
from quda_tpu.solvers import program as sprog
from quda_tpu.utils import config as qconf

LAT = (4, 4, 4, 4)                      # array order (T, Z, Y, X)
GEOM = LatticeGeometry(tuple(reversed(LAT)))
KAPPA, CSW = 0.32, 1.0
OFFSETS = (0.0064, 0.0464, 1.0064)
API = "invert_multishift_quda"
PROGRAMS = ("prepare", "multishift-cg", "verified-exit")
N = len(OFFSETS)


def _param(offsets=OFFSETS, **kw):
    d = dict(dslash_type="clover", kappa=KAPPA, csw=CSW,
             inv_type="multi-shift-cg", solve_type="normop-pc", tol=1e-6,
             maxiter=2000, cuda_prec="single", num_offset=len(offsets),
             offset=tuple(offsets))
    d.update(kw)
    return InvertParam(**d)


def _counts(name, key):
    out = {}
    for (n, labels), v in omet.snapshot()["counters"].items():
        lb = dict(labels)
        if n == name and lb.get("api", API) == API:
            k = tuple(lb[i] for i in key)
            out[k] = out.get(k, 0) + int(v)
    return out


def _delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _fields(xs):
    """The API's (N, T, Z, Y, X/2, 4, 3) even-site solutions as the
    reference's fields (N, 4, 3, T, Z, Y*X), odd sites zero."""
    full = jax.vmap(lambda e: even_odd_join(e, jnp.zeros_like(e),
                                            GEOM))(xs)
    return jnp.transpose(full, (0, 5, 6, 1, 2, 3, 4)).reshape(
        (xs.shape[0], 4, 3, LAT[0], LAT[1], -1))


def _checked(ref, links, b, xs, offsets, kappa=KAPPA):
    """The reference's residual of every shifted system for the
    offsets of a test (an operand of its jit: one compile)."""
    return np.asarray(ref.shift_residuals(
        links, kappa, LAT[3], b, _fields(xs),
        jnp.asarray(offsets, jnp.float32)))


@pytest.fixture(scope="module")
def solved(tmp_path_factory):
    """init, the benchmark's links resident, a metrics session; one
    solve of three offsets on the resident route (no load_clover_quda
    before it: the term is built on first use)."""
    data = importlib.import_module("benchmark.data")
    ref = importlib.import_module("benchmark.reference.clover_shifted")
    mp = pytest.MonkeyPatch()
    mp.setenv("QUDA_TPU_PACKED", "1")
    for knob in ("QUDA_TPU_PALLAS", "QUDA_TPU_PRECISION_FORM",
                 "QUDA_TPU_ROBUST", "QUDA_TPU_FAULT",
                 "QUDA_TPU_CLOVER_FORM"):
        mp.delenv(knob, raising=False)
    qconf.reset_cache()
    finj.reset()
    api.init_quda()
    omet.start(str(tmp_path_factory.mktemp("clover_multishift")))
    u = data.su3_field(data.key_of(101, 0), (4,), LAT, 0.7)
    gauge = data.to_canonical_gauge(u, LAT)
    api.load_gauge_quda(np.asarray(gauge), GaugeParam(
        X=tuple(reversed(LAT)), cuda_prec="single"))
    links = ref.fold_boundary(u, True)
    # a harness source with its odd sites emptied, as the benchmark's
    # entry hands it to the API
    b = data.gaussian_sources(data.key_of(2 ** 31 + 7, 1000), LAT, 1)[0]
    b = (b * ref.parity_mask(b.shape[-3:], LAT[3], 0)).astype(
        jnp.complex64)
    src = data.to_canonical_spinors(b[None], LAT)[0]
    p = _param()
    before = _counts("solve_program_total", ("solver", "outcome"))
    terms = _counts("clover_term_total", ("outcome",))
    xs = api.invert_multishift_quda(src, p)
    yield {"ref": ref, "links": links, "b": b, "src": src, "p": p,
           "xs": xs, "gauge": gauge,
           "first": _delta(before, _counts("solve_program_total",
                                           ("solver", "outcome"))),
           "term": _delta(terms, _counts("clover_term_total",
                                         ("outcome",))),
           "checked": _checked(ref, links, b, xs, OFFSETS)}
    omet.stop(flush_files=False)
    api.end_quda()
    finj.reset()
    mp.undo()
    qconf.reset_cache()


# (a) the reference is the program's canonical even-odd operator --------------

@pytest.mark.parametrize("matpc", [0, 1], ids=["even-even", "odd-odd"])
def test_reference_normal_operator_is_the_programs(solved, matpc):
    """``apply_m`` of the reference (masks, the full-lattice A and D,
    its own site-by-site inverse of A) against ``Mdag(M(.))`` of the
    canonical ``DiracCloverPC``, on the p sites; zero on the others."""
    from quda_tpu.models.clover import DiracCloverPC
    data = importlib.import_module("benchmark.data")
    ref = solved["ref"]
    d = DiracCloverPC(solved["gauge"], GEOM, 0.12, CSW, True, matpc)
    v = data.gaussian_sources(data.key_of(5, 1), LAT, 1)
    x_p = even_odd_split(data.to_canonical_spinors(v, LAT)[0],
                         GEOM)[matpc]
    mine = ref.apply_m(solved["links"], v, 0.12, LAT[3], parity=matpc)
    got = even_odd_split(data.to_canonical_spinors(mine, LAT)[0], GEOM)
    assert _rel(got[matpc], d.Mdag(d.M(x_p))) < 1e-6
    assert float(jnp.max(jnp.abs(got[1 - matpc]))) == 0.0


# (b) + (c) the route under the reference and against the eager branch --------

def test_first_call_goes_through_three_programs(solved):
    assert solved["first"] == {(s, "miss"): 1 for s in PROGRAMS}
    assert solved["term"] == {("built",): 1}
    p = solved["p"]
    assert p.converged and p.converged_multi == [True] * N
    assert p.true_res == p.true_res_offset[0]
    assert 30 < p.iter_count < 1000
    assert p.iter_count_offset[0] == p.iter_count
    assert p.iter_count_offset[-1] < p.iter_count
    assert solved["xs"].shape == (N, 4, 4, 4, 2, 4, 3)


@pytest.mark.parametrize("shift", range(N))
def test_every_shift_under_the_plain_reference(solved, shift):
    """Shift by shift: the reference's shifted residual is under 5e-6,
    the exit program's own agrees with it to 10 % (the configuration's
    agree_bound), and the loop's own zeta |r| is under tol."""
    p, checked = solved["p"], float(solved["checked"][shift])
    assert 0 < checked <= 5e-6
    assert abs(p.true_res_offset[shift] - checked) / checked < 0.1
    assert p.iter_res_offset[shift] <= p.tol


def test_route_equals_the_eager_canonical_branch(solved, monkeypatch):
    """The same call with the packed route off takes the last branch of
    ``_invert_multishift_body`` (canonical ``DiracCloverPC``, the eager
    loop): the same solutions to 1e-5, the same iterations."""
    monkeypatch.setenv("QUDA_TPU_PACKED", "0")
    qconf.reset_cache()
    before = _counts("solve_program_total", ("solver", "outcome"))
    p = _param()
    xs = api.invert_multishift_quda(solved["src"], p)
    monkeypatch.undo()
    qconf.reset_cache()
    assert _delta(before, _counts("solve_program_total",
                                  ("solver", "outcome"))) == {}
    assert xs.shape == solved["xs"].shape
    for i in range(N):
        assert _rel(solved["xs"][i], xs[i]) < 1e-5
    assert abs(p.iter_count - solved["p"].iter_count) <= 2


# (d) other offsets, kappa and csw: the same programs -------------------------

@pytest.mark.parametrize("change, kw, term, under_reference", [
    ("offsets", {}, "reused", True),
    ("kappa", dict(kappa=0.30), "rebuilt", True),
    # kappa alone is a leaf of the operators: the term's key is kappa * csw
    ("kappa_leaf", dict(kappa=0.15, csw=2.0), "reused", False),
    ("csw", dict(kappa=0.25, csw=0.8), "rebuilt", False)],
    ids=["offsets", "kappa", "kappa_leaf", "csw"])
def test_second_call_hits_and_builds_nothing(solved, change, kw, term,
                                             under_reference):
    """Other offsets of the same count, another kappa, another csw: no
    program is traced, and the term is reused or rebuilt as
    ``_clover_term_key`` says (the cases run in this order: each one's
    term is what the one before left)."""
    offsets = (0.01, 0.05, 0.7)
    before = _counts("solve_program_total", ("solver", "outcome"))
    terms = _counts("clover_term_total", ("outcome",))
    built, traces = len(obuild.snapshot()), sprog._traces[0]
    p = _param(offsets, **kw)
    xs = api.invert_multishift_quda(solved["src"], p)
    assert sprog._traces[0] == traces
    assert _delta(before, _counts("solve_program_total",
                                  ("solver", "outcome"))) == {
        (s, "hit"): 1 for s in PROGRAMS}
    assert _delta(terms, _counts("clover_term_total", ("outcome",))) == {
        (term,): 1}
    assert [r for r in obuild.snapshot()[built:]
            if r["api"] == API and r["program"].startswith("_")] == []
    assert p.converged and all(p.converged_multi)
    if under_reference:
        # the exit's residuals are of THESE offsets and THIS kappa (the
        # reference's CSW is the cell's, so only at csw 1.0)
        checked = _checked(solved["ref"], solved["links"], solved["b"],
                           xs, offsets, p.kappa)
        for i in range(N):
            assert abs(p.true_res_offset[i] - checked[i]) / checked[i] < 0.1


# (e) + (f) the exit judges every shift by itself -----------------------------

@pytest.fixture(scope="module")
def programs(solved):
    """The route's three programs called as the route calls them (all
    hits of its executables): the operator, the right-hand side and
    the loop's result."""
    d = api._CloverResidentSolve(api._resident_clover(_param(), ()), KAPPA)
    rhs, hit = sprog.prepare(d.op, solved["src"])
    shifts = np.asarray(OFFSETS, np.float32)
    res, hit2 = sprog.multishift_cg(d.op, rhs, shifts, tol=1e-6,
                                    maxiter=2000)
    assert hit and hit2
    return d.op, rhs, shifts, res


@pytest.mark.parametrize("row", range(N))
def test_an_altered_shift_fails_its_own_flag_only(programs, row):
    op, rhs, shifts, res = programs
    (_, true0, _, ok0), hit = sprog.verified_exit_shifts(
        op, rhs, res, shifts, 1e-4)
    assert hit and bool(ok0.all())
    bad = res._replace(x=res.x.at[row].multiply(1.01))
    (_, true1, _, ok1), hit = sprog.verified_exit_shifts(
        op, rhs, bad, shifts, 1e-4)
    assert hit
    assert [bool(v) for v in ok1] == [i != row for i in range(N)]
    others = [i for i in range(N) if i != row]
    np.testing.assert_array_equal(np.asarray(true1)[others],
                                  np.asarray(true0)[others])
    assert float(true1[row]) > 1e-4
    # a NaN fails, and a shift the loop did not claim fails whatever
    # its residual
    nan = res._replace(x=res.x.at[row].set(jnp.nan))
    assert not bool(sprog.verified_exit_shifts(
        op, rhs, nan, shifts, 1e-4)[0][3][row])
    unclaimed = res._replace(converged=res.converged.at[row].set(False))
    assert [bool(v) for v in sprog.verified_exit_shifts(
        op, rhs, unclaimed, shifts, 1e-4)[0][3]] == [
        i != row for i in range(N)]


def test_exit_is_the_single_source_operator_shift_by_shift(programs):
    """Every row of the exit's ONE batched application
    (``MdagM_pairs_mrhs`` on the N solutions) against |b' - (MdagM +
    sigma_i) x_i| / |b'| of the single-source ``MdagM_pairs``: a row
    of the batch is a batch of one (the source axis is vmapped here
    and a grid axis of the kernels), so N = 1 is each of these; an
    exit program of that shape would be a fourth compile."""
    op, rhs, shifts, res = programs
    (_, true_res, iter_res, ok), hit = sprog.verified_exit_shifts(
        op, rhs, res, shifts, 1e-4)
    assert hit and bool(ok.all())
    mdagm = jax.jit(lambda o, x: o.MdagM_pairs(x))
    for i in range(N):
        r = rhs - (mdagm(op, res.x[i]) + shifts[i] * res.x[i])
        want = float(jnp.sqrt(jnp.sum(r * r) / jnp.sum(rhs * rhs)))
        assert 0 < want < 5e-6
        # both are rounding's leftovers at 1e-6 |b'|: a percent apart
        assert abs(float(true_res[i]) - want) <= 1e-2 * want
        assert float(iter_res[i]) <= 1e-6


# MILC's entry point reaches the route ----------------------------------------

def test_milc_clover_multishift_is_the_api_call(solved):
    before = _counts("solve_program_total", ("solver", "outcome"))
    xs, info = milc.qudaCloverMultishiftInvert(
        KAPPA, CSW, OFFSETS, solved["src"], tol=1e-6, maxiter=2000,
        prec="single")
    assert _delta(before, _counts("solve_program_total",
                                  ("solver", "outcome"))) == {
        (s, "hit"): 1 for s in PROGRAMS}
    np.testing.assert_array_equal(np.asarray(xs),
                                  np.asarray(solved["xs"]))
    p = solved["p"]
    assert info == {"iters": p.iter_count,
                    "true_res_offset": p.true_res_offset,
                    "iter_res_offset": p.iter_res_offset,
                    "iter_count_offset": p.iter_count_offset,
                    "converged": [True] * N}


# what keeps the branch it had ------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(dslash_type="wilson"), dict(cuda_prec_sloppy="half"),
    dict(solve_type="direct-pc"), dict(dslash_type="twisted-clover")],
    ids=["wilson", "explicit-half", "direct-pc", "twisted-clover"])
def test_everything_else_keeps_its_branch(kw):
    assert api._clover_shift_route(_param())
    assert not api._clover_shift_route(_param(**kw))
