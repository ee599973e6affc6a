"""The resident KS pair operators (``_resident_staggered``, built by
``load_fat_long_quda``) and the staggered verified-exit program against
what they replaced: ``DiracStaggeredPC(...).pairs(...)`` built per call
from canonical arrays, and ``reconstruct_pairs`` + join + the canonical
complex64 ``DiracStaggered.M``.

CPU, seeded random SU(3) fat links and 0.1 x random long links, 4^4 and
one odd shape.  Everything here runs the XLA pair stencil: what is
compared is the term, the programs and the API's routing, not the
kernels (tests/test_staggered_pallas.py holds those).  The API cases
share one module-scoped session; f32 throughout, so agreement is held
to 1e-5 (a residual is two f32 sums of ~1e3-1e4 squares, and a solve to
tol 1e-6 on the PC system leaves tol / 2m on the full one).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.geometry import EVEN, ODD, LatticeGeometry
from quda_tpu.fields.spinor import even_odd_join, even_odd_split
from quda_tpu.interfaces import milc
from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.models.staggered import (DiracStaggered, DiracStaggeredPC,
                                       DiracStaggeredPCPairs)
from quda_tpu.obs import memory as omem
from quda_tpu.obs import metrics as omet
from quda_tpu.ops import staggered_packed as spk
from quda_tpu.solvers import program as sprog
from quda_tpu.utils import config as qconf

MASS = 0.1
LATTICES = [(4, 4, 4, 4), (4, 6, 2, 8)]


def _links(seed, dims):
    """(fat, long): random SU(3) and 0.1 x random SU(3), complex64."""
    geom = LatticeGeometry(dims)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    fat = GaugeField.random(k1, geom).data.astype(jnp.complex64)
    lng = (0.1 * GaugeField.random(k2, geom).data).astype(jnp.complex64)
    return fat, lng


def _field(seed, shape):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape)
                       + 1j * rng.standard_normal(shape), jnp.complex64)


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _from_links(fat, lng, geom, mass, matpc, ap, store=jnp.float32):
    """The operator as the resident term assembles it."""
    dims = tuple(geom.lattice_shape)
    f = spk.ks_links_eo_pairs(fat, dims, ap, 1)
    l = spk.ks_links_eo_pairs(lng, dims, ap, 3)
    return DiracStaggeredPCPairs.from_packed(
        geom, tuple(g.astype(store) for g in f),
        tuple(g.astype(store) for g in l), mass, matpc, store)


# (a) the term's operator is the one built per call ---------------------------

@pytest.mark.parametrize("store", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("ap", [True, False],
                         ids=["antiperiodic", "periodic"])
def test_from_packed_is_the_per_call_operator(store, ap):
    dims = LATTICES[1]
    geom = LatticeGeometry(dims)
    fat, lng = _links(7, dims)
    old = DiracStaggeredPC(fat, geom, MASS, improved=True, long_links=lng,
                           matpc=ODD, antiperiodic_t=ap).pairs(store)
    new = _from_links(fat, lng, geom, 0.0, ODD, ap, store).with_mass(MASS)
    assert new.program_signature == old.program_signature
    assert (jax.tree_util.tree_structure(new)
            == jax.tree_util.tree_structure(old))
    for a, b in zip(new.fat_eo_pp + new.long_eo_pp,
                    old.fat_eo_pp + old.long_eo_pp):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))
    T, Z, Y, X = geom.lattice_shape
    v = jnp.asarray(np.random.default_rng(4).standard_normal(
        (3, 2, T, Z, Y * X // 2)), store)
    np.testing.assert_array_equal(
        np.asarray(new.M_pairs(v).astype(jnp.float32)),
        np.asarray(old.M_pairs(v).astype(jnp.float32)))


def test_with_mass_shares_the_arrays_and_the_key():
    dims = LATTICES[0]
    fat, lng = _links(8, dims)
    op = _from_links(fat, lng, LatticeGeometry(dims), 0.0, EVEN, True)
    other = op.with_mass(0.05)
    assert other.mass == 0.05 and op.mass == 0.0
    assert other.fat_eo_pp is op.fat_eo_pp
    assert other.long_eo_pp is op.long_eo_pp
    assert other.program_signature == op.program_signature
    assert sprog.presents(op, other) and op.hermitian


# (b) the exit program against the path it replaced ---------------------------

@pytest.mark.parametrize("ap", [True, False],
                         ids=["antiperiodic", "periodic"])
@pytest.mark.parametrize("matpc", [EVEN, ODD], ids=["even", "odd"])
@pytest.mark.parametrize("dims", LATTICES, ids=["4x4x4x4", "4x6x2x8"])
def test_verified_exit_equals_reconstruct_and_canonical_m(dims, matpc, ap):
    """Any pair-form field in (not a solution: the residual is then
    O(1), and f32 rounding is 1e-7 of it), solution and residual out."""
    geom = LatticeGeometry(dims)
    fat, lng = _links(5, dims)
    op = _from_links(fat, lng, geom, MASS, matpc, ap)
    T, Z, Y, X = geom.lattice_shape
    b = _field(1, (T, Z, Y, X, 1, 3))
    x_pp = jnp.asarray(np.random.default_rng(2).standard_normal(
        (3, 2, T, Z, Y * X // 2)), jnp.float32)
    (x, res), _ = sprog.verified_exit(op, b, x_pp)
    assert x.shape == b.shape and x.dtype == b.dtype
    old = DiracStaggeredPC(fat, geom, MASS, improved=True, long_links=lng,
                           matpc=matpc, antiperiodic_t=ap).pairs(
        jnp.float32)
    be, bo = even_odd_split(b, geom)
    x_old = even_odd_join(*old.reconstruct_pairs(x_pp, be, bo), geom)
    r = b - DiracStaggered(fat, geom, MASS, improved=True,
                           long_links=lng, antiperiodic_t=ap).M(x_old)
    res_old = float(jnp.linalg.norm(r.ravel())
                    / jnp.linalg.norm(b.ravel()))
    assert _rel(x, x_old) < 1e-6
    assert res_old > 0.5
    assert abs(float(res) - res_old) < 1e-5 * res_old


def test_prepare_program_is_split_and_prepare_pairs():
    dims = LATTICES[0]
    geom = LatticeGeometry(dims)
    fat, lng = _links(6, dims)
    op = _from_links(fat, lng, geom, MASS, EVEN, True)
    b = _field(3, geom.lattice_shape + (1, 3))
    rhs, _ = sprog.prepare(op, b)
    assert _rel(rhs, op.prepare_pairs(*even_odd_split(b, geom))) < 1e-6
    _, hit = sprog.prepare(op.with_mass(0.2), 2.0 * b)
    assert hit          # another mass and source: the same executable


# (c) the term and the programs in the API's context --------------------------

L = 4


@pytest.fixture(scope="module")
def quda(tmp_path_factory):
    """init + a resident 4^4 gauge + a metrics session on the pair route
    (XLA stencil; bf16 sloppy, so the solve is the mixed one the chip
    runs).  ``load_fat_long_quda`` is each test's own."""
    mp = pytest.MonkeyPatch()
    mp.setenv("QUDA_TPU_PACKED", "1")
    mp.setenv("QUDA_TPU_SLOPPY_PRECISION", "half")
    for knob in ("QUDA_TPU_PALLAS", "QUDA_TPU_PRECISION_FORM"):
        mp.delenv(knob, raising=False)
    qconf.reset_cache()
    api.init_quda()
    omet.start(str(tmp_path_factory.mktemp("ks_resident")))
    fat, lng = _links(9, (L,) * 4)
    api.load_gauge_quda(np.asarray(fat),
                        GaugeParam(X=(L,) * 4, cuda_prec="single"))
    yield fat, lng
    omet.stop(flush_files=False)
    api.end_quda()
    mp.undo()
    qconf.reset_cache()


def _param(**kw):
    d = dict(dslash_type="hisq", inv_type="cg", solve_type="normop-pc",
             mass=MASS, tol=1e-6, maxiter=2000, cuda_prec="single",
             cuda_prec_sloppy="auto")
    d.update(kw)
    return InvertParam(**d)


def _counts(name, key):
    out = {}
    for (n, labels), v in omet.snapshot()["counters"].items():
        if n == name:
            lb = dict(labels)
            k = tuple(lb[i] for i in key)
            out[k] = out.get(k, 0) + int(v)
    return out


def _delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _true_residual(fat, lng, b, x, mass=MASS):
    geom = LatticeGeometry((L,) * 4)
    r = b - DiracStaggered(fat, geom, mass, improved=True,
                           long_links=lng).M(x)
    return float(jnp.linalg.norm(r.ravel()) / jnp.linalg.norm(b.ravel()))


def test_load_builds_then_solves_reuse_and_hit(quda):
    fat, lng = quda
    api._drop_resident("ks")
    t0 = _counts("ks_term_total", ("outcome",))
    api.load_fat_long_quda(fat, lng)
    assert _delta(t0, _counts("ks_term_total", ("outcome",))) == {
        ("built",): 1}
    term = api._ctx["ks"]
    assert set(term["ops"]) == {jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)}
    assert ("ks", "resident_ks") in {
        (r["family"], r["field"]) for r in omem.ledger()}
    b = _field(11, (L, L, L, L, 1, 3))
    p0 = _counts("solve_program_total", ("solver", "outcome"))
    p = _param()
    x = api.invert_quda(b, p)
    assert p.converged and p.true_res < 2e-5
    assert abs(_true_residual(fat, lng, b, x) - p.true_res) < 1e-5
    first = _delta(p0, _counts("solve_program_total",
                               ("solver", "outcome")))
    assert set(k[0] for k in first) == {"cg", "verified-exit"}
    # another source and another mass: the same term, the same programs
    p1 = _counts("solve_program_total", ("solver", "outcome"))
    t1 = _counts("ks_term_total", ("outcome",))
    b2 = _field(12, (L, L, L, L, 1, 3))
    p = _param(mass=0.2)
    x2 = api.invert_quda(b2, p)
    assert p.converged
    assert abs(_true_residual(fat, lng, b2, x2, 0.2) - p.true_res) < 1e-5
    assert _delta(p1, _counts("solve_program_total",
                              ("solver", "outcome"))) == {
        ("cg", "hit"): 1, ("verified-exit", "hit"): 1}
    assert _delta(t1, _counts("ks_term_total", ("outcome",))) == {
        ("reused",): 1}
    assert api._ctx["ks"] is term


@pytest.mark.parametrize("knob,value", [
    ("QUDA_TPU_STAGGERED_FORM", "two_pass"),
    ("QUDA_TPU_PRECISION_FORM", "fold"),
    ("QUDA_TPU_FUSED_TAIL", "1")])
def test_a_form_knob_in_the_environment_changes_no_key(quda, knob, value,
                                                       monkeypatch):
    """The staggered hop form is ``served_forms``' and the CG tail has
    one form: neither the term's key nor the single-source solve
    program's reads the environment for them, so a process that sets a
    retired form knob, or the Wilson family's storage form, runs the
    executables it ran without (the term ``reused``, both programs a
    ``hit``)."""
    fat, lng = quda
    api.load_fat_long_quda(fat, lng)        # warm: programs traced
    api.invert_quda(_field(15, (L, L, L, L, 1, 3)), _param())
    key = api._ks_term_key(_param(), False)
    monkeypatch.setenv(knob, value)
    qconf.reset_cache()
    try:
        assert api._ks_term_key(_param(), False) == key
        t0 = _counts("ks_term_total", ("outcome",))
        p0 = _counts("solve_program_total", ("solver", "outcome"))
        p = _param()
        api.invert_quda(_field(16, (L, L, L, L, 1, 3)), p)
        assert p.converged
        assert _delta(t0, _counts("ks_term_total", ("outcome",))) == {
            ("reused",): 1}
        assert _delta(p0, _counts("solve_program_total",
                                  ("solver", "outcome"))) == {
            ("cg", "hit"): 1, ("verified-exit", "hit"): 1}
    finally:
        monkeypatch.delenv(knob)
        qconf.reset_cache()


def test_new_links_rebuild_and_a_new_gauge_drops(quda):
    fat, lng = quda
    api.load_fat_long_quda(fat, lng)
    term = api._ctx["ks"]
    t0 = _counts("ks_term_total", ("outcome",))
    api.load_fat_long_quda(fat, 0.5 * lng)
    assert _delta(t0, _counts("ks_term_total", ("outcome",))) == {
        ("rebuilt",): 1}
    assert api._ctx["ks"] is not term
    # another matpc is another term too
    t1 = _counts("ks_term_total", ("outcome",))
    new = api._resident_staggered(_param(matpc_type="odd-odd"))
    assert _delta(t1, _counts("ks_term_total", ("outcome",))) == {
        ("rebuilt",): 1}
    assert new["ops"][jnp.dtype(jnp.float32)].matpc == ODD
    api.load_gauge_quda(np.asarray(fat),
                        GaugeParam(X=(L,) * 4, cuda_prec="single"))
    assert api._ctx["ks"] is None
    assert ("ks", "resident_ks") not in {
        (r["family"], r["field"]) for r in omem.ledger()}
    assert api._ctx["fat"] is not None      # the links stay


def test_milc_two_calls_reach_the_resident_route(quda):
    """qudaLoadKSLink + qudaInvert, MILC's own two calls, in single."""
    fat, lng = quda
    api.load_fat_long_quda(fat, lng)        # warm: programs traced
    api.invert_quda(_field(13, (L, L, L, L, 1, 3)), _param())
    t0 = _counts("ks_term_total", ("outcome",))
    p0 = _counts("solve_program_total", ("solver", "outcome"))
    milc.qudaLoadKSLink(fat, lng)
    b = _field(14, (L, L, L, L, 1, 3))
    x, info = milc.qudaInvert(MASS, b, tol=1e-6, maxiter=2000,
                              prec="single", sloppy_prec="auto")
    assert _delta(t0, _counts("ks_term_total", ("outcome",))) == {
        ("rebuilt",): 1, ("reused",): 1}
    assert _delta(p0, _counts("solve_program_total",
                              ("solver", "outcome"))) == {
        ("cg", "hit"): 1, ("verified-exit", "hit"): 1}
    assert abs(_true_residual(fat, lng, b, x) - info["true_res"]) < 1e-5
    assert info["true_res"] < 2e-5


# (d) a batch of sources on the same term (invert_multi_src_quda) ------------

N_SRC = 8


def _batch(seed, n=N_SRC):
    return _field(seed, (n, L, L, L, L, 1, 3))


@pytest.fixture
def boundary(quda, request):
    """The resident gauge under the requested fermion t boundary (the
    fat and long links stay loaded); antiperiodic again afterwards."""
    fat, lng = quda
    ap = request.param

    def load(tb):
        api.load_gauge_quda(np.asarray(fat), GaugeParam(
            X=(L,) * 4, cuda_prec="single", t_boundary=tb))
        api.load_fat_long_quda(fat, lng)
    if not ap:
        load("periodic")
    else:
        api.load_fat_long_quda(fat, lng)
    yield ap
    if not ap:
        load("antiperiodic")


@pytest.mark.parametrize("boundary", [True, False], indirect=True,
                         ids=["antiperiodic", "periodic"])
@pytest.mark.parametrize("matpc", ["even-even", "odd-odd"])
def test_batch_equals_single_calls_and_the_canonical_residual(
        quda, boundary, matpc):
    fat, lng = quda
    geom = LatticeGeometry((L,) * 4)
    B = _batch(21)
    p = _param(matpc_type=matpc)
    X = api.invert_multi_src_quda(B, p)
    assert X.shape == B.shape and all(p.converged_multi)
    assert len(p.true_res_multi) == len(p.iter_count_multi) == N_SRC
    d = DiracStaggered(fat, geom, MASS, improved=True, long_links=lng,
                       antiperiodic_t=boundary)
    for i in range(N_SRC):
        want = _rel(d.M(X[i]), B[i])
        assert abs(want - p.true_res_multi[i]) < 1e-5 * max(1.0, want / 1e-5)
        assert p.true_res_multi[i] < 2e-5
    # one at a time through invert_quda: the same systems to the
    # solver's tolerance (tol / 2m on the full system, from either
    # side); on the parity whose single-source programs this module has
    for i in (0, N_SRC - 1) if matpc == "even-even" else ():
        pi = _param(matpc_type=matpc)
        xi = api.invert_quda(B[i], pi)
        assert pi.converged and _rel(X[i], xi) < 4e-5


def test_batch_builds_once_then_reuses_and_hits(quda):
    """Two calls against the same loaded links: the first builds the
    term and traces the three programs, the second builds nothing and
    traces nothing; another mass reuses them; new links rebuild; the
    single-source route afterwards finds the same term."""
    fat, lng = quda
    n = 2                   # a batch size no other test traces
    api.load_fat_long_quda(fat, lng)
    api._drop_resident("ks")
    progs = lambda: _counts("solve_program_total",
                            ("api", "form", "solver", "outcome"))
    key = lambda solver, outcome: ("invert_multi_src_quda",
                                   "staggered_batched_pairs", solver,
                                   outcome)
    three = ("prepare", "batched-cg-pairs", "verified-exit")
    t0, p0 = _counts("ks_term_total", ("outcome",)), progs()
    p = _param()
    api.invert_multi_src_quda(_batch(31, n), p)
    assert all(p.converged_multi)
    assert _delta(t0, _counts("ks_term_total", ("outcome",))) == {
        ("built",): 1}
    assert _delta(p0, progs()) == {key(s, "miss"): 1 for s in three}
    # the batched program took the generic CG step (block.cg_step): no
    # Wilson MRHS kernel, in any epilogue form, was traced for it
    assert _counts("wilson_mrhs_route_total", ("epilogue",)) == {}
    term = api._ctx["ks"]
    # the second call, another mass and other sources
    t1, p1, n1 = _counts("ks_term_total", ("outcome",)), progs(), \
        sprog._traces[0]
    routes = _counts("staggered_mrhs_route_total", ("form",))
    p = _param(mass=0.2)
    B = _batch(32, n)
    X = api.invert_multi_src_quda(B, p)
    assert sprog._traces[0] == n1
    assert _counts("staggered_mrhs_route_total", ("form",)) == routes
    assert routes == {("vmap_xla",): routes[("vmap_xla",)]}
    assert _delta(t1, _counts("ks_term_total", ("outcome",))) == {
        ("reused",): 1}
    assert _delta(p1, progs()) == {key(s, "hit"): 1 for s in three}
    assert api._ctx["ks"] is term
    for i in range(n):
        assert abs(_true_residual(fat, lng, B[i], X[i], 0.2)
                   - p.true_res_multi[i]) < 1e-5
    # the single-source route on the term the batch left
    t2 = _counts("ks_term_total", ("outcome",))
    api.invert_quda(B[0], _param())
    assert _delta(t2, _counts("ks_term_total", ("outcome",))) == {
        ("reused",): 1}
    assert api._ctx["ks"] is term
    # new links: another term, the programs stay
    t3, p3 = _counts("ks_term_total", ("outcome",)), progs()
    api.load_fat_long_quda(fat, 0.5 * lng)
    p = _param()
    api.invert_multi_src_quda(B, p)
    assert _delta(t3, _counts("ks_term_total", ("outcome",))) == {
        ("rebuilt",): 1, ("reused",): 1}
    assert api._ctx["ks"] is not term
    assert _delta(p3, progs()) == {key(s, "hit"): 1 for s in three}


def test_no_canonical_staggered_operator_on_the_batched_route(
        quda, monkeypatch):
    fat, lng = quda
    api.load_fat_long_quda(fat, lng)

    def refuse(*a, **k):
        raise AssertionError("a canonical operator was built")
    monkeypatch.setattr(api, "_build_dirac", refuse)
    p = _param()
    api.invert_multi_src_quda(_batch(41), p)
    assert all(p.converged_multi)


def test_milc_msrc_reaches_the_resident_batched_route(quda):
    """qudaLoadKSLink + qudaInvertMsrc: the second call builds nothing
    and traces nothing, and returns the verified residuals."""
    fat, lng = quda
    milc.qudaLoadKSLink(fat, lng)
    kw = dict(tol=1e-6, maxiter=2000, prec="single", sloppy_prec="auto")
    milc.qudaInvertMsrc(MASS, _batch(51), **kw)
    t0 = _counts("ks_term_total", ("outcome",))
    p0 = _counts("solve_program_total", ("api", "solver", "outcome"))
    B = _batch(52)
    X, info = milc.qudaInvertMsrc(MASS, B, **kw)
    assert _delta(t0, _counts("ks_term_total", ("outcome",))) == {
        ("reused",): 1}
    assert _delta(p0, _counts("solve_program_total",
                              ("api", "solver", "outcome"))) == {
        ("invert_multi_src_quda", s, "hit"): 1
        for s in ("prepare", "batched-cg-pairs", "verified-exit")}
    assert len(info["iters"]) == len(info["true_res"]) == N_SRC
    for i in (0, N_SRC - 1):
        assert abs(_true_residual(fat, lng, B[i], X[i])
                   - info["true_res"][i]) < 1e-5
        assert info["true_res"][i] < 2e-5
