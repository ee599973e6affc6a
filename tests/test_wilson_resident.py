"""The resident Wilson pair operators (``_resident_wilson``) and the
verified-exit program (solvers/program.verified_exit) against what they
replaced: ``DiracWilsonPC(...).packed().pairs(...)`` built per call, and
``reconstruct_pairs`` + join + the canonical complex64 ``DiracWilson.M``.

CPU, seeded random SU(3) links, 4^4 and one odd shape.  The operators
here run the XLA pair stencil (what is compared is the program and the
term, not the kernels); the API cases that need the interpreted kernels
share the warm-up of tests/test_solve_program.py and live there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.geometry import EVEN, ODD, LatticeGeometry
from quda_tpu.fields.spinor import even_odd_join, even_odd_split
from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.models.wilson import (DiracWilson, DiracWilsonPC,
                                    DiracWilsonPCPackedSloppy,
                                    hop_route_knobs)
from quda_tpu.obs import memory as omem
from quda_tpu.obs import metrics as omet
from quda_tpu.ops import wilson as wops
from quda_tpu.ops import wilson_packed as wpk
from quda_tpu.ops.boundary import apply_t_boundary
from quda_tpu.solvers import program as sprog
from quda_tpu.utils import config as qconf

KAPPA = 0.12
LATTICES = [(4, 4, 4, 4), (4, 6, 2, 8)]


def _gauge(seed, dims):
    g = GaugeField.random(jax.random.PRNGKey(seed), LatticeGeometry(dims))
    return g.data.astype(jnp.complex64)


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _from_links(g, geom, kappa, matpc, ap, store=jnp.float32):
    """The operator as the resident term assembles it."""
    links = wpk.pack_gauge_eo(wops.split_gauge_eo(
        apply_t_boundary(g, geom, -1 if ap else 1), geom))
    return DiracWilsonPCPackedSloppy.from_packed(
        geom, links, kappa, matpc, store, tb_sign=ap)


def _fields(seed, shape):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape)
                       + 1j * rng.standard_normal(shape), jnp.complex64)


def _old_exit(g, geom, kappa, matpc, ap, b, x_pp):
    """What the API did before: reconstruct on the per-call pair
    operator, join, canonical complex64 ``M`` on the full lattice."""
    op = DiracWilsonPC(g, geom, kappa, ap, matpc).packed().pairs(
        jnp.float32)
    be, bo = even_odd_split(b, geom)
    x = even_odd_join(*op.reconstruct_pairs(x_pp, be, bo), geom)
    r = b - DiracWilson(g, geom, kappa, ap).M(x)
    return x, float(jnp.linalg.norm(r.ravel())
                    / jnp.linalg.norm(b.ravel()))


# (a) the program against the path it replaced --------------------------------

@pytest.mark.parametrize("form", ["single", "batched"])
@pytest.mark.parametrize("ap", [True, False],
                         ids=["antiperiodic", "periodic"])
@pytest.mark.parametrize("matpc", [EVEN, ODD], ids=["even", "odd"])
@pytest.mark.parametrize("dims", LATTICES, ids=["4x4x4x4", "4x6x2x8"])
def test_verified_exit_equals_reconstruct_and_canonical_m(dims, matpc, ap,
                                                          form):
    """Any pair-form field in (not a solution: the residual is then
    O(1), and f32 rounding is 1e-7 of it), solution and residual out."""
    geom = LatticeGeometry(dims)
    g = _gauge(5, dims)
    op = _from_links(g, geom, KAPPA, matpc, ap)
    T, Z, Y, X = geom.lattice_shape
    n = 1 if form == "single" else 3
    b = _fields(1, (n, T, Z, Y, X, 4, 3))
    x_pp = jnp.asarray(np.random.default_rng(2).standard_normal(
        (n, 4, 3, 2, T, Z, Y * X // 2)), jnp.float32)
    if form == "single":
        (x, res), _ = sprog.verified_exit(op, b[0], x_pp[0])
        x, res = x[None], res[None]
    else:
        (x, res), _ = sprog.verified_exit(op, b, x_pp)
    assert x.shape == b.shape and x.dtype == b.dtype
    assert res.shape == (n,) and res.dtype == jnp.float32
    for i in range(n):
        x_old, res_old = _old_exit(g, geom, KAPPA, matpc, ap, b[i],
                                   x_pp[i])
        assert _rel(x[i], x_old) < 1e-6
        assert res_old > 0.5
        # two f32 sums of 1e5 squares in another order: 1e-6 and a bit
        assert abs(float(res[i]) - res_old) < 5e-6 * res_old


@pytest.mark.parametrize("form", ["single", "batched"])
def test_a_perturbed_solution_is_reported_not_hidden(form):
    """An exact pair-form solution reads a residual at f32 rounding; the
    same one with one parity-p site moved reads what the canonical check
    of the RETURNED field reads."""
    dims = LATTICES[0]
    geom = LatticeGeometry(dims)
    g = _gauge(6, dims)
    op = _from_links(g, geom, KAPPA, EVEN, True)
    dpc = DiracWilsonPC(g, geom, KAPPA, True, EVEN)
    b = _fields(3, geom.lattice_shape + (4, 3))
    be, bo = even_odd_split(b, geom)
    from quda_tpu.solvers.cg import cg
    x_p = cg(dpc.MdagM, dpc.Mdag(dpc.prepare(be, bo)), tol=1e-7,
             maxiter=500).x
    x_pp = wpk.to_packed_pairs(wpk.pack_spinor(x_p), jnp.float32)
    bad = x_pp.at[0, 0, 0, 1, 2, 3].add(0.5)
    if form == "single":
        (_, good), _ = sprog.verified_exit(op, b, x_pp)
        (x, res), _ = sprog.verified_exit(op, b, bad)
    else:
        (x, res), _ = sprog.verified_exit(
            op, jnp.stack([b, b]), jnp.stack([x_pp, bad]))
        good, x, res = res[0], x[1], res[1]
    assert float(good) < 5e-7
    r = b - DiracWilson(g, geom, KAPPA, True).M(x)
    want = float(jnp.linalg.norm(r.ravel()) / jnp.linalg.norm(b.ravel()))
    assert want > 1e-3
    assert abs(float(res) - want) < 1e-5 * want


# (b) the operator the term holds is the one built per call -------------------

@pytest.mark.parametrize("store", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_from_packed_is_the_per_call_operator(store):
    dims = LATTICES[1]
    geom = LatticeGeometry(dims)
    g = _gauge(7, dims)
    old = DiracWilsonPC(g, geom, KAPPA, True, ODD).packed().pairs(store)
    new = _from_links(g, geom, 0.0, ODD, True, store).with_kappa(KAPPA)
    assert new.program_signature == old.program_signature
    assert (jax.tree_util.tree_structure(new)
            == jax.tree_util.tree_structure(old))
    T, Z, Y, X = geom.lattice_shape
    v = jnp.asarray(np.random.default_rng(4).standard_normal(
        (4, 3, 2, T, Z, Y * X // 2)), store)
    np.testing.assert_array_equal(
        np.asarray(new.MdagM_pairs(v).astype(jnp.float32)),
        np.asarray(old.MdagM_pairs(v).astype(jnp.float32)))


def test_with_kappa_shares_the_arrays_and_the_key():
    dims = LATTICES[0]
    op = _from_links(_gauge(8, dims), LatticeGeometry(dims), 0.0, EVEN,
                     True)
    other = op.with_kappa(0.11)
    assert other.kappa == 0.11 and op.kappa == 0.0
    assert other.gauge_eo_pp is op.gauge_eo_pp
    assert other.program_signature == op.program_signature


# (c) the term in the API's context -------------------------------------------

L = 4


@pytest.fixture(scope="module")
def quda(tmp_path_factory):
    """init + a resident 4^4 gauge + a metrics session; the term is
    asked for directly, so no kernel is interpreted and nothing solves."""
    mp = pytest.MonkeyPatch()
    mp.setenv("QUDA_TPU_PACKED", "1")
    for knob in ("QUDA_TPU_PALLAS", "QUDA_TPU_PRECISION_FORM",
                 "QUDA_TPU_RECONSTRUCT"):
        mp.delenv(knob, raising=False)
    qconf.reset_cache()
    api.init_quda()
    omet.start(str(tmp_path_factory.mktemp("wilson_resident")))
    api.load_gauge_quda(np.asarray(_gauge(9, (L,) * 4)),
                        GaugeParam(X=(L,) * 4, cuda_prec="single"))
    yield
    omet.stop(flush_files=False)
    api.end_quda()
    mp.undo()
    qconf.reset_cache()


def _param(**kw):
    d = dict(dslash_type="wilson", inv_type="cg", solve_type="normop-pc",
             kappa=KAPPA, tol=1e-6, maxiter=500, cuda_prec="single",
             cuda_prec_sloppy="half")
    d.update(kw)
    return InvertParam(**d)


def _outcomes():
    out = {}
    for (name, labels), v in omet.snapshot()["counters"].items():
        if name == "wilson_term_total":
            out[dict(labels)["outcome"]] = int(v)
    return out


def _ask(before, *args, **kw):
    """One ``_resident_wilson`` and the outcome it counted."""
    term = api._resident_wilson(*args, **kw)
    now = _outcomes()
    (outcome,) = [k for k, v in now.items() if v != before.get(k, 0)]
    before.update(now)
    return term, outcome


CHANGES = {
    # what the term depends on -> (InvertParam fields, environment)
    "matpc": (dict(matpc_type="odd-odd"), {}),
    "pallas_route": ({}, {"QUDA_TPU_PALLAS": "1"}),
    "precision_form": ({}, {"QUDA_TPU_PALLAS": "1",
                            "QUDA_TPU_PRECISION_FORM": "r12"}),
}


@pytest.mark.parametrize("what", sorted(CHANGES))
def test_term_is_kept_until_what_it_depends_on_changes(quda, what,
                                                       monkeypatch):
    api._drop_resident("wilson")
    seen = _outcomes()
    term, outcome = _ask(seen, _param())
    assert outcome == "built"
    assert ("wilson", "resident_wilson") in {
        (r["family"], r["field"]) for r in omem.ledger()}
    # another kappa, tolerance or source: the same term, nothing new
    again, outcome = _ask(seen, _param(kappa=0.1, tol=1e-4))
    assert outcome == "reused" and again is term
    assert list(term["ops"]) == [jnp.dtype(jnp.float32)]
    # a sloppy storage the term lacks is added to it, not a rebuild
    again, outcome = _ask(seen, _param(), (jnp.bfloat16,))
    assert outcome == "reused" and again is term
    assert set(term["ops"]) == {jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)}
    fields, env = CHANGES[what]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    qconf.reset_cache()
    try:
        new, outcome = _ask(seen, _param(**fields))
        assert outcome == "rebuilt" and new is not term
        assert api._ctx["wilson"] is new
        op = new["ops"][jnp.dtype(jnp.float32)]
        if what == "matpc":
            assert op.matpc == ODD
        elif what == "pallas_route":
            assert op.use_pallas and op._pallas_interpret
        else:
            assert op._precision_form == "r12"
    finally:
        monkeypatch.undo()
        qconf.reset_cache()
        api._drop_resident("wilson")


def test_the_retired_version_knob_changes_nothing(quda, monkeypatch):
    """There is one Wilson kernel generation: QUDA_TPU_PALLAS_VERSION is
    not read any more, so setting it keeps the resident term, and no
    version is part of the operator's program signature."""
    api._drop_resident("wilson")
    monkeypatch.setenv("QUDA_TPU_PALLAS", "1")
    qconf.reset_cache()
    try:
        seen = _outcomes()
        term, outcome = _ask(seen, _param())
        assert outcome == "built"
        monkeypatch.setenv("QUDA_TPU_PALLAS_VERSION", "3")
        qconf.reset_cache()
        again, outcome = _ask(seen, _param())
        assert outcome == "reused" and again is term
        op = term["ops"][jnp.dtype(jnp.float32)]
        assert op.use_pallas and op._u_bw is not None
        assert not hasattr(op, "_pallas_version")
        assert len(hop_route_knobs()) == 2
        assert "_pallas_version" not in op._PROGRAM_STATIC
    finally:
        monkeypatch.undo()
        qconf.reset_cache()
        api._drop_resident("wilson")


@pytest.mark.parametrize("how", ["load_gauge_quda", "free_gauge_quda"])
def test_a_gauge_that_goes_takes_the_term_with_it(quda, how):
    api._resident_wilson(_param())
    assert api._ctx["wilson"] is not None
    if how == "free_gauge_quda":
        api.free_gauge_quda()
    else:
        api.load_gauge_quda(np.asarray(_gauge(10, (L,) * 4)),
                            GaugeParam(X=(L,) * 4, cuda_prec="single"))
    try:
        assert api._ctx["wilson"] is None
        assert ("wilson", "resident_wilson") not in {
            (r["family"], r["field"]) for r in omem.ledger()}
    finally:
        api.load_gauge_quda(np.asarray(_gauge(9, (L,) * 4)),
                            GaugeParam(X=(L,) * 4, cuda_prec="single"))
