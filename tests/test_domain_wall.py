"""Domain-wall / Möbius operator tests vs host reference + PC consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import EVEN, ODD, LatticeGeometry
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_join, even_odd_split
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.models.domain_wall import (DiracDomainWall, DiracMobius,
                                         DiracMobiusEofa,
                                         DiracMobiusEofaPC, DiracMobiusPC,
                                         eofa_rank_one)
from quda_tpu.ops import blas
from quda_tpu.ops.dwf import apply_sop, identity_sop, m5_sop
from quda_tpu.solvers.cg import cg

from tests.host_reference.dwf_ref import mobius_mat_ref

GEOM = LatticeGeometry((4, 4, 4, 4))
LS = 6
M5, MF = 1.4, 0.04
B5, C5 = 1.5, 0.5


@pytest.fixture(scope="module")
def cfg():
    key = jax.random.PRNGKey(55)
    k1, k2 = jax.random.split(key)
    gauge = GaugeField.random(k1, GEOM).data
    psi = jnp.stack([
        ColorSpinorField.gaussian(jax.random.fold_in(k2, s), GEOM).data
        for s in range(LS)])
    return gauge, psi


@pytest.mark.parametrize("b5,c5", [(1.0, 0.0), (B5, C5)])
def test_mobius_matches_host(cfg, b5, c5):
    gauge, psi = cfg
    d = DiracMobius(gauge, GEOM, LS, M5, MF, b5, c5)
    got = np.asarray(d.M(psi))
    want = mobius_mat_ref(np.asarray(gauge), np.asarray(psi), M5, MF, b5, c5)
    assert np.allclose(got, want, atol=1e-11)


def test_m5_inverse(cfg):
    sop = m5_sop(LS, 3.7, -1.0, MF)
    _, psi = cfg
    back = apply_sop(sop.inv(), apply_sop(sop, psi))
    assert np.allclose(np.asarray(back), np.asarray(psi), atol=1e-12)


def test_mdag_adjointness(cfg):
    gauge, psi = cfg
    d = DiracMobius(gauge, GEOM, LS, M5, MF, B5, C5)
    key = jax.random.PRNGKey(66)
    chi = jnp.stack([
        ColorSpinorField.gaussian(jax.random.fold_in(key, s), GEOM).data
        for s in range(LS)])
    lhs = blas.cdot(chi, d.M(psi))
    rhs = jnp.conjugate(blas.cdot(psi, d.Mdag(chi)))
    assert np.allclose(complex(lhs), complex(rhs), atol=1e-10)


def test_pc_mdag_adjointness(cfg):
    gauge, psi = cfg
    dpc = DiracMobiusPC(gauge, GEOM, LS, M5, MF, B5, C5)
    pe = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(psi)
    key = jax.random.PRNGKey(67)
    chi = jnp.stack([
        ColorSpinorField.gaussian(jax.random.fold_in(key, s), GEOM).data
        for s in range(LS)])
    ce = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(chi)
    lhs = blas.cdot(ce, dpc.M(pe))
    rhs = jnp.conjugate(blas.cdot(pe, dpc.Mdag(ce)))
    assert np.allclose(complex(lhs), complex(rhs), atol=1e-10)


@pytest.mark.parametrize("b5,c5", [(1.0, 0.0), (B5, C5)])
@pytest.mark.parametrize("matpc", [EVEN, ODD])
def test_pc_solve_matches_full(cfg, b5, c5, matpc):
    gauge, psi = cfg
    d = DiracMobius(gauge, GEOM, LS, M5, MF, b5, c5)
    dpc = DiracMobiusPC(gauge, GEOM, LS, M5, MF, b5, c5, matpc=matpc)
    be = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(psi)
    bo = jax.vmap(lambda v: even_odd_split(v, GEOM)[1])(psi)
    b_pc = dpc.prepare(be, bo)
    res = cg(lambda v: dpc.Mdag(dpc.M(v)), dpc.Mdag(b_pc), tol=1e-11,
             maxiter=4000)
    assert bool(res.converged)
    xe, xo = dpc.reconstruct(res.x, be, bo)
    x = jax.vmap(lambda e, o: even_odd_join(e, o, GEOM))(xe, xo)
    rel = float(jnp.sqrt(blas.norm2(psi - d.M(x)) / blas.norm2(psi)))
    assert rel < 1e-8


def test_shamir_class(cfg):
    gauge, psi = cfg
    d1 = DiracDomainWall(gauge, GEOM, LS, M5, MF)
    d2 = DiracMobius(gauge, GEOM, LS, M5, MF, 1.0, 0.0)
    assert np.allclose(np.asarray(d1.M(psi)), np.asarray(d2.M(psi)))


# -- Möbius EOFA (lib/dirac_mobius.cpp:460, dslash_mobius_eofa.cuh) --------

EOFA_KW = dict(mq1=0.04, mq2=0.5, mq3=1.0, eofa_shift=0.3)


def test_eofa_shift_zero_is_mobius(cfg):
    gauge, psi = cfg
    d0 = DiracMobius(gauge, GEOM, LS, M5, MF, B5, C5)
    de = DiracMobiusEofa(gauge, GEOM, LS, M5, MF, B5, C5,
                         mq1=0.04, mq2=0.5, mq3=1.0, eofa_shift=0.0)
    assert np.allclose(np.asarray(d0.M(psi)), np.asarray(de.M(psi)))


def test_eofa_mq2_eq_mq3_vanishes():
    """eofa_norm carries (mq3 - mq2): equal masses -> no correction."""
    r1 = eofa_rank_one(LS, B5, C5, M5, 0.04, 0.7, 0.7, True, 0.3)
    assert np.allclose(r1, 0.0)
    r1b = eofa_rank_one(LS, B5, C5, M5, 0.04, 0.5, 1.0, True, 0.3)
    assert np.abs(r1b).max() > 0


@pytest.mark.parametrize("pm", [True, False])
def test_eofa_rank_one_structure(pm):
    """The correction is a single column on the pm chirality block
    (kernel: out += 0.5 u[s] P_pm psi(pm ? Ls-1 : 0))."""
    r1 = eofa_rank_one(LS, B5, C5, M5, 0.04, 0.5, 1.0, pm, 0.3)
    j = LS - 1 if pm else 0
    mask = np.zeros((LS, LS), bool)
    mask[:, j] = True
    assert np.all(r1[~mask] == 0.0)
    assert np.abs(r1[:, j]).max() > 0


@pytest.mark.parametrize("pm", [True, False])
def test_eofa_mdag_adjointness(cfg, pm):
    gauge, psi = cfg
    d = DiracMobiusEofa(gauge, GEOM, LS, M5, MF, B5, C5, eofa_pm=pm,
                        **EOFA_KW)
    chi = jnp.stack([
        ColorSpinorField.gaussian(jax.random.PRNGKey(500 + s), GEOM).data
        for s in range(LS)])
    lhs = blas.cdot(chi, d.M(psi))
    rhs = jnp.conjugate(blas.cdot(psi, d.Mdag(chi)))
    assert np.allclose(complex(lhs), complex(rhs), atol=1e-10)


@pytest.mark.parametrize("pm", [True, False])
def test_eofa_pc_solve_matches_full(cfg, pm):
    """prepare -> PC normal-equation CG -> reconstruct solves the FULL
    EOFA system (the same consistency contract as plain Möbius PC)."""
    gauge, psi = cfg
    d = DiracMobiusEofa(gauge, GEOM, LS, M5, MF, B5, C5, eofa_pm=pm,
                        **EOFA_KW)
    dpc = DiracMobiusEofaPC(gauge, GEOM, LS, M5, MF, B5, C5, eofa_pm=pm,
                            **EOFA_KW)
    be = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(psi)
    bo = jax.vmap(lambda v: even_odd_split(v, GEOM)[1])(psi)
    b_pc = dpc.prepare(be, bo)
    res = cg(lambda v: dpc.Mdag(dpc.M(v)), dpc.Mdag(b_pc), tol=1e-11,
             maxiter=4000)
    assert bool(res.converged)
    xe, xo = dpc.reconstruct(res.x, be, bo)
    x = jax.vmap(lambda e, o: even_odd_join(e, o, GEOM))(xe, xo)
    rel = float(jnp.sqrt(blas.norm2(psi - d.M(x)) / blas.norm2(psi)))
    assert rel < 1e-8


def test_eofa_through_api():
    """invert_quda with dslash_type='mobius-eofa' solves the full EOFA
    system through prepare/PC-solve/reconstruct."""
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.interfaces.quda_api import init_quda, invert_quda, \
        load_gauge_quda
    key = jax.random.PRNGKey(77)
    k1, k2 = jax.random.split(key)
    gauge = GaugeField.random(k1, GEOM).data
    b = jnp.stack([
        ColorSpinorField.gaussian(jax.random.fold_in(k2, s), GEOM).data
        for s in range(LS)])
    init_quda()
    load_gauge_quda(gauge, GaugeParam(X=GEOM.lattice_shape,
                                      cuda_prec="double"))
    p = InvertParam(dslash_type="mobius-eofa", mass=MF, m5=-M5, Ls=LS,
                    b5=B5, c5=C5, eofa_pm=False, eofa_shift=0.2,
                    eofa_mq1=MF, eofa_mq2=0.5, eofa_mq3=1.0,
                    inv_type="cg", solve_type="normop-pc", tol=1e-10,
                    maxiter=4000, cuda_prec="double",
                    cuda_prec_sloppy="single")
    x = invert_quda(b, p)
    d = DiracMobiusEofa(gauge, GEOM, LS, M5, MF, B5, C5, mq1=MF, mq2=0.5,
                        mq3=1.0, eofa_pm=False, eofa_shift=0.2)
    rel = float(jnp.sqrt(blas.norm2(b - d.M(jnp.asarray(x)))
                         / blas.norm2(b)))
    assert rel < 1e-8


# -- 5d-PC Shamir (lib/dirac_domain_wall.cpp:124, dslash_domain_wall_5d) ---

def test_5dpc_adjointness(cfg):
    from quda_tpu.models.domain_wall import DiracDomainWall5DPC
    gauge, psi = cfg
    dpc = DiracDomainWall5DPC(gauge, GEOM, LS, M5, MF)
    pe, _ = dpc.split5(psi)
    chi = jnp.stack([
        ColorSpinorField.gaussian(jax.random.PRNGKey(700 + s), GEOM).data
        for s in range(LS)])
    ce, _ = dpc.split5(chi)
    lhs = blas.cdot(ce, dpc.M(pe))
    rhs = jnp.conjugate(blas.cdot(pe, dpc.Mdag(ce)))
    assert np.allclose(complex(lhs), complex(rhs), atol=1e-10)


@pytest.mark.parametrize("matpc", [EVEN, ODD])
def test_5dpc_solve_matches_full(cfg, matpc):
    """5d-PC prepare/solve/reconstruct solves the same full Shamir system
    as the (already host-verified) full operator."""
    from quda_tpu.models.domain_wall import DiracDomainWall5DPC
    gauge, psi = cfg
    d = DiracDomainWall(gauge, GEOM, LS, M5, MF)
    dpc = DiracDomainWall5DPC(gauge, GEOM, LS, M5, MF, matpc=matpc)
    be5, bo5 = dpc.split5(psi)
    b_pc = dpc.prepare(be5, bo5)
    res = cg(lambda v: dpc.Mdag(dpc.M(v)), dpc.Mdag(b_pc), tol=1e-11,
             maxiter=6000)
    assert bool(res.converged)
    xe5, xo5 = dpc.reconstruct(res.x, be5, bo5)
    x = dpc.join5(xe5, xo5)
    rel = float(jnp.sqrt(blas.norm2(psi - d.M(x)) / blas.norm2(psi)))
    assert rel < 1e-8


def test_5dpc_matches_4dpc_solution(cfg):
    """The 5d-PC and 4d-PC Schur solves reconstruct the same full
    solution (both are exact decompositions of the same operator)."""
    from quda_tpu.models.domain_wall import DiracDomainWall5DPC
    gauge, psi = cfg
    d5 = DiracDomainWall5DPC(gauge, GEOM, LS, M5, MF)
    be5, bo5 = d5.split5(psi)
    res5 = cg(lambda v: d5.Mdag(d5.M(v)), d5.Mdag(d5.prepare(be5, bo5)),
              tol=1e-11, maxiter=6000)
    x5 = d5.join5(*d5.reconstruct(res5.x, be5, bo5))

    d4 = DiracMobiusPC(gauge, GEOM, LS, M5, MF, 1.0, 0.0)
    be = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(psi)
    bo = jax.vmap(lambda v: even_odd_split(v, GEOM)[1])(psi)
    res4 = cg(lambda v: d4.Mdag(d4.M(v)), d4.Mdag(d4.prepare(be, bo)),
              tol=1e-11, maxiter=6000)
    x4 = jax.vmap(lambda e, o: even_odd_join(e, o, GEOM))(
        *d4.reconstruct(res4.x, be, bo))
    rel = float(jnp.sqrt(blas.norm2(x5 - x4) / blas.norm2(x4)))
    assert rel < 1e-7


def test_5dpc_through_api():
    """invert_quda dslash_type='domain-wall' (QUDA: 5d-PC) end to end."""
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.interfaces.quda_api import init_quda, invert_quda, \
        load_gauge_quda
    key = jax.random.PRNGKey(88)
    k1, k2 = jax.random.split(key)
    gauge = GaugeField.random(k1, GEOM).data
    b = jnp.stack([
        ColorSpinorField.gaussian(jax.random.fold_in(k2, s), GEOM).data
        for s in range(LS)])
    init_quda()
    load_gauge_quda(gauge, GaugeParam(X=GEOM.lattice_shape,
                                      cuda_prec="double"))
    p = InvertParam(dslash_type="domain-wall", mass=MF, m5=-M5, Ls=LS,
                    inv_type="cg", solve_type="normop-pc", tol=1e-10,
                    maxiter=6000, cuda_prec="double",
                    cuda_prec_sloppy="single")
    x = invert_quda(b, p)
    d = DiracDomainWall(gauge, GEOM, LS, M5, MF)
    rel = float(jnp.sqrt(blas.norm2(b - d.M(jnp.asarray(x)))
                         / blas.norm2(b)))
    assert rel < 1e-8


# -- complex-free pair path (the TPU solve representation) -------------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_mobius_pairs_matches_complex(cfg, use_pallas):
    """DiracMobiusPCPairs (XLA and pallas-vmapped stencils) == the
    complex PC operator, M and Mdag."""
    gauge, psi = cfg
    dpc = DiracMobiusPC(gauge.astype(jnp.complex64), GEOM, LS, M5, MF,
                        B5, C5)
    op = dpc.pairs(jnp.float32, use_pallas=use_pallas,
                   pallas_interpret=use_pallas)
    pe = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(psi).astype(
        jnp.complex64)
    for fn in ("M", "Mdag"):
        ref = getattr(dpc, fn)(pe)
        got = getattr(op, fn)(pe)
        err = float(jnp.sqrt(blas.norm2(ref - got) / blas.norm2(ref)))
        assert err < 1e-5, (fn, err)


@pytest.mark.parametrize("case", ["eofa", "mobius-kernel"])
def test_mobius_pairs_mdag_with_gamma5_in_the_blocks(cfg, case, tmp_path):
    """``Mdag_pairs`` carries hop^dag = g5 hop g5 as a sign on the ``-``
    block of its first and last product, and ``x - 1/4 ...`` inside the
    last: equal to the complex ``DiracMobiusPC.Mdag`` (and ``M``), on
    EOFA's dense corrected blocks through the einsum and, on Möbius's
    own, with the s-block kernel forced (interpreted) beside the XLA
    hop, the counter saying which form ran (the einsum on Möbius's own
    blocks: ``test_mobius_pairs_matches_complex``)."""
    from quda_tpu.obs import metrics as omet
    gauge, psi = cfg
    g = gauge.astype(jnp.complex64)
    if case == "eofa":
        dpc = DiracMobiusEofaPC(g, GEOM, LS, M5, MF, B5, C5, mq1=MF,
                                mq2=0.08, mq3=0.2, eofa_shift=0.1)
    else:
        dpc = DiracMobiusPC(g, GEOM, LS, M5, MF, B5, C5)
    op = dpc.pairs(jnp.float32)
    assert op._op_form == "xla"
    form = "einsum"
    if case == "mobius-kernel":
        hop = op._hop_to_pairs
        op._hop_to_pairs = lambda *a, **k: hop(*a, form="xla", **k)
        op._op_form, op._pallas_interpret, form = "pallas", True, "pallas"
    pe = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(psi).astype(
        jnp.complex64)

    def applied():
        return {dict(labels)["form"]: int(v) for (n, labels), v
                in omet.snapshot()["counters"].items()
                if n == "dwf_sblock_route_total"}
    started = not omet.enabled()
    if started:
        omet.start(str(tmp_path))
    try:
        before = applied()
        for fn in ("Mdag", "M"):
            ref = getattr(dpc, fn)(pe)
            got = getattr(op, fn)(pe)
            err = float(jnp.sqrt(blas.norm2(ref - got) / blas.norm2(ref)))
            assert err < 1e-5, (fn, err)
        assert {k: v - before.get(k, 0) for k, v in applied().items()
                if v != before.get(k, 0)} == {form: 6}
    finally:
        if started:
            omet.stop(flush_files=False)


def test_the_form_race_times_m_pairs_under_each_form(cfg, monkeypatch):
    """``_op_form`` picks the hop and the s-blocks alike, so
    formsel.race_ls_hop hands the race the whole ``M_pairs`` of a copy
    pinned to each form (not the hop alone), under a cache key of its
    own, the operator an argument of each candidate, and leaves the
    operator's form as it was."""
    from quda_tpu.models import formsel
    gauge, psi = cfg
    op = DiracMobiusPC(gauge.astype(jnp.complex64), GEOM, LS, M5, MF,
                       B5, C5).pairs(jnp.float32)
    assert op._op_form == "xla"
    seen = {}

    def race_forms(family, raced, cands, args, aux=""):
        seen.update(family=family, raced=raced, cands=cands, args=args,
                    aux=aux)
        return "xla"
    monkeypatch.setattr(formsel, "race_forms", race_forms)
    assert formsel.race_ls_hop("dwf", op, aux="f32|ls4|mpairs") == "xla"
    assert (seen["family"], seen["raced"], seen["aux"]) == (
        "dwf", op, "f32|ls4|mpairs")
    # the pinned copy is the jitted M_pairs' first ARGUMENT (a pytree
    # operand: the links are no constants of a candidate)
    assert {form: (c.func.__wrapped__, c.args[0]._op_form)
            for form, c in seen["cands"].items()} == {
        form: (type(op).M_pairs, form) for form in ("pallas", "xla")}
    assert all(c.args[0] is not op for c in seen["cands"].values())
    assert op._op_form == "xla"
    (x0,) = seen["args"]
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        x0.shape).astype(np.float32))
    assert x0.dtype == op.store_dtype and x0.shape[0] == LS
    got, ref = seen["cands"]["xla"](x), op.M_pairs(x)
    assert float(jnp.max(jnp.abs(got - ref))) <= 1e-6 * float(
        jnp.max(jnp.abs(ref)))


def test_mobius_pairs_full_solve_chain(cfg):
    """Complex-free prepare -> CGNR on MdagM_pairs -> reconstruct solves
    M x = b to the same solution as the complex chain (every Krylov
    iterate a real pair array)."""
    gauge, psi = cfg
    g = gauge.astype(jnp.complex64)
    d = DiracMobius(g, GEOM, LS, M5, MF, B5, C5)
    dpc = DiracMobiusPC(g, GEOM, LS, M5, MF, B5, C5)
    op = dpc.pairs(jnp.float32)
    b = psi.astype(jnp.complex64)
    be = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(b)
    bo = jax.vmap(lambda v: even_odd_split(v, GEOM)[1])(b)
    rhs_pp = op.prepare_pairs(be, bo)
    res = cg(op.MdagM_pairs, op.Mdag_pairs(rhs_pp), tol=1e-7,
             maxiter=4000)
    assert bool(res.converged)
    xe, xo = op.reconstruct_pairs(res.x, be, bo)
    x = jax.vmap(lambda e, o: even_odd_join(e, o, GEOM))(xe, xo)
    rel = float(jnp.sqrt(blas.norm2(b - d.M(x)) / blas.norm2(b)))
    assert rel < 1e-4


def test_eofa_pairs_matches_complex(cfg):
    """The EOFA-corrected chirality blocks flow into the pair operator
    (non-degenerate mq so the rank-one term is active)."""
    gauge, psi = cfg
    dpc = DiracMobiusEofaPC(gauge.astype(jnp.complex64), GEOM, LS, M5, MF,
                            B5, C5, mq1=MF, mq2=0.08, mq3=0.2,
                            eofa_shift=0.1)
    plain = DiracMobiusPC(gauge.astype(jnp.complex64), GEOM, LS, M5, MF,
                          B5, C5)
    op = dpc.pairs(jnp.float32)
    pe = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(psi).astype(
        jnp.complex64)
    ref = dpc.M(pe)
    # the correction must be visible (else this test checks nothing)
    assert float(blas.norm2(ref - plain.M(pe))) > 0
    got = op.M(pe)
    err = float(jnp.sqrt(blas.norm2(ref - got) / blas.norm2(ref)))
    assert err < 1e-5


def test_mobius_pairs_api_invert(monkeypatch):
    """invert_quda routes 4d-PC Möbius CG solves through the complex-free
    pair adapter at single precision and converges to the true solution."""
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.interfaces import quda_api as api

    # force the packed/pair route (the default only on real TPU)
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    geom = LatticeGeometry((4, 4, 4, 4))
    key = jax.random.PRNGKey(77)
    U = GaugeField.random(key, geom).data.astype(jnp.complex64)
    ls = 4
    b = np.asarray(jnp.stack([
        ColorSpinorField.gaussian(jax.random.fold_in(key, s), geom).data
        for s in range(ls)])).astype(np.complex64)
    api.init_quda()
    api.load_gauge_quda(np.asarray(U), GaugeParam(X=(4, 4, 4, 4)))
    p = InvertParam(dslash_type="mobius", kappa=0.0, mass=MF, m5=M5,
                    Ls=ls, b5=B5, c5=C5, inv_type="cg",
                    solve_type="direct-pc", cuda_prec="single",
                    cuda_prec_sloppy="single", tol=1e-6, maxiter=4000)
    x = api.invert_quda(b, p)
    assert p.true_res < 1e-5
    api.end_quda()


def test_mobius_pairs_api_adapter_selected(monkeypatch):
    """The dwf_pairs gate really selects the pair adapter (guards the
    routing logic, not the numerics — one unconverged iteration)."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    captured = {}
    orig = api._PairOpSolve.__init__

    def spy(self, dpc, use_pallas, pallas_interpret=False):
        captured["hit"] = True
        orig(self, dpc, use_pallas, pallas_interpret)

    monkeypatch.setattr(api._PairOpSolve, "__init__", spy)
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    geom = LatticeGeometry((4, 4, 4, 4))
    key = jax.random.PRNGKey(78)
    U = GaugeField.random(key, geom).data.astype(jnp.complex64)
    ls = 4
    b = np.asarray(jnp.stack([
        ColorSpinorField.gaussian(jax.random.fold_in(key, s), geom).data
        for s in range(ls)])).astype(np.complex64)
    api.init_quda()
    api.load_gauge_quda(np.asarray(U), GaugeParam(X=(4, 4, 4, 4)))
    p = InvertParam(dslash_type="mobius", kappa=0.0, mass=MF, m5=M5,
                    Ls=ls, b5=B5, c5=C5, inv_type="cg",
                    solve_type="direct-pc", cuda_prec="single",
                    cuda_prec_sloppy="single", tol=1e-6, maxiter=1)
    api.invert_quda(b, p)
    api.end_quda()
    assert captured.get("hit"), "pair adapter was not selected"


def test_dw5dpc_pairs_matches_complex(cfg):
    """5d-PC pair operator == the complex DiracDomainWall5DPC (M, Mdag,
    prepare, reconstruct) — the last PC family to go complex-free."""
    from quda_tpu.models.domain_wall import DiracDomainWall5DPC
    gauge, psi = cfg
    dpc = DiracDomainWall5DPC(gauge.astype(jnp.complex64), GEOM, LS,
                              M5, MF)
    op = dpc.pairs(jnp.float32)
    be, bo = dpc.split5(psi.astype(jnp.complex64))
    for fn in ("M", "Mdag"):
        ref = getattr(dpc, fn)(be)
        got = getattr(op, fn)(be)
        err = float(jnp.sqrt(blas.norm2(ref - got) / blas.norm2(ref)))
        assert err < 1e-5, (fn, err)
    rr = dpc.prepare(be, bo)
    gg = op._from_pairs(op.prepare_pairs(be, bo), jnp.complex64)
    assert float(jnp.sqrt(blas.norm2(rr - gg) / blas.norm2(rr))) < 1e-5
    xe_r, xo_r = dpc.reconstruct(be, be, bo)
    xe_g, xo_g = op.reconstruct_pairs(op._to_pairs(be), be, bo)
    err = float(jnp.sqrt(
        (blas.norm2(xe_r - xe_g) + blas.norm2(xo_r - xo_g))
        / (blas.norm2(xe_r) + blas.norm2(xo_r))))
    assert err < 1e-5


def test_dw5dpc_pairs_api_adapter_selected(monkeypatch):
    """invert_quda routes plain 'domain-wall' (5d-PC) single-precision
    CG through the pair adapter, with the slice-aligned split5 hook."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    captured = {}
    orig = api._PairOpSolve.__init__

    def spy(self, dpc, use_pallas, pallas_interpret=False):
        captured["hit"] = True
        orig(self, dpc, use_pallas, pallas_interpret)

    monkeypatch.setattr(api._PairOpSolve, "__init__", spy)
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    geom = LatticeGeometry((4, 4, 4, 4))
    key = jax.random.PRNGKey(88)
    U = GaugeField.random(key, geom).data.astype(jnp.complex64)
    ls = 4
    b = np.asarray(jnp.stack([
        ColorSpinorField.gaussian(jax.random.fold_in(key, s), geom).data
        for s in range(ls)])).astype(np.complex64)
    api.init_quda()
    api.load_gauge_quda(np.asarray(U), GaugeParam(X=(4, 4, 4, 4)))
    p = InvertParam(dslash_type="domain-wall", kappa=0.0, mass=MF,
                    m5=-M5, Ls=ls, inv_type="cg",
                    solve_type="direct-pc", cuda_prec="single",
                    cuda_prec_sloppy="single", tol=1e-6, maxiter=4000)
    api.invert_quda(b, p)
    api.end_quda()
    assert captured.get("hit"), "pair adapter was not selected"
    assert p.true_res < 1e-5
