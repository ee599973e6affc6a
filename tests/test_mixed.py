"""Mixed-precision solver tests: reliable updates + iterative refinement.

Sloppy = complex64, precise = complex128 (the CPU analog of the TPU's
f32-precise / bf16-sloppy pairing).  Plain single-precision CG stalls well
above 1e-10; the mixed schemes must reach it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import EVEN, LatticeGeometry
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.models.wilson import DiracWilsonPC
from quda_tpu.ops import blas
from quda_tpu.solvers.cg import cg
from quda_tpu.solvers.mixed import cg_reliable, solve_refined

GEOM = LatticeGeometry((8, 8, 8, 8))
KAPPA = 0.125
TOL = 1e-10


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(21)
    k1, k2 = jax.random.split(key)
    gauge = GaugeField.random(k1, GEOM).data
    b_full = ColorSpinorField.gaussian(k2, GEOM).data
    dpc = DiracWilsonPC(gauge, GEOM, KAPPA)
    be, bo = even_odd_split(b_full, GEOM)
    rhs = dpc.Mdag(dpc.prepare(be, bo))
    dpc_lo = DiracWilsonPC(gauge.astype(jnp.complex64), GEOM, KAPPA)
    return dpc, dpc_lo, rhs


def test_pure_single_stalls(problem):
    """Sanity: single-precision CG cannot reach a TRUE residual of 1e-10
    (its recursive residual under-reports) — motivates mixing."""
    dpc, dpc_lo, rhs = problem
    res = cg(dpc_lo.MdagM, rhs.astype(jnp.complex64), tol=TOL, maxiter=500)
    true_r2 = blas.norm2(rhs - dpc.MdagM(res.x.astype(jnp.complex128)))
    assert float(jnp.sqrt(true_r2 / blas.norm2(rhs))) > 10 * TOL


def test_cg_reliable_reaches_double_tol(problem):
    dpc, dpc_lo, rhs = problem
    res = jax.jit(lambda b: cg_reliable(
        dpc.MdagM, dpc_lo.MdagM, b, jnp.complex64, tol=TOL,
        maxiter=2000))(rhs)
    assert bool(res.converged)
    r2 = blas.norm2(rhs - dpc.MdagM(res.x))
    assert float(jnp.sqrt(r2 / blas.norm2(rhs))) < 2 * TOL


def test_refinement_reaches_double_tol(problem):
    dpc, dpc_lo, rhs = problem
    inner = jax.jit(lambda r: cg(dpc_lo.MdagM, r, tol=1e-5, maxiter=500).x)
    res = solve_refined(dpc.MdagM, inner, rhs, jnp.complex64, tol=TOL)
    assert bool(res.converged)
    r2 = blas.norm2(rhs - dpc.MdagM(res.x))
    assert float(jnp.sqrt(r2 / blas.norm2(rhs))) < 2 * TOL


def test_reliable_iters_comparable_to_pure_double(problem):
    """Reliable-update CG shouldn't need dramatically more iterations."""
    dpc, dpc_lo, rhs = problem
    res_d = cg(dpc.MdagM, rhs, tol=TOL, maxiter=2000)
    res_m = cg_reliable(dpc.MdagM, dpc_lo.MdagM, rhs, jnp.complex64,
                        tol=TOL, maxiter=2000)
    assert int(res_m.iters) < 3 * int(res_d.iters)


# -- bf16/int8 pair-storage sloppy path (ops/pair.py) ----------------------

def test_pair_stencil_matches_complex(problem):
    """bf16 pair-form PC Wilson matvec tracks the exact operator to the
    bf16 rounding level (and int8 block-float to its scale)."""
    dpc, _, rhs = problem
    v = rhs.astype(jnp.complex64)
    exact = dpc.M(rhs)
    for prec, bound in (("half", 0.02), ("quarter", 0.05)):
        sl = dpc.sloppy(prec)
        err = blas.norm2(exact - sl.M(v).astype(rhs.dtype))
        assert float(jnp.sqrt(err / blas.norm2(exact))) < bound


def test_cg_reliable_bf16_pairs_reaches_double_tol(problem):
    """The whole sloppy loop runs on bf16 pair storage (QUDA half) and
    still reaches a precise-level 1e-10 true residual, at a comparable
    iteration count to pure precise CG."""
    from quda_tpu.solvers.mixed import pair_codec
    dpc, _, rhs = problem
    sl = dpc.sloppy("half")
    codec = pair_codec(jnp.bfloat16, rhs.dtype)
    res = cg_reliable(dpc.MdagM, sl.MdagM_pairs, rhs, tol=TOL,
                      maxiter=2000, codec=codec)
    assert bool(res.converged)
    r2 = blas.norm2(rhs - dpc.MdagM(res.x))
    assert float(jnp.sqrt(r2 / blas.norm2(rhs))) < 2 * TOL
    res_d = cg(dpc.MdagM, rhs, tol=TOL, maxiter=2000)
    assert int(res.iters) < 2 * int(res_d.iters)


def _parent_cg_reliable_loop(matvec_hi, matvec_lo, b, tol, maxiter, delta,
                             codec):
    """``mixed.cg_reliable_loop`` as it stood before the loop took a
    step (PR 49's, without history, sentinel and fault): the reference
    the generic step is held to, bit for bit."""
    b2 = blas.norm2(b)
    stop = (tol ** 2) * b2
    rdt = jnp.zeros((), b.dtype).real.dtype
    r_lo = codec.down(b)

    def cond(c):
        return jnp.logical_and(c["r2"] > stop, c["k"] < maxiter)

    def body(c):
        Ap = matvec_lo(c["p"])
        pAp = codec.redot(c["p"], Ap).astype(rdt)
        alpha = c["r2_lo"] / jnp.maximum(pAp, jnp.finfo(rdt).tiny)
        x_lo = codec.axpy(alpha, c["p"], c["x_lo"])
        r_lo, r2_new = codec.axpy_norm2(-alpha, Ap, c["r_lo"])
        r2_new = r2_new.astype(rdt)
        beta = r2_new / c["r2_lo"]
        p = codec.axpy(beta, c["p"], r_lo)
        r2max = jnp.maximum(c["r2max"], r2_new)
        do_reliable = jnp.logical_or(r2_new < (delta ** 2) * r2max,
                                     r2_new < stop)

        def reliable(_):
            x_new = c["x"] + codec.up(x_lo)
            r_true = c["b"] - matvec_hi(x_new)
            r2_true = blas.norm2_comp(r_true).astype(rdt)
            return dict(c, x=x_new, r2=r2_true, r_lo=codec.down(r_true),
                        p=codec.down(r_true), x_lo=jnp.zeros_like(x_lo),
                        r2_lo=r2_true, r2max=r2_true, k=c["k"] + 1)

        def keep(_):
            return dict(c, p=p, r_lo=r_lo, x_lo=x_lo, r2_lo=r2_new,
                        r2=r2_new.astype(rdt), r2max=r2max, k=c["k"] + 1)
        return jax.lax.cond(do_reliable, reliable, keep, None)

    r2 = b2.astype(rdt)
    out = jax.lax.while_loop(cond, body, dict(
        b=b, x=jnp.zeros_like(b), r2=r2, r_lo=r_lo, p=r_lo,
        x_lo=jnp.zeros_like(r_lo), r2_lo=r2, r2max=r2, k=jnp.int32(0)))
    x_fin = out["x"] + codec.up(out["x_lo"])
    return x_fin, out["k"], blas.norm2_comp(b - matvec_hi(x_fin))


def _dense_problem():
    """A dense Hermitian positive matrix of 48 unknowns, condition
    ~1e3, precise (complex64) and under the bf16 pair codec."""
    from quda_tpu.ops import pair as pops
    from quda_tpu.solvers.mixed import pair_codec
    rng = np.random.default_rng(50)
    n = 48
    q, _ = np.linalg.qr(rng.standard_normal((n, n))
                        + 1j * rng.standard_normal((n, n)))
    a = jnp.asarray((q * np.logspace(0, 3, n)) @ q.conj().T, jnp.complex64)
    b = jnp.asarray(rng.standard_normal(n) + 1j * rng.standard_normal(n),
                    jnp.complex64)
    hi = lambda x: a @ x
    lo = lambda x: pops.to_pairs(a @ pops.from_pairs(x, jnp.complex64),
                                 jnp.bfloat16)
    return hi, lo, b, pair_codec(jnp.bfloat16, jnp.complex64)


@pytest.mark.parametrize("maxiter,stretches", [
    (400, False), (400, True), (7, True)])
def test_loop_on_the_generic_step_is_the_parents_loop_bit_for_bit(
        maxiter, stretches):
    """``cg_reliable_loop`` applies a step since PR 50.  On ``cg_step``
    of a matvec and the codec it is the loop it was: the same ``x``,
    ``k`` and ``r2`` to the bit on a dense Hermitian positive matrix
    under the bf16 pair codec (no kernel, 48 unknowns, condition ~1e3:
    some forty iterations and several reliable updates).  So is the
    form an operator's own step runs in (``stretches``: the sloppy
    iterations in a loop of their own between reliable updates, no
    ``lax.cond``), also where ``maxiter`` ends the solve in the middle
    of a stretch (the update that nobody asked for folds ``x`` as the
    exit would)."""
    from quda_tpu.solvers.mixed import cg_reliable_loop, cg_step
    hi, lo, b, codec = _dense_problem()
    tol, delta = 1e-5, 0.1
    want = jax.jit(lambda b: _parent_cg_reliable_loop(
        hi, lo, b, tol, maxiter, delta, codec))(b)
    got = jax.jit(lambda b: cg_reliable_loop(
        hi, cg_step(lo, codec), b, tol, maxiter, delta, codec, False,
        None, stretches=stretches))(b)
    if maxiter == 7:
        assert int(want[1]) == 7 and not bool(got.converged)
    else:
        assert 10 < int(want[1]) < maxiter and bool(got.converged)
    assert int(got.iters) == int(want[1])
    assert bool(jnp.all(got.x == want[0]))
    assert float(got.r2) == float(want[2])


@pytest.mark.parametrize("maxiter", [400, 7])
def test_stretches_keep_the_history_and_the_sentinel_of_the_one_loop(
        maxiter):
    """With the history recorded and the breakdown sentinel in the
    carry the two forms of ``cg_reliable_loop`` agree entry for entry:
    residuals, reliable-update flags, iterations, verdict; where
    ``maxiter`` cuts a stretch the update nobody asked for is in
    neither history."""
    from quda_tpu.robust.sentinel import Sentinel
    from quda_tpu.solvers.mixed import cg_reliable_loop, cg_step
    hi, lo, b, codec = _dense_problem()
    one, two = (jax.jit(lambda b, s=s: cg_reliable_loop(
        hi, cg_step(lo, codec), b, 1e-5, maxiter, 0.1, codec, True,
        Sentinel(), stretches=s))(b) for s in (False, True))
    assert int(one.iters) == int(two.iters) > 6
    assert bool(one.converged) == bool(two.converged) == (maxiter == 400)
    np.testing.assert_array_equal(np.asarray(one.history["r2"]),
                                  np.asarray(two.history["r2"]))
    np.testing.assert_array_equal(np.asarray(one.history["reliable"]),
                                  np.asarray(two.history["reliable"]))
    assert int(np.sum(np.asarray(one.history["reliable"]))) >= (
        2 if maxiter == 400 else 0)
    assert bool(jnp.all(one.x == two.x)) and float(one.r2) == float(two.r2)
    assert int(one.breakdown) == int(two.breakdown) == 0


def test_cg_reliable_int8_pairs_converges(problem):
    """Quarter (int8 block-float gauge) sloppy operator still converges
    under reliable updates."""
    from quda_tpu.solvers.mixed import pair_codec
    dpc, _, rhs = problem
    sl = dpc.sloppy("quarter")
    codec = pair_codec(jnp.bfloat16, rhs.dtype)
    res = cg_reliable(dpc.MdagM, sl.MdagM_pairs, rhs, tol=TOL,
                      maxiter=4000, codec=codec)
    assert bool(res.converged)
    r2 = blas.norm2(rhs - dpc.MdagM(res.x))
    assert float(jnp.sqrt(r2 / blas.norm2(rhs))) < 2 * TOL


def test_api_mixed_bicgstab_refined(problem):
    """BiCGStab with bf16-internal inner solves through the API-level
    defect-correction path converges on the non-Hermitian PC system."""
    from quda_tpu.solvers.bicgstab import bicgstab
    from quda_tpu.solvers.mixed import solve_refined
    dpc, _, _ = problem
    key = jax.random.PRNGKey(5)
    b = even_odd_split(ColorSpinorField.gaussian(key, GEOM).data, GEOM)[0]
    sl = dpc.sloppy("half")
    inner = jax.jit(lambda r: bicgstab(sl.M, r, tol=1e-3, maxiter=500).x)
    res = solve_refined(dpc.M, inner, b, jnp.complex64, tol=1e-9)
    assert bool(res.converged)
    r2 = blas.norm2(b - dpc.M(res.x))
    assert float(jnp.sqrt(r2 / blas.norm2(b))) < 2e-9


def test_pair_complex_algebra_and_full_stencil(problem):
    """pair_cdot / pair_caxpy match the complex BLAS, and the full-lattice
    pair stencil matches the canonical full dslash at bf16 accuracy."""
    from quda_tpu.models.wilson import DiracWilson
    from quda_tpu.ops import pair as pops
    from quda_tpu.ops import wilson as wops
    key = jax.random.PRNGKey(9)
    k1, k2, k3 = jax.random.split(key, 3)
    x = (jax.random.normal(k1, (5, 7)) + 1j * jax.random.normal(k2, (5, 7))
         ).astype(jnp.complex64)
    y = (jax.random.normal(k3, (5, 7)) + 0.5j).astype(jnp.complex64)
    xp = pops.to_pairs(x, jnp.float32)
    yp = pops.to_pairs(y, jnp.float32)
    assert np.allclose(complex(pops.pair_cdot(xp, yp)),
                       complex(blas.cdot(x, y)), rtol=1e-5)
    a = 0.3 - 1.7j
    got = pops.from_pairs(pops.pair_caxpy(a, xp, yp), jnp.complex64)
    assert np.allclose(np.asarray(got), np.asarray(y + a * x), rtol=1e-5)

    geom = GEOM
    gauge = GaugeField.random(jax.random.PRNGKey(1), geom).data
    d = DiracWilson(gauge, geom, KAPPA)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(2), geom).data
    ref = wops.dslash_full(d.gauge, psi.astype(jnp.complex64))
    gst = pops.encode_gauge(d.gauge.astype(jnp.complex64), "half")
    out = pops.from_pairs(
        pops.dslash_full_pairs(gst, pops.to_pairs(psi, jnp.bfloat16)),
        jnp.complex64)
    rel = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert rel < 0.02


@pytest.fixture(scope="module")
def api_ctx():
    from quda_tpu.interfaces.params import GaugeParam
    from quda_tpu.interfaces.quda_api import init_quda, load_gauge_quda
    key = jax.random.PRNGKey(21)
    k1, k2 = jax.random.split(key)
    gauge = GaugeField.random(k1, GEOM).data
    b = ColorSpinorField.gaussian(k2, GEOM).data
    init_quda()
    load_gauge_quda(gauge, GaugeParam(X=GEOM.lattice_shape,
                                      cuda_prec="double"))
    return gauge, b


def test_invert_multishift_half_sloppy(api_ctx):
    """Multishift with bf16 sloppy + per-shift precise polish (the TPU
    default path via cuda_prec_sloppy='auto') reaches the tolerance on
    every shifted system."""
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.interfaces.params import InvertParam
    from quda_tpu.interfaces.quda_api import invert_multishift_quda
    from quda_tpu.models.wilson import DiracWilsonPC
    gauge, b = api_ctx
    shifts = (0.01, 0.05, 0.2)
    p = InvertParam(dslash_type="wilson", kappa=KAPPA, inv_type="cg",
                    solve_type="normop-pc", tol=1e-9, maxiter=2000,
                    cuda_prec="double", cuda_prec_sloppy="half",
                    num_offset=len(shifts), offset=shifts)
    xs = invert_multishift_quda(b, p)
    dpc = DiracWilsonPC(gauge, GEOM, KAPPA)
    be, bo = even_odd_split(b, GEOM)
    rhs = dpc.Mdag(dpc.prepare(be, bo))
    for i, s in enumerate(shifts):
        r = rhs - (dpc.MdagM(xs[i]) + s * xs[i])
        assert float(jnp.sqrt(blas.norm2(r) / blas.norm2(rhs))) < 1e-8
    assert p.iter_count > 0


@pytest.mark.parametrize("inv,solve", [
    ("bicgstab", "direct-pc"),
    ("gcr", "normop-pc"),        # inner operator must be MdagM here
    ("cg", "normop-pc"),
])
def test_invert_quda_half_sloppy_branches(api_ctx, inv, solve):
    """invert_quda with cuda_prec_sloppy='half' exercises the pair-sloppy
    branches (cg_reliable codec path / defect-correction bicgstab+gcr),
    including the normop case where the inner operator is MdagM."""
    from quda_tpu.interfaces.params import InvertParam
    from quda_tpu.interfaces.quda_api import invert_quda
    from quda_tpu.models.wilson import DiracWilson
    gauge, b = api_ctx
    tol = 1e-9
    p = InvertParam(dslash_type="wilson", kappa=KAPPA, inv_type=inv,
                    solve_type=solve, tol=tol, maxiter=2000,
                    cuda_prec="double", cuda_prec_sloppy="half",
                    gcrNkrylov=4)    # the unrolled cycle's compile
    x = invert_quda(b, p)
    d = DiracWilson(gauge, GEOM, KAPPA)
    r2 = blas.norm2(b - d.M(jnp.asarray(x)))
    assert float(jnp.sqrt(r2 / blas.norm2(b))) < 10 * tol
    assert p.true_res < 10 * tol


@pytest.mark.parametrize("dslash", ["clover", "twisted-mass", "mobius"])
def test_pair_families_bf16_sloppy_api(api_ctx, dslash, monkeypatch):
    """cuda_prec_sloppy='half' on the new pair families: the mixed CG
    runs the bf16 pair-storage sloppy operator inside cg_reliable and
    still converges to the precise tolerance."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.interfaces.params import InvertParam

    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    geom = GEOM
    key = jax.random.PRNGKey(91)
    if dslash == "mobius":
        ls = 4
        b = np.asarray(jnp.stack([
            ColorSpinorField.gaussian(jax.random.fold_in(key, s),
                                      geom).data
            for s in range(ls)])).astype(np.complex64)
        p = InvertParam(dslash_type="mobius", kappa=0.0, mass=0.04,
                        m5=-1.4, Ls=ls, b5=1.5, c5=0.5, inv_type="cg",
                        solve_type="direct-pc", cuda_prec="single",
                        cuda_prec_sloppy="half", tol=1e-6, maxiter=4000)
    else:
        b = np.asarray(ColorSpinorField.gaussian(key, geom).data
                       ).astype(np.complex64)
        kw = dict(kappa=0.12, inv_type="cg", solve_type="direct-pc",
                  cuda_prec="single", cuda_prec_sloppy="half",
                  tol=1e-6, maxiter=4000)
        if dslash == "clover":
            kw["csw"] = 1.0
        else:
            kw["mu"] = 0.2
        p = InvertParam(dslash_type=dslash, **kw)
    api.invert_quda(b, p)
    assert p.true_res < 1e-5, p.true_res
