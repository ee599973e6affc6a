"""Multi-shift CG: each shifted solution must match an independent solve.

The staggered-invert-test multi-shift scenario (tests/staggered_invert_test
--multishift in the reference, RHMC rational approximation shifts).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.models.staggered import DiracStaggeredPC
from quda_tpu.ops import blas
from quda_tpu.solvers.cg import cg
from quda_tpu.solvers.multishift import multishift_cg

GEOM = LatticeGeometry((4, 4, 4, 8))
MASS = 0.05
SHIFTS = (0.0, 0.01, 0.1, 0.5, 2.0)


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(77)
    k1, k2 = jax.random.split(key)
    gauge = GaugeField.random(k1, GEOM).data
    b_full = ColorSpinorField.gaussian(k2, GEOM, nspin=1).data
    dpc = DiracStaggeredPC(gauge, GEOM, MASS)
    be, _ = even_odd_split(b_full, GEOM)
    return dpc, be


def test_multishift_matches_individual_solves(problem):
    dpc, b = problem
    res = jax.jit(lambda rhs: multishift_cg(dpc.M, rhs, SHIFTS, tol=1e-10,
                                            maxiter=4000))(b)
    assert bool(jnp.all(res.converged))
    for i, s in enumerate(SHIFTS):
        mv = lambda v: dpc.M(v) + s * v
        # true residual of shifted system
        r2 = blas.norm2(b - mv(res.x[i]))
        rel = float(jnp.sqrt(r2 / blas.norm2(b)))
        assert rel < 5e-10, (i, s, rel)
        # cross-check against an independent CG solve
        ref = cg(mv, b, tol=1e-10, maxiter=4000)
        diff = float(jnp.sqrt(blas.norm2(res.x[i] - ref.x)
                              / blas.norm2(ref.x)))
        assert diff < 1e-7, (i, s, diff)


def test_larger_shifts_converge_faster_in_exact_arithmetic(problem):
    """Shifted residual |zeta_s| |r| decreases with shift size — verify the
    returned per-shift convergence flags are all set even at loose maxiter."""
    dpc, b = problem
    res = multishift_cg(dpc.M, b, SHIFTS, tol=1e-8, maxiter=1000)
    assert bool(jnp.all(res.converged))


def test_wilson_multishift_pairs_api(monkeypatch):
    """QUDA_TPU_PACKED=1 + single precision routes Wilson multishift
    through the complex-free pair representation; each shifted PC
    normal-equation solution matches the complex route."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.spinor import ColorSpinorField
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.ops import blas

    geom = LatticeGeometry((4, 4, 4, 4))
    key = jax.random.PRNGKey(61)
    U = GaugeField.random(key, geom).data.astype(jnp.complex64)
    b = np.asarray(ColorSpinorField.gaussian(
        jax.random.fold_in(key, 1), geom).data).astype(np.complex64)
    shifts = (0.05, 0.2)
    api.init_quda()
    api.load_gauge_quda(np.asarray(U), GaugeParam(X=(4, 4, 4, 4)))

    def solve(packed):
        monkeypatch.setenv("QUDA_TPU_PACKED", "1" if packed else "0")
        p = InvertParam(dslash_type="wilson", kappa=0.12,
                        inv_type="multi-shift-cg",
                        solve_type="normop-pc", cuda_prec="single",
                        cuda_prec_sloppy="single", tol=1e-7,
                        maxiter=2000, num_offset=len(shifts),
                        offset=shifts)
        return api.invert_multishift_quda(b, p)

    xs_pair = solve(True)
    xs_ref = solve(False)
    api.end_quda()
    assert xs_pair.shape == xs_ref.shape
    for i in range(len(shifts)):
        err = float(jnp.sqrt(blas.norm2(xs_pair[i] - xs_ref[i])
                             / blas.norm2(xs_ref[i])))
        assert err < 1e-4, (i, err)


# -- converged shifts leave the update (PR 41) --------------------------------

def _full_update_reference(matvec, b, shifts, tol, maxiter):
    """The loop as it was while every x_i and p_i was updated in every
    iteration, expression for expression -> (x, iters, r2, zeta_i^2 r2)."""
    ns = len(shifts)
    b2 = blas.norm2(b)
    rdt = b2.dtype
    shifts = jnp.asarray(shifts, rdt)
    sig = shifts - shifts[0]
    stop = (jnp.asarray(tol, rdt) ** 2) * b2
    ex = lambda a: a.reshape((ns,) + (1,) * b.ndim).astype(b.dtype)
    one = jnp.ones((ns,), rdt)
    c0 = (jnp.zeros((ns,) + b.shape, b.dtype),
          jnp.broadcast_to(b, (ns,) + b.shape).astype(b.dtype), b, b2, one,
          one, jnp.ones((), rdt), jnp.zeros((), rdt), jnp.int32(0))

    def cond(c):
        return jnp.logical_and(jnp.max(c[4] ** 2 * c[3]) > stop,
                               c[8] < maxiter)

    def body(c):
        x, p, r, r2, z, zo, ao, bo, k = c
        Ap = matvec(p[0]) + shifts[0].astype(b.dtype) * p[0]
        alpha = r2 / blas.redot(p[0], Ap).astype(rdt)
        zd = alpha * bo * (zo - z) + zo * ao * (1.0 + sig * alpha)
        zn = jnp.where(zd != 0, z * zo * ao / jnp.where(zd != 0, zd, 1.0),
                       0.0)
        ratio = jnp.where(z != 0, zn / jnp.where(z != 0, z, 1.0), 0.0)
        x = x + ex(alpha * ratio) * p
        r = r - alpha.astype(b.dtype) * Ap
        r2n = blas.norm2(r).astype(rdt)
        beta = r2n / r2
        p = ex(zn) * r[None] + ex(beta * ratio ** 2) * p
        return (x, p, r, r2n, zn, z, alpha, beta, k + 1)
    x, _, _, r2, z, *_, k = jax.lax.while_loop(cond, body, c0)
    return x, k, r2, z ** 2 * r2


DIAG_SHIFTS = (0.0, 0.05, 0.3, 1.0, 4.0)
DIAG_TOL = {"float64": 1e-10, "float32": 1e-6}


@pytest.fixture(scope="module", params=["float64", "float32"])
def diagonal(request):
    """A diagonal operator of condition 300, its solve with ascending
    shifts and the full-update reference under one jit."""
    dt, tol = jnp.dtype(request.param), DIAG_TOL[request.param]
    d = jnp.linspace(0.01, 3.0, 256).astype(dt)
    b = jnp.asarray(np.random.default_rng(5).standard_normal(256), dt)
    mv = lambda v: d * v
    ref = jax.jit(lambda maxiter: _full_update_reference(
        mv, b, DIAG_SHIFTS, tol, maxiter))
    res = jax.jit(lambda: multishift_cg(mv, b, DIAG_SHIFTS, tol=tol,
                                        maxiter=2000))()
    return {"d": d, "b": b, "mv": mv, "tol": tol, "ref": ref, "res": res}


def test_base_shift_is_the_full_update_bit_for_bit(diagonal):
    res = diagonal["res"]
    x, iters, r2, _ = diagonal["ref"](2000)
    assert 20 < int(res.iters) == int(iters) < 2000
    assert float(res.r2) == float(r2)
    np.testing.assert_array_equal(np.asarray(res.x[0]), np.asarray(x[0]))
    assert bool(res.converged.all())


@pytest.mark.parametrize("shift", range(1, len(DIAG_SHIFTS)))
def test_retired_shift_keeps_the_iterate_of_its_retirement(diagonal, shift):
    """x_i and shift_r2[i] are the full update's, stopped at the
    iteration in which shift i's own zeta_i |r| first read <= tol |b|."""
    res = diagonal["res"]
    k = int(res.shift_iters[shift])
    assert 0 < k < int(res.iters)
    x, iters, _, sr2 = diagonal["ref"](k)
    assert int(iters) == k
    np.testing.assert_array_equal(np.asarray(res.x[shift]),
                                  np.asarray(x[shift]))
    assert float(res.shift_r2[shift]) == float(sr2[shift])
    stop = diagonal["tol"] ** 2 * float(blas.norm2(diagonal["b"]))
    assert float(sr2[shift]) <= stop
    # one iteration earlier it was still above tol
    assert float(diagonal["ref"](k - 1)[3][shift]) > stop


def test_shift_iters_count_the_live_prefix(diagonal):
    res = diagonal["res"]
    n = np.asarray(res.shift_iters)
    assert n.dtype == np.int32 and n[0] == int(res.iters)
    assert (np.diff(n) <= 0).all() and n[-1] < n[0] / 3


def test_every_shift_is_under_three_tol(diagonal):
    """The true residual of every shift, retired early or not (f64 at
    tol 1e-10; f32 at 1e-6, which condition 300 still lets it reach)."""
    d, b, res = diagonal["d"], diagonal["b"], diagonal["res"]
    for i, s in enumerate(DIAG_SHIFTS):
        rel = float(jnp.linalg.norm(b - (d + s) * res.x[i])
                    / jnp.linalg.norm(b))
        assert rel <= 3 * diagonal["tol"], (i, rel)


@pytest.mark.parametrize("order", [(0, 4, 1, 3, 2), (0, 2, 3, 4, 1)])
def test_unordered_offsets_give_the_sorted_solutions(diagonal, order):
    """Shift 0 the smallest, the rest in any order: the live prefix
    reaches to the last shift still above tol, so a shift in front of
    it is updated on (correct, just not retired) and every row is the
    sorted run's solution to the tolerance both were asked for."""
    d, b, tol = diagonal["d"], diagonal["b"], diagonal["tol"]
    shifts = tuple(DIAG_SHIFTS[i] for i in order)
    got = multishift_cg(diagonal["mv"], b, shifts, tol=tol, maxiter=2000)
    want = diagonal["res"]
    assert int(got.iters) == int(want.iters) and bool(got.converged.all())
    n = np.asarray(got.shift_iters)
    assert (n >= np.asarray(want.shift_iters)[list(order)]).all()
    # a row behind a slower one rides on until that one retires
    assert (n == np.maximum.accumulate(n[::-1])[::-1]).all()
    for row, i in enumerate(order):
        rel = float(jnp.linalg.norm(b - (d + shifts[row]) * got.x[row])
                    / jnp.linalg.norm(b))
        assert rel <= 3 * tol, (row, rel)
        err = float(jnp.linalg.norm(got.x[row] - want.x[i])
                    / jnp.linalg.norm(want.x[i]))
        assert err <= 300 * tol, (row, err)   # cond x tol


def test_one_shift_is_plain_cg(diagonal):
    b, tol = diagonal["b"], diagonal["tol"]
    mv = lambda v: diagonal["mv"](v) + 0.05 * v
    one = multishift_cg(diagonal["mv"], b, (0.05,), tol=tol, maxiter=2000)
    ref = cg(mv, b, tol=tol, maxiter=2000)
    assert int(one.iters) == int(ref.iters)
    assert int(one.shift_iters[0]) == int(one.iters)
    np.testing.assert_allclose(np.asarray(one.x[0]), np.asarray(ref.x),
                               rtol=0, atol=1e3 * tol
                               * float(jnp.abs(ref.x).max()))
    assert float(one.shift_r2[0]) == float(one.r2)


@pytest.mark.parametrize("n_active", [1, 3, 5])
def test_update_kernel_is_the_xla_update(n_active):
    """ops/blas_pallas.multishift_update_pallas (interpreted) against
    update_live_xla on a stack of five (3, 2, 4, 8, 128) pair fields:
    the first n_active rows updated (to a rounding: XLA's CPU code
    contracts the multiply-adds of the two forms differently), the rest
    untouched bit for bit."""
    from quda_tpu.ops import blas_pallas as bpl
    from quda_tpu.solvers.multishift import update_live_xla
    rng = np.random.default_rng(n_active)
    f32 = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32)
    field = (3, 2, 4, 8, 128)
    x, p, r = f32(5, *field), f32(5, *field), f32(*field)
    a, z, bt = f32(5), f32(5), f32(5)
    na = jnp.int32(n_active)
    got = bpl.multishift_update_pallas(na, a, z, bt, x, p, r,
                                       interpret=True, block_rows=8)
    want = jax.jit(update_live_xla)(na, a, z, bt, x, p, r)
    for g, w, old in zip(got, want, (x, p)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(g[n_active:]),
                                      np.asarray(old[n_active:]))
        assert not np.array_equal(np.asarray(g[:n_active]),
                                  np.asarray(old[:n_active]))


def test_loop_with_the_kernel_is_the_loop_without():
    """multishift_cg_loop on a real (rows, lanes) source with the
    interpreted kernel and with the XLA update: the same iterations,
    the same retirements, the same solutions to f32 rounding."""
    from quda_tpu.solvers.multishift import multishift_cg_loop
    d = jnp.linspace(0.05, 3.0, 8 * 128).reshape(8, 128).astype(jnp.float32)
    b = jnp.asarray(np.random.default_rng(9).standard_normal((8, 128)),
                    jnp.float32)
    shifts = jnp.asarray((0.0, 0.1, 1.0), jnp.float32)
    run = lambda update: multishift_cg_loop(
        lambda v: d * v, b, shifts, 1e-6, 500, update=update)
    got, want = run("pallas-interpret"), run("xla")
    assert int(got.iters) == int(want.iters) < 500
    np.testing.assert_array_equal(np.asarray(got.shift_iters),
                                  np.asarray(want.shift_iters))
    assert int(got.shift_iters[2]) < int(got.shift_iters[0])
    np.testing.assert_allclose(np.asarray(got.x), np.asarray(want.x),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("source, backend, want", [
    ("pairs", "tpu", "pallas"), ("pairs", "cpu", "xla"),
    ("complex", "tpu", "xla"), ("vector", "tpu", "xla"),
    ("traced", "tpu", "xla"), ("host", "tpu", "xla")])
def test_update_form_is_chosen_from_the_source(monkeypatch, source,
                                               backend, want):
    """The kernel for a real (rows, lanes) source on one device of a TPU
    backend; the XLA form for everything else, a tracer and a host
    array included."""
    from quda_tpu.solvers.multishift import update_form
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    b = {"pairs": jnp.ones((3, 2, 4, 8, 128), jnp.float32),
         "traced": jnp.ones((3, 2, 4, 8, 128), jnp.float32),
         "complex": jnp.ones((4, 8, 128), jnp.complex64),
         "vector": jnp.ones((256,), jnp.float32),
         "host": np.ones((3, 2, 4, 8, 128), np.float32)}[source]
    if source == "traced":
        assert jax.jit(lambda v: update_form(v) == want)(b)
    else:
        assert update_form(b) == want
