"""Multi-RHS batching and split-grid tests, and the round-7 packed-pairs
MRHS pipeline: the pair-form batched/block CG solvers and the
invert_multi_src_quda entry point with per-RHS accounting.

The MRHS pallas kernels themselves (bit-match vs the vmapped single-RHS
v2 kernel, the routes, the epilogues) are tests/test_multirhs_kernels.py;
here tier-1 covers the MRHS math through the vmap-fallback operator
forms and the solver/API tests, which are exact against the same
composition, and the operator and the batched loop on the kernel route
once each."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import EVEN, LatticeGeometry
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.models.wilson import DiracWilsonPC
from quda_tpu.ops import blas
from quda_tpu.ops import wilson as wops
from quda_tpu.parallel.mesh import make_lattice_mesh
from quda_tpu.parallel.split import auto_split_mesh, split_grid_solve
from quda_tpu.solvers.block import (batched_cg, batched_cg_pairs,
                                    block_cg, block_cg_pairs)
from quda_tpu.solvers.cg import cg, cg_fixed_iters
from tests.test_multirhs_kernels import route_counts  # noqa: F401 (fixture)

GEOM = LatticeGeometry((6, 6, 6, 6))
GEOM_SMALL = LatticeGeometry((8, 4, 4, 4))    # (x,y,z,t) ctor order
NRHS = 3


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(61)
    gauge = GaugeField.random(key, GEOM).data
    dpc = DiracWilsonPC(gauge, GEOM, 0.115)
    B = jnp.stack([
        even_odd_split(ColorSpinorField.gaussian(
            jax.random.fold_in(key, i), GEOM).data, GEOM)[0]
        for i in range(NRHS)])
    return gauge, dpc, B


def test_batched_cg(problem):
    _, dpc, B = problem
    res = jax.jit(lambda b: batched_cg(dpc.MdagM, b, tol=1e-10,
                                       maxiter=2000))(B)
    assert bool(jnp.all(res.converged))
    for i in range(NRHS):
        rel = float(jnp.sqrt(blas.norm2(B[i] - dpc.MdagM(res.x[i]))
                             / blas.norm2(B[i])))
        assert rel < 5e-10


def test_block_cg_matches_and_shares_krylov(problem):
    _, dpc, B = problem
    res = jax.jit(lambda b: block_cg(dpc.MdagM, b, tol=1e-10,
                                     maxiter=2000))(B)
    assert bool(jnp.all(res.converged))
    for i in range(NRHS):
        rel = float(jnp.sqrt(blas.norm2(B[i] - dpc.MdagM(res.x[i]))
                             / blas.norm2(B[i])))
        assert rel < 1e-8, (i, rel)
    # shared Krylov space: block iterations <= single-RHS iterations
    single = cg(dpc.MdagM, B[0], tol=1e-10, maxiter=2000)
    assert int(res.iters) <= int(single.iters)


def test_split_grid_solve_matches_serial(problem):
    """Sources sharded over the src mesh axis reproduce serial solves
    (the test_split_grid pattern of dslash_test_utils.h)."""
    gauge, dpc, _ = problem
    mesh = make_lattice_mesh(grid=(2, 2, 1, 1), n_src=2)
    key = jax.random.PRNGKey(62)
    B = jnp.stack([ColorSpinorField.gaussian(
        jax.random.fold_in(key, i), GEOM).data for i in range(4)])

    kappa = 0.115
    from quda_tpu.ops.boundary import apply_t_boundary
    g_bc = apply_t_boundary(gauge, GEOM, -1)

    def solve_one(g, b):
        mv = lambda v: wops.matvec_full(g, v, kappa)
        from quda_tpu.models.dirac import apply_gamma5
        mdag = lambda v: apply_gamma5(mv(apply_gamma5(v)))
        rhs = mdag(b)
        return cg_fixed_iters(lambda v: mdag(mv(v)), rhs, None, 60)[0].x

    out = split_grid_solve(solve_one, g_bc, B, mesh)
    # serial reference
    want = jax.vmap(lambda b: solve_one(g_bc, b))(B)
    assert np.allclose(np.asarray(out), np.asarray(want), atol=1e-10)


# ---------------------------------------------------------------------------
# Round-7 MRHS packed-pairs pipeline
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pair_dpk():
    gauge = GaugeField.random(jax.random.PRNGKey(23),
                              GEOM_SMALL).data.astype(jnp.complex64)
    return DiracWilsonPC(gauge, GEOM_SMALL, 0.12, matpc=EVEN).packed()


@pytest.fixture(scope="module")
def pair_problem(pair_dpk):
    """Complex-free packed pair-form PC batch problem (XLA stencil — the
    vmap-fallback MRHS path, exact vs the pallas route's math)."""
    k = jax.random.PRNGKey(23)
    op = pair_dpk.pairs(jnp.float32)
    bs = [ColorSpinorField.gaussian(jax.random.fold_in(k, i),
                                    GEOM_SMALL).data.astype(jnp.complex64)
          for i in range(NRHS)]
    be = jnp.stack([even_odd_split(b, GEOM_SMALL)[0] for b in bs])
    bo = jnp.stack([even_odd_split(b, GEOM_SMALL)[1] for b in bs])
    rhs_b = op.prepare_pairs_mrhs(be, bo)
    nrm_b = op.Mdag_pairs_mrhs(rhs_b)
    return op, be, bo, rhs_b, nrm_b


def test_mrhs_operator_composition_matches_per_rhs(pair_problem):
    """The batched prepare/Mdag/MdagM compositions are EXACTLY the
    per-RHS single compositions stacked (same stencil, same order of
    operations) — the operator-level MRHS contract the pallas kernel
    tests then pin in interpreter mode."""
    op, be, bo, rhs_b, nrm_b = pair_problem
    rhs_i = jnp.stack([op.prepare_pairs(be[i], bo[i])
                       for i in range(NRHS)])
    assert bool(jnp.all(rhs_b == rhs_i))
    nrm_i = jnp.stack([op.Mdag_pairs(rhs_i[i]) for i in range(NRHS)])
    assert bool(jnp.all(nrm_b == nrm_i))
    mm_b = op.MdagM_pairs_mrhs(nrm_b)
    mm_i = jnp.stack([op.MdagM_pairs(nrm_b[i]) for i in range(NRHS)])
    assert bool(jnp.all(mm_b == mm_i))


@pytest.mark.parametrize("store", [
    jnp.float32, pytest.param(jnp.bfloat16, marks=pytest.mark.slow)])
def test_mrhs_operator_on_the_pallas_route_combines_in_the_second_hop(
        pair_dpk, pair_problem, store, route_counts):
    """On the pallas route the batched operator's second hop writes
    ``[g5] (x - kappa^2 D D x)`` itself (the combine epilogue), and the
    result is still the per-source composition stacked: against
    ``MdagM_pairs`` / ``Mdag_pairs`` of the XLA stencil operator, whose
    hop differs from the kernel's in the order of its sums (a few ulp;
    in bf16 storage the intermediate roundings may fall either way)."""
    xla = pair_dpk.pairs(store)
    op = pair_dpk.pairs(store, use_pallas=True, pallas_interpret=True)
    x = pair_problem[4].astype(store)
    tol = 8 * float(jnp.finfo(store).eps)
    for name in ("MdagM_pairs", "Mdag_pairs"):
        got = getattr(op, name + "_mrhs")(x).astype(jnp.float32)
        want = jnp.stack([getattr(xla, name)(x[i]) for i in range(NRHS)]
                         ).astype(jnp.float32)
        assert got.shape == want.shape
        assert float(jnp.max(jnp.abs(got - want))) \
            <= tol * float(jnp.max(jnp.abs(want)))
    # two kernels traced, both full-Z: the first hop bare, the second
    # with the epilogue (Mdag's are MdagM's own, not traced again)
    assert route_counts("epilogue") == {"none": 1.0, "combine": 1.0}
    assert route_counts("reduce") == {"none": 1.0, "norm2": 1.0}
    # what the batched CG applies, the first half of an iteration: pAp
    # as |g5 M p|^2 summed by the epilogue that stores g5 M p, then
    # r - alpha MdagM p and its squares out of the last hop's epilogue
    # (one more kernel traced: the residual form).  Against XLA's dot,
    # update and sum on the XLA stencil, with another alpha per source
    from quda_tpu.solvers.block import cg_step
    r = pair_problem[3].astype(store)
    rz = jnp.asarray([0.7, 1.9, 4.3], jnp.float32)
    got = op.MdagM_cg_step_pairs_mrhs(x, r, rz)
    want = cg_step(xla.MdagM_pairs_mrhs)(x, r, rz, 0)
    assert [(v.shape, v.dtype) for v in got] == [
        (x.shape, store)] + [((NRHS,), jnp.float32)] * 3
    # in bf16 storage q is rounded before its squares are summed and
    # before the second M reads it, and A p is not rounded at all
    # before r takes it
    rtol = 1e-5 if store == jnp.float32 else 2e-2
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol)
    assert len({float(a) for a in got[2]}) == NRHS
    w = want[0].astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got[0].astype(jnp.float32) - w))) \
        <= (tol if store == jnp.float32 else 2e-2) \
        * float(jnp.max(jnp.abs(w)))
    assert route_counts("epilogue") == {"none": 1.0, "combine": 1.0,
                                        "residual": 1.0}
    assert route_counts("reduce") == {"none": 1.0, "norm2": 2.0}
    assert route_counts() == {"fullz": 3.0}
    # off the kernel route the same method is that generic step
    for g, w in zip(xla.MdagM_cg_step_pairs_mrhs(x, r, rz, 0), want):
        assert bool(jnp.all(g == w))
    assert route_counts() == {"fullz": 3.0}


BATCH_TOL = 1e-7


@pytest.fixture(scope="module")
def batched_solution(pair_problem):
    """One batched_cg_pairs solve shared by the solver tests (each
    jitted solve costs a fresh ~20 s XLA compile on CPU; sharing keeps
    the tier-1 budget flat)."""
    op, _, _, _, nrm_b = pair_problem
    return batched_cg_pairs(op.MdagM_pairs_mrhs, nrm_b, tol=BATCH_TOL,
                            maxiter=800)


def test_batched_cg_pairs_matches_single_trajectory(pair_problem,
                                                    batched_solution):
    """Each lane of batched_cg_pairs follows the solo fused_cg
    trajectory (same iteration count, same residual), while issuing one
    batched matvec per iteration."""
    from quda_tpu.solvers.fused_iter import fused_cg
    op, _, _, _, nrm_b = pair_problem
    res = batched_solution
    assert bool(jnp.all(res.converged))
    assert res.iters.shape == (NRHS,)
    for i in range(NRHS):
        rel = float(jnp.sqrt(
            blas.norm2(nrm_b[i] - op.MdagM_pairs(res.x[i]))
            / blas.norm2(nrm_b[i])))
        assert rel < 5 * BATCH_TOL, (i, rel)
    # one solo reference (each lane is the same recurrence; one compile)
    single = fused_cg(op.MdagM_pairs, nrm_b[0], tol=BATCH_TOL,
                      maxiter=800)
    # same trajectory up to reduction-order ulps (the per-RHS
    # reductions sum in a different shape than blas.norm2)
    assert abs(int(res.iters[0]) - int(single.iters)) <= 1


def test_batched_cg_pairs_loop_on_the_operators_own_step(
        pair_dpk, pair_problem, batched_solution):
    """The loop on the kernel route's own step (``pAp``, the new ``r``
    and ``|r|^2`` out of the hops' epilogues, ``A p`` never stored)
    against the loop on the generic step of the XLA stencil operator
    (``batched_solution``): the same iterations to a source, the same
    solution to the solver's tolerance."""
    from quda_tpu.solvers.block import batched_cg_pairs_loop
    op = pair_dpk.pairs(jnp.float32, use_pallas=True,
                        pallas_interpret=True)
    nrm_b = pair_problem[4]
    got = jax.jit(lambda b: batched_cg_pairs_loop(
        op.MdagM_cg_step_pairs_mrhs, b, BATCH_TOL, 800, 1, False, None)
    )(nrm_b)
    want = batched_solution
    assert bool(jnp.all(got.converged))
    assert np.all(np.abs(np.asarray(got.iters)
                         - np.asarray(want.iters)) <= 1)
    for i in range(NRHS):
        assert float(jnp.sqrt(blas.norm2(got.x[i] - want.x[i])
                              / blas.norm2(want.x[i]))) < 10 * BATCH_TOL
        rel = float(jnp.sqrt(
            blas.norm2(nrm_b[i] - pair_problem[0].MdagM_pairs(got.x[i]))
            / blas.norm2(nrm_b[i])))
        assert rel < 5 * BATCH_TOL, (i, rel)


def _diagonal_batch():
    rng = np.random.default_rng(6)
    n, dim = 3, 128
    d = jnp.stack([jnp.linspace(1.0, 2.0 + i, dim).astype(jnp.float32)
                   for i in range(n)])
    return d, jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)


@jax.tree_util.register_pytree_node_class
class _Diagonal:
    """A batch of diagonal operators as a solve program's operand."""
    program_signature = ("diagonal",)

    def __init__(self, d):
        self.d = d

    def tree_flatten(self):
        return (self.d,), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)

    def MdagM_pairs_mrhs(self, V):
        return self.d * V


@jax.tree_util.register_pytree_node_class
class _DiagonalNaNStep(_Diagonal):
    """The same with a CG step of its own, which hands back NaN."""

    def MdagM_cg_step_pairs_mrhs(self, p, r, rz, k=None):
        return (r, rz) + (jnp.full_like(rz, jnp.nan),) * 2


@pytest.mark.parametrize("fault_k", [None, 10 ** 6])
def test_batched_cg_pairs_loop_takes_its_step_from_its_operator(fault_k):
    """The loop applies ``(p, r, rz, k) -> (new r, its squares, alpha,
    pAp)`` and makes no pass of its own for them: with ``cg_step`` it is
    the solver it was, and an operator whose own step hands back NaN
    breaks the solve.  With a dslash fault armed (here at an iteration
    never reached) the program takes ``cg_step`` of the operator's
    matvec, whose ``A p`` the fault corrupts: the operator's step is
    ignored, bit for bit."""
    from quda_tpu.solvers import program as sprog
    d, B = _diagonal_batch()
    n = B.shape[0]

    def solve(op):
        key = (1, sprog._LoopKnobs(False, None, None, fault_k), False)
        return sprog._batched_cg_pairs_program(op, B, 1e-6, 200, key=key)
    want = solve(_Diagonal(d))
    assert bool(jnp.all(want.converged))
    np.testing.assert_allclose(np.asarray(want.x), np.asarray(B / d),
                               rtol=1e-4, atol=1e-5)
    got = solve(_DiagonalNaNStep(d))
    if fault_k is None:
        assert not bool(jnp.any(got.converged))
        assert not bool(jnp.any(jnp.isfinite(got.x)))
    else:
        assert bool(jnp.all(got.converged))
        np.testing.assert_array_equal(np.asarray(got.iters),
                                      np.asarray(want.iters))
        np.testing.assert_array_equal(np.asarray(got.x),
                                      np.asarray(want.x))


def test_generic_cg_step_solves_the_diagonal_batch_as_the_parent_did():
    """``cg_step`` is operation for operation what the loop did itself
    before PR 39 (dot, clamp, update, sum): the solve of the synthetic
    diagonal batch is the parent's to the bit (values pinned from the
    parent commit's ``batched_cg_pairs_loop(with_dot(mv), ...)`` on this
    CPU backend)."""
    import hashlib
    from quda_tpu.solvers.block import batched_cg_pairs_loop, cg_step
    d, B = _diagonal_batch()
    res = batched_cg_pairs_loop(cg_step(lambda V: d * V), B, 1e-6, 200, 1,
                                False, None)
    np.testing.assert_array_equal(np.asarray(res.iters), [8, 11, 13])
    np.testing.assert_array_equal(
        np.asarray(res.r2).view(np.uint32),
        [574408242, 711960263, 774210360])
    x = np.asarray(res.x)
    np.testing.assert_array_equal(x[:, ::31].view(np.uint32), [
        [1065798783, 1043042583, 1053140022, 1062365434, 1059093241],
        [3205742788, 3148764080, 980381189, 1048709162, 1044638461],
        [3207082431, 1055123836, 1065060131, 1043839724, 3172677981]])
    assert hashlib.sha256(x.tobytes()).hexdigest() == (
        "af655ae0df771b7b005dbe320d6b8a03a9ae0f24d67592b86b8d05c14ce5eddc")


def test_batched_cg_pairs_check_cadence():
    """check_every=k stops at the first multiple of k past convergence
    per lane, and per-lane iteration counts are recorded independently
    (the fused_iter cadence semantics, batched).  A synthetic SPD batch
    operator with DISTINCT per-lane spectra keeps the compile cheap
    (cadence k unrolls k stencil applications into the loop body) and
    makes the lanes converge at different iterations — a stronger test
    of the per-RHS recording than the equal-spectrum Wilson batch."""
    rng = np.random.default_rng(5)
    n, dim = 3, 256
    # lane i: condition number grows with i -> more iterations
    diags = jnp.stack([
        jnp.linspace(1.0, 3.0 + 4.0 * i, dim).astype(jnp.float32)
        for i in range(n)])
    mv = lambda X: diags * X
    B = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
    r1 = batched_cg_pairs(mv, B, tol=1e-7, maxiter=400)
    rk = batched_cg_pairs(mv, B, tol=1e-7, maxiter=400, check_every=4)
    assert bool(jnp.all(r1.converged)) and bool(jnp.all(rk.converged))
    assert len(set(int(i) for i in r1.iters)) > 1   # lanes differ
    for i in range(n):
        assert int(rk.iters[i]) % 4 == 0
        assert (int(r1.iters[i]) <= int(rk.iters[i])
                <= int(r1.iters[i]) + 4)


def test_block_cg_pairs_matches_batched_cg_pairs(pair_problem,
                                                 batched_solution):
    """Convergence equivalence on pair arrays: the shared-Krylov block
    solve and the independent-lane batched solve land on the same
    solutions (the satellite's block-vs-batched contract), and the
    shared space converges in <= the slowest independent lane."""
    op, _, _, _, nrm_b = pair_problem
    res_b = batched_solution
    res_k = block_cg_pairs(op.MdagM_pairs_mrhs, nrm_b, tol=BATCH_TOL,
                           maxiter=800)
    assert bool(jnp.all(res_b.converged))
    assert bool(jnp.all(res_k.converged))
    for i in range(NRHS):
        num = float(blas.norm2(res_b.x[i] - res_k.x[i]))
        den = float(blas.norm2(res_b.x[i]))
        assert np.sqrt(num / den) < 1e-5, i
    assert int(res_k.iters) <= int(res_b.iters.max())


def test_block_cg_pairs_breakdown_reports_unconverged():
    """Linearly dependent sources (duplicates) break the block Gram
    matrices; the guard must exit cleanly with converged=False, never
    return NaN solutions as if checked (cheap synthetic operator)."""
    rng = np.random.default_rng(9)
    diag = jnp.linspace(1.0, 5.0, 128).astype(jnp.float32)
    mv = lambda X: diag * X
    b0 = jnp.asarray(rng.standard_normal(128), jnp.float32)
    B = jnp.stack([b0, b0, b0 * 2.0])        # rank-1 batch
    res = block_cg_pairs(mv, B, tol=1e-8, maxiter=100)
    assert not bool(jnp.all(res.converged))
    # independent lanes are immune to the same batch
    res_b = batched_cg_pairs(mv, B, tol=1e-8, maxiter=100)
    assert bool(jnp.all(res_b.converged))


def test_batched_bicgstab_pairs_solves_direct_system():
    """The round-15 setup solver: batched BiCGStab on a DIRECT
    (nonsymmetric) system — per-lane recurrences, two batched matvecs
    per iteration, all lanes converging to the true solution."""
    from quda_tpu.solvers.block import batched_bicgstab_pairs
    rng = np.random.default_rng(15)
    n, dim = 3, 48
    A = (np.eye(dim) + 0.3 * rng.standard_normal((dim, dim))
         / np.sqrt(dim)).astype(np.float32)
    assert not np.allclose(A, A.T)               # genuinely non-normal
    B = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
    Aj = jnp.asarray(A)
    mv = lambda X: X @ Aj.T
    res = batched_bicgstab_pairs(mv, B, tol=1e-6, maxiter=200)
    assert bool(jnp.all(res.converged))
    assert res.iters.shape == (n,)
    want = jnp.asarray(np.linalg.solve(A, np.asarray(B).T).T)
    for i in range(n):
        rel = float(jnp.sqrt(blas.norm2(B[i] - mv(res.x[None, i])[0])
                             / blas.norm2(B[i])))
        assert rel < 5e-6, (i, rel)
        err = float(jnp.max(jnp.abs(res.x[i] - want[i])))
        assert err < 1e-4 * float(jnp.max(jnp.abs(want[i]))), (i, err)


def test_batched_bicgstab_pairs_unconverged_reports_false():
    """Hitting maxiter before tolerance must come back converged=False
    with finite (best-effort) solutions — the setup path's sentinel
    contract."""
    from quda_tpu.solvers.block import batched_bicgstab_pairs
    rng = np.random.default_rng(16)
    dim = 64
    # stiff spectrum: far more than 3 iterations needed
    diag = jnp.asarray(np.geomspace(1.0, 1e4, dim), jnp.float32)
    mv = lambda X: diag * X
    B = jnp.asarray(rng.standard_normal((2, dim)), jnp.float32)
    res = batched_bicgstab_pairs(mv, B, tol=1e-10, maxiter=3)
    assert not bool(jnp.all(res.converged))
    assert bool(jnp.all(jnp.isfinite(res.x)))


def test_batched_cg_pairs_hermitian_complex_batch():
    """The complex-safe per-RHS dots (Re<u,v> with conjugation): a
    hermitian positive-definite COMPLEX batch converges through the
    same lanes the real pair arrays use — what lets the complex MG
    hierarchy run its null-vector solves through this solver."""
    rng = np.random.default_rng(17)
    dim = 32
    A = (rng.standard_normal((dim, dim))
         + 1j * rng.standard_normal((dim, dim))).astype(np.complex64)
    H = jnp.asarray(A @ A.conj().T / dim + 2.0 * np.eye(dim),
                    jnp.complex64)
    mv = lambda X: X @ H.T                       # row-vector form of Hx
    B = jnp.asarray(
        rng.standard_normal((NRHS, dim))
        + 1j * rng.standard_normal((NRHS, dim)), jnp.complex64)
    res = batched_cg_pairs(mv, B, tol=1e-6, maxiter=300)
    assert bool(jnp.all(res.converged))
    assert not jnp.iscomplexobj(res.r2)          # real scalar lanes
    for i in range(NRHS):
        rel = float(jnp.sqrt(blas.norm2(B[i] - mv(res.x[None, i])[0])
                             / blas.norm2(B[i])))
        assert rel < 1e-5, (i, rel)


def test_auto_split_mesh_choice():
    """Batched-vs-split routing: no mesh on one device or one source;
    otherwise the largest divisor of n_src <= device count becomes the
    src axis."""
    devs = jax.devices()
    assert auto_split_mesh(4, devices=devs[:1]) is None
    assert auto_split_mesh(1, devices=devs) is None
    if len(devs) == 8:
        m = auto_split_mesh(4, devices=devs)
        assert m is not None and m.shape["src"] == 4
        m3 = auto_split_mesh(3, devices=devs)
        assert m3 is not None and m3.shape["src"] == 3
    # 5 sources on 4 devices: no divisor > 1 fits -> batched route
    assert auto_split_mesh(5, devices=devs[:4]) is None


# -- invert_multi_src_quda ---------------------------------------------------

@pytest.fixture()
def api_ctx(monkeypatch):
    """Initialised API context on the small lattice, packed XLA-pair
    route (pallas off: the routing/accounting under test is identical
    and tier-1 stays fast; the pallas-in-batched-solve routing has its
    own slow test below)."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.interfaces.params import GaugeParam
    from quda_tpu.utils import config as qconf
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    monkeypatch.setenv("QUDA_TPU_PALLAS", "0")
    # pin the batched route: the 8-virtual-device test mesh would
    # auto-route multi-source solves through the split grid otherwise
    # (the split test overrides this to "1" itself)
    monkeypatch.setenv("QUDA_TPU_MULTI_SRC_SPLIT", "0")
    qconf.reset_cache()
    k = jax.random.PRNGKey(31)
    gauge = GaugeField.random(k, GEOM_SMALL).data.astype(jnp.complex64)
    api.init_quda()
    api.load_gauge_quda(np.asarray(gauge),
                        GaugeParam(X=tuple(GEOM_SMALL.dims),
                                   cuda_prec="single"))
    B = np.stack([np.asarray(ColorSpinorField.gaussian(
        jax.random.fold_in(k, 100 + i), GEOM_SMALL).data.astype(
            jnp.complex64)) for i in range(NRHS)])
    yield api, B
    api.end_quda()
    qconf.reset_cache()


def _msrc_param():
    from quda_tpu.interfaces.params import InvertParam
    return InvertParam(dslash_type="wilson", inv_type="cg",
                       solve_type="normop-pc", kappa=0.12, tol=1e-7,
                       maxiter=800, cuda_prec="single",
                       cuda_prec_sloppy="single")


def test_invert_multi_src_quda_batched(api_ctx):
    """The batched packed-pairs route returns per-RHS iters/residuals
    and charges per-RHS flops at the volume/2 PC convention."""
    import copy
    api, B = api_ctx
    p = _msrc_param()
    X = api.invert_multi_src_quda(B, p)
    assert X.shape == B.shape
    assert len(p.iter_count_multi) == NRHS
    assert len(p.true_res_multi) == NRHS
    assert all(r < 1e-6 for r in p.true_res_multi)
    assert p.iter_count == sum(p.iter_count_multi)
    vol = GEOM_SMALL.volume
    expected = (p.iter_count * 2.0 * (2 * 1320 + 48) * (vol // 2)) / 1e9
    assert abs(p.gflops - expected) / expected < 1e-12
    # solution matches the single-source API (one reference solve; every
    # lane is the same recurrence, pinned lane-by-lane in the solver
    # tests above)
    pi = copy.copy(p)
    xi = api.invert_quda(B[0], pi)
    rel = float(np.max(np.abs(np.asarray(xi) - np.asarray(X[0])))
                / np.max(np.abs(np.asarray(xi))))
    assert rel < 1e-5, rel
    assert p.iter_count_multi[0] == pi.iter_count


def test_invert_multi_src_quda_block_knob(api_ctx, monkeypatch):
    """QUDA_TPU_MULTI_SRC_BLOCK=1 routes through the shared-Krylov block
    solver; results still meet tolerance per RHS."""
    from quda_tpu.utils import config as qconf
    api, B = api_ctx
    monkeypatch.setenv("QUDA_TPU_MULTI_SRC_BLOCK", "1")
    qconf.reset_cache()
    p = _msrc_param()
    api.invert_multi_src_quda(B, p)
    assert all(r < 1e-6 for r in p.true_res_multi)
    # shared Krylov space: one iteration count reported for every RHS
    assert len(set(p.iter_count_multi)) == 1


def test_invert_multi_src_quda_split_grid(api_ctx, monkeypatch):
    """Forced split-grid route (sources sharded over the src mesh axis,
    gauge replicated) solves every source on the virtual 8-device mesh
    and agrees with the batched route."""
    from quda_tpu.utils import config as qconf
    api, B = api_ctx
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    p_b = _msrc_param()
    X_b = api.invert_multi_src_quda(B, p_b)
    monkeypatch.setenv("QUDA_TPU_MULTI_SRC_SPLIT", "1")
    qconf.reset_cache()
    p = _msrc_param()
    X = api.invert_multi_src_quda(B, p)
    assert all(r < 1e-6 for r in p.true_res_multi)
    assert len(p.iter_count_multi) == NRHS
    for i in range(NRHS):
        rel = float(np.max(np.abs(np.asarray(X[i]) - np.asarray(X_b[i])))
                    / np.max(np.abs(np.asarray(X_b[i]))))
        assert rel < 1e-4, (i, rel)
    # each lane's own pair-form exit reported the residual of what it
    # returned: the canonical operator on the host's copy reads the same
    from quda_tpu.models.wilson import DiracWilson
    d = DiracWilson(api._ctx["gauge"], GEOM_SMALL, p.kappa,
                    api._antiperiodic())
    for i in range(NRHS):
        r = jnp.asarray(B[i]) - d.M(jnp.asarray(X[i]))
        want = float(jnp.linalg.norm(r.ravel())
                     / np.linalg.norm(B[i].ravel()))
        assert abs(p.true_res_multi[i] - want) < 0.1 * want


def test_invert_multi_src_quda_fallback_non_wilson(api_ctx):
    """Operators outside the batched gate still solve through the
    per-source fallback with per-RHS results (the multi-source surface
    is total, like callMultiSrcQuda)."""
    from quda_tpu.interfaces.params import InvertParam
    api, B = api_ctx
    p = InvertParam(dslash_type="twisted-mass", inv_type="cg",
                    solve_type="normop-pc", kappa=0.12, mu=0.1,
                    tol=1e-6, maxiter=800, cuda_prec="single",
                    cuda_prec_sloppy="single")
    X = api.invert_multi_src_quda(B[:1], p)
    assert X.shape == B[:1].shape
    assert len(p.true_res_multi) == 1
    assert all(r < 1e-5 for r in p.true_res_multi)


@pytest.mark.slow
def test_invert_multi_src_routes_mrhs_pallas_kernel(api_ctx,
                                                    monkeypatch):
    """With pallas forced on, the batched invert runs the MRHS eo kernel
    INSIDE the compiled batch solve (interpret mode off-TPU) — the
    batched analog of the round-6 pallas-in-solver routing test."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    from quda_tpu.utils import config as qconf
    api, B = api_ctx
    monkeypatch.setenv("QUDA_TPU_PALLAS", "1")
    qconf.reset_cache()

    calls = {"n": 0}
    orig = wpp.dslash_eo_pallas_packed_mrhs

    def spy(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(wpp, "dslash_eo_pallas_packed_mrhs", spy)
    p = _msrc_param()
    p.tol = 1e-5                      # fewer f32-pair iterations
    api.invert_multi_src_quda(B, p)
    assert calls["n"] > 0
    assert all(r < 1e-4 for r in p.true_res_multi)


# -- round 10: staggered MRHS (the second headline family) ------------------

def test_staggered_mrhs_operator_composition_matches_per_rhs():
    """Batched staggered prepare/M/reconstruct compositions are EXACTLY
    the stacked per-RHS single compositions (XLA stencil route — the
    vmap fallback; the pallas MRHS kernel is pinned in
    tests/test_multirhs_kernels.py)."""
    from quda_tpu.models.staggered import DiracStaggeredPC
    k = jax.random.PRNGKey(29)
    fat = GaugeField.random(k, GEOM_SMALL).data.astype(jnp.complex64)
    lng = (0.1 * GaugeField.random(jax.random.fold_in(k, 1), GEOM_SMALL
                                   ).data).astype(jnp.complex64)
    dpc = DiracStaggeredPC(fat, GEOM_SMALL, 0.1, improved=True,
                           long_links=lng)
    op = dpc.pairs(jnp.float32)
    bs = [ColorSpinorField.gaussian(jax.random.fold_in(k, 10 + i),
                                    GEOM_SMALL, nspin=1
                                    ).data.astype(jnp.complex64)
          for i in range(3)]
    be = jnp.stack([even_odd_split(b, GEOM_SMALL)[0] for b in bs])
    bo = jnp.stack([even_odd_split(b, GEOM_SMALL)[1] for b in bs])
    rhs_b = op.prepare_pairs_mrhs(be, bo)
    rhs_i = jnp.stack([op.prepare_pairs(be[i], bo[i])
                       for i in range(3)])
    assert bool(jnp.all(rhs_b == rhs_i))
    mm_b = op.M_pairs_mrhs(rhs_b)
    mm_i = jnp.stack([op.M_pairs(rhs_b[i]) for i in range(3)])
    assert bool(jnp.all(mm_b == mm_i))
    xe_b, xo_b = op.reconstruct_pairs_mrhs(rhs_b, be, bo)
    for i in range(3):
        xe_i, xo_i = op.reconstruct_pairs(rhs_b[i], be[i], bo[i])
        assert bool(jnp.all(xe_b[i] == xe_i))
        assert bool(jnp.all(xo_b[i] == xo_i))


def test_invert_multi_src_quda_staggered_batched(api_ctx):
    """Round 10: the staggered family rides the batched pairs pipeline
    (direct batched CG on the Hermitian PC operator — one M apply per
    counted iteration) instead of the per-source fallback, with per-RHS
    results and the one-apply flop convention."""
    api, _ = api_ctx
    k = jax.random.PRNGKey(37)
    B = np.stack([np.asarray(ColorSpinorField.gaussian(
        jax.random.fold_in(k, i), GEOM_SMALL, nspin=1).data.astype(
            jnp.complex64)) for i in range(NRHS)])
    from quda_tpu.interfaces.params import InvertParam
    p = InvertParam(dslash_type="staggered", inv_type="cg", mass=0.1,
                    solve_type="normop-pc", tol=1e-7, maxiter=800,
                    cuda_prec="single", cuda_prec_sloppy="single")
    X = api.invert_multi_src_quda(B, p)
    assert X.shape == B.shape
    assert len(p.iter_count_multi) == NRHS
    # the PC system converges to tol; the FULL-system residual carries
    # the 1/(2m) reconstruction amplification (m=0.1 -> ~5x + Schur
    # coupling) on the f32 pair representation
    assert all(r < 1e-5 for r in p.true_res_multi)
    vol = GEOM_SMALL.volume
    # Hermitian PC: mv_applies = 1, staggered PC M = 2*570 + 24 per
    # updated site over volume/2 sites
    expected = (p.iter_count * 1.0 * (2 * 570 + 24) * (vol // 2)) / 1e9
    assert abs(p.gflops - expected) / expected < 1e-12
