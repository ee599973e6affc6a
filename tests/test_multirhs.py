"""Multi-RHS batching and split-grid tests, and the round-7 packed-pairs
MRHS pipeline: the gauge-amortized MRHS pallas kernels (bit-match vs the
vmapped single-RHS v2 kernel), the pair-form batched/block CG solvers,
and the invert_multi_src_quda entry point with per-RHS accounting.

The pallas-interpreter kernel tests are marked ``slow`` (each distinct
kernel shape costs a ~20-25 s interpreter compile — same policy as
test_fused_iter.py); tier-1 covers the MRHS math through the vmap-
fallback operator forms and the solver/API tests, which are exact against
the same composition."""

import sys

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


# -- MRHS pallas kernels (interpreter mode; slow: ~20-25 s compile per
# distinct kernel shape, same budget policy as test_fused_iter.py) ----------

KT, KZ, KY, KX = 4, 8, 4, 4          # kernel-test lattice extents


@pytest.mark.slow
@pytest.mark.parametrize("nrhs", [1, 3, 8])
def test_mrhs_kernel_bitmatches_vmapped_v2(nrhs):
    """dslash_pallas_packed_mrhs bit-matches jax.vmap of the single-RHS
    v2 kernel for N in {1, 3, 8} (N=1 is the degenerate case)."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    rng = np.random.default_rng(7)
    g = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    psi_b = jnp.asarray(rng.standard_normal(
        (nrhs, 4, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    gbw = wpp.backward_gauge(g, KX)
    want = jax.vmap(lambda p: wpp.dslash_pallas_packed(
        g, p, KX, interpret=True, gauge_bw=gbw))(psi_b)
    got = wpp.dslash_pallas_packed_mrhs(g, psi_b, KX, interpret=True,
                                        gauge_bw=gbw)
    assert bool(jnp.all(got == want))


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_mrhs_eo_kernel_bitmatches_all_parities(parity):
    """The eo MRHS kernel (the batched-solver hot path) bit-matches the
    single-RHS eo v2 kernel on both target parities, including N=1."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    dims = (KT, KZ, KY, KX)
    Xh = KX // 2
    rng = np.random.default_rng(8)
    u_here = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    u_there = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    u_bw = wpp.backward_gauge_eo(u_there, dims, parity)
    for nrhs in (1, 3):
        psi_b = jnp.asarray(rng.standard_normal(
            (nrhs, 4, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
        want = jnp.stack([wpp.dslash_eo_pallas_packed(
            u_here, u_bw, psi_b[i], dims, parity, interpret=True)
            for i in range(nrhs)])
        got = wpp.dslash_eo_pallas_packed_mrhs(
            u_here, u_bw, psi_b, dims, parity, interpret=True)
        assert bool(jnp.all(got == want)), (parity, nrhs)


# -- the MRHS kernel's two routes (ops/wilson_pallas_packed._mrhs_route) ---
#
# Full-Z tiles (three psi operands, the z shift wraps inside the tile,
# two time-slices a step) where their VMEM set fits, z-blocks (five)
# where it does not; both bitwise the single-source kernel per RHS.
# The single-source side is held to block_z = 8, so it really splices
# rows of its z-neighbour tiles; T = 4 so that a block's t neighbours
# are its own slice on one side and another block's on the other.

def _fz_dims(dtype, z_tiles=2):
    """(T, Z, Y, X) with Z = ``z_tiles`` sublane tiles of the storage
    dtype: two, and the full-Z body walks two chunks; one, and it works
    on the whole tile (as 24 rows of bf16 make it at 24^4)."""
    return (4, z_tiles * (8 if dtype == jnp.float32 else 16), 2, 4)


def _eo_mrhs_problem(dims, parity, nrhs, dtype, seed=8):
    from quda_tpu.ops import wilson_pallas_packed as wpp
    T, Z, Y, X = dims
    rng = np.random.default_rng(seed)

    def draw(shape):
        return jnp.asarray(rng.standard_normal(shape),
                           jnp.float32).astype(dtype)
    half = (T, Z, Y * X // 2)
    u_here = draw((4, 3, 3, 2) + half)
    u_bw = wpp.backward_gauge_eo(draw((4, 3, 3, 2) + half), dims, parity)
    return u_here, u_bw, draw((nrhs, 4, 3, 2) + half)


@pytest.fixture
def route_counts(tmp_path):
    """A metrics session of the test's own; calling the fixture reads
    ``wilson_mrhs_route_total`` as {route: count}, or by its other
    label, ``epilogue``."""
    from quda_tpu.obs import memory as omem
    from quda_tpu.obs import metrics as omet
    omet.stop(flush_files=False)
    omem.reset()
    omet.start(str(tmp_path))

    def read(label="route"):
        out = {}
        for (n, lab), v in omet.snapshot()["counters"].items():
            if n == "wilson_mrhs_route_total":
                out[dict(lab)[label]] = out.get(dict(lab)[label], 0.0) + v
        return out
    yield read
    omet.stop(flush_files=False)
    omem.reset()


@pytest.mark.parametrize("parity,nrhs,dtype,z_tiles", [
    (0, 2, jnp.float32, 2), (1, 8, jnp.float32, 2),
    (0, 8, jnp.bfloat16, 2), (1, 2, jnp.bfloat16, 2),
    pytest.param(1, 2, jnp.float32, 2, marks=pytest.mark.slow),
    pytest.param(0, 8, jnp.float32, 1, marks=pytest.mark.slow),
    pytest.param(1, 8, jnp.bfloat16, 1, marks=pytest.mark.slow),
    pytest.param(0, 2, jnp.bfloat16, 2, marks=pytest.mark.slow)])
def test_mrhs_fullz_route_bitmatches_single_source(parity, nrhs, dtype,
                                                   z_tiles, route_counts):
    """The full-Z route is bitwise ``jax.vmap`` of the z-blocked
    single-source kernel, and ``wilson_mrhs_route_total`` counts it once
    per traced call (a second call of the same shapes traces nothing)."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    dims = _fz_dims(dtype, z_tiles)
    u_here, u_bw, psi_b = _eo_mrhs_problem(dims, parity, nrhs, dtype)
    want = jax.vmap(lambda p: wpp.dslash_eo_pallas_packed(
        u_here, u_bw, p, dims, parity, interpret=True,
        block_z=8))(psi_b)
    got = wpp.dslash_eo_pallas_packed_mrhs(
        u_here, u_bw, psi_b, dims, parity, interpret=True)
    again = wpp.dslash_eo_pallas_packed_mrhs(
        u_here, u_bw, psi_b, dims, parity, interpret=True)
    assert got.dtype == dtype
    assert bool(jnp.all(got == want)) and bool(jnp.all(again == want))
    assert route_counts() == {"fullz": 1.0}


def test_mrhs_zblock_route_runs_where_fullz_does_not_fit(route_counts):
    """A large tile (Z = 40, Y*Xh = 640: 60.9 MiB of full-Z blocks,
    one time-slice a step, against the 48 MiB the route may ask for)
    sends the call to the z-blocked five-operand route, from its shapes
    alone; it is still bitwise the single-source kernel and counted as
    ``zblock``."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    dims = (2, 40, 32, 40)
    assert wpp._mrhs_fullz_vmem(40, 640, jnp.float32, jnp.float32, 3)[1] \
        > wpp._MRHS_FULLZ_VMEM_CAP
    u_here, u_bw, psi_b = _eo_mrhs_problem(dims, 0, 2, jnp.float32)
    want = jax.vmap(lambda p: wpp.dslash_eo_pallas_packed(
        u_here, u_bw, p, dims, 0, interpret=True))(psi_b)
    got = wpp.dslash_eo_pallas_packed_mrhs(
        u_here, u_bw, psi_b, dims, 0, interpret=True)
    assert bool(jnp.all(got == want))
    assert route_counts() == {"zblock": 1.0}


def _one_slice(monkeypatch, wpp, dims, dtype):
    # a cap that one time-slice a step passes with the xc block and two
    # do not
    T, Z, Y, X = dims
    one, two = (wpp._mrhs_fullz_vmem(Z, Y * X // 2, dtype, dtype, 3, bt,
                                     dtype)[1] for bt in (1, 2))
    monkeypatch.setattr(wpp, "_MRHS_FULLZ_VMEM_CAP", (one + two) // 2)


def _no_room_for_xc(monkeypatch, wpp, dims, dtype):
    # no full-Z tiles, and a z-block budget that the hop's 288 planes
    # pass and the 312 with the xc block do not
    monkeypatch.setattr(wpp, "_MRHS_FULLZ_VMEM_CAP", 0)
    plane = wpp._sublane_rows(dtype) * 128 * jnp.dtype(dtype).itemsize
    monkeypatch.setenv("QUDA_TPU_PALLAS_VMEM_MB",
                       str(300 * plane / 2 ** 20))


# route -> (what bends the shapes' own choice, the route counted, the
# epilogue counted).  A bent call has a batch of its own size: the cap
# and the knob are no part of the jitted call's key.
_COMBINE_ROUTES = {"fullz2": (None, "fullz", "combine"),
                   "fullz1": (_one_slice, "fullz", "combine"),
                   "zblock": (None, "zblock", "combine"),
                   "xla": (_no_room_for_xc, "zblock", "none")}


def _epilogue_case(route, parity, dtype, monkeypatch):
    """One of ``_COMBINE_ROUTES`` bent into place and a problem on it:
    (dims, nrhs, the call's keywords, links, backward links, psi, xc,
    the route counted, the epilogue counted)."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    bend, counted, epilogue = _COMBINE_ROUTES[route]
    dims, nrhs = _fz_dims(dtype), 2 if bend is None else 3
    kw = ({"block_z": wpp._sublane_rows(dtype)} if route == "zblock"
          else {})
    if bend is not None:
        bend(monkeypatch, wpp, dims, dtype)
    u_here, u_bw, psi_b = _eo_mrhs_problem(dims, parity, nrhs, dtype)
    xc = _eo_mrhs_problem(dims, parity, nrhs, dtype, seed=9)[2]
    return dims, nrhs, kw, u_here, u_bw, psi_b, xc, counted, epilogue


@pytest.mark.parametrize("parity,g5,route,dtype", [
    (0, True, "fullz2", jnp.float32),
    pytest.param(1, False, "zblock", jnp.float32, marks=pytest.mark.slow),
    pytest.param(1, True, "fullz1", jnp.float32, marks=pytest.mark.slow),
    pytest.param(0, True, "xla", jnp.float32, marks=pytest.mark.slow),
    pytest.param(1, True, "fullz2", jnp.bfloat16, marks=pytest.mark.slow),
    pytest.param(0, False, "fullz1", jnp.bfloat16,
                 marks=pytest.mark.slow),
    pytest.param(0, True, "zblock", jnp.bfloat16,
                 marks=pytest.mark.slow),
    # every route in tier 1 since the epilogue also sums (PR 37), with
    # and without g5
    (1, False, "fullz1", jnp.float32),
    (1, True, "zblock", jnp.float32),
    (0, False, "xla", jnp.float32)])
def test_mrhs_combine_epilogue_is_xla_on_the_plain_hop(
        parity, g5, route, dtype, route_counts, monkeypatch):
    """``xc``, ``coeff``, ``g5``: the call writes ``[g5] (xc + coeff *
    hop)`` from its f32 accumulators, rounded to the storage dtype once,
    on either route, and is counted with ``epilogue="combine"``; where
    no route holds the xc block (``xla``) XLA combines the bare hop, as
    without the epilogue.  Against the plain call's f32 hop combined by
    XLA: the last bit may differ (one contracts the multiply-add, one
    does not), and with it now and then the bf16 a sum rounds to.
    Besides, the call returns the per-source sums of squares of what
    it stored (f32, to f32 rounding of a sum taken in f64: the kernel's
    own partial sums, XLA's on the fallback), counted with
    ``reduce="norm2"``."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    (dims, nrhs, kw, u_here, u_bw, psi_b, xc, counted,
     epilogue) = _epilogue_case(route, parity, dtype, monkeypatch)
    coeff = -0.12 ** 2
    got, sums = wpp.dslash_eo_pallas_packed_mrhs_combine(
        u_here, u_bw, psi_b, dims, parity, interpret=True, xc=xc,
        coeff=coeff, g5=g5, **kw)
    assert sums.shape == (nrhs,) and sums.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(sums), np.asarray(jnp.sum(
            got.astype(jnp.float64).reshape(nrhs, -1) ** 2, axis=1)),
        rtol=2e-6)
    hop = wpp.dslash_eo_pallas_packed_mrhs(
        u_here, u_bw, psi_b, dims, parity, interpret=True,
        out_dtype=jnp.float32, **kw)
    want = xc.astype(jnp.float32) + jnp.float32(coeff) * hop
    if g5:
        want = want * jnp.asarray([1, 1, -1, -1], jnp.float32).reshape(
            1, 4, 1, 1, 1, 1, 1)
    want = want.astype(dtype).astype(jnp.float32)
    assert got.dtype == dtype and got.shape == psi_b.shape
    diff = jnp.abs(got.astype(jnp.float32) - want)
    ulp = float(jnp.finfo(dtype).eps) * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(diff)) <= ulp
    if dtype == jnp.bfloat16:
        assert float(jnp.mean(diff == 0)) > 0.99
    assert route_counts() == {counted: 2.0}
    assert route_counts("epilogue") == (
        {"none": 2.0} if epilogue == "none"
        else {"none": 1.0, "combine": 1.0})
    # the fallback's sums are XLA's: no kernel is counted with them
    assert route_counts("reduce") == (
        {"none": 2.0} if epilogue == "none"
        else {"none": 1.0, "norm2": 1.0})


def test_mrhs_kernel_is_traced_from_a_frame_too_large_for_a_chunk():
    """The MRHS ``pallas_call`` is made from a frame that no 16 KiB
    chunk of CPython's frame stack has room left for, so that the
    kernel body's trace does not straddle a chunk boundary by the luck
    of its caller's depth (PERF.md section 7 (22)); it hands through
    what it calls."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    call = wpp._on_a_stack_chunk_of_its_own
    assert call.__code__.co_nlocals * 8 > 2 * 16 * 1024
    assert call(lambda: sys._getframe(1).f_code) is call.__code__


@pytest.mark.parametrize("parity,g5,route,dtype", [
    (0, True, "fullz2", jnp.float32),
    (1, True, "xla", jnp.float32),
    pytest.param(1, False, "fullz1", jnp.float32, marks=pytest.mark.slow),
    pytest.param(0, True, "zblock", jnp.float32, marks=pytest.mark.slow),
    pytest.param(1, True, "fullz2", jnp.bfloat16, marks=pytest.mark.slow)])
def test_mrhs_residual_epilogue_is_xla_on_the_combine_hop(
        parity, g5, route, dtype, route_counts, monkeypatch):
    """``rc`` and ``alpha`` besides ``xc`` and ``coeff``: the call
    writes ``rc - alpha[n] * [g5] (xc + coeff * hop)``, source n with
    ITS alpha (every source has another: a swapped index is far off),
    rounded to the storage dtype once, and returns the per-source sums
    of squares of what it stored; counted with ``epilogue="residual"``.
    Where no route holds the two blocks (``xla``) it is the combine
    call (here in turn the bare hop and XLA's combine) and XLA's
    update and sum.  Against the combine call's f32 result updated by
    XLA."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    (dims, nrhs, kw, u_here, u_bw, psi_b, xc, counted,
     epilogue) = _epilogue_case(route, parity, dtype, monkeypatch)
    rc = _eo_mrhs_problem(dims, parity, nrhs, dtype, seed=10)[2]
    coeff = -0.12 ** 2
    alpha = jnp.asarray([0.37, -1.9, 2.6][:nrhs], jnp.float32)
    got, sums = wpp.dslash_eo_pallas_packed_mrhs_residual(
        u_here, u_bw, psi_b, dims, parity, interpret=True, xc=xc,
        coeff=coeff, g5=g5, rc=rc, alpha=alpha, **kw)
    assert sums.shape == (nrhs,) and sums.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(sums), np.asarray(jnp.sum(
            got.astype(jnp.float64).reshape(nrhs, -1) ** 2, axis=1)),
        rtol=2e-6)
    v = wpp.dslash_eo_pallas_packed_mrhs_combine(
        u_here, u_bw, psi_b, dims, parity, interpret=True, xc=xc,
        coeff=coeff, g5=g5, out_dtype=jnp.float32, **kw)[0]

    def update(a):
        w = rc.astype(jnp.float32) - a.reshape((nrhs,) + (1,) * 6) * v
        return w.astype(dtype).astype(jnp.float32)
    want = update(alpha)
    assert got.dtype == dtype and got.shape == psi_b.shape
    diff = jnp.abs(got.astype(jnp.float32) - want)
    ulp = float(jnp.finfo(dtype).eps) * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(diff)) <= ulp
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - update(alpha[::-1])))) > 1e3 * ulp
    assert route_counts() == {counted: 2.0}
    assert route_counts("epilogue") == (
        {"none": 2.0} if epilogue == "none"
        else {"combine": 1.0, "residual": 1.0})
    assert route_counts("reduce") == (
        {"none": 2.0} if epilogue == "none" else {"norm2": 2.0})
    with pytest.raises(ValueError, match="mrhs_residual"):
        wpp.dslash_eo_pallas_packed_mrhs_combine(
            u_here, u_bw, psi_b, dims, parity, interpret=True, xc=xc,
            coeff=coeff, rc=rc, alpha=alpha)


@pytest.mark.parametrize("case,want", [
    # (T, Z, YX, storage, out, link rows R, block_z[, xc storage
    # [, rc storage]]) -> (route, bz, bt)
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.bfloat16, jnp.bfloat16, 3, None), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.bfloat16, jnp.float32, 3, None), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.float32, jnp.float32, 2, None), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 24), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 8), ("zblock", 8, 1)),
    ((9, 24, 288, jnp.float32, jnp.float32, 3, None), ("fullz", 24, 1)),
    ((32, 32, 512, jnp.float32, jnp.float32, 3, None), ("fullz", 32, 1)),
    ((32, 32, 512, jnp.bfloat16, jnp.bfloat16, 3, None), ("fullz", 32, 2)),
    ((40, 40, 640, jnp.float32, jnp.float32, 3, None), ("zblock", 8, 1)),
    ((8, 8, 8, jnp.float32, jnp.float32, 3, None), ("fullz", 8, 2)),
    # the combine epilogue's xc block is one more operand, and its
    # block of sums one chunk of f32 rows more: 24^4 still takes two
    # slices a step (38.8 MiB of the 48), a 32 x 32 plane at
    # Z = 24 takes two without it and one with it, and where the hop's
    # own z-block is the largest that fits no route holds it
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None, jnp.float32),
     ("fullz", 24, 2)),
    ((24, 24, 288, jnp.bfloat16, jnp.bfloat16, 3, None, jnp.bfloat16),
     ("fullz", 24, 2)),
    ((24, 24, 512, jnp.float32, jnp.float32, 3, None), ("fullz", 24, 2)),
    ((24, 24, 512, jnp.float32, jnp.float32, 3, None, jnp.float32),
     ("fullz", 24, 1)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 8, jnp.float32),
     ("zblock", 8, 1)),
    ((40, 40, 640, jnp.float32, jnp.float32, 3, None, jnp.float32),
     ValueError),
    # the residual form's rc block is one more again: 24^4 still takes
    # two slices a step (42.2 MiB of the 48), eight rows of 1,408
    # lanes take two with the xc block and one with both
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.bfloat16, jnp.bfloat16, 3, None, jnp.bfloat16,
      jnp.bfloat16), ("fullz", 24, 2)),
    ((24, 8, 1408, jnp.float32, jnp.float32, 3, None, jnp.float32),
     ("fullz", 8, 2)),
    ((24, 8, 1408, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ("fullz", 8, 1)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 8, jnp.float32,
      jnp.float32), ("zblock", 8, 1)),
    ((40, 40, 640, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ValueError)])
def test_mrhs_route_follows_the_shapes(case, want, route_counts):
    """The route is arithmetic on (T, Z, YX, dtypes, R): padded planes x
    bytes, twice for the pipeline's buffers, plus the body's tiles,
    against the limit the call sets: two time-slices a step where they
    fit and T is even, one where only that fits, z-blocks where neither
    does; a caller's ``block_z`` wins.  The full-Z call's
    ``vmem_limit_bytes`` holds what it computed, and the VMEM audit
    keeps the route's blocks beside the knob's own row."""
    from quda_tpu.obs import memory as omem
    from quda_tpu.ops import wilson_pallas_packed as wpp
    T, Z, YX, dt, odt, R, block_z, xc_dt, rc_dt = (case + (None,) * 2)[:9]
    if want is ValueError:
        with pytest.raises(ValueError, match="fits the VMEM budget"):
            wpp._mrhs_route(T, Z, YX, dt, odt, R, block_z, xc_dt, rc_dt)
        assert route_counts() == {}
        return
    route, bz, bt, limit = wpp._mrhs_route(T, Z, YX, dt, odt, R, block_z,
                                           xc_dt, rc_dt)
    rows = {r["knob"]: r for r in omem.audit_vmem_budgets()}
    assert (route, bz, bt) == want and route_counts() == {route: 1.0}
    assert route_counts("epilogue") == {
        "none" if xc_dt is None else
        "combine" if rc_dt is None else "residual": 1.0}
    assert route_counts("reduce") == {
        "none" if xc_dt is None else "norm2": 1.0}
    blocks, need = wpp._mrhs_fullz_vmem(Z, YX, dt, odt, R, bt, xc_dt,
                                        rc_dt)
    if rc_dt is not None:
        # bt spinor tiles more than with the xc block alone
        with_xc = wpp._mrhs_fullz_vmem(Z, YX, dt, odt, R, bt, xc_dt)[0]
        sub = wpp._sublane_rows(rc_dt)
        assert blocks - with_xc == (
            bt * 24 * -(-Z // sub) * sub * -(-YX // 128) * 128
            * jnp.dtype(rc_dt).itemsize)
    elif xc_dt is not None:
        # bt spinor tiles and one chunk of the body's rows in f32 (the
        # epilogue's sums), every plane padded to 128 lanes
        def padded(n, d):
            sub = wpp._sublane_rows(d)
            return -(-n // sub) * sub
        lanes = -(-YX // 128) * 128
        assert blocks - wpp._mrhs_fullz_vmem(Z, YX, dt, odt, R, bt)[0] == (
            bt * 24 * padded(Z, xc_dt) * lanes * jnp.dtype(xc_dt).itemsize
            + padded(wpp._fullz_chunk(Z, dt), jnp.float32) * lanes * 4)
    if route == "fullz":
        assert need <= limit <= wpp._MRHS_FULLZ_VMEM_CAP
        row = rows["QUDA_TPU_PALLAS_VMEM_MB[fullz]"]
        assert row["last_bz"] == Z and row["last_block_bytes"] == blocks
        assert row["double_buffer_ok"]
    else:
        assert limit is None
        assert "QUDA_TPU_PALLAS_VMEM_MB[fullz]" not in rows


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

@pytest.mark.slow
@pytest.mark.parametrize("nrhs", [1, 3, 8])
def test_staggered_mrhs_kernel_bitmatches_vmapped(nrhs):
    """dslash_staggered_pallas_mrhs bit-matches jax.vmap of the
    single-RHS two-pass kernel for N in {1, 3, 8} (fat + Naik; the
    fat/long tiles are fetched once per (t, z-block) for all N)."""
    from quda_tpu.ops import staggered_pallas as stp
    rng = np.random.default_rng(9)
    fat = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    lng = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    psi_b = jnp.asarray(rng.standard_normal(
        (nrhs, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    fat_bw = stp.backward_links(fat, KX, 1)
    long_bw = stp.backward_links(lng, KX, 3)
    want = jax.vmap(lambda p: stp.dslash_staggered_pallas(
        fat, fat_bw, p, KX, long_pl=lng, long_bw_pl=long_bw,
        interpret=True))(psi_b)
    got = stp.dslash_staggered_pallas_mrhs(
        fat, fat_bw, psi_b, KX, long_pl=lng, long_bw_pl=long_bw,
        interpret=True)
    assert bool(jnp.all(got == want))


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_staggered_mrhs_eo_kernel_bitmatches_all_parities(parity):
    """The eo staggered MRHS kernel (the batched staggered solver hot
    path) bit-matches the single-RHS eo kernel on both target parities,
    including the degenerate N=1."""
    from quda_tpu.ops import staggered_pallas as stp
    dims = (KT, KZ, KY, KX)
    Xh = KX // 2
    rng = np.random.default_rng(10)
    fat_here = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    fat_there = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    lng_here = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    lng_there = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    fat_bw = stp.backward_links_eo(fat_there, dims, parity, 1)
    long_bw = stp.backward_links_eo(lng_there, dims, parity, 3)
    for nrhs in (1, 3):
        psi_b = jnp.asarray(rng.standard_normal(
            (nrhs, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
        want = jnp.stack([stp.dslash_staggered_eo_pallas(
            fat_here, fat_bw, psi_b[i], dims, parity,
            long_here_pl=lng_here, long_bw_pl=long_bw, interpret=True)
            for i in range(nrhs)])
        got = stp.dslash_staggered_eo_pallas_mrhs(
            fat_here, fat_bw, psi_b, dims, parity,
            long_here_pl=lng_here, long_bw_pl=long_bw, interpret=True)
        assert bool(jnp.all(got == want)), (parity, nrhs)


def test_staggered_mrhs_operator_composition_matches_per_rhs():
    """Batched staggered prepare/M/reconstruct compositions are EXACTLY
    the stacked per-RHS single compositions (XLA stencil route — the
    vmap fallback; the pallas MRHS kernel is pinned above)."""
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
