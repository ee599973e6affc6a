"""3-level multigrid: the coarsening must recurse through CoarseOperator
(coarse-of-coarse Galerkin via the same probing) and the W/V-cycle must
still solve — lib/coarsecoarse_op* parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.spinor import ColorSpinorField
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.models.wilson import DiracWilson
from quda_tpu.ops import blas
from quda_tpu.mg.coarse import build_coarse
from quda_tpu.mg.mg import MG, MGLevelParam, mg_solve
from quda_tpu.mg.transfer import Transfer

GEOM = LatticeGeometry((8, 8, 8, 8))
KAPPA = 0.124


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(3001)
    gauge = GaugeField.random(key, GEOM).data
    d = DiracWilson(gauge, GEOM, KAPPA)
    return d, key


def test_coarse_of_coarse_galerkin(setup):
    """Second-level coarsening: coarse2.M == R2 coarse1.M P2 exactly."""
    d, key = setup
    # level-1 transfer from random vectors (Galerkin holds for any V)
    from quda_tpu.mg.mg import _FinePartsAdapter
    from quda_tpu.mg.transfer import to_chiral
    n1, n2 = 4, 4
    nulls1 = jnp.stack([
        to_chiral(ColorSpinorField.gaussian(
            jax.random.fold_in(key, i), GEOM).data) for i in range(n1)])
    tr1 = Transfer.from_null_vectors(nulls1, (2, 2, 2, 2))
    c1 = build_coarse(_FinePartsAdapter(d), tr1)

    # level-2: null vectors are coarse fields (4,4,4,4 lattice, k=n1)
    shape2 = tr1.coarse_shape + (2, n1)
    k2 = jax.random.fold_in(key, 99)
    nulls2 = (jax.random.normal(k2, (n2,) + shape2)
              + 1j * jax.random.normal(jax.random.fold_in(k2, 1),
                                       (n2,) + shape2))
    tr2 = Transfer.from_null_vectors(nulls2, (2, 2, 2, 2))
    c2 = build_coarse(c1, tr2)     # CoarseOperator exposes diag/hop itself

    v = (jax.random.normal(jax.random.fold_in(k2, 2),
                           tr2.coarse_shape + (2, n2))
         + 1j * jax.random.normal(jax.random.fold_in(k2, 3),
                                  tr2.coarse_shape + (2, n2)))
    got = c2.M(v)
    want = tr2.restrict(c1.M(tr2.prolong(v)))
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-10)


def test_three_level_mg_solve(setup):
    """8^4 -> 4^4 -> 2^4 hierarchy converges to 1e-10."""
    d, key = setup
    b = ColorSpinorField.gaussian(jax.random.fold_in(key, 7), GEOM).data
    params = [
        MGLevelParam(block=(2, 2, 2, 2), n_vec=6, setup_iters=80,
                     post_smooth=4),
        MGLevelParam(block=(2, 2, 2, 2), n_vec=6, setup_iters=60,
                     post_smooth=4, coarse_solver_iters=12),
    ]
    # nkrylov sizes the unrolled GCR cycle, one V-cycle a step: its
    # XLA:CPU compile is most of this test (same 600-step cap)
    res, mg = mg_solve(d, GEOM, b, params, tol=1e-10, nkrylov=4,
                       max_restarts=150, key=jax.random.fold_in(key, 8))
    assert len(mg.levels) == 2
    assert mg.levels[1]["transfer"].coarse_shape == (2, 2, 2, 2)
    assert bool(res.converged)
    rel = float(jnp.sqrt(blas.norm2(b - d.M(res.x)) / blas.norm2(b)))
    assert rel < 5e-10


@pytest.mark.mid
def test_intermediate_level_replication_matches(setup):
    """coarse_replicate on an INTERMEDIATE level (the subset-communicator
    analog, lib/multigrid.cpp:185): replication is a sharding constraint,
    not a math change — the V-cycle output on the 8-device virtual mesh
    must match the unconstrained one to f32 roundoff."""
    from quda_tpu.parallel.mesh import make_lattice_mesh, shard_spinor

    d, key = setup
    base = [
        MGLevelParam(block=(2, 2, 2, 2), n_vec=4, setup_iters=20,
                     post_smooth=2, coarse_solver_iters=4),
        MGLevelParam(block=(2, 2, 2, 2), n_vec=4, setup_iters=10,
                     post_smooth=2, coarse_solver_iters=8),
    ]
    mg = MG(d, GEOM, base, key=jax.random.fold_in(key, 99))
    b = ColorSpinorField.gaussian(jax.random.fold_in(key, 98), GEOM).data

    mesh = make_lattice_mesh()
    b_sh = shard_spinor(b, mesh)
    with mesh:
        plain = jax.jit(mg.precondition)(b_sh)
        plain.block_until_ready()
        # flip replication on at the intermediate seam (level-0 param)
        # and at the bottom; same hierarchy, same math
        import dataclasses
        for lv in mg.levels:
            lv["param"] = dataclasses.replace(lv["param"],
                                              coarse_replicate=True)
        repl = jax.jit(mg.precondition)(b_sh)
        repl.block_until_ready()
    num = float(blas.norm2(repl - plain))
    den = float(blas.norm2(plain))
    assert num <= 1e-10 * den, (num, den)
