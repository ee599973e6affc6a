"""Staggered multigrid: KD level-0.5 + parity-chirality Galerkin hierarchy
(lib/multigrid.cpp:215 staggered-KD reset, lib/staggered_coarse_op.in.cu)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.mg.mg import MG, MGLevelParam, staggered_mg_solve
from quda_tpu.models.staggered import DiracStaggered
from quda_tpu.ops import blas
from quda_tpu.solvers.cg import cg

GEOM = LatticeGeometry((8, 8, 8, 8))
MASS = 0.02


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_caches():
    """The MG solves in this module compile some of the largest graphs in
    the suite; after ~250 earlier tests' executables accumulate in the
    process, the XLA:CPU compile of the GCR+V-cycle program has been
    observed to segfault (backend_compile_and_load).  Dropping the cached
    executables first keeps peak compiler memory bounded."""
    jax.clear_caches()
    yield


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(808)
    gauge = GaugeField.random(key, GEOM).data
    d = DiracStaggered(gauge, GEOM, MASS)
    k2 = jax.random.PRNGKey(809)
    re = jax.random.normal(k2, GEOM.lattice_shape + (1, 3))
    im = jax.random.normal(jax.random.fold_in(k2, 1),
                           GEOM.lattice_shape + (1, 3))
    b = (re + 1j * im).astype(d.fat.dtype)
    return d, b


def test_staggered_hop_decomposition(setup):
    """diag + 8 fat hops reconstructs M (plain staggered)."""
    d, b = setup
    full = d.M(b)
    parts = d.diag(b) + sum(d.hop(b, mu, s)
                            for mu in range(4) for s in (+1, -1))
    assert float(jnp.sqrt(blas.norm2(full - parts)
                          / blas.norm2(full))) < 1e-13


def test_staggered_chiral_adapter_round_trip(setup):
    from quda_tpu.mg.mg import _StaggeredLevelOp
    d, b = setup
    ad = _StaggeredLevelOp(d)
    vc = ad.to_chiral(b)
    assert vc.shape == GEOM.lattice_shape + (2, 3)
    assert np.allclose(np.asarray(ad.from_chiral(vc)), np.asarray(b))
    # chiral M equals standard M
    got = ad.from_chiral(ad.M(vc))
    assert np.allclose(np.asarray(got), np.asarray(d.M(b)), atol=1e-12)


def test_kd_adapter_is_m_xinv(setup):
    """apply_std with kd=True is M(Xinv(v)) with Xinv the block inverse."""
    from quda_tpu.mg.mg import _StaggeredLevelOp
    from quda_tpu.mg.staggered_kd import apply_kd_xinv
    d, b = setup
    ad = _StaggeredLevelOp(d, kd=True)
    got = ad.apply_std(b)
    want = d.M(apply_kd_xinv(ad.xinv, b))
    assert float(jnp.sqrt(blas.norm2(got - want)
                          / blas.norm2(want))) < 1e-12


@pytest.fixture(scope="module")
def stag_mg(setup):
    d, _ = setup
    params = [MGLevelParam(block=(2, 2, 2, 2), n_vec=8, setup_iters=60,
                           post_smooth=8, smoother="ca-gcr",
                           coarse_solver_iters=16, coarse_solver_cycles=2)]
    return MG(d, GEOM, params)


def test_staggered_mg_verify(stag_mg):
    """MG::verify analog: R P = I and Galerkin consistency at runtime."""
    report = stag_mg.verify()
    assert report[0]["rp_identity"] < 1e-10
    assert report[0]["galerkin"] < 1e-10


def test_staggered_mg_beats_cg(setup, stag_mg):
    """The VERDICT done-criterion: staggered MG converges in fewer
    fine-operator iterations than plain CG on the same system (m=0.02,
    where CG needs ~490 iterations)."""
    d, b = setup
    # nkrylov sizes the unrolled GCR cycle, one V-cycle a step: its
    # XLA:CPU compile is most of this test (same 800-step cap)
    res_mg, _ = staggered_mg_solve(d, GEOM, b, None, tol=1e-8,
                                   nkrylov=4, max_restarts=200, mg=stag_mg)
    assert bool(res_mg.converged)
    r = b - d.M(res_mg.x)
    assert float(jnp.sqrt(blas.norm2(r) / blas.norm2(b))) < 1e-7

    res_cg = cg(d.MdagM, d.Mdag(b), tol=1e-8, maxiter=2000)
    assert int(res_mg.iters) < int(res_cg.iters)
