"""Fused-iteration CG pipeline (solvers/fused_iter.py) and the
pallas-dslash-in-solver API routing — the round-6 tentpole surface.

Bit-tolerance documented here and in the module docstring: the cadence-k
solve follows the IDENTICAL iteration trajectory as cadence 1 and stops
at the first multiple of k past convergence (same final residual, up to
k-1 extra iterations).

The interpret-mode pallas-in-solver integration tests are marked ``slow``
(their cost is the pallas interpreter COMPILE, ~20-60 s each): the tier-1
budget is consumed by the fast oracle files, and displacing those for
interpret compiles would shrink coverage per second.  Run them directly:
``pytest tests/test_fused_iter.py -m slow``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import EVEN, LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
from quda_tpu.models.wilson import DiracWilsonPC
from quda_tpu.ops import blas
from quda_tpu.solvers.cg import cg
from quda_tpu.solvers.fused_iter import fused_cg

# small lattices keep the interpret-mode pallas solves inside the tier-1
# budget; the chip-sized configurations live in bench_suite.py
GEOM = LatticeGeometry((6, 6, 6, 6))
KAPPA = 0.12


@pytest.fixture(scope="module")
def pc_problem():
    k1, k2 = jax.random.split(jax.random.PRNGKey(42))
    gauge = GaugeField.random(k1, GEOM).data.astype(jnp.complex64)
    b = ColorSpinorField.gaussian(k2, GEOM).data.astype(jnp.complex64)
    dpc = DiracWilsonPC(gauge, GEOM, KAPPA, matpc=EVEN)
    be, bo = even_odd_split(b, GEOM)
    rhs = dpc.Mdag(dpc.prepare(be, bo))
    return dpc, rhs


# -- convergence-check cadence ----------------------------------------------

def test_check_cadence_matches_cadence_1(pc_problem):
    """QUDA_TPU_CG_CHECK_EVERY=k converges to the same final residual as
    cadence 1: identical trajectory, stop at the first multiple of k."""
    dpc, rhs = pc_problem
    tol = 1e-6
    r1 = jax.jit(lambda v: cg(dpc.MdagM, v, tol=tol, maxiter=400))(rhs)
    rk = jax.jit(lambda v: fused_cg(dpc.MdagM, v, tol=tol, maxiter=400,
                                    check_every=4))(rhs)
    assert bool(r1.converged) and bool(rk.converged)
    b2 = float(blas.norm2(rhs))
    for res in (r1, rk):
        rel = float(jnp.sqrt(
            blas.norm2(rhs - dpc.MdagM(res.x)) / b2))
        assert rel < tol
    # the cadence run stops at the first multiple of 4 past convergence
    assert int(r1.iters) <= int(rk.iters) <= int(r1.iters) + 4
    assert int(rk.iters) % 4 == 0


def test_check_cadence_env_knob(pc_problem, monkeypatch):
    from quda_tpu.utils import config as qconf
    monkeypatch.setenv("QUDA_TPU_CG_CHECK_EVERY", "3")
    qconf.reset_cache()
    dpc, rhs = pc_problem
    res = cg(dpc.MdagM, rhs, tol=1e-6, maxiter=400)
    assert bool(res.converged)
    assert int(res.iters) % 3 == 0
    qconf.reset_cache()


def test_pcg_with_cadence(pc_problem):
    """Cadence composes with a preconditioner (flexible PCG)."""
    dpc, rhs = pc_problem
    precond = lambda r: 0.9 * r          # trivial SPD preconditioner
    res = fused_cg(dpc.MdagM, rhs, tol=1e-6, maxiter=400,
                   precond=precond, check_every=2)
    assert bool(res.converged)
    rel = float(jnp.sqrt(blas.norm2(rhs - dpc.MdagM(res.x))
                         / blas.norm2(rhs)))
    assert rel < 1e-6


# -- pallas-dslash-in-solver routing ----------------------------------------

@pytest.mark.slow
def test_invert_quda_routes_pallas_v2_inside_solve(monkeypatch):
    """invert_quda routes the pallas eo dslash INSIDE
    the compiled solve via config (CPU: interpreter mode), and the PC
    GFLOPS accounting charges volume/2."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.ops import wilson_pallas_packed as wpp
    from quda_tpu.utils import config as qconf

    monkeypatch.setenv("QUDA_TPU_PALLAS", "1")
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    qconf.reset_cache()

    calls = {"n": 0}
    orig = wpp.dslash_eo_pallas_packed

    def spy(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(wpp, "dslash_eo_pallas_packed", spy)

    api.init_quda()
    try:
        k1, k2 = jax.random.split(jax.random.PRNGKey(5))
        gauge = GaugeField.random(k1, GEOM).data.astype(jnp.complex64)
        api.load_gauge_quda(np.asarray(gauge),
                            GaugeParam(X=tuple(GEOM.lattice_shape),
                                       cuda_prec="single"))
        b = np.asarray(ColorSpinorField.gaussian(k2, GEOM).data.astype(
            jnp.complex64))
        p = InvertParam(dslash_type="wilson", inv_type="cg",
                        solve_type="normop-pc", kappa=KAPPA, tol=1e-6,
                        maxiter=500, cuda_prec="single",
                        cuda_prec_sloppy="single")
        api.invert_quda(b, p)
        # the kernel actually executed inside the compiled solve
        assert calls["n"] > 0
        assert p.true_res < 5e-4
        # PC accounting: flops charged per UPDATED (half-lattice) site
        vol = int(np.prod(GEOM.lattice_shape))
        expected = (p.iter_count * 2.0 * (2 * 1320 + 48)
                    * (vol // 2)) / 1e9
        assert abs(p.gflops - expected) / expected < 1e-12
    finally:
        api.end_quda()
    qconf.reset_cache()


@pytest.mark.slow
def test_single_device_mesh_escapes_to_measured_winner():
    """A 1-device mesh shards nothing: it is dropped, and the operator
    is the unsharded one."""
    from jax.sharding import Mesh
    geom = GEOM_PAIR
    gauge = GaugeField.random(jax.random.PRNGKey(9), geom).data.astype(
        jnp.complex64)
    dpk = DiracWilsonPC(gauge, geom, KAPPA).packed()
    mesh1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("t", "z"))
    op = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   mesh=mesh1)
    assert op._mesh is None            # trivial mesh dropped
    # reference: the XLA pair stencil (avoids a second interpret compile)
    ref = dpk.pairs(jnp.float32)
    T, Z, Y, X = geom.lattice_shape
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 3, 2, T, Z, Y * X // 2)), jnp.float32)
    np.testing.assert_allclose(np.asarray(op.M_pairs(x)),
                               np.asarray(ref.M_pairs(x)),
                               rtol=1e-5, atol=1e-5)


def test_mesh_policy_emits_one_time_provenance_notice(monkeypatch,
                                                      capsys):
    """Under a multi-device mesh a one-time provenance notice names
    the selected halo policy and how it was chosen — a policy must
    never take effect silently."""
    import quda_tpu.models.wilson as mwil
    from quda_tpu.parallel.mesh import make_lattice_mesh
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    monkeypatch.setattr(mwil, "_SHARDED_NOTICED", False)
    geom = LatticeGeometry((4, 4, 8, 16))
    gauge = GaugeField.random(jax.random.PRNGKey(11), geom).data.astype(
        jnp.complex64)
    dpk = DiracWilsonPC(gauge, geom, KAPPA).packed()
    mesh = make_lattice_mesh(grid=(4, 2, 1, 1), n_src=1)
    op = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   mesh=mesh, sharded_policy="xla_facefix")
    assert op._u_bw is not None        # the gather kernel's links
    err = capsys.readouterr().err       # qlog emits on stderr
    assert "pallas eo interior" in err
    assert "halo policy xla_facefix" in err
    # one-time: a second construction stays quiet
    dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
              mesh=mesh, sharded_policy="xla_facefix")
    assert "halo policy" not in capsys.readouterr().err
