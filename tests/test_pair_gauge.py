"""Complex-free gauge/HMC sector: pair representation vs complex oracle.

Reference behavior: the whole of QUDA's gauge stack (lib/gauge_force.cu,
llfat_quda.cu, unitarize_links_quda.cu, hisq_paths_force_quda.cu,
momentum.cu, gauge_update_quda.cu) runs here in BOTH representations from
one polymorphic formula codebase (ops/su3.py dispatch); every pair result
is pinned against the complex implementation, and the RHMC force/update
chain is proven complex-free by jaxpr inspection.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.gauge import action as act
from quda_tpu.gauge import hisq
from quda_tpu.gauge import observables as obs
from quda_tpu.gauge import paths as gpaths
from quda_tpu.gauge.fermion_force import rational_force
from quda_tpu.ops import staggered as sops
from quda_tpu.ops import su3
from quda_tpu.ops.boundary import apply_staggered_phases
from quda_tpu.ops.pair import from_pairs, to_pairs

GEOM = LatticeGeometry((4, 4, 4, 8))


@pytest.fixture(scope="module")
def fields():
    U = GaugeField.random(jax.random.PRNGKey(0), GEOM).data.astype(
        jnp.complex64)
    return U, to_pairs(U, jnp.float32)


def _rel(c, p):
    c, p = np.asarray(c), np.asarray(p)
    return float(np.max(np.abs(c - p)) / max(np.max(np.abs(c)), 1e-30))


def test_su3_primitives_match(fields):
    U, Up = fields
    assert _rel(su3.mat_mul(U[0], U[1]),
                from_pairs(su3.mat_mul(Up[0], Up[1]))) < 1e-5
    h = 0.1 * (U[0] + su3.dagger(U[0]))
    hp = 0.1 * (Up[0] + su3.dagger(Up[0]))
    assert _rel(su3.expm_su3(h), from_pairs(su3.expm_su3(hp))) < 1e-5
    assert _rel(su3.project_su3(U[0] + 0.05 * U[1]),
                from_pairs(su3.project_su3(Up[0] + 0.05 * Up[1]))) < 1e-5
    assert _rel(su3.trace(U[0]), from_pairs(su3.trace(Up[0]))) < 1e-5
    assert _rel(jnp.real(su3.trace(U[0])), su3.re_trace(Up[0])) < 1e-5


def test_observables_and_actions_match(fields):
    U, Up = fields
    assert _rel(obs.plaquette(U)[0], obs.plaquette(Up)[0]) < 1e-5
    assert _rel(obs.qcharge(U), obs.qcharge(Up)) < 1e-4
    assert _rel(obs.energy(U)[0], obs.energy(Up)[0]) < 1e-5
    assert _rel(obs.polyakov_loop(U),
                from_pairs(obs.polyakov_loop(Up))) < 1e-5
    assert _rel(act.wilson_action(U, 5.7), act.wilson_action(Up, 5.7)) < 1e-5
    assert _rel(act.improved_action(U, 7.0, -1.0 / 12.0),
                act.improved_action(Up, 7.0, -1.0 / 12.0)) < 1e-5
    buf = gpaths.plaquette_paths()
    assert _rel(gpaths.gauge_path_action(U, buf, [1.0] * 6),
                gpaths.gauge_path_action(Up, buf, [1.0] * 6)) < 1e-5


def test_gauge_force_matches(fields):
    U, Up = fields
    fc = act.gauge_force(lambda g: act.wilson_action(g, 5.7), U)
    fp = act.gauge_force(lambda g: act.wilson_action(g, 5.7), Up)
    assert _rel(fc, from_pairs(fp)) < 1e-4


def test_hisq_fattening_matches(fields):
    """Fat, long, and reunitarised W links — including the inverse square
    root through the interleaved-embedding eigh — match the complex path."""
    U, Up = fields
    hc = hisq.hisq_fattening(U)
    hp = hisq.hisq_fattening(Up)
    assert _rel(hc.fat, from_pairs(hp.fat)) < 1e-4
    assert _rel(hc.long, from_pairs(hp.long)) < 1e-4
    assert _rel(hc.w_unitarized, from_pairs(hp.w_unitarized)) < 1e-4


def test_cold_start_unitarize_and_force_finite():
    """Degenerate-spectrum regression: on the unit (cold-start) pair
    gauge, V^dag V is proportional to the identity — the Cardano/Cayley-
    Hamilton inverse square root and the HISQ force through it must stay
    finite (a Vandermonde solve or embedded eigh NaNs here)."""
    up = su3.unit_gauge((4,) + GEOM.lattice_shape, jnp.float32)
    links = hisq.hisq_fattening(up)
    assert bool(jnp.isfinite(links.fat).all())
    assert bool(jnp.isfinite(links.w_unitarized).all())

    def s(u):
        return jnp.sum(hisq.hisq_fattening(u).fat[..., 0] ** 2)

    f = act.gauge_force(s, up)
    assert bool(jnp.isfinite(f).all())
    # near-degenerate band (the 0*inf clip-gradient trap)
    up2 = up + 1e-4 * jax.random.normal(jax.random.PRNGKey(0), up.shape,
                                        jnp.float32)
    assert bool(jnp.isfinite(act.gauge_force(s, up2)).all())


def test_momentum_and_update_match(fields):
    U, Up = fields
    p0 = act.random_momentum(jax.random.PRNGKey(5), U.shape[:-2],
                             jnp.complex64)
    p0p = to_pairs(p0, jnp.float32)
    assert _rel(act.mom_action(p0), act.mom_action(p0p)) < 1e-5
    assert _rel(act.update_gauge(U, p0, 0.05),
                from_pairs(act.update_gauge(Up, p0p, 0.05))) < 1e-4
    # pair-native sampling has the right second moment, <p_a^2> = 1:
    # E[tr(P^2)] = sum_a tr(T_a^2) = 8 * 1/2 = 4 per link matrix
    pp = act.random_momentum(jax.random.PRNGKey(6), U.shape[:-2],
                             jnp.float32)
    assert pp.shape == U.shape[:-2] + (3, 3, 2)
    per_mat = float(act.mom_action(pp)) / (4 * GEOM.volume)
    assert abs(per_mat - 4.0) < 0.2


def _staggered_mdagm(mass):
    """make_m factory: pair links -> full-lattice staggered M^dag M
    = 4m^2 - D^2 (the RHMC rational-term operator), built complex-free
    through the entire HISQ fattening chain."""
    def make_m(u_pairs):
        links = hisq.hisq_fattening(u_pairs)
        fat = apply_staggered_phases(links.fat, GEOM)
        lng = apply_staggered_phases(links.long, GEOM, nhop=3)

        def mdagm(x):
            d = sops.dslash_full(fat, x, lng)
            return (4.0 * mass ** 2) * x - sops.dslash_full(fat, d, lng)
        return mdagm
    return make_m


def test_rational_force_matches_complex(fields):
    """RHMC fermion force (AD through fattening + reunitarisation +
    phases + the staggered stencil) — pair vs complex."""
    U, Up = fields
    mass = 0.1
    k = jax.random.PRNGKey(7)
    x1 = (jax.random.normal(k, GEOM.lattice_shape + (1, 3))
          + 1j * jax.random.normal(jax.random.fold_in(k, 1),
                                   GEOM.lattice_shape + (1, 3))
          ).astype(jnp.complex64)
    x2 = jnp.roll(x1, 1, axis=0)
    residues = (0.7, 0.3)
    fc = rational_force(_staggered_mdagm(mass), U, (x1, x2), residues)
    fp = rational_force(_staggered_mdagm(mass), Up,
                        (to_pairs(x1, jnp.float32),
                         to_pairs(x2, jnp.float32)), residues)
    assert _rel(fc, from_pairs(fp)) < 5e-4


def test_pair_hmc_energy_conservation(fields):
    """Pure-gauge leapfrog on pair arrays: dH -> 0 as dt^2 (the energy-
    conservation pin for the whole complex-free force/update chain)."""
    U, _ = fields
    Up = to_pairs(U, jnp.float64)      # f64 pairs: clean dt^2 scaling
    beta = 5.5

    def s(g):
        return act.wilson_action(g, beta)

    def dh_of(dt, nsteps):
        p0 = act.random_momentum(jax.random.PRNGKey(11),
                                 Up.shape[:-3], jnp.float64)
        h0 = act.mom_action(p0) + s(Up)
        g1, p1 = act.leapfrog(s, Up, p0, nsteps, dt)
        return abs(float(act.mom_action(p1) + s(g1) - h0))

    dh1 = dh_of(0.02, 4)
    dh2 = dh_of(0.01, 8)      # same trajectory length, half the step
    assert dh2 < dh1 * 0.35   # O(dt^2): expect ~0.25, allow slack
    assert dh1 < 1.0


# 175 s alone (PR 25): one XLA:CPU compile of the whole step
@pytest.mark.slow
def test_rhmc_step_has_no_complex_dtype(fields):
    """One full RHMC kick-drift chain (HISQ fermion force + path-table
    gauge force + momentum kick + exp update + plaquette) traces with NO
    complex dtype anywhere — on-chip executability for runtimes without
    complex64 (the round-3/4 gap this module closes)."""
    _, Up = fields
    mass, dt = 0.1, 0.01
    buf = gpaths.plaquette_paths()
    x1 = jax.random.normal(jax.random.PRNGKey(9),
                           GEOM.lattice_shape + (1, 3, 2), jnp.float32)

    def step(u, p):
        ff = rational_force(_staggered_mdagm(mass), u, (x1,), (0.8,))
        fg = gpaths.gauge_path_force(u, buf, [-5.5 / 3.0 / 4.0] * 6)
        p = p - dt * (ff + fg)
        u = act.update_gauge(u, p, dt)
        return obs.plaquette(u)[0], act.mom_action(p)

    p0 = act.random_momentum(jax.random.PRNGKey(10), Up.shape[:-3],
                             jnp.float32)
    jaxpr = jax.make_jaxpr(step)(Up, p0)
    assert "complex" not in str(jaxpr)
    plaq, ke = jax.jit(step)(Up, p0)
    assert np.isfinite(float(plaq)) and np.isfinite(float(ke))
