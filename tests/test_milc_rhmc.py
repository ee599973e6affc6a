"""MILC interface breadth: HISQ RHMC trajectory end-to-end + the new
qudaXxx entry points (quda_milc_interface.h parity)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.interfaces import milc
from quda_tpu.interfaces import quda_api as api
from quda_tpu.ops import blas

GEOM = LatticeGeometry((4, 4, 4, 4))
MASS = 0.1


@pytest.fixture(scope="module")
def ctx():
    key = jax.random.PRNGKey(515)
    gauge = GaugeField.random(key, GEOM).data
    milc.qudaInit()
    milc.qudaSetLayout(GEOM.dims)
    milc.qudaHisqParamsInit()
    milc.qudaLoadGauge(gauge, GEOM.dims)
    return gauge


def _stag_source(seed):
    k = jax.random.PRNGKey(seed)
    re = jax.random.normal(k, GEOM.lattice_shape + (1, 3))
    im = jax.random.normal(jax.random.fold_in(k, 1),
                           GEOM.lattice_shape + (1, 3))
    return (re + 1j * im).astype(jnp.complex128)


def test_full_hisq_rhmc_step(ctx):
    """One complete RHMC leapfrog step through the MILC surface: KS-link
    fattening, pseudofermion multishift (rational) solve, HISQ fermion
    force + path-table gauge force, momentum update, U update,
    reunitarisation, observables."""
    from quda_tpu.gauge.action import random_momentum
    from quda_tpu.gauge.paths import plaquette_paths
    milc.qudaComputeKSLink()
    assert api._ctx["fat"] is not None and api._ctx["long"] is not None

    # pseudofermion on the even-parity PC system
    from quda_tpu.fields.spinor import even_odd_split
    phi_full = _stag_source(1)
    phi = even_odd_split(phi_full, GEOM)[0]

    # rational-fraction solve (shared Krylov, the RHMC inner loop)
    shifts = (0.01, 0.05, 0.25)
    xs = milc.qudaMultishiftInvert(MASS, shifts, phi_full, tol=1e-8,
                                   maxiter=2000)
    assert xs.shape[0] == len(shifts)

    # forces: fermion (AD through the fattening) + gauge (path tables)
    f_fermion = milc.qudaHisqForce(MASS, phi, n_cg_iters=12)
    mom0 = random_momentum(jax.random.PRNGKey(2),
                           api._ctx["gauge"].shape[:-2])
    milc.qudaMomLoad(mom0)
    h0 = milc.qudaMomAction(mom0)
    dt = 0.01
    mom = milc.qudaGaugeForcePhased(
        mom0, plaquette_paths(), [-5.5 / 3.0 / 4.0] * 6, dt)
    mom = mom - dt * f_fermion
    milc.qudaUpdateU(mom, dt)
    milc.qudaUnitarizeSU3()
    obs = milc.qudaGaugeMeasurementsPhased()
    assert np.isfinite(obs["plaquette"][0])
    assert np.isfinite(complex(obs["polyakov"]).real)
    assert np.isfinite(obs["qcharge"])
    assert np.isfinite(milc.qudaMomAction(mom)) and h0 > 0
    # links stayed unitary after the update + projection
    g = api._ctx["gauge"]
    uu = jnp.einsum("...ab,...cb->...ac", g, jnp.conjugate(g))
    eye = jnp.eye(3, dtype=g.dtype)
    assert float(jnp.max(jnp.abs(uu - eye))) < 1e-10


def test_quda_shift_covariance(ctx):
    """qudaShift forward then matching backward returns the original on a
    unitary gauge field (U^dag U = 1)."""
    milc.qudaLoadGauge(ctx, GEOM.dims)
    v = _stag_source(3)[..., 0, :]
    fwd = milc.qudaShift(v, 0)
    back = milc.qudaShift(fwd, 7)
    assert np.allclose(np.asarray(back), np.asarray(v), atol=1e-12)


def test_quda_spin_taste_runs(ctx):
    v = _stag_source(4)[..., 0, :]
    out = milc.qudaSpinTaste(v, "G5", "G5GX")
    assert np.isfinite(float(blas.norm2(out)))


def test_two_link_gaussian_smear_is_smoothing(ctx):
    """Smearing reduces the high-frequency content (norm of the lattice
    Laplacian image shrinks relative to the field norm)."""
    milc.qudaFreeTwoLink()
    v = _stag_source(5)[..., 0, :]
    sm = milc.qudaTwoLinkGaussianSmear(v, width=2.0, n_steps=10)
    assert sm.shape == v.shape

    def roughness(f):
        # two-link smearing smooths within a parity class: measure with
        # 2-hop differences (1-hop mixes parities, untouched by design)
        from quda_tpu.ops.shift import shift
        acc = 0.0
        for mu in range(3):
            d = f - shift(f, mu, +1, nhop=2)
            acc = acc + float(blas.norm2(d))
        return acc / float(blas.norm2(f))

    assert roughness(sm) < roughness(v)


def test_msrc_and_eigcg_and_dd_invert(ctx):
    milc.qudaLoadGauge(ctx, GEOM.dims)
    srcs = jnp.stack([_stag_source(10), _stag_source(11)])
    xs, info = milc.qudaInvertMsrc(MASS, srcs, tol=1e-8, improved=False)
    from quda_tpu.models.staggered import DiracStaggered
    d = DiracStaggered(ctx, GEOM, MASS)
    for i in range(2):
        r = srcs[i] - d.M(xs[i])
        assert float(jnp.sqrt(blas.norm2(r) / blas.norm2(srcs[i]))) < 1e-6

    x, info = milc.qudaEigCGInvert(MASS, srcs[0], tol=1e-8,
                                   improved=False)
    r = srcs[0] - d.M(x)
    assert float(jnp.sqrt(blas.norm2(r) / blas.norm2(srcs[0]))) < 1e-6

    x, info = milc.qudaDDInvert(MASS, srcs[0], domain=(2, 2, 2, 2),
                                tol=1e-7, improved=False)
    assert info["converged"]
    r = srcs[0] - d.M(x)
    assert float(jnp.sqrt(blas.norm2(r) / blas.norm2(srcs[0]))) < 1e-6


def test_clover_family(ctx):
    milc.qudaLoadGauge(ctx, GEOM.dims)
    from quda_tpu.fields.spinor import ColorSpinorField
    b = ColorSpinorField.gaussian(jax.random.PRNGKey(20), GEOM).data
    x, info = milc.qudaCloverInvert(0.12, 1.0, b, tol=1e-9,
                                    sloppy_prec="double")
    from quda_tpu.models.clover import DiracClover
    d = DiracClover(ctx, GEOM, 0.12, 1.0)
    r = b - d.M(jnp.asarray(x))
    assert float(jnp.sqrt(blas.norm2(r) / blas.norm2(b))) < 1e-7

    up, dn = milc.qudaCloverTrace(0.12, 1.0)
    assert np.isfinite(complex(up).real) and np.isfinite(complex(dn).real)

    f = milc.qudaCloverDerivative(0.12, 1.0)
    from quda_tpu.ops.su3 import dagger, trace
    assert np.allclose(np.asarray(trace(f)), 0.0, atol=1e-10)
    assert np.allclose(np.asarray(f), np.asarray(dagger(f)), atol=1e-12)


def test_clover_multishift_is_the_api_call(ctx):
    """qudaCloverMultishiftInvert goes through invert_multishift_quda:
    the API's solutions and per-shift numbers, shift by shift (here in
    double precision, the API's canonical branch; the resident route in
    single precision is tests/test_clover_multishift_resident.py's)."""
    from quda_tpu.fields.spinor import ColorSpinorField
    from quda_tpu.interfaces.params import InvertParam
    milc.qudaLoadGauge(ctx, GEOM.dims)
    b = ColorSpinorField.gaussian(jax.random.PRNGKey(21), GEOM).data
    offsets = (0.01, 0.1, 1.0)
    xs, info = milc.qudaCloverMultishiftInvert(0.12, 1.0, offsets, b,
                                               tol=1e-9, maxiter=500)
    p = InvertParam(dslash_type="clover", kappa=0.12, csw=1.0,
                    inv_type="multi-shift-cg", solve_type="normop-pc",
                    tol=1e-9, maxiter=500, cuda_prec="double",
                    num_offset=3, offset=offsets)
    want = api.invert_multishift_quda(b, p)
    assert xs.shape == (3,) + GEOM.lattice_shape[:3] + (2, 4, 3)
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(want))
    assert info == {"iters": p.iter_count,
                    "true_res_offset": list(p.true_res_offset),
                    "iter_res_offset": list(p.iter_res_offset),
                    "iter_count_offset": list(p.iter_count_offset),
                    "converged": [True] * 3}
    assert max(info["true_res_offset"]) < 1e-8 and info["iters"] > 5


def test_oprod_shapes(ctx):
    qs = jnp.stack([_stag_source(30)[..., 0, :],
                    _stag_source(31)[..., 0, :]])
    one, three = milc.qudaComputeOprod(qs, (0.7, 0.3))
    assert one.shape == (4,) + GEOM.lattice_shape + (3, 3)
    assert three.shape == one.shape


def test_gauge_field_file_round_trip(ctx, tmp_path):
    milc.qudaLoadGauge(ctx, GEOM.dims)
    p0 = milc.qudaPlaquettePhased()
    path = str(tmp_path / "milc_cfg.lime")
    milc.qudaSaveGaugeField(path)
    milc.qudaFreeGaugeField()
    api.load_gauge_field_quda(path, api.GaugeParam(cuda_prec="double"))
    assert np.allclose(np.asarray(milc.qudaPlaquettePhased()),
                       np.asarray(p0))


def test_phased_update_and_fixing_and_handles(ctx):
    """The last quda_milc_interface.h entries: phased gauge updates
    (peel phases -> exp update -> restore, matching the plain update on
    unphased links), OVR/FFT gauge fixing driving theta down, and the
    memory/comm handle no-ops."""
    from quda_tpu.gauge.action import random_momentum
    from quda_tpu.gauge.observables import plaquette

    g0 = api._ctx["gauge"]
    mom = random_momentum(jax.random.PRNGKey(77), g0.shape[:-2])
    dt = 0.01

    # phased update == plain update: the resident gauge is always the
    # canonical unphased field (the phase flag is a host-layout concern
    # the resident model subsumes, like qudaGaugeForcePhased)
    milc.qudaUpdateU(mom, dt)
    g_plain = api._ctx["gauge"]
    api._set_resident_gauge(g0)
    milc.qudaUpdateUPhasedPipeline(mom, dt, phase_in=True,
                                   want_gaugepipe=True)
    g_phased = api._ctx["gauge"]
    assert float(jnp.max(jnp.abs(g_plain - g_phased))) < 1e-12

    # gauge fixing: theta decreases and the plaquette is preserved
    from quda_tpu.gauge.fix import gaugefix_quality
    p0 = float(plaquette(api._ctx["gauge"])[0])
    _, theta0 = gaugefix_quality(api._ctx["gauge"])
    iters, theta = milc.qudaGaugeFixingOVR(max_iter=40, tolerance=1e-30)
    assert float(theta) < 0.5 * float(theta0)
    p1 = float(plaquette(api._ctx["gauge"])[0])
    assert abs(p0 - p1) < 1e-10          # fixing is a gauge transform
    _, theta1 = gaugefix_quality(api._ctx["gauge"])
    iters_f, theta_f = milc.qudaGaugeFixingFFT(max_iter=20,
                                               tolerance=1e-30)
    assert float(theta_f) < float(theta1)

    # handle management: standalone device handles leave the resident
    # gauge untouched (the reference's qudaCreateGaugeField contract)
    h = milc.qudaCreateGaugeField(None, geometry=4, precision=1)
    assert h.shape == api._ctx["gauge"].shape
    milc.qudaDestroyGaugeField(h)
    assert api._ctx["gauge"] is not None
    buf = milc.qudaAllocatePinned(128)
    milc.qudaFreePinned(buf)
    milc.qudaFreeManaged(milc.qudaAllocateManaged(64))
    milc.qudaSetMPICommHandle(object())
    milc.qudaFreeGaugeField()
    assert api._ctx["gauge"] is None
    # restore the resident gauge for any later module tests
    api._set_resident_gauge(g0)


def test_asqtad_force_finite(ctx):
    """qudaAsqtadForce end-to-end (quda_milc_interface.h:1147): the
    asqtad fattening chain (fat7+Naik, no reunitarisation) must produce
    a finite, antihermitian-shaped force.  Regression: the coefficient
    set was constructed as HisqCoeffs() with no arguments, which raises
    TypeError before the fattening runs."""
    from quda_tpu.fields.spinor import even_odd_split
    milc.qudaLoadGauge(ctx, GEOM.dims)
    be, _ = even_odd_split(_stag_source(77), GEOM)
    f = milc.qudaAsqtadForce(MASS, be, tol=1e-5)
    fn = np.asarray(f)
    assert fn.shape[0] == 4 and np.isfinite(fn).all()
