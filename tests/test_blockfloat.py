"""bf16 / int8 block-float storage codec tests, incl. use as the sloppy
format inside reliable-update CG (the half-precision-solver pattern)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.models.wilson import DiracWilsonPC
from quda_tpu.ops import blas
from quda_tpu.ops.blockfloat import (from_bf16, from_int8, to_bf16, to_int8)
from quda_tpu.solvers.mixed import solve_refined
from quda_tpu.solvers.cg import cg

GEOM = LatticeGeometry((4, 4, 4, 4))


def test_bf16_roundtrip_accuracy():
    x = ColorSpinorField.gaussian(jax.random.PRNGKey(1), GEOM,
                                  dtype=jnp.complex64).data
    back = from_bf16(to_bf16(x))
    rel = float(jnp.sqrt(blas.norm2(x - back) / blas.norm2(x)))
    assert rel < 1e-2          # bf16: ~8 mantissa bits
    assert to_bf16(x).data.dtype == jnp.bfloat16


def test_int8_roundtrip_accuracy():
    x = ColorSpinorField.gaussian(jax.random.PRNGKey(2), GEOM,
                                  dtype=jnp.complex64).data
    f = to_int8(x)
    assert f.data.dtype == jnp.int8
    back = from_int8(f)
    rel = float(jnp.sqrt(blas.norm2(x - back) / blas.norm2(x)))
    assert rel < 2e-2          # 7-bit mantissa + per-site scale


def test_int8_scale_is_per_site():
    x = ColorSpinorField.gaussian(jax.random.PRNGKey(3), GEOM,
                                  dtype=jnp.complex64).data
    # make one site huge: other sites must keep full relative accuracy
    x = x.at[0, 0, 0, 0].multiply(1e4)
    f = to_int8(x)
    back = from_int8(f)
    other = x[1:, :, :, :]
    rel = float(jnp.sqrt(blas.norm2(other - back[1:])
                         / blas.norm2(other)))
    assert rel < 2e-2


def test_gauge_int8_roundtrip():
    g = GaugeField.random(jax.random.PRNGKey(4), GEOM,
                          dtype=jnp.complex64).data
    back = from_int8(to_int8(g))
    rel = float(jnp.sqrt(blas.norm2(g - back) / blas.norm2(g)))
    assert rel < 2e-2


def test_bf16_sloppy_refinement_reaches_double():
    """Iterative refinement whose inner solve runs on a bf16-compressed
    gauge field still reaches 1e-10 — the QUDA half-precision-sloppy
    solver pattern with the TPU codec."""
    key = jax.random.PRNGKey(5)
    gauge = GaugeField.random(key, GEOM).data
    dpc = DiracWilsonPC(gauge, GEOM, 0.12)
    b = even_odd_split(ColorSpinorField.gaussian(
        jax.random.fold_in(key, 1), GEOM).data, GEOM)[0]
    rhs = dpc.Mdag(dpc.prepare(b, jnp.zeros_like(b)))

    g_lo = from_bf16(to_bf16(gauge.astype(jnp.complex64)))
    dpc_lo = DiracWilsonPC(g_lo, GEOM, 0.12)
    inner = jax.jit(lambda r: cg(dpc_lo.MdagM, r, tol=1e-3,
                                 maxiter=200).x.astype(jnp.complex64))
    res = solve_refined(dpc.MdagM, inner, rhs, jnp.complex64, tol=1e-10,
                        max_cycles=40)
    assert bool(res.converged)
    rel = float(jnp.sqrt(blas.norm2(rhs - dpc.MdagM(res.x))
                         / blas.norm2(rhs)))
    assert rel < 2e-10


# -- int8 block-float LINK storage (round 16) --------------------------------

def _packed_link_planes(seed=7, T=4, Z=4, YX=16):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((4, 3, 3, 2, T, Z, YX)),
                       jnp.float32)


def test_int8_links_roundtrip_bounds():
    """to_int8_links: one f32 scale per (direction, site), max-abs over
    the link's 18 reals / 127; the round-trip error is bounded per
    entry by half a quantisation step of THAT link's scale."""
    from quda_tpu.ops.blockfloat import from_int8_links, to_int8_links
    g = _packed_link_planes()
    q, scale = to_int8_links(g)
    assert q.dtype == jnp.int8 and q.shape == g.shape
    assert scale.dtype == jnp.float32 and scale.shape == (4, 4, 4, 16)
    # the scale is exactly max-abs/127 over the link matrix reals
    np.testing.assert_allclose(np.asarray(scale),
                               np.max(np.abs(np.asarray(g)),
                                      axis=(1, 2, 3)) / 127.0,
                               rtol=1e-6)
    back = from_int8_links(q, scale)
    err = np.abs(np.asarray(back) - np.asarray(g))
    bound = 0.5 * np.asarray(scale)[:, None, None, None] + 1e-7
    assert (err <= bound).all()
    rel = float(jnp.sqrt(blas.norm2(g - back) / blas.norm2(g)))
    assert rel < 5e-3          # 7-bit mantissas + per-link scale


def test_int8_links_scale_is_per_direction_site():
    """One outlier link (one direction of one site) must not degrade
    any other link's quantisation — the block is a single 3x3 matrix,
    not a plane."""
    from quda_tpu.ops.blockfloat import from_int8_links, to_int8_links
    g = _packed_link_planes(seed=8)
    g = g.at[2, :, :, :, 1, 2, 3].multiply(1e4)
    back = from_int8_links(*to_int8_links(g))
    mask = np.zeros(g.shape, bool)
    mask[2, :, :, :, 1, 2, 3] = True
    rest_g = np.asarray(g)[~mask]
    rest_b = np.asarray(back)[~mask]
    rel = np.sqrt(np.sum((rest_g - rest_b) ** 2) / np.sum(rest_g ** 2))
    assert rel < 5e-3


# 132 s alone (PR 25): an interpreted df64 CG to 1e-10, at 4^4 already
@pytest.mark.slow
def test_int8_links_df64_acceptance_drill(monkeypatch):
    """Round-16 acceptance drill: 'quarter' sloppy = int8 block-float
    links under the df64 reliable-update CG.  The quantised sloppy
    operator only slows iteration; the df64 precise side re-anchors the
    residual, so the solve still certifies a true residual <= 1e-10
    with robust supervision recording the verified exit."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    from quda_tpu.utils import config as qconf

    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    monkeypatch.setenv("QUDA_TPU_ROBUST", "verify")
    # pallas (interpreter off-TPU): the sloppy loop runs the SAME
    # in-kernel int8 decompression the chip serves — and the interpreted
    # kernels compile in seconds where the XLA packed stencil's CPU
    # compile takes minutes (see test_df64's route test)
    monkeypatch.setenv("QUDA_TPU_PALLAS", "1")
    qconf.reset_cache()
    geom = GEOM
    api.init_quda()
    try:
        gauge = GaugeField.random(jax.random.PRNGKey(11), geom
                                  ).data.astype(jnp.complex64)
        api.load_gauge_quda(gauge, GaugeParam(X=(4, 4, 4, 4)))
        b = ColorSpinorField.gaussian(jax.random.PRNGKey(12), geom
                                      ).data.astype(jnp.complex64)
        p = InvertParam(dslash_type="wilson", inv_type="cg",
                        solve_type="normop-pc", kappa=0.11, tol=1e-10,
                        maxiter=4000, cuda_prec="single",
                        cuda_prec_sloppy="quarter")
        x = api.invert_quda(b, p)
        assert p.solve_status == "converged", p.solve_status
        assert p.converged
        assert p.verified_res <= 1e-10, p.verified_res
        assert np.isfinite(np.asarray(x)).all()
        # oracle: residual of (x + lo word) under the f64-embedded
        # f32-link operator — 1e-10 is real, not self-reported
        from quda_tpu.models.wilson import DiracWilson
        d64 = DiracWilson(gauge.astype(jnp.complex128), geom, kappa=0.11)
        xf = (x.astype(jnp.complex128)
              + p.x_df64_lo.astype(jnp.complex128))
        r = b.astype(jnp.complex128) - d64.M(xf)
        rel = float(jnp.sqrt(blas.norm2(r)
                             / blas.norm2(b.astype(jnp.complex128))))
        assert rel < 1e-10, rel
    finally:
        api.end_quda()
        qconf.reset_cache()
