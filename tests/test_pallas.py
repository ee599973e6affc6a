"""Pallas Wilson kernels: spin-projection table structure and correctness
vs the XLA stencils (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.spinor import ColorSpinorField
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.ops import wilson as wops
from quda_tpu.ops.boundary import apply_t_boundary
from quda_tpu.ops.wilson_packed import TABLES

GEOM = LatticeGeometry((4, 4, 4, 6))


def test_projection_tables_complete():
    assert len(TABLES) == 8
    for (mu, sign), t in TABLES.items():
        assert set(t) == {"j0", "c0", "j1", "c1", "k2", "d2", "k3", "d3"}
        for c in (t["c0"], t["c1"], t["d2"], t["d3"]):
            assert abs(abs(c) - 1.0) < 1e-12  # coefficients are +-1, +-i


@pytest.mark.parametrize("antiperiodic", [False, True])
@pytest.mark.parametrize("shape", [(8, 4, 6, 4), (8, 4, 2, 6),
                                   (4, 4, 4, 6)])
def test_pallas_packed_matches_xla_packed(shape, antiperiodic):
    """Packed-layout pallas dslash (single psi fetch per plane,
    lane-roll shifts) == the XLA packed stencil AND the canonical XLA
    stencil, on isotropic and anisotropic lattices, with and without the
    antiperiodic-t phase folded into the links (interpret mode)."""
    from quda_tpu.ops import blas
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.ops import wilson_pallas_packed as wpp
    geom = LatticeGeometry(shape)
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(3), geom).data.astype(
        jnp.complex64)
    if antiperiodic:
        gauge = apply_t_boundary(gauge, geom, -1)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(4), geom).data.astype(
        jnp.complex64)
    gp, pp = wpk.pack_gauge(gauge), wpk.pack_spinor(psi)
    out = wpp.from_pallas_layout(wpp.dslash_pallas_packed(
        wpp.to_pallas_layout(gp), wpp.to_pallas_layout(pp), X,
        interpret=True))
    ref = wpk.dslash_packed(gp, pp, X, Y)
    assert float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref))) < 1e-6
    want = np.asarray(wops.dslash_full(gauge, psi))
    got = np.asarray(wpk.unpack_spinor(out, (T, Z, Y, X)))
    assert np.allclose(got, want, atol=3e-6 * np.max(np.abs(want)))


@pytest.mark.parametrize(
    "bz", [pytest.param(1, marks=pytest.mark.slow), 2])
def test_pallas_packed_multi_z_block(bz):
    """The z-blocked grid (the configuration the 24^4 headline bench
    runs: nzb > 1) splices boundary rows from neighbouring z-blocks —
    must bit-match the single-block kernel.  Tier-1 keeps three blocks
    of two rows; six of one (40 s of compile in a whole run) is slow."""
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.spinor import ColorSpinorField
    from quda_tpu.ops import blas
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.ops import wilson_pallas_packed as wpp
    geom = LatticeGeometry((4, 4, 6, 4))  # Z=6: nzb = 6, 3
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(5), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(6), geom).data.astype(
        jnp.complex64)
    gp, pp = wpk.pack_gauge(gauge), wpk.pack_spinor(psi)
    ref = wpk.dslash_packed(gp, pp, X, Y)
    out = wpp.from_pallas_layout(wpp.dslash_pallas_packed(
        wpp.to_pallas_layout(gp), wpp.to_pallas_layout(pp), X,
        interpret=True, block_z=bz))
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("parity", [0, 1])
@pytest.mark.parametrize("bz", [None, 2])
def test_pallas_eo_matches_xla_eo(parity, bz):
    """Even/odd pallas kernel (the solver hot-path stencil) == the XLA
    eo-pairs stencil, both parities, single and multi z-block."""
    import jax
    import jax.numpy as jnp
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
    from quda_tpu.ops.wilson import split_gauge_eo
    from quda_tpu.ops import blas
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.ops import wilson_pallas_packed as wpp

    geom = LatticeGeometry((4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    dims = (T, Z, Y, X)
    gauge = GaugeField.random(jax.random.PRNGKey(7), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(8), geom).data.astype(
        jnp.complex64)
    gauge_eo = split_gauge_eo(gauge, geom)
    pe, po = even_odd_split(psi, geom)
    src = pe if parity == 1 else po  # parity-(1-p) source

    gauge_eo_pp = tuple(wpk.to_packed_pairs(wpk.pack_gauge(g), jnp.float32)
                        for g in gauge_eo)
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(src), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(gauge_eo_pp, src_pp, dims, parity)

    u_bw = wpp.backward_gauge_eo(gauge_eo_pp[1 - parity], dims, parity)
    out = wpp.dslash_eo_pallas_packed(gauge_eo_pp[parity], u_bw, src_pp,
                                      dims, parity, interpret=True,
                                      block_z=bz)
    err = float(jnp.sqrt(
        blas.norm2(ref.astype(jnp.float32) - out.astype(jnp.float32))
        / blas.norm2(ref.astype(jnp.float32))))
    assert err < 1e-6


def test_pallas_eo_operator_in_cg():
    """The pallas-enabled packed pairs operator drives a CG solve to the
    same solution as the XLA pairs operator (interpret mode)."""
    import jax
    import jax.numpy as jnp
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
    from quda_tpu.models.wilson import DiracWilsonPC, DiracWilsonPCPacked
    from quda_tpu.ops import blas
    from quda_tpu.solvers.cg import cg

    geom = LatticeGeometry((4, 4, 4, 4))
    gauge = GaugeField.random(jax.random.PRNGKey(9), geom).data.astype(
        jnp.complex64)
    b = ColorSpinorField.gaussian(jax.random.PRNGKey(10), geom).data.astype(
        jnp.complex64)
    dpc = DiracWilsonPC(gauge, geom, kappa=0.11)
    dpk = DiracWilsonPCPacked(dpc)
    be, bo = even_odd_split(b, geom)
    rhs = dpk.prepare(be, bo)

    op_x = dpk.pairs(jnp.float32)
    op_p = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True)
    rx = cg(op_x.MdagM, rhs, tol=1e-8, maxiter=200)
    rp = cg(op_p.MdagM, rhs, tol=1e-8, maxiter=200)
    err = float(jnp.sqrt(blas.norm2(rx.x - rp.x) / blas.norm2(rx.x)))
    assert err < 1e-5


@pytest.mark.parametrize(
    "antiperiodic", [True, pytest.param(False, marks=pytest.mark.slow)])
@pytest.mark.parametrize("kernel", ["full_lattice", "eo"])
def test_pallas_recon12_matches_full(kernel, antiperiodic):
    """Reconstruct-12 storage (rows 0-1 + in-kernel cross-product third
    row, gauge_field_order.h Reconstruct<12> analog) on SU(3) links ==
    the XLA stencil on the full 18-real links, with and without the
    folded antiperiodic-t phase (whose sign must be re-applied to the
    reconstructed row: at t = T-1 on the forward links, at t = 0 on the
    pre-shifted backward ones), on the full-lattice and the even-odd
    kernel.  Tier-1 keeps the antiperiodic cases, the same kernels with
    the sign planes live; each is a 20-30 s compile."""
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.ops import blas
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.ops import wilson_pallas_packed as wpp
    from quda_tpu.ops.wilson import split_gauge_eo

    geom = LatticeGeometry((4, 4, 6, 4))
    T, Z, Y, X = dims = tuple(geom.lattice_shape)
    gauge = GaugeField.random(jax.random.PRNGKey(11), geom).data.astype(
        jnp.complex64)
    if antiperiodic:
        gauge = apply_t_boundary(gauge, geom, -1)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(12),
                                    geom).data.astype(jnp.complex64)
    if kernel == "full_lattice":
        gp, pp = wpk.pack_gauge(gauge), wpk.pack_spinor(psi)
        full = wpp.to_pallas_layout(wpk.dslash_packed(gp, pp, X, Y))
        r12 = wpp.dslash_pallas_packed(
            wpp.to_recon12(wpp.to_pallas_layout(gp)),
            wpp.to_pallas_layout(pp), X, interpret=True,
            tb_sign=antiperiodic)
    else:
        links = tuple(wpk.to_packed_pairs(wpk.pack_gauge(g), jnp.float32)
                      for g in split_gauge_eo(gauge, geom))
        src = wpk.to_packed_pairs(
            wpk.pack_spinor(even_odd_split(psi, geom)[1]), jnp.float32)
        full = wpk.dslash_eo_packed_pairs(links, src, dims, 0)
        r12 = wpp.dslash_eo_pallas_packed(
            wpp.to_recon12(links[0]),
            wpp.to_recon12(wpp.backward_gauge_eo(links[1], dims, 0)),
            src, dims, 0, interpret=True, tb_sign=antiperiodic)
    err = float(jnp.sqrt(blas.norm2(full - r12) / blas.norm2(full)))
    assert err < 1e-5


@pytest.mark.slow
def test_pallas_eo_v2_recon12_matches_full_storage():
    """The eo kernel reads 2-row storage through _link_getter
    (pre-shifted backward links compressed too, t-boundary row-2 signs
    at the t=T-1 forward / t=0 backward planes) and must reproduce the
    full-storage operator to f32 reconstruction accuracy."""
    import os

    import jax
    import jax.numpy as jnp
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.ops import blas
    from quda_tpu.utils import config as qconf

    geom = LatticeGeometry((4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(15), geom).data.astype(
        jnp.complex64)
    dpc = DiracWilsonPC(gauge, geom, kappa=0.12)
    rhs = jax.random.normal(jax.random.PRNGKey(16),
                            (4, 3, 2, T, Z, Y * X // 2), jnp.float32)
    prev = os.environ.get("QUDA_TPU_RECONSTRUCT")
    try:
        os.environ["QUDA_TPU_RECONSTRUCT"] = "18"
        qconf.reset_cache()
        sl_full = dpc.packed().pairs(jnp.float32, use_pallas=True,
                                     pallas_interpret=True)
        os.environ["QUDA_TPU_RECONSTRUCT"] = "12"
        qconf.reset_cache()
        sl_r12 = dpc.packed().pairs(jnp.float32, use_pallas=True,
                                    pallas_interpret=True)
    finally:
        if prev is None:
            os.environ.pop("QUDA_TPU_RECONSTRUCT", None)
        else:
            os.environ["QUDA_TPU_RECONSTRUCT"] = prev
        qconf.reset_cache()
    assert sl_r12.gauge_eo_pp[0].shape[1] == 2       # compressed resident
    assert sl_r12._u_bw[0].shape[1] == 2             # backward copy too
    a = sl_full.MdagM_pairs(rhs)
    b = sl_r12.MdagM_pairs(rhs)
    err = float(jnp.sqrt(blas.norm2(a - b) / blas.norm2(a)))
    assert err < 1e-5
