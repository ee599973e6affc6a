"""Pallas Wilson kernel: spin-projection table structure and correctness
vs the XLA stencil (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.spinor import ColorSpinorField
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.ops import wilson as wops
from quda_tpu.ops.boundary import apply_t_boundary
from quda_tpu.ops.wilson_pallas import TABLES, dslash_pallas

GEOM = LatticeGeometry((4, 4, 4, 6))


def test_projection_tables_complete():
    assert len(TABLES) == 8
    for (mu, sign), t in TABLES.items():
        assert set(t) == {"j0", "c0", "j1", "c1", "k2", "d2", "k3", "d3"}
        for c in (t["c0"], t["c1"], t["d2"], t["d3"]):
            assert abs(abs(c) - 1.0) < 1e-12  # coefficients are +-1, +-i


@pytest.mark.parametrize("antiperiodic", [True, False])
def test_pallas_matches_xla(antiperiodic):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    g = apply_t_boundary(
        GaugeField.random(k1, GEOM, dtype=jnp.complex64).data, GEOM,
        -1 if antiperiodic else 1)
    psi = ColorSpinorField.gaussian(k2, GEOM, dtype=jnp.complex64).data
    want = np.asarray(wops.dslash_full(g, psi))
    got = np.asarray(dslash_pallas(g, psi, interpret=True))
    scale = np.max(np.abs(want))
    assert np.allclose(got, want, atol=3e-6 * scale)


def test_pallas_anisotropic_lattice():
    geom = LatticeGeometry((8, 4, 2, 6))
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    g = GaugeField.random(k1, geom, dtype=jnp.complex64).data
    psi = ColorSpinorField.gaussian(k2, geom, dtype=jnp.complex64).data
    want = np.asarray(wops.dslash_full(g, psi))
    got = np.asarray(dslash_pallas(g, psi, interpret=True))
    scale = np.max(np.abs(want))
    assert np.allclose(got, want, atol=3e-6 * scale)


def test_pallas_packed_matches_xla_packed():
    """Round-2 kernel: packed-layout pallas dslash (single psi fetch per
    plane, lane-roll shifts) == the XLA packed stencil (interpret mode)."""
    import jax
    import jax.numpy as jnp
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.spinor import ColorSpinorField
    from quda_tpu.ops import blas
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.ops import wilson_pallas_packed as wpp
    geom = LatticeGeometry((8, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(3), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(4), geom).data.astype(
        jnp.complex64)
    gp, pp = wpk.pack_gauge(gauge), wpk.pack_spinor(psi)
    ref = wpk.dslash_packed(gp, pp, X, Y)
    out = wpp.from_pallas_layout(wpp.dslash_pallas_packed(
        wpp.to_pallas_layout(gp), wpp.to_pallas_layout(pp), X,
        interpret=True))
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("bz", [1, 2])
def test_pallas_packed_multi_z_block(bz):
    """The z-blocked grid (the configuration the 24^4 headline bench
    runs: nzb > 1) splices boundary rows from neighbouring z-blocks —
    must bit-match the single-block kernel."""
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


@pytest.mark.parametrize("bz", [1, 2])
def test_pallas_packed_v3_matches_xla_packed(bz):
    """Round-3 kernel: scatter-form backward hops (no backward-gauge
    copy, row-sized z-neighbour inputs) == the XLA packed stencil, at
    single and multi z-block configurations (interpret mode)."""
    import jax
    import jax.numpy as jnp
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.spinor import ColorSpinorField
    from quda_tpu.ops import blas
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.ops import wilson_pallas_packed as wpp
    geom = LatticeGeometry((4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(5), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(6), geom).data.astype(
        jnp.complex64)
    gp, pp = wpk.pack_gauge(gauge), wpk.pack_spinor(psi)
    ref = wpk.dslash_packed(gp, pp, X, Y)
    out = wpp.from_pallas_layout(wpp.dslash_pallas_packed_v3(
        wpp.to_pallas_layout(gp), wpp.to_pallas_layout(pp), X,
        interpret=True, block_z=bz))
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("parity", [0, 1])
def test_pallas_eo_v3_matches_xla_eo(parity):
    """Round-3 even/odd kernel: backward hops read the UNSHIFTED
    opposite-parity links (scatter form) — must match the XLA eo-pairs
    stencil on both parities across z-block boundaries."""
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
    src = pe if parity == 1 else po
    gauge_eo_pp = tuple(wpk.to_packed_pairs(wpk.pack_gauge(g), jnp.float32)
                        for g in gauge_eo)
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(src), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(gauge_eo_pp, src_pp, dims, parity)
    out = wpp.dslash_eo_pallas_packed_v3(
        gauge_eo_pp[parity], gauge_eo_pp[1 - parity], src_pp, dims,
        parity, interpret=True, block_z=2)
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


@pytest.mark.parametrize("antiperiodic", [True, False])
def test_pallas_v3_recon12_matches_full(antiperiodic):
    """Reconstruct-12 storage (rows 0-1 + in-kernel cross-product third
    row, gauge_field_order.h Reconstruct<12> analog) == full 18-real
    storage on SU(3) links, with and without the folded antiperiodic-t
    phase (whose sign must be re-applied to the reconstructed row)."""
    import jax
    import jax.numpy as jnp
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.spinor import ColorSpinorField
    from quda_tpu.ops import blas
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.ops import wilson_pallas_packed as wpp
    from quda_tpu.ops.boundary import apply_t_boundary

    geom = LatticeGeometry((4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(11), geom).data.astype(
        jnp.complex64)
    if antiperiodic:
        gauge = apply_t_boundary(gauge, geom, -1)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(12),
                                    geom).data.astype(jnp.complex64)
    g_pl = wpp.to_pallas_layout(wpk.pack_gauge(gauge))
    p_pl = wpp.to_pallas_layout(wpk.pack_spinor(psi))
    full = wpp.dslash_pallas_packed_v3(g_pl, p_pl, X, interpret=True,
                                       tb_sign=antiperiodic)
    r12 = wpp.dslash_pallas_packed_v3(wpp.to_recon12(g_pl), p_pl, X,
                                      interpret=True,
                                      tb_sign=antiperiodic)
    err = float(jnp.sqrt(blas.norm2(full - r12) / blas.norm2(full)))
    assert err < 1e-5


# 73 s alone (PR 25): four interpreted kernel compiles
@pytest.mark.slow
def test_pallas_eo_v3_recon12_solve_matches():
    """The reconstruct-12 eo operator (QUDA_TPU_RECONSTRUCT=12 wiring
    through DiracWilsonPCPackedSloppy) reproduces the full-storage
    operator application to f32 accuracy."""
    import jax
    import jax.numpy as jnp
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.ops import blas
    from quda_tpu.utils import config as qconf

    geom = LatticeGeometry((4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(13), geom).data.astype(
        jnp.complex64)
    dpc = DiracWilsonPC(gauge, geom, kappa=0.12)
    rhs = jax.random.normal(jax.random.PRNGKey(14),
                            (4, 3, 2, T, Z, Y * X // 2), jnp.float32)
    import os
    prev = os.environ.get("QUDA_TPU_RECONSTRUCT")
    try:
        # force BOTH modes explicitly: a user-exported
        # QUDA_TPU_RECONSTRUCT=12 must not make this comparison vacuous
        os.environ["QUDA_TPU_RECONSTRUCT"] = "18"
        qconf.reset_cache()
        sl_full = dpc.packed().pairs(jnp.float32, use_pallas=True,
                                     pallas_interpret=True,
                                     pallas_version=3)
        os.environ["QUDA_TPU_RECONSTRUCT"] = "12"
        qconf.reset_cache()
        sl_r12 = dpc.packed().pairs(jnp.float32, use_pallas=True,
                                    pallas_interpret=True,
                                    pallas_version=3)
    finally:
        if prev is None:
            os.environ.pop("QUDA_TPU_RECONSTRUCT", None)
        else:
            os.environ["QUDA_TPU_RECONSTRUCT"] = prev
        qconf.reset_cache()
    assert sl_full.gauge_eo_pp[0].shape[1] == 3
    assert sl_r12.gauge_eo_pp[0].shape[1] == 2       # compressed resident
    a = sl_full.MdagM_pairs(rhs)
    b = sl_r12.MdagM_pairs(rhs)
    err = float(jnp.sqrt(blas.norm2(a - b) / blas.norm2(a)))
    assert err < 1e-5


@pytest.mark.slow
def test_pallas_eo_v2_recon12_matches_full_storage():
    """Round 8 lifted reconstruct-12 off the v3-only path: the v2
    (gather) eo kernel reads 2-row storage through the same _link_getter
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
                                     pallas_interpret=True,
                                     pallas_version=2)
        os.environ["QUDA_TPU_RECONSTRUCT"] = "12"
        qconf.reset_cache()
        sl_r12 = dpc.packed().pairs(jnp.float32, use_pallas=True,
                                    pallas_interpret=True,
                                    pallas_version=2)
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
