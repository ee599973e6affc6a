"""Staggered pallas kernel: correctness vs the pair-form XLA stencil and
the complex host path (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.ops import blas
from quda_tpu.ops import staggered_packed as spk
from quda_tpu.ops import staggered_pallas as spl
from quda_tpu.ops.wilson_packed import to_packed_pairs


def _setup(key, dims):
    geom = LatticeGeometry(dims)
    T, Z, Y, X = geom.lattice_shape
    k1, k2, k3 = jax.random.split(key, 3)
    fat = GaugeField.random(k1, geom).data.astype(jnp.complex64)
    lng = GaugeField.random(k2, geom).data.astype(jnp.complex64)
    psi = (jax.random.normal(k3, (T, Z, Y, X, 1, 3), jnp.float32)
           + 1j * jax.random.normal(jax.random.fold_in(k3, 1),
                                    (T, Z, Y, X, 1, 3), jnp.float32)
           ).astype(jnp.complex64)
    fat_p = spk.pack_links(fat)
    long_p = spk.pack_links(lng)
    psi_p = spk.pack_staggered(psi)
    return geom, fat_p, long_p, psi_p


def test_pairs_stencil_matches_complex():
    """The pair-form staggered stencil == the complex packed stencil."""
    geom, fat_p, long_p, psi_p = _setup(jax.random.PRNGKey(0), (4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    ref = spk.dslash_staggered_packed(fat_p, psi_p, X, Y, long_p)
    fat_pp = to_packed_pairs(fat_p, jnp.float32)
    long_pp = to_packed_pairs(long_p, jnp.float32)
    psi_pp = to_packed_pairs(psi_p, jnp.float32)
    out_pp = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y,
                                               long_pp)
    out = spk.from_packed_pairs(out_pp, jnp.complex64)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("with_long", [False, True])
@pytest.mark.parametrize("bz", [None, 3])
def test_staggered_pallas_matches_pairs(with_long, bz):
    """Pallas staggered kernel (fat-only and fat+Naik, z-blocked) == the
    pair-form XLA stencil (interpret mode)."""
    geom, fat_p, long_p, psi_p = _setup(jax.random.PRNGKey(1), (4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    fat_pp = to_packed_pairs(fat_p, jnp.float32)
    long_pp = to_packed_pairs(long_p, jnp.float32) if with_long else None
    psi_pp = to_packed_pairs(psi_p, jnp.float32)
    ref = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y, long_pp)

    fat_bw = spl.backward_links(fat_pp, X, 1)
    long_bw = (spl.backward_links(long_pp, X, 3) if with_long else None)
    out = spl.dslash_staggered_pallas(fat_pp, fat_bw, psi_pp, X,
                                      long_pl=long_pp, long_bw_pl=long_bw,
                                      interpret=True, block_z=bz)
    err = float(jnp.sqrt(
        blas.norm2(ref.astype(jnp.float32) - out.astype(jnp.float32))
        / blas.norm2(ref.astype(jnp.float32))))
    assert err < 1e-6


def test_staggered_pallas_small_z_periodic():
    """nzb == 1 (bz = Z): 3-hop z shifts reduce to periodic rolls even
    when Z < 3 would forbid a multi-block splice."""
    geom, fat_p, long_p, psi_p = _setup(jax.random.PRNGKey(2), (4, 4, 4, 4))
    T, Z, Y, X = geom.lattice_shape
    fat_pp = to_packed_pairs(fat_p, jnp.float32)
    long_pp = to_packed_pairs(long_p, jnp.float32)
    psi_pp = to_packed_pairs(psi_p, jnp.float32)
    ref = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y, long_pp)
    out = spl.dslash_staggered_pallas(
        fat_pp, spl.backward_links(fat_pp, X, 1), psi_pp, X,
        long_pl=long_pp, long_bw_pl=spl.backward_links(long_pp, X, 3),
        interpret=True, block_z=Z)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("with_long", [False, True])
@pytest.mark.parametrize("bz", [None, 3])
def test_staggered_pallas_v3_matches_pairs(with_long, bz):
    """Round-3 kernel (scatter-form backward hops, no backward-links
    copies) == the pair-form XLA stencil (interpret mode)."""
    geom, fat_p, long_p, psi_p = _setup(jax.random.PRNGKey(6), (4, 6, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    fat_pp = to_packed_pairs(fat_p, jnp.float32)
    long_pp = to_packed_pairs(long_p, jnp.float32) if with_long else None
    psi_pp = to_packed_pairs(psi_p, jnp.float32)
    ref = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y, long_pp)
    out = spl.dslash_staggered_pallas_v3(fat_pp, psi_pp, X,
                                         long_pl=long_pp,
                                         interpret=True, block_z=bz)
    err = float(jnp.sqrt(
        blas.norm2(ref.astype(jnp.float32) - out.astype(jnp.float32))
        / blas.norm2(ref.astype(jnp.float32))))
    assert err < 1e-6


def test_staggered_pallas_v3_small_z_periodic():
    """v3 with nzb == 1 and Z % 3 != 0: the 3-hop z boundary inputs are
    bypassed for in-tile periodic rolls."""
    geom, fat_p, long_p, psi_p = _setup(jax.random.PRNGKey(7), (4, 4, 4, 4))
    T, Z, Y, X = geom.lattice_shape
    fat_pp = to_packed_pairs(fat_p, jnp.float32)
    long_pp = to_packed_pairs(long_p, jnp.float32)
    psi_pp = to_packed_pairs(psi_p, jnp.float32)
    ref = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y, long_pp)
    out = spl.dslash_staggered_pallas_v3(fat_pp, psi_pp, X, long_pl=long_pp,
                                         interpret=True, block_z=Z)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("parity", [0, 1])
@pytest.mark.parametrize("improved,bz", [(False, None), (True, 3)])
def test_staggered_eo_pallas_v3_matches_pairs(parity, improved, bz):
    """Round-3 EO staggered kernel: backward hops read the UNSHIFTED
    opposite-parity links — must match the eo pair stencil."""
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.ops.wilson import split_gauge_eo

    geom = LatticeGeometry((4, 6, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    dims = (T, Z, Y, X)
    key = jax.random.PRNGKey(8)
    k1, k2, k3 = jax.random.split(key, 3)
    fat = GaugeField.random(k1, geom).data.astype(jnp.complex64)
    lng = GaugeField.random(k2, geom).data.astype(jnp.complex64)
    psi = (jax.random.normal(k3, (T, Z, Y, X, 1, 3), jnp.float32)
           + 1j * jax.random.normal(jax.random.fold_in(k3, 1),
                                    (T, Z, Y, X, 1, 3), jnp.float32)
           ).astype(jnp.complex64)
    fat_eo = split_gauge_eo(fat, geom)
    long_eo = split_gauge_eo(lng, geom) if improved else None
    pe, po = even_odd_split(psi, geom)
    src = pe if parity == 1 else po

    fat_eo_pp = tuple(to_packed_pairs(spk.pack_links(g), jnp.float32)
                      for g in fat_eo)
    long_eo_pp = (tuple(to_packed_pairs(spk.pack_links(g), jnp.float32)
                        for g in long_eo) if improved else None)
    src_pp = to_packed_pairs(spk.pack_staggered(src), jnp.float32)
    ref = spk.dslash_staggered_eo_packed_pairs(
        fat_eo_pp, src_pp, dims, parity, long_eo_pp)
    out = spl.dslash_staggered_eo_pallas_v3(
        fat_eo_pp[parity], fat_eo_pp[1 - parity], src_pp, dims, parity,
        long_here_pl=long_eo_pp[parity] if improved else None,
        long_there_pl=long_eo_pp[1 - parity] if improved else None,
        interpret=True, block_z=bz)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("parity", [0, 1])
@pytest.mark.parametrize("improved", [False, True])
def test_staggered_eo_pairs_matches_canonical(parity, improved):
    """Pair-form eo staggered stencil (incl. 3-hop Naik via the
    nhop-generalised shift_eo_packed) == the canonical dslash_eo."""
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.ops.staggered import dslash_eo
    from quda_tpu.ops.wilson import split_gauge_eo

    geom = LatticeGeometry((4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    dims = (T, Z, Y, X)
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    fat = GaugeField.random(k1, geom).data.astype(jnp.complex64)
    lng = GaugeField.random(k2, geom).data.astype(jnp.complex64)
    psi = (jax.random.normal(k3, (T, Z, Y, X, 1, 3), jnp.float32)
           + 1j * jax.random.normal(jax.random.fold_in(k3, 1),
                                    (T, Z, Y, X, 1, 3), jnp.float32)
           ).astype(jnp.complex64)
    fat_eo = split_gauge_eo(fat, geom)
    long_eo = split_gauge_eo(lng, geom) if improved else None
    pe, po = even_odd_split(psi, geom)
    src = pe if parity == 1 else po
    ref = dslash_eo(fat_eo, src, geom, parity, long_eo)

    fat_eo_pp = tuple(to_packed_pairs(spk.pack_links(g), jnp.float32)
                      for g in fat_eo)
    long_eo_pp = (tuple(to_packed_pairs(spk.pack_links(g), jnp.float32)
                        for g in long_eo) if improved else None)
    src_pp = to_packed_pairs(spk.pack_staggered(src), jnp.float32)
    out_pp = spk.dslash_staggered_eo_packed_pairs(
        fat_eo_pp, src_pp, dims, parity, long_eo_pp)
    out = spk.unpack_staggered(
        spk.from_packed_pairs(out_pp, jnp.complex64), (T, Z, Y, X // 2))
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("parity", [0, 1])
@pytest.mark.parametrize("improved,bz", [(False, None), (True, 3),
                                         (True, None)])
def test_staggered_eo_pallas_matches_pairs(parity, improved, bz):
    """EO staggered pallas kernel == the eo pair stencil (interpret)."""
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.ops.wilson import split_gauge_eo

    geom = LatticeGeometry((4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    dims = (T, Z, Y, X)
    key = jax.random.PRNGKey(4)
    k1, k2, k3 = jax.random.split(key, 3)
    fat = GaugeField.random(k1, geom).data.astype(jnp.complex64)
    lng = GaugeField.random(k2, geom).data.astype(jnp.complex64)
    psi = (jax.random.normal(k3, (T, Z, Y, X, 1, 3), jnp.float32)
           + 1j * jax.random.normal(jax.random.fold_in(k3, 1),
                                    (T, Z, Y, X, 1, 3), jnp.float32)
           ).astype(jnp.complex64)
    fat_eo = split_gauge_eo(fat, geom)
    long_eo = split_gauge_eo(lng, geom) if improved else None
    pe, po = even_odd_split(psi, geom)
    src = pe if parity == 1 else po

    fat_eo_pp = tuple(to_packed_pairs(spk.pack_links(g), jnp.float32)
                      for g in fat_eo)
    long_eo_pp = (tuple(to_packed_pairs(spk.pack_links(g), jnp.float32)
                        for g in long_eo) if improved else None)
    src_pp = to_packed_pairs(spk.pack_staggered(src), jnp.float32)
    ref = spk.dslash_staggered_eo_packed_pairs(
        fat_eo_pp, src_pp, dims, parity, long_eo_pp)

    fat_bw = spl.backward_links_eo(fat_eo_pp[1 - parity], dims, parity, 1)
    long_bw = (spl.backward_links_eo(long_eo_pp[1 - parity], dims,
                                     parity, 3) if improved else None)
    out = spl.dslash_staggered_eo_pallas(
        fat_eo_pp[parity], fat_bw, src_pp, dims, parity,
        long_here_pl=long_eo_pp[parity] if improved else None,
        long_bw_pl=long_bw, interpret=True, block_z=bz)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("use_pallas", [False, True])
def test_staggered_pairs_operator_cg(use_pallas):
    """The complex-free staggered PC operator solves the same system as
    the complex operator: full HISQ prepare/solve/reconstruct chain with
    the pair operator (XLA and pallas-interpret stencils) in the middle."""
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.models.staggered import DiracStaggered, DiracStaggeredPC
    from quda_tpu.solvers.cg import cg

    geom = LatticeGeometry((4, 4, 4, 4))
    T, Z, Y, X = geom.lattice_shape
    key = jax.random.PRNGKey(5)
    k1, k2, k3 = jax.random.split(key, 3)
    fat = GaugeField.random(k1, geom).data.astype(jnp.complex64)
    lng = (0.1 * GaugeField.random(k2, geom).data).astype(jnp.complex64)
    b = (jax.random.normal(k3, (T, Z, Y, X, 1, 3), jnp.float32)
         + 1j * jax.random.normal(jax.random.fold_in(k3, 1),
                                  (T, Z, Y, X, 1, 3), jnp.float32)
         ).astype(jnp.complex64)
    mass = 0.1
    dpc = DiracStaggeredPC(fat, geom, mass, improved=True,
                           long_links=lng)
    op = dpc.pairs(jnp.float32, use_pallas=use_pallas,
                   pallas_interpret=use_pallas)
    be, bo = even_odd_split(b, geom)
    rhs = dpc.prepare(be, bo)

    # complex reference solve
    r_ref = cg(dpc.M, rhs, tol=1e-8, maxiter=300)
    # pair-form solve through the complex wrapper
    r_pp = cg(op.M, rhs, tol=1e-8, maxiter=300)
    from quda_tpu.ops import blas as qblas
    err = float(jnp.sqrt(qblas.norm2(r_ref.x - r_pp.x)
                         / qblas.norm2(r_ref.x)))
    assert err < 1e-5

    # full chain: reconstruct and check the true residual of M x = b
    d_full = DiracStaggered(fat, geom, mass, improved=True,
                            long_links=lng)
    from quda_tpu.fields.spinor import even_odd_join
    xe, xo = dpc.reconstruct(r_pp.x, be, bo)
    x = even_odd_join(xe, xo, geom)
    res = float(jnp.sqrt(qblas.norm2(b - d_full.M(x)) / qblas.norm2(b)))
    assert res < 1e-5


def test_long_bz_guard_raises_loudly():
    """0 < block_z < 3 with a Naik pass would silently corrupt the
    long-hop boundary rows (the gather splice only reaches the adjacent
    z-block; the scatter pass blocks z in units of 3) — every entry
    point rejects it while it traces, before any kernel is built."""
    geom, fat_p, long_p, psi_p = _setup(jax.random.PRNGKey(14),
                                        (4, 4, 6, 4))
    T, Z, Y, X = geom.lattice_shape
    fat_pp = to_packed_pairs(fat_p, jnp.float32)
    long_pp = to_packed_pairs(long_p, jnp.float32)
    psi_pp = to_packed_pairs(psi_p, jnp.float32)
    fat_bw = spl.backward_links(fat_pp, X, 1)
    long_bw = spl.backward_links(long_pp, X, 3)
    for bad in (1, 2):
        with pytest.raises(ValueError, match="block_z >= 3"):
            spl.dslash_staggered_pallas(
                fat_pp, fat_bw, psi_pp, X, long_pl=long_pp,
                long_bw_pl=long_bw, interpret=True, block_z=bad)
        with pytest.raises(ValueError, match="block_z >= 3"):
            spl.dslash_staggered_pallas_mrhs(
                fat_pp, fat_bw, psi_pp[None], X, long_pl=long_pp,
                long_bw_pl=long_bw, interpret=True, block_z=bad)
        with pytest.raises(ValueError, match="multiple of nhop=3"):
            spl.dslash_staggered_pallas_v3(
                fat_pp, psi_pp, X, long_pl=long_pp, interpret=True,
                block_z=bad)
    # the automatic picker must never land in the illegal window:
    # min_bz=3 excludes it by construction
    from quda_tpu.ops.wilson_pallas_packed import _pick_bz
    bz = _pick_bz(Z, Y * X, jnp.float32, planes=180, min_bz=3,
                  vmem_knob="QUDA_TPU_PALLAS_VMEM_MB_STAGGERED")
    assert bz == Z or bz >= 3


# -- kernel-form selection on the solver operator --------------------------

def _pairs_fixture(improved=True, dims=(4, 4, 4, 4)):
    from quda_tpu.models.staggered import DiracStaggeredPC
    geom = LatticeGeometry(dims)
    T, Z, Y, X = geom.lattice_shape
    key = jax.random.PRNGKey(15)
    k1, k2, k3 = jax.random.split(key, 3)
    fat = GaugeField.random(k1, geom).data.astype(jnp.complex64)
    lng = ((0.1 * GaugeField.random(k2, geom).data).astype(jnp.complex64)
           if improved else None)
    dpc = DiracStaggeredPC(fat, geom, 0.1, improved=improved,
                           long_links=lng)
    x = (jax.random.normal(k3, (3, 2, T, Z, Y * X // 2), jnp.float32))
    return dpc, x


@pytest.mark.slow
def test_staggered_forms_agree_on_M_pairs():
    """Both kernel forms compute the same PC operator: the scatter form
    (v3) matches the two-pass gather form to fp tolerance."""
    dpc, x = _pairs_fixture()
    outs = {}
    for form in ("two_pass", "v3"):
        op = dpc.pairs(jnp.float32, use_pallas=True,
                       pallas_interpret=True, form=form)
        assert op._pallas_form == form
        outs[form] = op.M_pairs(x)
    err = float(jnp.sqrt(
        blas.norm2(outs["v3"] - outs["two_pass"])
        / blas.norm2(outs["two_pass"])))
    assert err < 1e-6


def test_staggered_default_form_without_any_knob(monkeypatch):
    """With nothing asked, an operator serves ``served_forms``' pair:
    no race (utils.tune is never entered), no environment read of a
    form (a stray QUDA_TPU_STAGGERED_FORM changes nothing), and the
    gather form alone keeps pre-shifted backward links."""
    from quda_tpu.utils import tune as qtune

    def no_race(*a, **kw):
        raise AssertionError("a staggered operator raced its form")

    monkeypatch.setattr(qtune, "tune", no_race)
    monkeypatch.setenv("QUDA_TPU_STAGGERED_FORM", "two_pass")
    dpc, _ = _pairs_fixture()
    # pallas_interpret=False: what a chip builds (nothing compiles
    # until a hop is applied)
    for interpret in (True, False):
        op = dpc.pairs(jnp.float32, use_pallas=True,
                       pallas_interpret=interpret)
        assert (op._pallas_form, op._mrhs_form) == ("v3",
                                                    "scatter_two_pass")
        assert op._fat_bw is None and op._long_bw is None
    dpc_fat, _ = _pairs_fixture(improved=False)
    op2 = dpc_fat.pairs(jnp.float32, use_pallas=True,
                        pallas_interpret=True)
    assert (op2._pallas_form, op2._mrhs_form) == ("two_pass",
                                                  "gather_two_pass")
    assert op2._fat_bw is not None
    op3 = dpc.pairs(jnp.bfloat16)
    assert (op3._pallas_form, op3._mrhs_form) == ("two_pass", "vmap")


_TZ, _YX, _ONE = ("t", "z"), ("t", "y"), ()


@pytest.mark.parametrize("improved,use_pallas,mesh_axes,form,expect", [
    # one chip, kernels: the chip's readings
    (True, True, _ONE, None, ("v3", "scatter_two_pass")),
    (False, True, _ONE, None, ("two_pass", "gather_two_pass")),
    # a caller's request picks the hop, never the batched hop
    (True, True, _ONE, "two_pass", ("two_pass", "scatter_two_pass")),
    (False, True, _ONE, "v3", ("v3", "gather_two_pass")),
    # the XLA stencil: a label, whatever is asked
    (True, False, _ONE, None, ("two_pass", "vmap")),
    (False, False, _ONE, None, ("two_pass", "vmap")),
    (True, False, _ONE, "v3", ("two_pass", "vmap")),
    # a mesh: the gather interior unless v3 is asked for on t / z
    (True, True, _TZ, None, ("two_pass", "vmap")),
    (False, True, _TZ, None, ("two_pass", "vmap")),
    (True, True, _TZ, "v3", ("v3", "vmap")),
    (False, True, ("z",), "v3", ("v3", "vmap")),
    (True, True, _YX, None, ("two_pass", "vmap")),
    # the scatter exterior shards no y / x: a y / x mesh refuses v3
    (True, True, _YX, "v3", ("two_pass", "vmap")),
    (False, True, ("x",), "v3", ("two_pass", "vmap")),
    # a mesh needs the kernels; only two forms exist
    (True, False, _TZ, None, ValueError),
    (False, False, _YX, None, ValueError),
    (True, True, _ONE, "fused", ValueError),
    (True, True, _ONE, "auto", ValueError),
])
def test_served_forms_table(improved, use_pallas, mesh_axes, form,
                            expect):
    """The ONE decision of the staggered hop and batched-hop forms, as
    a table: (fat only | fat + Naik) x (kernels | XLA) x (one chip |
    t/z mesh | y/x mesh), with and without a caller's request."""
    from quda_tpu.models.staggered import served_forms
    if expect is ValueError:
        with pytest.raises(ValueError):
            served_forms(improved, use_pallas, mesh_axes, form)
    else:
        assert served_forms(improved, use_pallas, mesh_axes,
                            form) == expect
