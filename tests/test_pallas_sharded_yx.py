"""Round 18: y/x-sharded pallas dslash on 3D/4D virtual meshes.

The v2-form sharded stencils generalize beyond t/z — the y axis rides
pre-rotated row strips on the fused y*x array axis, the x axis rides
block-contiguous relayout (parallel/mesh.fuse_block_layout) + strided
column gathers — and every new seam must bit-match the single-device
stencil and land its bytes in the ICI ledger.  Heavy mesh shapes are
slow-marked; the fast tier keeps one 2-device witness per new axis
plus the pure-python policy-engine contracts."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
from quda_tpu.ops import blas
from quda_tpu.ops import wilson_packed as wpk
from quda_tpu.ops import wilson_pallas_packed as wpp
from quda_tpu.parallel.mesh import (fuse_block_layout, make_lattice_mesh,
                                    unfuse_block_layout)
from quda_tpu.parallel.pallas_dslash import (AXIS_NAMES, FUSED_HALO_AXES,
                                             SHARDED_POLICIES,
                                             _policy_label,
                                             resolve_axis_policies)

PSI_SPEC = P(None, None, None, "t", "z", ("y", "x"))
G_SPEC = P(None, None, None, None, "t", "z", ("y", "x"))
STAG_PSI_SPEC = P(None, None, "t", "z", ("y", "x"))


# -- the per-axis policy engine (pure python, fast tier) --------------------

def test_resolve_axis_policies_forms():
    """Bare name maps onto every axis (fused_halo keeps facefix on x),
    spec strings pin axes individually with facefix defaults, dicts
    pass through normalized."""
    assert resolve_axis_policies("xla_facefix") == {
        a: "xla_facefix" for a in AXIS_NAMES}
    fh = resolve_axis_policies("fused_halo")
    assert fh == {"t": "fused_halo", "z": "fused_halo",
                  "y": "fused_halo", "x": "xla_facefix"}
    spec = resolve_axis_policies("t=fused_halo, y=xla_facefix")
    assert spec == {"t": "fused_halo", "z": "xla_facefix",
                    "y": "xla_facefix", "x": "xla_facefix"}
    assert resolve_axis_policies(spec) == spec


def test_resolve_axis_policies_rejects():
    with pytest.raises(ValueError, match="unknown sharded halo policy"):
        resolve_axis_policies("bogus")
    with pytest.raises(ValueError, match="unknown sharded halo policy"):
        resolve_axis_policies("t=bogus")
    with pytest.raises(ValueError, match="unknown mesh axis"):
        resolve_axis_policies("w=fused_halo")
    # an EXPLICIT x=fused_halo is an error (strided column face), while
    # the bare legacy name silently keeps facefix there
    with pytest.raises(ValueError, match="strided column"):
        resolve_axis_policies("x=fused_halo")


def test_policy_label_is_joint():
    """The ledger scope carries ONE label: the plain name when every
    partitioned axis agrees, else the per-axis spec (obs/comms groups
    within a scope are alternatives — a per-axis label split would
    fracture the invocation model)."""
    pols = resolve_axis_policies("t=fused_halo,z=fused_halo")
    assert _policy_label(pols, ("t", "z")) == "fused_halo"
    assert _policy_label(pols, ("t", "z", "y")) == \
        "t=fused_halo,z=fused_halo,y=xla_facefix"
    assert _policy_label(resolve_axis_policies("xla_facefix"), ()) == \
        "xla_facefix"


# -- fixtures ---------------------------------------------------------------

def _eo_fixture(key1=51, key2=52, fold_t=True, shape=(4, 4, 8, 16)):
    """(dims, g_eo_pp, (pe, po)) — the test_pallas_sharded eo fixture
    (ctor order x,y,z,t; folded antiperiodic t so shard-edge signs are
    exercised), duplicated here because test modules are not a
    package."""
    from quda_tpu.ops.boundary import apply_t_boundary
    from quda_tpu.ops.wilson import split_gauge_eo
    geom = LatticeGeometry(shape)
    dims = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(key1), geom
                              ).data.astype(jnp.complex64)
    if fold_t:
        gauge = apply_t_boundary(gauge, geom, -1)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(key2), geom
                                    ).data.astype(jnp.complex64)
    g_eo = split_gauge_eo(gauge, geom)
    g_eo_pp = tuple(wpk.to_packed_pairs(wpk.pack_gauge(g), jnp.float32)
                    for g in g_eo)
    return dims, g_eo_pp, even_odd_split(psi, geom)


def _run_sharded_eo(dims, g_eo_pp, parity, src_pp, grid, policy,
                    recon12=False):
    """Shard the eo v2 stencil over ``grid`` (any axes, x included via
    block-contiguous relayout) and return the output in NATURAL
    layout."""
    from quda_tpu.parallel.pallas_dslash import dslash_eo_pallas_sharded
    T, Z, Y, X = dims
    n_dev = int(np.prod(grid))
    mesh = make_lattice_mesh(grid=grid, n_src=1,
                             devices=jax.devices()[:n_dev])
    n_y, n_x = grid[2], grid[3]
    uh, ut = g_eo_pp[parity], g_eo_pp[1 - parity]
    if recon12:
        uh, ut = wpp.to_recon12(uh), wpp.to_recon12(ut)
    # GLOBAL pre-shift on the NATURAL layout, THEN block-relayout, THEN
    # shard (the v2 design, x-generalized)
    u_bw = wpp.backward_gauge_eo(ut, dims, parity)
    rl = lambda a: fuse_block_layout(a, n_y, n_x, Y, X // 2)
    fn = jax.shard_map(
        lambda a, b, p: dslash_eo_pallas_sharded(
            a, b, p, dims, parity, mesh, interpret=True, policy=policy),
        mesh=mesh, in_specs=(G_SPEC, G_SPEC, PSI_SPEC),
        out_specs=PSI_SPEC, check_vma=False)
    uh_s = jax.device_put(rl(uh), NamedSharding(mesh, G_SPEC))
    ub_s = jax.device_put(rl(u_bw), NamedSharding(mesh, G_SPEC))
    src_s = jax.device_put(rl(src_pp), NamedSharding(mesh, PSI_SPEC))
    out = jax.jit(fn)(uh_s, ub_s, src_s)
    return unfuse_block_layout(out, n_y, n_x, Y, X // 2)


# -- fast witnesses: one per new axis ---------------------------------------

@pytest.mark.slow
def test_sharded_wilson_full_y_matches_single_device():
    """y-partitioned full-lattice Wilson: the fused y*x axis splits into
    contiguous row strips (n_x=1 needs no relayout) and the y face fix
    exchanges one row strip per direction — must bit-match the
    single-device pair stencil on a 2-device mesh.  (Slow: interpret
    -mode kernel compiles push it past the 30s fast budget; the fast
    tier keeps the x-sharded eo bit-match which covers the same
    wrapper seam.)"""
    from quda_tpu.parallel.pallas_dslash import dslash_pallas_sharded
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 virtual devices")
    geom = LatticeGeometry((4, 4, 4, 4))
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(21), geom
                              ).data.astype(jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(22), geom
                                    ).data.astype(jnp.complex64)
    gp = wpp.to_pallas_layout(wpk.pack_gauge(gauge))
    pp = wpp.to_pallas_layout(wpk.pack_spinor(psi))
    gbw = wpp.backward_gauge(gp, X)
    ref = wpk.dslash_packed_pairs(gp, pp, X, Y)

    mesh = make_lattice_mesh(grid=(1, 1, 2, 1), n_src=1,
                             devices=jax.devices()[:2])
    fn = jax.shard_map(
        lambda g, gb, p: dslash_pallas_sharded(g, gb, p, X, mesh,
                                               interpret=True),
        mesh=mesh, in_specs=(G_SPEC, G_SPEC, PSI_SPEC),
        out_specs=PSI_SPEC, check_vma=False)
    out = jax.jit(fn)(jax.device_put(gp, NamedSharding(mesh, G_SPEC)),
                      jax.device_put(gbw, NamedSharding(mesh, G_SPEC)),
                      jax.device_put(pp, NamedSharding(mesh, PSI_SPEC)))
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


def test_sharded_wilson_eo_x_matches_single_device():
    """x-partitioned eo Wilson: block-contiguous relayout makes each
    shard a (Y x Xh_loc) rectangle and the strided column faces ride
    the exchange — the odd-hop slot-select seam of the checkerboard,
    on a 2-device mesh."""
    dims, g_eo_pp, (pe, po) = _eo_fixture(shape=(8, 4, 4, 4))
    parity = 0
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(po), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 virtual devices")
    out = _run_sharded_eo(dims, g_eo_pp, parity, src_pp,
                          grid=(1, 1, 1, 2), policy="xla_facefix")
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


def test_psum_free_on_size1_mesh_axes():
    """Satellite: parallel/halo.psum_scalar psums over all four lattice
    axes unconditionally, claiming size-1 axes are free.  Pin it: on a
    t/z-only mesh the compiled all-reduce replica groups are IDENTICAL
    to a psum over just the live axes (the y/x names add no collective),
    and the ICI ledger records no exchange rows for it (reductions are
    not halo traffic)."""
    from quda_tpu.obs import comms as ocomms
    from quda_tpu.parallel.halo import psum_scalar
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    mesh = make_lattice_mesh(grid=(2, 2, 1, 1), n_src=1,
                             devices=jax.devices()[:4])
    spec = P("t", "z", "y", "x")
    x = jnp.arange(16, dtype=jnp.float32).reshape(2, 2, 2, 2)

    def compiled_allreduce_groups(body):
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                                   out_specs=P(),  # the psum is a scalar
                                   check_vma=False))
        txt = fn.lower(x).compile().as_text()
        groups = [ln.split("replica_groups=")[1].split(",")[0]
                  for ln in txt.splitlines()
                  if "all-reduce" in ln and "replica_groups=" in ln]
        return fn, groups

    f_all, g_all = compiled_allreduce_groups(
        lambda a: psum_scalar(jnp.sum(a), mesh))
    f_live, g_live = compiled_allreduce_groups(
        lambda a: jax.lax.psum(jnp.sum(a), ("t", "z")))
    assert g_all, "no all-reduce in the compiled psum"
    assert g_all == g_live          # size-1 y/x axes add no collective
    ocomms.reset()
    ocomms.start()
    try:
        total = f_all(jax.device_put(x, NamedSharding(mesh, spec)))
        assert float(total) == float(jnp.sum(x))
        assert ocomms.ledger() == []   # no halo bytes attributed
    finally:
        ocomms.reset()


@pytest.mark.slow
def test_operator_x_sharded_mesh_roundtrip():
    """Model-level x sharding: DiracWilsonPC.pairs(mesh=...) with an
    x-partitioned mesh block-relayouts its links and pair fields
    (_yx_block_pairs) and MdagM_pairs matches the unsharded operator
    after the inverse relayout.  (Slow: four interpret-mode kernel
    compiles — the fast tier keeps the wrapper-level x bit-match.)"""
    from quda_tpu.models.wilson import DiracWilsonPC
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 virtual devices")
    geom = LatticeGeometry((8, 4, 4, 4))     # (T,Z,Y,X) = (4,4,4,8)
    gauge = GaugeField.random(jax.random.PRNGKey(23), geom
                              ).data.astype(jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(24), geom
                                    ).data.astype(jnp.complex64)
    pe, po = even_odd_split(psi, geom)
    dpk = DiracWilsonPC(gauge, geom, kappa=0.11).packed()
    ref_op = dpk.pairs(jnp.float32)
    ref = ref_op.MdagM_pairs(ref_op.prepare_pairs(pe, po))

    mesh = make_lattice_mesh(grid=(1, 1, 1, 2), n_src=1,
                             devices=jax.devices()[:2])
    op = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   mesh=mesh, sharded_policy="xla_facefix")
    assert op._mesh_yx == (1, 2)
    out = op.MdagM_pairs(op.prepare_pairs(pe, po))
    out_nat = op._yx_block_pairs(out, inverse=True)
    err = float(jnp.sqrt(blas.norm2(ref - out_nat) / blas.norm2(ref)))
    assert err < 1e-5


def test_operator_accepts_per_axis_policy_spec():
    """QUDA_TPU_SHARDED_POLICY accepts the per-axis spec string at the
    operator seam and resolves it into the full {axis: policy} map."""
    from quda_tpu.models.wilson import DiracWilsonPC
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    geom = LatticeGeometry((4, 4, 4, 4))
    gauge = GaugeField.random(jax.random.PRNGKey(25), geom
                              ).data.astype(jnp.complex64)
    mesh = make_lattice_mesh(grid=(2, 2, 1, 1), n_src=1,
                             devices=jax.devices()[:4])
    op = DiracWilsonPC(gauge, geom, kappa=0.1).packed().pairs(
        jnp.float32, use_pallas=True, pallas_interpret=True, mesh=mesh,
        sharded_policy="t=xla_facefix,z=xla_facefix")
    assert op._sharded_policy == {a: "xla_facefix" for a in AXIS_NAMES}


# -- slow: 3D/4D mesh bit-match sweeps --------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_sharded_wilson_eo_3d_matches_single_device(parity):
    """Acceptance: eo Wilson v2 on a 3D (2,2,2,1) mesh — t, z AND y
    partitioned — bit-matches the single-device stencil, both
    parities."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    dims, g_eo_pp, (pe, po) = _eo_fixture()
    src = pe if parity == 1 else po
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(src), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
    out = _run_sharded_eo(dims, g_eo_pp, parity, src_pp,
                          grid=(2, 2, 2, 1), policy="xla_facefix")
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_sharded_wilson_eo_3d_recon12_matches_single_device(parity):
    """reconstruct-12 on the 3D mesh: the y/x face slabs rebuild row 2
    exactly like the t/z slabs (folded antiperiodic-t signs included via
    the fixture's fold)."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    dims, g_eo_pp, (pe, po) = _eo_fixture()
    src = pe if parity == 1 else po
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(src), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
    out = _run_sharded_eo(dims, g_eo_pp, parity, src_pp,
                          grid=(2, 2, 2, 1), policy="xla_facefix",
                          recon12=True)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-5          # f32 third-row reconstruction floor


@pytest.mark.slow
def test_sharded_wilson_eo_3axes_with_x_matches_single_device():
    """t+y+x partitioned together: the block-contiguous relayout and
    the strided x column exchange compose with the y row strips and the
    t plane slabs on one mesh."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    dims, g_eo_pp, (pe, po) = _eo_fixture(shape=(8, 4, 8, 16))
    parity = 1
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(pe), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
    out = _run_sharded_eo(dims, g_eo_pp, parity, src_pp,
                          grid=(2, 1, 2, 2), policy="xla_facefix")
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_sharded_wilson_eo_fused_halo_y_matches_facefix(parity):
    """Per-axis policy A/B on the 3D mesh: fused RDMA on the contiguous
    y row strip (t/z on facefix) is bit-identical to all-facefix."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    dims, g_eo_pp, (pe, po) = _eo_fixture()
    src = pe if parity == 1 else po
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(src), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
    out = _run_sharded_eo(
        dims, g_eo_pp, parity, src_pp, grid=(2, 2, 2, 1),
        policy="t=xla_facefix,z=xla_facefix,y=fused_halo")
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_sharded_staggered_eo_3d_matches_single_device(parity):
    """Checkerboarded staggered fat+Naik on a 3D (2,2,2,1) mesh: the
    y row-strip exchange carries the 2-row Naik window (w=2) and the
    eo slot select holds on every partitioned axis."""
    from quda_tpu.ops import staggered_packed as spk
    from quda_tpu.ops import staggered_pallas as stp
    from quda_tpu.ops.wilson import split_gauge_eo
    from quda_tpu.parallel.pallas_dslash import (
        dslash_staggered_eo_pallas_sharded)
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    # local extents must be >= 3 on every partitioned axis (Naik
    # 3-hop crosses at most one shard boundary) and even (eo masks):
    # 8/2 = 4 on t, z, and y
    geom = LatticeGeometry((8, 8, 8, 8))     # (T,Z,Y,X) = (8,8,8,8)
    T, Z, Y, X = geom.lattice_shape
    dims = (T, Z, Y, X)
    fat_c = GaugeField.random(jax.random.PRNGKey(71), geom
                              ).data.astype(jnp.complex64)
    long_c = GaugeField.random(jax.random.PRNGKey(72), geom
                               ).data.astype(jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(73), geom
                                    ).data.astype(jnp.complex64)[..., :1, :]
    fat_eo = split_gauge_eo(fat_c, geom)
    long_eo = split_gauge_eo(long_c, geom)
    pe, po = even_odd_split(psi, geom)
    src = pe if parity == 1 else po
    fat_eo_pp = tuple(wpk.to_packed_pairs(spk.pack_links(g), jnp.float32)
                      for g in fat_eo)
    long_eo_pp = tuple(wpk.to_packed_pairs(spk.pack_links(g),
                                           jnp.float32)
                       for g in long_eo)
    src_pp = wpk.to_packed_pairs(spk.pack_staggered(src), jnp.float32)
    ref = spk.dslash_staggered_eo_packed_pairs(
        fat_eo_pp, src_pp, dims, parity, long_eo_pp)
    fat_bw = stp.backward_links_eo(fat_eo_pp[1 - parity], dims, parity,
                                   1)
    long_bw = stp.backward_links_eo(long_eo_pp[1 - parity], dims,
                                    parity, 3)
    mesh = make_lattice_mesh(grid=(2, 2, 2, 1), n_src=1)
    fn = jax.shard_map(
        lambda fh, fb, lh, lb, p: dslash_staggered_eo_pallas_sharded(
            fh, fb, p, dims, parity, mesh, long_here_pl=lh,
            long_bw_pl=lb, interpret=True),
        mesh=mesh, in_specs=(G_SPEC,) * 4 + (STAG_PSI_SPEC,),
        out_specs=STAG_PSI_SPEC, check_vma=False)
    args = [jax.device_put(a, NamedSharding(mesh, G_SPEC))
            for a in (fat_eo_pp[parity], fat_bw, long_eo_pp[parity],
                      long_bw)]
    src_s = jax.device_put(src_pp, NamedSharding(mesh, STAG_PSI_SPEC))
    out = jax.jit(fn)(*args, src_s)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
def test_sharded_staggered_full_yx_matches_single_device():
    """Full-lattice staggered fat+Naik with y AND x partitioned
    (2,1,2,2): the 3-hop Naik slabs cross the y strip seam and the x
    wrap masks hold at the block-relayout shard edges."""
    from quda_tpu.ops import staggered_packed as spk
    from quda_tpu.ops import staggered_pallas as stp
    from quda_tpu.parallel.pallas_dslash import (
        dslash_staggered_pallas_sharded)
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    geom = LatticeGeometry((16, 8, 4, 8))    # (T,Z,Y,X) = (8,4,8,16)
    T, Z, Y, X = geom.lattice_shape
    fat_pp = wpk.to_packed_pairs(spk.pack_links(
        GaugeField.random(jax.random.PRNGKey(74), geom
                          ).data.astype(jnp.complex64)), jnp.float32)
    long_pp = wpk.to_packed_pairs(spk.pack_links(
        GaugeField.random(jax.random.PRNGKey(75), geom
                          ).data.astype(jnp.complex64)), jnp.float32)
    psi_pp = wpk.to_packed_pairs(spk.pack_staggered(
        ColorSpinorField.gaussian(jax.random.PRNGKey(76), geom
                                  ).data.astype(jnp.complex64)[..., :1, :]
    ), jnp.float32)
    ref = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y,
                                            long_pp)
    fat_bw = stp.backward_links(fat_pp, X, 1)
    long_bw = stp.backward_links(long_pp, X, 3)
    grid = (2, 1, 2, 2)
    mesh = make_lattice_mesh(grid=grid, n_src=1)
    n_y, n_x = grid[2], grid[3]
    rl = lambda a: fuse_block_layout(a, n_y, n_x, Y, X)
    fn = jax.shard_map(
        lambda f, fb, l, lb, p: dslash_staggered_pallas_sharded(
            f, fb, p, X, mesh, long_pl=l, long_bw_pl=lb,
            interpret=True),
        mesh=mesh, in_specs=(G_SPEC,) * 4 + (STAG_PSI_SPEC,),
        out_specs=STAG_PSI_SPEC, check_vma=False)
    args = [jax.device_put(rl(a), NamedSharding(mesh, G_SPEC))
            for a in (fat_pp, fat_bw, long_pp, long_bw)]
    psi_s = jax.device_put(rl(psi_pp),
                           NamedSharding(mesh, STAG_PSI_SPEC))
    out = unfuse_block_layout(jax.jit(fn)(*args, psi_s), n_y, n_x, Y, X)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
def test_sharded_wilson_eo_4d_mesh_subprocess():
    """True 4D decomposition — all four lattice axes partitioned on a
    (2,2,2,2) mesh — needs 16 virtual devices, so it runs in a
    subprocess with its own XLA_FLAGS (the in-process runtime is pinned
    to 8)."""
    code = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
from quda_tpu.ops import blas
from quda_tpu.ops import wilson_packed as wpk
from quda_tpu.ops import wilson_pallas_packed as wpp
from quda_tpu.ops.wilson import split_gauge_eo
from quda_tpu.parallel.mesh import (fuse_block_layout, make_lattice_mesh,
                                    unfuse_block_layout)
from quda_tpu.parallel.pallas_dslash import dslash_eo_pallas_sharded
assert len(jax.devices()) == 16, len(jax.devices())
geom = LatticeGeometry((8, 4, 4, 4))        # (T,Z,Y,X) = (4,4,4,8)
dims = geom.lattice_shape
T, Z, Y, X = dims
gauge = GaugeField.random(jax.random.PRNGKey(81), geom
                          ).data.astype(jnp.complex64)
psi = ColorSpinorField.gaussian(jax.random.PRNGKey(82), geom
                                ).data.astype(jnp.complex64)
g_eo = split_gauge_eo(gauge, geom)
g_eo_pp = tuple(wpk.to_packed_pairs(wpk.pack_gauge(g), jnp.float32)
                for g in g_eo)
pe, po = even_odd_split(psi, geom)
parity = 0
src_pp = wpk.to_packed_pairs(wpk.pack_spinor(po), jnp.float32)
ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
grid = (2, 2, 2, 2)
mesh = make_lattice_mesh(grid=grid, n_src=1)
u_bw = wpp.backward_gauge_eo(g_eo_pp[1 - parity], dims, parity)
rl = lambda a: fuse_block_layout(a, 2, 2, Y, X // 2)
psi_spec = P(None, None, None, "t", "z", ("y", "x"))
g_spec = P(None, None, None, None, "t", "z", ("y", "x"))
fn = jax.shard_map(
    lambda a, b, p: dslash_eo_pallas_sharded(
        a, b, p, dims, parity, mesh, interpret=True,
        policy="xla_facefix"),
    mesh=mesh, in_specs=(g_spec, g_spec, psi_spec),
    out_specs=psi_spec, check_vma=False)
out = jax.jit(fn)(
    jax.device_put(rl(g_eo_pp[parity]), NamedSharding(mesh, g_spec)),
    jax.device_put(rl(u_bw), NamedSharding(mesh, g_spec)),
    jax.device_put(rl(src_pp), NamedSharding(mesh, psi_spec)))
out = unfuse_block_layout(out, 2, 2, Y, X // 2)
err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
assert err < 1e-6, err
print("4D_OK", err)
"""
    import os
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=16")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "4D_OK" in res.stdout


# -- slow: ICI attribution on the 3D mesh -----------------------------------

@pytest.mark.slow
def test_halo_model_matches_ledger_on_3d_mesh(monkeypatch):
    """Acceptance: the analytic per-axis halo model is pinned BIT-EQUAL
    to the ledger rows on a 3D mesh — per-parity site totals equal the
    model's per-device bytes, the per-axis split equals model["axes"],
    and the solve attribution emits one ici sub-row per partitioned
    axis."""
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.obs import comms as ocomms
    from quda_tpu.utils import config as qconf
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    monkeypatch.setenv("QUDA_TPU_TRACE", "1")
    qconf.reset_cache()
    ocomms.reset()
    assert ocomms.maybe_start() is not None
    try:
        geom = LatticeGeometry((4, 4, 4, 8))   # (T,Z,Y,X) = (8,4,4,4)
        dims = geom.lattice_shape
        gauge = GaugeField.random(jax.random.PRNGKey(91), geom
                                  ).data.astype(jnp.complex64)
        psi = ColorSpinorField.gaussian(jax.random.PRNGKey(92), geom
                                        ).data.astype(jnp.complex64)
        pe, po = even_odd_split(psi, geom)
        mesh = make_lattice_mesh(grid=(2, 2, 2, 1), n_src=1)
        op = DiracWilsonPC(gauge, geom, kappa=0.1).packed().pairs(
            jnp.float32, use_pallas=True, pallas_interpret=True,
            mesh=mesh, sharded_policy="xla_facefix")
        rhs = op.prepare_pairs(pe, po)
        out = jax.jit(op.MdagM_pairs)(rhs)
        out.block_until_ready()

        model = ocomms.wilson_eo_halo_model(dims, (2, 2, 2, 1))
        assert set(model["axes"]) == {"t", "z", "y"}
        rows = ocomms.ledger()
        assert rows, "sharded apply recorded no ledger rows"
        per_site = {}
        per_site_axis = {}
        for r in rows:
            assert r["policy"] == "xla_facefix"
            assert r["axis"] in ("t", "z", "y")
            assert r["mesh"] == "2x2x2x1"
            per_site[r["site"]] = per_site.get(r["site"], 0) + r["bytes"]
            k = (r["site"], r["axis"])
            per_site_axis[k] = per_site_axis.get(k, 0) + r["bytes"]
        assert set(per_site) == {"wilson_eo_sharded_v2:p0",
                                 "wilson_eo_sharded_v2:p1"}
        for site, total in per_site.items():
            assert total == model["per_device"], (site, total, model)
            for ax, b in model["axes"].items():
                assert per_site_axis[(site, ax)] == b, (site, ax)
        assert ocomms.per_invocation_bytes() == model["per_device"]
        row = ocomms.attribute_solve("wilson_sharded_v2", 1, 1.0, 1.0)
        assert row["devices"] == 8
        assert row["axes"] == "t+y+z"
        subs = [r for r in ocomms.solve_rows()
                if r["form"].startswith("ici:wilson_sharded_v2:")]
        assert {r["form"] for r in subs} == {
            "ici:wilson_sharded_v2:t", "ici:wilson_sharded_v2:z",
            "ici:wilson_sharded_v2:y"}
        for r in subs:
            ax = r["form"].rsplit(":", 1)[1]
            assert r["bytes_per_invocation_per_device"] == \
                model["axes"][ax]
    finally:
        ocomms.reset()


@pytest.mark.slow
def test_split_grid_composes_with_mesh_sharding(monkeypatch):
    """Satellite: split-grid x mesh-sharding on one (src=2, t=2, z=2)
    mesh — the multi-src solve matches the single-chip batched solve
    (to f32 roundoff: GSPMD partitioning reorders the CG reductions
    vs the eager vmap reference), the mesh-sharded operator runs on
    the same mesh (src axis replicated), and the ICI ledger attributes
    the src gauge replication and the t/z halo exchanges as SEPARATE
    rows."""
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.obs import comms as ocomms
    from quda_tpu.ops import wilson as wops
    from quda_tpu.parallel.split import split_grid_solve
    from quda_tpu.solvers.cg import cg_fixed_iters
    from quda_tpu.utils import config as qconf
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    monkeypatch.setenv("QUDA_TPU_TRACE", "1")
    qconf.reset_cache()
    ocomms.reset()
    assert ocomms.maybe_start() is not None
    try:
        geom = LatticeGeometry((8, 4, 4, 4))   # (T,Z,Y,X) = (4,4,4,8)
        mesh = make_lattice_mesh(grid=(2, 2, 1, 1), n_src=2)
        assert dict(mesh.shape)["src"] == 2
        gauge = GaugeField.random(jax.random.PRNGKey(93), geom
                                  ).data.astype(jnp.complex64)
        key = jax.random.PRNGKey(94)
        B = jnp.stack([ColorSpinorField.gaussian(
            jax.random.fold_in(key, i), geom
        ).data.astype(jnp.complex64) for i in range(2)])

        def solve_one(g, b):
            mv = lambda v: wops.matvec_full(g, v, 0.1)
            from quda_tpu.models.dirac import apply_gamma5
            mdag = lambda v: apply_gamma5(mv(apply_gamma5(v)))
            rhs = mdag(b)
            return cg_fixed_iters(lambda v: mdag(mv(v)), rhs, None,
                                  12)[0].x
        out = split_grid_solve(solve_one, gauge, B, mesh)
        want = jax.vmap(lambda b: solve_one(gauge, b))(B)
        err_b = float(jnp.sqrt(blas.norm2(out - want)
                               / blas.norm2(want)))
        assert err_b < 1e-5, err_b

        # mesh-sharded pairs operator ON THE SAME MESH: the src axis is
        # simply replicated by the PartitionSpecs — split-grid and
        # lattice decomposition compose on one device grid
        psi = ColorSpinorField.gaussian(jax.random.PRNGKey(95), geom
                                        ).data.astype(jnp.complex64)
        pe, po = even_odd_split(psi, geom)
        dpk = DiracWilsonPC(gauge, geom, kappa=0.1).packed()
        ref_op = dpk.pairs(jnp.float32)
        ref = ref_op.MdagM_pairs(ref_op.prepare_pairs(pe, po))
        op = dpk.pairs(jnp.float32, use_pallas=True,
                       pallas_interpret=True, mesh=mesh,
                       sharded_policy="xla_facefix")
        out_pp = jax.jit(op.MdagM_pairs)(op.prepare_pairs(pe, po))
        err = float(jnp.sqrt(blas.norm2(ref - out_pp)
                             / blas.norm2(ref)))
        assert err < 1e-5

        rows = ocomms.ledger()
        rep = [r for r in rows if r["direction"] == "replicate"]
        exch = [r for r in rows if r["direction"] != "replicate"]
        assert len(rep) == 1 and rep[0]["site"] == "split_grid:gauge"
        assert rep[0]["axis"] == "src"
        assert exch and {r["axis"] for r in exch} == {"t", "z"}
        assert all(r["site"].startswith("wilson_eo_sharded_v2")
                   for r in exch)
    finally:
        ocomms.reset()
