"""Multi-chip pallas dslash: interior kernel + exterior XLA boundary
corrections under shard_map must bit-match the single-device stencil
(virtual 8-device CPU mesh, interpret-mode kernel)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.spinor import ColorSpinorField
from quda_tpu.ops import blas
from quda_tpu.ops import wilson_packed as wpk
from quda_tpu.ops import wilson_pallas_packed as wpp
from quda_tpu.parallel.mesh import make_lattice_mesh
from quda_tpu.parallel.pallas_dslash import dslash_pallas_sharded


@pytest.mark.slow
@pytest.mark.parametrize("grid", [(4, 2, 1, 1), (2, 4, 1, 1),
                                  (8, 1, 1, 1)])
def test_sharded_pallas_matches_single_device(grid):
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    geom = LatticeGeometry((4, 4, 8, 8))  # (x,y,z,t) ctor order
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(11), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(12), geom
                                    ).data.astype(jnp.complex64)
    gp = wpp.to_pallas_layout(wpk.pack_gauge(gauge))
    pp = wpp.to_pallas_layout(wpk.pack_spinor(psi))
    gbw = wpp.backward_gauge(gp, X)      # GLOBAL pre-shift (cross-shard
    #                                      backward links baked in)
    ref = wpk.dslash_packed_pairs(gp, pp, X, Y)

    mesh = make_lattice_mesh(grid=grid, n_src=1)
    # packed pair layout: psi (4,3,2,T,Z,YX), gauge (4,3,3,2,T,Z,YX) —
    # shard T onto mesh axis "t" and Z onto "z"
    psi_spec = P(None, None, None, "t", "z", None)
    g_spec = P(None, None, None, None, "t", "z", None)

    fn = jax.shard_map(
        lambda g, gb, p: dslash_pallas_sharded(g, gb, p, X, mesh,
                                               interpret=True),
        mesh=mesh, in_specs=(g_spec, g_spec, psi_spec),
        out_specs=psi_spec, check_vma=False)

    gp_s = jax.device_put(gp, NamedSharding(mesh, g_spec))
    gbw_s = jax.device_put(gbw, NamedSharding(mesh, g_spec))
    pp_s = jax.device_put(pp, NamedSharding(mesh, psi_spec))
    out = jax.jit(fn)(gp_s, gbw_s, pp_s)

    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.parametrize("grid", [(4, 2, 1, 1), (2, 4, 1, 1),
                                  (8, 1, 1, 1)])
def test_sharded_staggered_v3_matches_single_device(grid):
    """Staggered fused policy (fat 1-hop): interior v3 scatter kernel +
    face fixes must bit-match the single-device packed stencil
    (lib/dslash_policy.hpp:365 applied to dslash_staggered.cuh)."""
    from quda_tpu.ops import staggered_packed as spk
    from quda_tpu.parallel.pallas_dslash import (
        dslash_staggered_pallas_sharded_v3)
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    geom = LatticeGeometry((4, 4, 8, 8))
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(21), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(22), geom
                                    ).data.astype(jnp.complex64)[..., :1, :]
    fat_pp = wpk.to_packed_pairs(spk.pack_links(gauge), jnp.float32)
    psi_pp = wpk.to_packed_pairs(spk.pack_staggered(psi), jnp.float32)
    ref = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y)

    mesh = make_lattice_mesh(grid=grid, n_src=1)
    psi_spec = P(None, None, "t", "z", None)
    g_spec = P(None, None, None, None, "t", "z", None)
    fn = jax.shard_map(
        lambda g, p: dslash_staggered_pallas_sharded_v3(
            g, p, X, mesh, interpret=True),
        mesh=mesh, in_specs=(g_spec, psi_spec), out_specs=psi_spec,
        check_vma=False)
    fat_s = jax.device_put(fat_pp, NamedSharding(mesh, g_spec))
    psi_s = jax.device_put(psi_pp, NamedSharding(mesh, psi_spec))
    out = jax.jit(fn)(fat_s, psi_s)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
def test_sharded_improved_staggered_v3_matches_single_device():
    """Improved staggered (fat + 3-hop Naik): the 3-plane slab fixes per
    partitioned direction must bit-match the single-device stencil.
    Local extents must be >= 3 (checked by the kernel)."""
    from quda_tpu.ops import staggered_packed as spk
    from quda_tpu.parallel.pallas_dslash import (
        dslash_staggered_pallas_sharded_v3)
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    geom = LatticeGeometry((4, 4, 8, 12))    # (x,y,z,t): T=12 -> local 3
    T, Z, Y, X = geom.lattice_shape
    fat_c = GaugeField.random(jax.random.PRNGKey(23), geom).data.astype(
        jnp.complex64)
    long_c = GaugeField.random(jax.random.PRNGKey(24), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(25), geom
                                    ).data.astype(jnp.complex64)[..., :1, :]
    fat_pp = wpk.to_packed_pairs(spk.pack_links(fat_c), jnp.float32)
    long_pp = wpk.to_packed_pairs(spk.pack_links(long_c), jnp.float32)
    psi_pp = wpk.to_packed_pairs(spk.pack_staggered(psi), jnp.float32)
    ref = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y, long_pp)

    mesh = make_lattice_mesh(grid=(4, 2, 1, 1), n_src=1)
    psi_spec = P(None, None, "t", "z", None)
    g_spec = P(None, None, None, None, "t", "z", None)
    fn = jax.shard_map(
        lambda f, l, p: dslash_staggered_pallas_sharded_v3(
            f, p, X, mesh, long_pl=l, interpret=True),
        mesh=mesh, in_specs=(g_spec, g_spec, psi_spec),
        out_specs=psi_spec, check_vma=False)
    fat_s = jax.device_put(fat_pp, NamedSharding(mesh, g_spec))
    long_s = jax.device_put(long_pp, NamedSharding(mesh, g_spec))
    psi_s = jax.device_put(psi_pp, NamedSharding(mesh, psi_spec))
    out = jax.jit(fn)(fat_s, long_s, psi_s)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
def test_sharded_wilson_eo_operator_solve_path():
    """The operator-level wiring: DiracWilsonPCPacked.pairs(mesh=...)
    runs MdagM through the sharded eo pallas policy and matches the
    unsharded pair operator."""
    from quda_tpu.models.wilson import DiracWilsonPC
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    geom = LatticeGeometry((4, 4, 8, 16))
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(43), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(44), geom
                                    ).data.astype(jnp.complex64)
    from quda_tpu.fields.spinor import even_odd_split
    pe, _ = even_odd_split(psi, geom)
    dpk = DiracWilsonPC(gauge, geom, kappa=0.12).packed()
    ref_op = dpk.pairs(jnp.float32)
    x_pp = wpk.to_packed_pairs(wpk.pack_spinor(pe), jnp.float32)
    ref = ref_op.MdagM_pairs(x_pp)

    mesh = make_lattice_mesh(grid=(4, 2, 1, 1), n_src=1)
    sh_op = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                      mesh=mesh)
    x_s = jax.device_put(
        x_pp, NamedSharding(mesh, P(None, None, None, "t", "z", None)))
    out = jax.jit(sh_op.MdagM_pairs)(x_s)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-5


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_sharded_staggered_eo_v3_matches_single_device(parity):
    """Checkerboarded improved-staggered hop (the complex-free staggered
    SOLVE stencil) under shard_map == the single-device eo pair stencil,
    both parities, fat + Naik."""
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.ops import staggered_packed as spk
    from quda_tpu.ops.wilson import split_gauge_eo
    from quda_tpu.parallel.pallas_dslash import (
        dslash_staggered_eo_pallas_sharded_v3)
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    # T=16: local extents must be EVEN on partitioned axes (checkerboard
    # masks use local coordinates) and >= 3 for the Naik slab fix
    geom = LatticeGeometry((4, 4, 8, 16))
    T, Z, Y, X = geom.lattice_shape
    dims = (T, Z, Y, X)
    fat_c = GaugeField.random(jax.random.PRNGKey(31), geom).data.astype(
        jnp.complex64)
    long_c = GaugeField.random(jax.random.PRNGKey(32), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(33), geom
                                    ).data.astype(jnp.complex64)[..., :1, :]
    fat_eo = split_gauge_eo(fat_c, geom)
    long_eo = split_gauge_eo(long_c, geom)
    pe, po = even_odd_split(psi, geom)
    src = pe if parity == 1 else po
    fat_eo_pp = tuple(wpk.to_packed_pairs(spk.pack_links(g), jnp.float32)
                      for g in fat_eo)
    long_eo_pp = tuple(wpk.to_packed_pairs(spk.pack_links(g), jnp.float32)
                       for g in long_eo)
    src_pp = wpk.to_packed_pairs(spk.pack_staggered(src), jnp.float32)
    ref = spk.dslash_staggered_eo_packed_pairs(
        fat_eo_pp, src_pp, dims, parity, long_eo_pp)

    mesh = make_lattice_mesh(grid=(4, 2, 1, 1), n_src=1)
    psi_spec = P(None, None, "t", "z", None)
    g_spec = P(None, None, None, None, "t", "z", None)
    fn = jax.shard_map(
        lambda fh, ft, lh, lt, p: dslash_staggered_eo_pallas_sharded_v3(
            fh, ft, p, dims, parity, mesh, long_here_pl=lh,
            long_there_pl=lt, interpret=True),
        mesh=mesh,
        in_specs=(g_spec, g_spec, g_spec, g_spec, psi_spec),
        out_specs=psi_spec, check_vma=False)
    args = [jax.device_put(a, NamedSharding(mesh, g_spec))
            for a in (fat_eo_pp[parity], fat_eo_pp[1 - parity],
                      long_eo_pp[parity], long_eo_pp[1 - parity])]
    src_s = jax.device_put(src_pp, NamedSharding(mesh, psi_spec))
    out = jax.jit(fn)(*args, src_s)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


# -- round 8: v2-form sharded eo policy + the policy engine -----------------

def _eo_fixture(key1=51, key2=52, fold_t=True, shape=(4, 4, 8, 16)):
    """(dims, g_eo_pp, (pe, po)) on an eo-test geometry (ctor order
    x,y,z,t; partitioned local extents must come out even); folded
    antiperiodic t so the reconstruct-12 shard-edge signs are actually
    exercised."""
    from quda_tpu.fields.spinor import even_odd_split
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


def _run_sharded_eo_v2(dims, g_eo_pp, parity, src_pp, policy,
                       recon12=False, grid=(4, 2, 1, 1), n_dev=8):
    from quda_tpu.parallel.pallas_dslash import dslash_eo_pallas_sharded
    mesh = make_lattice_mesh(grid=grid, n_src=1,
                             devices=jax.devices()[:n_dev])
    psi_spec = P(None, None, None, "t", "z", None)
    g_spec = P(None, None, None, None, "t", "z", None)
    uh, ut = g_eo_pp[parity], g_eo_pp[1 - parity]
    if recon12:
        uh, ut = wpp.to_recon12(uh), wpp.to_recon12(ut)
    # GLOBAL pre-shift of the backward links, THEN shard: the cross-
    # shard links are then already resident per shard (the v2 design)
    u_bw = wpp.backward_gauge_eo(ut, dims, parity)
    fn = jax.shard_map(
        lambda a, b, p: dslash_eo_pallas_sharded(
            a, b, p, dims, parity, mesh, interpret=True, policy=policy),
        mesh=mesh, in_specs=(g_spec, g_spec, psi_spec),
        out_specs=psi_spec, check_vma=False)
    uh_s = jax.device_put(uh, NamedSharding(mesh, g_spec))
    ub_s = jax.device_put(u_bw, NamedSharding(mesh, g_spec))
    src_s = jax.device_put(src_pp, NamedSharding(mesh, psi_spec))
    return jax.jit(fn)(uh_s, ub_s, src_s)


@pytest.mark.parametrize(
    "parity", [0, pytest.param(1, marks=pytest.mark.slow)])
def test_sharded_wilson_eo_v2_matches_single_device(parity):
    """THE round-8 acceptance test: the v2 (gather, pre-shifted backward
    links) eo kernel — the measured single-chip winner, PERF.md round 5
    — under shard_map bit-matches the single-device eo pair stencil for
    both parities (the sharded path no longer pays the 3.2x scatter-form
    tax; VERDICT r7 #5)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    # tiny geometry + a 2x2 grid over 4 devices: the interpret-mode
    # compile dominates (50 s a parity in a whole run, so tier-1 keeps
    # one) — the 4-shard/edge-sign coverage lives in the slow recon-12
    # variants below
    dims, g_eo_pp, (pe, po) = _eo_fixture(shape=(4, 4, 4, 8))
    src = pe if parity == 1 else po
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(src), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
    out = _run_sharded_eo_v2(dims, g_eo_pp, parity, src_pp,
                             "xla_facefix", grid=(2, 2, 1, 1), n_dev=4)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_sharded_wilson_eo_v2_recon12_matches_single_device(parity):
    """recon-18-only restriction lifted: the sharded v2 path accepts
    reconstruct-12 links (in-kernel interior + _full_rows face slabs
    with shard-edge t signs) — folded antiperiodic t included, so the
    boundary-plane row-2 sign logic is live on both the first and last
    t shards."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    dims, g_eo_pp, (pe, po) = _eo_fixture()
    src = pe if parity == 1 else po
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(src), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
    out = _run_sharded_eo_v2(dims, g_eo_pp, parity, src_pp,
                             "xla_facefix", recon12=True)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-5          # f32 third-row reconstruction floor


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_sharded_wilson_eo_v2_fused_halo_matches_facefix(parity):
    """Policy A/B: the fused in-kernel RDMA slab exchange must be
    bit-identical to the ppermute face-fix transport (same algebra,
    different wire)."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    dims, g_eo_pp, (pe, po) = _eo_fixture()
    src = pe if parity == 1 else po
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(src), jnp.float32)
    ref = wpk.dslash_eo_packed_pairs(g_eo_pp, src_pp, dims, parity)
    out = _run_sharded_eo_v2(dims, g_eo_pp, parity, src_pp,
                             "fused_halo")
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


# 168 s alone (PR 25): ~16 interpreted applications, at 4^4 already
@pytest.mark.slow
def test_sharded_operator_defaults_to_v2_and_races_policy(tmp_path,
                                                          monkeypatch):
    """The model-layer dispatch: a multi-device mesh operator now
    resolves the kernel form exactly like single-chip (v2 default), and
    QUDA_TPU_SHARDED_POLICY=auto races the halo policies once per
    (volume, mesh, form) and caches the winner deterministically in the
    tunecache (QUDA policy-engine behavior, tune.cpp:862)."""
    import json

    import quda_tpu.models.wilson as mwil
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.utils import config as qconf
    from quda_tpu.utils import tune as qtune
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    monkeypatch.delenv("QUDA_TPU_SHARDED_POLICY", raising=False)
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    qconf.reset_cache()
    monkeypatch.setattr(qtune, "_cache", {})
    monkeypatch.setattr(mwil, "_SHARDED_NOTICED", True)

    # smallest legal config (even local extents on a 2x2 t/z grid over
    # 4 of the virtual devices): the race times ~16 interpret-mode
    # applications, so the lattice must be tiny to stay in the fast tier
    geom = LatticeGeometry((4, 4, 4, 4))
    gauge = GaugeField.random(jax.random.PRNGKey(61), geom
                              ).data.astype(jnp.complex64)
    dpk = DiracWilsonPC(gauge, geom, kappa=0.12).packed()
    mesh = make_lattice_mesh(grid=(2, 2, 1, 1), n_src=1,
                             devices=jax.devices()[:4])
    op = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   mesh=mesh)
    won = op._sharded_policy_winner
    # round 18: the engine races PER AXIS — the winner is a full
    # {axis: policy} map with every partitioned axis raced and the
    # unpartitioned ones pinned at the facefix transport
    assert set(won) == {"t", "z", "y", "x"}
    assert all(v in ("xla_facefix", "fused_halo") for v in won.values())
    # the winners are persisted: one cache entry PER PARTITIONED AXIS
    # (t and z here) and a second operator re-reads them without
    # re-racing (tune returns the cached params)
    cache = json.loads((tmp_path / "tunecache.json").read_text())
    keys = sorted(k for k in cache if "wilson_eo_sharded_policy" in k)
    assert len(keys) == 2
    assert any("wilson_eo_sharded_policy_t" in k for k in keys)
    assert any("wilson_eo_sharded_policy_z" in k for k in keys)
    for k in keys:
        ax = k.split("wilson_eo_sharded_policy_")[1].split("|")[0]
        assert cache[k]["param"] == won[ax]
    op2 = dpk.pairs(jnp.float32, use_pallas=True,
                    pallas_interpret=True, mesh=mesh)
    assert op2._sharded_policy_winner == won


# -- round 10: sharded staggered on the v2 gather form ----------------------

def _stag_sharded_fixture(improved=True, shape=(4, 4, 8, 16)):
    """(dims, fat_pp, long_pp, psi_pp) full-lattice staggered pair
    arrays (partitioned local extents even and >= 3 under Naik)."""
    from quda_tpu.ops import staggered_packed as spk
    geom = LatticeGeometry(shape)
    dims = geom.lattice_shape
    fat_c = GaugeField.random(jax.random.PRNGKey(61), geom).data.astype(
        jnp.complex64)
    long_c = GaugeField.random(jax.random.PRNGKey(62), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(63), geom
                                    ).data.astype(jnp.complex64)[..., :1, :]
    fat_pp = wpk.to_packed_pairs(spk.pack_links(fat_c), jnp.float32)
    long_pp = (wpk.to_packed_pairs(spk.pack_links(long_c), jnp.float32)
               if improved else None)
    psi_pp = wpk.to_packed_pairs(spk.pack_staggered(psi), jnp.float32)
    return dims, fat_pp, long_pp, psi_pp


@pytest.mark.slow
def test_sharded_staggered_v2_matches_single_device():
    """Round-10 tentpole (3): the v2 GATHER staggered form — globally
    pre-shifted backward links for BOTH hop sets (the Naik backward
    reach crosses the shard seam inside the pre-shift) — under
    shard_map matches the single-device stencil; only psi slabs ride
    the exchange (1-row fat + 3-row Naik)."""
    from quda_tpu.ops import staggered_packed as spk
    from quda_tpu.ops import staggered_pallas as stp
    from quda_tpu.parallel.pallas_dslash import (
        dslash_staggered_pallas_sharded)
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    (T, Z, Y, X), fat_pp, long_pp, psi_pp = _stag_sharded_fixture()
    ref = spk.dslash_staggered_packed_pairs(fat_pp, psi_pp, X, Y,
                                            long_pp)
    # GLOBAL pre-shift, THEN shard (the v2 design)
    fat_bw = stp.backward_links(fat_pp, X, 1)
    long_bw = stp.backward_links(long_pp, X, 3)

    mesh = make_lattice_mesh(grid=(4, 2, 1, 1), n_src=1)
    psi_spec = P(None, None, "t", "z", None)
    g_spec = P(None, None, None, None, "t", "z", None)
    fn = jax.shard_map(
        lambda f, fb, l, lb, p: dslash_staggered_pallas_sharded(
            f, fb, p, X, mesh, long_pl=l, long_bw_pl=lb,
            interpret=True),
        mesh=mesh, in_specs=(g_spec,) * 4 + (psi_spec,),
        out_specs=psi_spec, check_vma=False)
    args = [jax.device_put(a, NamedSharding(mesh, g_spec))
            for a in (fat_pp, fat_bw, long_pp, long_bw)]
    psi_s = jax.device_put(psi_pp, NamedSharding(mesh, psi_spec))
    out = jax.jit(fn)(*args, psi_s)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_sharded_staggered_eo_v2_matches_single_device(parity):
    """Checkerboarded v2-gather staggered hop (the staggered CG hot
    path) under shard_map == the single-device eo pair stencil, both
    parities, fat + Naik — the QUDA_TPU_SHARDED_POLICY seam now covers
    the staggered solve stencil in the measured-best kernel form."""
    from quda_tpu.fields.spinor import even_odd_split
    from quda_tpu.ops import staggered_packed as spk
    from quda_tpu.ops import staggered_pallas as stp
    from quda_tpu.ops.wilson import split_gauge_eo
    from quda_tpu.parallel.pallas_dslash import (
        dslash_staggered_eo_pallas_sharded)
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    geom = LatticeGeometry((4, 4, 8, 16))
    T, Z, Y, X = geom.lattice_shape
    dims = (T, Z, Y, X)
    fat_c = GaugeField.random(jax.random.PRNGKey(64), geom).data.astype(
        jnp.complex64)
    long_c = GaugeField.random(jax.random.PRNGKey(65), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(66), geom
                                    ).data.astype(jnp.complex64)[..., :1, :]
    fat_eo = split_gauge_eo(fat_c, geom)
    long_eo = split_gauge_eo(long_c, geom)
    pe, po = even_odd_split(psi, geom)
    src = pe if parity == 1 else po
    fat_eo_pp = tuple(wpk.to_packed_pairs(spk.pack_links(g), jnp.float32)
                      for g in fat_eo)
    long_eo_pp = tuple(wpk.to_packed_pairs(spk.pack_links(g), jnp.float32)
                       for g in long_eo)
    src_pp = wpk.to_packed_pairs(spk.pack_staggered(src), jnp.float32)
    ref = spk.dslash_staggered_eo_packed_pairs(
        fat_eo_pp, src_pp, dims, parity, long_eo_pp)
    # GLOBAL pre-shift of the eo backward links, THEN shard
    fat_bw = stp.backward_links_eo(fat_eo_pp[1 - parity], dims, parity, 1)
    long_bw = stp.backward_links_eo(long_eo_pp[1 - parity], dims,
                                    parity, 3)

    mesh = make_lattice_mesh(grid=(4, 2, 1, 1), n_src=1)
    psi_spec = P(None, None, "t", "z", None)
    g_spec = P(None, None, None, None, "t", "z", None)
    fn = jax.shard_map(
        lambda fh, fb, lh, lb, p: dslash_staggered_eo_pallas_sharded(
            fh, fb, p, dims, parity, mesh, long_here_pl=lh,
            long_bw_pl=lb, interpret=True),
        mesh=mesh, in_specs=(g_spec,) * 4 + (psi_spec,),
        out_specs=psi_spec, check_vma=False)
    args = [jax.device_put(a, NamedSharding(mesh, g_spec))
            for a in (fat_eo_pp[parity], fat_bw, long_eo_pp[parity],
                      long_bw)]
    src_s = jax.device_put(src_pp, NamedSharding(mesh, psi_spec))
    out = jax.jit(fn)(*args, src_s)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-6


@pytest.mark.slow
def test_sharded_staggered_operator_solve_path():
    """Operator-level wiring: DiracStaggeredPC.pairs(mesh=...) runs
    M_pairs through the sharded staggered eo policy (two-pass interior
    pinned under a mesh, halo policy resolved through the
    QUDA_TPU_SHARDED_POLICY engine) and matches the unsharded pair
    operator."""
    from quda_tpu.models.staggered import DiracStaggeredPC
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    geom = LatticeGeometry((4, 4, 8, 16))
    T, Z, Y, X = geom.lattice_shape
    fat_c = GaugeField.random(jax.random.PRNGKey(67), geom).data.astype(
        jnp.complex64)
    long_c = (0.1 * GaugeField.random(jax.random.PRNGKey(68), geom).data
              ).astype(jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(69), geom
                                    ).data.astype(jnp.complex64)[..., :1, :]
    from quda_tpu.fields.spinor import even_odd_split
    pe, _ = even_odd_split(psi, geom)
    from quda_tpu.ops import staggered_packed as spk
    dpc = DiracStaggeredPC(fat_c, geom, 0.1, improved=True,
                           long_links=long_c)
    ref_op = dpc.pairs(jnp.float32)
    x_pp = wpk.to_packed_pairs(spk.pack_staggered(pe), jnp.float32)
    ref = ref_op.M_pairs(x_pp)

    mesh = make_lattice_mesh(grid=(4, 2, 1, 1), n_src=1)
    sh_op = dpc.pairs(jnp.float32, use_pallas=True,
                      pallas_interpret=True, mesh=mesh,
                      sharded_policy="xla_facefix")
    assert sh_op._pallas_form == "two_pass"   # mesh pins the interior
    x_s = jax.device_put(
        x_pp, NamedSharding(mesh, P(None, None, "t", "z", None)))
    out = jax.jit(sh_op.M_pairs)(x_s)
    err = float(jnp.sqrt(blas.norm2(ref - out) / blas.norm2(ref)))
    assert err < 1e-5


def test_sharded_staggered_rejects_unknown_policy():
    """The staggered sharded wrappers ride the same policy registry as
    Wilson — an unknown QUDA_TPU_SHARDED_POLICY value fails loudly."""
    from quda_tpu.parallel.pallas_dslash import (
        dslash_staggered_eo_pallas_sharded)
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 virtual devices")
    mesh = make_lattice_mesh(grid=(2, 1, 1, 1), n_src=1,
                             devices=jax.devices()[:2])
    dims = (4, 4, 4, 8)
    z = jnp.zeros((4, 3, 3, 2, 4, 4, 16), jnp.float32)
    p = jnp.zeros((3, 2, 4, 4, 16), jnp.float32)
    with pytest.raises(ValueError, match="unknown sharded halo policy"):
        dslash_staggered_eo_pallas_sharded(z, z, p, dims, 0, mesh,
                                           interpret=True,
                                           policy="bogus")
