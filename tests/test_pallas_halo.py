"""Fused-halo prototype (parallel/pallas_halo.py) vs the XLA-composed
exchange, bit-matched on the 8-device virtual mesh.

Reference behavior: include/dslash_shmem.h (in-kernel NVSHMEM halo) vs
the packed/composed policies — QUDA times both and picks per-geometry;
here the fused path must first be EXACT against the composed one.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from quda_tpu.parallel.pallas_halo import (wilson_zbwd_composed,
                                           wilson_zbwd_fused_halo)

# The fused kernels hold in-kernel remote copies: off-chip they run
# under the Mosaic interpreter's cross-device DMA emulation
# (pltpu.InterpretParams).  The composed (pure-XLA) references below
# pin the hop math regardless.


def _run_fused(fused, psi, u, mesh):
    # The interpreted call returns before its io_callback threads have
    # run; eager jax work on the same CPU client then deadlocks them.
    return jax.block_until_ready(fused(psi, u, mesh, interpret=True))


@pytest.mark.mid
def test_fused_halo_matches_composed():
    # small on purpose: the Mosaic interpreter with cross-device DMA
    # emulation costs minutes at Z=16/YX=64 on a 1-core host, and the
    # seam it verifies is size-independent (mid-tier budget contract)
    Z, YX = 8, 4 * 4
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    psi = jax.random.normal(k1, (4, 3, 2, Z, YX), jnp.float32)
    uz = jax.random.normal(k2, (3, 3, 2, Z, YX), jnp.float32)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("z",))
    got = _run_fused(wilson_zbwd_fused_halo, psi, uz, mesh)
    want = wilson_zbwd_composed(psi, uz)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    assert err <= 1e-5 * scale, (err, scale)


@pytest.mark.mid
def test_bidir_fused_halo_matches_composed():
    """Both z hops, two RDMAs in flight behind one neighbour barrier."""
    from quda_tpu.parallel.pallas_halo import (wilson_z_composed,
                                               wilson_z_fused_halo)
    # Z=16 over 8 shards -> local z extent 2: BOTH the interior-roll
    # paths and the ghost splices are live (zl=1 would make every row a
    # ghost row and leave the interior logic untested)
    Z, YX = 16, 4 * 4
    key = jax.random.PRNGKey(5)
    k1, k2 = jax.random.split(key)
    psi = jax.random.normal(k1, (4, 3, 2, Z, YX), jnp.float32)
    uz = jax.random.normal(k2, (3, 3, 2, Z, YX), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("z",))
    got = _run_fused(wilson_z_fused_halo, psi, uz, mesh)
    want = wilson_z_composed(psi, uz)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    assert err <= 1e-5 * scale, (err, scale)


@pytest.mark.mid
def test_bidir_fused_halo_t_axis_matches_composed():
    """The t-axis widening (round 8): both t hops on (4,3,2,T,Z,YX)
    blocks, two RDMAs behind one neighbour barrier — the other slab axis
    of the sharded layout (VERDICT r7 #7)."""
    from quda_tpu.parallel.pallas_halo import (wilson_t_composed,
                                               wilson_t_fused_halo)
    T, Z, YX = 16, 4, 4 * 4          # local t extent 2 over 8 shards
    key = jax.random.PRNGKey(7)
    k1, k2 = jax.random.split(key)
    psi = jax.random.normal(k1, (4, 3, 2, T, Z, YX), jnp.float32)
    ut = jax.random.normal(k2, (3, 3, 2, T, Z, YX), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("t",))
    got = _run_fused(wilson_t_fused_halo, psi, ut, mesh)
    want = wilson_t_composed(psi, ut)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    assert err <= 1e-5 * scale, (err, scale)


def test_axis_composed_references_match_packed_stencil():
    """The composed references themselves are pinned against the
    production packed-stencil helpers for BOTH slab axes — this runs on
    every jax (no RDMA), so the t-axis hop math has coverage even where
    the fused kernel cannot execute."""
    from quda_tpu.ops.wilson_packed import (_hop_packed_pairs,
                                            _planes_psi, _planes_u,
                                            _stack_pairs, shift_packed)
    from quda_tpu.ops.wilson_packed import TABLES
    from quda_tpu.parallel.pallas_halo import (wilson_t_composed,
                                               wilson_z_composed)
    key = jax.random.PRNGKey(11)
    X, Y = 4, 4
    psi = jax.random.normal(key, (4, 3, 2, 6, 8, Y * X), jnp.float32)
    u = jax.random.normal(jax.random.fold_in(key, 1),
                          (3, 3, 2, 6, 8, Y * X), jnp.float32)

    def ref_axis(mu):
        fwd = _stack_pairs(_hop_packed_pairs(
            _planes_psi(shift_packed(psi, mu, +1, X, Y)), _planes_u(u),
            TABLES[(mu, +1)], False), jnp.float32)
        ub = shift_packed(u, mu, -1, X, Y)
        bwd = _stack_pairs(_hop_packed_pairs(
            _planes_psi(shift_packed(psi, mu, -1, X, Y)), _planes_u(ub),
            TABLES[(mu, -1)], True), jnp.float32)
        return fwd + bwd

    got_t = wilson_t_composed(psi, u)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(ref_axis(3)),
                               rtol=1e-5, atol=1e-5)
    # z shifts act per t-plane, so the rank-5 z form on one t plane must
    # equal that plane of the full-rank reference
    got_z = wilson_z_composed(psi[:, :, :, 0], u[:, :, :, 0])
    np.testing.assert_allclose(np.asarray(got_z),
                               np.asarray(ref_axis(2)[:, :, :, 0]),
                               rtol=1e-5, atol=1e-5)
