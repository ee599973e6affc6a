"""Fused-halo pallas prototype: the NVSHMEM-analog seam, realised.

Reference behavior: include/dslash_shmem.h:1-83 and the uber policies of
lib/dslash_policy.hpp:1669-1672 — QUDA's single-launch dslash packs the
boundary, sends it over NVSHMEM from INSIDE the kernel, computes the
interior while the transfer is in flight, then applies the exterior when
the arrival flag trips.  Every other path in this repo composes the face
exchange OUTSIDE the kernel (XLA ppermute around a pallas interior call,
`parallel/pallas_dslash.py`); this module moves one direction of the
exchange INSIDE the kernel with `pltpu.make_async_remote_copy` — the TPU
ICI analog of the NVSHMEM put + wait.

Scope (round 8): BOTH slab axes of the sharded layout.  The original
z-backward prototype remains as the minimal teaching form; the bidir
kernel is now axis-general (mu = 2 -> z hops on (4,3,2,Z,YX) blocks,
mu = 3 -> t hops on (4,3,2,T,Z,YX) blocks — `wilson_t_fused_halo`),
and `slab_exchange_bidir` packages the same mechanism as a ppermute
drop-in (two RDMAs behind one neighbour barrier, no hop math) that the
sharded dslash policies select via QUDA_TPU_SHARDED_POLICY=fused_halo
(parallel/pallas_dslash.py).  The original kernel:

  1. computes m(y) = U_z(y)^dag P^{+z} psi(y) for every LOCAL site
     (the scatter-form backward product),
  2. copies its top boundary row of m into a VMEM send buffer and
     STARTS the async remote copy to the +z neighbour's receive buffer,
  3. (the interior rows of the output are assembled while the DMA is in
     flight — the overlap window),
  4. waits on the receive semaphore and splices the arrived row in as
     local z=0's contribution (which lives at the -z neighbour's edge).

Executable two ways: compiled on real multi-chip TPU, and bit-exactly
on the virtual CPU mesh
via `pltpu.InterpretParams` — the A/B test against the XLA-composed
exchange runs on the latter (`tests/test_pallas_halo.py`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.wilson_pallas_packed import (TABLES, _color_mul, _project,
                                        _recon_acc)
F32 = jnp.float32


def _require_dist_interpret(interpret: bool):
    """The in-kernel remote copies need either real multi-chip hardware
    or the distributed Mosaic interpreter (pltpu.InterpretParams:
    cross-device DMA emulation) — a plain ``interpret=True`` cannot
    run them."""
    return pltpu.InterpretParams() if interpret else False


def _bwd_math(psi_at, link_of, mu: int):
    """m[s][c] = (U_mu^dag P^{+mu} psi) as (re, im) pairs, local rows."""
    tb = TABLES[(mu, -1)]
    h = _project(psi_at, tb)
    return _color_mul(h, link_of, True), tb


def _zbwd_math(psi_at, link_of):
    """m[s][c] = (U_z^dag P^{+z} psi) as (re, im) pairs, local rows."""
    return _bwd_math(psi_at, link_of, 2)


def _make_fused_kernel(axis_name: str):
    def kernel(psi_ref, uz_ref, out_ref, sendbuf, ghost, send_sem,
               recv_sem):
        my = jax.lax.axis_index(axis_name)
        n = jax.lax.axis_size(axis_name)
        nxt = (my + 1) % n

        def psi_at(s, c):
            # local blocks are (4,3,2,Zl,YX) — no t axis in this
            # one-direction prototype
            return (psi_ref[s, c, 0].astype(F32),
                    psi_ref[s, c, 1].astype(F32))

        def link_of(a, b):
            return (uz_ref[a, b, 0].astype(F32),
                    uz_ref[a, b, 1].astype(F32))

        # 1. local scatter-form product for ALL rows
        m, tb = _zbwd_math(psi_at, link_of)

        # 2. pack the top boundary row and start the remote copy — the
        #    +z neighbour's z=0 output needs OUR last row's product.
        #    BARRIER first: my write lands in the +z neighbour's ghost
        #    scratch, which is only live once IT has entered this kernel
        #    — so each device signals its -z neighbour "my buffers are
        #    ready" and waits for the same signal from its +z neighbour
        #    (the canonical neighbour-barrier; collective_id pins the
        #    shared barrier semaphore across devices)
        for s in range(2):
            for c in range(3):
                sendbuf[s, c, 0] = m[s][c][0][-1:]
                sendbuf[s, c, 1] = m[s][c][1][-1:]
        bsem = pltpu.get_barrier_semaphore()
        prv = (my - 1) % n
        pltpu.semaphore_signal(bsem, inc=1, device_id=(prv,),
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(bsem, 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=sendbuf, dst_ref=ghost,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=(nxt,), device_id_type=pltpu.DeviceIdType.MESH)
        rdma.start()

        # 3. interior assembly overlaps the DMA: rows z>0 of the output
        #    are the local rows shifted down by one — no remote data
        interior = [[(jnp.roll(m[s][c][0], 1, axis=0),
                      jnp.roll(m[s][c][1], 1, axis=0))
                     for c in range(3)] for s in range(2)]

        # 4. exterior: wait for the -z neighbour's row, splice at z=0
        rdma.wait()
        row = jax.lax.broadcasted_iota(
            jnp.int32, psi_ref.shape[-2:], 0)
        uh = [[None] * 3 for _ in range(2)]
        for s in range(2):
            for c in range(3):
                gr = ghost[s, c, 0].astype(F32)
                gi = ghost[s, c, 1].astype(F32)
                uh[s][c] = (jnp.where(row == 0, gr, interior[s][c][0]),
                            jnp.where(row == 0, gi, interior[s][c][1]))

        acc = [[(jnp.zeros(psi_ref.shape[-2:], F32),
                 jnp.zeros(psi_ref.shape[-2:], F32))
                for _ in range(3)] for _ in range(4)]
        _recon_acc(acc, uh, tb)
        for s in range(4):
            for c in range(3):
                out_ref[s, c, 0] = acc[s][c][0]
                out_ref[s, c, 1] = acc[s][c][1]

    return kernel


def _make_fused_kernel_bidir(axis_name: str, mu: int = 2):
    """Both hops of one partitioned direction in one launch: two RDMAs
    in flight behind one neighbour barrier — the full per-direction
    shape of the dslash_shmem uber-kernel.  ``mu`` selects the hop
    tables and the local block rank: mu=2 runs on (4,3,2,Z,YX) blocks
    (the original z form), mu=3 on (4,3,2,T,Z,YX) blocks — in both the
    partitioned axis is array axis 3 (spatial axis 0 of each plane), so
    the body is rank-generic.

    The backward-hop body repeats `_make_fused_kernel` (pack / interior
    roll / edge splice / recon): the unidirectional kernel is kept as
    the minimal teaching form of the seam, and the two must evolve
    together — change either hop's packing or splice in BOTH places (or
    retire the unidirectional kernel once a production path adopts this
    one)."""
    def kernel(psi_ref, u_ref, out_ref, sb_bwd, gh_bwd, sb_fwd, gh_fwd,
               send_b, recv_b, send_f, recv_f):
        my = jax.lax.axis_index(axis_name)
        n = jax.lax.axis_size(axis_name)
        nxt = (my + 1) % n
        prv = (my - 1) % n
        sp_shape = psi_ref.shape[3:]      # local spatial block planes
        L = psi_ref.shape[3]              # partitioned local extent

        def psi_at(s, c):
            return (psi_ref[s, c, 0].astype(F32),
                    psi_ref[s, c, 1].astype(F32))

        def link_of(a, b):
            return (u_ref[a, b, 0].astype(F32),
                    u_ref[a, b, 1].astype(F32))

        # local products/half-spinors for both hops
        m, tb = _bwd_math(psi_at, link_of, mu)   # bwd: U^dag P^{+mu} psi
        tf = TABLES[(mu, +1)]
        h = _project(psi_at, tf)                 # fwd: P^{-mu} psi

        # pack both boundary strips
        for s in range(2):
            for c in range(3):
                sb_bwd[s, c, 0] = m[s][c][0][-1:]   # my top product
                sb_bwd[s, c, 1] = m[s][c][1][-1:]
                sb_fwd[s, c, 0] = h[s][c][0][:1]    # my bottom half-spinor
                sb_fwd[s, c, 1] = h[s][c][1][:1]

        # neighbour barrier both ways, then both RDMAs in flight
        bsem = pltpu.get_barrier_semaphore()
        for dst in (prv, nxt):
            pltpu.semaphore_signal(bsem, inc=1, device_id=(dst,),
                                   device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(bsem, 2)
        rdma_b = pltpu.make_async_remote_copy(
            src_ref=sb_bwd, dst_ref=gh_bwd, send_sem=send_b,
            recv_sem=recv_b, device_id=(nxt,),
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma_f = pltpu.make_async_remote_copy(
            src_ref=sb_fwd, dst_ref=gh_fwd, send_sem=send_f,
            recv_sem=recv_f, device_id=(prv,),
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma_b.start()
        rdma_f.start()

        # interior work overlaps both transfers
        int_b = [[(jnp.roll(m[s][c][0], 1, axis=0),
                   jnp.roll(m[s][c][1], 1, axis=0))
                  for c in range(3)] for s in range(2)]
        int_f = [[(jnp.roll(h[s][c][0], -1, axis=0),
                   jnp.roll(h[s][c][1], -1, axis=0))
                  for c in range(3)] for s in range(2)]

        rdma_b.wait()
        rdma_f.wait()
        row = jax.lax.broadcasted_iota(jnp.int32, sp_shape, 0)
        uh_b = [[None] * 3 for _ in range(2)]
        h_sp = [[None] * 3 for _ in range(2)]
        for s in range(2):
            for c in range(3):
                uh_b[s][c] = (
                    jnp.where(row == 0, gh_bwd[s, c, 0].astype(F32),
                              int_b[s][c][0]),
                    jnp.where(row == 0, gh_bwd[s, c, 1].astype(F32),
                              int_b[s][c][1]))
                h_sp[s][c] = (
                    jnp.where(row == L - 1, gh_fwd[s, c, 0].astype(F32),
                              int_f[s][c][0]),
                    jnp.where(row == L - 1, gh_fwd[s, c, 1].astype(F32),
                              int_f[s][c][1]))
        # fwd: multiply the SPLICED half-spinor by the local link U(x)
        uh_f = _color_mul(h_sp, link_of, False)

        acc = [[(jnp.zeros(sp_shape, F32), jnp.zeros(sp_shape, F32))
                for _ in range(3)] for _ in range(4)]
        _recon_acc(acc, uh_b, tb)
        _recon_acc(acc, uh_f, tf)
        for s in range(4):
            for c in range(3):
                out_ref[s, c, 0] = acc[s][c][0]
                out_ref[s, c, 1] = acc[s][c][1]

    return kernel


@functools.partial(jax.jit, static_argnames=("mesh", "mu", "axis_name",
                                             "interpret"))
def wilson_axis_fused_halo(psi_pl: jnp.ndarray, u_pl: jnp.ndarray,
                           mesh, mu: int = 2, axis_name: str = "z",
                           interpret: bool = False) -> jnp.ndarray:
    """BOTH hops of one partitioned direction with their halos exchanged
    inside one kernel launch (two concurrent RDMAs behind one neighbour
    barrier).

    mu=2: psi (4,3,2,Z,YX) / u (3,3,2,Z,YX) sharded on ``axis_name``
    (the original z form); mu=3: psi (4,3,2,T,Z,YX) / u (3,3,2,T,Z,YX)
    sharded the same way — the OTHER slab axis of the sharded layout.
    Matches `wilson_axis_composed(psi, u, mu)`."""
    from jax.sharding import PartitionSpec as P

    kern = _make_fused_kernel_bidir(axis_name, mu)
    ip = _require_dist_interpret(interpret)

    # ICI ledger: two half-spinor boundary strips per device ride the
    # in-kernel RDMAs each invocation; the strips are kernel-internal
    # VMEM buffers, so the bytes are passed explicitly (obs/comms.py;
    # no-op when the ledger is off)
    from ..obs import comms as ocomms
    strip_elems = 2 * 3 * 2
    for s in psi_pl.shape[4:]:
        strip_elems *= s
    ocomms.record_exchange(axis=axis_name, direction="bidir",
                           policy="fused_halo", nbytes=2 * 4 * strip_elems,
                           n_slabs=2,
                           mesh_axes=(mesh.shape[axis_name],))

    def local(psi, u):
        strip = pltpu.VMEM((2, 3, 2, 1) + psi.shape[4:], F32)
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(psi.shape, psi.dtype),
            scratch_shapes=[strip, strip, strip, strip,
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
            compiler_params=pltpu.CompilerParams(collective_id=0),
            interpret=ip,
        )(psi, u)

    tail = (None,) * (psi_pl.ndim - 4)
    spec = P(None, None, None, axis_name, *tail)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                            out_specs=spec, check_vma=False)(psi_pl, u_pl)


def wilson_z_fused_halo(psi_pl: jnp.ndarray, uz_pl: jnp.ndarray,
                        mesh, axis_name: str = "z",
                        interpret: bool = False) -> jnp.ndarray:
    """BOTH z hops fused (layouts as `wilson_zbwd_fused_halo`); matches
    `wilson_z_composed`."""
    return wilson_axis_fused_halo(psi_pl, uz_pl, mesh, mu=2,
                                  axis_name=axis_name,
                                  interpret=interpret)


def wilson_t_fused_halo(psi_pl: jnp.ndarray, ut_pl: jnp.ndarray,
                        mesh, axis_name: str = "t",
                        interpret: bool = False) -> jnp.ndarray:
    """BOTH t hops fused: psi (4,3,2,T,Z,YX) / u_t (3,3,2,T,Z,YX)
    sharded on ``axis_name`` — the t-axis widening of the z prototype
    (VERDICT r7 #7).  Matches `wilson_t_composed`."""
    return wilson_axis_fused_halo(psi_pl, ut_pl, mesh, mu=3,
                                  axis_name=axis_name,
                                  interpret=interpret)


# -- ppermute drop-in: the fused-halo POLICY seam ---------------------------

def _make_exchange_kernel(axis_name: str, mesh_axes: tuple):
    """Slab exchange, both directions behind ONE neighbour barrier: my
    ``in_dn`` lands in the -1 neighbour's ``out_dn`` window and my
    ``in_up`` in the +1 neighbour's ``out_up`` — so locally, out_dn is
    the slab arriving FROM the +1 neighbour and out_up the one FROM the
    -1 neighbour (exactly lax.ppermute's towards_lower=True / False
    pair, fused into one launch with in-kernel remote copies)."""
    def kernel(in_dn, in_up, out_dn, out_up, send_d, recv_d, send_u,
               recv_u):
        my = jax.lax.axis_index(axis_name)
        n = jax.lax.axis_size(axis_name)

        def coords(target):
            # full mesh coordinates with the exchange axis replaced —
            # DeviceIdType.MESH addresses the whole (possibly >1-axis)
            # mesh, not just the ring axis
            return tuple(target if a == axis_name
                         else jax.lax.axis_index(a) for a in mesh_axes)

        bsem = pltpu.get_barrier_semaphore()
        for dst in ((my - 1) % n, (my + 1) % n):
            pltpu.semaphore_signal(bsem, inc=1, device_id=coords(dst),
                                   device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(bsem, 2)
        rdma_d = pltpu.make_async_remote_copy(
            src_ref=in_dn, dst_ref=out_dn, send_sem=send_d,
            recv_sem=recv_d, device_id=coords((my - 1) % n),
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma_u = pltpu.make_async_remote_copy(
            src_ref=in_up, dst_ref=out_up, send_sem=send_u,
            recv_sem=recv_u, device_id=coords((my + 1) % n),
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma_d.start()
        rdma_u.start()
        rdma_d.wait()
        rdma_u.wait()
    return kernel


def slab_exchange_bidir(send_down: jnp.ndarray, send_up: jnp.ndarray,
                        axis_name: str, mesh_axes: tuple,
                        interpret: bool = False):
    """Exchange two boundary slabs with in-kernel remote copies — call
    INSIDE shard_map.  Returns ``(from_up, from_down)``:

      from_up   = ppermute(send_down, towards_lower=True)   (from +1)
      from_down = ppermute(send_up,  towards_lower=False)   (from -1)

    i.e. one fused launch covering the two face transfers the sharded
    dslash needs per partitioned direction (include/dslash_shmem.h put
    + wait, expressed as a drop-in for parallel/halo._permute_slice).

    Generic over ``axis_name`` and slab shape: any CONTIGUOUS face
    works — t/z plane slabs and y row strips of the fused Y·X axis
    (pallas_dslash.FUSED_HALO_AXES).  x column faces are strided
    gathers and stay on the ppermute policy."""
    kern = _make_exchange_kernel(axis_name, tuple(mesh_axes))
    ip = _require_dist_interpret(interpret)
    # ICI ledger: both slabs leave this device in one fused launch
    # (obs/comms.py; the enclosing policy scope labels the row)
    from ..obs import comms as ocomms
    ocomms.record_exchange((send_down, send_up), axis=axis_name,
                           direction="bidir", policy="fused_halo")
    anyspec = pl.BlockSpec(memory_space=pltpu.ANY)
    return pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct(send_down.shape, send_down.dtype),
                   jax.ShapeDtypeStruct(send_up.shape, send_up.dtype)),
        in_specs=[anyspec, anyspec],
        out_specs=(anyspec, anyspec),
        scratch_shapes=[pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(collective_id=1,
                                               has_side_effects=True),
        interpret=ip,
    )(send_down, send_up)


def _composed_hop(psi_pl: jnp.ndarray, u_pl: jnp.ndarray,
                  sign: int, mu: int = 2) -> jnp.ndarray:
    """One hop of direction ``mu`` on GLOBAL arrays (jnp.roll = the
    GSPMD-composed exchange).  sign=-1: backward (adjoint link, product
    rolled down); sign=+1: forward (half-spinor rolled up, then local
    link).  The partitioned axis is array axis 3 of the (4,3,2,...)
    layout in both the z (rank 5) and t (rank 6) forms."""
    ax = 3 - psi_pl.ndim                     # axis 3, as a negative index
    pr, pi = psi_pl[:, :, 0], psi_pl[:, :, 1]
    t = TABLES[(mu, sign)]
    hs = []
    for a in (0, 1):
        cr, ci = np.real(t[f"c{a}"]), np.imag(t[f"c{a}"])
        j = t[f"j{a}"]
        hr = pr[a] + cr * pr[j] - ci * pi[j]
        hi = pi[a] + cr * pi[j] + ci * pr[j]
        if sign > 0:                         # shift psi BEFORE the link
            hr = jnp.roll(hr, -1, axis=ax)
            hi = jnp.roll(hi, -1, axis=ax)
        hs.append((hr, hi))
    ur, ui = u_pl[:, :, 0], u_pl[:, :, 1]
    m = []
    for a in (0, 1):
        if sign > 0:                         # U[a,b] h[b]
            mr = jnp.einsum("ab...,b...->a...", ur, hs[a][0]) \
                - jnp.einsum("ab...,b...->a...", ui, hs[a][1])
            mi = jnp.einsum("ab...,b...->a...", ur, hs[a][1]) \
                + jnp.einsum("ab...,b...->a...", ui, hs[a][0])
        else:                                # conj(U)[b,a] h[b]
            mr = jnp.einsum("bc...,b...->c...", ur, hs[a][0]) \
                + jnp.einsum("bc...,b...->c...", ui, hs[a][1])
            mi = jnp.einsum("bc...,b...->c...", ur, hs[a][1]) \
                - jnp.einsum("bc...,b...->c...", ui, hs[a][0])
        m.append((mr, mi))
    if sign < 0:                             # shift the product down
        m = [(jnp.roll(a, 1, axis=ax), jnp.roll(b, 1, axis=ax))
             for (a, b) in m]
    out = jnp.zeros_like(psi_pl)
    for a in (0, 1):
        out = out.at[a, :, 0].set(m[a][0]).at[a, :, 1].set(m[a][1])
    d2, k2 = np.real(t["d2"]), t["k2"]
    d2i = np.imag(t["d2"])
    d3, k3 = np.real(t["d3"]), t["k3"]
    d3i = np.imag(t["d3"])
    out = out.at[2, :, 0].set(d2 * m[k2][0] - d2i * m[k2][1])
    out = out.at[2, :, 1].set(d2 * m[k2][1] + d2i * m[k2][0])
    out = out.at[3, :, 0].set(d3 * m[k3][0] - d3i * m[k3][1])
    out = out.at[3, :, 1].set(d3 * m[k3][1] + d3i * m[k3][0])
    return out


def wilson_axis_composed(psi_pl: jnp.ndarray, u_pl: jnp.ndarray,
                         mu: int = 2) -> jnp.ndarray:
    """XLA-composed reference for BOTH mu hops on global arrays."""
    return (_composed_hop(psi_pl, u_pl, -1, mu)
            + _composed_hop(psi_pl, u_pl, +1, mu))


def wilson_z_composed(psi_pl: jnp.ndarray,
                      uz_pl: jnp.ndarray) -> jnp.ndarray:
    """XLA-composed reference for BOTH z hops on global arrays."""
    return wilson_axis_composed(psi_pl, uz_pl, 2)


def wilson_t_composed(psi_pl: jnp.ndarray,
                      ut_pl: jnp.ndarray) -> jnp.ndarray:
    """XLA-composed reference for BOTH t hops on (4,3,2,T,Z,YX)."""
    return wilson_axis_composed(psi_pl, ut_pl, 3)


@functools.partial(jax.jit, static_argnames=("mesh", "axis_name",
                                             "interpret"))
def wilson_zbwd_fused_halo(psi_pl: jnp.ndarray, uz_pl: jnp.ndarray,
                           mesh, axis_name: str = "z",
                           interpret: bool = False) -> jnp.ndarray:
    """z-backward Wilson hop with the halo exchanged INSIDE the kernel.

    psi_pl: (4,3,2,Z,YX) packed pair spinor, GLOBAL z extent, sharded on
    ``axis_name`` over ``mesh``; uz_pl: (3,3,2,Z,YX) z-links (phases
    folded), sharded the same way.  Returns the packed-pair z-backward
    contribution U_z(x-z)^dag P^{+z} psi(x-z), identical to the
    XLA-composed reference `wilson_zbwd_composed`.

    ``interpret=True`` runs the Mosaic interpreter with cross-device DMA
    emulation (`pltpu.InterpretParams`) — the only way to execute this
    without n real chips.
    """
    from jax.sharding import PartitionSpec as P

    kern = _make_fused_kernel(axis_name)
    ip = _require_dist_interpret(interpret)

    # ICI ledger: one product boundary row per device per invocation
    from ..obs import comms as ocomms
    ocomms.record_exchange(axis=axis_name, direction="down",
                           policy="fused_halo",
                           nbytes=4 * 2 * 3 * 2 * psi_pl.shape[-1],
                           n_slabs=1,
                           mesh_axes=(mesh.shape[axis_name],))

    def local(psi, uz):
        yx = psi.shape[-1]
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(psi.shape, psi.dtype),
            scratch_shapes=[
                pltpu.VMEM((2, 3, 2, 1, yx), F32),   # send buffer
                pltpu.VMEM((2, 3, 2, 1, yx), F32),   # ghost (recv)
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
            compiler_params=pltpu.CompilerParams(collective_id=0),
            interpret=ip,
        )(psi, uz)

    spec = P(None, None, None, axis_name, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                            out_specs=spec, check_vma=False)(psi_pl, uz_pl)


def wilson_zbwd_composed(psi_pl: jnp.ndarray,
                         uz_pl: jnp.ndarray) -> jnp.ndarray:
    """XLA-composed reference for the backward term on GLOBAL arrays:
    the exchange is a jnp.roll (which GSPMD lowers to CollectivePermute
    around the local compute) — today's production path."""
    return _composed_hop(psi_pl, uz_pl, -1)
