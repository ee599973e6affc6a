"""Multi-chip Wilson/staggered dslash with the pallas interior kernel —
the "fused" manual policy, all four lattice directions.

Reference behavior: QUDA's interior/exterior kernel split
(lib/dslash_policy.hpp: interior kernel overlapped with halo comms,
then exterior kernels fix the boundary faces; NVSHMEM variant in
include/dslash_shmem.h).  The TPU re-design:

1. run the single-chip pallas kernel (ops/wilson_pallas_packed) on the
   LOCAL block with its periodic wraps — every interior site is final,
   boundary faces carry a wrong-wrap contribution;
2. exchange the psi boundary faces with the neighbouring shards
   (backward-hop links need no exchange: `backward_gauge` runs on the
   GLOBAL field before sharding, so cross-shard links are already
   resident in each shard's pre-shifted block);
3. fix the faces in XLA: subtract the wrong-wrap hop term, add the
   halo hop term — O(surface) work that XLA's latency-hiding scheduler
   overlaps with the next interior launch.

Sharding model: mesh axes "t" and "z" partition the packed layout's
T and Z array axes (whole-plane slab faces); mesh axes "y" and "x"
partition the fused Y*X axis — row-major, so a y face is a CONTIGUOUS
row strip of the fused axis while an x face is a STRIDED column gather
(``_FaceIO`` owns the three geometries; the fix algebra above it is
shared).  x-partitioned blocks must be laid out block-contiguous
(parallel/mesh.fuse_block_layout) so one shard holds a (Y_loc, X_loc)
rectangle with the LOCAL row width as its fused minor.

The Wilson policies run the v2 gather kernel (globally pre-shifted
backward links), the staggered ones the gather and the scatter (v3)
form; reconstruct-12 storage has its face slabs rebuilt by
``_full_rows``.  The exchange seam is per axis:
``QUDA_TPU_SHARDED_POLICY`` accepts a per-axis spec
(``t=fused_halo,z=fused_halo,y=xla_facefix``) resolved by
``resolve_axis_policies``; every partitioned direction routes its face
transfers through ``exchange(send_down, send_up, name, n)`` and the
fused-RDMA transport serves any axis with a contiguous strip (t/z
slabs and y row strips — x columns are strided, ppermute only).

All arrays are the packed PAIR layout: psi (4,3,2,T,Z,YX) storage,
gauge/gauge_bw (4,3,3,2,T,Z,YX) — per-shard LOCAL blocks inside
shard_map.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.wilson_packed import TABLES
from ..ops.wilson_packed import (_hop_packed_pairs, _planes_psi, _planes_u,
                                 _stack_pairs)
from .halo import _permute_slice as _nbr

AXIS_NAMES = ("t", "z", "y", "x")


def _hop_term(psi_slab, u_slab, table, adjoint):
    """Single hop-direction contribution on a boundary slab (f32)."""
    return _stack_pairs(
        _hop_packed_pairs(_planes_psi(psi_slab), _planes_u(u_slab),
                          table, adjoint), jnp.float32)


def _face_n(arr, axis, lo: bool, n: int = 1):
    """n boundary planes (one slab; n=1 for Wilson, 3 for Naik)."""
    L = arr.shape[axis]
    return (lax.slice_in_dim(arr, 0, n, axis=axis) if lo
            else lax.slice_in_dim(arr, L - n, L, axis=axis))


def _add_face_n(out, corr, axis, lo: bool, n: int = 1):
    L = out.shape[axis]
    idx = 0 if lo else L - n
    face = lax.slice_in_dim(out, idx, idx + n, axis=axis)
    fixed = (face.astype(jnp.float32) + corr).astype(out.dtype)
    return lax.dynamic_update_slice_in_dim(out, fixed, idx, axis)


# -- per-direction face geometry --------------------------------------------

class _FaceIO:
    """Boundary-face gather/scatter for ONE partitioned lattice
    direction on the packed layouts (..., T, Z, Y·X):

    * ``plane`` — t/z: n whole planes of array axis -3/-2 (slabs);
    * ``rows``  — y: the fused axis is row-major (y outer, x inner), so
      an n-row face is the CONTIGUOUS first/last n*xcols entries of the
      fused axis — still slab-shaped, so the fused-RDMA transport
      serves it like a t/z slab;
    * ``cols``  — x: an n-column face is a STRIDED gather — unfuse the
      trailing axis to (rows, xcols), slice n columns, and keep the
      (rows, n) trailing shape (every fix algebra above is elementwise
      over the trailing dims, so slab and column faces share the same
      hop/fix code).

    ``xcols`` is the LOCAL row width: X//n_x on the full lattice,
    Xh//n_x checkerboarded.
    """

    __slots__ = ("kind", "axis", "xcols")

    def __init__(self, kind: str, axis: int = 0, xcols: int = 0):
        self.kind, self.axis, self.xcols = kind, axis, xcols

    def _unfused(self, arr):
        xc = self.xcols
        return arr.reshape(arr.shape[:-1] + (arr.shape[-1] // xc, xc))

    def face(self, arr, lo: bool, n: int = 1):
        if self.kind == "plane":
            return _face_n(arr, self.axis, lo, n)
        if self.kind == "rows":
            return _face_n(arr, -1, lo, n * self.xcols)
        return _face_n(self._unfused(arr), -1, lo, n)

    def add(self, out, corr, lo: bool, n: int = 1):
        if self.kind == "plane":
            return _add_face_n(out, corr, self.axis, lo, n)
        if self.kind == "rows":
            return _add_face_n(out, corr, -1, lo, n * self.xcols)
        r = _add_face_n(self._unfused(out), corr, -1, lo, n)
        return r.reshape(out.shape)


def _axis_plan(counts, xcols: int):
    """(fio, mesh-axis name, shard count, mu) per lattice direction,
    outermost first — the ONE place the four face geometries are wired
    to their mesh axes (t/z plane slabs, y row strip, x column
    gather)."""
    n_t, n_z, n_y, n_x = counts
    return ((_FaceIO("plane", axis=-3), "t", n_t, 3),
            (_FaceIO("plane", axis=-2), "z", n_z, 2),
            (_FaceIO("rows", xcols=xcols), "y", n_y, 1),
            (_FaceIO("cols", xcols=xcols), "x", n_x, 0))


def _mesh_counts(mesh):
    s = dict(mesh.shape)
    return tuple(int(s.get(a, 1)) for a in AXIS_NAMES)


# -- halo-exchange policies (QUDA_TPU_SHARDED_POLICY) -----------------------
#
# Every face fix needs exactly two transfers per partitioned direction:
# one face travelling towards the LOWER shard (the receiver splices it
# into its HIGH face) and one towards the UPPER shard (spliced into the
# LOW face).  ``exchange(send_down, send_up, name, n)`` returns
# (from_up, from_down) and is the single seam where the policy engine
# plugs in:
#   * xla_facefix — two lax.ppermute calls (GSPMD CollectivePermute,
#     scheduled/overlapped by XLA — works on every axis including the
#     strided x column faces);
#   * fused_halo — ONE pallas launch with both RDMAs in flight behind a
#     single neighbour barrier (parallel/pallas_halo.slab_exchange_bidir,
#     the include/dslash_shmem.h analog) — contiguous strips only, i.e.
#     t/z slabs and y row strips (FUSED_HALO_AXES).
#
# Round 18: the knob is a PER-AXIS engine — a bare policy name applies
# to every axis (legacy form; fused_halo silently keeps xla_facefix on
# x), a spec string "t=fused_halo,z=fused_halo,y=xla_facefix" pins each
# axis separately, and the models race each partitioned axis
# independently through utils.tune.

SHARDED_POLICIES = ("xla_facefix", "fused_halo")

# axes whose faces are contiguous strips — the only ones the fused-RDMA
# slab kernel can serve (x faces are strided column gathers)
FUSED_HALO_AXES = ("t", "z", "y")


def resolve_axis_policies(policy) -> dict:
    """Normalize a halo-policy spec into {axis: policy} over t/z/y/x.

    Accepts a bare policy name (applied to every axis; ``fused_halo``
    falls back to ``xla_facefix`` on x, where the strided column face
    has no contiguous strip for the RDMA kernel), a per-axis spec
    string ``"t=fused_halo,z=fused_halo,y=xla_facefix"`` (unlisted axes
    get xla_facefix; an EXPLICIT x=fused_halo is an error), or an
    already-resolved dict."""
    if isinstance(policy, dict):
        items = list(policy.items())
    elif isinstance(policy, str) and "=" in policy:
        items = []
        for part in policy.split(","):
            part = part.strip()
            if not part:
                continue
            ax, _, val = part.partition("=")
            items.append((ax.strip(), val.strip()))
    else:
        if policy not in SHARDED_POLICIES:
            raise ValueError(f"unknown sharded halo policy {policy!r}; "
                             f"known: {SHARDED_POLICIES}")
        return {ax: (policy if policy != "fused_halo"
                     or ax in FUSED_HALO_AXES else "xla_facefix")
                for ax in AXIS_NAMES}
    pols = {ax: "xla_facefix" for ax in AXIS_NAMES}
    for ax, val in items:
        if ax not in AXIS_NAMES:
            raise ValueError(f"unknown mesh axis {ax!r} in sharded halo "
                             f"policy spec; known: {AXIS_NAMES}")
        if val not in SHARDED_POLICIES:
            raise ValueError(f"unknown sharded halo policy {val!r}; "
                             f"known: {SHARDED_POLICIES}")
        if val == "fused_halo" and ax not in FUSED_HALO_AXES:
            raise ValueError(
                "x faces are strided column gathers (no contiguous "
                f"strip): fused_halo serves {FUSED_HALO_AXES} only")
        pols[ax] = val
    return pols


def _policy_label(pols: dict, live_axes) -> str:
    """ONE policy label for the ledger scope (obs/comms treats groups
    within a scope as alternatives of the same invocation, so the scope
    must carry a single joint label): the plain name when every
    partitioned axis agrees, else the per-axis spec string."""
    live = tuple(live_axes)
    vals = {pols[a] for a in live} if live else {pols["t"]}
    if len(vals) == 1:
        return vals.pop()
    return ",".join(f"{a}={pols[a]}" for a in live)


_LEGACY_POLICY_NOTICED = False


def notice_legacy_single_policy(value: str) -> None:
    """One-time deprecation-style notice for a bare (single-value)
    QUDA_TPU_SHARDED_POLICY: the legacy form maps onto EVERY
    partitioned mesh axis (x keeps xla_facefix under fused_halo); the
    per-axis spec is the replacement."""
    global _LEGACY_POLICY_NOTICED
    if _LEGACY_POLICY_NOTICED:
        return
    _LEGACY_POLICY_NOTICED = True
    from ..utils import logging as qlog
    qlog.printq(
        f"QUDA_TPU_SHARDED_POLICY={value}: the single-value form maps "
        "onto every partitioned mesh axis (x keeps xla_facefix under "
        "fused_halo); prefer the per-axis spec, e.g. "
        "QUDA_TPU_SHARDED_POLICY=t=fused_halo,z=fused_halo,y=xla_facefix",
        qlog.SUMMARIZE)


def _exchange_xla(send_down, send_up, name, n):
    return (_nbr(send_down, name, towards_lower=True, n=n),
            _nbr(send_up, name, towards_lower=False, n=n))


def _make_exchange(policy, mesh, interpret: bool):
    """Per-axis halo-transport dispatch: ``policy`` is anything
    ``resolve_axis_policies`` accepts; the returned
    ``exchange(send_down, send_up, name, n)`` routes each partitioned
    direction through its own policy."""
    pols = resolve_axis_policies(policy)
    if "fused_halo" not in pols.values():
        return _exchange_xla
    from .pallas_halo import slab_exchange_bidir
    mesh_axes = tuple(mesh.axis_names)

    def exchange(send_down, send_up, name, n):
        if pols.get(name) == "fused_halo":
            return slab_exchange_bidir(send_down, send_up, name,
                                       mesh_axes, interpret=interpret)
        return _exchange_xla(send_down, send_up, name, n)
    return exchange


# -- reconstruct-12 face slabs ----------------------------------------------

def _full_rows(u_slab, row2_sign=None):
    """Full 3x3 link slab from a face slab of either storage: row extent
    3 passes through; extent 2 (reconstruct-12, see
    wilson_pallas_packed.to_recon12) rebuilds row 2 = conj(row0 x row1)
    in f32 — O(surface) XLA work, the exterior analog of the in-kernel
    reconstruction.  ``row2_sign`` re-applies the folded antiperiodic-t
    phase (a +-1 scalar/plane; the two -1s of V = -U cancel in the cross
    product, so the boundary-plane row must be re-negated)."""
    if u_slab.shape[0] == 3:
        return u_slab
    u = u_slab.astype(jnp.float32)
    r0, r1 = u[0], u[1]                     # (3, 2, ...) each
    rows2 = []
    for b in range(3):
        b1, b2 = (b + 1) % 3, (b + 2) % 3
        re = ((r0[b1, 0] * r1[b2, 0] - r0[b1, 1] * r1[b2, 1])
              - (r0[b2, 0] * r1[b1, 0] - r0[b2, 1] * r1[b1, 1]))
        im = ((r0[b1, 0] * r1[b2, 1] + r0[b1, 1] * r1[b2, 0])
              - (r0[b2, 0] * r1[b1, 1] + r0[b2, 1] * r1[b1, 0]))
        re, im = re, -im                    # conjugate the cross product
        if row2_sign is not None:
            re, im = re * row2_sign, im * row2_sign
        rows2.append(jnp.stack([re, im]))
    return jnp.concatenate([u, jnp.stack(rows2)[None]], axis=0)


def _face_links(u_mu_slab, edge_sign):
    """(true, kernel) full-row slabs for one face: ``true`` carries the
    physically correct reconstructed row (edge_sign applied on the
    global-boundary shard), ``kernel`` reproduces the interior kernel's
    convention — the sharded wrappers run the in-kernel reconstruction
    UNSIGNED along a partitioned t axis (interior tb_sign=False), so the
    wrong-wrap term being subtracted must be rebuilt the same way."""
    true = _full_rows(u_mu_slab, edge_sign)
    if u_mu_slab.shape[0] == 3 or edge_sign is None:
        return true, true
    return true, _full_rows(u_mu_slab, None)


def _t_edge_signs(axis_idx_name: str, n: int, mu: int, R: int,
                  tb_sign: bool):
    """(sign_hi, sign_lo) for the reconstruct-12 t-boundary row on the
    two faces of a partitioned direction: the HIGH face of the last
    shard holds the global t = T-1 link plane; the pre-shifted backward
    LOW face of shard 0 holds the same plane.  None everywhere except
    recon-12 t-links with a folded boundary."""
    if mu != 3 or R == 3 or not tb_sign:
        return None, None
    idx = lax.axis_index(axis_idx_name)
    one = jnp.float32(1.0)
    sign_hi = jnp.where(idx == n - 1, -one, one)
    sign_lo = jnp.where(idx == 0, -one, one)
    return sign_hi, sign_lo


def _wilson_fix_faces_v2(out, links_fwd, links_bwd_sh, psi_pl, fio,
                         name, n, mu, exchange, sign_hi=None,
                         sign_lo=None):
    """Both face fixes for one partitioned direction, v2 gather-form
    conventions (pre-shifted backward links resident per shard):

    * forward hop, HIGH face: psi(x+mu) from the next shard's first
      face against ``links_fwd`` (local forward links — already
      correct);
    * backward hop, LOW face: ``links_bwd_sh`` is the LOCAL block of the
      GLOBALLY pre-shifted backward gauge, so its low face already holds
      the correct cross-shard link U_mu(x-mu) — only psi(x-mu) must come
      from the previous shard's last face.

    ``fio`` owns the face geometry (t/z slab, y row strip, x column
    gather — hop-to-face alignment is 1:1 for all of them on the full
    lattice and for t/z/y checkerboarded); both halos ride ONE
    ``exchange`` call (the policy seam)."""
    lo_first = fio.face(psi_pl, lo=True)
    hi_last = fio.face(psi_pl, lo=False)
    halo_hi, halo_lo = exchange(lo_first, hi_last, name, n)

    u_hi_true, u_hi_kern = _face_links(fio.face(links_fwd[mu], lo=False),
                                       sign_hi)
    tf = TABLES[(mu, +1)]
    corr_hi = (_hop_term(halo_hi, u_hi_true, tf, False)
               - _hop_term(lo_first, u_hi_kern, tf, False))
    out = fio.add(out, corr_hi, lo=False)

    u_lo_true, u_lo_kern = _face_links(fio.face(links_bwd_sh[mu],
                                                lo=True), sign_lo)
    tb = TABLES[(mu, -1)]
    corr_lo = (_hop_term(halo_lo, u_lo_true, tb, True)
               - _hop_term(hi_last, u_lo_kern, tb, True))
    return fio.add(out, corr_lo, lo=True)


def _check_sharded_mesh(name: str, psi_pl, X: int, mesh):
    """Shared guards of the full-lattice sharded policies: the x mesh
    axis must split X evenly and the local fused extent must be whole
    rows of the LOCAL row width (block-contiguous layout —
    parallel/mesh.fuse_block_layout).  Reconstruct-12 row extent 2 is
    accepted: the face fixes rebuild full rows on the O(surface) faces
    (_full_rows).  Returns ((n_t, n_z, n_y, n_x), x_loc)."""
    counts = _mesh_counts(mesh)
    n_x = counts[3]
    if X % n_x:
        raise ValueError(f"{name}: X={X} must divide evenly over the x "
                         f"mesh axis ({n_x})")
    x_loc = X // n_x
    if psi_pl.shape[-1] % x_loc:
        raise ValueError(
            f"{name}: local fused extent {psi_pl.shape[-1]} is not a "
            f"whole number of local rows of width {x_loc} (x-partitioned "
            "arrays must be block-contiguous — see "
            "parallel/mesh.fuse_block_layout)")
    return counts, x_loc


def dslash_pallas_sharded(gauge_pl, gauge_bw_pl, psi_pl, X: int, mesh,
                          interpret: bool = False, tb_sign: bool = True,
                          policy="xla_facefix"):
    """Wilson hop sum on per-shard local packed pair blocks — call
    INSIDE shard_map over ``mesh``; the t/z mesh axes partition the T/Z
    array axes and the y/x mesh axes partition the fused Y*X axis
    (block-contiguous rows — relayout x-partitioned global arrays with
    parallel/mesh.fuse_block_layout first).

    gauge_bw_pl is the LOCAL block of the pre-shifted backward gauge of
    the GLOBAL field (compute wilson_pallas_packed.backward_gauge on
    the global array before sharding — its shifts then already carry
    the cross-shard links along EVERY direction, and only psi halos
    plus the wrong local wraps remain to fix).  Row extent 2 selects
    reconstruct-12 (in-kernel interior + _full_rows face slabs);
    ``policy`` selects the halo transport per axis
    (resolve_axis_policies / SHARDED_POLICIES).  ``X`` is the GLOBAL x
    extent; the interior kernel runs on the local row width X//n_x.
    """
    from ..ops.wilson_pallas_packed import dslash_pallas_packed

    counts, x_loc = _check_sharded_mesh("dslash_pallas_sharded", psi_pl,
                                        X, mesh)
    n_t = counts[0]
    R = gauge_pl.shape[1]
    pols = resolve_axis_policies(policy)
    exchange = _make_exchange(pols, mesh, interpret)

    # interior pass: periodic single-chip kernel on the local block.
    # gauge_bw is exact even on the boundary (pre-shifted globally);
    # only psi wraps are wrong on the faces.  Along a partitioned t the
    # interior reconstruct-12 runs UNSIGNED (its local boundary plane is
    # not the global one); the face fixes re-apply the true edge sign.
    out = dslash_pallas_packed(gauge_pl, psi_pl, x_loc,
                               gauge_bw=gauge_bw_pl, interpret=interpret,
                               tb_sign=tb_sign and n_t == 1)

    plan = _axis_plan(counts, x_loc)
    live = [nm for _, nm, nn, _ in plan if nn > 1]
    from ..obs import comms as ocomms
    with ocomms.scope("wilson_sharded_v2", _policy_label(pols, live),
                      mesh_axes=counts):
        for fio, name, n, mu in plan:
            if n == 1:
                continue                  # periodic wrap is correct
            sign_hi, sign_lo = _t_edge_signs(name, n, mu, R, tb_sign)
            out = _wilson_fix_faces_v2(out, gauge_pl, gauge_bw_pl,
                                       psi_pl, fio, name, n, mu,
                                       exchange, sign_hi, sign_lo)
    return out


def _stag_term(u_slab, psi_slab, adjoint: bool):
    """Staggered color multiply on a boundary slab: (3,3,2,slab...) x
    (3,2,slab...) -> (3,2,slab...) f32 (no spin algebra)."""
    from ..ops.staggered_packed import (_color_planes, _mat_vec_pairs,
                                        _u_planes)
    out = _mat_vec_pairs(_u_planes(u_slab), _color_planes(psi_slab),
                         adjoint)
    return jnp.stack([jnp.stack([re, im]) for re, im in out])


def _stag_fix_faces(out, links_fwd, links_bwd, psi_pl, nhop: int, fio,
                    name, n, mu, exchange=_exchange_xla):
    """Fat (nhop=1) or Naik (nhop=3) face fixes for one partitioned
    direction, scatter-form conventions (the v3 two-pass kernels: their
    backward hops wrap the locally-computed product):

    * forward hop, HIGH face: psi(x + nhop*mu) must come from the next
      shard's first nhop planes/rows/columns (the kernel wrapped the
      local ones); hop-to-face alignment is 1:1 within the face;
    * backward hop, LOW face: the kernel wrapped the locally-computed
      product U^dag psi of the LAST nhop planes; permute the product
      face itself (linear in the face) — no link exchange.

    Both transfers ride ONE ``exchange`` call per hop set (the
    QUDA_TPU_SHARDED_POLICY seam — the psi face and the product face
    have identical shapes, so the fused-RDMA bidirectional kernel
    serves them on any contiguous-strip axis).

    ``links_fwd``/``links_bwd``: the link arrays each hop reads — the
    same full-lattice array, or (checkerboarded) the target-parity and
    opposite-parity link arrays respectively."""
    lo_first = fio.face(psi_pl, lo=True, n=nhop)
    prod = _stag_term(fio.face(links_bwd[mu], lo=False, n=nhop),
                      fio.face(psi_pl, lo=False, n=nhop), True)
    halo_hi, prod_in = exchange(lo_first, prod, name, n)

    u_hi = fio.face(links_fwd[mu], lo=False, n=nhop)
    corr_hi = 0.5 * (_stag_term(u_hi, halo_hi, False)
                     - _stag_term(u_hi, lo_first, False))
    out = fio.add(out, corr_hi, lo=False, n=nhop)

    corr_lo = -0.5 * (prod_in - prod)
    return fio.add(out, corr_lo, lo=True, n=nhop)


def _stag_fix_faces_v2(out, links_fwd, links_bwd_sh, psi_pl, nhop: int,
                       fio, name, n, mu, exchange=_exchange_xla):
    """Fat (nhop=1) or Naik (nhop=3) face fixes for one partitioned
    direction, v2 GATHER-form conventions — the staggered analog of
    ``_wilson_fix_faces_v2`` (round-8 tentpole ported to the second
    headline family):

    * forward hop, HIGH face: psi(x + nhop*mu) from the next shard's
      first nhop planes/rows/columns against ``links_fwd`` (local
      forward links — already correct);
    * backward hop, LOW face: ``links_bwd_sh`` is the LOCAL block of
      the GLOBALLY pre-shifted backward links
      (ops/staggered_pallas.backward_links / backward_links_eo computed
      on the global field BEFORE sharding), so its low face already
      holds the correct cross-shard U_mu(x - nhop*mu) — only
      psi(x - nhop*mu) must come from the previous shard's last nhop
      planes.

    Both psi faces ride ONE ``exchange`` call per hop set (the policy
    seam); the Naik hop set exchanges 3-deep faces."""
    lo_first = fio.face(psi_pl, lo=True, n=nhop)
    hi_last = fio.face(psi_pl, lo=False, n=nhop)
    halo_hi, halo_lo = exchange(lo_first, hi_last, name, n)

    u_hi = fio.face(links_fwd[mu], lo=False, n=nhop)
    corr_hi = 0.5 * (_stag_term(u_hi, halo_hi, False)
                     - _stag_term(u_hi, lo_first, False))
    out = fio.add(out, corr_hi, lo=False, n=nhop)

    u_lo = fio.face(links_bwd_sh[mu], lo=True, n=nhop)
    corr_lo = -0.5 * (_stag_term(u_lo, halo_lo, True)
                      - _stag_term(u_lo, hi_last, True))
    return fio.add(out, corr_lo, lo=True, n=nhop)


def _check_stag_mesh(name: str, mesh, psi_pl, X: int, with_long: bool):
    """Shared mesh/extent guards of the full-lattice sharded staggered
    policies: block-contiguous x split plus, under Naik, local extent
    >= 3 on every partitioned direction (the 3-hop face fix assumes the
    hop crosses at most one shard boundary)."""
    counts, x_loc = _check_sharded_mesh(name, psi_pl, X, mesh)
    if with_long:
        y_loc = psi_pl.shape[-1] // x_loc
        exts = (psi_pl.shape[-3], psi_pl.shape[-2], y_loc, x_loc)
        for nn, ext in zip(counts, exts):
            if nn > 1 and ext < 3:
                raise ValueError(
                    "local extent < 3 on a partitioned axis: the Naik "
                    "slab fix needs the 3-hop to cross at most one "
                    "shard boundary")
    return counts, x_loc


def dslash_staggered_pallas_sharded_v3(fat_pl, psi_pl, X: int, mesh,
                                       long_pl=None,
                                       interpret: bool = False,
                                       policy="xla_facefix"):
    """Staggered / improved-staggered D psi on per-shard local packed
    pair blocks — call INSIDE shard_map over ``mesh`` (t/z mesh axes
    partition T/Z; y/x mesh axes partition the fused Y*X axis,
    block-contiguous).  The interior runs the single-chip v3
    scatter-form kernel (ops/staggered_pallas); the Naik term's 3-hop
    boundary is three planes/rows/columns per face, fixed with ONE
    3-deep exchange per direction-sign (reference: the nFace=3
    staggered policies of lib/dslash_policy.hpp:365 applied to
    include/kernels/dslash_staggered.cuh).  ``policy`` selects the halo
    transport per axis (resolve_axis_policies — QUDA_TPU_SHARDED_POLICY
    covers staggered through the same seam as Wilson).

    Requires local extent >= 3 on every partitioned direction when
    ``long_pl`` is given (the face fix assumes the 3-hop crosses at
    most one shard boundary).  ``X`` is the GLOBAL x extent.
    """
    from ..ops.staggered_pallas import dslash_staggered_pallas_v3

    counts, x_loc = _check_stag_mesh("dslash_staggered_pallas_sharded_v3",
                                     mesh, psi_pl, X,
                                     long_pl is not None)
    pols = resolve_axis_policies(policy)
    exchange = _make_exchange(pols, mesh, interpret)

    out = dslash_staggered_pallas_v3(fat_pl, psi_pl, x_loc,
                                     long_pl=long_pl,
                                     interpret=interpret)

    plan = _axis_plan(counts, x_loc)
    live = [nm for _, nm, nn, _ in plan if nn > 1]
    from ..obs import comms as ocomms
    with ocomms.scope("staggered_sharded_v3", _policy_label(pols, live),
                      mesh_axes=counts):
        for fio, name, n, mu in plan:
            if n == 1:
                continue
            out = _stag_fix_faces(out, fat_pl, fat_pl, psi_pl, 1, fio,
                                  name, n, mu, exchange)
            if long_pl is not None:
                out = _stag_fix_faces(out, long_pl, long_pl, psi_pl, 3,
                                      fio, name, n, mu, exchange)
    return out


def dslash_staggered_pallas_sharded(fat_pl, fat_bw_pl, psi_pl, X: int,
                                    mesh, long_pl=None, long_bw_pl=None,
                                    interpret: bool = False,
                                    policy="xla_facefix"):
    """Staggered / improved-staggered D psi under shard_map on the v2
    GATHER kernel form — the measured single-chip staggered default
    brought to the mesh (the round-8 Wilson move applied to the second
    headline family), all four directions partitionable.

    ``fat_bw_pl``/``long_bw_pl`` are the LOCAL blocks of the GLOBALLY
    pre-shifted backward links (ops/staggered_pallas.backward_links on
    the global arrays BEFORE sharding — their shifts then already carry
    the cross-shard links along EVERY direction, including the 3-hop
    Naik reach), so the exterior fixes exchange ONLY psi faces: a
    1-deep face per fat hop set and a 3-deep face per Naik hop set,
    each riding one ``exchange`` call (the QUDA_TPU_SHARDED_POLICY
    seam).  ``X`` is the GLOBAL x extent."""
    from ..ops.staggered_pallas import dslash_staggered_pallas

    counts, x_loc = _check_stag_mesh("dslash_staggered_pallas_sharded",
                                     mesh, psi_pl, X,
                                     long_pl is not None)
    pols = resolve_axis_policies(policy)
    exchange = _make_exchange(pols, mesh, interpret)

    out = dslash_staggered_pallas(fat_pl, fat_bw_pl, psi_pl, x_loc,
                                  long_pl=long_pl,
                                  long_bw_pl=long_bw_pl,
                                  interpret=interpret)

    plan = _axis_plan(counts, x_loc)
    live = [nm for _, nm, nn, _ in plan if nn > 1]
    from ..obs import comms as ocomms
    with ocomms.scope("staggered_sharded_v2", _policy_label(pols, live),
                      mesh_axes=counts):
        for fio, name, n, mu in plan:
            if n == 1:
                continue
            out = _stag_fix_faces_v2(out, fat_pl, fat_bw_pl, psi_pl, 1,
                                     fio, name, n, mu, exchange)
            if long_pl is not None:
                out = _stag_fix_faces_v2(out, long_pl, long_bw_pl,
                                         psi_pl, 3, fio, name, n, mu,
                                         exchange)
    return out


# -- checkerboarded wrappers ------------------------------------------------

def _check_eo_mesh(name: str, mesh, psi_pl, dims, with_long: bool,
                   tz_only: bool = False):
    """Shared guards of the checkerboarded sharded policies:

    * partitioned t/z/y axes need EVEN local extents (the in-kernel
      parity masks use local coordinates, so shard offsets must not
      flip the site parity; the x mesh axis splits xh SLOTS, which
      never enter the parity, so it carries no evenness rule);
    * the x mesh axis must divide Xh = X//2 evenly (block-contiguous
      layout — parallel/mesh.fuse_block_layout with the HALF row
      width);
    * Naik (with_long) needs local extent >= 3 on partitioned t/z/y
      and local Xh >= 2 on a partitioned x (the 3-hop crosses at most
      one shard boundary; the eo x window is (nhop+1)//2 = 2 columns).

    Returns ((n_t, n_z, n_y, n_x), dims_local, xh_loc)."""
    counts = _mesh_counts(mesh)
    n_t, n_z, n_y, n_x = counts
    if tz_only and (n_y != 1 or n_x != 1):
        raise ValueError(f"{name} shards t/z only (y/x mesh axes must "
                         "be 1)")
    T, Z, Y, X = dims
    Xh = X // 2
    if Y % n_y or Xh % n_x:
        raise ValueError(
            f"{name}: Y={Y} / Xh={Xh} must divide evenly over the y/x "
            f"mesh axes ({n_y}/{n_x})")
    y_loc, xh_loc = Y // n_y, Xh // n_x
    t_loc, z_loc = int(psi_pl.shape[-3]), int(psi_pl.shape[-2])
    if psi_pl.shape[-1] != y_loc * xh_loc:
        raise ValueError(
            f"{name}: local fused extent {psi_pl.shape[-1]} != local "
            f"Y*Xh = {y_loc}*{xh_loc} (x-partitioned arrays must be "
            "block-contiguous — see parallel/mesh.fuse_block_layout)")
    for nn, ext, nm in ((n_t, t_loc, "T"), (n_z, z_loc, "Z"),
                        (n_y, y_loc, "Y")):
        if nn > 1 and ext % 2 != 0:
            raise ValueError(
                f"local {nm} extent {ext} must be even on a partitioned "
                f"axis (the checkerboard masks use local coordinates)")
        if nn > 1 and with_long and ext < 3:
            raise ValueError(
                "local extent < 3 on a partitioned axis: the Naik slab "
                "fix needs the 3-hop to cross at most one shard "
                "boundary")
    if n_x > 1 and with_long and xh_loc < 2:
        raise ValueError(
            "local Xh extent < 2 on a partitioned x axis: the Naik "
            "column fix needs the 3-hop to cross at most one shard "
            "boundary")
    dims_local = (t_loc, z_loc, y_loc, 2 * xh_loc)
    return counts, dims_local, xh_loc


@lru_cache(maxsize=None)
def _eo_r0_mask(T: int, Z: int, Y: int, parity: int):
    """(T, Z, Y, 1) numpy bool over LOCAL coordinates: True where the
    parity-p half-site occupies the even x slot (x = 2*xh + r with
    r = (t+z+y+p) % 2 == 0) — the unfused-view version of
    wilson_packed._slot_mask_packed, broadcast over the column window.
    Valid locally because partitioned t/z/y have even local extents."""
    t = np.arange(T)[:, None, None]
    z = np.arange(Z)[None, :, None]
    y = np.arange(Y)[None, None, :]
    return (((t + z + y + parity) % 2) == 0)[..., None]


def _eo_x_psi_sources(psi_pl, xh_loc: int, exchange, name, n, w: int,
                      r0):
    """True/kernel psi source column stacks for the checkerboarded
    x-direction fixes.

    The eo x hop is a SLOT-SELECT, not a roll: a target half-site at
    slot xh with slot parity r = (t+z+y+p)%2 reads slot xh + k + r
    forward and xh + r - (k+1) backward, k = (nhop-1)//2
    (ops/wilson_packed.shift_eo_packed).  With the fused axis split
    into rows of width ``xh_loc``, only the last/first w = k+1 columns
    can reach across the shard boundary — build, per boundary window,
    the TRUE source (local edge columns extended by the neighbour halo)
    and the KERNEL source (local edge columns extended by the local
    same-row wrap), selecting the (k + r)-th window of each extension
    per site.  Sites whose hop stays local select identical columns in
    both stacks, so their correction cancels exactly.

    Returns (hi_true, hi_kern, lo_true, lo_kern), each shaped
    (..., Y_loc, w) in the unfused view; the two halo column stacks
    ride ONE ``exchange`` call (the policy seam — x is always
    xla_facefix, see FUSED_HALO_AXES)."""
    uf = psi_pl.reshape(psi_pl.shape[:-1]
                        + (psi_pl.shape[-1] // xh_loc, xh_loc))
    first = lax.slice_in_dim(uf, 0, w, axis=-1)
    last = lax.slice_in_dim(uf, xh_loc - w, xh_loc, axis=-1)
    halo_hi, halo_lo = exchange(first, last, name, n)
    k = w - 1

    def sel_hi(ext):
        return jnp.where(r0, lax.slice_in_dim(ext, k, k + w, axis=-1),
                         lax.slice_in_dim(ext, k + 1, k + w + 1,
                                          axis=-1))

    def sel_lo(ext):
        return jnp.where(r0, lax.slice_in_dim(ext, 0, w, axis=-1),
                         lax.slice_in_dim(ext, 1, w + 1, axis=-1))

    hi_true = sel_hi(jnp.concatenate([last, halo_hi], axis=-1))
    hi_kern = sel_hi(jnp.concatenate([last, first], axis=-1))
    lo_true = sel_lo(jnp.concatenate([halo_lo, first], axis=-1))
    lo_kern = sel_lo(jnp.concatenate([last, first], axis=-1))
    return hi_true, hi_kern, lo_true, lo_kern


def _wilson_eo_fix_x(out, u_here_pl, u_bw_pl, psi_pl, fio, name, n,
                     exchange, dims_local, target_parity: int):
    """Checkerboarded x-direction fixes, v2 gather form: unlike t/z/y
    the halo column a target needs depends on its slot parity
    (_eo_x_psi_sources), but the hop algebra is the usual
    subtract-wrong/add-true pair against the local forward links (HIGH
    window) and the globally pre-shifted backward links (LOW window).
    Window w=1: the Wilson hop reaches at most one column across the
    boundary.  x never carries the folded antiperiodic-t sign, so the
    reconstruct-12 faces rebuild unsigned."""
    w = 1
    r0 = jnp.asarray(_eo_r0_mask(dims_local[0], dims_local[1],
                                 dims_local[2], target_parity))
    hi_true, hi_kern, lo_true, lo_kern = _eo_x_psi_sources(
        psi_pl, fio.xcols, exchange, name, n, w, r0)

    u_hi = _full_rows(fio.face(u_here_pl[0], lo=False, n=w))
    tf = TABLES[(0, +1)]
    corr_hi = (_hop_term(hi_true, u_hi, tf, False)
               - _hop_term(hi_kern, u_hi, tf, False))
    out = fio.add(out, corr_hi, lo=False, n=w)

    u_lo = _full_rows(fio.face(u_bw_pl[0], lo=True, n=w))
    tb = TABLES[(0, -1)]
    corr_lo = (_hop_term(lo_true, u_lo, tb, True)
               - _hop_term(lo_kern, u_lo, tb, True))
    return fio.add(out, corr_lo, lo=True, n=w)


def _stag_eo_fix_x(out, links_fwd, links_bwd_sh, psi_pl, nhop: int,
                   fio, name, n, exchange, r0):
    """Checkerboarded staggered x-direction fixes, v2 gather form — the
    slot-select analog of ``_stag_fix_faces_v2`` (window
    w = (nhop+1)//2: 1 column for the fat hop, 2 for Naik; the odd-hop
    slot algebra is shared with Wilson via _eo_x_psi_sources)."""
    w = (nhop + 1) // 2
    hi_true, hi_kern, lo_true, lo_kern = _eo_x_psi_sources(
        psi_pl, fio.xcols, exchange, name, n, w, r0)

    u_hi = fio.face(links_fwd[0], lo=False, n=w)
    corr_hi = 0.5 * (_stag_term(u_hi, hi_true, False)
                     - _stag_term(u_hi, hi_kern, False))
    out = fio.add(out, corr_hi, lo=False, n=w)

    u_lo = fio.face(links_bwd_sh[0], lo=True, n=w)
    corr_lo = -0.5 * (_stag_term(u_lo, lo_true, True)
                      - _stag_term(u_lo, lo_kern, True))
    return fio.add(out, corr_lo, lo=True, n=w)


def dslash_staggered_eo_pallas_sharded_v3(fat_here_pl, fat_there_pl,
                                          psi_pl, dims,
                                          target_parity: int, mesh,
                                          long_here_pl=None,
                                          long_there_pl=None,
                                          interpret: bool = False,
                                          policy="xla_facefix"):
    """Checkerboarded staggered hop under shard_map, v3 scatter form —
    t/z mesh axes only (the scatter-form exterior permutes products,
    which have no slot-select column fix; the v2 gather form below is
    the all-axes production path and what the models pin under a mesh).

    Interior eo v3 kernel + slab face fixes, with forward hops reading
    the target-parity links and the backward product built from the
    opposite-parity links (both already resident per shard; only psi
    slabs and product slabs ride the ``exchange`` policy seam).
    ``dims`` are the GLOBAL (T, Z, Y, X); partitioned axes must have
    EVEN local extents (the in-kernel x-slot parity masks use local
    coordinates).
    """
    from ..ops.staggered_pallas import dslash_staggered_eo_pallas_v3

    counts, dims_local, xh_loc = _check_eo_mesh(
        "dslash_staggered_eo_pallas_sharded_v3", mesh, psi_pl, dims,
        long_here_pl is not None, tz_only=True)
    pols = resolve_axis_policies(policy)
    exchange = _make_exchange(pols, mesh, interpret)

    out = dslash_staggered_eo_pallas_v3(
        fat_here_pl, fat_there_pl, psi_pl, dims_local, target_parity,
        long_here_pl=long_here_pl, long_there_pl=long_there_pl,
        interpret=interpret)

    plan = _axis_plan(counts, xh_loc)
    live = [nm for _, nm, nn, _ in plan if nn > 1]
    from ..obs import comms as ocomms
    with ocomms.scope(f"staggered_eo_sharded_v3:p{target_parity}",
                      _policy_label(pols, live), mesh_axes=counts):
        for fio, name, n, mu in plan:
            if n == 1:
                continue
            out = _stag_fix_faces(out, fat_here_pl, fat_there_pl,
                                  psi_pl, 1, fio, name, n, mu,
                                  exchange)
            if long_here_pl is not None:
                out = _stag_fix_faces(out, long_here_pl, long_there_pl,
                                      psi_pl, 3, fio, name, n, mu,
                                      exchange)
    return out


def dslash_staggered_eo_pallas_sharded(fat_here_pl, fat_bw_pl, psi_pl,
                                       dims, target_parity: int, mesh,
                                       long_here_pl=None,
                                       long_bw_pl=None,
                                       interpret: bool = False,
                                       policy="xla_facefix"):
    """Checkerboarded staggered / improved-staggered hop under shard_map
    on the v2 GATHER kernel form — the staggered CG hot path on the
    mesh, all four directions partitionable (reference: the nFace=3
    staggered policies of lib/dslash_policy.hpp:365 over
    include/kernels/dslash_staggered.cuh).

    ``fat_bw_pl``/``long_bw_pl`` are the LOCAL blocks of the GLOBALLY
    pre-shifted backward links (ops/staggered_pallas.backward_links_eo
    on the global eo arrays BEFORE sharding — their shifts already
    carry the cross-shard links along EVERY direction, including the
    3-hop Naik reach), so the exterior fixes exchange ONLY psi faces.
    t/z/y hops keep the checkerboarded x-slot layout (y is a pure
    fused-axis roll for odd hop counts), so the full-lattice face
    alignment carries over; the x direction is a slot-select and gets
    its own column fix (_stag_eo_fix_x).  ``dims`` are the GLOBAL
    (T, Z, Y, X); extent rules per _check_eo_mesh (even local t/z/y,
    >= 3 under Naik, Xh divisible by the x mesh axis)."""
    from ..ops.staggered_pallas import dslash_staggered_eo_pallas

    counts, dims_local, xh_loc = _check_eo_mesh(
        "dslash_staggered_eo_pallas_sharded", mesh, psi_pl, dims,
        long_here_pl is not None)
    pols = resolve_axis_policies(policy)
    exchange = _make_exchange(pols, mesh, interpret)

    out = dslash_staggered_eo_pallas(
        fat_here_pl, fat_bw_pl, psi_pl, dims_local, target_parity,
        long_here_pl=long_here_pl, long_bw_pl=long_bw_pl,
        interpret=interpret)

    plan = _axis_plan(counts, xh_loc)
    live = [nm for _, nm, nn, _ in plan if nn > 1]
    from ..obs import comms as ocomms
    with ocomms.scope(f"staggered_eo_sharded_v2:p{target_parity}",
                      _policy_label(pols, live), mesh_axes=counts):
        for fio, name, n, mu in plan:
            if n == 1:
                continue
            if name == "x":
                r0 = jnp.asarray(_eo_r0_mask(dims_local[0],
                                             dims_local[1],
                                             dims_local[2],
                                             target_parity))
                out = _stag_eo_fix_x(out, fat_here_pl, fat_bw_pl,
                                     psi_pl, 1, fio, name, n, exchange,
                                     r0)
                if long_here_pl is not None:
                    out = _stag_eo_fix_x(out, long_here_pl, long_bw_pl,
                                         psi_pl, 3, fio, name, n,
                                         exchange, r0)
                continue
            out = _stag_fix_faces_v2(out, fat_here_pl, fat_bw_pl,
                                     psi_pl, 1, fio, name, n, mu,
                                     exchange)
            if long_here_pl is not None:
                out = _stag_fix_faces_v2(out, long_here_pl, long_bw_pl,
                                         psi_pl, 3, fio, name, n, mu,
                                         exchange)
    return out


def dslash_eo_pallas_sharded(u_here_pl, u_bw_pl, psi_pl, dims,
                             target_parity: int, mesh,
                             interpret: bool = False,
                             out_dtype=None, tb_sign: bool = True,
                             policy="xla_facefix"):
    """Checkerboarded Wilson hop under shard_map on the v2 (gather)
    kernel — the interior every cell runs — driving the multi-chip CG
    hot loop, all four directions partitionable (reference:
    lib/dslash_policy.hpp:365-560; full 4-d decomposition with
    per-dimension policies is QUDA's production story).

    Interior: ops/wilson_pallas_packed.dslash_eo_pallas_packed on the
    LOCAL block.  ``u_bw_pl`` is the LOCAL block of the GLOBALLY
    pre-shifted backward links (backward_gauge_eo on the global arrays
    BEFORE sharding): its shifts already carry the cross-shard links
    along EVERY direction, so the exterior fixes exchange ONLY psi
    faces, each pair riding one ``exchange`` per direction (the policy
    seam; per-axis via resolve_axis_policies).

    Row extent 2 on the link arrays selects reconstruct-12 (interior
    in-kernel + _full_rows face slabs with shard-edge t signs).  t/z/y
    hops keep the checkerboarded x-slot layout (y is a pure fused-axis
    roll), so the full-lattice face alignment carries over; the x
    direction is a slot-select and gets its own column fix
    (_wilson_eo_fix_x).  Partitioned t/z/y need EVEN local extents; the
    x mesh axis splits Xh slots block-contiguously
    (parallel/mesh.fuse_block_layout).  ``dims`` is the GLOBAL
    (T, Z, Y, X).
    """
    from ..ops.wilson_pallas_packed import dslash_eo_pallas_packed

    counts, dims_local, xh_loc = _check_eo_mesh(
        "dslash_eo_pallas_sharded", mesh, psi_pl, dims, False)
    n_t = counts[0]
    R = u_here_pl.shape[1]
    pols = resolve_axis_policies(policy)
    exchange = _make_exchange(pols, mesh, interpret)

    out = dslash_eo_pallas_packed(
        u_here_pl, u_bw_pl, psi_pl, dims_local, target_parity,
        interpret=interpret, out_dtype=out_dtype,
        tb_sign=tb_sign and n_t == 1)

    plan = _axis_plan(counts, xh_loc)
    live = [nm for _, nm, nn, _ in plan if nn > 1]
    from ..obs import comms as ocomms
    with ocomms.scope(f"wilson_eo_sharded_v2:p{target_parity}",
                      _policy_label(pols, live), mesh_axes=counts):
        for fio, name, n, mu in plan:
            if n == 1:
                continue
            if name == "x":
                out = _wilson_eo_fix_x(out, u_here_pl, u_bw_pl, psi_pl,
                                       fio, name, n, exchange,
                                       dims_local, target_parity)
                continue
            sign_hi, sign_lo = _t_edge_signs(name, n, mu, R, tb_sign)
            out = _wilson_fix_faces_v2(out, u_here_pl, u_bw_pl, psi_pl,
                                       fio, name, n, mu, exchange,
                                       sign_hi, sign_lo)
    return out
