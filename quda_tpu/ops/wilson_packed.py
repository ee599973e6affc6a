"""Wilson dslash on the TPU-native packed field order.

The canonical layout (T,Z,Y,X,4,3) is the HOST order (QUDA's QDP-like
order).  On TPU, XLA tiles the two minormost axes to (sublane, lane) =
(8, 128) for f32 — so trailing (4, 3) dof axes waste ~97% of every vector
lane and inflate HBM traffic by the same factor.  This module is the
analog of QUDA's *native* device orders (FloatN, include/gauge_field_order.h,
include/color_spinor_field_order.h): a layout chosen for the hardware plus
pack/unpack conversions at the boundary.

Packed order:
    spinor  (4, 3, T, Z, Y*X)    complex
    gauge   (4, 3, 3, T, Z, Y*X) complex   [direction, row, col, ...]

so the minor-two axes are (Z, Y*X): Z is a multiple of 8 for any even
lattice, Y*X is within 11% of a 128 multiple at 24^4 and exact at 16^4 —
near-full lane utilisation, and every spin/color component is its own
(T,Z,YX) plane so the stencil algebra is pure elementwise VPU work.

Shifts on the packed layout:
  t, z : jnp.roll on their own axes.
  y    : roll by X on the fused Y*X axis — EXACT including the periodic
         wrap, because (y*X + x ± X) mod (Y*X) is the correct neighbour
         index for every site.
  x    : roll by 1 is correct except at the x-boundary column; a second
         roll by (1-X) and a lane mask select fix the wrap (branch-free,
         same trick as ops/shift.py's checkerboard masks).

The spin algebra uses the projection tables ``TABLES`` below (project to
2 half-spinors, one 3x3 color multiply each, reconstruct) — 1320
flops/site, matching Dslash::flops() (include/dslash.h:475; kernel
reference include/kernels/dslash_wilson.cuh:84-162).  Every Wilson-type
kernel (the pallas kernels, the df64 stencil, the sharded face fixes)
reads the tables from here.
"""

from __future__ import annotations

from functools import lru_cache

import jax.numpy as jnp
import numpy as np

from .gamma import GAMMAS

# -- spin projection tables (derived, then trusted) ------------------------
# DERIVED from ops/gamma.py at import and asserted, not hand-copied: for
# each (mu, sign), P = 1 -+ gamma_mu has rank 2 with rows 2,3 proportional
# to rows 0,1.  Half-spinor h_a = psi_a + c_a * psi_{j_a} (a=0,1);
# reconstruction rows: out_2 = d_2 * h_{k_2}, out_3 = d_3 * h_{k_3}.


def _derive_tables():
    tables = {}
    for mu in range(4):
        for sign in (+1, -1):
            P = np.eye(4) - sign * np.asarray(GAMMAS[mu])
            entry = {}
            for a in (0, 1):
                row = P[a]
                assert row[a] == 1.0
                nz = [j for j in range(4) if j != a and abs(row[j]) > 1e-12]
                assert len(nz) == 1, (mu, sign, a, row)
                entry[f"j{a}"] = nz[0]
                entry[f"c{a}"] = complex(row[nz[0]])
            for b in (2, 3):
                row = P[b]
                # row b = d * row a for exactly one a in (0,1)
                found = False
                for a in (0, 1):
                    ra = P[a]
                    nz_b = np.nonzero(np.abs(row) > 1e-12)[0]
                    nz_a = np.nonzero(np.abs(ra) > 1e-12)[0]
                    if set(nz_b) == set(nz_a):
                        d = row[nz_b[0]] / ra[nz_b[0]]
                        assert np.allclose(row, d * ra), (mu, sign, b)
                        entry[f"k{b}"] = a
                        entry[f"d{b}"] = complex(d)
                        found = True
                        break
                assert found, (mu, sign, b)
            tables[(mu, sign)] = entry
    return tables


TABLES = _derive_tables()


# -- pack / unpack (host order <-> native order) ---------------------------

def pack_spinor(psi: jnp.ndarray) -> jnp.ndarray:
    """(T,Z,Y,X,4,3) -> (4,3,T,Z,Y*X)."""
    T, Z, Y, X = psi.shape[:4]
    return jnp.transpose(psi, (4, 5, 0, 1, 2, 3)).reshape(4, 3, T, Z, Y * X)


def unpack_spinor(pp: jnp.ndarray, lattice_shape) -> jnp.ndarray:
    T, Z, Y, X = lattice_shape
    return jnp.transpose(pp.reshape(4, 3, T, Z, Y, X), (2, 3, 4, 5, 0, 1))


def pack_gauge(gauge: jnp.ndarray) -> jnp.ndarray:
    """(4,T,Z,Y,X,3,3) -> (4,3,3,T,Z,Y*X)."""
    _, T, Z, Y, X = gauge.shape[:5]
    return jnp.transpose(gauge, (0, 5, 6, 1, 2, 3, 4)).reshape(
        4, 3, 3, T, Z, Y * X)


def unpack_gauge(gp: jnp.ndarray, lattice_shape) -> jnp.ndarray:
    T, Z, Y, X = lattice_shape
    return jnp.transpose(gp.reshape(4, 3, 3, T, Z, Y, X),
                         (0, 3, 4, 5, 6, 1, 2))


# -- packed shifts ----------------------------------------------------------

@lru_cache(maxsize=None)
def _x_wrap_masks(Y: int, X: int, nhop: int = 1):
    """Lane masks (numpy, see ops/shift.py tracer-cache note) marking the
    x-columns of the fused Y*X axis whose +nhop (resp. -nhop) neighbour
    wraps around the x extent."""
    x = np.arange(Y * X) % X
    return (x >= X - nhop), (x < nhop)


def shift_packed(arr: jnp.ndarray, mu: int, sign: int, X: int,
                 Y: int, nhop: int = 1) -> jnp.ndarray:
    """result[site] = arr[site + sign*nhop*mu_hat] on packed layout;
    lattice axes are the LAST three (T, Z, Y*X); mu = 0,1,2,3 = x,y,z,t."""
    if mu == 3:
        return jnp.roll(arr, -sign * nhop, axis=-3)
    if mu == 2:
        return jnp.roll(arr, -sign * nhop, axis=-2)
    if mu == 1:
        return jnp.roll(arr, -sign * nhop * X, axis=-1)
    # x-coordinate arithmetic is mod X, so an nhop shift equals an
    # (nhop % X) shift — this also keeps the 2-case wrap select valid
    # for nhop >= X (e.g. Naik on an X=2 lattice)
    nhop = nhop % X
    if nhop == 0:
        return arr
    last, first = _x_wrap_masks(Y, X, nhop)
    if sign > 0:
        interior = jnp.roll(arr, -nhop, axis=-1)
        wrapped = jnp.roll(arr, X - nhop, axis=-1)
        return jnp.where(jnp.asarray(last), wrapped, interior)
    interior = jnp.roll(arr, nhop, axis=-1)
    wrapped = jnp.roll(arr, -(X - nhop), axis=-1)
    return jnp.where(jnp.asarray(first), wrapped, interior)


# -- the stencil ------------------------------------------------------------

def _hop_packed(psi_s, u, table, adjoint: bool):
    """One direction: project -> 3x3 color multiply on 2 spins ->
    reconstruct.  psi_s: (4,3,T,Z,YX) shifted spinor; u: (3,3,T,Z,YX).
    Returns a length-4 list of (3,T,Z,YX) spin components (unrolled —
    every op is elementwise over the site planes)."""
    t = table
    # project to half spinor h[a][b_color]
    h = [psi_s[a] + t[f"c{a}"] * psi_s[t[f"j{a}"]] for a in (0, 1)]
    # color multiply (u or u^dag), unrolled 3x3
    uh = []
    for s in (0, 1):
        rows = []
        for a in range(3):
            if adjoint:
                acc = (jnp.conjugate(u[0, a]) * h[s][0]
                       + jnp.conjugate(u[1, a]) * h[s][1]
                       + jnp.conjugate(u[2, a]) * h[s][2])
            else:
                acc = (u[a, 0] * h[s][0] + u[a, 1] * h[s][1]
                       + u[a, 2] * h[s][2])
            rows.append(acc)
        uh.append(jnp.stack(rows))
    # reconstruct spins 2,3 from the half spinor
    return [uh[0], uh[1], t["d2"] * uh[t["k2"] ], t["d3"] * uh[t["k3"]]]


def dslash_packed(gauge_p: jnp.ndarray, psi_p: jnp.ndarray, X: int,
                  Y: int) -> jnp.ndarray:
    """Wilson hop sum D psi on packed arrays.

    gauge_p: (4,3,3,T,Z,Y*X) with boundary phases folded;
    psi_p: (4,3,T,Z,Y*X).  X, Y are static ints (the fused-axis split).
    """
    acc = None
    for mu in range(4):
        u = gauge_p[mu]
        # forward: (1 - gamma_mu) U_mu(x) psi(x+mu)
        fwd = _hop_packed(shift_packed(psi_p, mu, +1, X, Y), u,
                          TABLES[(mu, +1)], adjoint=False)
        # backward: (1 + gamma_mu) U_mu(x-mu)^dag psi(x-mu)
        ub = shift_packed(u, mu, -1, X, Y)
        bwd = _hop_packed(shift_packed(psi_p, mu, -1, X, Y), ub,
                          TABLES[(mu, -1)], adjoint=True)
        term = [f + b for f, b in zip(fwd, bwd)]
        acc = term if acc is None else [a + t for a, t in zip(acc, term)]
    return jnp.stack(acc)


def matvec_packed(gauge_p, psi_p, kappa: float, X: int, Y: int):
    """M psi = psi - kappa D psi on packed arrays."""
    return psi_p - kappa * dslash_packed(gauge_p, psi_p, X, Y)


# ---------------------------------------------------------------------------
# Checkerboarded (even/odd) packed stencil
# ---------------------------------------------------------------------------
#
# Half-lattice packed order: (4, 3, T, Z, Y*Xh) with Xh = X//2 and the
# same slot-parity convention as ops/shift.py: physical
# x = 2*xh + ((t+z+y+p) % 2).  The x-direction shift needs two masks:
# the slot-parity mask over (T, Z, Y*Xh) and the xh wrap columns.

def pack_spinor_eo(psi: jnp.ndarray) -> jnp.ndarray:
    """(T,Z,Y,Xh,4,3) -> (4,3,T,Z,Y*Xh)."""
    return pack_spinor(psi)


def unpack_spinor_eo(pp: jnp.ndarray, half_shape) -> jnp.ndarray:
    return unpack_spinor(pp, half_shape)


def pack_gauge_eo(gauge_eo) -> tuple:
    """((4,T,Z,Y,Xh,3,3) even, odd) -> packed pair ((4,3,3,T,Z,Y*Xh) x2)."""
    return tuple(pack_gauge(g) for g in gauge_eo)


@lru_cache(maxsize=None)
def _slot_mask_packed(T: int, Z: int, Y: int, Xh: int, parity: int):
    """(T, Z, Y*Xh) numpy bool: True where the parity-p half-site occupies
    the even x slot (r == 0) — fused-axis version of shift.py's mask."""
    t = np.arange(T)[:, None, None]
    z = np.arange(Z)[None, :, None]
    y = (np.arange(Y * Xh) // Xh)[None, None, :]
    return ((t + z + y + parity) % 2) == 0


def shift_eo_packed(arr: jnp.ndarray, dims, mu: int, sign: int,
                    target_parity: int, nhop: int = 1) -> jnp.ndarray:
    """Checkerboarded shift by nhop sites on the packed half lattice.

    arr: (..., T, Z, Y*Xh) holding a parity-(1-p) field when nhop is odd
    (parity-p when even); result indexed by parity-p half-sites is arr
    evaluated at x + sign*nhop*mu_hat.  ``dims`` is the full (T, Z, Y, X).
    x decomposition follows ops/shift.shift_eo: an even hop is a pure
    xh-slot roll; an odd hop is (nhop-1)/2 slot rolls plus one
    slot-parity flip selected by the target site's x slot.
    """
    T, Z, Y, X = dims
    Xh = X // 2
    if mu == 3:
        return jnp.roll(arr, -sign * nhop, axis=-3)
    if mu == 2:
        return jnp.roll(arr, -sign * nhop, axis=-2)
    if mu == 1:
        return jnp.roll(arr, -sign * nhop * Xh, axis=-1)
    # x direction: slot rolls ride shift_packed's fused-axis x case with
    # the HALF extent Xh as the wrap width
    if nhop % 2 == 0:
        return (shift_packed(arr, 0, sign, Xh, Y, nhop // 2)
                if nhop else arr)
    k = (nhop - 1) // 2
    base = shift_packed(arr, 0, sign, Xh, Y, k) if k else arr
    moved = shift_packed(base, 0, sign, Xh, Y, 1)
    mask_r0 = jnp.asarray(_slot_mask_packed(T, Z, Y, Xh, target_parity))
    if sign > 0:
        return jnp.where(mask_r0, base, moved)
    return jnp.where(mask_r0, moved, base)


def dslash_eo_packed(gauge_eo_p, psi_p: jnp.ndarray, dims,
                     target_parity: int) -> jnp.ndarray:
    """Checkerboarded Wilson hop on packed half-lattice arrays (mirrors
    ops/wilson.dslash_eo).

    gauge_eo_p: (even_p, odd_p) packed half-site links; psi_p of parity
    1-p; result indexed by parity-p sites.
    """
    u_here = gauge_eo_p[target_parity]
    u_there = gauge_eo_p[1 - target_parity]
    acc = None
    for mu in range(4):
        fwd = _hop_packed(
            shift_eo_packed(psi_p, dims, mu, +1, target_parity),
            u_here[mu], TABLES[(mu, +1)], adjoint=False)
        ub = shift_eo_packed(u_there[mu], dims, mu, -1, target_parity)
        bwd = _hop_packed(
            shift_eo_packed(psi_p, dims, mu, -1, target_parity),
            ub, TABLES[(mu, -1)], adjoint=True)
        term = [f + b for f, b in zip(fwd, bwd)]
        acc = term if acc is None else [a + t for a, t in zip(acc, term)]
    return jnp.stack(acc)


# ---------------------------------------------------------------------------
# bf16 pair-form packed stencils (the sloppy fast path)
# ---------------------------------------------------------------------------
#
# Pair layout on packed arrays: re/im as axis 2, keeping (Z, Y*X) minor:
#   spinor (4, 3, 2, T, Z, Y*Xh)    gauge (4, 3, 3, 2, T, Z, Y*Xh)
# Storage bf16 (or f32), arithmetic f32 (see ops/pair.py rationale).

def to_packed_pairs(arr: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    """complex packed (..., T, Z, YX) -> pairs with re/im before T."""
    return jnp.stack([arr.real, arr.imag], axis=-4).astype(dtype)


def from_packed_pairs(p: jnp.ndarray, dtype=jnp.complex64) -> jnp.ndarray:
    f = p.astype(jnp.float32)
    return (f[..., 0, :, :, :] + 1j * f[..., 1, :, :, :]).astype(dtype)


def _pp_cmul(a, b):
    return (a[0] * b[0] - a[1] * b[1], a[0] * b[1] + a[1] * b[0])


def _pp_cmul_conj(a, b):
    return (a[0] * b[0] + a[1] * b[1], a[0] * b[1] - a[1] * b[0])


def _pp_cscale(c: complex, x):
    cr, ci = float(c.real), float(c.imag)
    if ci == 0.0:
        return (cr * x[0], cr * x[1])
    if cr == 0.0:
        return (-ci * x[1], ci * x[0])
    return (cr * x[0] - ci * x[1], cr * x[1] + ci * x[0])


def _pp_add(a, b):
    return (a[0] + b[0], a[1] + b[1])


def _planes_psi(arr):
    """(4,3,2,...) pair storage -> {(spin, color): (re, im)} f32 planes."""
    a = arr.astype(jnp.float32)
    return {(s, c): (a[s, c, 0], a[s, c, 1])
            for s in range(4) for c in range(3)}


def _planes_u(arr):
    """(3,3,2,...) pair storage -> {(row, col): (re, im)} f32 planes."""
    a = arr.astype(jnp.float32)
    return {(i, j): (a[i, j, 0], a[i, j, 1])
            for i in range(3) for j in range(3)}


def _stack_pairs(acc, out_dtype):
    """acc[s][c] = (re, im) planes -> (4,3,2,...) array of out_dtype."""
    return jnp.stack([
        jnp.stack([jnp.stack([acc[s][c][0], acc[s][c][1]])
                   for c in range(3)])
        for s in range(4)]).astype(out_dtype)


def _hop_packed_pairs(psi_s, u, table, adjoint: bool):
    """Pair-form analog of _hop_packed.  psi_s[(s,c)] / u[(a,b)] are
    (re, im) tuples of f32 lattice planes."""
    t = table
    h = [[_pp_add(psi_s[(a, c)],
                  _pp_cscale(t[f"c{a}"], psi_s[(t[f"j{a}"], c)]))
          for c in range(3)] for a in (0, 1)]
    uh = [[None] * 3 for _ in range(2)]
    for s in range(2):
        for a in range(3):
            acc = None
            for b in range(3):
                m = (_pp_cmul_conj(u[(b, a)], h[s][b]) if adjoint
                     else _pp_cmul(u[(a, b)], h[s][b]))
                acc = m if acc is None else _pp_add(acc, m)
            uh[s][a] = acc
    return [uh[0], uh[1],
            [_pp_cscale(t["d2"], uh[t["k2"]][c]) for c in range(3)],
            [_pp_cscale(t["d3"], uh[t["k3"]][c]) for c in range(3)]]


def dslash_packed_pairs(gauge_pp: jnp.ndarray, psi_pp: jnp.ndarray,
                        X: int, Y: int, out_dtype=None) -> jnp.ndarray:
    """Full-lattice Wilson hop on PAIR-FORM packed arrays — no complex
    dtype anywhere (the honest single-precision path to compare against
    GPU f32 dslash numbers, and the layout the pallas kernels share).

    gauge_pp: (4,3,3,2,T,Z,Y*X) storage (f32 or bf16), phases folded;
    psi_pp: (4,3,2,T,Z,Y*X).  Compute f32; output cast to ``out_dtype``
    (default: psi storage dtype).
    """
    out_dtype = out_dtype or psi_pp.dtype
    acc = None
    for mu in range(4):
        u = gauge_pp[mu]
        fwd = _hop_packed_pairs(
            _planes_psi(shift_packed(psi_pp, mu, +1, X, Y)),
            _planes_u(u), TABLES[(mu, +1)], adjoint=False)
        bwd = _hop_packed_pairs(
            _planes_psi(shift_packed(psi_pp, mu, -1, X, Y)),
            _planes_u(shift_packed(u, mu, -1, X, Y)),
            TABLES[(mu, -1)], adjoint=True)
        term = [[_pp_add(f, b) for f, b in zip(fs, bs)]
                for fs, bs in zip(fwd, bwd)]
        acc = term if acc is None else [
            [_pp_add(a, t) for a, t in zip(as_, ts)]
            for as_, ts in zip(acc, term)]
    return _stack_pairs(acc, out_dtype)


def dslash_eo_packed_pairs(gauge_eo_pp, psi_pp: jnp.ndarray, dims,
                           target_parity: int,
                           out_dtype=None) -> jnp.ndarray:
    """Checkerboarded Wilson hop on PAIR-FORM packed half-lattice arrays
    (the bf16 sloppy stencil of the packed solve path).

    gauge_eo_pp: (even, odd) of (4,3,3,2,T,Z,Y*Xh) storage arrays;
    psi_pp: (4,3,2,T,Z,Y*Xh) of parity 1-p.  Compute at f32, output cast
    to ``out_dtype`` (default: psi storage dtype).
    """
    out_dtype = out_dtype or psi_pp.dtype
    u_here = gauge_eo_pp[target_parity]
    u_there = gauge_eo_pp[1 - target_parity]
    acc = None
    for mu in range(4):
        fwd_arr = shift_eo_packed(psi_pp, dims, mu, +1, target_parity)
        fwd = _hop_packed_pairs(_planes_psi(fwd_arr),
                                _planes_u(u_here[mu]),
                                TABLES[(mu, +1)], adjoint=False)
        ub = shift_eo_packed(u_there[mu], dims, mu, -1, target_parity)
        bwd_arr = shift_eo_packed(psi_pp, dims, mu, -1, target_parity)
        bwd = _hop_packed_pairs(_planes_psi(bwd_arr), _planes_u(ub),
                                TABLES[(mu, -1)], adjoint=True)
        term = [[_pp_add(f, b) for f, b in zip(fs, bs)]
                for fs, bs in zip(fwd, bwd)]
        acc = term if acc is None else [
            [_pp_add(a, t) for a, t in zip(as_, ts)]
            for as_, ts in zip(acc, term)]
    return _stack_pairs(acc, out_dtype)
