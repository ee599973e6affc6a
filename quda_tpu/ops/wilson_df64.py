"""Extended-precision (df64) Wilson even/odd stencil on the packed layout.

Why a dedicated stencil: the residual recompute r = b - M x of the reliable
update (reference: include/reliable_updates.h:33-54, fp64 operator in
lib/inv_cg_quda.cpp:63) suffers catastrophic cancellation — near convergence
|r| ~ tol*|b|, so an f32 apply's internal rounding (~eps*|b| ~ 1e-7*|b|)
floors the certifiable residual at 1e-7 regardless of how x is stored.
Linearity alone cannot fix this (A x_hi at f32 still rounds); every
elementary product and every accumulation inside the hop must carry its
error word.  Here each U * psi product goes through Dekker two_prod, each
add through the df64 two_sum chain (ops/df64.py), with the gauge links held
as plain f32 (the operator being solved IS the f32-link operator; its f64
embedding is exact, which is what the CPU oracle checks).

Representation: a df64 spinor is a (hi, lo) tuple of packed pair arrays
(4, 3, 2, T, Z, Y*Xh) f32 — the same layout as the pair-form sloppy
stencils (ops/wilson_packed.dslash_eo_packed_pairs), so the sloppy loop and
the precise df64 operator share shifts, converters, and field geometry.
Shifts are permutations (exact), applied to both words.

Cost: ~20x the f32 pair stencil in VPU flops — irrelevant, it runs once per
reliable update (every ~30-100 CG iterations), not in the hot loop.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import df64 as dfm
from .wilson_packed import TABLES, shift_eo_packed


# -- complex df64 helpers ----------------------------------------------------
# value = (re_df, im_df); each *_df = (hi, lo) f32 planes.

def _dfc_add(a, b):
    return dfm.add(a[0], b[0]), dfm.add(a[1], b[1])


def _df_scale_unit(v, f: float):
    """Scale a df64 by a float that is ±1 for every Wilson table constant
    (exact); falls back to a two_prod scale for generality."""
    if f == 1.0:
        return v
    if f == -1.0:
        return dfm.neg(v)
    return dfm.mul_f32(v, jnp.float32(f))


def _dfc_cscale(c: complex, x):
    """Multiply complex df64 x by a complex constant (table entries are in
    {±1, ±i}: pure component shuffles/negations — exact)."""
    cr, ci = float(c.real), float(c.imag)
    if ci == 0.0:
        return _df_scale_unit(x[0], cr), _df_scale_unit(x[1], cr)
    if cr == 0.0:
        return _df_scale_unit(x[1], -ci), _df_scale_unit(x[0], ci)
    re = dfm.add(_df_scale_unit(x[0], cr), _df_scale_unit(x[1], -ci))
    im = dfm.add(_df_scale_unit(x[1], cr), _df_scale_unit(x[0], ci))
    return re, im


def _mul_f32_df(a, x):
    """plain f32 a times df64 x (one home: ops/df64.mul_f32)."""
    return dfm.mul_f32(x, a)


def _dfc_cmul_f32(u, h):
    """(complex f32 u) * (complex df64 h)."""
    ur, ui = u
    hr, hi = h
    re = dfm.sub(_mul_f32_df(ur, hr), _mul_f32_df(ui, hi))
    im = dfm.add(_mul_f32_df(ur, hi), _mul_f32_df(ui, hr))
    return re, im


def _dfc_cmul_conj_f32(u, h):
    """conj(complex f32 u) * (complex df64 h)."""
    ur, ui = u
    hr, hi = h
    re = dfm.add(_mul_f32_df(ur, hr), _mul_f32_df(ui, hi))
    im = dfm.sub(_mul_f32_df(ur, hi), _mul_f32_df(ui, hr))
    return re, im


# -- plane views -------------------------------------------------------------

def _planes_psi_df(psi_df):
    """((4,3,2,...) hi, lo) -> {(s,c): ((reh,rel),(imh,iml))}."""
    h, l = psi_df
    return {(s, c): ((h[s, c, 0], l[s, c, 0]), (h[s, c, 1], l[s, c, 1]))
            for s in range(4) for c in range(3)}


def _planes_u(u):
    """(3,3,2,...) f32 pair links -> {(i,j): (re, im)} f32 planes."""
    u = u.astype(jnp.float32)
    return {(i, j): (u[i, j, 0], u[i, j, 1])
            for i in range(3) for j in range(3)}


def _stack_df(acc):
    """acc[s][c] = complex df64 -> ((4,3,2,...) hi, (4,3,2,...) lo)."""
    hi = jnp.stack([
        jnp.stack([jnp.stack([acc[s][c][0][0], acc[s][c][1][0]])
                   for c in range(3)]) for s in range(4)])
    lo = jnp.stack([
        jnp.stack([jnp.stack([acc[s][c][0][1], acc[s][c][1][1]])
                   for c in range(3)]) for s in range(4)])
    return hi, lo


# -- the hop -----------------------------------------------------------------

def _hop_df(psi_s, u, table, adjoint: bool):
    """df64 analog of wilson_packed._hop_packed_pairs: project, 3x3 color
    multiply (two_prod products), reconstruct."""
    t = table
    h = [[_dfc_add(psi_s[(a, c)],
                   _dfc_cscale(t[f"c{a}"], psi_s[(t[f"j{a}"], c)]))
          for c in range(3)] for a in (0, 1)]
    uh = [[None] * 3 for _ in range(2)]
    for s in range(2):
        for a in range(3):
            acc = None
            for b in range(3):
                m = (_dfc_cmul_conj_f32(u[(b, a)], h[s][b]) if adjoint
                     else _dfc_cmul_f32(u[(a, b)], h[s][b]))
                acc = m if acc is None else _dfc_add(acc, m)
            uh[s][a] = acc
    return [uh[0], uh[1],
            [_dfc_cscale(t["d2"], uh[t["k2"]][c]) for c in range(3)],
            [_dfc_cscale(t["d3"], uh[t["k3"]][c]) for c in range(3)]]


def _shift_df(psi_df, dims, mu, sign, parity):
    return (shift_eo_packed(psi_df[0], dims, mu, sign, parity),
            shift_eo_packed(psi_df[1], dims, mu, sign, parity))


def dslash_eo_df(gauge_eo_pp, psi_df, dims, target_parity: int):
    """Checkerboarded Wilson hop in df64.

    gauge_eo_pp: (even, odd) of (4,3,3,2,T,Z,Y*Xh) f32 pair links with
    boundary phases folded; psi_df: (hi, lo) packed pair spinor of parity
    1-p; result: (hi, lo) indexed by parity-p sites.
    """
    u_here = gauge_eo_pp[target_parity]
    u_there = gauge_eo_pp[1 - target_parity]
    acc = None
    for mu in range(4):
        fwd = _hop_df(
            _planes_psi_df(_shift_df(psi_df, dims, mu, +1, target_parity)),
            _planes_u(u_here[mu]), TABLES[(mu, +1)], adjoint=False)
        ub = shift_eo_packed(u_there[mu], dims, mu, -1, target_parity)
        bwd = _hop_df(
            _planes_psi_df(_shift_df(psi_df, dims, mu, -1, target_parity)),
            _planes_u(ub), TABLES[(mu, -1)], adjoint=True)
        term = [[_dfc_add(f, b) for f, b in zip(fs, bs)]
                for fs, bs in zip(fwd, bwd)]
        acc = term if acc is None else [
            [_dfc_add(a, t) for a, t in zip(as_, ts)]
            for as_, ts in zip(acc, term)]
    return _stack_df(acc)


# -- field-level df64 linear algebra ----------------------------------------

class WilsonPCDF64:
    """df64 precise companion of DiracWilsonPCPacked (reference contract:
    the fp64 matPrecise of lib/inv_cg_quda.cpp + dbldbl reductions).

    Fields are (hi, lo) packed pair arrays; links are the packed f32 pair
    links shared with the f32/bf16 sloppy operators.  M = 1 - kappa^2 D D
    on parity ``matpc``; Mdag via the exact gamma5 trick; prepare /
    reconstruct / full-residual all carried in df64 so the certified
    residual survives to the full-lattice statement.
    """

    def __init__(self, dpk):
        from . import wilson_packed as wpk
        self.dims = tuple(dpk.dims)
        self.matpc = dpk.matpc
        self.kappa = dfm.const(float(dpk.kappa))
        self.kappa2 = dfm.const(float(dpk.kappa) ** 2)
        self.gauge_eo_pp = tuple(
            wpk.to_packed_pairs(g, jnp.float32) for g in dpk.gauge_eo_p)

    # -- conversions --------------------------------------------------------
    def to_df(self, x):
        """Canonical complex half-lattice field -> df64 packed pairs
        (exact: complex64 components are f32)."""
        from . import wilson_packed as wpk
        pp = wpk.to_packed_pairs(wpk.pack_spinor(x), jnp.float32)
        return dfm.promote(pp)

    def from_df(self, x_df, dtype=jnp.complex64):
        """df64 packed pairs -> (canonical complex hi, canonical complex
        lo): hi + lo is the full-precision solution (the analog of QUDA
        returning an fp64 x)."""
        from . import wilson_packed as wpk
        T, Z, Y, X = self.dims
        half = (T, Z, Y, X // 2)
        out = []
        for w in x_df:
            c = wpk.from_packed_pairs(w, dtype)
            out.append(wpk.unpack_spinor(c, half))
        return tuple(out)

    # -- operator applications ----------------------------------------------
    def D_to(self, x_df, target_parity):
        return dslash_eo_df(self.gauge_eo_pp, x_df, self.dims,
                            target_parity)

    def M(self, x_df):
        p = self.matpc
        t = self.D_to(x_df, 1 - p)
        dd = self.D_to(t, p)
        return dfm.sub(x_df, dfm.mul(dd, self.kappa2))

    def _g5(self, x_df):
        sign = jnp.asarray([1.0, 1.0, -1.0, -1.0], jnp.float32)
        s = sign[:, None, None, None, None, None]
        return (x_df[0] * s, x_df[1] * s)

    def Mdag(self, x_df):
        return self._g5(self.M(self._g5(x_df)))

    def MdagM(self, x_df):
        return self.Mdag(self.M(x_df))

    # -- solve-boundary compositions ----------------------------------------
    def prepare_df(self, b_even, b_odd):
        """b_p + kappa D b_q carried in df64 (DiracWilsonPC.prepare)."""
        from ..fields.geometry import EVEN
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        t = self.D_to(self.to_df(b_q), p)
        return dfm.add(self.to_df(b_p), dfm.mul(t, self.kappa))

    def reconstruct_df(self, x_df, b_even, b_odd):
        """x_q = b_q + kappa D x_p in df64; returns (x_even, x_odd) df64."""
        from ..fields.geometry import EVEN
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        t = self.D_to(x_df, 1 - p)
        x_q = dfm.add(self.to_df(b_q), dfm.mul(t, self.kappa))
        return (x_df, x_q) if p == EVEN else (x_q, x_df)

    def residual_df(self, rhs_df, x_df):
        """rhs - M x in df64 (the PC direct residual)."""
        return dfm.sub(rhs_df, self.M(x_df))

    def full_residual_norm2(self, x_e_df, x_o_df, b_even, b_odd):
        """|b - M_full x|^2 in df64 over both parities -> df64 scalar.

        (M_full x)_p = x_p - kappa D_{p,q} x_q with every term df64."""
        out = None
        for par, x_p, x_q, b_p in ((0, x_e_df, x_o_df, b_even),
                                   (1, x_o_df, x_e_df, b_odd)):
            t = self.D_to(x_q, par)
            r = dfm.add(dfm.sub(self.to_df(b_p), x_p),
                        dfm.mul(t, self.kappa))
            n = dfm.norm2(r)
            out = n if out is None else dfm.add(out, n)
        return out
