"""Staggered / improved-staggered dslash on the TPU-native packed order.

Same layout move as ops/wilson_packed.py, for the second headline
family (reference: QUDA's staggered/HISQ dslash kernels,
include/kernels/dslash_staggered.cuh):

    staggered spinor  (3, T, Z, Y*X)     [color planes]
    links             (3, 3, T, Z, Y*X)  per direction

1-hop (fat) and 3-hop (Naik long-link) shifts both ride the fused-axis
lane rolls of shift_packed (nhop-aware wrap masks); the color multiply
is unrolled 3x3 elementwise work on full vector tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .wilson_packed import pack_gauge as pack_links  # (4,3,3,T,Z,Y*X)
from .wilson_packed import shift_packed


def pack_staggered(psi: jnp.ndarray) -> jnp.ndarray:
    """(T,Z,Y,X,1,3) -> (3,T,Z,Y*X)."""
    T, Z, Y, X = psi.shape[:4]
    return jnp.transpose(psi[..., 0, :],
                         (4, 0, 1, 2, 3)).reshape(3, T, Z, Y * X)


def unpack_staggered(pp: jnp.ndarray, lattice_shape) -> jnp.ndarray:
    T, Z, Y, X = lattice_shape
    return jnp.transpose(pp.reshape(3, T, Z, Y, X),
                         (1, 2, 3, 4, 0))[..., None, :]


def _mat_vec(u, v, adjoint: bool):
    """u: (3,3,lat...), v: (3,lat...) color planes -> list of 3 planes."""
    out = []
    for a in range(3):
        acc = None
        for b in range(3):
            t = (jnp.conjugate(u[b, a]) * v[b] if adjoint
                 else u[a, b] * v[b])
            acc = t if acc is None else acc + t
        out.append(acc)
    return out


def dslash_staggered_packed(fat_p: jnp.ndarray, psi_p: jnp.ndarray,
                            X: int, Y: int,
                            long_p: jnp.ndarray = None) -> jnp.ndarray:
    """D psi on packed arrays (phases folded in the links).

    fat_p/long_p: (4,3,3,T,Z,YX); psi_p: (3,T,Z,YX).
    Mirrors ops/staggered.dslash_full: 0.5 * [U psi(+1) - U^dag psi(-1)]
    per hop set; whole arrays are shifted at once (shift_packed acts on
    the last three axes), matching wilson_packed.dslash_packed.
    """
    acc = None
    for links, nhop in (((fat_p, 1),) if long_p is None
                        else ((fat_p, 1), (long_p, 3))):
        for mu in range(4):
            u = links[mu]
            fwd = _mat_vec(u, shift_packed(psi_p, mu, +1, X, Y, nhop),
                           adjoint=False)
            ub = shift_packed(u, mu, -1, X, Y, nhop)
            bwd = _mat_vec(ub, shift_packed(psi_p, mu, -1, X, Y, nhop),
                           adjoint=True)
            term = [0.5 * (f - b) for f, b in zip(fwd, bwd)]
            acc = term if acc is None else [a + t
                                            for a, t in zip(acc, term)]
    return jnp.stack(acc)


def matvec_staggered_packed(fat_p, psi_p, mass: float, X: int, Y: int,
                            long_p=None):
    """M psi = 2m psi + D psi on packed arrays."""
    return 2.0 * mass * psi_p + dslash_staggered_packed(
        fat_p, psi_p, X, Y, long_p)


# ---------------------------------------------------------------------------
# pair-form stencil (complex-free: required on TPU runtimes without
# complex64 execution; also the bf16 sloppy staggered stencil)
# ---------------------------------------------------------------------------
#
# Layout: spinor (3, 2, T, Z, Y*X), links (3, 3, 2, T, Z, Y*X) per
# direction — re/im planes exactly as wilson_packed.to_packed_pairs
# produces from the complex packed arrays above.

from .wilson_packed import (_planes_u as _u_planes,  # noqa: E402
                            _pp_add, _pp_cmul, _pp_cmul_conj,
                            to_packed_pairs, from_packed_pairs)


def _color_planes(arr):
    """(3,2,...) pair storage -> [(re, im)] f32 planes per color."""
    a = arr.astype(jnp.float32)
    return [(a[c, 0], a[c, 1]) for c in range(3)]


def _mat_vec_pairs(u, v, adjoint: bool):
    out = []
    for a in range(3):
        acc = None
        for b in range(3):
            t = (_pp_cmul_conj(u[(b, a)], v[b]) if adjoint
                 else _pp_cmul(u[(a, b)], v[b]))
            acc = t if acc is None else _pp_add(acc, t)
        out.append(acc)
    return out


def dslash_staggered_packed_pairs(fat_pp: jnp.ndarray, psi_pp: jnp.ndarray,
                                  X: int, Y: int,
                                  long_pp: jnp.ndarray = None,
                                  out_dtype=None) -> jnp.ndarray:
    """Pair-form D psi (mirrors dslash_staggered_packed; phases folded).

    fat_pp/long_pp: (4,3,3,2,T,Z,YX); psi_pp: (3,2,T,Z,YX) storage
    arrays (f32 or bf16).  Compute f32; output cast to ``out_dtype``
    (default: psi storage dtype).
    """
    out_dtype = out_dtype or psi_pp.dtype
    acc = None
    for links, nhop in (((fat_pp, 1),) if long_pp is None
                        else ((fat_pp, 1), (long_pp, 3))):
        for mu in range(4):
            u = _u_planes(links[mu])
            fwd = _mat_vec_pairs(
                u, _color_planes(shift_packed(psi_pp, mu, +1, X, Y, nhop)),
                adjoint=False)
            ub = _u_planes(shift_packed(links[mu], mu, -1, X, Y, nhop))
            bwd = _mat_vec_pairs(
                ub, _color_planes(shift_packed(psi_pp, mu, -1, X, Y, nhop)),
                adjoint=True)
            term = [(0.5 * (f[0] - b[0]), 0.5 * (f[1] - b[1]))
                    for f, b in zip(fwd, bwd)]
            acc = term if acc is None else [_pp_add(a, t)
                                            for a, t in zip(acc, term)]
    return jnp.stack([jnp.stack([re, im]) for re, im in acc]).astype(
        out_dtype)


def dslash_staggered_eo_packed_pairs(fat_eo_pp, psi_pp: jnp.ndarray, dims,
                                     target_parity: int,
                                     long_eo_pp=None,
                                     out_dtype=None) -> jnp.ndarray:
    """Checkerboarded pair-form staggered hop (mirrors
    ops/staggered.dslash_eo; the complex-free staggered solver stencil).

    fat_eo_pp/long_eo_pp: (even, odd) of (4,3,3,2,T,Z,Y*Xh) half-site
    link storage (phases folded); psi_pp: (3,2,T,Z,Y*Xh) of parity 1-p.
    Result indexed by parity-p sites.  Both 1-hop (fat) and 3-hop (Naik)
    neighbours flip parity (odd hop counts), so forward links live at
    the target parity and backward links are the opposite-parity links
    shifted back nhop sites.
    """
    from .wilson_packed import shift_eo_packed
    out_dtype = out_dtype or psi_pp.dtype
    p = target_parity
    acc = None
    for links_eo, nhop in (((fat_eo_pp, 1),) if long_eo_pp is None
                           else ((fat_eo_pp, 1), (long_eo_pp, 3))):
        u_here = links_eo[p]
        u_there = links_eo[1 - p]
        for mu in range(4):
            fwd = _mat_vec_pairs(
                _u_planes(u_here[mu]),
                _color_planes(shift_eo_packed(psi_pp, dims, mu, +1, p,
                                              nhop)),
                adjoint=False)
            ub = shift_eo_packed(u_there[mu], dims, mu, -1, p, nhop)
            bwd = _mat_vec_pairs(
                _u_planes(ub),
                _color_planes(shift_eo_packed(psi_pp, dims, mu, -1, p,
                                              nhop)),
                adjoint=True)
            term = [(0.5 * (f[0] - b[0]), 0.5 * (f[1] - b[1]))
                    for f, b in zip(fwd, bwd)]
            acc = term if acc is None else [_pp_add(a, t)
                                            for a, t in zip(acc, term)]
    return jnp.stack([jnp.stack([re, im]) for re, im in acc]).astype(
        out_dtype)


# ---------------------------------------------------------------------------
# resident KS links: canonical fat / long links -> the (even, odd) pair
# arrays of the solve operators, built lattice-minor
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("dims", "antiperiodic_t", "nhop"))
def ks_links_eo_pairs(links, dims, antiperiodic_t: bool, nhop: int = 1):
    """Canonical (4,T,Z,Y,X,3,3) fat (``nhop`` 1) or long (``nhop`` 3)
    links -> (even, odd) f32 pair arrays (4,3,3,2,T,Z,Y*Xh) with the
    MILC staggered phases and the antiperiodic t boundary (the last
    ``nhop`` time slices) folded in: what
    ``apply_staggered_phases`` + ``split_gauge_eo`` + ``pack_links`` +
    ``to_packed_pairs`` give, as ONE program whose every intermediate
    keeps the lattice minor (a canonical (...,3,3) temporary tile-pads
    ~57x on a TPU: PERF.md, PR 22 cause 3).  A lower storage dtype is a
    cast of the result."""
    from .clover_packed import split_eo_packed
    T, Z, Y, X = dims
    shape = (T, Z, Y * X)
    t, z, yx = (jax.lax.broadcasted_iota(jnp.int32, shape, a)
                for a in range(3))
    x, y = yx % X, yx // X
    sign = lambda n: (1 - 2 * (n % 2)).astype(jnp.float32)
    eta_t = sign(x + y + z)
    if antiperiodic_t:
        eta_t = jnp.where(t >= T - nhop, -eta_t, eta_t)
    eta = jnp.stack([jnp.ones(shape, jnp.float32), sign(x), sign(x + y),
                     eta_t])
    gp = pack_links(links) * eta[:, None, None].astype(links.dtype)
    return tuple(to_packed_pairs(h, jnp.float32)
                 for h in split_eo_packed(gp, dims))
