"""Plain reference: the full five-dimensional Möbius domain-wall matrix

    M psi = D_W(-M5) (b5 psi + c5 chi) + psi - chi

on the whole lattice, with ``D_W(-M5) = (4 - M5) - D / 2`` the Wilson
operator at the negative mass -M5 (``D`` the hop sum of
``reference/wilson.py``: QUDA's DeGrand-Rossi basis, antiperiodic in t
by a sign on the last t-links) and ``chi`` the hop in the fifth
dimension,

    chi(s) = P_- psi(s+1) + P_+ psi(s-1),   P_+- = (1 +- gamma5) / 2,
    P_- psi(Ls) = -mf P_- psi(0),   P_+ psi(-1) = -mf P_+ psi(Ls-1),

written as a loop over s with the two wall terms spelled out: no
(Ls, Ls) matrix, no inverse, no even/odd split, no packing, no kernel,
no dot, and nothing of the program is imported.  complex64 throughout.

The action is a constant of this module (``rel_residual`` is handed the
links and the fields, not the configuration): ``LS``, ``B5``, ``C5``,
``M5``, ``MF``; ``benchmark/tests/test_mobius.py`` holds them to
``configs/mobius24_single.json`` and ``traffic/strange_mf03.json``.
``kappa`` is taken and not read (``KAPPA_B`` is the law the traffic file
states for it).

The right-hand side is the WALL SOURCE of a propagator code, made here
from the harness's one 4-d source ``b`` and once more, independently,
by the entry module: the physical quark field of this convention is
``q = P_- psi(0) + P_+ psi(Ls-1)`` (the mass term couples exactly these
two), so ``<q qbar>`` needs

    B(0) = P_+ b,   B(Ls-1) = P_- b,   B(s) = 0 between them

(in this basis gamma5 = diag(1, 1, -1, -1): P_+ keeps spin rows 0, 1 and
P_- rows 2, 3).  ``|B| = |b|``.

Layout: a 4-d field is (4, 3, T, Z, Y*X); a 5-d field is
(Ls, 4, 3, T, Z, Y*X).  A SOLUTION arrives as (4 Ls, 3, T, Z, Y*X), row
4 s + spin = x(s)[spin] (the harness's spin-row axis carries s), or
already as (Ls, 4, 3, ...): ``_five`` takes both.  ``links`` is what
``fold_boundary`` returns.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..data import shift
from .wilson import (GAMMA, GAMMA5, STORES, _colour, _colour_dag,  # noqa: F401
                     _spin, fold_boundary)

LS = 12
B5, C5 = 1.5, 0.5
M5 = 1.8
MF = 0.03
KAPPA_B = 1.0 / (2.0 * (B5 * (4.0 - M5) + 1.0))   # QUDA's kappa_b


def _five(x, ls=None):
    """(4 Ls, 3, ...) or (Ls, 4, 3, ...) -> (Ls, 4, 3, T, Z, Y*X)."""
    x = x.reshape((-1, 4) + x.shape[-4:])
    if ls is not None and x.shape[0] != ls:
        raise ValueError(f"a 5-d field has {ls} s-slices of four spin "
                         f"rows, got {x.shape}")
    return x


def _hop(links, v, nx):
    """D v of one 4-d field (4, 3, T, Z, Y*X): the Wilson hop sum."""
    eye = np.eye(4, dtype=np.complex64)
    d = jnp.zeros_like(v)
    for mu in range(4):
        u = links[:, :, mu]
        d = d + _spin(eye - GAMMA[mu], _colour(u, shift(v, mu, +1, nx)))
        d = d + _spin(eye + GAMMA[mu],
                      shift(_colour_dag(u, v), mu, -1, nx))
    return d


def _chi(psi, dagger=False):
    """chi(s) = P_- psi(s+1) + P_+ psi(s-1) with the -mf walls, slice
    by slice; its adjoint swaps the two projectors."""
    ls = psi.shape[0]
    plus = jnp.asarray((1.0 + GAMMA5) / 2.0)[:, None, None, None, None]
    minus = 1.0 - plus
    from_up, from_dn = (plus, minus) if dagger else (minus, plus)
    out = []
    for s in range(ls):
        up = psi[s + 1] if s + 1 < ls else -MF * psi[0]
        dn = psi[s - 1] if s > 0 else -MF * psi[ls - 1]
        out.append(from_up * up + from_dn * dn)
    return jnp.stack(out)


def _wilson(links, v, nx, dagger):
    """D_W(-M5) v = (4 - M5) v - D v / 2 on every s-slice; the adjoint
    is gamma5 D_W gamma5."""
    g5 = jnp.asarray(GAMMA5)[:, None, None, None, None]
    w = g5 * v if dagger else v
    out = (4.0 - M5) * w - 0.5 * jax.vmap(
        lambda f: _hop(links, f, nx))(w)
    return g5 * out if dagger else out


@functools.partial(jax.jit, static_argnames=("nx", "dagger", "store"))
def apply_m(links, psi, kappa, nx, dagger=False, store="single"):
    """M psi (or M^dag psi) of a 5-d field of any Ls with
    boundary-folded ``links``; ``kappa`` is not read; every field
    passes through ``STORES[store]``."""
    st = STORES[store]
    shape = psi.shape
    psi, links = st(_five(psi)), st(links)
    if dagger:
        # M^dag = (b5 + c5 chi^dag) D_W^dag + 1 - chi^dag
        w = st(_wilson(links, psi, nx, True))
        out = B5 * w + C5 * _chi(w, True) + psi - _chi(psi, True)
    else:
        chi = _chi(psi)
        out = _wilson(links, st(B5 * psi + C5 * chi), nx, False) + psi - chi
    return st(out).reshape(shape)


def wall_source(b, ls=LS):
    """(4, 3, T, Z, Y*X) -> (Ls, 4, 3, T, Z, Y*X): P_+ b on s = 0,
    P_- b on s = Ls - 1."""
    plus = jnp.asarray((1.0 + GAMMA5) / 2.0)[:, None, None, None, None]
    five = jnp.zeros((ls,) + b.shape, b.dtype)
    return five.at[0].set(plus * b).at[ls - 1].set((1.0 - plus) * b)


def rel_residual(links, kappa, nx, b, x):
    """||B - M x|| / ||B|| of the 5-d solution rows ``x`` for the wall
    source B of the harness's 4-d source ``b``, in f32 on the device,
    as a Python float: all Ls x 4 rows are held."""
    x = _five(x, LS)
    r = wall_source(b) - apply_m(links, x, kappa, nx)
    return float(jnp.sqrt(jnp.sum(jnp.abs(r) ** 2)
                          / jnp.sum(jnp.abs(b) ** 2)))


@functools.partial(jax.jit, static_argnames=("nx", "store", "maxiter"))
def solve_normal(links, b, kappa, nx, tol, maxiter, store="single"):
    """Plain CG on M^dag M x = M^dag B for the wall source B of ``b``,
    every vector kept in ``store`` (the control: the reference in the
    program's place, one precision down).  Returns (x, iterations) with
    x (Ls, 4, 3, T, Z, Y*X): ``control.py``'s own ``b - apply_m(x)``
    broadcasts the 4-d ``b`` over it, so that number, the control's
    claim, is not this system's residual (nothing rests on it)."""
    st = STORES[store]

    def mdagm(v):
        return apply_m(links, apply_m(links, v, kappa, nx, store=store),
                       kappa, nx, dagger=True, store=store)

    def dot(a, c):
        return jnp.sum(jnp.real(jnp.conj(a) * c))
    rhs = apply_m(links, st(wall_source(b)), kappa, nx, dagger=True,
                  store=store)
    stop = tol * tol * dot(rhs, rhs)

    def cond(c):
        _, _, _, rr, k = c
        return (rr > stop) & (k < maxiter)

    def body(c):
        x, r, p, rr, k = c
        ap = mdagm(p)
        alpha = rr / dot(p, ap)
        x = st(x + alpha * p)
        r = st(r - alpha * ap)
        rr_new = dot(r, r)
        p = st(r + (rr_new / rr) * p)
        return x, r, p, rr_new, k + 1
    x, _, _, _, k = jax.lax.while_loop(
        cond, body, (jnp.zeros_like(rhs), rhs, rhs, dot(rhs, rhs),
                     jnp.int32(0)))
    return x, k
