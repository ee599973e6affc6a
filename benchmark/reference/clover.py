"""Plain reference: the full-lattice Wilson-clover matrix M = A - kappa*D.

    A(x)   = 1 + (kappa * CSW / 2) * sum_{mu<nu} sigma_{mu nu} F_{mu nu}(x)
    sigma  = (i/2) [gamma_mu, gamma_nu]
    F      = -i/8 (Q - Q^dag), trace removed          (Hermitian)
    Q(x)   = U_mu(x) U_nu(x+mu) U_mu(x+nu)^dag U_nu(x)^dag
           + U_nu(x) U_mu(x-mu+nu)^dag U_nu(x-mu)^dag U_mu(x-mu)
           + U_mu(x-mu)^dag U_nu(x-mu-nu)^dag U_mu(x-mu-nu) U_nu(x-nu)
           + U_nu(x-nu)^dag U_mu(x-nu) U_nu(x+mu-nu) U_mu(x)^dag

and D the Wilson hop of ``reference/wilson.py`` (QUDA's kappa
normalisation, DeGrand-Rossi basis, mu = x,y,z,t).  Written from these
formulas with ``jnp.roll`` and elementwise complex multiplies only: the
four leaves of each plane multiplied out, sigma as a constant 4x4 spin
matrix per plane applied entry by entry, no chiral blocks, no even/odd
split, no packing, no kernel, no dot.  It imports nothing of the
program (the benchmark's own lattice shift and the gamma matrices of
the Wilson reference).  complex64 throughout.

Layout as the Wilson reference: psi (4, 3, T, Z, Y*X), links
(3, 3, 4, T, Z, Y*X).  What the functions below take as ``links`` is
what ``fold_boundary`` returns: the pair (boundary-folded links, the
links as given).  The hop reads the first, the field strength is built
from the second, UNFOLDED (the clover term is a property of the gauge
field; the antiperiodic sign belongs to the fermion hop only).
``fold_boundary`` is handed no x extent, so F is computed where ``nx``
is known: once per ``apply_m`` call, once per ``solve_normal``.

Departures from lib/dirac_clover.cpp / lib/clover_quda.cu, none of
which changes the matrix: QUDA stores A as two packed Hermitian 6x6
chiral blocks (72 reals) and its inverse, and applies M through the
even-odd Schur complement; here A is applied as sum sigma F on the
full lattice and nothing is inverted.  QUDA's ``clover_coeff`` is
kappa*csw; ``CSW`` is fixed here (the configuration's ``csw``) and the
kappa that multiplies it is the solve's.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..data import shift
from .wilson import GAMMA, GAMMA5, STORES, _colour, _colour_dag, _spin

CSW = 1.0
PLANES = ((0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3))
SIGMA = np.stack([0.5j * (GAMMA[mu] @ GAMMA[nu] - GAMMA[nu] @ GAMMA[mu])
                  for mu, nu in PLANES]).astype(np.complex64)


def _mul(a, b):
    """(3,3,...) x (3,3,...) per site: three broadcast multiplies."""
    return sum(a[:, j][:, None] * b[j][None, :] for j in range(3))


def _dag(a):
    return jnp.conj(jnp.swapaxes(a, 0, 1))


def field_strength(links, nx):
    """F_{mu nu}(x) of the six planes, (6, 3, 3, T, Z, Y*X)."""
    def at(v, *steps):                       # v(x + sum of the steps)
        for mu, sign in steps:
            v = shift(v, mu, sign, nx)
        return v
    eye = jnp.eye(3, dtype=links.dtype)[:, :, None, None, None]
    planes = []
    for mu, nu in PLANES:
        um, un = links[:, :, mu], links[:, :, nu]
        q = _mul(_mul(um, at(un, (mu, +1))),
                 _mul(_dag(at(um, (nu, +1))), _dag(un)))
        q = q + _mul(_mul(un, _dag(at(um, (mu, -1), (nu, +1)))),
                     _mul(_dag(at(un, (mu, -1))), at(um, (mu, -1))))
        q = q + _mul(_mul(_dag(at(um, (mu, -1))),
                          _dag(at(un, (mu, -1), (nu, -1)))),
                     _mul(at(um, (mu, -1), (nu, -1)), at(un, (nu, -1))))
        q = q + _mul(_mul(_dag(at(un, (nu, -1))), at(um, (nu, -1))),
                     _mul(at(un, (mu, +1), (nu, -1)), _dag(um)))
        f = -0.125j * (q - _dag(q))
        planes.append(f - (f[0, 0] + f[1, 1] + f[2, 2]) / 3.0 * eye)
    return jnp.stack(planes)


def fold_boundary(links, antiperiodic_t):
    """(folded links, links as given): the fermion boundary condition
    as a sign on the last t-links, beside the links the clover term is
    built from."""
    if not antiperiodic_t:
        return links, links
    return links.at[:, :, 3, -1].multiply(-1.0), links


def _apply(u, f, v, kappa, nx):
    """(A - kappa D) v from folded links ``u`` and field strength ``f``."""
    eye = np.eye(4, dtype=np.complex64)
    d = jnp.zeros_like(v)
    for mu in range(4):
        fwd = _colour(u[:, :, mu], shift(v, mu, +1, nx))
        d = d + _spin(eye - GAMMA[mu], fwd)
        bwd = shift(_colour_dag(u[:, :, mu], v), mu, -1, nx)
        d = d + _spin(eye + GAMMA[mu], bwd)
    sf = jnp.zeros_like(v)
    for p in range(6):
        sf = sf + _spin(SIGMA[p], _colour(f[p], v))
    return v + (0.5 * CSW * kappa) * sf - kappa * d


def _stored(links, nx, store):
    """(folded links, F) with every field in ``STORES[store]``."""
    st = STORES[store]
    return st(links[0]), st(field_strength(st(links[1]), nx))


def _apply_stored(u, f, psi, kappa, nx, dagger, store):
    st = STORES[store]
    g5 = jnp.asarray(GAMMA5)[:, None, None, None, None]
    psi = st(psi)
    out = _apply(u, f, g5 * psi if dagger else psi, kappa, nx)
    return st(g5 * out if dagger else out)


@functools.partial(jax.jit, static_argnames=("nx", "dagger", "store"))
def apply_m(links, psi, kappa, nx, dagger=False, store="single"):
    """M psi (or M^dag psi = gamma5 M gamma5 psi); ``links`` the pair
    ``fold_boundary`` returns; every field passes through
    ``STORES[store]``."""
    u, f = _stored(links, nx, store)
    return _apply_stored(u, f, psi, kappa, nx, dagger, store)


def rel_residual(links, kappa, nx, b, x):
    """||b - M x|| / ||b|| in f32 on the device, as a Python float."""
    r = b - apply_m(links, x, kappa, nx)
    return float(jnp.sqrt(jnp.sum(jnp.abs(r) ** 2)
                          / jnp.sum(jnp.abs(b) ** 2)))


@functools.partial(jax.jit, static_argnames=("nx", "store", "maxiter"))
def solve_normal(links, b, kappa, nx, tol, maxiter, store="single"):
    """Plain CG on M^dag M x = M^dag b, every vector kept in ``store``
    (the control: the reference in the program's place, one precision
    down; the loop of reference/wilson.py on this family's ``apply_m``).
    Returns (x, iterations)."""
    st = STORES[store]
    u, f = _stored(links, nx, store)

    def op(v, dagger=False):
        return _apply_stored(u, f, v, kappa, nx, dagger, store)

    def mdagm(v):
        return op(op(v), dagger=True)

    def dot(a, c):
        return jnp.sum(jnp.real(jnp.conj(a) * c))
    rhs = op(b, dagger=True)
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
    x0 = jnp.zeros_like(b)
    x, _, _, _, k = jax.lax.while_loop(
        cond, body, (x0, rhs, rhs, dot(rhs, rhs), jnp.int32(0)))
    return x, k
