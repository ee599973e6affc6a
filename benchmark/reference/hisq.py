"""Plain reference: the full-lattice improved staggered matrix
M = 2m + D of one colour vector per site,

    D psi(x) = 1/2 sum_mu eta_mu(x) [ F_mu(x) psi(x+mu)
                                    - F_mu(x-mu)^dag psi(x-mu)
                                    + L_mu(x) psi(x+3mu)
                                    - L_mu(x-3mu)^dag psi(x-3mu) ]

with the links the configuration fixes: fat F = the run's thin links U,
long L_mu(x) = -(1/24) U_mu(x) U_mu(x+mu) U_mu(x+2mu) (the Naik term at
tadpole 1), MILC's phases eta_x = 1, eta_y = (-1)^x, eta_z = (-1)^(x+y),
eta_t = (-1)^(x+y+z) (mu = x,y,z,t), antiperiodic in t by a sign on the
t-links of the last time slice (fat) or the last three (long: a
three-hop from any of them crosses the boundary once).  The 1/2 is the
program's normalisation of the hop (quda_tpu/ops/staggered.py), so that
M = 2m + D and M^dag M = 4m^2 - D^2 per parity.  D is anti-Hermitian.

Written from the formula with ``jnp.roll`` (the benchmark's own
``data.shift``) and elementwise complex multiplies only: no even/odd
split, no packing, no kernel, no dot.  It imports nothing of the
program.  complex64 throughout.

Layout: a field is (S, 3, T, Z, Y*X) = [row, colour, lattice]; every
row is a colour vector of its own (the harness's sources have four: the
solved system is ROW 0, and ``rel_residual`` reads row 0 of source and
solution and nothing else).  Thin links (3, 3, 4, T, Z, Y*X).  What the
functions below take as ``links`` is what ``fold_boundary`` returns:
the pair (thin links, the sign a hop across the t boundary takes).
``fold_boundary`` is handed no x extent, so the phases and the long
links are built where ``nx`` is known: once per ``apply_m`` call, once
per ``solve_normal``.  ``kappa`` is the family's 1 / (2 (4 + m)); the
mass is read back from it.
"""

import functools

import jax
import jax.numpy as jnp

from ..data import shift
from .wilson import STORES, _colour, _colour_dag


def mass_of(kappa):
    return 1.0 / (2.0 * kappa) - 4.0


def _mul(a, b):
    """(3,3,...) x (3,3,...) per site: three broadcast multiplies."""
    return sum(a[:, j][:, None] * b[j][None, :] for j in range(3))


def fold_boundary(links, antiperiodic_t):
    """(thin links, t-boundary sign): the phases need the x extent, so
    the folding itself is ``ks_links``'s."""
    return links, jnp.float32(-1.0 if antiperiodic_t else 1.0)


def ks_links(links, nx):
    """(fat, long), each (3, 3, 4, T, Z, Y*X), phases and boundary
    folded in."""
    u, t_sign = links
    T, Z, YX = u.shape[-3:]
    t, z, yx = (jax.lax.broadcasted_iota(jnp.int32, (T, Z, YX), a)
                for a in range(3))
    x, y = yx % nx, yx // nx
    sign = lambda n: (1 - 2 * (n % 2)).astype(jnp.float32)
    eta = (jnp.ones((T, Z, YX), jnp.float32), sign(x), sign(x + y),
           sign(x + y + z))
    fat, lng = [], []
    for mu in range(4):
        u0 = u[:, :, mu]
        u1 = shift(u0, mu, +1, nx)
        u2 = shift(u1, mu, +1, nx)
        naik = (-1.0 / 24.0) * _mul(_mul(u0, u1), u2)
        e1 = e3 = eta[mu]
        if mu == 3:
            e1 = jnp.where(t >= T - 1, t_sign * e1, e1)
            e3 = jnp.where(t >= T - 3, t_sign * e3, e3)
        fat.append(u0 * e1)
        lng.append(naik * e3)
    return jnp.stack(fat, axis=2), jnp.stack(lng, axis=2)


def _shift_n(v, mu, sign, nx, n):
    for _ in range(n):
        v = shift(v, mu, sign, nx)
    return v


def _dslash(fat, lng, v, nx):
    d = jnp.zeros_like(v)
    for mu in range(4):
        for u, n in ((fat[:, :, mu], 1), (lng[:, :, mu], 3)):
            d = d + 0.5 * _colour(u, _shift_n(v, mu, +1, nx, n))
            d = d - 0.5 * _shift_n(_colour_dag(u, v), mu, -1, nx, n)
    return d


def _apply_stored(fat, lng, psi, mass, nx, dagger, store):
    st = STORES[store]
    psi = st(psi)
    d = _dslash(fat, lng, psi, nx)
    return st(2.0 * mass * psi + (-d if dagger else d))


def _stored(links, nx, store):
    st = STORES[store]
    fat, lng = ks_links((st(links[0]), links[1]), nx)
    return st(fat), st(lng)


@functools.partial(jax.jit, static_argnames=("nx", "dagger", "store"))
def apply_m(links, psi, kappa, nx, dagger=False, store="single"):
    """M psi = 2m psi + D psi (or M^dag psi = 2m psi - D psi) on every
    row of ``psi`` (S, 3, T, Z, Y*X); ``links`` the pair
    ``fold_boundary`` returns; every field passes through
    ``STORES[store]``."""
    fat, lng = _stored(links, nx, store)
    return _apply_stored(fat, lng, psi, mass_of(kappa), nx, dagger, store)


def rel_residual(links, kappa, nx, b, x):
    """||b - M x|| / ||b|| of ROW 0 of ``b`` and ``x`` (the colour
    vector a call solves), in f32 on the device, as a Python float."""
    b0, x0 = b[:1], x[:1]
    r = b0 - apply_m(links, x0, kappa, nx)
    return float(jnp.sqrt(jnp.sum(jnp.abs(r) ** 2)
                          / jnp.sum(jnp.abs(b0) ** 2)))


@functools.partial(jax.jit, static_argnames=("nx", "store", "maxiter"))
def solve_normal(links, b, kappa, nx, tol, maxiter, store="single"):
    """Plain CG on (2m - D)(2m + D) x = (2m - D) b, every row of ``b`` a
    system of its own under shared CG scalars, every vector kept in
    ``store`` (the control: the reference in the program's place, one
    precision down).  Returns (x, iterations)."""
    st = STORES[store]
    fat, lng = _stored(links, nx, store)
    mass = mass_of(kappa)

    def op(v, dagger=False):
        return _apply_stored(fat, lng, v, mass, nx, dagger, store)

    def dot(a, c):
        return jnp.sum(jnp.real(jnp.conj(a) * c))
    rhs = op(b, dagger=True)
    stop = tol * tol * dot(rhs, rhs)

    def cond(c):
        _, _, _, rr, k = c
        return (rr > stop) & (k < maxiter)

    def body(c):
        x, r, p, rr, k = c
        ap = op(op(p), dagger=True)
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
