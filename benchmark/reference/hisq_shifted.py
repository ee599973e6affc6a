"""Plain reference: the shifted even-odd improved staggered systems of a
multi-shift (RHMC) solve,

    (A + sigma_i) x_i = 2m b_e,   A = 4m^2 - D_eo D_oe,   i = 0 .. N-1,

on the EVEN sites ((t + z + y + x) even) of the lattice.  ``D`` is
``reference/hisq.py``'s full-lattice roll-and-multiply hop (fat = U,
long = -(1/24) UUU, MILC's phases, the program's factor 1/2), and ``A``
is built from it and a site mask: for a field that lives on the even
sites, ``D`` of it lives on the odd ones and ``D`` of that on the even
ones again, so ``A x = e (4m^2 x - D D x)`` with ``e`` the even-site
mask.  No even/odd split, no packing, no kernel, no dot: it imports
nothing of the program.  complex64 throughout.

The right-hand side is the program's: ``b_e`` is the even-site half of
SPIN ROW 0 of the harness's four-row source, and ``2m b_e`` is what the
API's ``prepare`` (``2m b_e - D_eo b_o``) makes of a source whose odd
sites are empty (the entry empties them).  A relative residual does not
forgive the 2m: ``x_i`` is the solution for that right-hand side.

The shifts are ``OFFSETS``, a constant of this module (``rel_residual``
is handed the links, kappa and the fields, not the traffic file:
``benchmark/tests/test_hisq_multishift.py`` holds the traffic file's
``offsets`` to it).  They are ADDED to ``A`` at the traffic's mass:
upstream's ladder ``0.06 + 0.01 i^2`` with its floor replaced by the
cell's own ``4m^2``.

Layout: a field is (rows, 3, T, Z, Y*X).  A SOLUTION has one row a
shift, row i = ``x_i`` (the harness's spin-row axis is the shift axis;
odd sites are not read).  ``links`` is what ``fold_boundary`` returns,
as in ``reference/hisq.py``; ``kappa`` is the family's 1 / (2 (4 + m)).
"""

import functools

import jax
import jax.numpy as jnp

from .hisq import _dslash, _stored, fold_boundary, mass_of  # noqa: F401
from .wilson import STORES

# sigma_i = 0.01 i^2, i = 0 .. 13 (i * i / 100.0 is the literal's double)
OFFSETS = tuple(i * i / 100.0 for i in range(14))


def even_mask(shape, nx):
    """1.0 on the sites with (t + z + y + x) even, (T, Z, Y*X) f32."""
    t, z, yx = (jax.lax.broadcasted_iota(jnp.int32, tuple(shape), a)
                for a in range(3))
    return (1 - (t + z + yx // nx + yx % nx) % 2).astype(jnp.float32)


def _rows(v):
    """Every axis before the colour axis is a row: (..., 3, T, Z, Y*X)
    -> (rows, 3, T, Z, Y*X)."""
    return v.reshape((-1,) + v.shape[-4:])


def _apply_stored(fat, lng, x, mass, nx, store):
    st = STORES[store]
    e = even_mask(x.shape[-3:], nx)
    x = st(e * x)
    dx = st(_dslash(fat, lng, x, nx))
    return st(e * (4.0 * mass * mass * x - _dslash(fat, lng, dx, nx)))


@functools.partial(jax.jit, static_argnames=("nx", "store"))
def apply_m(links, x, kappa, nx, store="single"):
    """A x = (4m^2 - D_eo D_oe) x on the even sites of every row of
    ``x`` (..., 3, T, Z, Y*X), zero on the odd ones; Hermitian, so there
    is no dagger.  Every field passes through ``STORES[store]``."""
    fat, lng = _stored(links, nx, store)
    return _apply_stored(fat, lng, _rows(x), mass_of(kappa), nx,
                         store).reshape(x.shape)


def rhs_of(b, kappa, nx):
    """2m b_e of spin row 0 of the harness's source: (1, 3, T, Z, Y*X)."""
    return (2.0 * mass_of(kappa)) * even_mask(b.shape[-3:], nx) * b[:1]


@functools.partial(jax.jit, static_argnames=("nx",))
def shift_residuals(links, kappa, nx, b, x):
    """||2m b_e - (A + sigma_i) x_i|| / ||2m b_e|| for every shift:
    (N,) f32; ``x`` holds row i = x_i, N = len(OFFSETS)."""
    x = even_mask(x.shape[-3:], nx) * _rows(x)
    rhs = rhs_of(b, kappa, nx)
    sig = jnp.asarray(OFFSETS, jnp.float32).reshape((-1, 1, 1, 1, 1))
    r = rhs - (apply_m(links, x, kappa, nx) + sig * x)
    return jnp.sqrt(jnp.sum(jnp.abs(r) ** 2, axis=(1, 2, 3, 4))
                    / jnp.sum(jnp.abs(rhs) ** 2))


def rel_residual(links, kappa, nx, b, x):
    """The LARGEST of the N shifted residuals (a NaN is the largest) of
    the solution rows ``x`` for spin row 0 of ``b``, as a Python float:
    one number a call for ``correct.compare``, which every shift has to
    hold."""
    if _rows(x).shape[0] != len(OFFSETS):
        raise ValueError(f"a solution has one row a shift: "
                         f"{len(OFFSETS)} rows, got {x.shape}")
    return float(jnp.max(shift_residuals(links, kappa, nx, b, x)))


@functools.partial(jax.jit, static_argnames=("nx", "store", "maxiter"))
def solve_normal(links, b, kappa, nx, tol, maxiter, store="single"):
    """Plain CG on (A + sigma_i) x_i = 2m b_e, one system a shift with
    CG scalars of its own, in lockstep until every shift is under
    ``tol`` or ``maxiter`` (a shift under ``tol`` stands still while
    the others go on); every vector kept in ``store`` (the control:
    the reference in the program's place, one precision down).  No
    shared Krylov space: N plain solves.  Returns (x, iterations) with
    x (N, 1, 3, T, Z, Y*X): the unit axis lets ``control.py``'s
    ``b - apply_m(x)`` broadcast against its four-row source; that
    number, the control's own claim, is not this system's residual."""
    st = STORES[store]
    fat, lng = _stored(links, nx, store)
    mass = mass_of(kappa)
    n = len(OFFSETS)
    sig = jnp.asarray(OFFSETS, jnp.float32).reshape((n, 1, 1, 1, 1))

    def op(v):
        return st(_apply_stored(fat, lng, v, mass, nx, store) + sig * v)

    def dot(a, c):
        return jnp.sum(jnp.real(jnp.conj(a) * c), axis=(1, 2, 3, 4),
                       keepdims=True)
    rhs = st(jnp.broadcast_to(rhs_of(b, kappa, nx),
                              (n,) + b.shape[-4:]))
    rr0 = dot(rhs, rhs)
    stop = tol * tol * rr0

    def cond(c):
        _, _, _, rr, k = c
        return jnp.any(rr > stop) & (k < maxiter)

    def body(c):
        x, r, p, rr, k = c
        go = rr > stop
        ap = op(p)
        alpha = jnp.where(go, rr / dot(p, ap), 0.0)
        x = st(x + alpha * p)
        r = st(r - alpha * ap)
        rr_new = jnp.where(go, dot(r, r), rr)
        p = st(r + jnp.where(go, rr_new / rr, 0.0) * p)
        return x, r, p, rr_new, k + 1
    x, _, _, _, k = jax.lax.while_loop(
        cond, body, (jnp.zeros_like(rhs), rhs, rhs, rr0, jnp.int32(0)))
    return x[:, None], k
