"""Plain reference: the full-lattice Wilson matrix M = 1 - kappa*D.

    D psi(x) = sum_mu [ (1 - gamma_mu) U_mu(x) psi(x+mu)
                      + (1 + gamma_mu) U_mu(x-mu)^dag psi(x-mu) ]

QUDA's kappa normalisation and DeGrand-Rossi gamma basis (mu = x,y,z,t),
antiperiodic in t by a sign on the t-links of the last time slice.
Written from the formula with ``jnp.roll`` and elementwise complex
multiplies only — no even/odd split, no packing, no kernel, no dot —
and it imports nothing of the program (only the benchmark's own
lattice shift, ``data.shift``).  complex64 throughout (elementwise
products are exact f32 work on the VPU; there is no matmul whose
precision could drop).

Layout: psi (4, 3, T, Z, Y*X) = [spin, colour, lattice]; links
(3, 3, 4, T, Z, Y*X) = [row, column, mu, lattice] as ``data.su3_links``
makes them (Y and X share the minor axis so that it does not tile-pad
on a TPU; ``data.shift`` steps in x inside it).  ``nx`` is the x extent.
``store`` rounds a field to the precision the operator is asked to work
in (identity for the check; bfloat16 for the control).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..data import shift

_I = 1j
GAMMA = np.zeros((4, 4, 4), np.complex64)       # [mu] = x, y, z, t
GAMMA[0] = [[0, 0, 0, _I], [0, 0, _I, 0], [0, -_I, 0, 0], [-_I, 0, 0, 0]]
GAMMA[1] = [[0, 0, 0, -1], [0, 0, 1, 0], [0, 1, 0, 0], [-1, 0, 0, 0]]
GAMMA[2] = [[0, 0, _I, 0], [0, 0, 0, -_I], [-_I, 0, 0, 0], [0, _I, 0, 0]]
GAMMA[3] = [[0, 0, 1, 0], [0, 0, 0, 1], [1, 0, 0, 0], [0, 1, 0, 0]]
GAMMA5 = np.array([1, 1, -1, -1], np.float32)   # diagonal in this basis


def _spin(mat, psi):
    """(4,4) constant x psi over the spin axis, entry by entry."""
    rows = []
    for s in range(4):
        row = 0
        for t in range(4):
            if mat[s, t] != 0:
                row = row + complex(mat[s, t]) * psi[t]
        rows.append(row)
    return jnp.stack(rows)


def _colour(u, psi):
    """U psi: out[s, a] = sum_b u[a, b] psi[s, b]."""
    return sum(u[:, b][None, :] * psi[:, b][:, None] for b in range(3))


def _colour_dag(u, psi):
    """U^dag psi: out[s, a] = sum_b conj(u[b, a]) psi[s, b]."""
    return sum(jnp.conj(u[b])[None, :] * psi[:, b][:, None]
               for b in range(3))


def identity(x):
    return x


def bf16_store(x):
    """Round re and im to bfloat16 and back: bf16 storage, f32 compute."""
    r = jnp.real(x).astype(jnp.bfloat16).astype(jnp.float32)
    i = jnp.imag(x).astype(jnp.bfloat16).astype(jnp.float32)
    return jax.lax.complex(r, i)


STORES = {"single": identity, "bfloat16": bf16_store}


def fold_boundary(links, antiperiodic_t):
    """The fermion boundary condition as a sign on the last t-links."""
    if not antiperiodic_t:
        return links
    return links.at[:, :, 3, -1].multiply(-1.0)


@functools.partial(jax.jit, static_argnames=("nx", "dagger", "store"))
def apply_m(links, psi, kappa, nx, dagger=False, store="single"):
    """M psi (or M^dag psi = gamma5 M gamma5 psi) with boundary-folded
    ``links``; every field passes through ``STORES[store]``."""
    st = STORES[store]
    g5 = jnp.asarray(GAMMA5)[:, None, None, None, None]
    psi = st(psi)
    links = st(links)
    v = g5 * psi if dagger else psi
    eye = np.eye(4, dtype=np.complex64)
    d = jnp.zeros_like(v)
    for mu in range(4):
        u = links[:, :, mu]
        fwd = _colour(u, shift(v, mu, +1, nx))            # U(x) psi(x+mu)
        d = d + _spin(eye - GAMMA[mu], fwd)
        bwd = shift(_colour_dag(u, v), mu, -1, nx)        # at x from x-mu
        d = d + _spin(eye + GAMMA[mu], bwd)
    out = v - kappa * d
    return st(g5 * out if dagger else out)


def rel_residual(links, kappa, nx, b, x):
    """||b - M x|| / ||b|| in f32 on the device, as a Python float."""
    r = b - apply_m(links, x, kappa, nx)
    return float(jnp.sqrt(jnp.sum(jnp.abs(r) ** 2)
                          / jnp.sum(jnp.abs(b) ** 2)))


@functools.partial(jax.jit, static_argnames=("nx", "store", "maxiter"))
def solve_normal(links, b, kappa, nx, tol, maxiter, store="single"):
    """Plain CG on M^dag M x = M^dag b, every vector kept in ``store``.
    The control: the reference in the program's place, one precision
    down.  Returns (x, iterations)."""
    st = STORES[store]

    def mdagm(v):
        return apply_m(links, apply_m(links, v, kappa, nx, store=store),
                       kappa, nx, dagger=True, store=store)

    def dot(a, c):
        return jnp.sum(jnp.real(jnp.conj(a) * c))
    rhs = apply_m(links, b, kappa, nx, dagger=True, store=store)
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
