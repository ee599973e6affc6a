"""Plain reference: the shifted even-odd Wilson-clover normal systems of
a multi-shift (RHMC) solve,

    (M^dag M + sigma_i) x_i = M^dag b_p,   i = 0 .. N-1,
    M = A_pp - kappa^2 D_pq A_qq^-1 D_qp,

on the sites of parity p ((t + z + y + x) mod 2 = ``PARITY``) of the
lattice, q the others.  ``M`` is the program's ASYMMETRIC Schur
complement, read off ``DiracCloverPC.M`` (``A_p x - kappa^2 D_to(Ainv_q
D_to(x, q), p)``); ``M^dag = gamma5 M gamma5`` (sigma and with it A
commute with gamma5, ``gamma5 D gamma5 = D^dag``).  ``A = 1 + (kappa
CSW / 2) sum sigma F`` is ``reference/clover.py``'s (its field strength
from the UNFOLDED links, its sigma matrices) and ``D`` the Wilson hop of
``reference/wilson.py`` on the full lattice (its pieces summed over the
four directions by ``reference/mobius.py``'s ``_hop``); the even-odd
structure is two site masks: for a field that lives on the p sites,
``D`` of it lives on the q sites and ``D`` of that on the p sites again.

``A_qq^-1`` is this module's OWN: the site's 12 x 12 matrix ``A[(s, a),
(s', a')] = delta + (kappa CSW / 2) sum_planes sigma[s, s'] F[a, a']``
is assembled entry by entry and inverted by an elementwise Gauss-Jordan
elimination over the lattice (twelve pivots, no row exchange: A is
Hermitian and positive wherever the program's own inverse exists), f32
complex, the lattice axes minor throughout; A itself is applied through
the same twelve rows.  Nothing of the program's blocks, packing or
kernels is read, and nothing of the program is imported.  No even/odd
split, no dot.  complex64 throughout.

The right-hand side is the program's: ``b_p`` is the p-parity half of
the harness's four-row source (what the API's ``prepare``, ``b_p + kappa
D_pq A_qq^-1 b_q``, makes of a source whose q sites are empty; the entry
empties them) and ``M^dag b_p`` what the normal equations are solved
for.  QUDA's RHMC callers ask ``MATPCDAG_MATPC`` of ``b`` as given (no
``M^dag`` on the source): ``InvertParam.solution_type`` is read nowhere
in the program, the configuration lists it under ``assumed``, and the
loop's cost is the same.

The shifts are ``OFFSETS``, a constant of this module (``rel_residual``
is handed the links, kappa and the fields, not the traffic file:
``benchmark/tests/test_clover_multishift.py`` holds the traffic file's
``offsets`` to it): upstream's ladder ``0.06 + 0.01 i^2`` with its floor
lowered to 0.0064.  ``CSW`` is ``reference/clover.py``'s.

Layout: a field is (rows, 4, 3, T, Z, Y*X).  A SOLUTION has four rows a
shift and arrives as (4 N, 3, T, Z, Y*X), row ``4 i + spin`` =
``x_i[spin]`` (the harness's spin-row axis carries the shift), or
already as (N, 4, 3, ...); q-parity sites are not read.  ``links`` is
what ``fold_boundary`` returns (``reference/clover.py``'s pair).
"""

import functools

import jax
import jax.numpy as jnp

from .clover import CSW, PLANES, SIGMA, field_strength, fold_boundary  # noqa: F401
from .mobius import _hop        # the Wilson hop sum D v of one 4-d field
from .wilson import GAMMA5, STORES

# sigma_i = 0.0064 + 0.01 i^2, i = 0 .. 13, each the double of its
# four-digit decimal (what the traffic file's literals parse to)
OFFSETS = tuple(round(0.0064 + i * i / 100.0, 4) for i in range(14))
PARITY = 0          # the configuration's matpc: even-even


def parity_mask(shape, nx, parity):
    """1.0 on the sites with (t + z + y + x) mod 2 = ``parity``,
    (T, Z, Y*X) f32."""
    t, z, yx = (jax.lax.broadcasted_iota(jnp.int32, tuple(shape), a)
                for a in range(3))
    return ((t + z + yx // nx + yx % nx) % 2 == parity).astype(
        jnp.float32)


def _fields(v):
    """(4 N, 3, ...) or (..., 4, 3, ...) -> (N, 4, 3, T, Z, Y*X)."""
    return v.reshape((-1, 4) + v.shape[-4:])


def site_matrix(f, kappa):
    """A = 1 + (kappa CSW / 2) sum sigma F as the site's 12 x 12 matrix,
    (12, 12, T, Z, Y*X): row 3 s + a, column 3 s' + a', entry by
    entry from the six planes."""
    c = 0.5 * CSW * kappa
    rows = []
    for s in range(4):
        for a in range(3):
            row = []
            for t in range(4):
                for b in range(3):
                    e = sum(complex(SIGMA[p][s, t]) * f[p, a, b]
                            for p in range(len(PLANES))
                            if SIGMA[p][s, t] != 0)
                    e = c * e + (1.0 if (s, a) == (t, b) else 0.0)
                    row.append(e + jnp.zeros(f.shape[-3:], f.dtype))
            rows.append(jnp.stack(row))
    return jnp.stack(rows)


def invert_site_matrix(a):
    """The inverse of every site's 12 x 12 matrix by Gauss-Jordan
    elimination on [A | 1], elementwise over the lattice: pivot k
    scales row k by 1 / A[k, k] and clears column k from the others."""
    n = a.shape[0]
    eye = jnp.broadcast_to(
        jnp.eye(n, dtype=a.dtype)[:, :, None, None, None], a.shape)
    m = jnp.concatenate([a, eye], axis=1)            # (n, 2n, lattice)
    for k in range(n):
        row = m[k] / m[k, k][None]
        m = m - m[:, k][:, None] * row[None]
        m = m.at[k].set(row)
    return m[:, n:]


def _site_apply(a, v):
    """out[(s, a)] = sum_(s', a') A[(s, a), (s', a')] v[(s', a')] on
    every site of the fields ``v`` (N, 4, 3, T, Z, Y*X): twelve
    broadcast multiplies."""
    w = v.reshape((v.shape[0], 12) + v.shape[-3:])
    out = sum(a[:, j][None] * w[:, j][:, None] for j in range(12))
    return out.reshape(v.shape)


@functools.partial(jax.jit, static_argnames=("nx", "store"))
def terms(links, kappa, nx, store="single"):
    """(folded links, A, A^-1) with every field in ``STORES[store]``;
    the inverse is computed in f32 from the stored A and then stored.
    A program of its own: the operator applications below take its
    result, so the field strength and the elimination compile once."""
    st = STORES[store]
    a = st(site_matrix(st(field_strength(st(links[1]), nx)), kappa))
    return st(links[0]), a, st(invert_site_matrix(a))


def _m_pc(tm, x, kappa, nx, parity, dagger, store):
    """M x (or M^dag x = gamma5 M gamma5 x) of fields on the p sites;
    ``tm`` what ``terms`` returns."""
    u, a, ainv = tm
    st = STORES[store]
    mp = parity_mask(x.shape[-3:], nx, parity)
    mq = 1.0 - mp
    g5 = jnp.asarray(GAMMA5)[:, None, None, None, None]
    hop = jax.vmap(lambda v: _hop(u, v, nx))
    x = st(mp * x)
    v = g5 * x if dagger else x
    t = st(_site_apply(ainv, mq * hop(v)))
    out = mp * (_site_apply(a, v) - (kappa * kappa) * hop(t))
    return st(g5 * out if dagger else out)


def _normal(tm, x, kappa, nx, parity, store):
    return _m_pc(tm, _m_pc(tm, x, kappa, nx, parity, False, store),
                 kappa, nx, parity, True, store)


def rhs_of(tm, b, kappa, nx, store="single"):
    """M^dag b_p of the p-parity half of the harness's source
    (4, 3, T, Z, Y*X): (1, 4, 3, T, Z, Y*X)."""
    return _m_pc(tm, STORES[store](_fields(b)), kappa, nx, PARITY, True,
                 store)


_normal_jit = jax.jit(_normal, static_argnames=("nx", "store"))


def apply_m(links, x, kappa, nx, store="single", parity=PARITY):
    """M^dag M x on the p sites of every field of ``x`` (..., 4, 3, T,
    Z, Y*X), zero on the q sites; Hermitian, so there is no dagger.
    Every field passes through ``STORES[store]``."""
    return _normal_jit(terms(links, kappa, nx, store), _fields(x), kappa,
                       nx, parity, store).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("nx",))
def _shift_residuals(tm, kappa, nx, b, x, offsets):
    x = parity_mask(x.shape[-3:], nx, PARITY) * _fields(x)
    rhs = rhs_of(tm, b, kappa, nx)
    sig = jnp.asarray(offsets, jnp.float32).reshape((-1, 1, 1, 1, 1, 1))
    r = rhs - (_normal(tm, x, kappa, nx, PARITY, "single") + sig * x)
    return jnp.sqrt(jnp.sum(jnp.abs(r) ** 2, axis=(1, 2, 3, 4, 5))
                    / jnp.sum(jnp.abs(rhs) ** 2))


def shift_residuals(links, kappa, nx, b, x, offsets=OFFSETS):
    """||M^dag b_p - (M^dag M + sigma_i) x_i|| / ||M^dag b_p|| for
    every shift: (N,) f32; ``x`` holds rows 4 i + spin = x_i[spin],
    N = len(offsets) (the cell's ``OFFSETS`` unless a test hands
    others)."""
    return _shift_residuals(terms(links, kappa, nx, "single"), kappa, nx,
                            b, x, offsets)


def rel_residual(links, kappa, nx, b, x):
    """The LARGEST of the N shifted residuals (a NaN is the largest) of
    the solution rows ``x`` for the source ``b``, as a Python float: one
    number a call for ``correct.compare``, which every shift has to
    hold."""
    if _fields(x).shape[0] != len(OFFSETS):
        raise ValueError(f"a solution has four rows a shift: "
                         f"{4 * len(OFFSETS)} rows, got {x.shape}")
    return float(jnp.max(shift_residuals(links, kappa, nx, b, x)))


@functools.partial(jax.jit, static_argnames=("nx", "store", "maxiter"))
def solve_normal(links, b, kappa, nx, tol, maxiter, store="single"):
    """Plain CG on (M^dag M + sigma_i) x_i = M^dag b_p, one system a
    shift with CG scalars of its own, in lockstep until every shift is
    under ``tol`` or ``maxiter`` (a shift under ``tol`` stands still
    while the others go on); every vector kept in ``store`` (the
    control: the reference in the program's place, one precision down).
    No shared Krylov space: N plain solves.  Returns (x, iterations)
    with x (N, 4, 3, T, Z, Y*X): ``control.py``'s own ``b - apply_m(x)``
    broadcasts its source against it; that number, the control's own
    claim, is not this system's residual (nothing rests on it)."""
    st = STORES[store]
    tm = terms(links, kappa, nx, store)
    n = len(OFFSETS)
    sig = jnp.asarray(OFFSETS, jnp.float32).reshape((n, 1, 1, 1, 1, 1))

    def op(v):
        return st(_normal(tm, v, kappa, nx, PARITY, store) + sig * v)

    def dot(x, y):
        return jnp.sum(jnp.real(jnp.conj(x) * y), axis=(1, 2, 3, 4, 5),
                       keepdims=True)
    rhs = rhs_of(tm, b, kappa, nx, store)
    rhs = st(jnp.broadcast_to(rhs, (n,) + rhs.shape[1:]))
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
    return x, k
