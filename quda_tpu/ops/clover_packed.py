"""The clover term built lattice-minor: F_munu, the chiral 6x6 blocks
and their inverse on arrays whose MATRIX indices lead and whose minor
axes are the lattice, (..., T, Z, Y*X) full or (..., T, Z, Y*Xh) per
parity.

Why a second construction beside ops/clover.clover_blocks: on a TPU a
trailing (3,3) or (6,6) pads to an (8,128) tile, ~57x as a temporary,
so the canonical construction does not fit 24^4 (F_munu alone 16 GB).
Here every product is an elementwise multiply of site planes (three
broadcast multiplies a 3x3 product, no dot, no batched ``cholesky``),
as ops/wilson_packed does for the hop.  The formulas are the canonical
ones: the leaves are ops/fmunu._leaf_sum itself with the packed shift
and leading-index products handed in, sigma is ops/clover's table;
tests/test_clover_resident.py holds the two constructions together.

Reference behavior: lib/clover_quda.cu (term from F_munu),
lib/clover_invert.cu (per-site Cholesky inverse; here an unpivoted
Gauss-Jordan over the block indices).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import wilson_packed as wpk
from .clover import sigma_blocks_np
from .fmunu import PLANES, _leaf_sum


def _mm(a, b):
    """(3,3,...) x (3,3,...), elementwise over the trailing axes."""
    return sum(a[:, j][:, None] * b[j][None, :] for j in range(3))


def _dag(a):
    return jnp.conj(jnp.swapaxes(a, 0, 1))


def split_eo_packed(a, dims):
    """(..., T, Z, Y*X) -> (even, odd) halves (..., T, Z, Y*Xh), the
    layout rule of fields/spinor.even_odd_split: half-site (t,z,y,xh)
    of parity p is x = 2*xh + ((t+z+y+p) % 2)."""
    T, Z, Y, X = dims
    slot0, slot1 = a[..., 0::2], a[..., 1::2]
    m = jnp.asarray(wpk._slot_mask_packed(T, Z, Y, X // 2, 0))
    return jnp.where(m, slot0, slot1), jnp.where(m, slot1, slot0)


@functools.partial(jax.jit, static_argnames=("dims",))
def field_strength_eo(gauge, dims):
    """Hermitian traceless F_h = -i/8 (Q - Q^dag) of the six planes from
    the canonical (4,T,Z,Y,X,3,3) links, as (even, odd) arrays
    (6, 3, 3, T, Z, Y*Xh).  The links are the physical ones (no fermion
    boundary phase), as in models/clover."""
    _, _, Y, X = dims
    gp = wpk.pack_gauge(gauge)                  # (4,3,3,T,Z,Y*X)
    sh = lambda v, mu, sign: wpk.shift_packed(v, mu, sign, X, Y)
    eye = jnp.eye(3, dtype=gp.dtype)[:, :, None, None, None]
    fs = []
    for mu, nu in PLANES:
        q = _leaf_sum(gp, mu, nu, sh, mat_mul=_mm, dagger=_dag)
        f = -0.125j * (q - _dag(q))
        fs.append(f - (f[0, 0] + f[1, 1] + f[2, 2]) / 3.0 * eye)
    return split_eo_packed(jnp.stack(fs), dims)


@jax.jit
def clover_blocks_packed(f, coeff):
    """A = 1 + coeff * sum_p sigma_p (x) F_p as chiral blocks
    (2, 6, 6, T, Z, Y*Xh), block index i = 3*spin + colour; ``f`` one
    parity of ``field_strength_eo``, ``coeff`` = kappa*csw/2 (an
    operand: a new coefficient reuses the executable)."""
    sig = sigma_blocks_np()                     # (6,2,2,2) host constants
    chir = []
    for ch in range(2):
        rows = []
        for s in range(2):
            cols = []
            for t in range(2):
                # (three of the six planes reach each (s, t) entry)
                cols.append(sum(complex(sig[p, ch, s, t]) * f[p]
                                for p in range(6)
                                if sig[p, ch, s, t] != 0))
            rows.append(jnp.concatenate(cols, axis=1))      # (3,6,...)
        chir.append(jnp.concatenate(rows, axis=0))          # (6,6,...)
    sf = jnp.stack(chir)
    eye = jnp.eye(6, dtype=sf.dtype)[None, :, :, None, None, None]
    return eye + jnp.asarray(coeff, sf.real.dtype) * sf


@jax.jit
def invert_blocks_packed(blocks):
    """Per-site inverse of the Hermitian positive-definite blocks
    (2, 6, 6, ...lattice): Gauss-Jordan in place, one pivot a step,
    no pivoting (the blocks are 1 + a small Hermitian term); each step
    is a rank-one update broadcast over (chirality, lattice), so the
    whole inverse is a few dozen elementwise operations."""
    a = jnp.moveaxis(blocks, 0, 2)              # (6, 6, 2, ...lattice)
    for k in range(a.shape[0]):
        row = a[k].at[k].set(1.0) / a[k, k]
        col = a[:, k].at[k].set(0.0)
        a = a.at[:, k].set(0.0) - col[:, None] * row[None, :]
        a = a.at[k].set(row)
    return jnp.moveaxis(a, 2, 0)


def clover_term_packed(gauge, coeff, dims, matpc: int):
    """(A_p, A_q, A_q^-1) as packed complex blocks (2,6,6,T,Z,Y*Xh),
    p = ``matpc``, q = 1-p: the three stages above composed (jit it to
    get the one construction program; interfaces/quda_api runs the
    stages under its phase timers)."""
    f = field_strength_eo(gauge, dims)
    a = tuple(clover_blocks_packed(f_par, coeff) for f_par in f)
    return a[matpc], a[1 - matpc], invert_blocks_packed(a[1 - matpc])

