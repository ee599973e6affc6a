"""Clover term: construction, application, inversion.

Reference behavior: lib/clover_quda.cu (compute from F_munu), CloverField
compressed chiral-block storage (include/clover_field.h:195,
include/clover_field_order.h), lib/clover_invert.cu (Cholesky inversion).

In the DeGrand-Rossi chiral basis sigma_{mu nu} is block-diagonal over
chirality, so the clover matrix A(x) = 1 + coeff * sum_{mu<nu} sigma_p F_p(x)
splits into two Hermitian 6x6 blocks ((spin within chirality) x color).
Storage here is exactly those blocks: (..., 2, 6, 6) — the uncompressed
form of QUDA's 72-real packed layout; XLA batches the 6x6 algebra
(inverse via Cholesky, matvec via einsum) over all sites.

coeff = kappa * csw / 2 with the conventions of models/clover.py.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import gamma as g
from .fmunu import PLANES, field_strength


def sigma_blocks_np() -> np.ndarray:
    """sigma_{mu nu} chiral blocks for the 6 planes as host constants:
    (6, 2, 2, 2) — [plane, chirality, s, s']."""
    blocks = np.zeros((6, 2, 2, 2), dtype=np.complex128)
    for p, (mu, nu) in enumerate(PLANES):
        s = g.SIGMA[mu, nu]
        assert np.allclose(s[:2, 2:], 0) and np.allclose(s[2:, :2], 0), \
            "sigma must be chiral-block-diagonal in this basis"
        blocks[p, 0] = s[:2, :2]
        blocks[p, 1] = s[2:, 2:]
    return blocks


def _sigma_blocks(dtype):
    return jnp.asarray(sigma_blocks_np(), dtype)


def clover_blocks(gauge: jnp.ndarray, coeff: float,
                  shift_fn=None) -> jnp.ndarray:
    """Build A(x) chiral blocks: (T,Z,Y,X,2,6,6), Hermitian.

    A = 1 + coeff * sum_p sigma_p (x) F_p   (spin (x) color -> 6x6).
    """
    kwargs = {} if shift_fn is None else {"shift_fn": shift_fn}
    f = field_strength(gauge, **kwargs)          # (6,T,Z,Y,X,3,3)
    sig = _sigma_blocks(gauge.dtype)             # (6,2,2,2)
    # (T,Z,Y,X, chir, s, a, s', b) so the reshape groups (s,a) x (s',b)
    sf = jnp.einsum("pcij,p...ab->...ciajb", sig, f)
    lat = sf.shape[:4]
    a = coeff * sf.reshape(lat + (2, 6, 6))
    eye = jnp.eye(6, dtype=gauge.dtype)
    return a + eye


def apply_clover(blocks: jnp.ndarray, psi: jnp.ndarray) -> jnp.ndarray:
    """A psi with psi (..., 4, 3): chirality split, 6x6 matvec, rejoin."""
    lat = psi.shape[:-2]
    chi = psi.reshape(lat + (2, 6))
    out = jnp.einsum("...cij,...cj->...ci", blocks, chi)
    return out.reshape(lat + (4, 3))


def invert_clover(blocks: jnp.ndarray) -> jnp.ndarray:
    """Per-site inverse of the Hermitian 6x6 blocks via Cholesky.

    TPU note: on-device this runs at f32; the MG/clover-PC use cases
    tolerate that, and tests run f64 on CPU.  (QUDA: lib/clover_invert.cu
    cholesky + forward/back substitution per site.)
    """
    import jax.scipy.linalg as jsl
    chol = jnp.linalg.cholesky(blocks)
    eye = jnp.broadcast_to(jnp.eye(6, dtype=blocks.dtype), blocks.shape)
    # solve L L^H X = I  -> X = A^{-1}
    y = jsl.solve_triangular(chol, eye, lower=True)
    return jsl.solve_triangular(
        jnp.conjugate(jnp.swapaxes(chol, -1, -2)), y, lower=False)


def clover_trlog(blocks: jnp.ndarray):
    """log det A summed over sites, per chirality (lib/clover_invert.cu
    trlog, used by HMC).  Returns (trlog_even_chir, trlog_odd_chir)."""
    chol = jnp.linalg.cholesky(blocks)
    diag = jnp.einsum("...ii->...i", chol).real
    logs = 2.0 * jnp.sum(jnp.log(diag), axis=-1)  # (...,2)
    site_axes = tuple(range(logs.ndim - 1))
    return jnp.sum(logs, axis=site_axes)
