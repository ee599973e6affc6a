"""Site-wise BLAS and reductions over lattice fields.

QUDA hand-fuses ~50 axpy-family kernels and update+reduce kernels
(include/blas_quda.h, include/kernels/blas_core.cuh, reduce_core.cuhs) because
CUDA kernels can't fuse across launches.  Under jax.jit XLA performs exactly
that fusion automatically, so this module is a thin, *named* layer kept for
API parity and for the solvers' readability; everything here is safe inside
jit/scan.  Multi-RHS ("multi-BLAS", lib/multi_blas_quda.cu) is a leading
batch axis plus einsum — no instantiation matrix needed.

All reductions return real/complex scalars (0-d arrays).  Global-sum
determinism: XLA reductions are deterministic for a fixed compilation, which
already exceeds QUDA's QUDA_DETERMINISTIC_REDUCE guarantee.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _r(x):
    """Real view used for norm-type reductions (avoids complex mults)."""
    return x.real * x.real + x.imag * x.imag


# -- reductions -------------------------------------------------------------

def norm2(x):
    return jnp.sum(_r(x))


def cdot(x, y):
    """<x, y> = sum conj(x) y (blas::cDotProduct)."""
    return jnp.sum(jnp.conjugate(x) * y)


def redot(x, y):
    """Re<x, y> (blas::reDotProduct)."""
    return jnp.sum(x.real * y.real + x.imag * y.imag)


def cdot_norm_b(x, y):
    """(<x,y>, |y|^2) fused (blas::cDotProductNormB)."""
    return cdot(x, y), norm2(y)


def xmy_norm(x, y):
    """y <- x - y; return |new y|^2 (blas::xmyNorm)."""
    out = x - y
    return out, norm2(out)


def heavy_quark_residual_norm(x, r):
    """Volume-averaged site-wise |r|^2/|x|^2 (blas::HeavyQuarkResidualNorm).

    Reference: include/kernels/reduce_core.cuh HeavyQuarkResidualNorm_;
    returns (|x|^2, |r|^2, sum_sites |r(x)|^2/|x(x)|^2 / volume).
    """
    site_axes = tuple(range(x.ndim - 2, x.ndim))
    xs = jnp.sum(_r(x), axis=site_axes)
    rs = jnp.sum(_r(r), axis=site_axes)
    ratio = jnp.where(xs > 0, rs / jnp.where(xs > 0, xs, 1.0), 1.0)
    vol = ratio.size
    return norm2(x), norm2(r), jnp.sum(ratio) / vol


# -- compensated reductions -------------------------------------------------
# The dbldbl.h analog (include/dbldbl.h via include/reduce_helper.h): global
# sums whose accumulation error is O(eps^2 log n) instead of the plain-sum
# O(eps sqrt(n)) — used wherever a reported residual must be trusted below
# the f32 accumulation floor (reliable updates, final true_res).  f64
# inputs already exceed that floor and keep the plain reduction.

def _needs_comp(x) -> bool:
    return x.dtype not in (jnp.float64, jnp.complex128)


def norm2_comp(x):
    """|x|^2 with two_prod/two_sum compensation (f32-class inputs)."""
    if not _needs_comp(x):
        return norm2(x)
    from . import df64 as dfm
    v = jnp.stack([x.real, x.imag]) if jnp.iscomplexobj(x) else x
    return dfm.to_f32(dfm.norm2_f32(v))


def cdot_comp(x, y):
    """<x, y> with compensation; returns a complex scalar."""
    if not _needs_comp(x):
        return cdot(x, y)
    from . import df64 as dfm
    re = dfm.add(dfm.dot_f32(x.real, y.real), dfm.dot_f32(x.imag, y.imag))
    im = dfm.sub(dfm.dot_f32(x.real, y.imag), dfm.dot_f32(x.imag, y.real))
    return jax.lax.complex(dfm.to_f32(re), dfm.to_f32(im))


# -- axpy family ------------------------------------------------------------

def axpy(a, x, y):
    return a * x + y


def xpay(x, a, y):
    return x + a * y


def axpby(a, x, b, y):
    return a * x + b * y


def caxpy(a, x, y):
    return a * x + y


def caxpby(a, x, b, y):
    return a * x + b * y


def axpy_zpbx(a, p, x, r, b):
    """Fused CG tail: x <- x + a p ; p <- r + b p (blas::axpyZpbx)."""
    return x + a * p, r + b * p


def axpy_norm2(a, x, y):
    """y <- y + a x; return (y, |y|^2) (blas::axpyNorm2).

    Under jit XLA fuses the update with the reduction into one traversal;
    the explicit single-VMEM-pass pallas version lives in
    ops/blas_pallas.py (reference include/kernels/reduce_core.cuh:668).
    """
    out = y + a * x
    return out, norm2(out)


def triple_cg_update(a, p, Ap, x, r):
    """x += a p; r -= a Ap; return (x, r, |r|^2) — the fused CG-iteration
    tail (blas::axpyNorm-style): both updates and the residual reduction
    share one traversal under jit."""
    xn = x + a * p
    rn = r - a * Ap
    return xn, rn, norm2(rn)


# -- multi-RHS (block) ops --------------------------------------------------

def block_cdot(xs, ys):
    """Gram block <x_i, y_j> for stacked fields (N, site..., s, c).

    QUDA multi_reduce (lib/multi_reduce_quda.cu cDotProduct block) — here a
    single einsum that XLA maps onto the MXU.
    """
    n = xs.shape[0]
    return jnp.einsum("i...,j...->ij", jnp.conjugate(xs), ys)


def block_caxpy(alpha, xs, ys):
    """y_j += sum_i alpha[i,j] x_i (lib/multi_blas_quda.cu caxpy)."""
    return ys + jnp.einsum("ij,i...->j...", alpha, xs)
