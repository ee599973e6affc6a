"""SU(N) matrix utilities: random links, projection, exponential map.

Covers what QUDA spreads across lib/gauge_random.cu (Gaussian momenta /
random links), include/svd_quda.h + lib/unitarize_links_quda.cu
(reunitarization), and the exponentiation inside lib/gauge_update_quda.cu.
All functions are batched over arbitrary leading axes — fields pass their
(T,Z,Y,X) site axes straight through; XLA maps the small (3,3) algebra onto
the VPU/MXU without per-site loops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Gell-Mann matrices (su(3) generators, T_a = lambda_a / 2).
import numpy as np

_l = np.zeros((8, 3, 3), dtype=np.complex128)
_l[0, 0, 1] = _l[0, 1, 0] = 1
_l[1, 0, 1] = -1j
_l[1, 1, 0] = 1j
_l[2, 0, 0] = 1
_l[2, 1, 1] = -1
_l[3, 0, 2] = _l[3, 2, 0] = 1
_l[4, 0, 2] = -1j
_l[4, 2, 0] = 1j
_l[5, 1, 2] = _l[5, 2, 1] = 1
_l[6, 1, 2] = -1j
_l[6, 2, 1] = 1j
_l[7, 0, 0] = _l[7, 1, 1] = 1 / np.sqrt(3)
_l[7, 2, 2] = -2 / np.sqrt(3)
GELL_MANN = _l


# -- representation dispatch ------------------------------------------------
#
# Every primitive below is POLYMORPHIC over two matrix representations:
#   complex  (..., N, N)      — the canonical fields
#   pairs    (..., N, N, 2)   — real re/im pair arrays, the representation
#                               TPU runtimes without complex64 execute
# so the gauge-sector formulas written on top of them (staples, fattening,
# plaquettes, AD forces — gauge/*.py) run unchanged in either.  The pair
# recipes follow ops/pair.py; Hermitian matrix functions go through the
# interleaved real embedding (ops/pair.interleave_mat).

def is_pairs(m: jnp.ndarray) -> bool:
    """True iff m is a pair-form matrix field (..., N, N, 2)."""
    return (not jnp.issubdtype(m.dtype, jnp.complexfloating)
            and m.ndim >= 3 and m.shape[-1] == 2
            and m.shape[-2] == m.shape[-3])


def dagger(m: jnp.ndarray) -> jnp.ndarray:
    """Hermitian conjugate over the trailing (c,c) axes."""
    if is_pairs(m):
        mt = jnp.swapaxes(m, -3, -2)
        return jnp.stack([mt[..., 0], -mt[..., 1]], axis=-1)
    return jnp.conjugate(jnp.swapaxes(m, -1, -2))


def mat_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    if is_pairs(a):
        ar, ai = a[..., 0], a[..., 1]
        br, bi = b[..., 0], b[..., 1]
        re = (jnp.einsum("...ab,...bc->...ac", ar, br)
              - jnp.einsum("...ab,...bc->...ac", ai, bi))
        im = (jnp.einsum("...ab,...bc->...ac", ar, bi)
              + jnp.einsum("...ab,...bc->...ac", ai, br))
        return jnp.stack([re, im], axis=-1)
    return jnp.einsum("...ab,...bc->...ac", a, b)


def trace(m: jnp.ndarray) -> jnp.ndarray:
    """Complex trace: a complex scalar, or a (..., 2) pair scalar."""
    if is_pairs(m):
        return jnp.einsum("...aap->...p", m)
    return jnp.einsum("...aa->...", m)


def re_trace(m: jnp.ndarray) -> jnp.ndarray:
    """Re tr m as a plain real array in BOTH representations (use this
    instead of trace(m).real, which silently keeps the pair axis)."""
    if is_pairs(m):
        return jnp.einsum("...aa->...", m[..., 0])
    return jnp.real(jnp.einsum("...aa->...", m))


def mat_i(m: jnp.ndarray) -> jnp.ndarray:
    """i * m in either representation (a bare ``1j *`` would silently
    promote a pair array to complex)."""
    if is_pairs(m):
        return jnp.stack([-m[..., 1], m[..., 0]], axis=-1)
    return 1j * m


def eye_like(m: jnp.ndarray) -> jnp.ndarray:
    """Identity matrix broadcast to m's shape, in m's representation."""
    if is_pairs(m):
        n = m.shape[-2]
        e = jnp.zeros((n, n, 2), m.dtype).at[:, :, 0].set(jnp.eye(n, dtype=m.dtype))
        return jnp.broadcast_to(e, m.shape)
    return jnp.broadcast_to(jnp.eye(m.shape[-1], dtype=m.dtype), m.shape)


def random_hermitian_traceless(key, shape, n=3, dtype=jnp.complex128):
    """Gaussian traceless Hermitian matrices H = sum_a xi_a T_a, xi~N(0,1).

    This is the HMC momentum distribution (reference: lib/gauge_random.cu
    gaussGaugeQuda with the momentum flag).  A FLOATING dtype requests the
    pair representation (..., 3, 3, 2) — the generators' re/im parts are
    real constants, so the momenta are sampled complex-free.
    """
    if jnp.issubdtype(dtype, jnp.floating):
        xi = jax.random.normal(key, shape + (8,), dtype=dtype)
        gen = jnp.asarray(
            np.stack([GELL_MANN.real, GELL_MANN.imag], axis=-1) / 2.0,
            dtype=dtype)
        return jnp.einsum("...a,aijp->...ijp", xi, gen)
    real_dtype = jnp.real(jnp.zeros((), dtype)).dtype
    xi = jax.random.normal(key, shape + (8,), dtype=real_dtype)
    gen = jnp.asarray(GELL_MANN / 2.0, dtype=dtype)
    return jnp.einsum("...a,aij->...ij", xi.astype(dtype), gen)


def expm_su3(h: jnp.ndarray, order: int = 16) -> jnp.ndarray:
    """exp(i h) for (batched) Hermitian h via scaling-and-squaring Taylor.

    Used for the HMC gauge update U <- exp(i eps p) U (reference:
    lib/gauge_update_quda.cu, kernels/gauge_update.cuh) and stout smearing.
    A fixed 6-squaring/Taylor scheme is exact to machine precision for the
    step sizes HMC uses and is branch-free (jit/TPU friendly).  Works on
    complex or pair-form h (mat_i/eye_like/mat_mul are polymorphic).
    """
    x = mat_i(h) / (2.0 ** 6)
    eye = eye_like(h)
    term = eye
    acc = eye
    for k in range(1, order):
        term = mat_mul(term, x) / k
        acc = acc + term
    for _ in range(6):
        acc = mat_mul(acc, acc)
    return acc


def random_su3(key, shape, dtype=jnp.complex128, scale: float = 1.0):
    """Random SU(3) links: exp(i * scale * H) with H Gaussian in su(3).

    scale ~ 0.5-1 gives a "hot" disordered configuration; small scale gives
    links near identity (QUDA tests' weak-field configs,
    tests/utils/host_utils.cpp:1022 constructs random SU(3) similarly).
    """
    h = random_hermitian_traceless(key, shape, dtype=dtype)
    return expm_su3(scale * h)


def det3_pairs(m: jnp.ndarray) -> jnp.ndarray:
    """det of a (..., 3, 3, 2) pair matrix as a (..., 2) pair scalar."""
    def cmul(x, y):
        return jnp.stack([x[..., 0] * y[..., 0] - x[..., 1] * y[..., 1],
                          x[..., 0] * y[..., 1] + x[..., 1] * y[..., 0]],
                         axis=-1)
    a, b, c = m[..., 0, 0, :], m[..., 0, 1, :], m[..., 0, 2, :]
    d, e, f = m[..., 1, 0, :], m[..., 1, 1, :], m[..., 1, 2, :]
    g, h, i = m[..., 2, 0, :], m[..., 2, 1, :], m[..., 2, 2, :]
    return (cmul(a, cmul(e, i) - cmul(f, h))
            - cmul(b, cmul(d, i) - cmul(f, g))
            + cmul(c, cmul(d, h) - cmul(e, g)))


def inv_sqrt_herm3_pairs(h: jnp.ndarray) -> jnp.ndarray:
    """H^{-1/2} for a (..., 3, 3, 2) pair-form Hermitian positive-definite
    matrix, by Cayley-Hamilton: f(H) = a0 I + a1 H + a2 H^2 with the a_i
    solved from f(lambda_i) = lambda_i^{-1/2} at the three eigenvalues,
    which come from Cardano's trigonometric form on the (real) invariants.

    This is the reference's own recipe (lib/unitarize_links_quda.cu,
    include/svd_quda.h use Cayley-Hamilton + closed-form roots) and —
    unlike an eigh of the interleaved 6x6 embedding, whose eigenvalues are
    exactly doubled — it is cleanly DIFFERENTIABLE: jax.grad flows through
    real scalar arithmetic only, so the HISQ force works in pair form.
    """
    h2 = mat_mul(h, h)
    tr1 = re_trace(h)
    tr2 = re_trace(h2)
    d = det3_pairs(h)[..., 0]            # det of Hermitian h is real
    # characteristic polynomial: l^3 + a l^2 + b l + c
    a = -tr1
    b = 0.5 * (tr1 * tr1 - tr2)
    c = -d
    # depressed cubic x^3 + p x + r with l = x - a/3
    p = b - a * a / 3.0
    r = 2.0 * a ** 3 / 27.0 - a * b / 3.0 + c
    # three real roots (H Hermitian): trigonometric method.  p = r = 0
    # exactly when the spectrum is fully degenerate (h = c*I: the unit
    # cold-start gauge!) — guard the 0/0 with a safe denominator so both
    # the value AND the gradient stay finite (jnp.where alone would leak
    # NaN through the untaken branch's gradient).
    m = 2.0 * jnp.sqrt(jnp.maximum(-p / 3.0, 1e-30))
    pm = p * m
    # RELATIVE near-degeneracy test (pm scales as (mean eigenvalue *
    # relative spread)^3): an absolute test leaves a band where
    # d(r/pm)/d(pm) ~ r/pm^2 overflows to inf in f32 and the clipped
    # arccos turns it into 0 * inf = NaN in the force
    s_mean = jnp.maximum(tr1 / 3.0, 1e-30)
    degenerate = jnp.abs(pm) < 1e-9 * s_mean ** 3
    arg_raw = 3.0 * r / jnp.where(degenerate, 1.0, pm)
    arg = jnp.clip(jnp.where(degenerate, 0.0, arg_raw),
                   -1.0 + 1e-7, 1.0 - 1e-7)   # keep arccos' finite
    theta = jnp.arccos(arg) / 3.0
    two_pi_3 = 2.0 * jnp.pi / 3.0
    lams = [jnp.maximum(m * jnp.cos(theta - k * two_pi_3) - a / 3.0,
                        1e-18) for k in range(3)]

    # f(H) = f(l0) I + f[l0,l1](H - l0) + f[l0,l1,l2](H - l0)(H - l1)
    # via Newton divided differences with CONFLUENT limits: when two
    # eigenvalues collide the difference quotient smoothly becomes the
    # derivative, so degenerate and near-degenerate spectra (where a
    # Vandermonde solve is singular) are exact instead of NaN.
    def f(l):
        return 1.0 / jnp.sqrt(l)

    def df(l):                           # f'
        return -0.5 * l ** -1.5

    def ddf_half(l):                     # f''/2
        return 0.375 * l ** -2.5

    def dd1(la, lb):
        diff = la - lb
        near = jnp.abs(diff) < 1e-6 * (la + lb)
        safe = jnp.where(near, 1.0, diff)
        return jnp.where(near, df(0.5 * (la + lb)),
                         (f(la) - f(lb)) / safe)

    l0, l1, l2 = lams
    d01 = dd1(l0, l1)
    d12 = dd1(l1, l2)
    diff02 = l0 - l2
    near02 = jnp.abs(diff02) < 1e-6 * (l0 + l2)
    safe02 = jnp.where(near02, 1.0, diff02)
    d012 = jnp.where(near02, ddf_half((l0 + l1 + l2) / 3.0),
                     (d01 - d12) / safe02)

    def sc(x):
        return x[..., None, None, None]

    eye = eye_like(h)
    h_l0 = h - sc(l0) * eye
    h_l1 = h - sc(l1) * eye
    return (sc(f(l0)) * eye + sc(d01) * h_l0
            + sc(d012) * mat_mul(h_l0, h_l1))


def unitarity_deviation(u: jnp.ndarray) -> jnp.ndarray:
    """max over links of max_ij |(U U^dag - I)_ij| — the load-time
    unitarity screen (load_gauge_quda's QUDA_TPU_GAUGE_UNITARITY_TOL
    gate).  A deviating-but-finite gauge can be repaired with
    :func:`project_su3` (update_gauge_field_quda's reunitarize path);
    this helper only measures, so the screen stays a warning."""
    eye = jnp.eye(3, dtype=u.dtype)
    d = jnp.einsum("...ab,...cb->...ac", u, jnp.conjugate(u)) - eye
    return jnp.max(jnp.abs(d))


def project_su3(u: jnp.ndarray, iters: int = 2) -> jnp.ndarray:
    """Project a near-SU(3) matrix back onto SU(3).

    Polar-type projection: W = U (U^dag U)^{-1/2} via Newton iteration for
    the inverse square root, then fix det to 1 by phase division.  This is
    the TPU-friendly replacement for QUDA's SVD-based reunitarization
    (include/svd_quda.h:616) for links that are already close to unitary
    (smearing / gauge updates).  HISQ force differentiation uses its own
    routine in gauge/hisq.py.  Pair-form inputs run complex-free: inverses
    through the interleaved real embedding, the det phase by angle/3.
    """
    from .pair import deinterleave_mat, interleave_mat
    pairs = is_pairs(u)
    w = u
    for _ in range(iters + 2):
        # Newton iteration for polar decomposition: w <- 0.5 (w + w^-dag)
        if pairs:
            winv = deinterleave_mat(jnp.linalg.inv(
                interleave_mat(dagger(w))))
        else:
            winv = jnp.linalg.inv(dagger(w))
        w = 0.5 * (w + winv)
    if pairs:
        det = det3_pairs(w)
        # det is (close to) unit modulus; det^{-1/3} = r^{-1/3} e^{-i a/3}
        r = jnp.sqrt(det[..., 0] ** 2 + det[..., 1] ** 2)
        ang = jnp.arctan2(det[..., 1], det[..., 0])
        mag = r ** (-1.0 / 3.0)
        ph = jnp.stack([mag * jnp.cos(ang / 3.0),
                        -mag * jnp.sin(ang / 3.0)], axis=-1)
        wr, wi = w[..., 0], w[..., 1]
        pr = ph[..., None, None, 0]
        pi = ph[..., None, None, 1]
        return jnp.stack([wr * pr - wi * pi, wr * pi + wi * pr], axis=-1)
    det = jnp.linalg.det(w)
    phase = det ** (-1.0 / 3.0)
    return w * phase[..., None, None]


def unit_gauge(shape, dtype=jnp.complex128):
    """Identity links; a floating dtype gives the pair representation."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        e = jnp.zeros((3, 3, 2), dtype).at[:, :, 0].set(
            jnp.eye(3, dtype=dtype))
        return jnp.broadcast_to(e, shape + (3, 3, 2))
    return jnp.broadcast_to(jnp.eye(3, dtype=dtype), shape + (3, 3))


def compress8(u: jnp.ndarray) -> jnp.ndarray:
    """Reconstruct-8 storage (QUDA QUDA_RECONSTRUCT_8,
    include/gauge_field_order.h Reconstruct<8>, arXiv:0911.3191): eight
    reals per SU(3) link.  Works on the row-swapped matrix
    M = {{u1},{u0},{-u2}} (det M = det U; avoids the unit-gauge
    singularity): stores arg(M00)/pi, arg(M20)/pi, and the complex
    M01, M02, M10.  (..., 3, 3) complex -> (..., 8) real."""
    m00 = u[..., 1, 0]
    m20 = -u[..., 2, 0]
    out = jnp.stack([
        jnp.arctan2(m00.imag, m00.real) / jnp.pi,
        jnp.arctan2(m20.imag, m20.real) / jnp.pi,
        u[..., 1, 1].real, u[..., 1, 1].imag,
        u[..., 1, 2].real, u[..., 1, 2].imag,
        u[..., 0, 0].real, u[..., 0, 0].imag,
    ], axis=-1)
    return out            # already u's real dtype


def reconstruct8(r: jnp.ndarray, dtype=jnp.complex64) -> jnp.ndarray:
    """Inverse of compress8 (valid for SU(3); u0 = 1, boundary phases
    NOT folded — fold after reconstruction).  (..., 8) -> (..., 3, 3)."""
    m01 = (r[..., 2] + 1j * r[..., 3]).astype(dtype)
    m02 = (r[..., 4] + 1j * r[..., 5]).astype(dtype)
    m10 = (r[..., 6] + 1j * r[..., 7]).astype(dtype)
    ph0 = jnp.exp(1j * jnp.pi * r[..., 0]).astype(dtype)
    ph2 = jnp.exp(1j * jnp.pi * r[..., 1]).astype(dtype)
    row_sum = (jnp.abs(m01) ** 2 + jnp.abs(m02) ** 2).real
    m00_mag = jnp.sqrt(jnp.maximum(1.0 - row_sum, 0.0))
    m00 = ph0 * m00_mag.astype(dtype)
    col_sum = (jnp.abs(m00) ** 2 + jnp.abs(m10) ** 2).real
    m20 = ph2 * jnp.sqrt(jnp.maximum(1.0 - col_sum, 0.0)).astype(dtype)
    r_inv2 = (1.0 / jnp.maximum(row_sum, 1e-30)).astype(dtype)
    a = jnp.conjugate(m00) * m10
    m11 = -(jnp.conjugate(m20) * jnp.conjugate(m02) + a * m01) * r_inv2
    m12 = (jnp.conjugate(m20) * jnp.conjugate(m01) - a * m02) * r_inv2
    b = jnp.conjugate(m00) * m20
    m21 = (jnp.conjugate(m10) * jnp.conjugate(m02) - b * m01) * r_inv2
    m22 = -(jnp.conjugate(m10) * jnp.conjugate(m01) + b * m02) * r_inv2
    row0 = jnp.stack([m00, m01, m02], axis=-1)
    row1 = jnp.stack([m10, m11, m12], axis=-1)
    row2 = jnp.stack([m20, m21, m22], axis=-1)
    # undo the row swap: U = {{m1}, {m0}, {-m2}}
    return jnp.stack([row1, row0, -row2], axis=-2)


def compress13(w: jnp.ndarray, scale: float):
    """Reconstruct-13 (QUDA Reconstruct<13>, staggered long links):
    the link is scale * V with V in SU(3) (HISQ Naik links are scaled
    products of unitarized links) — store V's first two rows + the
    global scale.  Returns ((..., 2, 3) complex, scale)."""
    return compress12(w / scale), float(scale)


def reconstruct13(r, scale: float) -> jnp.ndarray:
    return scale * reconstruct12(r)


def compress9(w: jnp.ndarray, scale: float):
    """Reconstruct-9 (QUDA Reconstruct<9>): recon-8 of V = w / scale
    plus the global scale.  Returns ((..., 8) real, scale)."""
    return compress8(w / scale), float(scale)


def reconstruct9(r, scale: float, dtype=jnp.complex64) -> jnp.ndarray:
    return scale * reconstruct8(r, dtype)


def compress12(u: jnp.ndarray) -> jnp.ndarray:
    """Reconstruct-12 storage: keep the first two rows of an SU(3) link
    (QUDA QUDA_RECONSTRUCT_12, include/gauge_field_order.h Reconstruct<12>).
    (..., 3, 3) -> (..., 2, 3); bandwidth 12/18 of full storage."""
    return u[..., :2, :]


def reconstruct12(r: jnp.ndarray) -> jnp.ndarray:
    """Rebuild the third row: row2 = conj(row0 x row1) (valid for SU(3):
    unitarity + det 1).  (..., 2, 3) -> (..., 3, 3)."""
    a, b = r[..., 0, :], r[..., 1, :]
    c = jnp.conjugate(jnp.cross(a, b))
    return jnp.concatenate([r, c[..., None, :]], axis=-2)
