"""Complex-free multigrid: the MG hierarchy on re/im pair arrays.

Reference behavior: lib/multigrid.cpp (the hierarchy this realifies),
lib/transfer.cpp, lib/coarse_op.in.cu.  QUDA runs MG in complex
arithmetic; the pallas kernels and the bf16 storage codecs consume real
arrays, so this module re-poses the identical hierarchy over the
REALIFICATION of every object:

* chiral fields   (lat, 2, K)     complex -> (lat, 2, K, 2)     real
* transfer V      (latc, 2, D, N) complex -> (latc, 2, D, N, 2) real
* coarse links    (latc, Nc, Nc)  complex -> (latc, Nc, Nc, 2)  real

Complex products become explicit 4-einsum pair products (the MXU-native
complex multiply, same recipe as ops/pair.py).  The one genuinely
complex-structured step — block orthonormalisation of the null vectors —
uses Cholesky-QR on the INTERLEAVED real embedding: mapping each complex
entry g to the 2x2 real block [[re,-im],[im,re]] is a ring homomorphism
C -> R^{2x2} that sends Hermitian-positive-definite to symmetric-positive-
definite and lower-triangular (real positive diagonal) to lower-
triangular, so by Cholesky uniqueness the REAL Cholesky of the embedded
Gram matrix IS the embedding of the complex Cholesky.  Two passes
(CholQR2) restore f32 orthonormality to working precision.

Krylov pieces (null-vector CG, MR/GCR smoothers, the outer GCR) run the
existing dtype-generic solvers directly on the pair arrays: a real-
coefficient Krylov method on the realified operator (the eig/pair_eig.py
trick).  The V-cycle, probing construction, and verify() are inherited
from mg/mg.py via its layout hooks — the hierarchy logic is written once.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..fields.geometry import axis_of_mu
from ..ops import blas
from ..ops import gamma as g
from ..ops.pair import (color_mul_pairs, dagger_pairs,
                        deinterleave_mat as _deinterleave,
                        interleave_mat as _interleave, spin_mul_pairs,
                        to_pairs)
from ..ops.shift import shift
from .coarse import DIRS
from .mg import MG, MGLevelParam, parity_eps

F32 = jnp.float32


# -- chiral pair layout -----------------------------------------------------

def to_chiral_pairs(psi: jnp.ndarray) -> jnp.ndarray:
    """(lat..., 4, 3, 2) -> (lat..., 2, 6, 2)."""
    lat = psi.shape[:-3]
    return psi.reshape(lat + (2, 6, 2))


def from_chiral_pairs(psi: jnp.ndarray) -> jnp.ndarray:
    lat = psi.shape[:-3]
    return psi.reshape(lat + (4, 3, 2))


# -- pair linear algebra ----------------------------------------------------

def _pair_ein(spec: str, a: jnp.ndarray, b: jnp.ndarray,
              conj_a: bool = False) -> jnp.ndarray:
    """Complex einsum on (..., 2) pair arrays: one spec, four real
    einsums, f32 accumulation."""
    ar, ai = a[..., 0], a[..., 1]
    if conj_a:
        ai = -ai
    br, bi = b[..., 0], b[..., 1]
    import functools
    ein = functools.partial(jnp.einsum, spec, preferred_element_type=F32)
    re = ein(ar, br) - ein(ai, bi)
    im = ein(ar, bi) + ein(ai, br)
    return jnp.stack([re, im], axis=-1)




def _cholqr_pass(cols: jnp.ndarray) -> jnp.ndarray:
    """One Cholesky-QR pass on (..., D, N, 2) pair columns."""
    n = cols.shape[-2]
    gram = _pair_ein("...dn,...dm->...nm", cols, cols, conj_a=True)
    emb = _interleave(gram)
    chol = jnp.linalg.cholesky(emb)
    eye = jnp.broadcast_to(jnp.eye(2 * n, dtype=chol.dtype), chol.shape)
    linv = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    w = dagger_pairs(_deinterleave(linv))          # (..., N, N, 2): L^-dag
    return _pair_ein("...dn,...nm->...dm", cols, w)


def cholqr2(cols: jnp.ndarray) -> jnp.ndarray:
    """Orthonormalise complex columns given as (..., D, N, 2) pairs.
    Two Cholesky-QR passes (CholQR2) for f32-grade orthonormality."""
    return _cholqr_pass(_cholqr_pass(cols))


# -- transfer ---------------------------------------------------------------

def _block_fields_pairs(fields: jnp.ndarray, block):
    """(B, T,Z,Y,X, 2, K, 2) -> (B, Tc,Zc,Yc,Xc, 2, D, 2)."""
    Bn, T, Z, Y, X, two, K, _ = fields.shape
    bt, bz, by, bx = block
    r = fields.reshape(Bn, T // bt, bt, Z // bz, bz, Y // by, by,
                       X // bx, bx, two, K, 2)
    r = r.transpose(0, 1, 3, 5, 7, 9, 2, 4, 6, 8, 10, 11)
    return r.reshape(Bn, T // bt, Z // bz, Y // by, X // bx, two,
                     bt * bz * by * bx * K, 2)


def _unblock_fields_pairs(blocked: jnp.ndarray, block, fine_shape, K):
    Bn = blocked.shape[0]
    T, Z, Y, X = fine_shape
    bt, bz, by, bx = block
    r = blocked.reshape(Bn, T // bt, Z // bz, Y // by, X // bx, 2,
                        bt, bz, by, bx, K, 2)
    r = r.transpose(0, 1, 6, 2, 7, 3, 8, 4, 9, 5, 10, 11)
    return r.reshape(Bn, T, Z, Y, X, 2, K, 2)


@dataclasses.dataclass
class PairTransfer:
    """Block transfer on pair arrays (realified mg/transfer.Transfer).

    v: (Tc,Zc,Yc,Xc, 2, D, N, 2) orthonormal complex aggregates as pairs.
    """

    v: jnp.ndarray
    block: Tuple[int, int, int, int]
    fine_shape: Tuple[int, int, int, int]
    k_fine: int
    n_vec: int

    @classmethod
    def from_null_vectors(cls, null_vecs: jnp.ndarray,
                          block) -> "PairTransfer":
        """null_vecs: (N, T,Z,Y,X, 2, K, 2) pair chiral fields."""
        n, T, Z, Y, X, two, K, _ = null_vecs.shape
        bt, bz, by, bx = block
        assert T % bt == 0 and Z % bz == 0 and Y % by == 0 and X % bx == 0, \
            (null_vecs.shape, block)
        blocked = _block_fields_pairs(null_vecs, block)
        cols = jnp.moveaxis(blocked, 0, -2)         # (latc, 2, D, N, 2)
        return cls(cholqr2(cols), tuple(block), (T, Z, Y, X), K, n)

    @classmethod
    def from_complex(cls, transfer) -> "PairTransfer":
        """Realify an existing complex Transfer (e.g. CPU-built setup
        migrating to a complex-free runtime)."""
        return cls(to_pairs(transfer.v, F32), tuple(transfer.block),
                   tuple(transfer.fine_shape), transfer.k_fine,
                   transfer.n_vec)

    @property
    def coarse_shape(self):
        T, Z, Y, X = self.fine_shape
        bt, bz, by, bx = self.block
        return (T // bt, Z // bz, Y // by, X // bx)

    def restrict(self, fine: jnp.ndarray) -> jnp.ndarray:
        """(T,Z,Y,X,2,K,2) -> (Tc,Zc,Yc,Xc,2,N,2): R = V^dag aggregate."""
        blocked = _block_fields_pairs(fine[None], self.block)[0]
        return _pair_ein("...dn,...d->...n", self.v, blocked, conj_a=True)

    def prolong(self, coarse: jnp.ndarray) -> jnp.ndarray:
        """(Tc,Zc,Yc,Xc,2,N,2) -> (T,Z,Y,X,2,K,2)."""
        blocked = _pair_ein("...dn,...n->...d", self.v, coarse)
        return _unblock_fields_pairs(blocked[None], self.block,
                                     self.fine_shape, self.k_fine)[0]


# -- coarse operator --------------------------------------------------------

@dataclasses.dataclass
class PairCoarseOperator:
    """Nearest-neighbour coarse stencil on (latc, 2, N, 2) pair fields
    (realified mg/coarse.CoarseOperator).

    ``use_embedding=True`` applies each link as ONE real
    (2Nc, 2Nc) matmul on the interleaved embedding instead of four
    (Nc, Nc) einsums: identical flops (a complex matvec is 4 Nc^2 real
    multiplies either way) but a single, larger MXU contraction per
    link — the shape the systolic array wants.  Embedded links are
    built lazily and cached.
    """

    x_diag: jnp.ndarray                      # (latc, Nc, Nc, 2)
    y: Dict[Tuple[int, int], jnp.ndarray]    # (mu,sign) -> (latc, Nc, Nc, 2)
    n_vec: int
    g5_hermitian: bool = True
    use_embedding: bool = False
    identity_diag: bool = False              # Yhat form (yhat_links)
    # fused single-pass coarse stencil (ops/coarse_pallas.py): diag +
    # all 8 hops in one kernel launch over the embedded links — raced
    # against the einsum/embedding forms via QUDA_TPU_MG_COARSE_FORM
    # (resolve_coarse_form); interpret only drives off-chip tests
    use_pallas: bool = False
    pallas_interpret: bool = False

    @property
    def nc(self):
        return 2 * self.n_vec

    def _flat(self, v):
        return v.reshape(v.shape[:4] + (self.nc, 2))

    def _unflat(self, v):
        return v.reshape(v.shape[:4] + (2, self.n_vec, 2))

    def _emb(self, key):
        cache = self.__dict__.setdefault("_emb_cache", {})
        if key not in cache:
            m = self.x_diag if key == "diag" else self.y[key]
            cache[key] = _interleave(m)      # (latc, 2Nc, 2Nc)
        return cache[key]

    def _apply(self, key, f):
        """One coarse link application on the flat (latc, Nc, 2) field."""
        if self.use_embedding:
            # vector pairs -> interleaved (.., 2Nc): (re0, im0, re1, ..)
            fi = f.reshape(f.shape[:4] + (self.nc * 2,))
            out = jnp.einsum("...ab,...b->...a", self._emb(key), fi)
            return out.reshape(f.shape)
        m = self.x_diag if key == "diag" else self.y[key]
        return _pair_ein("...ab,...b->...a", m, f)

    def diag(self, v):
        if self.identity_diag:
            return v            # Yhat form: M_hat = v + sum(hops)
        return self._unflat(self._apply("diag", self._flat(v)))

    def hop(self, v, mu, sign):
        f = self._flat(v)
        nbr = jnp.roll(f, -sign, axis=axis_of_mu(mu))
        return self._unflat(self._apply((mu, sign), nbr))

    def _pl_links(self):
        """(9, S, E, E) embedded link stack [diag, *DIRS] for the fused
        pallas apply (built lazily, cached like the embeddings).  The
        per-direction embeddings are interleaved directly — NOT via
        ``_emb`` — so the pallas form holds one resident stack, not the
        stack plus 9 dead per-key copies the apply path never reads."""
        cache = self.__dict__.setdefault("_emb_cache", {})
        if "_pl_links" not in cache:
            mats = [_interleave(self.x_diag)] + \
                [_interleave(self.y[d]) for d in DIRS]
            e = 2 * self.nc
            cache["_pl_links"] = jnp.stack(mats).reshape(9, -1, e, e)
        return cache["_pl_links"]

    def _pallas_apply(self, v):
        """Fused single-pass coarse M (ops/coarse_pallas.py): the input
        and its 8 pre-rolled neighbour copies stream once through the
        kernel against the resident embedded link stack."""
        from ..ops.coarse_pallas import coarse_apply_pallas
        f = self._flat(v)
        latc = f.shape[:4]
        e = 2 * self.nc
        fi = f.reshape(latc + (e,))            # interleaved (re0,im0,..)
        rolls = [fi] + [jnp.roll(fi, -sign, axis_of_mu(mu))
                        for mu, sign in DIRS]
        psi9 = jnp.stack(rolls).reshape(9, -1, e)
        out = coarse_apply_pallas(self._pl_links(), psi9,
                                  interpret=self.pallas_interpret)
        return self._unflat(out.reshape(latc + (self.nc, 2)))

    def M(self, v):
        if self.use_pallas and not self.identity_diag:
            return self._pallas_apply(v)
        out = self.diag(v)
        for mu, sign in DIRS:
            out = out + self.hop(v, mu, sign)
        return out

    def gamma5(self, v):
        sign = jnp.array([1.0, -1.0], v.dtype)
        return v * sign[:, None, None]

    def Mdag(self, v):
        if not self.g5_hermitian:
            raise NotImplementedError
        return self.gamma5(self.M(self.gamma5(v)))

    def MdagM(self, v):
        return self.Mdag(self.M(v))

    @classmethod
    def from_complex(cls, coarse) -> "PairCoarseOperator":
        return resolve_coarse_form(cls(
            to_pairs(coarse.x_diag, F32),
            {d: to_pairs(coarse.y[d], F32) for d in DIRS},
            coarse.n_vec, coarse.g5_hermitian))


def yhat_links(coarse: PairCoarseOperator,
               xinv: jnp.ndarray | None = None
               ) -> "PairCoarseOperator":
    """Explicit preconditioned coarse links Yhat = X^{-1} Y (QUDA
    calculateYhat, lib/coarse_op_preconditioned.in.cu:329): returns a
    coarse operator whose diag is the identity and whose links are
    X^{-1}-premultiplied, so M_hat = I + sum X^{-1} Y hops — the
    Jacobi-preconditioned coarse stencil QUDA smooths with.

    COMPONENTS.md §2.7 argues XLA's fusion makes the precompute moot on
    TPU (apply X^{-1} on the fly); this explicit form exists so that
    claim can be MEASURED — bench_suite's mg suite times both.  The
    inverse runs through the interleaved embedding (complex-free).
    """
    if xinv is None:
        xinv = _deinterleave(jnp.linalg.inv(
            _interleave(coarse.x_diag)))             # (latc, Nc, Nc, 2)
    yhat = {d: _pair_ein("...ab,...bc->...ac", xinv, coarse.y[d])
            for d in DIRS}
    # identity_diag: M_hat = v + sum(hops) — no dense identity matmul
    # (charging one would bias the A/B against the explicit form)
    return dataclasses.replace(coarse, y=yhat, g5_hermitian=False,
                               identity_diag=True)


def _embed_default() -> bool:
    """QUDA_TPU_MG_EMBED: apply coarse links as single interleaved-
    embedding matmuls (MXU-shaped) instead of 4-einsum pair products."""
    from ..utils import config as qconf
    return str(qconf.get("QUDA_TPU_MG_EMBED", fresh=True)) == "1"


def _arr_on_tpu(x) -> bool:
    """Whether the array actually LIVES on TPU devices — the pallas
    gates must follow placement, not the global backend: a hierarchy
    built under ``jax.default_device(cpu)`` on a chip host (the bench
    suite's setup discipline) holds CPU arrays, and a non-interpret
    pallas call on them would fail to lower."""
    import jax as _jax
    try:
        devs = x.devices() if callable(getattr(x, "devices", None)) \
            else None
        if devs:
            return all(d.platform == "tpu" for d in devs)
    except Exception:
        pass
    return _jax.default_backend() == "tpu"


def resolve_coarse_form(op: PairCoarseOperator) -> PairCoarseOperator:
    """Pick the coarse-apply form per QUDA_TPU_MG_COARSE_FORM: an
    explicit pin is honored (pallas runs interpret off-chip — test
    territory), 'auto' races einsum vs embedding vs the fused pallas
    kernel via utils.tune on chip (cached per (coarse shape, Nc) like
    every other kernel race) and falls back to the static
    QUDA_TPU_MG_EMBED default off-chip, where interpret-mode timings
    would be meaningless."""
    from ..utils import config as qconf
    form = str(qconf.get("QUDA_TPU_MG_COARSE_FORM", fresh=True)) \
        or "auto"
    on_tpu = _arr_on_tpu(op.x_diag)
    if form == "einsum":
        return dataclasses.replace(op, use_embedding=False,
                                   use_pallas=False)
    if form == "embed":
        return dataclasses.replace(op, use_embedding=True,
                                   use_pallas=False)
    if form == "pallas":
        return dataclasses.replace(op, use_pallas=True,
                                   pallas_interpret=not on_tpu)
    if not on_tpu:
        return dataclasses.replace(op, use_embedding=_embed_default(),
                                   use_pallas=False)
    from ..utils import tune
    latc = tuple(int(s) for s in op.x_diag.shape[:4])
    probe = jax.random.normal(jax.random.PRNGKey(7),
                              latc + (2, op.n_vec, 2), F32)
    cands = {
        "einsum": jax.jit(dataclasses.replace(
            op, use_embedding=False, use_pallas=False).M),
        "embed": jax.jit(dataclasses.replace(
            op, use_embedding=True, use_pallas=False).M),
        "pallas": jax.jit(dataclasses.replace(op, use_pallas=True).M),
    }
    win = tune.tune("mg_coarse_form", latc + (op.nc,), cands, (probe,))
    return dataclasses.replace(
        op, use_embedding=(win == "embed"), use_pallas=(win == "pallas"))


def build_coarse_pairs(fine_parts, transfer: PairTransfer,
                       g5_hermitian: bool = True) -> PairCoarseOperator:
    """Probing construction of the coarse stencil on pair arrays —
    structure identical to mg/coarse.build_coarse (see its docstring for
    the parity-masking argument); probing with REAL unit coarse vectors
    reads off each complex column directly as its (re, im) pair."""
    import numpy as np

    latc = transfer.coarse_shape
    n = transfer.n_vec
    nc = 2 * n

    for mu in range(4):
        ext = latc[axis_of_mu(mu)]
        if ext != 1 and ext % 2 != 0:
            raise ValueError(
                f"coarse extent {ext} along mu={mu} must be even or 1")

    @jax.jit
    def probe_diag(vc):
        return transfer.restrict(fine_parts.diag(transfer.prolong(vc)))

    from functools import partial

    @partial(jax.jit, static_argnums=(1, 2))
    def probe_hop(vc, mu, sign):
        return transfer.restrict(
            fine_parts.hop(transfer.prolong(vc), mu, sign))

    def coord_parity(mu):
        ax = axis_of_mu(mu)
        shape = [1, 1, 1, 1]
        shape[ax] = latc[ax]
        c = np.arange(latc[ax]).reshape(shape) % 2
        return np.broadcast_to(c, latc)

    def as_col(out):                       # (latc, 2, n, 2) -> (latc, nc, 2)
        return out.reshape(latc + (nc, 2))

    from ..obs import trace as otr

    diag_cols = []
    hop_cols = {d: [] for d in DIRS}
    # spanned like mg/coarse.build_coarse: the coarse_probe phase of the
    # MG setup breakdown shows the probe loop in the trace
    with otr.span("mg_coarse_probe_loop", cat="mg", n_vec=n,
                  coarse_shape=list(latc)):
        for chir in range(2):
            for b in range(n):
                e = jnp.zeros(latc + (2, n, 2),
                              F32).at[..., chir, b, 0].set(1.0)
                dcol = as_col(probe_diag(e))
                for mu, sign in DIRS:
                    ext = latc[axis_of_mu(mu)]
                    if ext == 1:
                        hop_cols[(mu, sign)].append(
                            as_col(probe_hop(e, mu, sign)))
                        continue
                    par = jnp.asarray(coord_parity(mu))[..., None, None,
                                                        None]
                    ycol = jnp.zeros(latc + (nc, 2), F32)
                    for p in (0, 1):
                        mask = (par == p).astype(F32)
                        out = as_col(probe_hop(e * mask, mu, sign))
                        lit = (jnp.asarray(coord_parity(mu)) == p)[
                            ..., None, None]
                        ycol = jnp.where(lit, ycol, out)
                        dcol = dcol + jnp.where(lit, out, 0.0)
                    hop_cols[(mu, sign)].append(ycol)
                diag_cols.append(dcol)

    x_diag = jnp.stack(diag_cols, axis=-2)         # (latc, Nc, Nc, 2)
    y = {d: jnp.stack(hop_cols[d], axis=-2) for d in DIRS}
    return PairCoarseOperator(x_diag, y, n, g5_hermitian,
                              use_embedding=_embed_default())


# -- fine-level pair adapters ----------------------------------------------

def wilson_hop_pairs(gauge_pairs, psi, mu, sign, kappa):
    """-kappa * single-direction Wilson hop on (lat,4,3,2) pair arrays
    (pair mirror of models/wilson.DiracWilson.hop)."""
    if sign > 0:
        u = gauge_pairs[mu]
        proj = g.PROJ_MINUS[mu]
        h = color_mul_pairs(u, shift(psi, mu, +1))
    else:
        u = shift(dagger_pairs(gauge_pairs[mu]), mu, -1)
        proj = g.PROJ_PLUS[mu]
        h = color_mul_pairs(u, shift(psi, mu, -1))
    return -kappa * spin_mul_pairs(proj, h)


def _fine_pallas_default(arr) -> bool:
    """Fine-level MG operators ride the pallas kernels when their
    arrays live on chip unless QUDA_TPU_PALLAS forbids them — the same
    gate as the API solvers (placement-checked via ``arr``), so the
    gcr_mg outer solve's smoother/residual applies run on the kernel
    form the fused-iteration solver proved out."""
    from ..utils import config as qconf
    return (_arr_on_tpu(arr)
            and str(qconf.get("QUDA_TPU_PALLAS", fresh=True)) != "0")


class PairWilsonLevelOp:
    """Fine-level adapter for Wilson on pair arrays: the realified
    mg/mg._LevelOp (K = 6 chiral components, gamma5 = chirality sign).

    Standard layout here means canonical pair spinors (T,Z,Y,X,4,3,2);
    the gauge (with t-boundary phases folded in by the wrapped Dirac
    operator) is converted to f32 pairs once at construction.

    On chip the fine dslash rides the v2 pallas kernel with resident
    packed links + pre-shifted backward copy (one layout transpose per
    apply, amortised against the 1,152 B/site kernel traffic), so the
    outer GCR's residuals, the V-cycle smoother, AND the MRHS
    null-vector block solve (``MdagM_mrhs`` -> the MRHS kernel: gauge
    tiles fetched once per (t, z-block) for all n_vec) all run the
    measured-fastest stencil; off-chip the XLA pair stencil serves, as
    everywhere else.
    """

    k_fine = 6
    dtype = F32

    def __init__(self, dirac, use_pallas: Optional[bool] = None,
                 pallas_interpret: bool = False):
        from ..ops.pair import dslash_full_pairs
        self.dirac = dirac
        self.kappa = dirac.kappa
        self.gauge_pairs = to_pairs(dirac.gauge, F32)
        self._dslash = dslash_full_pairs
        self.use_pallas = (_fine_pallas_default(self.gauge_pairs)
                           if use_pallas is None else bool(use_pallas))
        self._interp = bool(pallas_interpret)
        if self.use_pallas:
            from ..ops import wilson_packed as wpk
            from ..ops.wilson_pallas_packed import (backward_gauge,
                                                    to_pallas_layout)
            self._X = int(dirac.geom.lattice_shape[-1])
            self.gauge_pl = to_pallas_layout(wpk.pack_gauge(dirac.gauge))
            self.gauge_bw = backward_gauge(self.gauge_pl, self._X)

    def to_chiral(self, v):
        return to_chiral_pairs(v)

    def from_chiral(self, v):
        return from_chiral_pairs(v)

    # -- pallas-layout shuttles ----------------------------------------
    @staticmethod
    def _pl_of(v):
        """canonical pairs (T,Z,Y,X,4,3,2) -> kernel layout
        (4,3,2,T,Z,YX)."""
        T, Z, Y, X = v.shape[:4]
        return jnp.transpose(v, (4, 5, 6, 0, 1, 2, 3)).reshape(
            4, 3, 2, T, Z, Y * X)

    @staticmethod
    def _pl_back(out, lat):
        T, Z, Y, X = lat
        return jnp.transpose(out.reshape(4, 3, 2, T, Z, Y, X),
                             (3, 4, 5, 6, 0, 1, 2))

    # -- standard (canonical pair) layout ------------------------------
    def _d_std(self, v):
        if self.use_pallas:
            from ..ops.wilson_pallas_packed import dslash_pallas_packed
            d = dslash_pallas_packed(self.gauge_pl, self._pl_of(v),
                                     self._X, interpret=self._interp,
                                     gauge_bw=self.gauge_bw)
            return self._pl_back(d, v.shape[:4])
        return self._dslash(self.gauge_pairs, v, out_dtype=F32)

    def M_std(self, v):
        return v - self.kappa * self._d_std(v)

    def Mdag_std(self, v):
        g5 = jnp.array([1.0, 1.0, -1.0, -1.0], v.dtype)
        sgn = g5[:, None, None]
        return sgn * self.M_std(sgn * v)

    # -- batched MRHS forms (the null-vector block solve's matvec) -----
    def _d_std_mrhs(self, V):
        if self.use_pallas:
            from ..ops.wilson_pallas_packed import \
                dslash_pallas_packed_mrhs
            lat = V.shape[1:5]
            pp = jax.vmap(self._pl_of)(V)
            d = dslash_pallas_packed_mrhs(self.gauge_pl, pp, self._X,
                                          interpret=self._interp,
                                          gauge_bw=self.gauge_bw)
            return jax.vmap(lambda o: self._pl_back(o, lat))(d)
        return jax.vmap(lambda v: self._dslash(self.gauge_pairs, v,
                                               out_dtype=F32))(V)

    def M_mrhs(self, Vc):
        """(N, lat, 2, 6, 2) chiral batch -> M per RHS through ONE
        batched stencil — the null-vector block solve's direct-system
        matvec."""
        s = from_chiral_pairs(Vc)          # reshape works batched
        return to_chiral_pairs(s - self.kappa * self._d_std_mrhs(s))

    def MdagM_mrhs(self, Vc):
        """(N, lat, 2, 6, 2) chiral batch -> MdagM per RHS through ONE
        batched stencil (the MRHS kernel on chip: link tiles read once
        per (t, z-block) and all N RHS streamed through them)."""
        s = from_chiral_pairs(Vc)
        g5 = jnp.array([1.0, 1.0, -1.0, -1.0], s.dtype)[:, None, None]
        ms = s - self.kappa * self._d_std_mrhs(s)
        md = g5 * (g5 * ms - self.kappa * self._d_std_mrhs(g5 * ms))
        return to_chiral_pairs(md)

    # -- chiral layout (the MG hierarchy's view) -----------------------
    def M(self, v):
        return to_chiral_pairs(self.M_std(from_chiral_pairs(v)))

    def MdagM(self, v):
        s = from_chiral_pairs(v)
        return to_chiral_pairs(self.Mdag_std(self.M_std(s)))

    def diag(self, v):
        return v

    def hop(self, v, mu, sign):
        s = from_chiral_pairs(v)
        return to_chiral_pairs(
            wilson_hop_pairs(self.gauge_pairs, s, mu, sign, self.kappa))


class PairStaggeredLevelOp:
    """Fine-level adapter for STAGGERED operators on pair arrays — the
    realified mg/mg._StaggeredLevelOp (direct hierarchy; the KD
    composition is complex-only for now).  Chirality is the site parity
    epsilon(x); K = 3 colors; chiral fields are (lat, 2, 3, 2) with the
    even-site part in component 0.

    The staggered stencil pieces (ops/staggered dslash_full / the hop
    decomposition) are pair-polymorphic, so this adapter only converts
    the (phase-folded) links once and handles the chiral masks."""

    k_fine = 3
    dtype = F32
    nspin = 1

    def __init__(self, dirac, use_pallas: Optional[bool] = None,
                 pallas_interpret: bool = False):
        self.dirac = dirac
        self.geom = dirac.geom
        self.mass = float(dirac.mass)
        self.fat_pairs = to_pairs(dirac.fat, F32)
        self.use_pallas = (_fine_pallas_default(self.fat_pairs)
                           if use_pallas is None else bool(use_pallas))
        self._interp = bool(pallas_interpret)
        if self.use_pallas:
            from ..ops.staggered_pallas import backward_links
            from ..ops.wilson_packed import pack_gauge, to_packed_pairs
            self._X = int(dirac.geom.lattice_shape[-1])
            # the hierarchy represents the FAT-ONLY stencil — only the
            # fat links go resident in kernel layout
            self.fat_pl = to_packed_pairs(pack_gauge(dirac.fat), F32)
            self.fat_bw = backward_links(self.fat_pl, self._X, 1)
        # Improved staggered: the HIERARCHY represents the fat-link
        # stencil (the standard preconditioner simplification, matching
        # mg/mg._StaggeredLevelOp and QUDA's coarse construction,
        # lib/staggered_coarse_op.in.cu), while M_std_full applies the
        # full fat+Naik operator — mg_solve_pairs runs the outer Krylov
        # on M_std_full so the fat-only V-cycle defect-corrects the
        # Naik term implicitly (ref lib/dirac_improved_staggered_kd.cpp).
        self.long_pairs = (to_pairs(dirac.long, F32)
                           if getattr(dirac, "long", None) is not None
                           else None)
        self._eps = parity_eps(self.geom.lattice_shape, 3)

    # -- pallas-layout shuttles ----------------------------------------
    @staticmethod
    def _pl_of(v):
        """canonical pairs (T,Z,Y,X,1,3,2) -> kernel layout
        (3,2,T,Z,YX)."""
        T, Z, Y, X = v.shape[:4]
        return jnp.transpose(v[..., 0, :, :],
                             (4, 5, 0, 1, 2, 3)).reshape(
            3, 2, T, Z, Y * X)

    @staticmethod
    def _pl_back(out, lat):
        T, Z, Y, X = lat
        return jnp.transpose(out.reshape(3, 2, T, Z, Y, X),
                             (2, 3, 4, 5, 0, 1))[..., None, :, :]

    # -- standard (canonical pair, (lat, 1, 3, 2)) layout --------------
    def _d_std(self, v):
        if self.use_pallas:
            from ..ops.staggered_pallas import dslash_staggered_pallas
            d = dslash_staggered_pallas(self.fat_pl, self.fat_bw,
                                        self._pl_of(v), self._X,
                                        interpret=self._interp)
            return self._pl_back(d, v.shape[:4])
        from ..ops import staggered as sops
        return sops.dslash_full(self.fat_pairs, v)

    def _d_std_mrhs(self, V):
        """(N, lat, 1, 3, 2) batched fat-only D through ONE stencil —
        the MRHS kernel on chip (link tiles amortised over all N)."""
        if self.use_pallas:
            from ..ops.staggered_pallas import \
                dslash_staggered_pallas_mrhs
            lat = V.shape[1:5]
            pp = jax.vmap(self._pl_of)(V)
            d = dslash_staggered_pallas_mrhs(self.fat_pl, self.fat_bw,
                                             pp, self._X,
                                             interpret=self._interp)
            return jax.vmap(lambda o: self._pl_back(o, lat))(d)
        from ..ops import staggered as sops
        return jax.vmap(lambda v: sops.dslash_full(self.fat_pairs,
                                                   v))(V)

    def M_mrhs(self, Vc):
        """(N, lat, 2, 3, 2) chiral batch -> M per RHS, one batched
        stencil (null-vector block solve direct matvec)."""
        s = self.from_chiral(Vc)
        return self.to_chiral(2.0 * self.mass * s + self._d_std_mrhs(s))

    def MdagM_mrhs(self, Vc):
        """(N, lat, 2, 3, 2) chiral batch -> MdagM per RHS, one batched
        stencil per application (null-vector block solve matvec)."""
        s = self.from_chiral(Vc)
        ms = 2.0 * self.mass * s + self._d_std_mrhs(s)
        md = 2.0 * self.mass * ms - self._d_std_mrhs(ms)
        return self.to_chiral(md)

    def M_std(self, v):
        return 2.0 * self.mass * v + self._d_std(v)

    def _mdag_std(self, v):
        return 2.0 * self.mass * v - self._d_std(v)

    # -- full improved operator (fat + Naik), standard layout ----------
    def _d_std_full(self, v):
        from ..ops import staggered as sops
        return sops.dslash_full(self.fat_pairs, v, self.long_pairs)

    def M_std_full(self, v):
        """The operator the OUTER solve targets: fat+Naik when long
        links exist, else identical to M_std."""
        if self.long_pairs is None:
            return self.M_std(v)
        return 2.0 * self.mass * v + self._d_std_full(v)

    def Mdag_std_full(self, v):
        if self.long_pairs is None:
            return self._mdag_std(v)
        return 2.0 * self.mass * v - self._d_std_full(v)

    # -- chiral layout --------------------------------------------------
    def to_chiral(self, v):
        eps = jnp.asarray(self._eps)
        even = jnp.where(eps == 0, v, 0)[..., 0, :, :]
        odd = jnp.where(eps == 1, v, 0)[..., 0, :, :]
        return jnp.stack([even, odd], axis=-3)

    def from_chiral(self, vc):
        return (vc[..., 0, :, :] + vc[..., 1, :, :])[..., None, :, :]

    def M(self, v):
        return self.to_chiral(self.M_std(self.from_chiral(v)))

    def MdagM(self, v):
        s = self.from_chiral(v)
        return self.to_chiral(self._mdag_std(self.M_std(s)))

    def diag(self, v):
        # through the chiral roundtrip like the complex adapter: the
        # (lat, 2, 3) chiral space is larger than the image of
        # to_chiral, and M = diag + sum(hop) must hold as CHIRAL-space
        # operators for the probing construction to be consistent
        return self.to_chiral(2.0 * self.mass * self.from_chiral(v))

    def hop(self, v, mu, sign):
        from ..ops import staggered as sops
        return self.to_chiral(sops.hop_term(self.fat_pairs,
                                            self.from_chiral(v), mu,
                                            sign))

    def project_null_source(self, bs):
        """Parity-subspace projection of random chiral sources (the
        complex adapter's project_null_source, pair layout)."""
        return self.to_chiral(self.from_chiral(bs))


# -- the hierarchy ----------------------------------------------------------

class PairMG(MG):
    """Complex-free multigrid hierarchy: same driver as MG (V-cycle,
    probing, verify are inherited), pair-array representation throughout.
    Setup runs real CG on the realified fine operator, CholQR2 block
    orthonormalisation, and real probing — no complex dtype anywhere."""

    _transfer_from_nulls = staticmethod(PairTransfer.from_null_vectors)
    _build_coarse = staticmethod(build_coarse_pairs)     # legacy probe

    @staticmethod
    def _build_coarse_gemm(parts, transfer):
        from .gemm import build_coarse_pairs_gemm
        return build_coarse_pairs_gemm(parts, transfer)

    def _example_field(self, lat_shape, k, dtype):
        rdt = jnp.zeros((), dtype).real.dtype
        return jnp.zeros(lat_shape + (2, k, 2), rdt)

    def _random_like(self, example, key):
        return jax.random.normal(key, example.shape, example.dtype)

    @staticmethod
    def _adapt(fine_dirac, kd: bool = False):
        if getattr(fine_dirac, "nspin", 4) == 1:
            if kd:
                raise NotImplementedError(
                    "pair staggered MG: the Kaehler-Dirac composition "
                    "is complex-only (the direct hierarchy is the "
                    "measured-better configuration; mg/mg.py)")
            return PairStaggeredLevelOp(fine_dirac)
        return PairWilsonLevelOp(fine_dirac)

    @classmethod
    def from_complex(cls, mg: MG, fine_dirac=None) -> "PairMG":
        """Realify an existing complex hierarchy (CPU-built setup ->
        complex-free apply path) without re-running setup."""
        if getattr(mg.adapter, "kd", False):
            raise NotImplementedError(
                "PairMG.from_complex: the source hierarchy composes the "
                "Kaehler-Dirac Xinv, which has no pair fine adapter — "
                "realifying only the transfers would silently break "
                "Galerkin consistency")
        self = object.__new__(cls)
        self.geom = mg.geom
        self.params = list(mg.params)
        self.adapter = cls._adapt(fine_dirac if fine_dirac is not None
                                  else mg.adapter.dirac)
        self.levels = []
        op = self.adapter
        for lv in mg.levels:
            transfer = PairTransfer.from_complex(lv["transfer"])
            coarse = PairCoarseOperator.from_complex(lv["coarse"])
            self.levels.append(dict(op=op, transfer=transfer,
                                    coarse=coarse, param=lv["param"]))
            op = coarse
        return self


def mg_solve_pairs(fine_dirac, geom, b_pairs, params: Sequence[MGLevelParam],
                   tol: float = 1e-6, nkrylov: int = 16,
                   max_restarts: int = 100, key=None,
                   mg: Optional[PairMG] = None):
    """Outer GCR on canonical pair spinors preconditioned by the pair MG
    V-cycle — the complex-free analog of mg/mg.mg_solve AND
    mg/mg.staggered_mg_solve (the adapter supplies the right M_std:
    Wilson (T,Z,Y,X,4,3,2) or staggered (T,Z,Y,X,1,3,2) pair fields).

    For improved staggered (fine_dirac.long is not None) the outer GCR
    applies the FULL fat+Naik operator while the hierarchy preconditions
    with the fat-only stencil — flexible-Krylov defect correction of the
    Naik term (ref lib/dirac_improved_staggered_kd.cpp:1, the production
    improved-staggered MG wiring).

    Returns (SolverResult with pair x, mg).
    """
    from ..solvers.gcr import gcr
    if mg is None:
        mg = PairMG(fine_dirac, geom, params, key)
    a = mg.adapter
    outer = getattr(a, "M_std_full", a.M_std)
    res = gcr(outer, b_pairs, precond=mg.precondition, tol=tol,
              nkrylov=nkrylov, max_restarts=max_restarts)
    return res, mg
