"""Multi-RHS solving: batched CG (vmap) and true block CG.

Reference behavior: QUDA threads cvector_ref<ColorSpinorField> through
every solver for multi-RHS batching (inv_msrc_cg_quda.cpp, the src_idx
kernel dimension, QUDA_MAX_MULTI_RHS); the MG coarse-dslash MMA path
batches RHS onto tensor cores.

TPU-native: a leading RHS axis + vmap gives the batched solver (XLA turns
the batched stencils into one larger kernel — the MXU sees nrhs x the
work, exactly what the hardware wants), and true block CG shares one
Krylov space across RHS with (nrhs x nrhs) Gram matrices solved on the
fly — communication-optimal for small nrhs.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops import blas
from .cg import SolverResult


def _check_nrhs(n: int):
    """QUDA_TPU_MAX_MULTI_RHS advisory cap.  The reference's
    QUDA_MAX_MULTI_RHS is a compile-time instantiation bound, not a
    runtime rejection of user batches — so WARN (the risk is batching
    past device memory) rather than refuse."""
    import warnings

    from ..utils import config as qconf
    cap = qconf.get("QUDA_TPU_MAX_MULTI_RHS", fresh=True)
    if n > cap:
        warnings.warn(
            f"{n} right-hand sides exceeds QUDA_TPU_MAX_MULTI_RHS={cap}; "
            "device memory may not hold the batch — raise the knob to "
            "silence this warning or chunk the sources", stacklevel=3)


def batched_cg(matvec: Callable, B: jnp.ndarray, tol: float = 1e-10,
               maxiter: int = 1000) -> SolverResult:
    """vmapped CG over a leading RHS axis; iterates until ALL converge."""
    from .cg import cg
    _check_nrhs(B.shape[0])
    return jax.vmap(lambda b: cg(matvec, b, tol=tol, maxiter=maxiter))(B)


class BlockCGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray
    r2: jnp.ndarray          # (nrhs,)
    converged: jnp.ndarray   # (nrhs,)
    # optional (slots, nrhs) per-iteration |r|^2 lanes (record=True)
    history: object = None
    # optional typed breakdown code (robust/sentinel.py; None on
    # unguarded solves — see solvers/cg.SolverResult.breakdown)
    breakdown: object = None


def block_cg(matvec: Callable, B: jnp.ndarray, tol: float = 1e-10,
             maxiter: int = 1000) -> BlockCGResult:
    """Block CG (O'Leary): solve A X = B sharing one Krylov space.

    B: (nrhs, ...).  Per iteration ONE batched matvec plus two small
    (nrhs, nrhs) Gram solves; RHS with shared spectral content converge in
    fewer iterations than independent CG.
    """
    n = B.shape[0]
    _check_nrhs(n)
    b2 = jax.vmap(blas.norm2)(B)
    stop = (tol ** 2) * b2
    cdt = B.dtype

    def gram(U, V):
        return jnp.einsum("i...,j...->ij", jnp.conjugate(U), V)

    X = jnp.zeros_like(B)
    R = B
    P = R

    def cond(c):
        return jnp.logical_and(jnp.any(c["r2"] > stop),
                               c["k"] < maxiter)

    def body(c):
        X, R, P = c["X"], c["R"], c["P"]
        AP = jax.vmap(matvec)(P)
        pap = gram(P, AP)                       # (n, n)
        rr = gram(R, R)
        # alpha solves (P^H A P) alpha = P^H R
        alpha = jnp.linalg.solve(pap, gram(P, R))
        X = X + jnp.einsum("ij,i...->j...", alpha, P)
        R = R - jnp.einsum("ij,i...->j...", alpha, AP)
        rr_new = gram(R, R)
        beta = jnp.linalg.solve(rr, rr_new)
        P = R + jnp.einsum("ij,i...->j...", beta, P)
        return dict(X=X, R=R, P=P,
                    r2=jnp.real(jnp.einsum("...ii->...i", rr_new[None]))[0],
                    k=c["k"] + 1)

    state = dict(X=X, R=R, P=P, r2=b2, k=jnp.int32(0))
    out = jax.lax.while_loop(cond, body, state)
    return BlockCGResult(out["X"], out["k"], out["r2"],
                         out["r2"] <= stop)


# ---------------------------------------------------------------------------
# Pair-form (complex-free) multi-RHS solvers — the packed MRHS pipeline
# ---------------------------------------------------------------------------
#
# The batched invert path (interfaces/quda_api.invert_multi_src_quda)
# keeps every Krylov iterate on packed PAIR arrays (N, 4, 3, 2, T, Z,
# Y*Xh) so the MRHS pallas eo stencil runs INSIDE the compiled batch
# solve.  CG coefficients on the (realified) Hermitian normal operator
# are real, so the pair representation is exact — the same argument as
# the single-RHS pair routes.  Both solvers take a matvec over the FULL
# batch (models/wilson.MdagM_pairs_mrhs or any (N, ...) -> (N, ...)
# callable), not a per-RHS matvec: batching the stencil is the whole
# point (one gauge fetch amortised over N).


class BatchedCGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray       # (nrhs,) iterations to convergence per RHS
    r2: jnp.ndarray          # (nrhs,) final |r|^2
    converged: jnp.ndarray   # (nrhs,)
    # optional (slots, nrhs) per-check-point |r|^2 lanes (record=True)
    history: object = None
    # optional typed breakdown code (robust/sentinel.py; None on
    # unguarded solves — see solvers/cg.SolverResult.breakdown)
    breakdown: object = None


def _per_rhs_dot(u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """(N,) real per-RHS inner products — one fused traversal.

    Re<u, v> per RHS: for the real pair arrays every TPU route uses,
    conjugate/real are identity ops (XLA emits the same HLO as the
    plain product — the compiled pair solves are bit-identical); the
    conjugation makes the same lanes serve HERMITIAN COMPLEX batches,
    which is what lets the MG setup run its null-vector inverse
    iterations through this solver on the complex hierarchy too."""
    n = u.shape[0]
    return jnp.sum(jnp.real(jnp.conjugate(u) * v).reshape(n, -1), axis=1)


def _wide_dot(u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``_per_rhs_dot`` in the batch's reduction dtype: its own, f32
    for a bf16 batch."""
    rdt = jnp.float32 if u.dtype == jnp.bfloat16 else u.dtype
    return _per_rhs_dot(u.astype(rdt), v.astype(rdt))


def cg_alpha(rz: jnp.ndarray, pAp: jnp.ndarray) -> jnp.ndarray:
    """The batched CG's per-source step length ``|r|^2 / p . A p``, the
    pivot clamped away from zero: the one definition, for ``cg_step``
    and for an operator's own step."""
    return rz / jnp.maximum(pAp, jnp.finfo(pAp.dtype).tiny)


def cg_step(matvec_batch: Callable, fault_k: Optional[int] = None
            ) -> Callable:
    """Lift a batched matvec ``A`` to what ``batched_cg_pairs_loop``
    applies, the first half of an iteration: ``(p, r, rz, k) ->
    (r - alpha A p, its squared norms per source, alpha, p . A p)``
    with XLA's dot, update and sum over the batch, operation for
    operation what the loop did itself before an operator could hand
    them over.  For every operator that has nothing better
    (models/wilson.MdagM_cg_step_pairs_mrhs has).  ``fault_k``: the
    armed dslash fault iteration, which corrupts ``A p`` at iteration
    ``k`` before anything reads it."""
    from ..robust import faultinject as finj

    def step(p, r, rz, k):
        Ap = matvec_batch(p)
        if fault_k is not None:
            Ap = finj.corrupt(Ap, k, fault_k)
        pAp = _wide_dot(p, Ap)
        alpha = cg_alpha(rz, pAp)
        r = r - _bcast(alpha, r).astype(r.dtype) * Ap
        return r, _wide_dot(r, r), alpha, pAp
    return step


def _bcast(s: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """(N,) scalars broadcast over the per-RHS field axes."""
    return s.reshape((s.shape[0],) + (1,) * (like.ndim - 1))


def batched_cg_pairs(matvec_batch: Callable, B: jnp.ndarray,
                     tol: float = 1e-10, maxiter: int = 1000,
                     check_every: Optional[int] = None,
                     record: bool = False
                     ) -> BatchedCGResult:
    """Batched CG on pair arrays with the fused-iteration tail.

    Independent CG recurrences in (N,)-vector scalar lanes — each RHS
    follows EXACTLY the trajectory of a solo fused_cg solve — but every
    iteration issues ONE batched matvec, so the MRHS stencil amortises
    the gauge reads.  The fused tail (x += a p; r -= a Ap; per-RHS
    |r|^2 in one traversal) and the ``check_every`` convergence-check
    cadence mirror solvers/fused_iter.py; the loop runs until ALL RHS
    converge (converged lanes keep iterating harmlessly, like
    batched_cg's vmap), and ``iters`` records each RHS's first cadence
    boundary at convergence (unconverged lanes report the total).
    """
    from ..robust import faultinject as finj
    from ..robust import sentinel as rsent
    from .fused_iter import _resolve_check_every
    return batched_cg_pairs_loop(
        cg_step(matvec_batch, finj.iteration_fault("dslash")), B, tol,
        maxiter, _resolve_check_every(check_every), record, rsent.make())


def batched_cg_pairs_loop(step: Callable, B: jnp.ndarray, tol, maxiter,
                          check_every: int, record: bool, sent
                          ) -> BatchedCGResult:
    """``batched_cg_pairs`` with every knob already resolved by the
    caller (the cadence, ``sent``: robust/sentinel.Sentinel or None),
    so nothing here reads host state: the body the cached solve program
    (solvers/program.py) traces once per key.  ``tol`` and ``maxiter``
    may be traced scalars, except that ``record`` sizes the history by
    a concrete ``maxiter``.

    ``step`` is the first half of an iteration, ``(p, r, rz, k) ->
    (r - alpha A p, its squared norms per source, alpha, p . A p)``
    (``k``: the iteration): the loop makes no pass over the batch for
    ``pAp``, for ``r`` or for ``|r|^2``, and keeps ``x += alpha p``,
    ``beta`` and ``p = r + beta p``.  It comes from the operator where
    that has the whole of it for one more tile read (the Wilson and
    the clover-type Schur pair operators'
    ``MdagM_cg_step_pairs_mrhs``: ``pAp = |g5 M p|^2`` out
    of the first ``M``, so the last hop's epilogue writes the new ``r``
    and sums it) and from ``cg_step`` of the batched matvec everywhere
    else, which is also where an armed dslash fault corrupts ``A p``:
    it reaches ``alpha``, ``r`` and the sentinel's pivot."""
    from ..robust import sentinel as rsent
    n = B.shape[0]
    _check_nrhs(n)
    rdt = jnp.float32 if B.dtype == jnp.bfloat16 else B.dtype
    # scalar-lane dtype: the real counterpart of rdt, so complex
    # batches (the MG setup's null-vector solves on the complex
    # hierarchy) carry real residual lanes; identical to rdt for the
    # real pair arrays
    sdt = jnp.zeros((), rdt).real.dtype
    b2 = _wide_dot(B, B)
    stop = (tol ** 2) * b2
    tiny = jnp.asarray(jnp.finfo(sdt).tiny, sdt)

    x = jnp.zeros_like(B)
    r = B
    p = B
    rz = b2

    def one_iter(x, r, p, rz, k):
        r, r2, alpha, pAp = step(p, r, rz, k)
        x = x + _bcast(alpha, x).astype(x.dtype) * p
        beta = r2 / jnp.maximum(rz, tiny)
        p = r + _bcast(beta, p).astype(p.dtype) * p
        return x, r, p, r2, pAp

    def cond(carry):
        rz, k = carry[3], carry[4]
        go = jnp.logical_and(jnp.any(rz > stop), k < maxiter)
        if sent is not None:
            go = jnp.logical_and(go, sent.ok(carry[-1]))
        return go

    def body(carry):
        x, r, p, rz, k, it_conv = carry[:6]
        pAp = None
        for j in range(check_every):
            x, r, p, rz, pAp = one_iter(x, r, p, rz, k + j)
        k_new = k + check_every
        it_conv = jnp.where((it_conv < 0) & (rz <= stop), k_new, it_conv)
        out = (x, r, p, rz, k_new, it_conv)
        if record:
            out = out + (carry[6].at[k // check_every].set(rz),)
        if sent is not None:
            # aggregate lanes into one scalar per predicate: the sum
            # propagates any lane's NaN, the min pivot flags any
            # non-HPD lane
            out = out + (sent.step(carry[-1], jnp.sum(rz),
                                   denom=jnp.min(pAp)),)
        return out

    it_conv0 = jnp.full((n,), -1, jnp.int32)
    init = (x, r, p, rz, jnp.int32(0), it_conv0)
    if record:
        slots = maxiter // check_every + 2
        init = init + (jnp.full((slots, n), jnp.nan, sdt),)
    if sent is not None:
        init = init + (sent.init(jnp.sum(b2)),)
    out = jax.lax.while_loop(cond, body, init)
    x, r, p, rz, k, it_conv = out[:6]
    it_conv = jnp.where(it_conv < 0, k, it_conv)
    conv, bk = rsent.finalize(sent,
                              out[-1] if sent is not None else None,
                              rz <= stop)
    return BatchedCGResult(x, it_conv, rz, conv,
                           out[6] if record else None, bk)


def batched_bicgstab_pairs(matvec_batch: Callable, B: jnp.ndarray,
                           tol: float = 1e-10, maxiter: int = 1000,
                           ) -> BatchedCGResult:
    """Batched BiCGStab with independent per-RHS scalar lanes.

    The multi-source sibling of solvers/bicgstab.py for DIRECT
    (non-normal) systems: every iteration issues TWO batched matvecs
    (A p and A s) so the MRHS stencil amortises link reads across all
    N lanes, while each lane follows its own BiCGStab recurrence.
    Real arithmetic throughout — pair arrays realify complex systems
    (a real-coefficient Krylov method on the realified operator, the
    same embedding argument as the pair CG routes; the real dots are
    Re<.,.> of the underlying complex vectors).

    This is the MG setup's null-vector solver (mg/mg.py): QUDA's
    generateNullVectors solves M v = r with the setup solver
    (BiCGStab-class) at setup_tol — on kappa-critical Wilson drills
    that converges in ~3-5x fewer dslash applications than CG on the
    squared-condition normal equations, which is where the legacy
    fixed-iteration inverse iteration burned its time.  ``iters``
    reports the iteration of each lane's first converged check
    (2 matvec applies per iteration); converged lanes keep iterating
    harmlessly until all lanes finish."""
    from ..robust import faultinject as finj
    from ..robust import sentinel as rsent
    if jnp.iscomplexobj(B):
        # the scalar lanes are REAL recurrences (Re<.,.> dots — the
        # pair-route embedding): a complex batch fed directly would
        # follow a real-projected BiCGStab that generally stalls for
        # 2*maxiter matvecs.  Realify around the call (as mg/mg.py
        # does) — unlike batched_cg_pairs there is no complex-safe
        # variant of this recurrence to fall through to.
        raise TypeError(
            "batched_bicgstab_pairs needs a REAL (pair/realified) "
            "batch; realify complex systems around the call")
    n = B.shape[0]
    _check_nrhs(n)
    sent = rsent.make()
    fault_k = finj.iteration_fault("dslash")
    rdt = jnp.float32 if B.dtype == jnp.bfloat16 else B.dtype
    sdt = jnp.zeros((), rdt).real.dtype
    b2 = _per_rhs_dot(B.astype(rdt), B.astype(rdt))
    stop = (tol ** 2) * b2
    tiny = jnp.asarray(jnp.finfo(sdt).tiny, sdt)

    def _safe(d):
        # magnitude-preserving denominator guard: BiCGStab scalars can
        # legitimately be negative (real embedding), so clamp |d| only
        return jnp.where(jnp.abs(d) > tiny, d,
                         jnp.where(d < 0, -tiny, tiny))

    x = jnp.zeros_like(B)
    r = B
    r0 = B
    p = B
    rho = b2

    def body(carry):
        x, r, p, rho, k, it_conv = carry[:6]
        Av = matvec_batch(p)
        if fault_k is not None:
            Av = finj.corrupt(Av, k, fault_k)
        r0v = _per_rhs_dot(r0.astype(rdt), Av.astype(rdt))
        alpha = rho / _safe(r0v)
        s = r - _bcast(alpha, r).astype(r.dtype) * Av
        At = matvec_batch(s)
        tt = _per_rhs_dot(At.astype(rdt), At.astype(rdt))
        ts = _per_rhs_dot(At.astype(rdt), s.astype(rdt))
        omega = ts / jnp.maximum(tt, tiny)
        x = x + _bcast(alpha, x).astype(x.dtype) * p \
            + _bcast(omega, x).astype(x.dtype) * s
        r = s - _bcast(omega, r).astype(r.dtype) * At
        r2 = _per_rhs_dot(r.astype(rdt), r.astype(rdt))
        rho_new = _per_rhs_dot(r0.astype(rdt), r.astype(rdt))
        beta = (rho_new / _safe(rho)) * (alpha / _safe(omega))
        p = r + _bcast(beta, p).astype(p.dtype) * (
            p - _bcast(omega, p).astype(p.dtype) * Av)
        k_new = k + 1
        it_conv = jnp.where((it_conv < 0) & (r2 <= stop), k_new, it_conv)
        out = (x, r, p, rho_new, k_new, it_conv, r2)
        if sent is not None:
            out = out + (sent.step(carry[-1], jnp.sum(r2)),)
        return out

    def cond(carry):
        r2, k = carry[6], carry[4]
        go = jnp.logical_and(
            jnp.logical_and(jnp.any(r2 > stop),
                            jnp.all(jnp.isfinite(r2))),
            k < maxiter)
        if sent is not None:
            go = jnp.logical_and(go, sent.ok(carry[-1]))
        return go

    it_conv0 = jnp.full((n,), -1, jnp.int32)
    init = (x, r, p, rho, jnp.int32(0), it_conv0, b2)
    if sent is not None:
        init = init + (sent.init(jnp.sum(b2)),)
    out = jax.lax.while_loop(cond, body, init)
    x, r2, k, it_conv = out[0], out[6], out[4], out[5]
    it_conv = jnp.where(it_conv < 0, k, it_conv)
    conv, bk = rsent.finalize(sent,
                              out[-1] if sent is not None else None,
                              r2 <= stop)
    return BatchedCGResult(x, it_conv, r2, conv, None, bk)


def block_cg_pairs(matvec_batch: Callable, B: jnp.ndarray,
                   tol: float = 1e-10, maxiter: int = 1000,
                   record: bool = False
                   ) -> BlockCGResult:
    """Block CG (O'Leary) on pair arrays: one shared Krylov space.

    The realified Hermitian system is real SPD, so block CG runs in
    PURE real arithmetic: the (nrhs x nrhs) Gram matrices are real
    matmuls over the flattened site axis — exactly the MXU-friendly
    shape (QUDA's multi_reduce blocks, lib/multi_reduce_quda.cu).  RHS
    sharing spectral content converge in fewer iterations than the
    independent-lane batched solve; the iteration count is shared
    (one Krylov space).

    Breakdown: linearly DEPENDENT sources (e.g. duplicates) make the
    Gram matrices singular — the classic block-CG breakdown, which
    QUDA handles by deflating the block.  Here the loop stops as soon
    as any residual norm goes non-finite and reports those lanes
    unconverged (never garbage-as-success); dedupe the batch or use
    batched_cg_pairs (independent lanes are immune) for such inputs.
    """
    from ..robust import sentinel as rsent
    n = B.shape[0]
    _check_nrhs(n)
    sent = rsent.make()
    rdt = jnp.float32 if B.dtype == jnp.bfloat16 else B.dtype
    b2 = _per_rhs_dot(B.astype(rdt), B.astype(rdt))
    stop = (tol ** 2) * b2

    def gram(U, V):
        # real (N, D) @ (D, N) matmul — the MXU shape
        return jnp.matmul(U.reshape(n, -1).astype(rdt),
                          V.reshape(n, -1).astype(rdt).T)

    def comb(M, U):
        # X_j <- sum_i M[i, j] U_i over the flattened site axis
        return jnp.matmul(M.T.astype(rdt),
                          U.reshape(n, -1).astype(rdt)).reshape(U.shape)

    X = jnp.zeros_like(B)
    R = B
    P = B

    def cond(c):
        # the finiteness guard turns a Gram-breakdown NaN into a clean
        # exit with converged=False instead of silent NaN solutions
        # (always on — it predates the opt-in sentinel and stays as the
        # last line of defense at QUDA_TPU_ROBUST=off)
        go = jnp.logical_and(
            jnp.logical_and(jnp.any(c["r2"] > stop),
                            jnp.all(jnp.isfinite(c["r2"]))),
            c["k"] < maxiter)
        if sent is not None:
            go = jnp.logical_and(go, sent.ok(c["sent"]))
        return go

    def body(c):
        X, R, P = c["X"], c["R"], c["P"]
        AP = matvec_batch(P)
        pap = gram(P, AP)
        rr = gram(R, R)
        alpha = jnp.linalg.solve(pap, gram(P, R))
        X = X + comb(alpha, P)
        R = R - comb(alpha, AP)
        rr_new = gram(R, R)
        beta = jnp.linalg.solve(rr, rr_new)
        P = R + comb(beta, P)
        nxt = dict(X=X, R=R, P=P, r2=jnp.diagonal(rr_new),
                   k=c["k"] + 1)
        if record:
            nxt["hist"] = c["hist"].at[c["k"]].set(nxt["r2"])
        if sent is not None:
            # Gram-pivot breakdown: the sum propagates any lane's NaN
            # (a singular Gram solve NaNs the whole block)
            nxt["sent"] = sent.step(c["sent"], jnp.sum(nxt["r2"]))
        return nxt

    state = dict(X=X, R=R, P=P, r2=b2, k=jnp.int32(0))
    if record:
        state["hist"] = jnp.full((maxiter + 1, n), jnp.nan, rdt)
    if sent is not None:
        state["sent"] = sent.init(jnp.sum(b2))
    out = jax.lax.while_loop(cond, body, state)
    conv, bk = rsent.finalize(sent, out.get("sent"),
                              out["r2"] <= stop)
    return BlockCGResult(out["X"], out["k"], out["r2"], conv,
                         out["hist"] if record else None, bk)
