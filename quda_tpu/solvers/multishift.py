"""Multi-shift CG: solve (A + sigma_i) x_i = b for all shifts at once.

Reference behavior: lib/inv_multi_cg_quda.cpp (493 LoC) — the RHMC
rational-approximation solver for staggered/HISQ.  One Krylov space serves
every shift via the shifted-CG zeta recurrences (a single matvec per
iteration); per-shift convergence is tracked through the analytically known
shifted residual |r_s| = zeta_s |r|.

The shifts are an ARRAY (``multishift_cg_loop``: an operand of the
cached program, solvers/program.multishift_cg, so an RHMC that changes
its poles between the force and the action compiles once); the shifted
iterates are a stacked leading axis so the per-shift axpys are one fused
broadcast — QUDA's hand-written multi-shift update kernels (multi_blas)
fall out of XLA fusion for free.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from ..ops import blas


class MultiShiftResult(NamedTuple):
    x: jnp.ndarray          # (n_shifts, ...) solutions
    iters: jnp.ndarray
    r2: jnp.ndarray         # base-system final |r|^2
    converged: jnp.ndarray  # (n_shifts,) bool
    # optional per-iteration history (record=True): {"r2": base-system
    # norms, "shift_r2": (slots, n_shifts) analytic shifted residuals}
    history: object = None
    # optional typed breakdown code (robust/sentinel.py; None on
    # unguarded solves — see solvers/cg.SolverResult.breakdown)
    breakdown: object = None
    # (n_shifts,) analytic shifted residuals zeta_i^2 |r|^2 at exit
    shift_r2: object = None


def multishift_cg(matvec: Callable, b: jnp.ndarray,
                  shifts: Sequence[float], tol: float = 1e-10,
                  maxiter: int = 2000,
                  record: bool = False) -> MultiShiftResult:
    """Solve (matvec + shift_i) x_i = b, matvec Hermitian positive
    semi-definite and every shift >= 0 (the RHMC setting).

    The BASE system is shift 0's, which must be the smallest (QUDA
    takes its offsets ascending and iterates the zeroth; anything else
    is refused here, where the shifts are still host numbers);
    convergence of shift i is |r_i|^2 = zeta_i^2 |r|^2 <= tol^2 |b|^2.

    ``iters`` counts the loop's iterations: one application of
    ``matvec`` each, shared by every shift (the loop runs until the
    slowest shift, the base system, is under ``tol`` or ``maxiter`` is
    reached; it is NOT a sum over shifts).

    ``record=True`` additionally returns per-iteration base residual
    norms and the analytically-known per-shift residuals
    (|r_s|^2 = zeta_s^2 |r|^2) as ``history`` for obs/convergence.py.

    Resolves the sentinel and the armed dslash fault from host state
    and runs ``multishift_cg_loop``; callable eagerly with any closure.
    """
    from ..robust import faultinject as finj
    from ..robust import sentinel as rsent
    shifts = [float(s) for s in shifts]
    if min(shifts) < shifts[0]:
        raise ValueError(
            f"multishift_cg: shift 0 ({shifts[0]:g}) must be the "
            f"smallest, got {shifts}")
    return multishift_cg_loop(
        matvec, b, jnp.asarray(shifts, b.real.dtype), tol, maxiter,
        record, rsent.make(), finj.iteration_fault("dslash"))


def multishift_cg_loop(matvec: Callable, b: jnp.ndarray, shifts,
                       tol, maxiter, record: bool = False, sent=None,
                       fault_k=None) -> MultiShiftResult:
    """The body of ``multishift_cg`` with every host-state knob an
    argument (``sent``: robust/sentinel.Sentinel or None; ``fault_k``:
    the armed dslash fault iteration or None), so it can sit under a
    cached ``jax.jit`` (solvers/program.py).  ``shifts`` is an
    (n_shifts,) real array, shift 0 the smallest, and may be traced, as
    may ``tol`` and (unless ``record`` sizes the history by it)
    ``maxiter``."""
    from ..robust import faultinject as finj
    from ..robust import sentinel as rsent
    ns = shifts.shape[0]
    b2 = blas.norm2(b)
    rdt = b2.dtype
    shifts = shifts.astype(rdt)
    s0 = shifts[0]
    sig = shifts - s0                                  # >= 0
    base = lambda v: matvec(v) + s0.astype(v.dtype) * v

    # tol in the residual's dtype first: a host float and the cached
    # program's operand then stop at the same iteration
    stop = (jnp.asarray(tol, rdt) ** 2) * b2

    def expand(a):
        """(ns,) scalars -> broadcastable over stacked fields."""
        return a.reshape((ns,) + (1,) * b.ndim)

    state = dict(
        x=jnp.zeros((ns,) + b.shape, b.dtype),
        p=jnp.broadcast_to(b, (ns,) + b.shape).astype(b.dtype),
        r=b,
        r2=b2,
        zeta=jnp.ones((ns,), rdt),
        zeta_old=jnp.ones((ns,), rdt),
        alpha_old=jnp.ones((), rdt),
        beta_old=jnp.zeros((), rdt),
        k=jnp.int32(0),
    )
    if record:
        state["hist"] = jnp.full((maxiter + 1,), jnp.nan, rdt)
        state["shist"] = jnp.full((maxiter + 1, ns), jnp.nan, rdt)
    if sent is not None:
        state["sent"] = sent.init(b2)

    def shift_r2(c):
        return (c["zeta"] ** 2) * c["r2"]

    def cond(c):
        go = jnp.logical_and(jnp.max(shift_r2(c)) > stop,
                             c["k"] < maxiter)
        if sent is not None:
            go = jnp.logical_and(go, sent.ok(c["sent"]))
        return go

    def body(c):
        p0 = c["p"][0]
        Ap = base(p0)
        if fault_k is not None:
            Ap = finj.corrupt(Ap, c["k"], fault_k)
        pAp = blas.redot(p0, Ap).astype(rdt)
        alpha = c["r2"] / pAp

        # zeta recurrence (Frommer/van der Vorst shifted CG)
        zn = c["zeta"] * c["zeta_old"] * c["alpha_old"]
        zd = (alpha * c["beta_old"] * (c["zeta_old"] - c["zeta"])
              + c["zeta_old"] * c["alpha_old"] * (1.0 + sig * alpha))
        zeta_new = jnp.where(zd != 0, zn / jnp.where(zd != 0, zd, 1.0), 0.0)
        alpha_s = alpha * jnp.where(c["zeta"] != 0,
                                    zeta_new / jnp.where(c["zeta"] != 0,
                                                         c["zeta"], 1.0), 0.0)

        x = c["x"] + expand(alpha_s).astype(b.dtype) * c["p"]
        r = c["r"] - alpha.astype(b.dtype) * Ap
        r2_new = blas.norm2(r).astype(rdt)
        beta = r2_new / c["r2"]
        beta_s = beta * jnp.where(
            c["zeta"] != 0,
            (zeta_new / jnp.where(c["zeta"] != 0, c["zeta"], 1.0)) ** 2, 0.0)
        p = (expand(zeta_new).astype(b.dtype) * r[None]
             + expand(beta_s).astype(b.dtype) * c["p"])

        nxt = dict(x=x, p=p, r=r, r2=r2_new, zeta=zeta_new,
                   zeta_old=c["zeta"], alpha_old=alpha, beta_old=beta,
                   k=c["k"] + 1)
        if record:
            nxt["hist"] = c["hist"].at[c["k"]].set(r2_new)
            nxt["shist"] = c["shist"].at[c["k"]].set(
                (zeta_new ** 2) * r2_new)
        if sent is not None:
            nxt["sent"] = sent.step(c["sent"], r2_new, denom=pAp)
        return nxt

    out = jax.lax.while_loop(cond, body, state)
    conv = shift_r2(out) <= stop
    hist = ({"r2": out["hist"], "shift_r2": out["shist"]} if record
            else None)
    conv, bk = rsent.finalize(sent, out.get("sent"), conv)
    return MultiShiftResult(out["x"], out["k"], out["r2"], conv, hist,
                            bk, shift_r2(out))
