"""Multi-shift CG: solve (A + sigma_i) x_i = b for all shifts at once.

Reference behavior: lib/inv_multi_cg_quda.cpp (493 LoC) — the RHMC
rational-approximation solver for staggered/HISQ.  One Krylov space serves
every shift via the shifted-CG zeta recurrences (a single matvec per
iteration); per-shift convergence is tracked through the analytically known
shifted residual |r_s| = zeta_s |r|.

The shifts are an ARRAY (``multishift_cg_loop``: an operand of the
cached program, solvers/program.multishift_cg, so an RHMC that changes
its poles between the force and the action compiles once); the shifted
iterates are a stacked leading axis.

The shifted update runs over the LIVE shifts only, as the reference's
does (``num_offset_now``): a shift whose own recurrence says
zeta_i |r| <= tol |b| is retired, its ``x_i`` left as it is and its row
of the stacks neither read nor written again.  What is updated is a
prefix ``[:n_active]`` of the stacks, 1 + the largest index still above
tol: right for any order of the offsets, least work for ascending ones
(QUDA's contract, MILC's practice), where the prefix is exactly the
live set.  Updating all N to the end cost a quarter of the HISQ RHMC
solve at fourteen shifts on a v5e, three quarters of it on shifts that
had converged (PERF.md section 6, PR 41).
"""

from __future__ import annotations

from functools import partial
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
    # (n_shifts,) analytic shifted residuals zeta_i^2 |r|^2 of the x_i
    # returned: a retired shift's is the one at its retirement
    shift_r2: object = None
    # (n_shifts,) int32: the iterations in which shift i was updated
    # (``iters`` for shift 0; their sum over N x iters is the share of
    # the full update's work that was done)
    shift_iters: object = None


def multishift_cg(matvec: Callable, b: jnp.ndarray,
                  shifts: Sequence[float], tol: float = 1e-10,
                  maxiter: int = 2000,
                  record: bool = False) -> MultiShiftResult:
    """Solve (matvec + shift_i) x_i = b, matvec Hermitian positive
    semi-definite and every shift >= 0 (the RHMC setting).

    The BASE system is shift 0's, which must be the smallest (QUDA
    takes its offsets ascending and iterates the zeroth; anything else
    is refused here, where the shifts are still host numbers);
    convergence of shift i is |r_i|^2 = zeta_i^2 |r|^2 <= tol^2 |b|^2,
    and a shift that has converged leaves the update: its ``x_i`` is
    the iterate of that iteration, ``shift_r2[i]`` the residual it was
    retired at and ``shift_iters[i]`` the iterations it was updated in.

    ``iters`` counts the loop's iterations: one application of
    ``matvec`` each, shared by every shift (the loop runs until the
    slowest shift, the base system, is under ``tol`` or ``maxiter`` is
    reached; it is NOT a sum over shifts).

    ``record=True`` additionally returns per-iteration base residual
    norms and the analytically-known per-shift residuals
    (|r_s|^2 = zeta_s^2 |r|^2) as ``history`` for obs/convergence.py.

    Resolves the sentinel, the armed dslash fault and the form of the
    update (``update_form``) from host state and runs
    ``multishift_cg_loop``; callable eagerly with any closure.
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
        record, rsent.make(), finj.iteration_fault("dslash"),
        update_form(b))


def update_form(b) -> str:
    """How ``multishift_cg_loop`` updates its live shifts for a source
    like ``b``: ``"pallas"``, the kernel
    (ops/blas_pallas.multishift_update_pallas), where the backend is a
    TPU and ``b`` a real array on ONE device whose row-blocks fit the
    kernel's VMEM budget (the pair representation of every TPU solve);
    ``"xla"`` (``update_live_xla``) everywhere else: a complex source,
    the CPU, and a source whose placement cannot be seen (a host array;
    a tracer of an outer jit, which GSPMD may have sharded: a kernel is
    not partitioned).  Read from host state, outside any trace."""
    from ..ops import blas_pallas as bpl
    if (jax.default_backend() != "tpu" or not isinstance(b, jax.Array)
            or isinstance(b, jax.core.Tracer) or b.ndim < 2
            or not jnp.issubdtype(b.dtype, jnp.floating)
            or len(b.sharding.device_set) != 1):
        return "xla"
    try:
        bpl._pick_rows(b.size // b.shape[-1], b.shape[-1], 5)
    except ValueError:
        return "xla"
    return "pallas"


def update_live_xla(n_active, alpha_s, zeta, beta_s, x, p, r):
    """``x[i] += alpha_s[i] p[i]``, ``p[i] = zeta[i] r + beta_s[i] p[i]``
    for i < ``n_active`` (traced, in [1, N]) on the stacked iterates,
    any dtype: a ``lax.switch`` over the N static prefix lengths, each
    branch ONE elementwise fusion that ends in a dynamic-update-slice
    of the stacks, so rows from ``n_active`` on are neither read nor
    written.  On a TPU XLA carries a stack in on-chip memory and copies
    it out of and back into it around the conditional, every
    iteration: there the real-valued solves take the kernel
    (``update_form``)."""
    ns = x.shape[0]

    def rows(a):
        return a.reshape(a.shape + (1,) * r.ndim).astype(x.dtype)

    def prefix(n):
        def f(x, p):
            if n == 1:
                # the base shift alone is plain CG's update on row 0,
                # written on that row: XLA's CPU code then rounds it as
                # it rounds the full update (the one-row slice of the
                # general form contracts its multiply-add the other way)
                a, z, bt = (v[0].astype(x.dtype)
                            for v in (alpha_s, zeta, beta_s))
                xn, pn = (x[0] + a * p[0])[None], (z * r + bt * p[0])[None]
            else:
                xn = x[:n] + rows(alpha_s[:n]) * p[:n]
                pn = rows(zeta[:n]) * r[None] + rows(beta_s[:n]) * p[:n]
            if n == ns:
                return xn, pn
            return (jax.lax.dynamic_update_slice_in_dim(x, xn, 0, 0),
                    jax.lax.dynamic_update_slice_in_dim(p, pn, 0, 0))
        return f

    return jax.lax.switch(n_active - 1,
                          [prefix(n) for n in range(1, ns + 1)], x, p)


def multishift_cg_loop(matvec: Callable, b: jnp.ndarray, shifts,
                       tol, maxiter, record: bool = False, sent=None,
                       fault_k=None,
                       update: str = "xla") -> MultiShiftResult:
    """The body of ``multishift_cg`` with every host-state knob an
    argument (``sent``: robust/sentinel.Sentinel or None; ``fault_k``:
    the armed dslash fault iteration or None; ``update``: what
    ``update_form`` read, or ``"pallas-interpret"`` for the kernel
    interpreted), so it can sit under a cached ``jax.jit``
    (solvers/program.py).
    ``shifts`` is an (n_shifts,) real array, shift 0 the smallest, and
    may be traced, as may ``tol`` and (unless ``record`` sizes the
    history by it) ``maxiter``."""
    from ..robust import faultinject as finj
    from ..robust import sentinel as rsent
    if update == "xla":
        update_live = update_live_xla
    else:
        from ..ops import blas_pallas as bpl
        update_live = partial(bpl.multishift_update_pallas,
                              interpret=update == "pallas-interpret")
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

    idx = jnp.arange(ns)

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
        # zeta_i^2 |r|^2 of x_i as it stands: a retired shift's stays
        shift_r2=jnp.full((ns,), b2, rdt),
        shift_iters=jnp.zeros((ns,), jnp.int32),
    )
    if record:
        state["hist"] = jnp.full((maxiter + 1,), jnp.nan, rdt)
        state["shist"] = jnp.full((maxiter + 1, ns), jnp.nan, rdt)
    if sent is not None:
        state["sent"] = sent.init(b2)

    def cond(c):
        go = jnp.logical_and(jnp.max(c["shift_r2"]) > stop,
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

        # zeta recurrence (Frommer/van der Vorst shifted CG): scalars,
        # carried on for every shift, retired or not
        zn = c["zeta"] * c["zeta_old"] * c["alpha_old"]
        zd = (alpha * c["beta_old"] * (c["zeta_old"] - c["zeta"])
              + c["zeta_old"] * c["alpha_old"] * (1.0 + sig * alpha))
        zeta_new = jnp.where(zd != 0, zn / jnp.where(zd != 0, zd, 1.0), 0.0)
        ratio = jnp.where(c["zeta"] != 0,
                          zeta_new / jnp.where(c["zeta"] != 0,
                                               c["zeta"], 1.0), 0.0)
        r = c["r"] - alpha.astype(b.dtype) * Ap
        r2_new = blas.norm2(r).astype(rdt)
        beta = r2_new / c["r2"]

        # the live prefix: 1 + the last shift still above tol (a row
        # past it holds a residual under tol that no longer changes, so
        # the prefix never grows back; shift 0 is in it to the end)
        n_active = jnp.max(jnp.where(c["shift_r2"] > stop, idx + 1, 1))
        live = idx < n_active
        x, p = update_live(n_active, alpha * ratio, zeta_new,
                           beta * ratio ** 2, c["x"], c["p"], r)
        sr2 = jnp.where(live, (zeta_new ** 2) * r2_new, c["shift_r2"])

        nxt = dict(x=x, p=p, r=r, r2=r2_new, zeta=zeta_new,
                   zeta_old=c["zeta"], alpha_old=alpha, beta_old=beta,
                   k=c["k"] + 1, shift_r2=sr2,
                   shift_iters=c["shift_iters"] + live.astype(jnp.int32))
        if record:
            nxt["hist"] = c["hist"].at[c["k"]].set(r2_new)
            nxt["shist"] = c["shist"].at[c["k"]].set(sr2)
        if sent is not None:
            nxt["sent"] = sent.step(c["sent"], r2_new, denom=pAp)
        return nxt

    out = jax.lax.while_loop(cond, body, state)
    conv = out["shift_r2"] <= stop
    hist = ({"r2": out["hist"], "shift_r2": out["shist"]} if record
            else None)
    conv, bk = rsent.finalize(sent, out.get("sent"), conv)
    return MultiShiftResult(out["x"], out["k"], out["r2"], conv, hist,
                            bk, out["shift_r2"], out["shift_iters"])
