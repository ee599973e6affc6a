"""Mixed-precision solving: reliable updates and iterative refinement.

QUDA threads sloppy/precise operator pairs through every solver
(include/invert_quda.h; reliable update logic include/reliable_updates.h:33-54
and lib/inv_cg_quda.cpp).  The TPU precision ladder differs from CUDA's
{double,single,half,quarter}: the compute dtypes are
{float64 (CPU only), float32/complex64, bfloat16-pair} — see
utils/precision.py.  'quarter' drops the LINKS (not the iterates) to
int8 block-float storage — ops/blockfloat.to_int8_links resident gauge,
decompressed at link load inside the kernel, served under the df64
reliable update (interfaces/quda_api._invert_wilson_df64 +
models/wilson precision_form="int8"); spinor iterates stay bf16 pairs,
so the codecs below are unchanged.  Two strategies are provided:

* ``cg_reliable``: QUDA-style in-loop reliable updates — iterate entirely in
  the sloppy precision inside one lax.while_loop; when the sloppy residual
  falls below ``delta`` * (max residual since the last update), recompute the
  true residual with the precise operator and re-inject it (lax.cond keeps
  this branch-free for XLA; where the sloppy operator owns the CG step the
  update stands between two stretches of an inner while_loop instead, so
  the iterations' vectors can stay on chip).  The whole solve is ONE
  compiled computation.

* ``solve_refined``: outer defect-correction (iterative refinement) driving
  any inner solver — the pattern QUDA calls refinement in multi-shift
  (lib/inv_multi_cg_quda.cpp final refinement phase).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops import blas
from .block import cg_alpha
from .cg import SolverResult, cg


class StorageCodec(NamedTuple):
    """How the sloppy iterates are stored and operated on.

    ``down``/``up`` convert between the precise representation (complex
    array) and the sloppy storage; ``norm2``/``redot`` reduce in storage;
    ``axpy(a, x, y) = y + a*x`` for REAL scalar a, computed at f32 and
    rounded back to storage; ``axpy_norm2(a, x, y) = (y + a*x, |..|^2)``
    is the fused update+reduce tail (one traversal under jit — the
    reduce_core.cuh:668 axpyNorm2 analog).  Two instances cover the TPU
    ladder: a plain dtype cast (single sloppy) and bf16/int8 pair
    storage (half/quarter — see ops/pair.py).
    """
    down: Callable
    up: Callable
    norm2: Callable
    redot: Callable
    axpy: Callable
    axpy_norm2: Optional[Callable] = None


def dtype_codec(sloppy_dtype, precise_dtype) -> StorageCodec:
    def _axpy_norm2(a, x, y):
        return blas.axpy_norm2(a.astype(sloppy_dtype), x, y)
    return StorageCodec(
        down=lambda x: x.astype(sloppy_dtype),
        up=lambda x: x.astype(precise_dtype),
        norm2=blas.norm2,
        redot=blas.redot,
        axpy=lambda a, x, y: y + a.astype(sloppy_dtype) * x,
        axpy_norm2=_axpy_norm2)


def _make_pair_codec(down, up, store_dtype) -> StorageCodec:
    """Shared reductions/axpy for every pair-storage layout — ONE home
    for the f32-accumulate rounding policy the reliable updates rely on;
    layouts differ only in their down/up converters.  The fused
    update+reduce takes its norm on the ROUNDED stored value."""
    from ..ops import pair as pops
    f32 = jnp.float32

    def axpy(a, x, y):
        return (y.astype(f32) + a.astype(f32) * x.astype(f32)
                ).astype(store_dtype)

    def axpy_norm2(a, x, y):
        out = axpy(a, x, y)
        return out, pops.pair_norm2(out)

    return StorageCodec(
        down=down, up=up,
        norm2=pops.pair_norm2,
        redot=pops.pair_redot,
        axpy=axpy,
        axpy_norm2=axpy_norm2)


def pair_codec(store_dtype, precise_dtype) -> StorageCodec:
    from ..ops import pair as pops
    return _make_pair_codec(
        lambda x: pops.to_pairs(x, store_dtype),
        lambda x: pops.from_pairs(x, precise_dtype), store_dtype)


def packed_pair_codec(store_dtype, precise_dtype) -> StorageCodec:
    """Pair storage on the PACKED device layout: re/im as axis 2 of
    (4,3,2,T,Z,YX) (ops/wilson_packed pair stencils)."""
    from ..ops import wilson_packed as wpk
    return _make_pair_codec(
        lambda x: wpk.to_packed_pairs(x, store_dtype),
        lambda x: wpk.from_packed_pairs(x, precise_dtype), store_dtype)


def pair_inplace_config(store_dtype):
    """What of ``pair_inplace_codec`` the cached solve program
    (solvers/program.py) keys on and rebuilds the codec from inside its
    trace: the storage dtype, hashable, and nothing else."""
    return jnp.dtype(store_dtype)


def pair_inplace_codec(store_dtype) -> StorageCodec:
    """Codec for when the PRECISE representation is itself an f32 pair
    array on the SAME layout as the sloppy storage — the fully
    complex-free solve path (TPU runtimes without complex64 execution;
    also the zero-conversion native-order path).  down/up are plain
    dtype casts."""
    store_dtype = pair_inplace_config(store_dtype)
    return _make_pair_codec(
        lambda x: x.astype(store_dtype),
        lambda x: x.astype(jnp.float32), store_dtype)


def cg_reliable(matvec_hi: Callable, matvec_lo: Callable, b: jnp.ndarray,
                sloppy_dtype=None, tol: float = 1e-10, maxiter: int = 2000,
                delta: float = 0.1,
                codec: Optional[StorageCodec] = None,
                record: bool = False) -> SolverResult:
    """Mixed-precision CG with reliable updates.

    matvec_hi acts on the precise (complex) representation; matvec_lo acts
    on the SLOPPY STORAGE (a complex array for a dtype codec, a (...,2)
    pair array for the bf16/int8 codec).  Convergence is judged on the
    TRUE residual norm maintained through reliable updates, so the
    returned r2 is trustworthy at the precise level.

    ``record=True`` returns ``history={'r2': per-iteration residual
    norms (the true residual at reliable-update iterations, the sloppy
    one otherwise), 'reliable': per-iteration reliable-update flags}``
    for obs/convergence.py; record=False leaves the carry unchanged.
    """
    if codec is None:
        if sloppy_dtype is None:
            raise ValueError("cg_reliable needs sloppy_dtype or codec")
        codec = dtype_codec(sloppy_dtype, b.dtype)
    # breakdown sentinel + dslash fault site (robust/): None/None at
    # QUDA_TPU_ROBUST=off & nothing armed — the loop then traces the
    # exact unguarded computation
    from ..robust import faultinject as finj
    from ..robust import sentinel as rsent
    step = cg_step(matvec_lo, codec, finj.iteration_fault("dslash"))
    return cg_reliable_loop(matvec_hi, step, b, tol, maxiter, delta,
                            codec, record, rsent.make())


def cg_step(matvec_lo: Callable, codec: StorageCodec,
            fault_k: Optional[int] = None) -> Callable:
    """The generic first half of a ``cg_reliable_loop`` iteration on a
    sloppy matvec: ``step(p, r_lo, r2_lo, k) -> (r_lo - alpha A p, its
    squared norm, alpha, p . A p)``, ``A p`` stored, the dot, the
    update and the sum the codec's (XLA passes over the stored
    vectors), the scalars at ``r2_lo``'s dtype.  ``fault_k``: the armed
    dslash fault iteration or None; the fault corrupts ``A p``, which
    only this step has."""
    from ..robust import faultinject as finj

    def step(p, r_lo, r2_lo, k):
        rdt = r2_lo.dtype
        Ap = matvec_lo(p)
        if fault_k is not None:
            Ap = finj.corrupt(Ap, k, fault_k)
        pAp = codec.redot(p, Ap).astype(rdt)
        alpha = cg_alpha(r2_lo, pAp)
        # fused residual update+reduce: one traversal (optionally the
        # single-pass pallas kernel, see StorageCodec.axpy_norm2)
        if codec.axpy_norm2 is not None:
            r_lo, r2_new = codec.axpy_norm2(-alpha, Ap, r_lo)
        else:
            r_lo = codec.axpy(-alpha, Ap, r_lo)
            r2_new = codec.norm2(r_lo)
        return r_lo, r2_new.astype(rdt), alpha, pAp
    return step


def cg_reliable_loop(matvec_hi: Callable, step: Callable,
                     b: jnp.ndarray, tol, maxiter, delta: float,
                     codec: StorageCodec, record: bool,
                     sent, stretches: bool = False) -> SolverResult:
    """``cg_reliable`` with every knob already resolved by the caller
    (``sent``: robust/sentinel.Sentinel or None), so nothing here reads
    host state: the body the cached solve program (solvers/program.py)
    traces once per key.  ``tol`` and ``maxiter`` may be traced
    scalars, except that ``record`` sizes the history by a concrete
    ``maxiter``.

    ``step(p, r_lo, r2_lo, k) -> (r_lo - alpha A p, its squared norm,
    alpha, p . A p)`` is the first half of an iteration on the sloppy
    operator, in storage: ``cg_step`` of a matvec and the codec (with
    the armed dslash fault, if any), or the operator's own where its
    kernels make those four on the way (models/wilson
    ``_SchurPairOpBase.MdagM_cg_step_pairs``: ``A p`` is never stored
    and no pass over the vectors makes a dot or a sum).  The loop keeps
    ``x_lo``, ``beta``, ``p``, the sentinel, the reliable update on
    ``matvec_hi`` and the exit, as ``block.batched_cg_pairs_loop``
    does for a batch.

    ``stretches``: the same arithmetic in the same order as two nested
    loops (``_cg_reliable_stretches``), where the caller's step is the
    operator's own."""
    if stretches:
        return _cg_reliable_stretches(matvec_hi, step, b, tol, maxiter,
                                      delta, codec, record, sent)
    from ..robust import sentinel as rsent
    b2 = blas.norm2(b)
    stop = (tol ** 2) * b2

    x = jnp.zeros_like(b)          # precise accumulated solution
    r = b                          # precise residual
    r2 = b2
    r_lo = codec.down(r)
    p = r_lo
    x_lo = jnp.zeros_like(r_lo)    # sloppy partial solution since last update
    rdt = jnp.zeros((), b.dtype).real.dtype

    def cond(c):
        go = jnp.logical_and(c["r2"] > stop, c["k"] < maxiter)
        if sent is not None:
            go = jnp.logical_and(go, sent.ok(c["sent"]))
        return go

    def body(c):
        r_lo, r2_new, alpha, pAp = step(c["p"], c["r_lo"], c["r2_lo"],
                                        c["k"])
        x_lo = codec.axpy(alpha, c["p"], c["x_lo"])
        beta = r2_new / c["r2_lo"]
        p = codec.axpy(beta, c["p"], r_lo)
        r2max = jnp.maximum(c["r2max"], r2_new)
        st_new = (sent.step(c["sent"], r2_new, denom=pAp)
                  if sent is not None else None)

        do_reliable = jnp.logical_or(r2_new < (delta ** 2) * r2max,
                                     r2_new < stop)

        def reliable(_):
            x_new = c["x"] + codec.up(x_lo)
            r_true = c["b"] - matvec_hi(x_new)
            # compensated: the reported residual must be trustworthy
            # below the plain-f32 accumulation floor (dbldbl.h analog)
            r2_true = blas.norm2_comp(r_true).astype(rdt)
            d = dict(
                c, x=x_new, r=r_true, r2=r2_true,
                r_lo=codec.down(r_true),
                # restart the direction at the true residual (QUDA resets
                # beta using the new residual after a reliable update)
                p=codec.down(r_true),
                x_lo=jnp.zeros_like(x_lo),
                r2_lo=r2_true, r2max=r2_true, k=c["k"] + 1)
            if record:
                d["hist"] = c["hist"].at[c["k"]].set(r2_true)
                d["rel"] = c["rel"].at[c["k"]].set(True)
            if sent is not None:
                d["sent"] = st_new
            return d

        def keep(_):
            d = dict(c, p=p, r_lo=r_lo, x_lo=x_lo, r2_lo=r2_new,
                     r2=r2_new.astype(rdt), r2max=r2max, k=c["k"] + 1)
            if record:
                d["hist"] = c["hist"].at[c["k"]].set(r2_new.astype(rdt))
                d["rel"] = c["rel"]
            if sent is not None:
                d["sent"] = st_new
            return d

        return jax.lax.cond(do_reliable, reliable, keep, None)

    init = dict(b=b, x=x, r=r, r2=r2.astype(rdt), r_lo=r_lo, p=p, x_lo=x_lo,
                r2_lo=r2.astype(rdt), r2max=r2.astype(rdt), k=jnp.int32(0))
    if record:
        init["hist"] = jnp.full((maxiter + 1,), jnp.nan, rdt)
        init["rel"] = jnp.zeros((maxiter + 1,), bool)
    if sent is not None:
        init["sent"] = sent.init(r2.astype(rdt))
    out = jax.lax.while_loop(cond, body, init)
    # final fold of any un-injected sloppy contribution
    x_fin = out["x"] + codec.up(out["x_lo"])
    r_fin = b - matvec_hi(x_fin)
    r2_fin = blas.norm2_comp(r_fin)
    hist = ({"r2": out["hist"], "reliable": out["rel"]} if record
            else None)
    conv, bk = rsent.finalize(sent, out.get("sent"), r2_fin <= stop)
    return SolverResult(x_fin, out["k"], r2_fin, conv, hist, bk)


def _cg_reliable_stretches(matvec_hi, step, b, tol, maxiter, delta,
                           codec, record, sent) -> SolverResult:
    """``cg_reliable_loop`` as two nested loops, the same arithmetic in
    the same order: the inner runs sloppy iterations until one asks for
    a reliable update (``due``) or ends the solve, the outer makes that
    update and goes on.  No conditional anywhere: a ``lax.cond`` in the
    iterations' loop sends every vector it carries through HBM each
    iteration and keeps XLA from holding them on chip, and an
    operator's own step, with no XLA pass left between its kernels,
    earns nothing end to end under one (PERF.md section 6, PR 50).  A
    stretch that ends the solve without asking (``maxiter``, the
    sentinel) gets the update too: one precise matvec more at the end
    of a solve that failed, ``x`` folded where the exit would fold it,
    the same ``SolverResult``.  The loops on the generic step keep the
    one-loop form, which the benchmark's loop readers count right
    (PERF.md section 7 (44))."""
    from ..robust import sentinel as rsent
    b2 = blas.norm2(b)
    stop = (tol ** 2) * b2
    rdt = jnp.zeros((), b.dtype).real.dtype
    r2 = b2.astype(rdt)
    r_lo = codec.down(b)           # the precise residual at x = 0 is b

    def go(c):
        s = c["lo"]
        ok = jnp.logical_and(s["r2"] > stop, s["k"] < maxiter)
        if sent is not None:
            ok = jnp.logical_and(ok, sent.ok(s["sent"]))
        return ok

    def sloppy(s):
        r_lo, r2_new, alpha, pAp = step(s["p"], s["r_lo"], s["r2_lo"],
                                        s["k"])
        x_lo = codec.axpy(alpha, s["p"], s["x_lo"])
        beta = r2_new / s["r2_lo"]
        p = codec.axpy(beta, s["p"], r_lo)
        r2max = jnp.maximum(s["r2max"], r2_new)
        d = dict(s, p=p, r_lo=r_lo, x_lo=x_lo, r2_lo=r2_new,
                 r2=r2_new.astype(rdt), r2max=r2max, k=s["k"] + 1,
                 due=jnp.logical_or(r2_new < (delta ** 2) * r2max,
                                    r2_new < stop))
        if record:
            d["hist"] = s["hist"].at[s["k"]].set(r2_new.astype(rdt))
        if sent is not None:
            d["sent"] = sent.step(s["sent"], r2_new, denom=pAp)
        return d

    def reliable(c):
        s = c["lo"]
        x_new = c["x"] + codec.up(s["x_lo"])
        r_true = b - matvec_hi(x_new)
        # compensated, and the direction restarted, as in the one loop
        r2_true = blas.norm2_comp(r_true).astype(rdt)
        r_lo = codec.down(r_true)
        d = dict(c, x=x_new, lo=dict(
            s, r_lo=r_lo, p=r_lo, x_lo=jnp.zeros_like(r_lo), r2=r2_true,
            r2_lo=r2_true, r2max=r2_true, due=jnp.bool_(False)))
        if record:
            # the update belongs to the iteration that asked for it
            # (none did where the stretch ended the solve)
            at = s["k"] - 1
            d["lo"]["hist"] = s["hist"].at[at].set(
                jnp.where(s["due"], r2_true, s["hist"][at]))
            d["rel"] = c["rel"].at[at].set(s["due"])
        return d

    def stretch(c):
        lo = jax.lax.while_loop(
            lambda s: jnp.logical_and(go({"lo": s}),
                                      jnp.logical_not(s["due"])),
            sloppy, c["lo"])
        return reliable(dict(c, lo=lo))

    lo = dict(r_lo=r_lo, p=r_lo, x_lo=jnp.zeros_like(r_lo), r2=r2,
              r2_lo=r2, r2max=r2, k=jnp.int32(0), due=jnp.bool_(False))
    init = dict(x=jnp.zeros_like(b), lo=lo)
    if record:
        lo["hist"] = jnp.full((maxiter + 1,), jnp.nan, rdt)
        init["rel"] = jnp.zeros((maxiter + 1,), bool)
    if sent is not None:
        lo["sent"] = sent.init(r2)
    out = jax.lax.while_loop(go, stretch, init)
    lo = out["lo"]
    # final fold of any un-injected sloppy contribution
    x_fin = out["x"] + codec.up(lo["x_lo"])
    r_fin = b - matvec_hi(x_fin)
    r2_fin = blas.norm2_comp(r_fin)
    hist = ({"r2": lo["hist"], "reliable": out["rel"]} if record
            else None)
    conv, bk = rsent.finalize(sent, lo.get("sent"), r2_fin <= stop)
    return SolverResult(x_fin, lo["k"], r2_fin, conv, hist, bk)


def cg_reliable_df(op_df, matvec_lo: Callable, rhs_df, codec: StorageCodec,
                   tol: float = 1e-10, maxiter: int = 4000,
                   delta: float = 0.1, record: bool = False) -> SolverResult:
    """Extended-precision reliable-update CG on the normal equations.

    The TPU analog of QUDA's double-precise / sloppy-pair solve to 1e-10
    (fp64 matPrecise in lib/inv_cg_quda.cpp:63 + dbldbl accumulators,
    include/dbldbl.h): the precise side runs in df64 (float32-pair,
    ops/df64.py) — no f64, no complex, executable on TPU.

    * ``op_df``: df64 operator bundle (ops/wilson_df64.WilsonPCDF64):
      ``M``/``Mdag`` on df64 fields and ``residual_df``.
    * ``matvec_lo``: the SLOPPY normal operator (MdagM) acting on the
      storage representation (f32/bf16 pair arrays, same layout as the
      df64 hi word).
    * ``rhs_df``: df64 DIRECT rhs (the PC system b).  The loop iterates
      on Mdag M x = Mdag b in sloppy storage; convergence is judged on
      the df64 DIRECT residual |b - M x| recomputed at every reliable
      update, so the returned r2 certifies the direct system at the
      ~1e-14 df64 floor.

    The normal-residual trigger threshold tightens itself (x1/16) when
    the normal system looks converged but the direct residual is not —
    the branch-free analog of QUDA tightening solver tolerances between
    refinement cycles.
    """
    from ..ops import df64 as dfm
    from ..robust import sentinel as rsent
    sent = rsent.make()

    f32 = jnp.float32
    b2d = dfm.to_f32(dfm.norm2(rhs_df)).astype(f32)
    stop_d = (tol ** 2) * b2d

    rn_df = op_df.Mdag(rhs_df)           # normal residual at x = 0
    rn = dfm.to_f32(rn_df)
    bn2 = dfm.to_f32(dfm.norm2_f32(rn)).astype(f32)
    stop_n = (tol ** 2) * bn2

    x = (jnp.zeros_like(rhs_df[0]), jnp.zeros_like(rhs_df[1]))
    r_lo = codec.down(rn)
    x_lo = jnp.zeros_like(r_lo)
    rn2 = codec.norm2(r_lo).astype(f32)

    def cond(c):
        go = jnp.logical_and(c["d2"] > stop_d, c["k"] < maxiter)
        if sent is not None:
            go = jnp.logical_and(go, sent.ok(c["sent"]))
        return go

    def body(c):
        Ap = matvec_lo(c["p"])
        pAp = codec.redot(c["p"], Ap).astype(f32)
        alpha = c["r2_lo"] / jnp.maximum(pAp, jnp.finfo(f32).tiny)
        x_lo = codec.axpy(alpha, c["p"], c["x_lo"])
        if codec.axpy_norm2 is not None:
            r_lo, r2_new = codec.axpy_norm2(-alpha, Ap, c["r_lo"])
            r2_new = r2_new.astype(f32)
        else:
            r_lo = codec.axpy(-alpha, Ap, c["r_lo"])
            r2_new = codec.norm2(r_lo).astype(f32)
        beta = r2_new / c["r2_lo"]
        p = codec.axpy(beta, c["p"], r_lo)
        r2max = jnp.maximum(c["r2max"], r2_new)
        st_new = (sent.step(c["sent"], r2_new, denom=pAp)
                  if sent is not None else None)

        do_reliable = jnp.logical_or(r2_new < (delta ** 2) * r2max,
                                     r2_new < c["stop_n"])

        def reliable(_):
            x_new = dfm.add(c["x"], dfm.promote(codec.up(x_lo)))
            d_df = op_df.residual_df(rhs_df, x_new)
            d2 = dfm.to_f32(dfm.norm2(d_df)).astype(f32)
            rn_df = op_df.Mdag(d_df)
            rn = dfm.to_f32(rn_df)
            rn2_true = dfm.to_f32(dfm.norm2_f32(rn)).astype(f32)
            # not converged on the direct system but the normal target
            # was met -> tighten the inner target
            tighten = jnp.logical_and(d2 > stop_d,
                                      rn2_true <= c["stop_n"])
            stop_n_new = jnp.where(tighten, c["stop_n"] / 16.0,
                                   c["stop_n"])
            d = dict(
                c, x=x_new, d2=d2, stop_n=stop_n_new,
                r_lo=codec.down(rn), p=codec.down(rn),
                x_lo=jnp.zeros_like(x_lo),
                r2_lo=rn2_true, r2max=rn2_true, k=c["k"] + 1)
            if sent is not None:
                d["sent"] = st_new
            if record:
                # record the TRUE normal-equation residual, not d2: the
                # keep branch records sloppy normal-eq norms, and one
                # history must stay one system or the curve is
                # unreadable (the direct-system certificate is the
                # returned r2, judged against stop_d)
                d["hist"] = c["hist"].at[c["k"]].set(rn2_true)
                d["rel"] = c["rel"].at[c["k"]].set(True)
            return d

        def keep(_):
            d = dict(c, p=p, r_lo=r_lo, x_lo=x_lo, r2_lo=r2_new,
                     r2max=r2max, k=c["k"] + 1)
            if record:
                d["hist"] = c["hist"].at[c["k"]].set(r2_new)
                d["rel"] = c["rel"]
            if sent is not None:
                d["sent"] = st_new
            return d

        return jax.lax.cond(do_reliable, reliable, keep, None)

    init = dict(x=x, d2=b2d, stop_n=stop_n, r_lo=r_lo, p=r_lo, x_lo=x_lo,
                r2_lo=rn2, r2max=rn2, k=jnp.int32(0))
    if record:
        init["hist"] = jnp.full((maxiter + 1,), jnp.nan, f32)
        init["rel"] = jnp.zeros((maxiter + 1,), bool)
    if sent is not None:
        init["sent"] = sent.init(rn2)
    out = jax.lax.while_loop(cond, body, init)
    x_fin = dfm.add(out["x"], dfm.promote(codec.up(out["x_lo"])))
    d_df = op_df.residual_df(rhs_df, x_fin)
    d2_fin = dfm.to_f32(dfm.norm2(d_df))
    # the history is the NORMAL-equation residual curve (|Mdag r|^2,
    # sloppy between reliable updates, true at them) — ship its own
    # reference norm |Mdag b|^2 so harvest() normalizes relres in the
    # recorded system instead of the caller's direct-system b2
    hist = ({"r2": out["hist"], "reliable": out["rel"], "b2": bn2}
            if record else None)
    conv, bk = rsent.finalize(sent, out.get("sent"), d2_fin <= stop_d)
    return SolverResult(x_fin, out["k"], d2_fin, conv, hist, bk)


def solve_refined(matvec_hi: Callable, inner_solve: Callable, b: jnp.ndarray,
                  sloppy_dtype, tol: float = 1e-10, max_cycles: int = 10):
    """Defect-correction refinement: repeat { r = b - A x ;  x += solve(r) }.

    ``inner_solve(rhs) -> x`` runs at sloppy_dtype (any solver).  Host-side
    outer loop (few cycles), jitted inner — QUDA's refinement phase pattern.
    """
    b2 = float(blas.norm2(b))
    stop = (tol ** 2) * b2
    x = jnp.zeros_like(b)
    r = b
    cycles = 0
    for _ in range(max_cycles):
        y = inner_solve(r.astype(sloppy_dtype))
        x = x + y.astype(x.dtype)
        r = b - matvec_hi(x)
        cycles += 1
        if float(blas.norm2(r)) <= stop:
            break
    r2 = blas.norm2(r)
    return SolverResult(x, jnp.int32(cycles), r2, r2 <= stop)
