"""The solver loop as ONE cached, jitted program per key.

``mixed.cg_reliable``, ``block.batched_cg_pairs`` and
``multishift.multishift_cg`` run ``lax.while_loop`` over closures;
called eagerly from the API with operators rebuilt per call, every call
re-traced the loop (a dozen pallas_calls), re-lowered it through Mosaic
and hashed the module before the persistent cache could serve the
executable: 1.2-1.4 s of host time per call at 24^4 with the device idle
(PERF_LEDGER, PR 25 lines).  Here the same loop bodies
(``cg_reliable_loop`` / ``batched_cg_pairs_loop`` /
``multishift_cg_loop``, no solver logic copied) sit under one
module-level ``jax.jit`` each, so a later call with the same key is an
in-process executable lookup.

* **Operands** (new values reuse the executable; nothing the size of a
  field is closed over): the source, the operators' resident links and
  kappa (the operator is a pytree: models/wilson
  ``DiracWilsonPCPackedSloppy.tree_flatten``), ``tol``, ``maxiter``
  unless ``record`` sizes the history by it, and the multi-shift
  program's shifts (their number is the operand's shape: an RHMC that
  changes its poles between the force and the action compiles once).
* **Key** (a change gives a new program, never a stale one), all
  resolved OUTSIDE the trace on every call: the operators' static
  signature (class, dims, matpc, tb_sign, pallas version, block_z,
  precision form, storage dtype, pallas/interpret flags: the treedef),
  the operand avals, and the loop's own knobs below (``delta``, the
  codec's storage dtype, ``check_every``, ``record``, the sentinel,
  the armed fault iteration, the multi-shift loop's form of its update:
  ``multishift.update_form``).  The key holds no array and no operator
  identity.

The verified exit of the Wilson, staggered and Möbius pair routes and
of the batched clover route is a program of the same kind
(``verified_exit``): from the canonical source and the pair-form
solution to the canonical solution and its true residual, the resident
f32 pair operator an operand (the clover operator with the A blocks of
its other parity a leaf beside its own: ``with_full_diag``).
``prepare`` is the entry's: the canonical source, split by parity, to
the pair-form PC right-hand side, or to the normal equations' where the
operator folds ``Mdag`` in (``prepare_normal_pairs``: Möbius, clover).

An operator goes through a program when it ``presents``: its class is a
registered pytree with a ``program_signature``.  Everything else (a
mesh operator, an MG closure, a bare lambda, the twisted pair
operators) keeps the eager solver call.  ``cg_reliable`` solves the NORMAL
equations of a non-Hermitian PC operator (``MdagM_pairs``: Wilson,
clover) and applies a ``hermitian`` one once an iteration (``M_pairs``:
the staggered PC operator is already 4m^2 - D D); the batched program
does the same on ``MdagM_pairs_mrhs`` / ``M_pairs_mrhs``.  Both loops
take the first half of an iteration from one callable, ``(p, r, rz,
k) -> (r - alpha A p, its squared norm(s), alpha, p . A p)``, and keep
the updates of ``x`` and ``p`` (the single-source loop its reliable
update too): the operator's own ``MdagM_cg_step_pairs`` /
``M_cg_step_pairs`` (the sloppy operator's, in its storage; since PR
50 the clover-type Schur pair operators on their fused kernels) or,
for a batch, ``MdagM_cg_step_pairs_mrhs`` / ``M_cg_step_pairs_mrhs``
(the Wilson pair operator and, since PR 48, the Schur pair operators)
where its class has one: ``pAp`` is ``|g5 M p|^2``, summed in the
epilogue of the kernel that stores ``g5 M p``, so ``alpha`` is known
before the last hop, whose epilogue writes ``r - alpha A p`` in
``r``'s place and sums it: ``A p`` is never stored.  Else
``mixed.cg_step`` of the matvec and the codec, ``block.cg_step`` for a
batch (XLA's dot, update and sum).  With a dslash fault armed it is
the generic step whatever the operator offers: the fault corrupts ``A
p``, which only that step has.  Which of the two is the operand's
class and static signature and ``knobs.fault_k``, so the key has no
field for it.  The single-source loop runs an operator's own step in
stretches (``mixed._cg_reliable_stretches``: the iterations in a
``while`` of their own, the reliable update between two of them, no
``lax.cond``) and the generic step in the one loop it always had, the
same arithmetic in the same order either way.  With a leading
source axis ``verified_exit`` and ``prepare`` are the batched route's.
``multishift_cg`` is the third loop, on one right-hand side and N
shifted systems; its exit (``verified_exit_shifts``) takes the N
solutions as a batch for the operator's batched hop.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax

from ..utils.frames import on_a_stack_chunk_of_its_own
from . import block, mixed, multishift

# program bodies run only while jax traces them: a call that leaves
# this count unchanged was served by the in-process executable cache
_traces = [0]


class _LoopKnobs(NamedTuple):
    """What the loops read from host state, resolved per call."""
    record: bool
    maxiter: Optional[int]       # only when ``record`` sizes by it
    sentinel: Optional[object]   # robust/sentinel.Sentinel (hashable)
    fault_k: Optional[int]       # armed dslash fault iteration


def _loop_knobs(record: bool, maxiter: int) -> _LoopKnobs:
    from ..robust import faultinject as finj
    from ..robust import sentinel as rsent
    return _LoopKnobs(bool(record), int(maxiter) if record else None,
                      rsent.make(), finj.iteration_fault("dslash"))


def presents(*ops) -> bool:
    """Whether every operator can cross the jit boundary as (static
    signature, array operands)."""
    return all(getattr(op, "program_signature", None) is not None
               for op in ops)


def _run(program, *operands, **static):
    n0 = _traces[0]
    out = program(*operands, **static)
    return out, _traces[0] == n0


@partial(jax.jit, static_argnames=("key",))
def _cg_reliable_program(op_hi, op_lo, b, tol, maxiter, key):
    _traces[0] += 1
    delta, codec_cfg, knobs, hermitian = key
    mv = "M" if hermitian else "MdagM"
    codec = mixed.pair_inplace_codec(codec_cfg)
    step = (getattr(op_lo, mv + "_cg_step_pairs", None)
            if knobs.fault_k is None else None)
    own = step is not None
    if not own:
        step = mixed.cg_step(getattr(op_lo, mv + "_pairs"), codec,
                             knobs.fault_k)
    # the operator's own step in the loop of stretches (no conditional
    # around its kernels), the generic step in the one loop as before;
    # traced from a stack chunk of its own, as the multi-shift loop is
    # (PERF.md section 7 (22))
    return on_a_stack_chunk_of_its_own(
        lambda: mixed.cg_reliable_loop(
            getattr(op_hi, mv + "_pairs"), step, b, tol,
            knobs.maxiter if knobs.record else maxiter, delta, codec,
            knobs.record, knobs.sentinel, stretches=own))


def cg_reliable(op_hi, op_lo, b, tol: float, maxiter: int, delta: float,
                record: bool = False):
    """``mixed.cg_reliable`` on ``op_hi.MdagM_pairs`` (precise) and
    ``op_lo.MdagM_pairs`` (sloppy storage, the in-place pair codec)
    through the cached program; on ``M_pairs`` where the operators say
    they are ``hermitian``; with the sloppy operator's own
    ``*_cg_step_pairs`` where it has one and no dslash fault is armed.
    Returns ``(SolverResult, hit)``."""
    key = (float(delta), mixed.pair_inplace_config(op_lo.store_dtype),
           _loop_knobs(record, maxiter),
           bool(getattr(op_hi, "hermitian", False)))
    return _run(_cg_reliable_program, op_hi, op_lo, b, float(tol),
                int(maxiter), key=key)


@partial(jax.jit, static_argnames=("key",))
def _batched_cg_pairs_program(op, B, tol, maxiter, key):
    _traces[0] += 1
    check_every, knobs, hermitian = key
    mv = "M" if hermitian else "MdagM"
    step = getattr(op, mv + "_cg_step_pairs_mrhs", None)
    if step is None or knobs.fault_k is not None:
        step = block.cg_step(getattr(op, mv + "_pairs_mrhs"),
                             knobs.fault_k)
    return block.batched_cg_pairs_loop(
        step, B, tol, knobs.maxiter if knobs.record else maxiter,
        check_every, knobs.record, knobs.sentinel)


def batched_cg_pairs(op, B, tol: float, maxiter: int,
                     record: bool = False):
    """``block.batched_cg_pairs`` on ``op.MdagM_pairs_mrhs`` through
    the cached program; on ``M_pairs_mrhs``, once an iteration, where
    the operator says it is ``hermitian``; with the operator's own
    ``*_cg_step_pairs_mrhs`` where it has one and no dslash fault is
    armed.  Returns ``(BatchedCGResult, hit)``."""
    from .fused_iter import _resolve_check_every
    key = (_resolve_check_every(None), _loop_knobs(record, maxiter),
           bool(getattr(op, "hermitian", False)))
    return _run(_batched_cg_pairs_program, op, B, float(tol),
                int(maxiter), key=key)


@partial(jax.jit, static_argnames=("key",))
def _multishift_program(op, b, shifts, tol, maxiter, key):
    _traces[0] += 1
    knobs, hermitian, update = key
    # traced from a stack chunk of its own: the operator's kernels
    # trace in 0.8 s from there and in 6-9 s from where this frame
    # happened to stand (PERF.md section 6, PR 41; section 7 (22))
    return on_a_stack_chunk_of_its_own(
        lambda: multishift.multishift_cg_loop(
            getattr(op, "M_pairs" if hermitian else "MdagM_pairs"), b,
            shifts, tol, knobs.maxiter if knobs.record else maxiter,
            knobs.record, knobs.sentinel, knobs.fault_k, update))


def multishift_cg(op, b, shifts, tol: float, maxiter: int,
                  record: bool = False):
    """``multishift.multishift_cg`` on ``op.M_pairs`` where the operator
    says it is ``hermitian`` (else ``op.MdagM_pairs``) through the
    cached program.  ``shifts`` is an (n,) f32 array, shift 0 the
    smallest: an operand, so other values of the same count reuse the
    executable.  Returns ``(MultiShiftResult, hit)``."""
    key = (_loop_knobs(record, maxiter),
           bool(getattr(op, "hermitian", False)),
           multishift.update_form(b))
    return _run(_multishift_program, op, b, shifts, float(tol),
                int(maxiter), key=key)


@jax.jit
def _verified_exit_shifts_program(op, b_pp, X_pp, shifts, claimed,
                                  shift_r2, bound):
    _traces[0] += 1
    return op.verified_exit_shifts_pairs(b_pp, X_pp, shifts, claimed,
                                         shift_r2, bound)


def verified_exit_shifts(op, b_pp, res, shifts, bound: float):
    """The verified exit of a multi-shift solve on the f32 pair operator
    ``op`` (``verified_exit_shifts_pairs``) through the cached program:
    the pair-form PC right-hand side, the loop's ``MultiShiftResult``
    and the shifts -> ``((canonical parity solutions (N, ...), true
    residuals (N,), the loop's analytic residuals (N,), verified flags
    (N,): the loop's claim AND a true residual <= bound), hit)``."""
    return _run(_verified_exit_shifts_program, op, b_pp, res.x, shifts,
                res.converged, res.shift_r2, float(bound))


@jax.jit
def _verified_exit_program(op, b, x_pp):
    _traces[0] += 1
    return op.verified_exit_pairs(b, x_pp)


def verified_exit(op, b, x_pp):
    """The verified exit of a solve on the f32 packed pair operator
    ``op`` (``verified_exit_pairs``: Wilson, staggered, Möbius, clover
    ``with_full_diag``) through the cached program: canonical source(s)
    and pair-form PC solution(s) ->
    ``((canonical full-lattice solution, true residual), hit)``.  With
    a leading source axis on ``b`` and ``x_pp`` the N residuals come
    back in one array."""
    return _run(_verified_exit_program, op, b, x_pp)


@jax.jit
def _prepare_program(op, b):
    _traces[0] += 1
    from ..fields.spinor import even_odd_split
    if hasattr(op, "prepare_normal_pairs"):
        # the operator's own entry, Mdag folded in: a 5-d operator (the
        # leading axis is Ls, never a batch) or the clover operator
        # (one source or a batch)
        return op.prepare_normal_pairs(b)
    if b.ndim == 7:
        return op.prepare_pairs_mrhs(
            *jax.vmap(lambda v: even_odd_split(v, op.geom))(b))
    return op.prepare_pairs(*even_odd_split(b, op.geom))


def prepare(op, b):
    """The entry of a solve on the pair operator ``op``: the canonical
    full-lattice source, split by parity and through
    ``op.prepare_pairs``, to the pair-form PC right-hand side, as one
    cached program; a batch of sources (a leading axis) through
    ``op.prepare_pairs_mrhs``; an operator's own
    ``prepare_normal_pairs`` where it has one (the Möbius pair
    operator: the split over every s-slice, ``prepare`` and ``Mdag``;
    the clover pair operator: the same for one source or a batch), so
    that what comes back is the normal equations' right-hand side.
    Returns ``(rhs, hit)``."""
    return _run(_prepare_program, op, b)
