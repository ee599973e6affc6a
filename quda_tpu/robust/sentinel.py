"""Shared in-loop breakdown sentinel for the Krylov solvers.

Reference behavior: the reference's solvers guard their compiled hot
loops against numerical breakdown — reliable updates recompute the true
residual (include/reliable_updates.h), the CG family checks pivots, and
the block solvers deflate singular Gram systems — so a solve that goes
non-finite exits with a diagnosable state instead of spinning NaN
arithmetic to maxiter ("A Framework for Lattice QCD Calculations on
GPUs", arXiv:1408.5925, production posture).  Before this module only
``solvers/block.block_cg_pairs`` had a finiteness guard; every other
while_loop would happily burn maxiter dslash applies on NaNs.

This module generalises that guard into ONE predicate threaded through
the loop carries of cg/fused_iter, mixed.cg_reliable[_df], bicgstab,
multishift, block and the small gcr-family loops:

* **non-finite residual** — |r|^2 is NaN/Inf (SDC, overflow, a poisoned
  operand);
* **pivot breakdown** — a CG-family denominator (pAp) non-finite or
  <= 0: the operator is not behaving HPD on this Krylov space;
* **stagnation** — the residual has not improved for
  QUDA_TPU_ROBUST_STAGNATION consecutive convergence checks (opt-in,
  0 = disabled: plateaus are workload-dependent).

Zero-overhead contract (the obs no-op-span discipline): with
``QUDA_TPU_ROBUST=off`` :func:`make` returns ``None`` and the solvers
build EXACTLY the loop they build today — same carry structure, same
ops, bit-identical compiled solve (pinned by tests/test_robust.py's
raising-stub test).  When active, the carry gains a three-scalar state
``(code, best_r2, checks_since_improvement)`` and the loop cond gains
one ``code == 0`` conjunct; the first breakdown is sticky and is
surfaced as ``SolverResult.breakdown`` for the API layer's verified
exits and escalation ladder (robust/escalate.py).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

# breakdown reason codes (static ints so they compile into the loop)
NONE = 0
NONFINITE = 1          # |r|^2 went NaN/Inf
PIVOT = 2              # CG denominator (pAp) non-finite or <= 0
STAGNATION = 3         # no residual improvement for N checks

REASONS = {NONE: "none", NONFINITE: "nonfinite", PIVOT: "pivot",
           STAGNATION: "stagnation"}


def mode() -> str:
    """Current QUDA_TPU_ROBUST level: 'off' | 'verify' | 'escalate'."""
    from ..utils import config as qconf
    return str(qconf.get("QUDA_TPU_ROBUST", fresh=True)) or "off"


def active() -> bool:
    return mode() != "off"


def reason(code) -> str:
    """Host-side name of a breakdown code (unknown codes stringify)."""
    return REASONS.get(int(code), f"code{int(code)}")


def make(stagnation_checks: Optional[int] = None) -> Optional["Sentinel"]:
    """The per-solve sentinel, or ``None`` when QUDA_TPU_ROBUST=off —
    the None path is the zero-overhead contract: callers guard every
    sentinel touch with ``if sent is not None`` so the disabled solve
    traces exactly the pre-sentinel computation."""
    if not active():
        return None
    if stagnation_checks is None:
        from ..utils import config as qconf
        stagnation_checks = int(qconf.get("QUDA_TPU_ROBUST_STAGNATION",
                                          fresh=True))
    # flight-recorder marker (host-side, no-op when QUDA_TPU_FLIGHT is
    # off): the ring shows which solves ran sentinel-guarded, so a
    # postmortem tail distinguishes "breakdown detected" from "nothing
    # was watching" — the trip itself arrives via the
    # breakdown_detected trace-event tap
    from ..obs import flight as ofl
    ofl.record("sentinel_armed", cat="robust", mode=mode(),
               stagnation=stagnation_checks)
    return Sentinel(stagnation_checks)


def finalize(sent, state, conv):
    """Shared solver-exit epilogue: returns ``(converged, breakdown)``
    where a tripped sentinel masks the convergence claim (a NaN
    residual compares False against the CONTINUE criterion ``r2 >
    stop``, so the naive not-not-done exit would report a poisoned
    solve as converged) and exposes the typed code.  ``sent is None``
    (QUDA_TPU_ROBUST=off) passes ``conv`` through untouched with
    ``breakdown=None`` — zero ops added."""
    if sent is None:
        return conv, None
    code = sent.code(state)
    return jnp.logical_and(conv, code == NONE), code


class Sentinel:
    """In-loop breakdown predicate over a (code, best_r2, since) state
    tuple.  ``init`` seeds the state from the initial residual norm,
    ``step`` runs once per convergence check inside the loop body, and
    ``ok`` is the extra while_loop cond conjunct.  The first non-NONE
    code is sticky so the exit state names the ORIGINAL failure, not a
    downstream symptom."""

    __slots__ = ("stagnation_checks",)

    def __init__(self, stagnation_checks: int = 0):
        self.stagnation_checks = int(stagnation_checks)

    # value semantics: a sentinel is part of a cached solve program's
    # key (solvers/program.py), and two with one count trace alike
    def __eq__(self, other):
        return (isinstance(other, Sentinel)
                and self.stagnation_checks == other.stagnation_checks)

    def __hash__(self):
        return hash((Sentinel, self.stagnation_checks))

    def init(self, r2):
        r2 = jnp.asarray(r2)
        return (jnp.int32(NONE), r2, jnp.int32(0))

    def step(self, state, r2, denom=None):
        """Advance the state with this check point's residual norm (a
        scalar; batched solvers pass an aggregate that propagates any
        lane's NaN, e.g. the sum) and optionally the CG pivot
        denominator pAp (HPD solves only — it must be finite and
        positive there)."""
        code, best, since = state
        r2 = jnp.asarray(r2)
        nonfin = jnp.logical_not(jnp.isfinite(r2))
        if denom is not None:
            # a FINITE non-positive pivot is the PIVOT class (the
            # operator is not behaving HPD — the original cause, which
            # this same step's r2 overflow would otherwise mask); a
            # non-finite denominator is just more non-finiteness
            d = jnp.asarray(denom)
            d_fin = jnp.isfinite(d)
            pivot = jnp.logical_and(d_fin, d <= 0)
            nonfin = jnp.logical_or(nonfin, jnp.logical_not(d_fin))
            new = jnp.where(pivot, PIVOT,
                            jnp.where(nonfin, NONFINITE, NONE))
        else:
            new = jnp.where(nonfin, NONFINITE, NONE)
        improved = r2 < best
        best = jnp.where(improved, r2, best)
        since = jnp.where(improved, 0, since + 1).astype(jnp.int32)
        if self.stagnation_checks > 0:
            stalled = since >= self.stagnation_checks
            new = jnp.where(jnp.logical_and(new == NONE, stalled),
                            STAGNATION, new)
        code = jnp.where(code == NONE, new, code).astype(jnp.int32)
        return (code, best, since)

    def ok(self, state):
        return state[0] == NONE

    @staticmethod
    def code(state):
        """The int32 breakdown code of an exited state (NONE = clean)."""
        return state[0]
