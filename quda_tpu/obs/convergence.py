"""Per-solve convergence recording: residual histories + solver events.

Reference behavior: the reference prints per-iteration residuals at
VERBOSE verbosity from every solver (PrintStats, lib/solver.cpp) and
reports reliable-update/restart events; convergence history is the
first thing a failing production solve needs and the one thing a
compiled lax.while_loop hides.

TPU mechanics: solvers cannot append to host lists from inside a
while_loop, so each solver (solvers/cg.py, fused_iter.py, mixed.py,
multishift.py, bicgstab.py, block.py) takes an opt-in ``record=True``
that threads a preallocated NaN-filled history buffer through the loop
carry — written at convergence-check points, i.e. every iteration at
cadence 1 and every k-th at QUDA_TPU_CG_CHECK_EVERY=k — and returns it
as ``SolverResult.history``.  ``harvest`` turns that device buffer into
a host-side :class:`ConvergenceRecord` (cadence inferred, gaps marked,
reliable-update/breakdown/per-shift/per-RHS events extracted) and
``publish`` surfaces it on InvertParam (``res_history`` / ``events``)
and as per-iteration ``residual`` events in the trace JSONL stream.

With ``record=False`` (the default, and always when QUDA_TPU_TRACE is
off) the history buffer is never allocated and the loop carry is
byte-identical to the unrecorded solver — zero overhead.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class ConvergenceRecord:
    """One solve's convergence story, host-side and dumpable."""
    solver: str
    tol: float
    cadence: int                      # check cadence the history was
                                      # recorded at (1 = every iteration)
    iters: int                        # iterations actually executed
    b2: float                         # |b|^2 of the recorded system
    history: List[dict]               # [{"iter", "r2", "relres"}, ...]
    events: List[dict]                # reliable_update / restart /
                                      # breakdown / shift_converged /
                                      # cadence markers
    lanes: Optional[dict] = None      # per-RHS/per-shift histories:
                                      # {label: [{"iter","r2","relres"}]}

    def dump(self, path: str):
        """Write the record as JSON (per-solve dump)."""
        with open(path, "w") as fh:
            json.dump(dataclasses.asdict(self), fh, indent=1)

    def relres_final(self) -> Optional[float]:
        return self.history[-1]["relres"] if self.history else None


def _relres(r2: float, b2: float) -> float:
    if not (b2 > 0.0) or not math.isfinite(r2):
        return float("nan")
    return math.sqrt(max(r2, 0.0) / b2)


def _entries(r2_slots: np.ndarray, cadence: int, b2: float) -> List[dict]:
    out = []
    for i, v in enumerate(r2_slots):
        v = float(v)
        if math.isnan(v):
            break
        out.append({"iter": (i + 1) * cadence, "r2": v,
                    "relres": _relres(v, b2)})
    return out


def _infer_cadence(r2_slots: np.ndarray, iters: int) -> int:
    n_valid = 0
    for v in np.asarray(r2_slots, dtype=np.float64):
        if math.isnan(float(v)):
            break
        n_valid += 1
    if n_valid <= 0 or iters <= 0:
        return 1
    return max(1, int(round(iters / n_valid)))


def harvest(solver: str, res, tol: float, b2
            ) -> Optional[ConvergenceRecord]:
    """SolverResult-with-history -> ConvergenceRecord (None when the
    solve recorded nothing — the zero-overhead path).

    ``b2`` is the reference norm relres is judged against: a scalar, or
    — for per-RHS (2-D) histories — an (nrhs,) vector so every lane is
    normalized against ITS OWN |b_i|^2 (a single worst-lane scalar
    under-reports every other lane's relative residual).  A dict
    history that carries its own ``b2`` key (a solver that recorded a
    different system than the caller's, e.g. cg_reliable_df's
    normal-equation curve) overrides the argument."""
    h = getattr(res, "history", None)
    if h is None:
        return None
    # per-RHS solvers report an (nrhs,) iteration vector; the executed
    # lockstep iteration count is the slowest lane's
    iters = int(np.max(np.asarray(res.iters)))
    b2_vec = np.asarray(b2, dtype=np.float64).reshape(-1)
    b2 = float(np.max(b2_vec))
    events: List[dict] = []
    lanes = None

    if isinstance(h, dict):
        if h.get("b2") is not None:
            b2 = float(np.asarray(h["b2"], dtype=np.float64))
        r2 = np.asarray(h["r2"], dtype=np.float64)
        cadence = _infer_cadence(r2, iters)
        history = _entries(r2, cadence, b2)
        rel = h.get("reliable")
        if rel is not None:
            rel = np.asarray(rel)
            for i in range(min(len(rel), len(history))):
                if bool(rel[i]):
                    events.append({"type": "reliable_update",
                                   "iter": (i + 1) * cadence})
        sh = h.get("shift_r2")
        if sh is not None:
            sh = np.asarray(sh, dtype=np.float64)
            lanes = {}
            stop = (tol ** 2) * b2
            for s in range(sh.shape[1]):
                lane = _entries(sh[:, s], cadence, b2)
                lanes[f"shift{s}"] = lane
                conv_at = next((e["iter"] for e in lane
                                if e["r2"] <= stop), None)
                if conv_at is not None:
                    events.append({"type": "shift_converged",
                                   "shift": s, "iter": conv_at})
    else:
        a = np.asarray(h, dtype=np.float64)
        if a.ndim == 2:
            # per-RHS lanes (block solvers): each lane is normalized
            # against its own b2 (scalar b2 broadcasts), and the
            # headline history is the worst RELATIVE lane per slot —
            # the lane-picking must happen in relres units or a
            # big-norm RHS masks a stalled small-norm one (-inf fill
            # keeps fully-unwritten slots NaN without a nanmax warning)
            nl = a.shape[1]
            lane_b2 = (np.full(nl, b2_vec[0]) if b2_vec.size == 1
                       else b2_vec[:nl])
            rel_a = a / np.where(lane_b2 > 0.0, lane_b2, np.nan)[None, :]
            filled = np.where(np.isnan(rel_a), -np.inf, rel_a)
            idx = (filled.argmax(axis=1) if a.size
                   else np.zeros(len(a), np.intp))
            worst = a[np.arange(len(a)), idx]
            worst = np.where(np.isneginf(filled.max(axis=1)),
                             np.nan, worst)
            worst_b2 = lane_b2[idx]
            cadence = _infer_cadence(worst, iters)
            history = []
            for i, v in enumerate(worst):
                v = float(v)
                if math.isnan(v):
                    break
                history.append({"iter": (i + 1) * cadence, "r2": v,
                                "relres": _relres(v,
                                                  float(worst_b2[i]))})
            lanes = {f"rhs{i}": _entries(a[:, i], cadence,
                                         float(lane_b2[i]))
                     for i in range(nl)}
        else:
            cadence = _infer_cadence(a, iters)
            history = _entries(a, cadence, b2)

    if cadence > 1:
        # the cadence gap marker the check-cadence contract requires:
        # residuals between check points were computed but not observed
        events.insert(0, {"type": "check_cadence", "every": cadence,
                          "note": f"residuals recorded every {cadence} "
                                  "iterations; intermediate iterations "
                                  "are cadence gaps"})
    if history and not math.isnan(history[-1]["r2"]):
        if not np.asarray(res.converged).all():
            events.append({"type": "unconverged", "iter": iters,
                           "r2": history[-1]["r2"]})
    if any(math.isinf(e["r2"]) or math.isnan(e["r2"]) for e in history):
        events.append({"type": "breakdown",
                       "note": "non-finite residual in history"})
    return ConvergenceRecord(solver=solver, tol=float(tol),
                             cadence=cadence, iters=iters, b2=b2,
                             history=history, events=events, lanes=lanes)


def publish(rec: Optional[ConvergenceRecord], param=None):
    """Surface a record on an InvertParam (res_history/events) and emit
    per-iteration ``residual`` events into the trace stream (one per
    history entry of the headline lane; the per-lane histories stay on
    the record)."""
    if rec is None:
        return None
    if param is not None:
        param.res_history = list(rec.history)
        param.events = list(rec.events)
    from . import trace as otr
    if otr.enabled():
        for e in rec.history:
            otr.event("residual", cat="convergence", solver=rec.solver,
                      iter=e["iter"], r2=e["r2"], relres=e["relres"])
        for ev in rec.events:
            otr.event(ev.get("type", "solver_event"), cat="convergence",
                      solver=rec.solver,
                      **{k: v for k, v in ev.items() if k != "type"})
    return rec
