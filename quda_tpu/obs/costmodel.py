"""XLA cost-model cross-check: the analytic KERNEL_MODELS vs what the
compiler and the argument footprints actually claim.

The roofline numbers this repo publishes (obs/roofline.py, PERF.md)
rest on hand-derived per-site flops/bytes models.  Hand arithmetic
drifts: a model edited for one kernel form and not its sharded twin, a
traffic table copied with a factor-2 slip, silently corrupts every
achieved-BW percentage downstream.  This module makes the models
checkable against two independent witnesses:

* **flops** — ``Compiled.cost_analysis()`` of the XLA *reference
  stencil* of the same operator family (the jnp forms the pallas
  kernels are bit-matched against).  XLA counts HLO flops on its own;
  the analytic ``flops_per_site`` must agree within ``FLOPS_RTOL``.
  (The pallas call itself is opaque to XLA — and in interpret mode its
  cost analysis reports interpreter machinery — so the reference
  stencil, which computes the identical math, is the honest witness.)
* **bytes** — the operand-footprint floor: the distinct input + output
  array bytes of a real probe invocation of the form, per updated
  site.  An analytic bytes/site below the floor claims less traffic
  than the data touched once (impossible); one above
  ``BYTES_REREAD_MAX`` x the floor claims more re-reading than any
  kernel form in this codebase performs (measured worst case: the
  twisted-mass MRHS model, five psi reads, at 2.14x the floor at the
  n=4 probe point; the Wilson MRHS model, two, is at 1.29x; the
  deliberate-mistake fixtures in tests/test_costmodel.py pin that a
  factor-2 slip in either direction fails).

Surfaces:

* :func:`check_forms` / :func:`lint` — the drift lint over every
  registered pallas form (tests/test_costmodel.py runs it in tier-1;
  the bench ``costmodel`` suite records its ratios as trended rows).
* :func:`note_compile` — called by ``obs.metrics.record_execution`` on
  every first execution, so the session knows WHICH forms actually
  compiled; :func:`save_report` (end_quda, metrics-gated) joins the
  noted keys with the models and any cached probe results into
  ``cost_drift.tsv`` under the resource path.

Probes run on any backend (two tiny 4^4 reference-stencil compiles,
cached per process); footprints are pure shape arithmetic.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from .roofline import KERNEL_MODELS

# analytic flops_per_site vs the XLA reference-stencil count: XLA's HLO
# counting runs ~6-12% above the hand models (it charges the projector
# adds the models fold away); measured ratios 1.06-1.13 across families
FLOPS_RTOL = 0.5
# analytic bytes_per_site vs the operand-footprint floor: must be >= 1x
# (cannot move less than the data once) and <= this re-read factor.
# Ratios across the registered forms: 1.06 (the MG coarse stencil) to
# 2.04 (the int8 links form; the twisted-mass MRHS row read 2.14 at the
# n=4 probe point while its model was the z-blocked call's five reads,
# before PR 47, and reads 1.29 on the full-Z route); 2.5 leaves headroom
# while a factor-2 slip in either direction still fails (the
# tests/test_costmodel.py fixtures pin both directions)
BYTES_REREAD_MAX = 2.5
BYTES_REREAD_MIN = 1.0

# MRHS models are probed at this batch size (their bytes models are
# nrhs-callables)
_PROBE_NRHS = 4
_PROBE_L = 4

_lock = threading.Lock()
_probe_cache: Dict[str, dict] = {}     # form -> drift row
_ref_flops_cache: Dict[str, float] = {}
_noted: List[dict] = []                # record_execution compile keys
_NOTED_MAX = 1000


def reset():
    with _lock:
        _probe_cache.clear()
        _noted.clear()


def note_compile(api: str, form: str, shape, dtype: str, solver: str,
                 seconds: float):
    """Record one first-execution key (obs.metrics.record_execution
    hook): the drift report then covers exactly what compiled this
    session."""
    with _lock:
        if len(_noted) < _NOTED_MAX:
            _noted.append({"api": api, "form": form,
                           "shape": tuple(shape), "dtype": dtype,
                           "solver": solver,
                           "seconds": round(float(seconds), 6)})


def noted_compiles() -> List[dict]:
    with _lock:
        return list(_noted)


def xla_cost(fn, *args) -> dict:
    """{'flops', 'bytes'} from ``jit(fn).lower(*args).compile()
    .cost_analysis()`` (the Compiled cost-analysis capture).  Entries
    the backend does not report come back None."""
    import jax
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    return {"flops": ca.get("flops"),
            "bytes": ca.get("bytes accessed")}


# -- per-family XLA reference stencils (flops witnesses) --------------------

def _ref_flops_per_site(family: str) -> float:
    """XLA-counted flops/site of the family's reference jnp stencil on a
    4^4 lattice (compiled once per process)."""
    with _lock:
        if family in _ref_flops_cache:
            return _ref_flops_cache[family]
    import numpy as np
    import jax.numpy as jnp
    L = _PROBE_L
    T = Z = Y = X = L
    vol = L ** 4
    rng = np.random.default_rng(0)

    def arr(shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    if family == "wilson":
        from ..ops import wilson_packed as wpk
        g = arr((4, 3, 3, 2, T, Z, Y * X))
        p = arr((4, 3, 2, T, Z, Y * X))
        cost = xla_cost(lambda g, p: wpk.dslash_packed_pairs(g, p, X, Y),
                        g, p)
    elif family == "staggered_fat":
        from ..ops import staggered_packed as spk
        f = arr((4, 3, 3, 2, T, Z, Y * X))
        p = arr((3, 2, T, Z, Y * X))
        cost = xla_cost(
            lambda f, p: spk.dslash_staggered_packed_pairs(f, p, X, Y),
            f, p)
    elif family == "staggered_fat_naik":
        from ..ops import staggered_packed as spk
        f = arr((4, 3, 3, 2, T, Z, Y * X))
        ln = arr((4, 3, 3, 2, T, Z, Y * X))
        p = arr((3, 2, T, Z, Y * X))
        cost = xla_cost(
            lambda f, ln, p: spk.dslash_staggered_packed_pairs(
                f, p, X, Y, long_pp=ln), f, ln, p)
    elif family == "clover":
        # hop + one chiral-block matvec: the staged composition the
        # fused clover/twisted-clover kernels are bit-matched against
        from ..models.clover import apply_clover_pairs
        from ..ops import wilson_packed as wpk
        g = arr((4, 3, 3, 2, T, Z, Y * X))
        blk = arr((2, 6, 6, 2, T, Z, Y * X))
        p = arr((4, 3, 2, T, Z, Y * X))
        cost = xla_cost(
            lambda g, blk, p: apply_clover_pairs(
                blk, wpk.dslash_packed_pairs(g, p, X, Y)), g, blk, p)
    elif family == "twisted_mass":
        # hop + the (1 + i a g5)^{-1} chirality rotation
        from ..models.twisted import _twist_inv_pairs
        from ..ops import wilson_packed as wpk
        g = arr((4, 3, 3, 2, T, Z, Y * X))
        p = arr((4, 3, 2, T, Z, Y * X))
        cost = xla_cost(
            lambda g, p: _twist_inv_pairs(
                wpk.dslash_packed_pairs(g, p, X, Y), 0.25, +1), g, p)
    elif family in ("dwf_ls4", "dwf_ls8"):
        # the Ls-batched 4d hop (the s-diagonal seam the DWF/Möbius
        # fused form accelerates); vol below is 4d sites so the count
        # lands per updated 4d site, matching the Ls x 1320 models
        import jax
        from ..ops import wilson_packed as wpk
        Ls = int(family.rsplit("ls", 1)[1])
        g = arr((4, 3, 3, 2, T, Z, Y * X))
        p = arr((Ls, 4, 3, 2, T, Z, Y * X))
        cost = xla_cost(
            lambda g, p: jax.vmap(
                lambda v: wpk.dslash_packed_pairs(g, v, X, Y))(p), g, p)
    elif family == "mg_coarse":
        # the MG coarse stencil at the canonical probe size (n_vec=4,
        # E=16): the XLA form of the identical stacked contraction the
        # pallas kernel computes (ops/coarse_pallas.coarse_apply_ref)
        # on a 4^4 COARSE lattice — vol below is coarse sites
        from ..ops.coarse_pallas import coarse_apply_ref
        E = 16
        links = arr((9, vol, E, E))
        psi9 = arr((9, vol, E))
        cost = xla_cost(coarse_apply_ref, links, psi9)
    else:
        raise KeyError(f"no reference stencil for family {family!r}")
    fps = float(cost["flops"] or 0.0) / vol
    with _lock:
        _ref_flops_cache[family] = fps
    return fps


# -- per-form operand footprints (bytes floors) -----------------------------
#
# Per-UPDATED-site bytes of the arrays one invocation of the form reads
# and writes ONCE, on the same layout basis the KERNEL_MODELS rows were
# derived (full-lattice pair arrays; gauge 288 B/site full rows, 192
# reconstruct-12, wilson spinor 96, staggered color-spinor 24).  Sharded
# forms alias their single-chip interior (the models exclude the
# O(surface) halo transport — the comms ledger owns it).

_G, _G12, _PSI, _SPSI = 288.0, 192.0, 96.0, 24.0
# packed clover/twisted-clover chiral pair blocks: 2 x 6x6 complex f32
_BLK = 576.0

_FOOTPRINTS: Dict[str, dict] = {
    # v2 gather: forward links + resident pre-shifted backward copy
    "wilson_v2": {"family": "wilson",
                  "floor": lambda n: 2 * _G + 2 * _PSI},
    "wilson_v2_r12": {"family": "wilson",
                      "floor": lambda n: 2 * _G12 + 2 * _PSI},
    # either route of the MRHS kernel: the floor counts each operand once
    "wilson_mrhs": {"family": "wilson",
                    "floor": lambda n: 2 * _G / n + 2 * _PSI},
    # precision storage forms (PERF.md round 16).  Floors are the
    # distinct operand bytes of one invocation AT THE FORM'S STORAGE
    # dtype — the bf16 rows halve the f32 basis, the int8 row charges
    # 1-byte mantissas + the f32 scale planes (4 dirs x 4 B = 16/site
    # per array).  r12f/int8 read here+there link arrays (no resident
    # backward copy); fold keeps the v2 operand set in folded layout.
    "wilson_v2_r12f": {"family": "wilson",
                       "floor": lambda n: 2 * _G12 + 2 * _PSI},
    "wilson_v2_fold": {"family": "wilson",
                       "floor": lambda n: 2 * _G + 2 * _PSI},
    "wilson_v2_bf16_fold": {"family": "wilson",
                            "floor": lambda n: (2 * _G + 2 * _PSI) / 2},
    "wilson_v2_bf16_bzfull": {"family": "wilson",
                              "floor": lambda n:
                              (2 * _G + 2 * _PSI) / 2},
    "wilson_v2_int8": {"family": "wilson",
                       "floor": lambda n: 2 * (_G / 4 + 16.0)
                       + 2 * _PSI},
    "wilson_sharded_v2": {"alias": "wilson_v2"},
    "wilson_sharded_v2_r12": {"alias": "wilson_v2_r12"},
    "staggered_fat": {"family": "staggered_fat",
                      "floor": lambda n: 2 * _G + 2 * _SPSI},
    "staggered_fat_naik": {"family": "staggered_fat_naik",
                           "floor": lambda n: 4 * _G + 2 * _SPSI},
    "staggered_fat_v3": {"family": "staggered_fat",
                         "floor": lambda n: _G + 2 * _SPSI},
    "staggered_fat_naik_v3": {"family": "staggered_fat_naik",
                              "floor": lambda n: 2 * _G + 2 * _SPSI},
    "staggered_mrhs": {"family": "staggered_fat_naik",
                       "floor": lambda n: 4 * _G / n + 2 * _SPSI},
    "staggered_fat_mrhs": {"family": "staggered_fat",
                           "floor": lambda n: 2 * _G / n + 2 * _SPSI},
    "staggered_sharded_fat": {"alias": "staggered_fat"},
    "staggered_sharded_fat_naik": {"alias": "staggered_fat_naik"},
    # operator-zoo fused forms (PERF.md round 18): hop operand set +
    # the resident diagonal term's storage.  The clover/twisted-clover
    # rows read the packed chiral blocks once per pass; the twisted-mass
    # twist is two compiled-in scalars (zero bytes); the MRHS rows
    # amortize links AND blocks over the RHS stream (either route of
    # the fused MRHS call: the floor counts each operand once; the
    # models' re-reads over it are the full-Z route's, PR 47: ratio
    # 1.40 clover, 1.29 twisted mass at the n=4 probe point).  The r12 floors
    # charge the reconstruct-12 link storage at the FORM's dtype basis
    "clover_pallas": {"family": "clover",
                      "floor": lambda n: 2 * _PSI + 2 * _G + _BLK},
    "clover_pallas_r12": {"family": "clover",
                          "floor": lambda n: 2 * _PSI + 2 * _G12
                          + _BLK},
    "clover_pallas_mrhs": {"family": "clover",
                           "floor": lambda n: 2 * _PSI
                           + (2 * _G + _BLK) / n},
    "twisted_mass_pallas": {"family": "twisted_mass",
                            "floor": lambda n: 2 * _PSI + 2 * _G},
    "twisted_mass_pallas_r12": {"family": "twisted_mass",
                                "floor": lambda n: 2 * _PSI + 2 * _G12},
    "twisted_mass_pallas_mrhs": {"family": "twisted_mass",
                                 "floor": lambda n: 2 * _PSI
                                 + 2 * _G / n},
    # twisted clover runs the clover operand set (twist folded into the
    # inverse blocks / an in-register rotation)
    "twisted_clover_pallas": {"alias": "clover_pallas"},
    "twisted_clover_pallas_r12": {"alias": "clover_pallas_r12"},
    "twisted_clover_pallas_mrhs": {"alias": "clover_pallas_mrhs"},
    # Ls-batched DWF hop: Ls spinor planes in+out, ONE gauge fetch
    "dwf_ls4_pallas": {"family": "dwf_ls4",
                       "floor": lambda n: 4 * 2 * _PSI + 2 * _G},
    "dwf_ls8_pallas": {"family": "dwf_ls8",
                       "floor": lambda n: 8 * 2 * _PSI + 2 * _G},
    # fused MG coarse stencil at the canonical probe size (E=16): the
    # distinct operands of one invocation are the 9 embedded link
    # matrices (36*E^2 B/site), the input vector read once (4*E) and
    # the output (4*E); the model's 9 psi stream reads (pre-rolled
    # neighbour copies) are re-reads over this floor
    "mg_coarse_pallas": {"family": "mg_coarse",
                         "floor": lambda n: 36.0 * 256 + 8 * 16.0},
}


def checkable_forms() -> List[str]:
    """Every KERNEL_MODELS form the drift lint covers: pallas forms with
    a traffic model.  Forms with ``bytes_per_site`` None (the XLA
    stencils, 'generic') are honest flops-only rows — nothing to
    cross-check."""
    return [f for f, m in KERNEL_MODELS.items()
            if m["bytes_per_site"] is not None]


def drift_row(form: str, probe: bool = True) -> dict:
    """One model-drift verdict: analytic flops vs the XLA reference
    count, analytic bytes vs the operand-footprint floor.  With
    ``probe=False`` a form not already probed this process comes back
    ``checked=False`` (no compile is triggered)."""
    with _lock:
        cached = _probe_cache.get(form)
    if cached is not None:
        return cached
    spec = _FOOTPRINTS.get(form)
    if spec is None:
        return {"form": form, "checked": False, "ok": False,
                "reasons": ["no footprint spec registered in "
                            "obs/costmodel.py — a pallas form shipped "
                            "without its drift check"]}
    base = form
    while "alias" in spec:
        base = spec["alias"]
        spec = _FOOTPRINTS[base]
    if not probe:
        return {"form": form, "checked": False, "ok": None,
                "reasons": []}
    m = KERNEL_MODELS[form]
    nrhs = _PROBE_NRHS if callable(m["bytes_per_site"]) else 1
    bps = m["bytes_per_site"](nrhs) if callable(m["bytes_per_site"]) \
        else float(m["bytes_per_site"])
    fps = float(m["flops_per_site"])
    floor = float(spec["floor"](nrhs))
    ref_fps = _ref_flops_per_site(spec["family"])
    flops_ratio = ref_fps / fps if fps else float("inf")
    bytes_ratio = bps / floor if floor else float("inf")
    reasons = []
    if not (1.0 - FLOPS_RTOL <= flops_ratio <= 1.0 + FLOPS_RTOL):
        reasons.append(
            f"flops drift: XLA counts {ref_fps:g} flops/site for the "
            f"{spec['family']} reference stencil but the model claims "
            f"{fps:g} (ratio {flops_ratio:.2f}, tolerance "
            f"±{FLOPS_RTOL:.0%})")
    if not (BYTES_REREAD_MIN <= bytes_ratio <= BYTES_REREAD_MAX):
        reasons.append(
            f"bytes drift: model claims {bps:g} B/site but the operand "
            f"footprint floor is {floor:g} (ratio {bytes_ratio:.2f}, "
            f"allowed [{BYTES_REREAD_MIN:g}, {BYTES_REREAD_MAX:g}]x)")
    row = {"form": form, "checked": True, "ok": not reasons,
           "nrhs": nrhs, "analytic_flops_per_site": fps,
           "xla_ref_flops_per_site": round(ref_fps, 1),
           "flops_ratio": round(flops_ratio, 4),
           "analytic_bytes_per_site": bps,
           "footprint_floor_bytes_per_site": floor,
           "bytes_ratio": round(bytes_ratio, 4),
           "reasons": reasons}
    with _lock:
        _probe_cache[form] = row
    from . import trace as otr
    otr.event("cost_drift", cat="costmodel", form=form, ok=row["ok"],
              flops_ratio=row["flops_ratio"],
              bytes_ratio=row["bytes_ratio"])
    return row


def check_forms(forms=None) -> List[dict]:
    """Drift rows for every checkable (or named) form — the model-drift
    report body."""
    return [drift_row(f) for f in (forms or checkable_forms())]


def lint(forms=None) -> List[dict]:
    """The drift LINT: raises with every failing form's reasons; returns
    the rows when all pass.  Run by tests/test_costmodel.py so a
    KERNEL_MODELS edit that disagrees with XLA's claim beyond tolerance
    cannot ship."""
    rows = check_forms(forms)
    bad = [r for r in rows if not r["ok"]]
    if bad:
        msg = "; ".join(f"{r['form']}: {'; '.join(r['reasons'])}"
                        for r in bad)
        raise AssertionError(f"cost-model drift lint failed: {msg}")
    return rows


def save_report(path: Optional[str] = None,
                fname: str = "cost_drift.tsv") -> Optional[str]:
    """The session's model-drift report: one row per form that COMPILED
    this session (note_compile keys), joined with its analytic model
    and any probe verdict already computed (``probe=False`` here — the
    shutdown path never triggers fresh compiles; the lint/bench own
    exhaustive probing).  None when nothing compiled or no output
    path."""
    import os

    from ..utils import config as qconf
    path = path or qconf.get("QUDA_TPU_RESOURCE_PATH", fresh=True)
    noted = noted_compiles()
    if not path or not noted:
        return None
    os.makedirs(path, exist_ok=True)
    cols = ("api", "form", "solver", "dtype", "compile_seconds",
            "analytic_flops_per_site", "analytic_bytes_per_site",
            "checked", "ok", "flops_ratio", "bytes_ratio")
    out = os.path.join(path, fname)

    def cell(v):
        # unprobed verdicts are None — render as EMPTY like the ratio
        # columns, not the string 'None'
        return "" if v is None else str(v)

    with open(out, "w") as fh:
        fh.write("\t".join(cols) + "\n")
        for n in noted:
            m = KERNEL_MODELS.get(n["form"], KERNEL_MODELS["generic"])
            bps = m["bytes_per_site"]
            d = drift_row(n["form"], probe=False) \
                if n["form"] in _FOOTPRINTS else None

            fh.write("\t".join(cell(v) for v in (
                n["api"], n["form"], n["solver"], n["dtype"],
                n["seconds"], m["flops_per_site"],
                bps(_PROBE_NRHS) if callable(bps) else bps,
                d["checked"] if d else None,
                d.get("ok") if d else None,
                d.get("flops_ratio") if d else None,
                d.get("bytes_ratio") if d else None)) + "\n")
    return out
