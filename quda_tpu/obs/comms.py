"""ICI comms ledger: bytes-over-interconnect attribution per solve.

Reference behavior: the only Python in the entire reference is
``lib/generate/wrap.py`` — a code generator for an NVTX-annotated MPI
wrapper, built because comms attribution matters enough to tool.  PLQCD
(arXiv:1405.0700) makes the point quantitatively: the comms-overlap
fraction is *the* number that decides pod-scale viability.  This module
is the TPU-native home for that number's numerator: every halo-exchange
seam in the package (``lax.ppermute`` via
``parallel/halo._permute_slice``, the in-kernel RDMA policies of
``parallel/pallas_halo``, the split-grid gauge replication of
``parallel/split.py``) records (axis, direction, bytes/device, mesh,
policy, dtype) into one ledger, and the solve epilogue joins those rows
with measured seconds into an ICI roofline row emitted alongside the
HBM roofline in ``roofline.tsv``.

Semantics — a MODEL ledger, recorded at trace time: the exchange seams
execute inside ``jit``/``shard_map`` *tracing*, so each distinct
compiled stencil contributes its rows ONCE (per trace), with the bytes
computed from the actual traced slab shapes.  That is the point: the
ledger rows ARE the analytic halo model, harvested from the real seams
instead of hand arithmetic, and the per-solve total is rows x measured
operator applications (``attribute_solve``).  Entry ``count`` is the
number of traces that recorded the row, not an execution count.  The
split-grid replication row is the exception: it records at the actual
``device_put`` call, so its bytes are real per-call transfer volume.

Activation: rides the existing observability knobs — ``init_quda``
starts the ledger iff ``QUDA_TPU_TRACE`` or ``QUDA_TPU_METRICS`` is set
(:func:`maybe_start`); the bench harness and tests call :func:`start`
directly.  **Off means off**: every recording entry point returns after
one module-global load and ``scope()`` hands back a no-op singleton, so
the seams stay branch-cheap on the disabled path and compiled solves
are bit-identical (pinned by a raising-stub test, the trace/metrics
discipline).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

# Nominal per-chip aggregate ICI bandwidth used for the percent column
# of the ICI roofline rows.  This is the published v5e interconnect spec
# (1600 Gbps/chip), NOT a demonstrated number — no multi-chip window has
# measured a sustained link rate yet, so the column answers "how close
# would this solve's comms volume alone come to saturating the nominal
# link" (the PLQCD overlap-fraction numerator).  Replace with a measured
# peak the first time a chip window times a saturating exchange; on CPU
# meshes the percentage is computed but physically meaningless.
ICI_NOMINAL_GBPS = 200.0


class _NoopScope:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SCOPE = _NoopScope()


class _Ledger:
    """Live ledger session.  Exchange ENTRIES live at module level (see
    ``_entries``): they are recorded at trace time and model the jit
    cache, which outlives any one init/end session — a second session
    reusing cached executables would otherwise silently lose all ICI
    attribution.  The session holds only the per-session solve rows and
    gates whether recording happens at all."""

    def __init__(self):
        self.solve_rows: List[dict] = []      # attribute_solve output
        self.lock = threading.Lock()

    def record(self, site: str, axis: str, direction: str, nbytes: int,
               policy: str, dtype: str, mesh: str, n_slabs: int):
        key = (site, axis, direction, int(nbytes), policy, dtype, mesh,
               int(n_slabs))
        with _entries_lock:
            _entries[key] = _entries.get(key, 0) + 1
        from . import trace as otr
        otr.event("ici_exchange", cat="comms", site=site, axis=axis,
                  direction=direction, bytes=int(nbytes), policy=policy,
                  dtype=dtype, mesh=mesh, n_slabs=int(n_slabs))


_session: Optional[_Ledger] = None

# (site, axis, direction, bytes, policy, dtype, mesh, n_slabs) -> trace
# count.  Module-level (NOT per session): entries record what each
# compiled stencil's trace exchanged, and compiled executables persist
# across init/end cycles in one process — the entries must too, or a
# later session attributes nothing because nothing re-traces.
_entries: Dict[tuple, int] = {}
_entries_lock = threading.Lock()

# Scope stack: the sharded dslash wrappers push (site, policy) while
# their face-fix tracing runs, so the primitive seams (_permute_slice,
# slab_exchange_bidir) can label their rows without threading arguments
# through every call chain.  Host-side list, touched only at trace time.
_scopes: List[dict] = []


def enabled() -> bool:
    return _session is not None


def start() -> _Ledger:
    """Open a ledger session (idempotent — an active session is kept)."""
    global _session
    if _session is None:
        _session = _Ledger()
    return _session


def maybe_start() -> Optional[_Ledger]:
    """Start iff QUDA_TPU_TRACE or QUDA_TPU_METRICS is set (init_quda
    hook — the ledger rides the existing observability knobs, no knob
    of its own)."""
    from ..utils import config as qconf
    if (qconf.get("QUDA_TPU_TRACE", fresh=True)
            or qconf.get("QUDA_TPU_METRICS", fresh=True)):
        return start()
    return None


def stop():
    """Drop the session and its solve rows (end_quda epilogue).  The
    exchange ENTRIES survive on purpose: they mirror the process's jit
    cache, which a later init/end cycle reuses without re-tracing."""
    global _session
    _session = None
    # the scope stack is trace-time LIFO state owned by the tracing
    # thread's context nesting; end_quda teardown runs after tracing
    _scopes.clear()  # quda-lint: disable=lock-discipline  reason=trace-time LIFO scope stack; teardown runs on the owning thread after tracing


def reset():
    """Full reset — session, solve rows AND the process-lifetime
    exchange entries (test isolation only; production uses stop())."""
    stop()
    with _entries_lock:
        _entries.clear()


def scope(site: str, policy: Optional[str] = None, mesh_axes=()):
    """Context manager labeling exchanges recorded inside it (pushed by
    the sharded dslash wrappers around their face-fix construction);
    ``mesh_axes`` are the partitioned ring sizes, inherited by seams
    that cannot see the mesh themselves (slab_exchange_bidir).  The
    no-op singleton when the ledger is off."""
    if _session is None:
        return _NOOP_SCOPE

    import contextlib

    @contextlib.contextmanager
    def _ctx():
        # the scope stack is per-trace LIFO state owned by the tracing
        # thread's context nesting (the postmortem._scopes rationale);
        # a lock cannot linearize cross-thread push/pop meaningfully
        _scopes.append({"site": site, "policy": policy,  # quda-lint: disable=lock-discipline  reason=trace-time LIFO scope stack, push/pop ordering is the tracing thread's own nesting
                        "mesh_axes": tuple(mesh_axes)})
        try:
            yield
        finally:
            _scopes.pop()  # quda-lint: disable=lock-discipline  reason=trace-time LIFO scope stack, push/pop ordering is the tracing thread's own nesting

    return _ctx()


def _tracer_nbytes(arr) -> int:
    """Bytes of an array OR tracer (tracers carry size/dtype, not
    nbytes)."""
    nb = getattr(arr, "nbytes", None)
    if isinstance(nb, int):
        return nb
    import numpy as np
    return int(arr.size) * int(np.dtype(arr.dtype).itemsize)


def record_exchange(arrs=None, axis: str = "?",
                    direction: str = "bidir",
                    policy: Optional[str] = None, mesh_axes=(),
                    nbytes: Optional[int] = None, n_slabs: int = 1,
                    dtype: str = "float32") -> None:
    """One halo exchange at a primitive seam: ``arrs`` is the slab (or
    tuple of slabs) a device sends per invocation — per-device bytes
    come from the traced shapes — or pass ``nbytes``/``n_slabs``/
    ``dtype`` explicitly where the slabs are kernel-internal VMEM
    buffers (the fused-halo entry points).  No-op (one global load)
    when the ledger is off."""
    s = _session
    if s is None:
        return
    if nbytes is None:
        if not isinstance(arrs, (tuple, list)):
            arrs = (arrs,)
        nbytes = sum(_tracer_nbytes(a) for a in arrs)
        n_slabs = len(arrs)
        import numpy as np
        dtype = str(np.dtype(arrs[0].dtype).name)
    top = _scopes[-1] if _scopes else {}
    # the scope's mesh sizes WIN over a seam-supplied single ring: the
    # sharded wrappers know the full (n_t, n_z) partition while
    # _permute_slice sees only its own axis — attribution's device
    # count needs the full product
    mesh_axes = tuple(top.get("mesh_axes") or ()) or tuple(mesh_axes)
    s.record(site=top.get("site") or "unscoped",
             axis=axis, direction=direction, nbytes=int(nbytes),
             policy=policy or top.get("policy") or "ppermute",
             dtype=dtype, mesh="x".join(str(a) for a in mesh_axes),
             n_slabs=n_slabs)


def record_replication(obj, axis: str, n_devices: int,
                       what: str = "gauge") -> None:
    """Split-grid lane placement: ``obj`` (array/pytree) is replicated
    onto every sub-grid — (n_devices - 1) x its bytes travel the
    interconnect at the actual ``device_put``.  Unlike the exchange
    rows this is a per-CALL record (it runs host-side, not in a
    trace)."""
    s = _session
    if s is None:
        return
    from . import memory as omem
    from . import metrics as omet
    nbytes = omem.nbytes_of(obj) * max(0, int(n_devices) - 1)
    s.record(site=f"split_grid:{what}", axis=axis,
             direction="replicate", nbytes=nbytes, policy="split_grid",
             dtype="", mesh=str(n_devices), n_slabs=1)
    omet.inc("ici_bytes_total", float(nbytes), axis=axis,
             policy="split_grid")


def _ledger_rows() -> List[dict]:
    """Ledger rows in TRACE (insertion) order — the order the
    invocation grouping's latest-wins rule depends on."""
    with _entries_lock:
        items = list(_entries.items())
    return [{"site": k[0], "axis": k[1], "direction": k[2],
             "bytes": k[3], "policy": k[4], "dtype": k[5], "mesh": k[6],
             "n_slabs": k[7], "traces": c} for k, c in items]


def ledger() -> List[dict]:
    """Current ledger rows (largest first; process-lifetime entries)."""
    return sorted(_ledger_rows(), key=lambda r: -r["bytes"])


def _invocation_rows(site_prefix: str = "") -> List[dict]:
    """Ledger exchange rows eligible for per-invocation attribution, in
    trace order (latest-wins grouping depends on it): replication rows
    excluded (per-call, not per-invocation), sites filtered by
    prefix."""
    return [r for r in _ledger_rows()
            if r["direction"] != "replicate"
            and (not site_prefix or r["site"].startswith(site_prefix))]


def _invocation_groups(site_prefix: str = "") -> Dict[tuple, dict]:
    """Ledger exchange rows grouped by (site, policy, dtype, mesh) —
    the identity of ONE traced stencil configuration.  Within a group,
    one invocation performs at most one exchange per (axis, direction,
    n_slabs); a second entry under the same slot means the site was
    re-traced at a DIFFERENT lattice shape (the entries are process-
    lifetime, like the jit cache), and the LATEST one wins — summing
    shapes would bill one invocation for every size the worker ever
    served.  The surviving slots sum into the invocation's bytes.
    Rows across groups are ALTERNATIVES, never additive: the parity
    stencils are symmetric, an auto race traces both policies, a
    mixed-precision solve traces both dtypes — each invocation runs
    exactly one of them."""
    groups: Dict[tuple, dict] = {}
    for r in _invocation_rows(site_prefix):
        key = (r["site"], r["policy"], r["dtype"], r["mesh"])
        slot = (r["axis"], r["direction"], r["n_slabs"])
        # _entries is insertion-ordered, so a later-traced shape's row
        # replaces the earlier one here
        groups.setdefault(key, {})[slot] = r
    return {key: {"bytes": sum(r["bytes"] for r in slots.values()),
                  "rows": list(slots.values())}
            for key, slots in groups.items()}


def per_invocation_bytes(site_prefix: str = "") -> int:
    """Per-device ICI bytes of ONE stencil invocation: the max
    (site, policy, dtype) group total (see _invocation_groups for why
    max, not sum).  ``site_prefix`` confines the model to one operator
    family's stencils."""
    groups = _invocation_groups(site_prefix)
    return max((g["bytes"] for g in groups.values()), default=0)


def attribute_solve(form: str, applies: float, dslash_per_apply: float,
                    seconds: float, label: str = "",
                    site_prefix: str = "") -> Optional[dict]:
    """Join the ledger's per-invocation model with a solve's measured
    applies/seconds into one ICI roofline row (the HBM-roofline sibling
    obs/roofline.py records): total bytes = per-invocation bytes x
    applies x dslash_per_apply x mesh devices, ``gbps`` = aggregate
    bytes/seconds, and ``pct_nominal_ici`` = the PER-DEVICE rate vs
    ICI_NOMINAL_GBPS (devices send concurrently — the per-chip link
    saturates on per-device traffic).  Appended to the session rows
    (dumped into roofline.tsv by its save()) + an ``ici_solve`` trace
    event + the ``ici_bytes_total`` counter.  None when the ledger is
    off or holds no exchange rows."""
    s = _session
    if s is None:
        return None
    groups = _invocation_groups(site_prefix)
    if not groups:
        return None
    # the solve executed ONE stencil configuration per invocation; take
    # the max-bytes group(s).  Racing candidates move identical slabs,
    # so ties across policies are expected — the label then names all
    # tied policies (the ledger cannot know the race winner), but the
    # TOTAL is counted once, never split across policies a solve may
    # not have executed.
    per_inv = max(g["bytes"] for g in groups.values())
    win_rows = [r for g in groups.values()
                if g["bytes"] == per_inv for r in g["rows"]]
    policies = sorted({r["policy"] for r in win_rows})
    axes = sorted({r["axis"] for r in win_rows})
    # devices participating: every exchange row is per-device; the mesh
    # column carries the partition sizes — total ICI traffic is the
    # per-device bytes summed over devices
    n_dev = 1
    for r in win_rows:
        try:
            n = 1
            for p in r["mesh"].split("x"):
                if p:
                    n *= int(p)
            n_dev = max(n_dev, n)
        except ValueError:
            pass
    total = per_inv * float(applies) * float(dslash_per_apply) * n_dev
    gbps = (total / seconds / 1e9) if seconds > 0 else 0.0
    # saturation percentage is PER DEVICE: every device sends its
    # per_inv bytes concurrently, so the per-chip nominal link compares
    # against the per-device rate — dividing the mesh-aggregate total
    # by one chip's nominal would overstate saturation n_dev-fold
    gbps_dev = gbps / n_dev
    pol_label = "+".join(policies)
    row = {"form": f"ici:{form}", "label": label,
           "ici_bytes": int(total),
           "bytes_per_invocation_per_device": int(per_inv),
           "applies": float(applies),
           "dslash_per_apply": float(dslash_per_apply),
           "devices": n_dev, "seconds": round(float(seconds), 6),
           "gbps": round(gbps, 3),
           "gbps_per_device": round(gbps_dev, 3),
           "pct_nominal_ici": round(100.0 * gbps_dev
                                    / ICI_NOMINAL_GBPS, 2),
           "policy": pol_label,
           "axes": "+".join(axes)}
    # per-axis breakdown from ONE representative max group (the tied
    # groups are alternatives moving identical slabs, so any one of
    # them carries the per-axis split; summing the union would
    # double-count ties).  Multi-axis meshes additionally get one
    # ici:{form}:{axis} sub-row per partitioned axis so the roofline
    # dump shows where the bytes go.
    rep = next(g for g in groups.values() if g["bytes"] == per_inv)
    axis_bytes: Dict[str, int] = {}
    for r in rep["rows"]:
        axis_bytes[r["axis"]] = axis_bytes.get(r["axis"], 0) + r["bytes"]
    sub_rows = []
    if len(axis_bytes) > 1:
        for ax in sorted(axis_bytes):
            b_ax = axis_bytes[ax]
            t_ax = b_ax * float(applies) * float(dslash_per_apply) * n_dev
            g_ax = (t_ax / seconds / 1e9) if seconds > 0 else 0.0
            sub_rows.append({
                "form": f"ici:{form}:{ax}", "label": label,
                "ici_bytes": int(t_ax),
                "bytes_per_invocation_per_device": int(b_ax),
                "applies": float(applies),
                "dslash_per_apply": float(dslash_per_apply),
                "devices": n_dev, "seconds": round(float(seconds), 6),
                "gbps": round(g_ax, 3),
                "gbps_per_device": round(g_ax / n_dev, 3),
                "pct_nominal_ici": round(100.0 * g_ax / n_dev
                                         / ICI_NOMINAL_GBPS, 2),
                "policy": pol_label, "axes": ax})
    with s.lock:
        s.solve_rows.append(row)
        s.solve_rows.extend(sub_rows)
    from . import metrics as omet
    from . import trace as otr
    otr.event("ici_solve", cat="comms", **row)
    # the counter splits per axis (ici_bytes_total{axis, policy}); the
    # per-axis totals sum exactly to the row's mesh-aggregate bytes
    for ax in sorted(axis_bytes):
        t_ax = (axis_bytes[ax] * float(applies)
                * float(dslash_per_apply) * n_dev)
        omet.inc("ici_bytes_total", float(t_ax), axis=ax,
                 policy=pol_label)
    return row


def solve_rows() -> List[dict]:
    s = _session
    if s is None:
        return []
    with s.lock:
        return list(s.solve_rows)


def reset_rows():
    """Drop the accumulated SOLVE rows but keep the session and the
    process-lifetime exchange entries (an incremental dump-then-reset
    for harnesses that flush roofline.tsv mid-session)."""
    s = _session
    if s is None:
        return
    with s.lock:
        s.solve_rows.clear()


# -- analytic halo models (notice/bench consumers) --------------------------

def wilson_eo_halo_model(dims, mesh_shape, itemsize: int = 4) -> dict:
    """Per-dslash-invocation ICI bytes of the sharded eo Wilson policies
    from first principles — the number the ledger must reproduce from
    the seams, and what the QUDA_TPU_SHARDED_POLICY race notice quotes
    next to its timing winner.  ``dims`` = global (T, Z, Y, X),
    ``mesh_shape`` = (n_t, n_z) or the full (n_t, n_z, n_y, n_x).  The
    policy exchanges exactly two psi-shaped faces per partitioned
    direction (one ``exchange`` call): 2 x face bytes per axis.  t/z faces are whole planes, the y face is
    one local row strip, and the x face is one local COLUMN stack of xh
    slots (the eo slot-select reaches one column, w=1) — strided, which
    is why x is the cheapest axis per device but ppermute-only."""
    T, Z, Y, X = dims
    n_t, n_z, n_y, n_x = tuple(mesh_shape) + (1,) * (4 - len(mesh_shape))
    t_l, z_l = T // n_t, Z // n_z
    y_l, xh_l = Y // n_y, (X // 2) // n_x
    axes = {}
    per_device = 0
    for name, n, face_elems in (("t", n_t, 4 * 3 * 2 * z_l * y_l * xh_l),
                                ("z", n_z, 4 * 3 * 2 * t_l * y_l * xh_l),
                                ("y", n_y, 4 * 3 * 2 * t_l * z_l * xh_l),
                                ("x", n_x, 4 * 3 * 2 * t_l * z_l * y_l)):
        if n <= 1:
            continue
        b = 2 * face_elems * itemsize
        axes[name] = b
        per_device += b
    return {"per_device": per_device,
            "total": per_device * n_t * n_z * n_y * n_x, "axes": axes}
