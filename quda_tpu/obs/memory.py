"""HBM field ledger + device-memory sampling + VMEM budget audit.

Reference behavior: the reference's device_malloc ledger (lib/malloc.cpp)
tracks every allocation with a label and reports the high-water mark at
shutdown; QUDA_ENABLE_MONITOR samples device state periodically.  On
TPU, XLA/PJRT owns allocation, so what a serving fleet needs instead is
*attribution*: which resident FIELDS (gauge, clover, fat/Naik links, MG
hierarchy levels, eig workspaces) account for the HBM a worker holds,
what the per-device ``memory_stats()`` high-water was around solves,
and whether the pallas kernels' VMEM budgets
(``QUDA_TPU_PALLAS_VMEM_MB*``) are sane against the 16 MB scoped limit.

Three surfaces:

* the **field ledger** — :func:`track` / :func:`release` called at every
  resident-field load/free site (interfaces/quda_api.py, models/).
  Host-side dict bookkeeping (nanoseconds, no device ops), ALWAYS
  maintained; mirrored into the metrics registry (family totals and
  high-water gauges) and the trace stream only when
  those sessions are active.
* **device snapshots** — :func:`device_snapshot` reads
  ``memory_stats()`` from **all** local devices (not just device 0 —
  the round-12 monitor fix) and folds per-device high-water into the
  ledger; :func:`sample` is the solve-phase hook quda_api calls when
  metrics are on.
* the **VMEM audit** — :func:`vmem_audit` records each ``_pick_bz``
  block decision against its budget knob, and
  :func:`audit_vmem_budgets` checks every registered budget against
  the 16 MB Mosaic scoped limit (single-buffer budget must leave room
  for double buffering) for the fleet report.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

# Mosaic scoped-VMEM limit the budgets are carved from (see
# QUDA_TPU_PALLAS_VMEM_MB's registration doc: 6 MB default = < half of
# 16 MB so double buffering fits)
SCOPED_VMEM_MB = 16.0

# the per-form single-buffer budget knobs (utils/config.py)
VMEM_KNOBS = ("QUDA_TPU_PALLAS_VMEM_MB", "QUDA_TPU_PALLAS_VMEM_MB_STAGGERED")

_fields: Dict[tuple, dict] = {}        # (family, name) -> {bytes, since}
_family_high: Dict[str, int] = {}      # family -> high-water bytes
_device_last: Dict[str, int] = {}      # device label -> last bytes_in_use
_device_high: Dict[str, int] = {}      # device label -> high-water
_vmem_last: Dict[str, dict] = {}       # knob -> last _pick_bz decision
# the monitor's background thread and the solve-phase sampling hook
# both read-modify-write the device high-water dicts — a lost update
# would under-report the peak the fleet report quotes
_lock = threading.Lock()


def reset():
    """Drop all ledger state (end_quda epilogue / test isolation)."""
    with _lock:
        _fields.clear()
        _family_high.clear()
        _device_last.clear()
        _device_high.clear()
        _vmem_last.clear()


def nbytes_of(obj, _seen: Optional[set] = None, _depth: int = 0) -> int:
    """Total array bytes reachable from ``obj``: jax/numpy arrays count
    ``.nbytes``; containers and plain objects (MG hierarchies, pair
    operators) are walked recursively with cycle/depth guards.  Host
    bookkeeping only — never forces device transfers."""
    if _seen is None:
        _seen = set()
    if _depth > 8 or id(obj) in _seen:
        return 0
    _seen.add(id(obj))
    nb = getattr(obj, "nbytes", None)
    if isinstance(nb, int) and hasattr(obj, "dtype"):
        return nb
    if isinstance(obj, (int, float, complex, str, bytes, bool,
                        type(None))):
        return 0
    if isinstance(obj, dict):
        return sum(nbytes_of(v, _seen, _depth + 1) for v in obj.values())
    if isinstance(obj, (list, tuple, set, frozenset)):
        return sum(nbytes_of(v, _seen, _depth + 1) for v in obj)
    d = getattr(obj, "__dict__", None)
    if isinstance(d, dict):
        return sum(nbytes_of(v, _seen, _depth + 1) for v in d.values())
    return 0


def _family_total_locked(family: str) -> int:
    """Resident bytes of one family; caller holds ``_lock``."""
    return sum(e["bytes"] for (f, _), e in _fields.items()
               if f == family)


def _mirror_family(family: str, total: int, high: int):
    from . import metrics as omet
    omet.set_gauge("hbm_family_bytes", total, family=family)
    omet.set_gauge("hbm_family_high_water_bytes", high, family=family)


def track(family: str, name: str, obj) -> int:
    """(Re)register a resident field: ``obj`` is an array/pytree/object
    (bytes computed via :func:`nbytes_of`) or an int byte count.
    Re-tracking the same (family, name) replaces the entry — resident
    mutations (smearing, HMC updates) keep one row, not a leak."""
    nbytes = obj if isinstance(obj, int) else nbytes_of(obj)
    with _lock:
        _fields[(family, name)] = {"bytes": int(nbytes),
                                   "since": time.time()}
        fam_total = _family_total_locked(family)
        if fam_total > _family_high.get(family, 0):
            _family_high[family] = fam_total
        high = _family_high.get(family, 0)
    from . import trace as otr
    _mirror_family(family, fam_total, high)
    otr.event("hbm_field_tracked", cat="memory", family=family,
              field=name, bytes=int(nbytes))
    return int(nbytes)


def release_family(family: str, keep: tuple = ()) -> int:
    """Release every field of a family but those named in ``keep``
    (the per-API-call transient rows — per-call clover operators, eig
    workspaces — whose arrays die with the call; family high-water is
    retained as the peak signal).  Returns the number of entries
    released."""
    with _lock:
        names = [n for (f, n) in _fields if f == family
                 and n not in keep]
    for n in names:
        release(family, n)
    return len(names)


def release(family: str, name: str) -> bool:
    """Unregister a resident field (free/end_quda site); True iff it
    was tracked."""
    with _lock:
        entry = _fields.pop((family, name), None)
        if entry is None:
            return False
        fam_total = _family_total_locked(family)
        high = _family_high.get(family, 0)
    from . import trace as otr
    _mirror_family(family, fam_total, high)
    otr.event("hbm_field_released", cat="memory", family=family,
              field=name, bytes=entry["bytes"])
    return True


def ledger() -> List[dict]:
    """Current ledger rows, largest first."""
    with _lock:
        rows = [{"family": f, "field": n, "bytes": e["bytes"]}
                for (f, n), e in _fields.items()]
    return sorted(rows, key=lambda r: -r["bytes"])


def family_bytes() -> Dict[str, int]:
    out: Dict[str, int] = {}
    with _lock:
        for (family, _), e in _fields.items():
            out[family] = out.get(family, 0) + e["bytes"]
    return out


def high_water() -> Dict[str, int]:
    with _lock:
        return dict(_family_high)


def device_high_water() -> Dict[str, int]:
    with _lock:
        return dict(_device_high)


def device_snapshot() -> List[dict]:
    """``memory_stats()`` across ALL local devices (the monitor
    previously sampled only ``jax.local_devices()[0]`` — a sharded
    solve's other shards were invisible).  Folds per-device high-water
    into the ledger.  Backends without memory_stats (CPU) yield
    bytes_in_use 0 rows, one per device, so consumers always see the
    device count."""
    rows: List[dict] = []
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return rows
    for d in devices:
        label = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        in_use = int(stats.get("bytes_in_use", 0))
        peak = int(stats.get("peak_bytes_in_use", in_use))
        with _lock:
            _device_last[label] = in_use
            if max(in_use, peak) > _device_high.get(label, 0):
                _device_high[label] = max(in_use, peak)
        rows.append({"device": label, "bytes_in_use": in_use,
                     "peak_bytes_in_use": peak})
    return rows


def sample(phase: str = "") -> List[dict]:
    """Solve-phase device sampling hook (quda_api, metrics-gated at the
    call sites): snapshot all local devices into the ledger's per-device
    high-water marks (the fleet report reads them).  ``phase`` is
    advisory (kept for call-site readability)."""
    return device_snapshot()


# -- VMEM budget audit ------------------------------------------------------

def vmem_audit(knob: str, block_bytes: int, budget_bytes: int,
               bz: Optional[int] = None, single_buffered: bool = False,
               route: Optional[str] = None):
    """Record one ``_pick_bz`` decision: selected single-buffer working
    set vs the knob's budget (ops/wilson_pallas_packed.py call sites).
    ``block_bytes`` is the PADDED tile working set — sublane rows at the
    dtype's tile height (8 f32 / 16 bf16 / 32 int8), lanes padded to
    128 — so the audit charges what the block really occupies.
    ``single_buffered`` marks a full-block admission that only fits the
    scoped window once (the bf16/int8 bz=Z fallback): Mosaic cannot
    double-buffer it, so the pipeline serialises.  ``route`` names a
    call that sets its own ``vmem_limit_bytes`` (the multi-RHS Wilson
    kernel's full-Z route): ``budget_bytes`` is then that limit, which
    holds the blocks twice, and the decision is kept beside the knob's,
    under ``knob[route]``."""
    key = knob if route is None else f"{knob}[{route}]"
    with _lock:
        _vmem_last[key] = {"block_bytes": int(block_bytes),
                           "budget_bytes": int(budget_bytes), "bz": bz,
                           "single_buffered": bool(single_buffered)}
    from . import metrics as omet
    omet.set_gauge("vmem_block_bytes", block_bytes, knob=key)
    omet.set_gauge("vmem_budget_bytes", budget_bytes, knob=key)


def audit_vmem_budgets() -> List[dict]:
    """Every registered per-form VMEM budget vs the scoped limit: a
    single-buffer budget above SCOPED_VMEM_MB/2 leaves Mosaic no room
    to double-buffer (legal but measure-before-pinning territory —
    flagged, not rejected).  Fleet-report consumable."""
    from ..utils import config as qconf
    out = []
    for knob in VMEM_KNOBS:
        mb = float(qconf.get(knob, fresh=True))
        with _lock:
            last = dict(_vmem_last.get(knob, {}))
        out.append({
            "knob": knob, "budget_mb": mb,
            "double_buffer_ok": mb <= SCOPED_VMEM_MB / 2,
            "last_block_bytes": last.get("block_bytes"),
            "last_bz": last.get("bz"),
            "last_single_buffered": last.get("single_buffered", False),
        })
    with _lock:
        routed = {k: dict(v) for k, v in _vmem_last.items()
                  if k not in VMEM_KNOBS}
    for key in sorted(routed):
        last = routed[key]
        out.append({
            "knob": key, "budget_mb": last["budget_bytes"] / 2 ** 20,
            "double_buffer_ok": not last["single_buffered"],
            "last_block_bytes": last["block_bytes"],
            "last_bz": last["bz"],
            "last_single_buffered": last["single_buffered"],
        })
    return out
