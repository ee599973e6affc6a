"""Serving-grade metrics: a labeled counter/gauge/histogram registry.

Reference behavior: the reference's production accounting lives in its
persistent tunecache + per-kernel profile tsv (lib/tune.cpp:450-610)
and per-solve convergence reporting — counts of what compiled, what was
served warm, and what every solve did.  A serving fleet reads exactly
this before it scales (ROADMAP item 2: "serves its first solve without
a compile/race storm"); this module is the TPU-native home for it.

Activation: ``QUDA_TPU_METRICS=1`` (read by ``init_quda`` via
:func:`maybe_start`) or an explicit :func:`start` (bench_suite's
``--metrics``).  **Off means off** — the trace-module discipline
(obs/trace.py): every recording entry point (:func:`inc`,
:func:`set_gauge`, :func:`observe`, :func:`record_execution`) returns
after one module-global load, no registry object exists, and no device
op is ever added either way, so instrumented call sites are safe in
hot host paths and the compiled solves stay bit-identical (pinned by a
raising-stub test like the tracer's).

Every metric NAME must be registered in obs/schema.py (type + help);
the registry validates at record time, and the schema lint
(tests/test_obs_schema_lint.py) validates every call site statically —
dashboards never break silently.

``end_quda`` exports the session as Prometheus text (``metrics.prom``,
scrapeable after copy/serve) and a flat ``metrics.tsv``, plus the
human-readable fleet report (obs/report.py), under the resource path.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from . import schema

# histogram bucket upper bounds in seconds (+Inf is implicit); chosen
# for solve/compile wall times: sub-10ms CI toys through minute-class
# chip compiles
HIST_BUCKETS = (0.005, 0.02, 0.1, 0.5, 2.0, 10.0, 60.0)


def _hist_bounds(name: str) -> tuple:
    """Bucket upper bounds for one histogram.  serve_request_seconds
    honors QUDA_TPU_SERVE_SLO_BUCKETS (comma-separated seconds) so a
    sub-second SLO is not quantized into one default bucket; a
    malformed value warns once and falls back — a typoed knob must
    never take down the recording path."""
    if name != "serve_request_seconds":
        return HIST_BUCKETS
    from ..utils import config as qconf
    raw = str(qconf.get("QUDA_TPU_SERVE_SLO_BUCKETS", fresh=True) or "")
    if not raw.strip():
        return HIST_BUCKETS
    try:
        bounds = tuple(sorted({float(t) for t in raw.split(",")
                               if t.strip()}))
    except ValueError:
        from ..utils import logging as qlog
        qlog.warn_once(
            "serve_slo_buckets",
            f"QUDA_TPU_SERVE_SLO_BUCKETS={raw!r} is not a comma-"
            "separated list of seconds; using the default buckets")
        return HIST_BUCKETS
    return bounds or HIST_BUCKETS

# export file prefix: quda_tpu_solves_total etc.
_PROM_PREFIX = "quda_tpu_"


class _Registry:
    """The live session store.  All methods validate the metric name
    against obs/schema.py — an unregistered name raises the first time
    its code path runs (the runtime half of the schema lint)."""

    def __init__(self, path: str):
        self.path = path
        self.wall0 = time.time()
        self.counters: dict = {}      # (name, labels) -> float
        self.gauges: dict = {}        # (name, labels) -> float
        self.hists: dict = {}         # (name, labels) -> {counts,sum,n}
        self.seen_keys: set = set()   # compile-accounting keys
        self.lock = threading.Lock()

    @staticmethod
    def _key(name: str, labels: dict) -> tuple:
        return (name, tuple(sorted((k, str(v))
                                   for k, v in labels.items())))

    @staticmethod
    def _check(name: str, kind: str):
        m = schema.METRICS.get(name)
        if m is None:
            raise KeyError(
                f"unregistered metric {name!r}; register it in "
                "quda_tpu/obs/schema.py (type + help) — an ad-hoc "
                "name breaks dashboards silently")
        if m["type"] != kind:
            raise TypeError(
                f"metric {name!r} is registered as {m['type']}, "
                f"recorded as {kind}")

    def inc(self, name: str, value: float, labels: dict):
        self._check(name, schema.COUNTER)
        k = self._key(name, labels)
        with self.lock:
            self.counters[k] = self.counters.get(k, 0.0) + float(value)

    def set(self, name: str, value: float, labels: dict):
        self._check(name, schema.GAUGE)
        with self.lock:
            self.gauges[self._key(name, labels)] = float(value)

    def observe(self, name: str, value: float, labels: dict):
        self._check(name, schema.HISTOGRAM)
        k = self._key(name, labels)
        with self.lock:
            h = self.hists.get(k)
            if h is None:
                bounds = _hist_bounds(name)
                h = self.hists[k] = {
                    "counts": [0] * (len(bounds) + 1),
                    "sum": 0.0, "n": 0, "buckets": bounds}
            for i, ub in enumerate(h["buckets"]):
                if value <= ub:
                    h["counts"][i] += 1
                    break
            else:
                h["counts"][-1] += 1
            h["sum"] += float(value)
            h["n"] += 1


_session: Optional[_Registry] = None


def enabled() -> bool:
    return _session is not None


def _metrics_dir() -> str:
    from ..utils import config as qconf
    return qconf.get("QUDA_TPU_RESOURCE_PATH", fresh=True) or "."


def start(path: Optional[str] = None) -> _Registry:
    """Open a metrics session (idempotent: an active session and its
    path win, trace.start semantics)."""
    global _session
    if _session is None:
        _session = _Registry(path or _metrics_dir())
    elif path is not None and path != _session.path:
        from ..utils import logging as qlog
        qlog.warningq(
            f"obs.metrics.start({path!r}): a session is already "
            f"active, keeping its artifacts at {_session.path}")
    return _session


def maybe_start() -> Optional[_Registry]:
    """Start a session iff QUDA_TPU_METRICS is set (init_quda hook)."""
    from ..utils import config as qconf
    if qconf.get("QUDA_TPU_METRICS", fresh=True):
        return start()
    return None


def stop(flush_files: bool = True) -> Optional[dict]:
    """Close the session; returns {'prom', 'tsv', 'report'} paths when
    artifacts were written (end_quda hook).  The session is cleared
    even when the flush raises (unwritable resource path): a later
    init/solve cycle must start a FRESH registry, not silently reuse
    the stale counters and seen-compile keys of the failed one."""
    global _session
    if _session is None:
        return None
    try:
        return flush() if flush_files else None
    finally:
        _session = None


# -- recording entry points (one global load when off) ----------------------

def inc(name: str, value: float = 1.0, **labels):
    """Add ``value`` to a labeled counter (no-op when metrics are off)."""
    r = _session
    if r is None:
        return
    r.inc(name, value, labels)


def set_gauge(name: str, value: float, **labels):
    """Set a labeled gauge (no-op when metrics are off)."""
    r = _session
    if r is None:
        return
    r.set(name, value, labels)


def observe(name: str, value: float, **labels):
    """Observe a value into a labeled histogram (no-op when off)."""
    r = _session
    if r is None:
        return
    r.observe(name, value, labels)


def record_execution(api: str, form: str, shape, dtype: str,
                     solver: str, seconds: float) -> bool:
    """Compile/executable-cache accounting for one compute phase.

    The first execution of a distinct (api, operator form, shape,
    dtype, solver) key in this process pays the XLA compile inside its
    wall time — count it as a compile (``compiles_total`` +
    ``compile_seconds`` + a ``compile`` trace event); later executions
    of the same key ran the cached executable (``executions_total``
    only).  Returns True iff this was a first execution."""
    r = _session
    if r is None:
        return False
    key = f"{api}|{form}|{tuple(shape)}|{dtype}|{solver}"
    with r.lock:
        first = key not in r.seen_keys
        r.seen_keys.add(key)
    if first:
        r.inc("compiles_total", 1.0, {"api": api, "form": form})
        r.observe("compile_seconds", seconds, {"api": api})
        from . import trace as otr
        otr.event("compile", cat="metrics", api=api, form=form,
                  shape=list(shape), dtype=dtype, solver=solver,
                  seconds=round(float(seconds), 6))
        # cost-model cross-check capture: the session's drift report
        # (obs/costmodel.py, cost_drift.tsv at end_quda) covers exactly
        # the forms that compiled here
        from . import costmodel as ocost
        ocost.note_compile(api, form, shape, dtype, solver, seconds)
    r.inc("executions_total", 1.0, {"api": api, "form": form})
    return first


def record_solve_program(api: str, form: str, solver: str, outcome: str):
    """One call through a cached solve program (solvers/program.py):
    ``outcome`` 'miss' traced the loop program in this call, 'hit'
    found its executable in the process (no-op when metrics are off)."""
    r = _session
    if r is None:
        return
    r.inc("solve_program_total", 1.0,
          {"api": api, "form": form, "solver": solver,
           "outcome": outcome})


def executable_keys() -> set:
    """Snapshot of the (api, form, shape, dtype, solver) keys executed
    this session (the rendered-string form ``record_execution`` keys
    on).  serve/persist.py writes these to the resource path at worker
    shutdown so the NEXT process knows which executables the persisted
    XLA compilation cache already holds."""
    r = _session
    if r is None:
        return set()
    with r.lock:
        return set(r.seen_keys)


def seed_executable_keys(keys) -> int:
    """Pre-seed the compile-accounting key set (serve/persist.py warm
    start): a key seeded here was compiled by a PREVIOUS process whose
    executable the persisted compilation cache serves, so its first
    execution in THIS process must count as a warm execution, not a
    compile — ``compiles_total == 0`` for already-keyed executables is
    the ROADMAP item-2 acceptance instrument.  Returns the number of
    keys newly seeded (0 when no session is active)."""
    r = _session
    if r is None:
        return 0
    with r.lock:
        fresh = {str(k) for k in keys} - r.seen_keys
        r.seen_keys |= fresh
    return len(fresh)


# -- snapshot / export ------------------------------------------------------

def snapshot() -> dict:
    """Host-side copy of the live registry: {'counters', 'gauges',
    'histograms'} keyed by (name, ((label, value), ...)).  Empty dicts
    when no session is active (report renders 'no metrics session')."""
    r = _session
    if r is None:
        return {"counters": {}, "gauges": {}, "histograms": {}}
    with r.lock:
        return {"counters": dict(r.counters),
                "gauges": dict(r.gauges),
                "histograms": {k: {"counts": list(h["counts"]),
                                   "sum": h["sum"], "n": h["n"],
                                   "buckets": tuple(
                                       h.get("buckets", HIST_BUCKETS))}
                               for k, h in r.hists.items()}}


def _esc(v) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"')


def _num(v: float) -> str:
    """Full-precision sample rendering: '%g' truncates to 6 significant
    digits, which corrupts any counter/gauge >= 1e6 (a session easily
    accumulates more solver iterations or ledger bytes than that, and a
    rounded counter can read as zero/negative under rate()).  Integral
    values print as integers, others as repr (round-trip exact)."""
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


def _prom_labels(labels: tuple, extra: str = "") -> str:
    parts = [f'{k}="{_esc(v)}"' for k, v in labels]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def render_prometheus(snap: Optional[dict] = None) -> str:
    """The session as Prometheus text-format exposition."""
    snap = snap or snapshot()
    by_name: dict = {}
    for kind in ("counters", "gauges", "histograms"):
        for (name, labels), v in snap[kind].items():
            by_name.setdefault(name, []).append((labels, v))
    lines = []
    for name in sorted(by_name):
        meta = schema.METRICS[name]
        full = _PROM_PREFIX + name
        lines.append(f"# HELP {full} {meta['help']}")
        lines.append(f"# TYPE {full} {meta['type']}")
        for labels, v in sorted(by_name[name]):
            if meta["type"] == schema.HISTOGRAM:
                cum = 0
                for i, ub in enumerate(v.get("buckets", HIST_BUCKETS)):
                    cum += v["counts"][i]
                    le = f'le="{ub}"'
                    lines.append(
                        f"{full}_bucket{_prom_labels(labels, le)} {cum}")
                cum += v["counts"][-1]
                inf = 'le="+Inf"'
                lines.append(
                    f"{full}_bucket{_prom_labels(labels, inf)} {cum}")
                lines.append(f"{full}_sum{_prom_labels(labels)}"
                             f" {v['sum']:.6f}")
                lines.append(f"{full}_count{_prom_labels(labels)} {cum}")
            else:
                lines.append(f"{full}{_prom_labels(labels)} {_num(v)}")
    return "\n".join(lines) + ("\n" if lines else "")


def render_tsv(snap: Optional[dict] = None) -> str:
    """Flat name/labels/value tsv (the profile_N.tsv sibling)."""
    snap = snap or snapshot()
    rows = ["metric\ttype\tlabels\tvalue"]
    for kind, tname in (("counters", schema.COUNTER),
                        ("gauges", schema.GAUGE)):
        for (name, labels), v in sorted(snap[kind].items()):
            lab = ",".join(f"{k}={v2}" for k, v2 in labels)
            rows.append(f"{name}\t{tname}\t{lab}\t{_num(v)}")
    for (name, labels), h in sorted(snap["histograms"].items()):
        lab = ",".join(f"{k}={v2}" for k, v2 in labels)
        rows.append(f"{name}\thistogram\t{lab}\t"
                    f"n={h['n']},sum={h['sum']:.6f}")
    return "\n".join(rows) + "\n"


def flush() -> Optional[dict]:
    """Write metrics.prom + metrics.tsv + the fleet report under the
    session path; the session stays active (incremental overwrites)."""
    r = _session
    if r is None:
        return None
    os.makedirs(r.path, exist_ok=True)
    snap = snapshot()
    prom_path = os.path.join(r.path, "metrics.prom")
    tsv_path = os.path.join(r.path, "metrics.tsv")
    with open(prom_path, "w") as fh:
        fh.write(render_prometheus(snap))
    with open(tsv_path, "w") as fh:
        fh.write(render_tsv(snap))
    from . import report as orep
    report_path = orep.save(os.path.join(r.path, "fleet_report.txt"),
                            snap=snap)
    return {"prom": prom_path, "tsv": tsv_path, "report": report_path}
