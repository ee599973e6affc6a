"""Span tracer: nestable named spans + instant events, chrome-trace out.

Reference behavior: the reference's profiling surface is pushProfile
RAII spans (include/timer.h:243) + the tunecache profiler tsv
(lib/tune.cpp:450-474).  This module adds the modern export formats on
top of the same span discipline: a chrome-trace/perfetto JSON
(``trace.json``) and a flat JSONL event stream
(``trace_events.jsonl``), written under QUDA_TPU_TRACE_PATH (default:
the resource path) when tracing is active.

Activation: ``QUDA_TPU_TRACE=1`` (read by init_quda via
``maybe_start``) or an explicit ``start()`` (the bench harness's
``--trace``).  With no session open ``event()`` returns after one
global load and a span keeps no buffer and reads no clock: it opens a
``jax.profiler.TraceAnnotation`` of its name (StartTraceRegion analog:
nanoseconds while no profiler captures) and puts the name on the build
accounting's stack (obs/build.py), so ANY ``jax.profiler`` capture holds
the program's spans on the profiler's own clock, beside the device's
operations, and everything jax builds is charged to the span it was
built under.  ``QUDA_TPU_DO_NOT_PROFILE`` turns that off with the phase
timers: ``span()`` then returns a module-level no-op singleton.  The
call sites are at the API layer, never inside a solver iteration.
(The session's spans time HOST regions; device work inside a span is
attributed to it only up to XLA's async dispatch.  In a profiler
capture the device's own timeline says what ran under which span.)
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

from jax.profiler import TraceAnnotation

from ..utils import timer as _qtimer
from . import build as _build
# the flight recorder taps this module's event stream (obs/flight.py
# imports nothing from here at module level, so the edge is acyclic)
from . import flight as _flight
from . import schema


class _NoopSpan:
    """Zero-overhead disabled span (QUDA_TPU_DO_NOT_PROFILE: the
    QUDA_DO_NOT_PROFILE analog)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NOOP = _NoopSpan()


class _BareSpan:
    """A span with no session open: its name on the build accounting's
    stack and as a profiler annotation, nothing recorded here."""
    __slots__ = ("name", "_api", "_ann")

    def __init__(self, name: str, api: bool = False):
        self.name = name
        self._api = api

    def __enter__(self):
        _build.push(self.name, self._api)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _build.pop()
        return False

    def set(self, **attrs):
        pass


class _Session:
    def __init__(self, path: str, prefix: str, max_events: int):
        self.path = path
        self.prefix = prefix
        self.max_events = max_events
        self.t0 = time.perf_counter()
        self.wall0 = time.time()
        self.chrome: list = []     # chrome traceEvents dicts
        self.jsonl: list = []      # flat event-stream dicts
        self.dropped = 0
        self.lock = threading.Lock()
        self.depth: dict = {}      # thread ident -> current span depth
        self.device_pids: dict = {}  # device label -> chrome pid


_session: Optional[_Session] = None


def enabled() -> bool:
    return _session is not None


def _trace_dir() -> str:
    from ..utils import config as qconf
    return (qconf.get("QUDA_TPU_TRACE_PATH", fresh=True)
            or qconf.get("QUDA_TPU_RESOURCE_PATH", fresh=True)
            or ".")


def start(path: Optional[str] = None, prefix: str = "trace") -> _Session:
    """Open a trace session (idempotent: an active session is kept —
    and its path/prefix WIN; explicit arguments that conflict with the
    active session are discarded with a warning, so a driver that
    init_quda'd with QUDA_TPU_TRACE=1 and then asks for bench_trace
    artifacts learns where its events actually went).
    Artifacts land in ``path`` (default: QUDA_TPU_TRACE_PATH, else the
    resource path, else cwd) as <prefix>.json / <prefix>_events.jsonl."""
    global _session
    if _session is None:
        from ..utils import config as qconf
        _session = _Session(path or _trace_dir(), prefix,
                            qconf.get("QUDA_TPU_TRACE_EVENTS_MAX",
                                      fresh=True))
    elif ((path is not None and path != _session.path)
          or prefix != _session.prefix):
        from ..utils import logging as qlog
        qlog.warningq(
            f"obs.trace.start({path!r}, prefix={prefix!r}): a session "
            f"is already active, keeping its artifacts at "
            f"{_session.path}/{_session.prefix}.json")
    return _session


def maybe_start() -> Optional[_Session]:
    """Start a session iff QUDA_TPU_TRACE is set (init_quda hook)."""
    from ..utils import config as qconf
    if qconf.get("QUDA_TPU_TRACE", fresh=True):
        return start()
    return None


def stop(flush_files: bool = True) -> Optional[dict]:
    """Close the session; returns {'chrome': path, 'jsonl': path} when
    artifacts were written (end_quda hook)."""
    global _session
    if _session is None:
        return None
    paths = flush() if flush_files else None
    _session = None
    return paths


def _now_us(s: _Session) -> float:
    return (time.perf_counter() - s.t0) * 1e6


def _push(s: _Session, chrome_ev: dict, jsonl_ev: Optional[dict]):
    with s.lock:
        if len(s.chrome) >= s.max_events:
            s.dropped += 1
            return
        s.chrome.append(chrome_ev)
        if jsonl_ev is not None:
            s.jsonl.append(jsonl_ev)


def _device_pid(s: _Session, label: str, desc: str) -> int:
    """Chrome pid for one device track; first use emits the perfetto
    process metadata naming it (mesh coordinates in the track name) —
    metadata rows bypass the event cap (bounded by device count) and
    pid 0 stays the host track."""
    with s.lock:
        pid = s.device_pids.get(label)
        if pid is not None:
            return pid
        if not s.device_pids:
            s.chrome.append({"name": "process_name", "ph": "M",
                             "pid": 0, "tid": 0,
                             "args": {"name": "host (api spans)"}})
        pid = 1 + len(s.device_pids)
        s.device_pids[label] = pid
        s.chrome.append({"name": "process_name", "ph": "M", "pid": pid,
                         "tid": 0, "args": {"name": desc}})
        s.chrome.append({"name": "process_sort_index", "ph": "M",
                         "pid": pid, "tid": 0,
                         "args": {"sort_index": pid}})
        return pid


def _mirror_span_per_device(s: _Session, name: str, cat: str, ts: float,
                            dur: float, mesh, args: dict) -> int:
    """One chrome span row per LOCAL device of ``mesh``, on that
    device's own pid track (mesh coordinates in the track name), so
    perfetto shows a sharded solve as parallel device rows instead of
    one collapsed host track.  The duration is the host-measured span
    (per-device device timelines need a profiler capture); what the
    rows add is the device/mesh-coordinate attribution."""
    import numpy as np
    try:
        import jax
        my_proc = jax.process_index()
    except Exception:
        return 0
    n = 0
    # partitioned axes only in the track names (a size-1 axis carries
    # no placement information); all axes when nothing is partitioned
    parted = [ax for ax in mesh.axis_names if mesh.shape[ax] > 1] \
        or list(mesh.axis_names)
    for idx, dev in np.ndenumerate(mesh.devices):
        if getattr(dev, "process_index", 0) != my_proc:
            continue
        label = f"{getattr(dev, 'platform', 'dev')}:{getattr(dev, 'id', 0)}"
        coords = ",".join(f"{ax}={i}" for ax, i
                          in zip(mesh.axis_names, idx) if ax in parted)
        pid = _device_pid(s, label, f"device {label} [{coords}]")
        _push(s, {"name": name, "cat": cat, "ph": "X",
                  "ts": round(ts, 3), "dur": round(dur, 3),
                  "pid": pid, "tid": 0,
                  "args": dict(args, device=label, mesh_coords=coords)},
              None)
        n += 1
    return n


class _Span:
    __slots__ = ("name", "cat", "args", "_ts", "_ann", "_depth", "_tid",
                 "_mesh", "_api")

    def __init__(self, name: str, cat: str, args: dict, mesh=None,
                 api: bool = False):
        self.name = name
        self.cat = cat
        self.args = args
        self._api = api
        self._ann = None
        self._ts = 0.0
        self._depth = 0
        self._tid = 0
        self._mesh = mesh

    def set(self, **attrs):
        """Attributes known only once the spanned work has run (every
        name registered in obs/schema.SPAN_ATTRS)."""
        unknown = set(attrs) - set(schema.SPAN_ATTRS)
        if unknown:
            raise KeyError(
                f"unregistered span attribute(s) {sorted(unknown)}; "
                "register them in quda_tpu/obs/schema.py SPAN_ATTRS")
        self.args.update(attrs)

    def __enter__(self):
        s = _session
        if s is None:            # stopped between creation and entry
            return self
        self._tid = threading.get_ident()
        self._depth = s.depth.get(self._tid, 0) + 1
        s.depth[self._tid] = self._depth
        _build.push(self.name, self._api)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._ts = _now_us(s)
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
            _build.pop()
        s = _session
        if s is None or self._depth == 0:
            return False
        dur = _now_us(s) - self._ts
        s.depth[self._tid] = self._depth - 1
        args = dict(self.args, depth=self._depth)
        n_dev = 0
        if self._mesh is not None:
            n_dev = _mirror_span_per_device(s, self.name, self.cat,
                                            self._ts, dur, self._mesh,
                                            dict(self.args))
        jsonl = {"kind": "span", "name": self.name, "cat": self.cat,
                 "ts_us": round(self._ts, 3), "dur_us": round(dur, 3),
                 "depth": self._depth, **self.args}
        if n_dev:
            jsonl["devices"] = n_dev
        _push(s, {"name": self.name, "cat": self.cat, "ph": "X",
                  "ts": round(self._ts, 3), "dur": round(dur, 3),
                  "pid": 0, "tid": 0, "args": args}, jsonl)
        return False


def _bare(name: str, annotate: Optional[bool] = None, api: bool = False):
    """The span of a process with no session open.  ``annotate``: whether
    the profile is on, where the caller has asked already; a span inside
    an open one follows it and does not ask again."""
    if annotate is None:
        annotate = _build.inside() or _qtimer._profiling_enabled()
    return _BareSpan(name, api) if annotate else _NOOP


def span(name: str, cat: str = "api", mesh=None, **args):
    """A nestable named span.  In a session it is recorded; with none
    open it is a profiler annotation and a frame of the build
    accounting's stack (module docstring), and under
    QUDA_TPU_DO_NOT_PROFILE the module's no-op singleton.  With
    ``mesh`` (a jax.sharding.Mesh) a session's span is additionally
    mirrored onto one chrome track per local mesh device, mesh
    coordinates in the track names — a sharded solve renders as parallel
    device rows in perfetto instead of one collapsed host track."""
    if _session is None:
        return _bare(name)
    return _Span(name, cat, args, mesh=mesh)


def event(name: str, cat: str = "event", **fields):
    """Instant event into both the chrome trace and the JSONL stream.

    Every call here also lands in the flight-recorder ring when
    QUDA_TPU_FLIGHT is on — the recorder rides the SAME emission sites
    (tuner decisions, escalation rungs, sentinel codes, gauge loads/
    rejections, exchange-policy picks) independently of whether a
    trace session is active, so the black box costs zero new
    instrumentation.  Both disabled paths stay one-global-load
    no-ops."""
    fl = _flight._session
    if fl is not None:
        fl.append(name, cat, fields)
    s = _session
    if s is None:
        return
    ts = _now_us(s)
    _push(s, {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": round(ts, 3), "pid": 0, "tid": 0, "args": fields},
          {"kind": "event", "name": name, "cat": cat,
           "ts_us": round(ts, 3), **fields})


def flush() -> Optional[dict]:
    """Write the chrome-trace JSON + JSONL stream; returns their paths.
    The session stays active (incremental flushes overwrite)."""
    s = _session
    if s is None:
        return None
    os.makedirs(s.path, exist_ok=True)
    chrome_path = os.path.join(s.path, f"{s.prefix}.json")
    jsonl_path = os.path.join(s.path, f"{s.prefix}_events.jsonl")
    with s.lock:
        doc = {"traceEvents": list(s.chrome),
               "displayTimeUnit": "ms",
               "otherData": {"source": "quda_tpu.obs.trace",
                             "wall_start": s.wall0,
                             "dropped_events": s.dropped}}
        lines = [json.dumps(e) for e in s.jsonl]
    with open(chrome_path, "w") as fh:
        json.dump(doc, fh)
    with open(jsonl_path, "w") as fh:
        fh.write("\n".join(lines) + ("\n" if lines else ""))
    return {"chrome": chrome_path, "jsonl": jsonl_path}


# -- TimeProfile-bridged helpers for the API layer --------------------------

@contextmanager
def api_span(name: str, **args):
    """Top-level API span: a pushProfile interval (category 'total' on
    the named TimeProfile) + a trace span — one context for every
    interface entry point (invert_quda, eigensolve_quda, ...).  API
    entries/exits are also marked into the flight-recorder ring
    (host-side, no-op when QUDA_TPU_FLIGHT is off) so a postmortem
    bundle's tail shows what the worker was serving when it failed."""
    _flight.record("api_enter", cat="api", api=name, **args)
    try:
        with _qtimer.push_profile(name) as prof:
            with (_bare(name, prof is not None, api=True)
                  if _session is None
                  else _Span(name, "api", args, api=True)):
                yield
    finally:
        _flight.record("api_exit", cat="api", api=name)


@contextmanager
def phase(category: str, profile: Optional[str] = None, mesh=None,
          **args):
    """One category interval on ``profile``'s TimeProfile + a trace span
    — the setup/compute/comms/epilogue breakdown inside an api_span.
    ``mesh`` mirrors the span onto per-device chrome tracks (see
    :func:`span`)."""
    profiling = _qtimer._profiling_enabled()
    prof = (_qtimer.get_profile(profile)
            if profile is not None and profiling else None)
    if prof is not None:
        prof.start(category)
    try:
        with (_bare(category, profiling) if _session is None
              else _Span(category, category, args, mesh=mesh)):
            yield
    finally:
        if prof is not None:
            prof.stop(category)
