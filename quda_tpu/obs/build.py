"""Build accounting: what jax traced, lowered and compiled, for which
program, under which of the program's spans.

A process's first call of a cell spends tens of seconds before the
device runs anything, and the phase clocks (utils/timer) only say in
which phase of which API call.  jax publishes the rest through
``jax.monitoring``: one duration event per program and stage
(``jaxpr_trace_duration``: Python tracing to a jaxpr;
``jaxpr_to_mlir_module_duration``: lowering, which for a pallas kernel
holds the Mosaic lowering; ``backend_compile_duration``: XLA's compile,
or the fetch from the persistent cache), each with the program's
``fun_name``, and events that say whether the persistent cache was
asked and served.  This module listens and keeps one record per event:

    {"program", "stage": trace | lower | compile, "seconds",
     "cache": hit | miss | off (compile only),
     "inside": the outermost program whose trace this trace is part of
               (None for a program traced by itself),
     "repeats": later trace events folded into this record (below),
     "span": the innermost open span or phase of the program,
     "path": every open span, outermost first, joined by " > ",
     "api": the OUTERMOST open API span (invert_quda, load_clover_quda,
            ...; "none" for work outside any), "ordinal": which call of
            that API name in the process this is (0 with "none")}

A jitted function traced inside another (a kernel wrapper inside the
solve program) fires its own trace event while the outer one is timed:
its seconds are part of the outer's.  Such records are kept, marked
``inside``, and every sum here leaves them out.

A route that traces the same small programs again in every call (the
eager entry of the batched Wilson route: 17 a call, microseconds each)
would add a record a trace for ever.  From an API span's second call
on, a trace of a program under the spans it was already traced under
in an earlier such call is folded into that first record: its
``repeats`` counts them and its ``seconds`` sums them, so sums and
counts stay whole and a long-lived process keeps a few records a
route.  Lowerings and compiles are never folded.  Past ``MAX_RECORDS``
(a process that really builds anew in every call) records are dropped,
counted, and said once.

Always on once ``install`` has run (``init_quda``), like the phase
timers: no listener fires in a call that builds nothing, and a build is
milliseconds at the least.  The spans are whatever ``obs/trace`` opens
(``span``, ``phase``, ``api_span``); under ``QUDA_TPU_DO_NOT_PROFILE``
it opens none and every record reads ``api="none"``.  Under
``QUDA_TPU_METRICS`` the same records feed the
``program_build_seconds{program, stage}`` counter.
"""

from __future__ import annotations

import re
import threading

from . import metrics as omet

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
STAGES = {TRACE_EVENT: "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
# the persistent cache, in the order jax fires them inside one compile
_CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "miss",
                 "/jax/compilation_cache/cache_hits": "hit"}
_WRAPPED = re.compile(r"^(?:jit|pmap)\((.*)\)$")
# a process that builds anew on every call would grow the list for ever:
# past this many records the rest are counted and dropped
MAX_RECORDS = 50_000

# one lock for what every thread shares (a serve worker solves beside
# the thread that reads): the records, the API counts, the install flag
_lock = threading.Lock()
_records: list = []
_retraced: dict = {}     # (program, path, api, inside) -> its first record
_dropped = [0]
_api_counts: dict = {}
_installed = [False]


class _PerThread(threading.local):
    """A thread's open spans ((name, ordinal) frames: ordinal 0 for a
    span or phase, the call's number for an API span), the programs
    whose trace is in progress, and the cache outcome of the compile in
    progress."""

    def __init__(self):
        self.spans = []
        self.tracing = []
        self.cache = None


_local = _PerThread()


# -- the span stack (obs/trace pushes and pops) ------------------------------

def push(name: str, api: bool = False):
    ordinal = 0
    if api:
        with _lock:
            ordinal = _api_counts[name] = _api_counts.get(name, 0) + 1
    _local.spans.append((name, ordinal))


def pop():
    if _local.spans:
        _local.spans.pop()


def inside() -> bool:
    """Whether the calling thread has a span open."""
    return bool(_local.spans)


def where() -> dict:
    """The calling thread's open spans as a record's four fields."""
    spans = _local.spans
    api, ordinal = next(((n, o) for n, o in spans if o), ("none", 0))
    return {"span": spans[-1][0] if spans else "none",
            "path": " > ".join(n for n, _ in spans),
            "api": api, "ordinal": ordinal}


# -- the listeners -----------------------------------------------------------

def program_name(fun_name: str) -> str:
    """``jit(f)`` (lowering, compile) and ``f`` (tracing) are one
    program."""
    m = _WRAPPED.match(fun_name)
    return m.group(1) if m else fun_name


def _on_start(event: str, value, fun_name: str = "", **kw):
    # jax records a stage's start time as a scalar under the duration
    # event's name: the one way to know a trace is nested in another
    if event == TRACE_EVENT:
        _local.tracing.append(program_name(fun_name))


def _on_event(event: str, **kw):
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        _local.cache = outcome


def _on_duration(event: str, seconds: float, fun_name: str = "", **kw):
    stage = STAGES.get(event)
    if stage is None:
        return
    rec = {"program": program_name(fun_name), "stage": stage,
           "seconds": float(seconds), "inside": None, "repeats": 0,
           **where()}
    if stage == "trace":
        tracing = _local.tracing
        if tracing:
            tracing.pop()
        if tracing:
            rec["inside"] = tracing[0]
    elif stage == "compile":
        rec["cache"] = _local.cache or "off"
        _local.cache = None
    with _lock:
        _keep(rec)
    if rec["inside"] is None:
        omet.inc("program_build_seconds", rec["seconds"],
                 program=rec["program"], stage=stage)


def _keep(rec):
    """Append ``rec``, or fold a later call's repeated trace into the
    first record of its kind (the module's text); under the lock."""
    key = None
    if rec["stage"] == "trace" and rec["ordinal"] >= 2:
        key = (rec["program"], rec["path"], rec["api"], rec["inside"])
        first = _retraced.get(key)
        if first is not None:
            first["repeats"] += 1
            first["seconds"] += rec["seconds"]
            return
    if len(_records) >= MAX_RECORDS:
        if not _dropped[0]:
            from ..utils.logging import warningq
            warningq(f"obs/build: {MAX_RECORDS} build records kept; "
                     "later ones are counted and dropped (some program is "
                     "built anew in every call: build.summary() names it)")
        _dropped[0] += 1
        return
    _records.append(rec)
    if key is not None:
        _retraced[key] = rec


def install():
    """Register the listeners with ``jax.monitoring``, once a process
    (``init_quda``)."""
    from jax import monitoring
    with _lock:
        if _installed[0]:
            return
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed[0] = True


# -- reading -----------------------------------------------------------------

def seconds_here() -> float:
    """Seconds of everything built so far in the calling thread's API
    call under its innermost open span (a cached program's miss asks
    from inside its ``solve:*`` / ``verified_exit`` / ``prepare`` span);
    0.0 with no span open."""
    here = where()
    if not here["path"]:
        return 0.0
    with _lock:
        return sum(r["seconds"] for r in _records
                   if r["inside"] is None and r["api"] == here["api"]
                   and r["ordinal"] == here["ordinal"]
                   and (r["path"] + " > ").startswith(here["path"] + " > "))


def snapshot() -> list:
    """A copy of the records, oldest first."""
    with _lock:
        return [dict(r) for r in _records]


def dropped() -> int:
    return _dropped[0]


def by_program(records) -> list:
    """One row a (program, causing path, API span, ordinal), heaviest
    first: seconds by stage, their total, how many times it was built
    and what the persistent cache answered."""
    rows: dict = {}
    for r in records:
        if r["inside"] is not None:
            continue
        key = (r["program"], r["path"], r["api"], r["ordinal"])
        row = rows.setdefault(key, {
            "program": r["program"], "path": r["path"], "api": r["api"],
            "ordinal": r["ordinal"], "trace": 0.0, "lower": 0.0,
            "compile": 0.0, "seconds": 0.0, "builds": 0, "cache": {}})
        row[r["stage"]] += r["seconds"]
        row["seconds"] += r["seconds"]
        if r["stage"] == "compile":
            row["builds"] += 1
            row["cache"][r["cache"]] = row["cache"].get(r["cache"], 0) + 1
    return sorted(rows.values(), key=lambda row: -row["seconds"])


def summary(top: int = 10) -> str:
    """The ``top`` heaviest rows of ``by_program`` as text
    (``utils/timer.print_summary``); empty where nothing was built."""
    rows = by_program(snapshot())
    if not rows:
        return ""
    total = sum(row["seconds"] for row in rows)
    lines = [f"Programs built: {len(rows)}, {total:.2f} s "
             "(trace / lower / compile; cache; under)"]
    for row in rows[:top]:
        cache = ",".join(f"{k}={v}" for k, v in sorted(row["cache"].items()))
        lines.append(
            f"  {row['seconds']:8.2f} s  {row['program']}: "
            f"{row['trace']:.2f} / {row['lower']:.2f} / "
            f"{row['compile']:.2f}; {cache or '-'}; "
            f"{row['path'] or 'none'} #{row['ordinal']}")
    if _dropped[0]:
        lines.append(f"  ({_dropped[0]} records dropped past "
                     f"{MAX_RECORDS})")
    return "\n".join(lines)


def reset():
    """Forget the records, the API counts and the calling thread's
    stacks (tests; the listeners stay registered)."""
    with _lock:
        del _records[:]
        _retraced.clear()
        _dropped[0] = 0
        _api_counts.clear()
    del _local.spans[:]
    del _local.tracing[:]
    _local.cache = None
