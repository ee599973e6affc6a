"""The program's own spans in a profiler capture, per traced call.

The program opens a ``jax.profiler.TraceAnnotation`` for each of its
spans (``quda_tpu/obs/trace.py``: ``invert_quda``, ``setup``,
``source_split``, ``prepare``, ``mdag``, ``solve:*``, ``dispatch``,
``wait``, ``verified_exit``, ``exit_read``, ...), so a capture holds
them on the host plane, on the clock of the device's operations.
``trace_reduce.load_xplane`` keeps host spans of 5 ms and more, for its
idle-gap labels; the spans read here are often shorter, so this module
loads the named ones whatever their length.

Two steps, as ``trace_reduce``: ``host_events`` reads a ``.xplane.pb``
(``jax.profiler.ProfileData``, nothing else); ``from_events`` keeps of
them ``{"calls": [[start_ns, end_ns], ...], "spans": {name: [[start_ns,
dur_ns], ...]}}`` and ``per_call`` is arithmetic on that, both checked
on ``tests/fixtures/`` by ``tests/test_program_spans.py``.  ``run.py`` empties the
cell's directory under ``.bench_trace/`` before a run and hands the readers
neither the path nor the cell: the run's capture is the newest one there,
and ``same_capture`` holds it to what ``run.py`` did hand over, its own
reduction of the capture (as many ``bench_call`` spans, the same window to
the microsecond), so another cell's stale capture is not read for this one.
"""

import functools
import glob
import os

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(os.path.dirname(HERE), ".bench_trace")
HOST_PLANE = "/host:CPU"
CALL = "bench_call"           # the harness's annotation around each call


def newest_xplane(root=TRACE_ROOT):
    """The newest .xplane.pb of any cell's capture, or None."""
    files = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def same_capture(loaded, reduced):
    """Whether ``loaded`` (``from_events``) is of the capture that
    ``trace_reduce.reduce`` gave ``reduced`` for: the window there runs
    from the first ``bench_call``'s start to the last one's end."""
    calls = loaded["calls"]
    return bool(calls) and len(calls) == reduced.get("n_spans") and abs(
        (calls[-1][1] - calls[0][0]) / 1e9 - reduced["window_s"]) < 1e-6


def from_events(events, names):
    """``events``: (name, start_ns, dur_ns) of a host plane, any order."""
    wanted = set(names)
    calls, spans = [], {}
    for name, start, dur in events:
        if name == CALL:
            calls.append([float(start), float(start) + float(dur)])
        elif name in wanted:
            spans.setdefault(name, []).append([float(start), float(dur)])
    return {"calls": sorted(calls),
            "spans": {n: sorted(v) for n, v in spans.items()}}


@functools.lru_cache(maxsize=1)
def host_events(path):
    """Every (name, start_ns, dur_ns) of a capture's host plane; the
    newest capture's are kept, one metric after another reads them."""
    from jax.profiler import ProfileData
    return tuple((e.name, e.start_ns, e.duration_ns)
                 for pl in ProfileData.from_file(path).planes
                 if pl.name == HOST_PLANE
                 for ln in pl.lines for e in ln.events)


def per_call(loaded, name):
    """Seconds per traced call inside spans of that name: the summed
    duration of the spans that start inside a ``bench_call``, over the
    number of ``bench_call`` spans.  None where there is no call, or no
    such span inside one."""
    calls = loaded["calls"]
    inside = [d for s, d in loaded["spans"].get(name, ())
              if any(c0 <= s < c1 for c0, c1 in calls)]
    if not calls or not inside:
        return None
    return sum(inside) / len(calls) / 1e9
