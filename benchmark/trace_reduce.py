"""From a profiler trace to numbers: device busy time, kernel time by
name, the longest idle gaps and what the host was doing in them.

Two steps, kept apart so the arithmetic can be checked on a small
recorded trace (``fixtures/``) without the profiler:

* ``load_xplane(path)`` turns the profiler's ``.xplane.pb`` into a plain
  structure ``{"planes": [{"name", "lines": [{"name", "events":
  [[name, start_ns, dur_ns], ...]}]}]}`` (``jax.profiler.ProfileData``,
  nothing else);
* ``reduce(trace, ...)`` is pure arithmetic on that structure.

On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event per executed HLO operation (a ``while`` and the
operations inside it are all events of that line, nested in time; a
pallas kernel is one ``custom-call`` event named after the function that
wraps its ``pallas_call``), ``XLA Modules`` one per program run.  An
event's name is the whole HLO instruction; ``short_name`` cuts it to the
instruction's own name and, for a pallas kernel, the element types of its
result, first operand and last operand (``dslash_eo_pallas_packed.24
bf16<-bf16,bf16``).  Host threads are lines of the plane ``/host:CPU``;
the harness's own ``jax.profiler.TraceAnnotation`` spans (``SPAN``) and
the runtime's own spans (``PjitFunction(while)``,
``backend_compile_and_load``, ...) are events there.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN = "bench_call"           # the harness's annotation around each call
HOST_MIN_NS = 5e6             # host spans shorter than this are dropped
_TYPED = re.compile(r"\b([a-z]+\d+)\[")


def short_name(hlo):
    """``%name = type[...] opcode(operands...), attrs`` -> ``name`` and,
    for a pallas kernel, `` out<-first,last`` element types."""
    name, _, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    if 'custom_call_target="tpu_custom_call"' not in rest:
        return name
    head, _, tail = rest.partition(" custom-call(")
    out = _TYPED.findall(head)
    ins = _TYPED.findall(tail.partition("), custom_call_target")[0])
    if not out or not ins:
        return name
    return f"{name} {out[0]}<-{ins[0]},{ins[-1]}"


def find_xplane(trace_dir):
    """The newest .xplane.pb under a jax.profiler trace directory."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path):
    """Device planes in full, names cut by ``short_name``; of the host
    plane the harness's spans and every span of ``HOST_MIN_NS`` or more
    (a host plane holds every runtime call and is of no use whole)."""
    from jax.profiler import ProfileData
    planes = []
    for pl in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(pl.name))
        if not (device or pl.name == HOST_PLANE):
            continue
        lines = []
        for ln in pl.lines:
            evs = [[short_name(e.name) if device else e.name,
                    float(e.start_ns), float(e.duration_ns)]
                   for e in ln.events
                   if device or e.name == SPAN
                   or e.duration_ns >= HOST_MIN_NS]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def spans(trace, name=SPAN):
    """[start_ns, end_ns] of every host span of that name, in order."""
    out = []
    for pl in trace["planes"]:
        if pl["name"] != HOST_PLANE:
            continue
        for ln in pl["lines"]:
            out += [[s, s + d] for n, s, d in ln["events"] if n == name]
    return sorted(out)


def device_ops(trace):
    """{plane name: [[name, start_ns, dur_ns], ...]} of the ops lines."""
    out = {}
    for pl in trace["planes"]:
        if not DEVICE_PLANE.match(pl["name"]):
            continue
        evs = []
        for ln in pl["lines"]:
            if ln["name"] == OPS_LINE:
                evs += ln["events"]
        out[pl["name"]] = evs
    return out


def reduce(trace, top=10):
    """Busy time, kernel time and idle gaps inside the traced window.

    The window runs from the start of the first ``SPAN`` to the end of
    the last (the whole calls that were traced; what the profiler caught
    before and after them is cut off).  ``busy_s`` is the union of the
    intervals in which an operation ran, averaged over the chips.
    """
    sp = spans(trace)
    ops = device_ops(trace)
    if not ops or not any(ops.values()):
        return None
    if sp:
        t0, t1 = sp[0][0], sp[-1][1]
    else:                       # no span recorded: the ops' own extent
        starts = [e[1] for evs in ops.values() for e in evs]
        ends = [e[1] + e[2] for evs in ops.values() for e in evs]
        t0, t1 = min(starts), max(ends)
    busy, by_name, gaps = [], {}, []
    for plane, evs in ops.items():
        iv = _union(_clip([(s, s + d) for _, s, d in evs], t0, t1))
        busy.append(sum(e - s for s, e in iv))
        for n, s, d in evs:
            if s + d > t0 and s < t1:
                c = by_name.setdefault(n, [0, 0.0])
                c[0] += 1
                c[1] += d
        edges = [t0] + [t for s_e in iv for t in s_e] + [t1]
        gaps += [(edges[i + 1] - edges[i], edges[i])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(ops)

    host = [(n, s, s + d) for pl in trace["planes"]
            if pl["name"] == HOST_PLANE for ln in pl["lines"]
            for n, s, d in ln["events"] if n != SPAN]

    def label(t, g):
        """Where in which call, and the (up to three) longest host spans
        that cover half of the gap or more."""
        where = "between calls"
        for i, (s, e) in enumerate(sp):
            if s <= t < e:
                where = f"call {i} +{(t - s) / 1e9:.3f}s"
        over = {}
        for n, s, e in host:
            if min(e, t + g) - max(s, t) >= 0.5 * g:
                over[n] = max(over.get(n, 0.0), e - s)
        doing = " > ".join(sorted(over, key=lambda n: -over[n])[:3])
        return f"{where}: {doing or 'no host span'}"
    gaps.sort(reverse=True)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "n_devices": n_dev,
        "n_spans": len(sp),
        "kernels": {n: {"count": c, "seconds": s / 1e9}
                    for n, (c, s) in by_name.items()},
        "device_ops": [[n, s / 1e9] for n, (c, s) in sorted(
            by_name.items(), key=lambda kv: -kv[1][1])[:top]],
        "idle_gaps": [[label(t, g), g / 1e9] for g, t in gaps[:top]],
    }


def kernel_time(reduced, pattern):
    """(count, seconds) summed over the op names a regex matches."""
    rx = re.compile(pattern)
    hits = [v for n, v in reduced["kernels"].items() if rx.search(n)]
    return (sum(v["count"] for v in hits),
            sum(v["seconds"] for v in hits))
