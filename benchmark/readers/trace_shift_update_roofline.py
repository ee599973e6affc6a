"""The share of its memory roofline of a multi-shift CG's live-shift
update kernel, in percent: the bytes the update NEEDS over the traced
calls (``kernel_models/<model>.py``: ``x_i`` and ``p_i`` in and out for
every shifted update the loop MADE, which the trace cannot say and the
program reports: a converged shift leaves the update, so the work of
one kernel event changes from iteration to iteration) over the
published HBM bandwidth of this device kind (``peaks.py``) over the
device time the trace shows for the kernel.

The kernel runs once a loop iteration, so its events in the capture
number the iterations of the traced calls: the traced calls are the
window's calls from the second on (``run.py`` starts the profiler
before call 1) whose ``iters`` add up to exactly that count, and the
updates they made are their ``shift_iters_sum`` (the entry's sum of
``InvertParam.iter_count_offset``).  None where the run was not traced,
the capture holds no such kernel, the calls carry no
``shift_iters_sum``, or no run of calls accounts for the events."""

import importlib
import re

from .. import peaks
from .trace_roofline import BYTES, _SIG


def traced_updates(calls, events):
    """The shifted updates of the calls 1, 2, ... whose iterations add
    up to ``events`` (one kernel event an iteration), or None."""
    iters = updates = 0
    for call in calls[1:]:
        if "shift_iters_sum" not in call:
            return None
        iters += sum(call["iters"])
        updates += call["shift_iters_sum"]
        if iters >= events:
            break
    return updates if iters == events else None


def read(ctx, pattern, model):
    if ctx["trace"] is None:
        return None
    rx = re.compile(pattern)
    events, seconds, widths = 0, 0.0, set()
    for name, k in ctx["trace"]["kernels"].items():
        sig = _SIG.search(name)
        if rx.search(name) and sig:
            events += k["count"]
            seconds += k["seconds"]
            widths.add(BYTES[sig.group(1)])
    if not events or seconds <= 0 or len(widths) != 1:
        return None
    updates = traced_updates(ctx["calls"], events)
    if updates is None:
        return None
    width = widths.pop()
    w = ctx["config"]["widths"]
    need = importlib.import_module(
        f"{ctx['package']}.kernel_models.{model}").needed(
        ctx["lattice"], in_bytes=width, out_bytes=width, n_rhs=1,
        spins=w["spins"], colours=w["colours"])["bytes"] * updates
    bw = peaks.peak(ctx["device_kind"], "hbm_bytes_per_s")
    return 100.0 * need / bw / seconds
