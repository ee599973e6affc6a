"""Seconds per traced call inside one of the program's own spans, on
the profiler's clock: the spans of that name on the capture's host
plane that start inside the harness's ``bench_call`` spans, summed,
over the number of those (``program_spans.py``).  None where the run
was not traced, the newest capture on disk is not the one the harness
reduced for this run, or the capture holds no such span inside a call
(a program that opens none, a route that does not take that step)."""

from .. import program_spans


def read(ctx, span):
    if ctx["trace"] is None:
        return None
    path = program_spans.newest_xplane()
    if path is None:
        return None
    loaded = program_spans.from_events(program_spans.host_events(path),
                                       [span])
    if not program_spans.same_capture(loaded, ctx["trace"]):
        return None
    return program_spans.per_call(loaded, span)
