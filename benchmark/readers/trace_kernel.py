"""Mean device microseconds of the trace's operations whose name matches
``pattern`` (summed device duration of the events / their count)."""

from .. import trace_reduce


def read(ctx, pattern):
    if ctx["trace"] is None:
        return None
    count, seconds = trace_reduce.kernel_time(ctx["trace"], pattern)
    if not count:
        return None
    return seconds / count * 1e6
