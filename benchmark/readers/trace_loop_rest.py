"""What a solver loop does on the device OUTSIDE its kernels: the
device time of the trace's ``loop`` operations (the solve program's
``while``: an event of the ops line that spans everything nested in
it) less the device time of the ``kernel`` events, through
``trace_reduce.kernel_time`` on instruction names alone, so XLA's
numbering of its fusions does not matter.  What is left is every XLA
operation of the loop body: the vector updates, the dots, the sums.

``per`` = ``iteration``: microseconds of that rest per loop iteration,
an iteration being ``kernels_per_iteration`` kernel events;
``per`` = ``loop``: the rest as a percentage of the loop's time.

The ``kernel`` events of the traced calls that run outside the loop
(another program's: an entry or an exit that calls the same kernel)
are counted in: a call of the multi-shift cell runs two such passes
beside four an iteration over ~200 iterations, which overstates the
iterations and the kernels' seconds by a quarter of a percent each.
None where the run was not traced or the capture holds no such loop or
kernel."""

from .. import trace_reduce


def read(ctx, loop, kernel, kernels_per_iteration, per):
    if ctx["trace"] is None:
        return None
    loops, loop_s = trace_reduce.kernel_time(ctx["trace"], loop)
    count, kernel_s = trace_reduce.kernel_time(ctx["trace"], kernel)
    if not loops or not count or loop_s <= 0:
        return None
    rest_s = loop_s - kernel_s
    if per == "loop":
        return 100.0 * rest_s / loop_s
    return rest_s / (count / kernels_per_iteration) * 1e6
