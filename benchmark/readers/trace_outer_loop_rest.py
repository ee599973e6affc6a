"""``trace_loop_rest`` on the OUTERMOST of the loops its pattern names.

``trace_loop_rest`` sums every event its ``loop`` pattern matches, and
a solve whose iterations run in a ``while`` inside another (the
mixed-precision CG on an operator's own step: stretches of sloppy
iterations, a reliable update between them) has two: the outer's time
holds the inner's, and the sum counts the iterations twice.  Here the
one matching name with the most seconds stands for the loop (nested
loops: the outermost, since it holds the others; one loop: itself, the
same number as ``trace_loop_rest``), so the rest is the whole solve's
loop outside its kernels: the XLA operations of an iteration and the
reliable updates' share, under either structure.

Arguments and result are ``trace_loop_rest``'s.  Names are XLA's per
module: two programs of one capture that each hold a loop of the same
name count as one.  None where that reader returns None."""

import re

from . import trace_loop_rest


def read(ctx, loop, **args):
    if ctx["trace"] is None:
        return None
    ops = ctx["trace"]["kernels"]
    rx = re.compile(loop)
    loops = [n for n in ops if rx.search(n)]
    if not loops:
        return None
    outer = max(loops, key=lambda n: ops[n]["seconds"])
    kept = {n: v for n, v in ops.items() if n == outer or n not in loops}
    return trace_loop_rest.read(
        dict(ctx, trace=dict(ctx["trace"], kernels=kept)), loop, **args)
