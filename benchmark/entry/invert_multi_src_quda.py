"""Entry point ``invert_multi_src_quda``: N sources per call (on one
device the batched-pairs route).  Interface as ``entry/invert_quda.py``."""

from quda_tpu.interfaces import quda_api as api
from . import invert_quda as single

PROFILE = "invert_multi_src_quda"

open = single.open
close = single.close


def call(state, sources):
    """sources (N,T,Z,Y,X,4,3) -> solutions (N,...) and per-source info."""
    p = single.invert_param(state)
    x = api.invert_multi_src_quda(sources, p)
    return x, {"iters": [int(i) for i in p.iter_count_multi],
               "true_res": [float(r) for r in p.true_res_multi],
               "converged": [bool(c) for c in p.converged_multi]}


def counters():
    return single.phase_counters(PROFILE)
