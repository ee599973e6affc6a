"""Entry point ``invert_multi_src_quda`` on the improved staggered
(asqtad / HISQ) operator with application-supplied fat and long links:
N colour-vector sources a call against one loaded (fat, long) pair
(upstream's ``staggered_invert_test --compute-fat-long false --nsrc N``;
MILC's ``qudaLoadKSLink`` + ``qudaInvertMsrc``).  ``open`` is
``entry/invert_quda_hisq.open`` (init, ``load_gauge_quda``, the Naik
links, ``load_fat_long_quda`` under the ``phase.ks_load`` clock);
``call`` = ``invert_multi_src_quda``.

The harness's sources are (N, T, Z, Y, X, 4, 3); a call's N colour
vectors are SPIN ROW 0 of each, and the solutions come back in row 0 of
a zero array of the same shape.  The slice and the embed are one jitted
program each and are inside the timed call, as in the single-source
entry.  Interface as ``entry/invert_quda.py``.
"""

import jax
import jax.numpy as jnp

from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import InvertParam
from . import invert_quda as single
from . import invert_quda_hisq as hisq

PROFILE = "invert_multi_src_quda"

open = hisq.open
close = hisq.close


@jax.jit
def _colour_vectors(sources):
    """(N,T,Z,Y,X,4,3) -> (N,T,Z,Y,X,1,3): spin row 0 of every source."""
    return sources[..., 0:1, :]


@jax.jit
def _embed(x):
    """(N,T,Z,Y,X,1,3) -> (N,T,Z,Y,X,4,3), rows 1-3 zero."""
    return jnp.pad(x, ((0, 0),) * 5 + ((0, 3), (0, 0)))


def call(state, sources):
    """sources (N,T,Z,Y,X,4,3) -> solutions (N,...) and per-source info."""
    p = InvertParam(mass=state["mass"], **state["config"]["invert_param"])
    x = api.invert_multi_src_quda(_colour_vectors(sources), p)
    return _embed(x), {"iters": [int(i) for i in p.iter_count_multi],
                       "true_res": [float(r) for r in p.true_res_multi],
                       "converged": [bool(c) for c in p.converged_multi]}


def counters():
    out = single.phase_counters(PROFILE)
    out["phase.ks_load"] = hisq.counters()["phase.ks_load"]
    return out
