"""Entry point ``invert_multishift_quda`` on the Wilson-clover operator
with the clover term resident: ONE spin-colour source and N shifts a
call against one loaded gauge field and one clover term (upstream's
``invert_test --dslash-type clover --multishift N``: ``loadCloverQuda``
once a configuration, then ``invertMultiShiftQuda``; MILC's
``qudaCloverMultishiftInvert``; the single-flavour determinant of a
2+1-flavour clover RHMC).  ``open`` is ``entry/invert_quda_clover.open``
(init, ``load_gauge_quda``, ``load_clover_quda``) plus the traffic's
``offsets``; ``call`` = ``invert_multishift_quda`` with them.

The harness's source is (1, T, Z, Y, X, 4, 3); the call's source is its
p-parity half, the q sites emptied (p = even, the configuration's
``matpc``: the API's ``prepare`` then hands ``Mdag`` the p half itself).
The API returns the N p-site solutions (N, T, Z, Y, X/2, 4, 3); they
come back on the p sites of a zero (1, T, Z, Y, X, 4 N, 3) array whose
row ``4 i + spin`` is ``x_i[spin]``, which is what
``reference/clover_shifted.py`` reads.  Emptying and embedding are one
jitted program each and are inside the timed call, as in the HISQ
entries.  One call is one harness source: ``true_res`` is the LARGEST
of the API's per-shift true residuals, ``converged`` is every shift's;
``shift_iters_sum`` is the sum of ``iter_count_offset``, the shifted
updates the loop made.  Interface as ``entry/invert_quda.py``.

A program without the resident shifted clover route is not driven at
all: the import fails at once.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.spinor import even_odd_join, even_odd_split
from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import InvertParam
from . import invert_quda as single
from . import invert_quda_clover as clover

# The guard: a commit without the resident route (the parent of PR 49)
# sends dslash_type="clover" down the last branch of
# _invert_multishift_body, which rebuilds the canonical clover operator
# in every call and runs an eager loop on two stacks of fourteen
# canonical (T, Z, Y, X/2, 4, 3) complex spinors (PERF.md section 6,
# PR 49: what it did on the chip).  Such a tree fails here, at import,
# in seconds.
from quda_tpu.interfaces.quda_api import (  # noqa: E402,F401
    _invert_clover_multishift_resident)

PROFILE = "invert_multishift_quda"

close = clover.close


def open(config, traffic, gauge):
    state = clover.open(config, traffic, gauge)
    state["offsets"] = tuple(float(s) for s in traffic["offsets"])
    return state


def _geom(shape):
    return LatticeGeometry(tuple(reversed(shape)))


@jax.jit
def _even_half(sources):
    """(1,T,Z,Y,X,4,3) -> (T,Z,Y,X,4,3): source 0, odd sites zero."""
    v = sources[0]
    geom = _geom(v.shape[:4])
    even, odd = even_odd_split(v, geom)
    return even_odd_join(even, jnp.zeros_like(odd), geom)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(xs, dims):
    """(N,T,Z,Y,X/2,4,3) even-site solutions -> (1,T,Z,Y,X,4 N,3): row
    4 i + spin = x_i[spin] on the even sites, odd sites zero."""
    geom = _geom(dims)
    full = jax.vmap(lambda e: even_odd_join(e, jnp.zeros_like(e),
                                            geom))(xs)
    rows = jnp.moveaxis(full, 0, 4)             # (T,Z,Y,X,N,4,3)
    return rows.reshape(rows.shape[:4] + (-1, 3))[None]


def call(state, sources):
    """sources (1,T,Z,Y,X,4,3) -> the N solutions as the rows of one
    (1,T,Z,Y,X,4 N,3) array and one entry of info for the call."""
    offs = state["offsets"]
    p = InvertParam(kappa=state["kappa"], num_offset=len(offs),
                    offset=offs, **state["config"]["invert_param"])
    xs = api.invert_multishift_quda(_even_half(sources), p)
    return _embed(xs, tuple(sources.shape[1:5])), {
        "iters": [int(p.iter_count)],
        # np.max: a NaN shift is the largest (Python's max skips it)
        "true_res": [float(np.max(np.asarray(p.true_res_offset)))],
        "converged": [bool(all(p.converged_multi))],
        "true_res_offset": [float(r) for r in p.true_res_offset],
        "shift_iters_sum": int(sum(p.iter_count_offset))}


def counters():
    out = single.phase_counters(PROFILE)
    out["phase.clover_load"] = clover.counters()["phase.clover_load"]
    return out
