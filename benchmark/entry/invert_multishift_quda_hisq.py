"""Entry point ``invert_multishift_quda`` on the improved staggered
(asqtad / HISQ) operator with application-supplied fat and long links:
ONE even-parity colour-vector source and N shifts a call against one
loaded (fat, long) pair (upstream's ``staggered_invert_test
--compute-fat-long false --multishift N``; MILC's ``qudaLoadKSLink`` +
``qudaMultishiftInvert``, the solve of the RHMC force and action).
``open`` is ``entry/invert_quda_hisq.open`` (init, ``load_gauge_quda``,
the Naik links, ``load_fat_long_quda`` under the ``phase.ks_load``
clock); ``call`` = ``invert_multishift_quda`` with the traffic's
``offsets``.

The harness's source is (1, T, Z, Y, X, 4, 3); the call's colour vector
is SPIN ROW 0 with its odd sites emptied (MILC's even parity: the API's
``prepare`` then hands the loop ``2m b_e``).  The API returns the N
even-site solutions (N, T, Z, Y, X/2, 1, 3); they come back on the even
sites of a zero (1, T, Z, Y, X, N, 3) array whose spin-row axis is the
SHIFT axis, row i = ``x_i``, which is what ``reference/hisq_shifted.py``
reads.  The slice and the embed are one jitted program each and are
inside the timed call, as in the other HISQ entries.  One call is one
harness source: ``true_res`` is the LARGEST of the API's per-shift true
residuals, ``converged`` is every shift's.  Interface as
``entry/invert_quda.py``.

A program whose ``InvertParam`` reports no per-shift residuals is not
driven at all: the import fails at once.
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
from . import invert_quda_hisq as hisq

if "true_res_offset" not in InvertParam.__dataclass_fields__:
    raise ImportError(
        "this program's InvertParam has no true_res_offset: its "
        "invert_multishift_quda verifies shift 0 only, and the "
        "configuration's guarantee is every shift's")

PROFILE = "invert_multishift_quda"

close = hisq.close


def open(config, traffic, gauge):
    state = hisq.open(config, traffic, gauge)
    state["offsets"] = tuple(float(s) for s in traffic["offsets"])
    return state


def _geom(shape):
    return LatticeGeometry(tuple(reversed(shape)))


@jax.jit
def _even_colour_vector(sources):
    """(1,T,Z,Y,X,4,3) -> (T,Z,Y,X,1,3): spin row 0 of source 0, odd
    sites zero."""
    v = sources[0][..., 0:1, :]
    geom = _geom(v.shape[:4])
    even, odd = even_odd_split(v, geom)
    return even_odd_join(even, jnp.zeros_like(odd), geom)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(xs, dims):
    """(N,T,Z,Y,X/2,1,3) even-site solutions -> (1,T,Z,Y,X,N,3): row i
    = x_i on the even sites, odd sites zero."""
    geom = _geom(dims)
    full = jax.vmap(lambda e: even_odd_join(e, jnp.zeros_like(e),
                                            geom))(xs)
    return jnp.moveaxis(full[..., 0, :], 0, -2)[None]


def call(state, sources):
    """sources (1,T,Z,Y,X,4,3) -> the N solutions as the rows of one
    (1,T,Z,Y,X,N,3) array and one entry of info for the call."""
    offs = state["offsets"]
    p = InvertParam(mass=state["mass"], num_offset=len(offs), offset=offs,
                    **state["config"]["invert_param"])
    xs = api.invert_multishift_quda(_even_colour_vector(sources), p)
    return _embed(xs, tuple(sources.shape[1:5])), {
        "iters": [int(p.iter_count)],
        # np.max: a NaN shift is the largest (Python's max skips it)
        "true_res": [float(np.max(np.asarray(p.true_res_offset)))],
        "converged": [bool(all(p.converged_multi))],
        "true_res_offset": [float(r) for r in p.true_res_offset]}


def counters():
    out = single.phase_counters(PROFILE)
    out["phase.ks_load"] = hisq.counters()["phase.ks_load"]
    return out
