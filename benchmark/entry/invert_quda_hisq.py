"""Entry point ``invert_quda`` on the improved staggered (asqtad / HISQ)
operator with application-supplied fat and long links: ``open`` =
``init_quda``, ``load_gauge_quda``, ``load_fat_long_quda`` (upstream's
``staggered_invert_test --compute-fat-long false``: ``loadGaugeQuda``
with QUDA_ASQTAD_FAT_LINKS / QUDA_ASQTAD_LONG_LINKS; MILC's
``qudaLoadKSLink``), ``call`` = ``invert_quda`` (``qudaInvert``).

The links are the configuration's: fat = the thin links the harness
hands over, long = -(1/24) U_mu(x) U_mu(x+mu) U_mu(x+2mu), made here as
the application would, lattice-minor (three broadcast multiplies a
product on (3, 3, T, Z, Y*X) planes: a canonical (...,3,3) product
tile-pads ~57x on a TPU and does not fit 24^4).

The harness's sources are (n, T, Z, Y, X, 4, 3); a call's colour vector
is SPIN ROW 0 of source 0, and the solution comes back in row 0 of a
zero array of the same shape.  The slice and the embed are one jitted
program each and are inside the timed call.  Interface as
``entry/invert_quda.py``; the counters add the seconds of
``load_fat_long_quda`` (``phase.ks_load``: this module's own clock
around the API call, to ``block_until_ready`` of what it left
resident).
"""

import functools

import jax
import jax.numpy as jnp

from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.ops import wilson_packed as wpk
from quda_tpu.utils import timer
from . import invert_quda as single

LOAD_PROFILE = "load_fat_long_quda"
LOAD_CATEGORY = "entry"

close = single.close


def _mul(a, b):
    return sum(a[:, j][:, None] * b[j][None, :] for j in range(3))


@functools.partial(jax.jit, static_argnames=("dims",))
def naik_links(gauge, dims):
    """-(1/24) U_mu(x) U_mu(x+mu) U_mu(x+2mu); canonical
    (4,T,Z,Y,X,3,3) in and out, the products lattice-minor."""
    _, _, Y, X = dims
    gp = wpk.pack_gauge(gauge)                       # (4,3,3,T,Z,Y*X)
    out = []
    for mu in range(4):
        u1 = wpk.shift_packed(gp[mu], mu, +1, X, Y)
        u2 = wpk.shift_packed(u1, mu, +1, X, Y)
        out.append((-1.0 / 24.0) * _mul(_mul(gp[mu], u1), u2))
    return wpk.unpack_gauge(jnp.stack(out), dims)


@jax.jit
def _colour_vector(sources):
    """(n,T,Z,Y,X,4,3) -> (T,Z,Y,X,1,3): spin row 0 of source 0."""
    return sources[0][..., 0:1, :]


@jax.jit
def _embed(x):
    """(T,Z,Y,X,1,3) -> (1,T,Z,Y,X,4,3), rows 1-3 zero."""
    return jnp.pad(x, ((0, 0),) * 4 + ((0, 3), (0, 0)))[None]


def open(config, traffic, gauge):
    """init + resident gauge + resident fat and long links; ``gauge``
    is (4,T,Z,Y,X,3,3) complex64."""
    dims = tuple(gauge.shape[1:5])
    api.init_quda()
    api.load_gauge_quda(gauge, GaugeParam(
        X=tuple(reversed(dims)), **config["gauge_param"]))
    long_links = naik_links(gauge, dims).block_until_ready()
    with timer.get_profile(LOAD_PROFILE)(LOAD_CATEGORY):
        api.load_fat_long_quda(gauge, long_links)
        jax.block_until_ready((api._ctx["fat"], api._ctx["long"]))
    return {"config": config, "mass": float(traffic["mass"])}


def call(state, sources):
    """sources (1,T,Z,Y,X,4,3) -> solutions (1,...) and per-source info."""
    p = InvertParam(mass=state["mass"], **state["config"]["invert_param"])
    x = api.invert_quda(_colour_vector(sources), p)
    return _embed(x), {"iters": [int(p.iter_count)],
                       "true_res": [float(p.true_res)],
                       "converged": [bool(p.converged)]}


def counters():
    out = single.counters()
    out["phase.ks_load"] = float(
        timer.get_profile(LOAD_PROFILE).seconds.get(LOAD_CATEGORY, 0.0))
    return out
