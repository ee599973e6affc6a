"""Entry point ``invert_quda`` on the Möbius domain-wall operator: ONE
4-d source and one 5-d solve a call against one loaded gauge field
(upstream's ``invert_test --dslash-type mobius --Lsdim 12``; the valence
propagator solve of a domain-wall campaign).  ``open`` is
``entry/invert_quda.open`` (init, ``load_gauge_quda``); ``call`` =
``invert_quda`` with the configuration's ``invert_param`` (Ls, b5, c5,
m5) and the traffic's ``mass`` (mf).

The harness's source is (1, T, Z, Y, X, 4, 3); the call's right-hand
side is its WALL SOURCE (Ls, T, Z, Y, X, 4, 3): the physical quark field
of the program's convention (``quda_tpu/ops/dwf.py``: chi(s) = P_-
psi(s+1) + P_+ psi(s-1), the -mf wrap coupling P_- psi(0) and P_+
psi(Ls-1)) is q = P_- psi(0) + P_+ psi(Ls-1), so a propagator's source
is P_+ b on s = 0 and P_- b on s = Ls - 1 (spin rows 0, 1 and 2, 3 in
the DeGrand-Rossi basis) and zero between them.  The API returns the
5-d solution (Ls, T, Z, Y, X, 4, 3); it comes back as ONE
(1, T, Z, Y, X, 4 Ls, 3) array whose row 4 s + spin is x(s)[spin], which
is what ``reference/mobius.py`` reads.  Source build and row embed are
one jitted program each and are inside the timed call, as in the HISQ
entries.  Interface as ``entry/invert_quda.py``.

A program whose Möbius pair operator is not a solve-program operand
solves this eagerly, from canonical arrays, with a hop-form race in
every process: it is not driven at all, the import fails at once.
"""

import functools

import jax
import jax.numpy as jnp

from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import InvertParam
from quda_tpu.models.domain_wall import DiracMobiusPCPairs
from . import invert_quda as single

if not hasattr(DiracMobiusPCPairs, "program_signature"):
    raise ImportError(
        "this program's DiracMobiusPCPairs is not a solve-program "
        "operand: its invert_quda(dslash_type='mobius') builds the "
        "operator from canonical arrays and re-traces the loop in every "
        "call, which is not the deployment this configuration measures")

counters = single.counters
close = single.close


def open(config, traffic, gauge):
    state = single.open(config, traffic, gauge)
    state["mass"] = float(traffic["mass"])
    return state


@functools.partial(jax.jit, static_argnames=("ls",))
def _wall_source(sources, ls):
    """(1,T,Z,Y,X,4,3) -> (Ls,T,Z,Y,X,4,3): spin rows 0, 1 of source 0
    on s = 0, rows 2, 3 on s = Ls - 1."""
    b = sources[0]
    zero = jnp.zeros_like(b[..., :2, :])
    walls = (jnp.concatenate([b[..., :2, :], zero], axis=-2),
             jnp.concatenate([zero, b[..., 2:, :]], axis=-2))
    between = jnp.zeros((ls - 2,) + b.shape, b.dtype)
    return jnp.concatenate([walls[0][None], between, walls[1][None]])


@jax.jit
def _embed(x5):
    """(Ls,T,Z,Y,X,4,3) -> (1,T,Z,Y,X,4 Ls,3): row 4 s + spin."""
    x = jnp.moveaxis(x5, 0, 4)                  # (T,Z,Y,X,Ls,4,3)
    return x.reshape(x.shape[:4] + (-1, 3))[None]


def call(state, sources):
    """sources (1,T,Z,Y,X,4,3) -> the 5-d solution as the rows of one
    (1,T,Z,Y,X,4 Ls,3) array and one entry of info for the call."""
    p = InvertParam(mass=state["mass"], **state["config"]["invert_param"])
    x5 = api.invert_quda(_wall_source(sources, int(p.Ls)), p)
    return _embed(x5), {"iters": [int(p.iter_count)],
                        "true_res": [float(p.true_res)],
                        "converged": [bool(p.converged)]}
