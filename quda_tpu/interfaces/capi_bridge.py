"""Python side of the C ABI: buffer-based wrappers over interfaces.quda_api.

Called by the embedded interpreter in interfaces/capi/quda_tpu_c.cpp.
All fields cross the boundary as raw double buffers (memoryviews over the
caller's memory — zero copy on the host side); layouts are documented in
quda_tpu.h and match utils/io.py's ILDG conventions for links.
"""

from __future__ import annotations

import os

import numpy as np

import jax

if os.environ.get("QUDA_TPU_FORCE_CPU"):
    jax.config.update("jax_platforms", "cpu")
# the C ABI speaks double; without x64 complex128 silently degrades to
# c64.  Only where double exists: on a TPU backend (jax_platforms unset
# is exactly the chip machine's case) x64 makes Mosaic refuse every
# pallas kernel (i64 index maps fail to legalise, the dslash lowering
# recurses out), so the default backend decides, not the config string.
if jax.default_backend() != "tpu":
    jax.config.update("jax_enable_x64", True)

from ..fields.geometry import LatticeGeometry
from . import quda_api as api
from .params import GaugeParam, InvertParam

_geom = None


def init():
    api.init_quda()
    return True


def end():
    api.end_quda()
    return True


def volume():
    return int(_geom.volume) if _geom else 0


def load_gauge(buf, X, antiperiodic_t):
    global _geom
    x, y, z, t = X
    _geom = LatticeGeometry((x, y, z, t))
    a = np.frombuffer(buf, dtype=np.float64)
    links = a.view(np.complex128).reshape(
        (4,) + _geom.lattice_shape + (3, 3))
    api.load_gauge_quda(links, GaugeParam(
        X=tuple(X),
        t_boundary="antiperiodic" if antiperiodic_t else "periodic"))
    return True


def plaq():
    return api.plaq_quda()


def invert(sol_buf, src_buf, dslash_type, inv_type, solve_type, kappa,
           mass, mu, csw, tol, maxiter):
    src = np.frombuffer(src_buf, dtype=np.float64).view(
        np.complex128).reshape(_geom.lattice_shape + (4, 3))
    p = InvertParam(dslash_type=dslash_type, inv_type=inv_type,
                    solve_type=solve_type, kappa=kappa, mass=mass, mu=mu,
                    csw=csw, tol=tol, maxiter=maxiter)
    x = api.invert_quda(src, p)
    out = np.frombuffer(sol_buf, dtype=np.float64)
    out.setflags(write=True)
    out_c = out.view(np.complex128).reshape(_geom.lattice_shape + (4, 3))
    np.copyto(out_c, np.asarray(x))
    return p.true_res, p.iter_count, p.secs
