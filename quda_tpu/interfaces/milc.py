"""MILC-convention interface: the staggered/HISQ RHMC workflow entry points.

Reference behavior: lib/milc_interface.cpp (3284 LoC) /
include/quda_milc_interface.h — ~60 qudaXxx functions wrapping the C API
with MILC's conventions (mass instead of kappa, MILC site ordering, fat/
long link pairs, multi-shift rational fractions, fermion/gauge forces).

This module is the Python-level equivalent driving interfaces/quda_api;
MILC layout conventions match our canonical layout up to the phase
convention (MILC staggered phases are folded by the operator layer).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..utils import logging as qlog
from . import quda_api as api
from .params import GaugeParam, InvertParam


def qudaInit(verbosity: str = "summarize"):
    qlog.set_verbosity(verbosity)
    api.init_quda()


def qudaFinalize():
    api.end_quda()


def qudaLoadGauge(links, X, antiperiodic_t: bool = True, prec="double"):
    api.load_gauge_quda(links, GaugeParam(
        X=tuple(X), cuda_prec=prec,
        t_boundary="antiperiodic" if antiperiodic_t else "periodic"))


def qudaLoadKSLink(fat, long_links):
    """Load precomputed fat/long links (MILC supplies its own fattening)."""
    api.load_fat_long_quda(fat, long_links)


def qudaComputeKSLink(naik_eps: float = 0.0):
    """Fatten the resident thin links in-framework (computeKSLinkQuda)."""
    return api.compute_ks_link_quda(naik_eps)


def qudaInvert(mass: float, source, tol: float = 1e-10,
               maxiter: int = 10000, improved: bool = True,
               prec="double", sloppy_prec="single"):
    """qudaInvert: staggered/HISQ CG solve; returns (solution, info)."""
    p = InvertParam(
        dslash_type="hisq" if improved else "staggered",
        inv_type="cg", solve_type="normop-pc", mass=mass, tol=tol,
        maxiter=maxiter, cuda_prec=prec, cuda_prec_sloppy=sloppy_prec)
    x = api.invert_quda(source, p)
    return x, {"true_res": p.true_res, "iters": p.iter_count,
               "secs": p.secs}


def qudaMultishiftInvert(mass: float, offsets: Sequence[float], source,
                         tol: float = 1e-10, maxiter: int = 10000,
                         improved: bool = True, prec="double",
                         tol_offset: Sequence[float] = (),
                         info: Optional[dict] = None):
    """qudaMultishiftInvert: the RHMC rational-fraction solve
    ((4m^2 - D_eo D_oe) + offset_i) x_i = b.  ``tol_offset`` (MILC's
    per-shift ``target_residual[]``) is accepted where every entry
    equals ``tol``.  A dict given as ``info`` is filled with what
    MILC's ``final_residual[]`` carries: ``true_res_offset`` and
    ``iter_res_offset`` (per shift), ``converged_multi``, ``iters`` and
    ``iter_count_offset`` (the iterations each shift was updated in)."""
    p = InvertParam(
        dslash_type="hisq" if improved else "staggered",
        inv_type="multi-shift-cg", solve_type="normop-pc", mass=mass,
        tol=tol, maxiter=maxiter, cuda_prec=prec,
        num_offset=len(offsets), offset=tuple(offsets),
        tol_offset=tuple(tol_offset))
    xs = api.invert_multishift_quda(source, p)
    if info is not None:
        info.update(true_res_offset=list(p.true_res_offset),
                    iter_res_offset=list(p.iter_res_offset),
                    converged_multi=list(p.converged_multi),
                    iters=p.iter_count,
                    iter_count_offset=list(p.iter_count_offset))
    return xs


def qudaDslash(source, parity: int, mass: float = 0.0,
               improved: bool = True):
    p = InvertParam(dslash_type="hisq" if improved else "staggered",
                    mass=mass, solve_type="normop-pc")
    return api.dslash_quda(source, p, parity)


def qudaPlaquette():
    return api.plaq_quda()


def qudaGaugeForce(beta: float, c1: float = 0.0):
    return api.compute_gauge_force_quda(beta, c1)


def qudaUpdateU(mom=None, dt: float = 0.0):
    """mom=None uses the resident momentum (qudaMomLoad)."""
    if mom is None:
        if _milc["mom"] is None:
            qlog.errorq("qudaUpdateU with mom=None requires qudaMomLoad")
        mom = _milc["mom"]
    api.update_gauge_field_quda(mom, dt)


def qudaMomAction(mom) -> float:
    return api.mom_action_quda(mom)


def qudaHisqForce(mass: float, phi, n_cg_iters: int = 0,
                  tol: float = 1e-10, maxiter: int = 4000):
    """computeHISQForceQuda-class fermion force: d/dU of the HISQ
    pseudofermion action, with jax.grad differentiating through the full
    fattening chain (fat7 + reunitarisation + asqtad).

    n_cg_iters > 0 runs a truncated fixed-iteration force solve (the
    cheap inner-force evaluations MILC's integrators request); otherwise
    the solve converges to `tol`.
    """
    from ..gauge.fermion_force import pseudofermion_force
    from ..gauge.hisq import hisq_fattening
    from ..models.staggered import DiracStaggeredPC
    from ..solvers.cg import cg, cg_fixed_iters

    gauge = api._ctx["gauge"]
    geom = api._ctx["geom"]

    def make_op(u):
        links = hisq_fattening(u)
        return DiracStaggeredPC(links.fat, geom, mass, improved=True,
                                long_links=links.long).M

    op = make_op(gauge)
    if n_cg_iters > 0:
        x = cg_fixed_iters(op, phi, None, n_cg_iters)[0].x
    else:
        x = cg(op, phi, tol=tol, maxiter=maxiter).x

    # the staggered PC operator is already the normal operator
    return pseudofermion_force(make_op, gauge, x)


# ---------------------------------------------------------------------------
# Layout / parameter state (qudaSetLayout, qudaHisqParamsInit)
# ---------------------------------------------------------------------------

_milc = {
    "layout": None,          # (X, grid) from qudaSetLayout
    "hisq_params": {},       # qudaHisqParamsInit knobs
    "mom": None,             # resident momentum (qudaMomLoad/Save)
    "clover": None,          # resident clover blocks (qudaLoadCloverField)
    "two_link": None,        # resident two-link field (Gaussian smearing)
}


def qudaSetLayout(X, grid=(1, 1, 1, 1)):
    """qudaSetLayout (quda_milc_interface.h:164): record the local lattice
    and process grid; on TPU the mesh analog is parallel.mesh."""
    _milc["layout"] = (tuple(X), tuple(grid))


def qudaHisqParamsInit(reunit_allow_svd=True, reunit_svd_only=False,
                       reunit_svd_rel_error=1e-6, reunit_svd_abs_error=1e-6,
                       force_filter=5e-5):
    """qudaHisqParamsInit (quda_milc_interface.h:203): reunitarisation
    knobs — recorded for parity; the eigh-based unitarize_links needs no
    SVD fallback switches."""
    _milc["hisq_params"] = dict(
        reunit_allow_svd=reunit_allow_svd, reunit_svd_only=reunit_svd_only,
        reunit_svd_rel_error=reunit_svd_rel_error,
        reunit_svd_abs_error=reunit_svd_abs_error,
        force_filter=force_filter)


# ---------------------------------------------------------------------------
# Field residency (gauge/clover/momentum/two-link)
# ---------------------------------------------------------------------------

def qudaLoadGaugeField(links, X=None, prec="double"):
    """qudaLoadGaugeField: alias of qudaLoadGauge (resident gauge)."""
    if X is None:
        if _milc["layout"] is None:
            qlog.errorq("qudaLoadGaugeField without X requires "
                        "qudaSetLayout first")
        X = _milc["layout"][0]
    qudaLoadGauge(links, X, prec=prec)


def qudaFreeGaugeField():
    api.free_gauge_quda()


def qudaSaveGaugeField(path: str, precision: int = 64):
    """qudaSaveGaugeField: resident gauge -> SciDAC/ILDG lime file."""
    api.save_gauge_field_quda(path, precision=precision)


def qudaLoadUnitarizedLink(ulink):
    """qudaLoadUnitarizedLink: MILC supplies the unitarized W links (used
    as the fat links of the HISQ level-2 smearing input)."""
    api._set_resident_ks(jnp.asarray(ulink), api._ctx["long"])


def qudaFreeKSLink():
    api._set_resident_ks(None, None)


def qudaLoadCloverField(clover_blocks):
    """qudaLoadCloverField: resident chiral 6x6 clover blocks."""
    _milc["clover"] = jnp.asarray(clover_blocks)


def qudaFreeCloverField():
    _milc["clover"] = None


def qudaFreeTwoLink():
    _milc["two_link"] = None


def qudaMomLoad(mom):
    """qudaMomLoad (quda_milc_interface.h:898): resident momentum."""
    _milc["mom"] = jnp.asarray(mom)
    return _milc["mom"]


def qudaMomSave():
    """qudaMomSave: return the resident momentum to the host."""
    return _milc["mom"]


# ---------------------------------------------------------------------------
# Covariant shifts, spin-taste, rephase, reunitarise
# ---------------------------------------------------------------------------

def qudaShift(source, direction: int):
    """qudaShift (quda_milc_interface.h:256): one-hop covariant shift of a
    staggered color field; direction encodes mu (0-3 fwd, 7-mu back)."""
    from ..ops.shift import shift
    from ..ops.su3 import dagger
    g = api._ctx["gauge"]
    v = jnp.asarray(source)
    if direction < 4:
        return jnp.einsum("...ab,...b->...a", g[direction],
                          shift(v, direction, +1))
    mu = 7 - direction
    return jnp.einsum("...ab,...b->...a",
                      shift(dagger(g[mu]), mu, -1), shift(v, mu, -1))


def qudaSpinTaste(source, spin, taste):
    """qudaSpinTaste (quda_milc_interface.h:272): staggered spin-taste
    interpolator (ops/spin_taste.py)."""
    from ..ops.spin_taste import spin_taste_quda
    return spin_taste_quda(api._ctx["gauge"], jnp.asarray(source), spin,
                           taste)


def qudaRephase(phase_in: bool = True, antiperiodic_t: bool = True):
    """qudaRephase (quda_milc_interface.h:933): fold (or unfold — the
    phases are +-1, self-inverse) the MILC staggered phases into the
    resident gauge."""
    from ..ops.boundary import apply_staggered_phases
    g = apply_staggered_phases(api._ctx["gauge"], api._ctx["geom"],
                               antiperiodic_t)
    api._set_resident_gauge(g)


def qudaUnitarizeSU3():
    """qudaUnitarizeSU3 (quda_milc_interface.h:943): project the resident
    gauge back onto SU(3)."""
    from ..ops.su3 import project_su3
    api._set_resident_gauge(project_su3(api._ctx["gauge"]))


def qudaUpdateUPhased(mom=None, dt: float = 0.0,
                      phase_in: bool = False):
    """qudaUpdateUPhased (quda_milc_interface.h:875): evolve
    U <- exp(dt pi) U.  In the reference, phase_in says whether the
    HOST site-struct links arrive with the MILC staggered phases, which
    QUDA strips before updating and restores on save-out.  Here the
    resident gauge is always the canonical unphased field (phases are
    folded per-operator, see qudaComputeKSLink/qudaRephase), so the
    flag is accepted for source compatibility and the update acts
    directly — the same convention as qudaGaugeForcePhased /
    qudaGaugeMeasurementsPhased.  Argument order follows this module's
    qudaUpdateU(mom, dt) (the reference's precision/site-struct
    arguments do not exist here)."""
    del phase_in
    qudaUpdateU(mom, dt)


def qudaUpdateUPhasedPipeline(mom=None, dt: float = 0.0,
                              phase_in: bool = False,
                              want_gaugepipe: bool = False):
    """qudaUpdateUPhasedPipeline (quda_milc_interface.h:887):
    want_gaugepipe overlaps the gauge update with MILC's pipelined
    force accumulation on GPUs; under jit the whole update is one fused
    XLA program, so the flag is accepted and the phased update runs."""
    del want_gaugepipe
    qudaUpdateUPhased(mom, dt, phase_in)


def qudaGaugeFixingOVR(gauge_dirs: int = 4, max_iter: int = 1000,
                       tolerance: float = 1e-6, relax_boost: float = 1.5,
                       reunit_interval: int = 10):
    """qudaGaugeFixingOVR (quda_milc_interface.h:1157): overrelaxation
    Landau (gauge_dirs=4) / Coulomb (3) fixing of the resident gauge.
    MILC's relax_boost is the overrelaxation omega; reunit_interval maps
    to the convergence-check interval (reunitarisation is exact here)."""
    return api.compute_gauge_fixing_ovr_quda(
        gauge_dirs, max_iter=max_iter, tol=tolerance,
        omega=relax_boost, check_interval=reunit_interval)


def qudaGaugeFixingFFT(gauge_dirs: int = 4, max_iter: int = 1000,
                       tolerance: float = 1e-6, alpha: float = 0.08):
    """qudaGaugeFixingFFT (quda_milc_interface.h:1180):
    Fourier-accelerated fixing of the resident gauge."""
    return api.compute_gauge_fixing_fft_quda(
        gauge_dirs, max_iter=max_iter, tol=tolerance, alpha=alpha)


def qudaCreateGaugeField(gauge=None, geometry: int = 4,
                         precision: int = 2):
    """qudaCreateGaugeField (quda_milc_interface.h:1053): create a
    standalone DEVICE matrix-field handle (distinct from the resident
    gauge) from host data, or zeroed when gauge is None.  geometry:
    1 scalar, 4 vector, 6 tensor matrix fields per site."""
    if api._ctx["geom"] is None:
        qlog.errorq("qudaCreateGaugeField requires qudaLoadGauge/"
                    "qudaSetLayout first (lattice shape unknown)")
    dtype = jnp.complex128 if precision == 2 else jnp.complex64
    shape = (geometry,) + api._ctx["geom"].lattice_shape + (3, 3)
    if gauge is None:
        return jnp.zeros(shape, dtype)
    return jnp.asarray(gauge, dtype).reshape(shape)


def qudaDestroyGaugeField(gauge):
    """qudaDestroyGaugeField (quda_milc_interface.h:1070): destroy a
    STANDALONE device handle from qudaCreateGaugeField.  The resident
    gauge is untouched (use qudaFreeGaugeField for that); JAX arrays
    are runtime reference-counted, so dropping the reference is the
    whole job."""
    del gauge


def qudaAllocatePinned(nbytes: int):
    """qudaAllocatePinned (quda_milc_interface.h:176): host staging
    buffer.  No pinned memory exists on this runtime — a plain host
    buffer serves the same role (PJRT stages transfers itself)."""
    return np.zeros(int(nbytes), np.uint8)


def qudaAllocateManaged(nbytes: int):
    """qudaAllocateManaged (quda_milc_interface.h:189): as
    qudaAllocatePinned — no managed memory on this runtime."""
    return np.zeros(int(nbytes), np.uint8)


def qudaSetMPICommHandle(comm_handle=None):
    """qudaSetMPICommHandle (quda_milc_interface.h:150): adopt the
    host application's MPI communicator.  Process topology is owned by
    JAX distributed initialisation / PJRT on TPU; accepted for source
    compatibility."""
    del comm_handle


def qudaFreePinned(ptr=None):
    """qudaFreePinned (quda_milc_interface.h:182): pinned host staging
    buffers do not exist on this runtime (PJRT owns transfers); no-op
    for source compatibility."""
    del ptr


def qudaFreeManaged(ptr=None):
    """qudaFreeManaged (quda_milc_interface.h:195): managed memory does
    not exist on this runtime; no-op for source compatibility."""
    del ptr


# ---------------------------------------------------------------------------
# Solvers: DD / MG / multi-source / eigCG / clover family
# ---------------------------------------------------------------------------

def qudaDDInvert(mass: float, source, domain=(4, 4, 4, 4),
                 tol: float = 1e-10, maxiter: int = 10000,
                 improved: bool = True):
    """qudaDDInvert (quda_milc_interface.h:317): Schwarz domain-
    decomposition preconditioned GCR on the staggered operator."""
    from ..models.staggered import DiracStaggered
    from ..ops import staggered as sops
    from ..parallel.schwarz import additive_schwarz, make_domain_shift
    from ..solvers.gcr import gcr
    geom = api._ctx["geom"]
    fat = api._ctx["fat"] if improved else api._ctx["gauge"]
    lng = api._ctx["long"] if improved else None
    d = DiracStaggered(fat, geom, mass, improved, lng)
    dshift = make_domain_shift(geom, tuple(domain))
    local = lambda v: 2.0 * mass * v + sops.dslash_full(
        d.fat, v, d.long, shift_fn=dshift)
    res = gcr(d.M, jnp.asarray(source),
              precond=additive_schwarz(local), tol=tol,
              max_restarts=max(1, maxiter // 16))
    return res.x, {"iters": int(res.iters),
                   "converged": bool(res.converged)}


def qudaInvertMG(mass: float, source, tol: float = 1e-10,
                 improved: bool = True):
    """qudaInvertMG (quda_milc_interface.h:409): staggered MG solve."""
    from ..mg.mg import MGLevelParam, staggered_mg_solve
    from ..models.staggered import DiracStaggered
    geom = api._ctx["geom"]
    fat = api._ctx["fat"] if improved else api._ctx["gauge"]
    lng = api._ctx["long"] if improved else None
    d = DiracStaggered(fat, geom, mass, improved, lng)
    params = [MGLevelParam(block=(2, 2, 2, 2), n_vec=8, setup_iters=60,
                           post_smooth=8, smoother="ca-gcr",
                           coarse_solver_iters=16)]
    key = ("stag_mg", mass, improved, api._ctx["gauge_epoch"])
    mg = _milc.get("mg") if _milc.get("mg_key") == key else None
    res, mg = staggered_mg_solve(d, geom, jnp.asarray(source), params,
                                 tol=tol, mg=mg)
    _milc["mg"] = mg
    _milc["mg_key"] = key
    return res.x, {"iters": int(res.iters),
                   "converged": bool(res.converged)}


def qudaMultigridDestroy():
    _milc.pop("mg", None)
    api.destroy_multigrid_quda()


def qudaInvertMsrc(mass: float, sources, tol: float = 1e-10,
                   maxiter: int = 10000, improved: bool = True,
                   prec="double", sloppy_prec="single"):
    """qudaInvertMsrc (quda_milc_interface.h:443): one mass, num_src
    sources (leading axis) against the loaded links, through
    ``invert_multi_src_quda`` as ``qudaInvert`` goes through
    ``invert_quda``: in single precision on the packed route the batch
    solves on the resident KS term (``qudaLoadKSLink``).  Returns
    (solutions, info) with per-source iterations and verified
    residuals."""
    p = InvertParam(
        dslash_type="hisq" if improved else "staggered",
        inv_type="cg", solve_type="normop-pc", mass=mass, tol=tol,
        maxiter=maxiter, cuda_prec=prec, cuda_prec_sloppy=sloppy_prec)
    x = api.invert_multi_src_quda(sources, p)
    return x, {"iters": list(p.iter_count_multi),
               "true_res": list(p.true_res_multi), "secs": p.secs}


def qudaEigCGInvert(mass: float, source, n_ev: int = 8, m: int = 32,
                    tol: float = 1e-10, improved: bool = True):
    """qudaEigCGInvert (quda_milc_interface.h:526): eigCG with a resident
    deflation space accumulated across calls (incremental eigCG)."""
    from ..fields.spinor import even_odd_join, even_odd_split
    from ..models.staggered import DiracStaggeredPC
    from ..solvers.eigcg import IncrementalEigCG
    geom = api._ctx["geom"]
    fat = api._ctx["fat"] if improved else api._ctx["gauge"]
    lng = api._ctx["long"] if improved else None
    dpc = DiracStaggeredPC(fat, geom, mass, improved, lng)
    be, bo = even_odd_split(jnp.asarray(source), geom)
    rhs = dpc.prepare(be, bo)
    key = ("eigcg", mass, improved, api._ctx["gauge_epoch"])
    inc = _milc.get("eigcg")
    if inc is None or _milc.get("eigcg_key") != key:
        # operator changed (mass or resident gauge) — a stale deflation
        # space would solve the OLD system; rebuild (gauge-epoch guard,
        # same pattern as quda_api._solve_mg)
        inc = IncrementalEigCG(dpc.M, n_ev=n_ev, m=m)
        _milc["eigcg"] = inc
        _milc["eigcg_key"] = key
    res = inc.solve(rhs, tol=tol)
    xe, xo = dpc.reconstruct(res.x, be, bo)
    return even_odd_join(xe, xo, geom), {"iters": int(res.iters)}


def _clover_op(kappa: float, csw: float):
    """Full clover operator honoring qudaLoadCloverField residency: a
    loaded block field replaces the gauge-derived clover term."""
    from ..models.clover import DiracClover
    d = DiracClover(api._ctx["gauge"], api._ctx["geom"], kappa, csw)
    if _milc["clover"] is not None:
        d.clover = _milc["clover"]
    return d


def qudaCloverInvert(kappa: float, csw: float, source, tol: float = 1e-10,
                     maxiter: int = 10000, prec="double",
                     sloppy_prec="auto"):
    """qudaCloverInvert (quda_milc_interface.h:566).  Uses the loaded
    clover field (qudaLoadCloverField) when resident, else builds it
    from the resident gauge."""
    if _milc["clover"] is not None:
        from ..solvers.bicgstab import bicgstab
        d = _clover_op(kappa, csw)
        res = bicgstab(d.M, jnp.asarray(source), tol=tol, maxiter=maxiter)
        return res.x, {"true_res": float(jnp.sqrt(
            res.r2 / (jnp.sum(jnp.abs(jnp.asarray(source))**2) + 1e-300))),
            "iters": int(res.iters)}
    p = InvertParam(dslash_type="clover", kappa=kappa, csw=csw,
                    inv_type="bicgstab", solve_type="direct-pc", tol=tol,
                    maxiter=maxiter, cuda_prec=prec,
                    cuda_prec_sloppy=sloppy_prec)
    x = api.invert_quda(source, p)
    return x, {"true_res": p.true_res, "iters": p.iter_count}


def qudaCloverMultishiftInvert(kappa: float, csw: float, offsets, source,
                               tol: float = 1e-10, maxiter: int = 10000,
                               prec="double"):
    """qudaCloverMultishiftInvert (quda_milc_interface.h:711): the
    shifted solves (Mdag M + offset_i) x_i = Mdag b_p on the even-odd
    clover operator, through ``invert_multishift_quda`` as
    ``qudaMultishiftInvert`` goes: in single precision on the packed
    route the solve runs on the resident clover term.  Returns
    (solutions, info): ``iters``, and per shift ``true_res_offset``,
    ``iter_res_offset``, ``iter_count_offset`` and ``converged``."""
    p = InvertParam(
        dslash_type="clover", kappa=kappa, csw=csw,
        inv_type="multi-shift-cg", solve_type="normop-pc", tol=tol,
        maxiter=maxiter, cuda_prec=prec, num_offset=len(offsets),
        offset=tuple(offsets))
    xs = api.invert_multishift_quda(source, p)
    return xs, {"iters": p.iter_count,
                "true_res_offset": list(p.true_res_offset),
                "iter_res_offset": list(p.iter_res_offset),
                "iter_count_offset": list(p.iter_count_offset),
                "converged": list(p.converged_multi)}


def qudaEigCGCloverInvert(kappa: float, csw: float, source, n_ev: int = 8,
                          m: int = 32, tol: float = 1e-10):
    """qudaEigCGCloverInvert (quda_milc_interface.h:610)."""
    from ..fields.spinor import even_odd_join, even_odd_split
    from ..models.clover import DiracCloverPC
    from ..solvers.eigcg import IncrementalEigCG
    geom = api._ctx["geom"]
    d = DiracCloverPC(api._ctx["gauge"], geom, kappa, csw)
    be, bo = even_odd_split(jnp.asarray(source), geom)
    rhs = d.Mdag(d.prepare(be, bo))
    key = ("eigcg_clover", kappa, csw, api._ctx["gauge_epoch"])
    inc = _milc.get("eigcg_clover")
    if inc is None or _milc.get("eigcg_clover_key") != key:
        inc = IncrementalEigCG(lambda v: d.Mdag(d.M(v)), n_ev=n_ev, m=m)
        _milc["eigcg_clover"] = inc
        _milc["eigcg_clover_key"] = key
    res = inc.solve(rhs, tol=tol)
    xe, xo = d.reconstruct(res.x, be, bo)
    return even_odd_join(xe, xo, geom), {"iters": int(res.iters)}


# ---------------------------------------------------------------------------
# Phased gauge paths / observables
# ---------------------------------------------------------------------------

def qudaGaugeForcePhased(mom=None, input_path_buf=None, loop_coeff=None,
                         dt: float = 0.0):
    """qudaGaugeForcePhased (quda_milc_interface.h:786): path-table force
    on the (phase-folded) resident gauge.  With mom=None the RESIDENT
    momentum (qudaMomLoad) is updated in place and returned — the MILC
    residency pattern."""
    use_resident = mom is None
    if use_resident:
        if _milc["mom"] is None:
            qlog.errorq("qudaGaugeForcePhased with mom=None requires "
                        "qudaMomLoad first")
        mom = _milc["mom"]
    out = api.compute_gauge_force_paths_quda(mom, input_path_buf,
                                             loop_coeff, dt)
    if use_resident:
        _milc["mom"] = out
    return out


def qudaGaugeLoopTracePhased(paths, coeffs, factor: float = 1.0):
    """qudaGaugeLoopTracePhased (quda_milc_interface.h:805)."""
    return api.gauge_loop_trace_quda(paths, coeffs, factor)


def qudaPlaquettePhased():
    return api.plaq_quda()


def qudaPolyakovLoopPhased():
    """qudaPolyakovLoopPhased (quda_milc_interface.h:829)."""
    from ..gauge.observables import polyakov_loop
    return polyakov_loop(api._ctx["gauge"])


def qudaGaugeMeasurementsPhased():
    """qudaGaugeMeasurementsPhased (quda_milc_interface.h:850): plaquette,
    Polyakov loop, topological charge in one call."""
    from ..gauge.observables import polyakov_loop, qcharge
    g = api._ctx["gauge"]
    return {"plaquette": api.plaq_quda(),
            "polyakov": polyakov_loop(g),
            "qcharge": float(qcharge(g))}


# ---------------------------------------------------------------------------
# Clover force family / oprod / asqtad force / two-link smear
# ---------------------------------------------------------------------------

def qudaCloverForce(kappa: float, csw: float, phi, tol: float = 1e-10):
    """qudaCloverForce (quda_milc_interface.h:974): d/dU of the clover
    pseudofermion action — jax.grad differentiates through the clover
    term too (no separate cloverDerivative kernels)."""
    from ..gauge.fermion_force import pseudofermion_force
    from ..models.clover import DiracCloverPC
    from ..solvers.cg import cg
    gauge = api._ctx["gauge"]
    geom = api._ctx["geom"]

    def make_op(u):
        d = DiracCloverPC(u, geom, kappa, csw)
        return lambda v: d.Mdag(d.M(v))

    x = cg(make_op(gauge), jnp.asarray(phi), tol=tol, maxiter=4000).x
    return pseudofermion_force(make_op, gauge, x)


def qudaCloverTrace(kappa: float, csw: float):
    """qudaCloverTrace (quda_milc_interface.h:989): log det of the
    resident-gauge clover term per chirality."""
    from ..ops.clover import clover_blocks, clover_trlog
    blocks = (_milc["clover"] if _milc["clover"] is not None else
              clover_blocks(api._ctx["gauge"], kappa * csw / 2.0))
    return clover_trlog(blocks)


def qudaCloverDerivative(kappa: float, csw: float):
    """qudaCloverDerivative (quda_milc_interface.h:1009): su(3) force of
    the clover log-determinant (the det term of even-odd clover HMC) via
    AD instead of the oprod insertion kernels."""
    from ..gauge.action import gauge_force
    from ..ops.clover import clover_blocks, clover_trlog

    def act(u):
        blocks = clover_blocks(u, kappa * csw / 2.0)
        up, dn = clover_trlog(blocks)
        return -(up + dn).real

    return gauge_force(act, api._ctx["gauge"])


def qudaComputeOprod(quarks, coeffs):
    """qudaComputeOprod (quda_milc_interface.h:1158): per-direction
    outer products sum_i c_i x_i(x+mu) (x) x_i(x)^dag (1-hop) and the
    3-hop Naik variant — the force-insertion fields MILC accumulates."""
    from ..ops.shift import shift
    qs = jnp.asarray(quarks)  # (n, T,Z,Y,X, 3) color vectors
    one = []
    three = []
    for mu in range(4):
        o1 = sum(c * jnp.einsum("...a,...b->...ab",
                                shift(qs[i], mu, +1), jnp.conjugate(qs[i]))
                 for i, c in enumerate(coeffs))
        o3 = sum(c * jnp.einsum("...a,...b->...ab",
                                shift(qs[i], mu, +1, 3),
                                jnp.conjugate(qs[i]))
                 for i, c in enumerate(coeffs))
        one.append(o1)
        three.append(o3)
    return jnp.stack(one), jnp.stack(three)


def qudaAsqtadForce(mass: float, phi, tol: float = 1e-10):
    """qudaAsqtadForce (quda_milc_interface.h:1147): asqtad fermion force
    (fat7 + Naik chain, NO reunitarisation) via AD through the fattening."""
    from ..gauge.fermion_force import pseudofermion_force
    from ..gauge.hisq import ASQTAD_COEFFS, fat_links, naik_links
    from ..models.staggered import DiracStaggeredPC
    from ..solvers.cg import cg
    gauge = api._ctx["gauge"]
    geom = api._ctx["geom"]

    def make_op(u):
        fat = fat_links(u, ASQTAD_COEFFS)
        lng = ASQTAD_COEFFS.naik * naik_links(u)
        return DiracStaggeredPC(fat, geom, mass, improved=True,
                                long_links=lng).M

    x = cg(make_op(gauge), jnp.asarray(phi), tol=tol, maxiter=4000).x
    return pseudofermion_force(make_op, gauge, x)


def qudaTwoLinkGaussianSmear(source, width: float, n_steps: int):
    """qudaTwoLinkGaussianSmear (quda_milc_interface.h:1138): staggered
    Gaussian quark smearing with the doubled (two-link) gauge field."""
    from ..gauge.hisq import two_link
    from ..gauge.quark_smear import gaussian_smear
    epoch = api._ctx["gauge_epoch"]
    if _milc["two_link"] is None or _milc.get("two_link_epoch") != epoch:
        _milc["two_link"] = two_link(api._ctx["gauge"])
        _milc["two_link_epoch"] = epoch
    # color-vector field: add a unit spin axis for the smearing kernel
    v = jnp.asarray(source)
    had_spin = v.ndim >= 6
    if not had_spin:
        v = v[..., None, :]
    out = gaussian_smear(api._ctx["gauge"], v, width, n_steps,
                         two_link_gauge=_milc["two_link"])
    return out if had_spin else out[..., 0, :]


def qudaContractFT(x, y, momenta=None):
    """qudaContractFT (quda_milc_interface.h:1127): momentum-projected
    meson contractions."""
    return api.contract_quda(jnp.asarray(x), jnp.asarray(y),
                             contract_type="open", momenta=momenta)
