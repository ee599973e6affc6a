"""Public entry points — the interface_quda.cpp analog.

Mirrors the C API surface (include/quda.h): init_quda / load_gauge_quda /
invert_quda / invert_multishift_quda / eigensolve_quda / dslash_quda /
mat_quda / plaq_quda / gauss_gauge_quda / perform_gauge_smear_quda /
perform_wflow_quda / compute_gauge_fixing_* / compute_ks_link_quda /
compute_gauge_force_quda / update_gauge_field_quda / mom_action_quda /
contract_quda, with resident-field state (make_resident_gauge) kept in a
module-level context the way interface_quda.cpp keeps gaugePrecise etc.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..fields.geometry import EVEN, ODD, LatticeGeometry
from ..fields.spinor import even_odd_join, even_odd_split
from ..ops import blas
from ..utils import logging as qlog
from ..utils.precision import complex_dtype
from .params import EigParamAPI, GaugeParam, InvertParam, MultigridParamAPI

_ctx = {
    "initialized": False,
    "geom": None,
    "gauge": None,          # resident gauge (4,T,Z,Y,X,3,3)
    "gauge_param": None,
    "fat": None,
    "long": None,
    "mg": None,
    "mg_epoch": -1,         # gauge_epoch the resident MG was built against
    "clover": None,         # resident clover term (load_clover_quda)
    "wilson": None,         # resident Wilson pair operators
    "ks": None,             # resident KS pair operators (fat + long)
    "mobius": None,         # resident Möbius pair operators
    "gauge_epoch": 0,       # bumped whenever the resident gauge changes
    "ks_epoch": 0,          # bumped whenever the fat / long links change
}


def init_quda(device: int = 0):
    """initQuda analog (device selection is PJRT's job on TPU)."""
    from ..obs import metrics as omet
    from ..obs import trace as otr
    from ..utils import config as qconf
    from ..utils import monitor as qmon
    from ..utils import tune as qtune
    qconf.check_environment()  # warn on typoed / CUDA-era env knobs
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()     # persistent XLA cache, placed from outside
    qmon.start_default()       # QUDA_TPU_ENABLE_MONITOR sampling thread
    otr.maybe_start()          # QUDA_TPU_TRACE span/event session
    omet.maybe_start()         # QUDA_TPU_METRICS counter/gauge registry
    from ..obs import build as obuild
    obuild.install()           # what jax builds, by program and span
    from ..obs import comms as ocomms
    ocomms.maybe_start()       # ICI comms ledger (rides both knobs)
    from ..obs import flight as ofl
    from ..obs import live as olive
    from ..obs import postmortem as opm
    ofl.maybe_start()          # QUDA_TPU_FLIGHT black-box ring buffer
    olive.maybe_start()        # QUDA_TPU_LIVE telemetry HTTP plane
    opm.reset_session()        # fresh postmortem bundle index
    # warm-start the chip-keyed tuner cache (tune.cpp persistent-cache
    # behavior): a fresh worker with a shared QUDA_TPU_RESOURCE_PATH
    # serves its first solve from already-raced (platform, volume,
    # form) winners — zero re-races, and the load is mirrored as a
    # tune_cache_loaded trace event (after maybe_start, so it lands in
    # the session)
    usable = qtune.warm_start()
    if usable:
        qlog.printq(f"tuner warm cache: {usable} entries usable on "
                    f"{qtune.platform_key()}", qlog.VERBOSE)
    _ctx["initialized"] = True
    qlog.printq("initialized", qlog.VERBOSE)


def _packed_enabled(on_tpu: bool) -> bool:
    """QUDA_TPU_PACKED override, else the platform default (packed
    device order on TPU)."""
    from ..utils import config as qconf
    v = qconf.get("QUDA_TPU_PACKED", fresh=True)
    return on_tpu if v == "" else v == "1"


def _pallas_enabled(on_tpu: bool) -> bool:
    """QUDA_TPU_PALLAS override, else pallas on real TPU."""
    from ..utils import config as qconf
    v = qconf.get("QUDA_TPU_PALLAS", fresh=True)
    return on_tpu if v == "" else v == "1"


def _pallas_interpret(on_tpu: bool) -> bool:
    """Interpret-mode pallas off-TPU: forcing QUDA_TPU_PALLAS=1 on a CPU
    host (CI, the kernel-in-solver routing tests) runs the SAME kernels
    through the pallas interpreter instead of failing to lower."""
    return not on_tpu


def end_quda():
    # gauge_epoch stays MONOTONE across re-initialisation: resident
    # caches elsewhere (interfaces/milc.py) key on it, and a reset would
    # let a post-reinit epoch collide with a pre-reset one, reviving
    # stale operators built against the old gauge.
    keep = {k: _ctx[k] for k in ("gauge_epoch", "ks_epoch")}
    for k in list(_ctx):
        _ctx[k] = None if k != "initialized" else False
    _ctx.update(keep)
    _ctx["mg_epoch"] = -1
    # shutdown telemetry flush (endQuda summary semantics): the timer
    # summary + profile.tsv, the tuner's profiler half (profile_0.tsv),
    # the roofline rows, the metrics export + fleet report, the flight
    # recorder's black-box tail, and the trace session artifacts.
    # Every step runs even when an earlier one raises (a broken
    # profile writer must not eat the trace of the crashed session it
    # would explain) — the first error is re-raised AFTER the epilogue
    # completes.  Everything flushed is indexed (name -> path + size +
    # the session knob snapshot) into artifacts_manifest.json — the
    # ONE file an operator or CI collects to find every artifact,
    # postmortem bundles included.
    from ..obs import comms as ocomms
    from ..obs import costmodel as ocost
    from ..obs import flight as ofl
    from ..obs import live as olive
    from ..obs import memory as omem
    from ..obs import metrics as omet
    from ..obs import postmortem as opm
    from ..obs import roofline as orf
    from ..obs import trace as otr
    from ..utils import monitor as qmon
    from ..utils import tune as qtune
    from ..utils.timer import print_summary

    artifacts: dict = {}

    def _flush_metrics():
        try:
            paths = omet.stop()
            if paths:
                artifacts["metrics.prom"] = paths["prom"]
                artifacts["metrics.tsv"] = paths["tsv"]
                artifacts["fleet_report.txt"] = paths["report"]
                qlog.printq(f"metrics artifacts: {paths['prom']} / "
                            f"{paths['report']}", qlog.SUMMARIZE)
        finally:
            # the ledger follows the resident fields _ctx drops — even
            # when the flush raised (unwritable path), or the next
            # session would report this one's fields as still resident
            omem.reset()

    def _flush_flight():
        # before the trace flush: a wrapped ring emits flight_dropped,
        # which must land in the trace artifact it explains
        paths = ofl.stop()
        if paths:
            artifacts["flight.jsonl"] = paths["flight"]
            qlog.printq(f"flight recorder: {paths['flight']} "
                        f"({paths['events']} events, "
                        f"{paths['dropped']} dropped)", qlog.SUMMARIZE)

    def _flush_trace():
        paths = otr.stop()
        if paths:
            artifacts["trace.json"] = paths["chrome"]
            artifacts["trace_events.jsonl"] = paths["jsonl"]
            qlog.printq(f"trace artifacts: {paths['chrome']} / "
                        f"{paths['jsonl']}", qlog.SUMMARIZE)

    def _save_tune_profile():
        artifacts["profile_0.tsv"] = qtune.save_profile()

    def _save_roofline():
        # dumps the ICI ledger rows alongside
        artifacts["roofline.tsv"] = orf.save()

    def _save_cost_report():
        # cost_drift.tsv for noted compiles
        artifacts["cost_drift.tsv"] = ocost.save_report()

    errors = []
    # olive.stop FIRST: the scrape plane reads every other leg's live
    # session — it must be down before those sessions close, or a
    # mid-teardown scrape races the flushes below
    for step in (olive.stop,
                 qmon.stop_default, print_summary, _save_tune_profile,
                 _save_roofline,
                 orf.reset,  # a later init/end must not re-dump rows
                 _save_cost_report,
                 ocost.reset,
                 ocomms.stop,    # ledger follows the session it served
                 _flush_metrics, _flush_flight, _flush_trace):
        try:
            step()
        except Exception as e:   # noqa: BLE001 — epilogue must finish
            errors.append(e)
    try:
        mpath = opm.write_artifacts_manifest(artifacts)
        if mpath:
            qlog.printq(f"artifacts manifest: {mpath}", qlog.SUMMARIZE)
    except Exception as e:       # noqa: BLE001 — epilogue must finish
        errors.append(e)
    opm.reset_session()
    if errors:
        raise errors[0]


def _require_init():
    if not _ctx["initialized"]:
        qlog.errorq("initQuda has not been called")


def _serve_rid_attrs() -> dict:
    """Request-id span/flight attributes when this API call executes a
    solve-service batch (obs/postmortem.serve_requests scope): the
    comma-joined ticket ids, {} outside the service so non-serve spans
    stay unchanged."""
    from ..obs import postmortem as opm
    rids = opm.current_request_ids()
    return {"request_ids": ",".join(rids)} if rids else {}


def _pm_api(api: str, payload: Optional[str] = None):
    """API-boundary postmortem guard (obs/postmortem.py).

    When failure capture is enabled, enters a solve scope carrying the
    caller's payload field (source/gauge), the param, and the knob
    snapshot as of API entry, and captures any uncaught exception
    crossing this boundary as an ``exception:<type>`` bundle before
    re-raising — unless a more specific trigger (breakdown, verify
    mismatch, gauge rejection, ladder exhaustion) already captured
    inside the call: one failure, one bundle.  Capture disabled = one
    knob read, then the undecorated call — no scope, no try frame
    semantics change, no bundle I/O (the raising-stub pin in
    tests/test_flight.py).  tests/test_flight_lint.py pins that every
    inverting entry point carries this guard and that its except-to-
    status site calls the capture hook."""
    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            from ..obs import postmortem as opm
            if not opm.enabled():
                return fn(*args, **kwargs)
            src = None
            if payload is not None:
                # positional or keyword spelling of the payload (the
                # entry points name it source / sources / gauge) — a
                # keyword-style call must still dump a replayable field
                src = args[0] if args else next(
                    (kwargs[k] for k in ("source", "sources", "gauge")
                     if k in kwargs), None)
            param = (args[1] if len(args) > 1 else
                     kwargs.get("param", kwargs.get("invert_param")))
            with opm.solve_scope(api, param=param, source=src,
                                 source_name=payload or "source"):
                try:
                    return fn(*args, **kwargs)
                except Exception as e:
                    opm.capture_exception(api, e)
                    raise
        return wrapper
    return deco


def _set_resident_gauge(g):
    """Every resident-gauge mutation goes through here so the MG
    staleness guard (gauge_epoch) can never miss one — and so the HBM
    ledger re-tracks the resident bytes on every mutation (smear, HMC
    update, gauss) with one row, not a leak."""
    _ctx["gauge"] = g
    _ctx["gauge_epoch"] += 1
    from ..obs import memory as omem
    omem.track("gauge", "resident_gauge", g)
    _drop_resident_terms()      # built from the links that just went


@_pm_api("load_gauge_quda", payload="gauge")
def load_gauge_quda(gauge, param: GaugeParam):
    """loadGaugeQuda: host layout (4,T,Z,Y,X,3,3) -> resident device gauge."""
    _require_init()
    param.validate()
    geom = LatticeGeometry(tuple(param.X))
    dtype = complex_dtype(param.cuda_prec)
    if param.gauge_order != "canonical":
        from ..utils import host_order as ho
        conv = {"qdp": ho.gauge_from_qdp, "milc": ho.gauge_from_milc}
        if param.gauge_order == "cps":
            gauge = ho.gauge_from_cps(gauge, geom, param.anisotropy)
        else:
            gauge = conv[param.gauge_order](gauge, geom)
    g = jnp.asarray(gauge, dtype)
    if g.shape != (4,) + geom.lattice_shape + (3, 3):
        qlog.errorq(f"gauge shape {g.shape} != expected for {param.X}")
    # gauge validation (robust/): a NaN link poisons every subsequent
    # solve on this configuration, so reject non-finite input LOUDLY at
    # the boundary; the fault site lets tests drill the rejection.
    # Runs BEFORE the anisotropy fold — the unitarity screen must see
    # the links as the user supplied them (folded spatial links are
    # legitimately non-unitary)
    from ..obs import trace as otr
    from ..robust import faultinject as finj
    g = finj.maybe_poison_gauge(g)
    if not bool(jnp.all(jnp.isfinite(g))):
        otr.event("gauge_rejected", cat="robust", reason="nonfinite",
                  X=list(param.X))
        # failure capture BEFORE the raise: the bundle dumps the gauge
        # AS REJECTED (fault-poisoned links included) so a replay of
        # the bundle reproduces the rejection from the dump alone
        from ..obs import postmortem as opm
        opm.capture("gauge_rejected", api="load_gauge_quda",
                    fields={"gauge": g},
                    note=f"non-finite links rejected at load, "
                         f"X={list(param.X)}")
        qlog.errorq(
            "load_gauge_quda: non-finite link values in the input "
            "gauge field — rejected (a NaN link silently poisons every "
            "subsequent solve); check the file/transfer and reload")
    from ..utils import config as qconf
    utol = float(qconf.get("QUDA_TPU_GAUGE_UNITARITY_TOL", fresh=True))
    if utol > 0.0:
        from ..ops.su3 import unitarity_deviation
        dev = float(unitarity_deviation(g))
        if dev > utol:
            otr.event("gauge_unitarity", cat="robust", deviation=dev,
                      tol=utol)
            qlog.warningq(
                f"load_gauge_quda: max unitarity deviation {dev:.2e} "
                f"exceeds QUDA_TPU_GAUGE_UNITARITY_TOL={utol:g}; "
                "repair with update_gauge_field_quda's reunitarize "
                "(ops.su3.project_su3) or reload a clean configuration")
    if param.anisotropy != 1.0:
        # QUDA folds the Wilson anisotropy into the links at load time:
        # spatial links are divided by xi (GaugeFieldParam anisotropy)
        scale = jnp.ones((4, 1, 1, 1, 1, 1, 1), g.real.dtype)
        scale = scale.at[:3].set(1.0 / param.anisotropy)
        g = g * scale.astype(dtype)
    _install_resident_gauge(g, param, geom)


def _install_resident_gauge(g, param: GaugeParam, geom: LatticeGeometry):
    """Install an ALREADY converted/validated device gauge as the
    resident one: geometry + param + epoch bump + ledger re-track —
    the residency-manager seam (serve/residency.py) that generalises
    the single ``_ctx['gauge']`` slot to multiple cached gauges
    without re-running load_gauge_quda's host-order conversion and
    input screens on every activation.  ``load_gauge_quda`` itself
    ends here, so single-slot callers (MILC interface included) see
    exactly the pre-round-15 behavior."""
    _ctx["geom"] = geom
    _set_resident_gauge(g)
    _ctx["gauge_param"] = param


def resident_gauge_state():
    """(gauge, gauge_param, geom) of the currently resident gauge —
    how serve/residency adopts a gauge just loaded through
    ``load_gauge_quda`` into its multi-gauge table."""
    return _ctx["gauge"], _ctx["gauge_param"], _ctx["geom"]


def resident_mg_state():
    """The resident MG hierarchy, or None when there is none or it was
    built for a gauge other than the resident one (stale hierarchies
    are never handed to the residency manager — they would be restored
    as 'valid' later).  The serve layer stashes this next to its cached
    gauge so a multi-tenant worker keeps one warm hierarchy PER gauge
    instead of rebuilding on every activation."""
    mg = _ctx.get("mg")
    if mg is None or _ctx.get("mg_epoch") != _ctx.get("gauge_epoch"):
        return None
    return mg


def _install_resident_mg(mg):
    """Adopt a hierarchy known to match the CURRENTLY resident gauge
    (the residency manager's table pairs them): epoch pinned to the
    live gauge epoch + ledger re-track — the MG sibling of
    ``_install_resident_gauge``.  ``mg=None`` clears the slot (the
    ledger row is the caller's to move)."""
    _ctx["mg"] = mg
    if mg is None:
        return
    _ctx["mg_epoch"] = _ctx["gauge_epoch"]
    from ..obs import memory as omem
    omem.track("mg", "hierarchy", mg)


def free_gauge_quda():
    _ctx["gauge"] = None
    from ..obs import memory as omem
    omem.release("gauge", "resident_gauge")
    _drop_resident_terms()      # or a later solve would run on them


def _antiperiodic():
    return _ctx["gauge_param"].t_boundary == "antiperiodic"


# -- what is built from the resident links and kept: the clover term --------
# -- (loadCloverQuda), the Wilson pair operators and the KS pair -------------
# -- operators of the fat and long links (load_fat_long_quda) ----------------

_CLOVER_FIELD = "resident_clover"     # their rows in the HBM ledger
_WILSON_FIELD = "resident_wilson"
_KS_FIELD = "resident_ks"
_MOBIUS_FIELD = "resident_mobius"
_RESIDENT_FIELDS = {"clover": _CLOVER_FIELD, "wilson": _WILSON_FIELD,
                    "ks": _KS_FIELD, "mobius": _MOBIUS_FIELD}


def _pair_store(prec: str):
    """Storage dtype of a pair operator at an API precision name."""
    return jnp.bfloat16 if prec in ("half", "quarter") else jnp.float32


def _drop_resident(family: str):
    """Forget the term kept under ``_ctx[family]`` and its ledger row."""
    if _ctx.get(family) is not None:
        _ctx[family] = None
        from ..obs import memory as omem
        omem.release(family, _RESIDENT_FIELDS[family])


def _drop_resident_terms():
    for family in _RESIDENT_FIELDS:
        _drop_resident(family)


def _resident_packed_links(antiperiodic: bool):
    """(even, odd) packed complex links of the resident gauge with the
    fermion boundary folded in: what the packed pair operators are
    assembled from."""
    from ..ops import wilson as wops
    from ..ops import wilson_packed as wpk
    from ..ops.boundary import apply_t_boundary
    geom = _ctx["geom"]
    return wpk.pack_gauge_eo(wops.split_gauge_eo(apply_t_boundary(
        _ctx["gauge"], geom, -1 if antiperiodic else 1), geom))


def _wilson_term_key(param: InvertParam, on_tpu: bool) -> tuple:
    """What the resident Wilson pair operators depend on: the gauge
    generation, matpc, the fermion boundary and the kernel route
    (pallas or not, interpreted or not, and what the hop set-up reads
    from the environment).  kappa is NOT part of it: it is a leaf of
    the operators."""
    from ..models.wilson import hop_route_knobs
    matpc = EVEN if param.matpc_type == "even-even" else ODD
    return (_ctx["gauge_epoch"], matpc, _antiperiodic(),
            _pallas_enabled(on_tpu), _pallas_interpret(on_tpu),
            hop_route_knobs())


def _resident_wilson(param: InvertParam, stores=()) -> dict:
    """The Wilson packed pair operators of (resident gauge, matpc,
    boundary, kernel route) at the storage dtypes ``stores`` (f32
    always), under kappa 0: callers take them ``with_kappa``.  The
    resident ones when their key matches (``reused``), else assembled
    from the resident gauge and kept in ``_ctx``: ``built`` when
    nothing was resident, ``rebuilt`` when matpc, boundary or route
    differ.  No canonical Dirac object is constructed."""
    from ..models.wilson import DiracWilsonPCPackedSloppy
    from ..obs import memory as omem
    from ..obs import metrics as omet
    from ..obs import trace as otr
    on_tpu = jax.default_backend() == "tpu"
    key = _wilson_term_key(param, on_tpu)
    term = _ctx.get("wilson")
    outcome = ("reused" if term is not None and term["key"] == key
               else "built" if term is None else "rebuilt")
    with otr.span("wilson_term", cat="setup", outcome=outcome):
        if outcome != "reused":
            _drop_resident("wilson")
            term = {"key": key, "ops": {}}
        missing = [st for st in dict.fromkeys(
            jnp.dtype(s) for s in (jnp.float32,) + tuple(stores))
            if st not in term["ops"]]
        if missing:
            _, matpc, ap, use_pallas, interpret, _ = key
            links = _resident_packed_links(ap)
            for st in missing:
                term["ops"][st] = DiracWilsonPCPackedSloppy.from_packed(
                    _ctx["geom"], links, 0.0, matpc, st,
                    use_pallas=use_pallas, pallas_interpret=interpret,
                    tb_sign=ap)
            _ctx["wilson"] = term
            omem.track("wilson", _WILSON_FIELD, term["ops"])
    omet.inc("wilson_term_total", outcome=outcome)
    return term


def _mobius_resident_route(param: InvertParam) -> bool:
    """Whether ``invert_quda`` solves this on ``_resident_mobius``:
    Möbius, CG on the normal equations of the 4d-PC system
    (``normop-pc``) on the packed pair representation at f32 or with
    bf16 sloppy storage.  Everything else of the family (5d-PC domain
    wall, EOFA, other solvers and solve types, an f64 solve) keeps the
    canonical classes and the eager loop."""
    on_tpu = jax.default_backend() == "tpu"
    return (param.dslash_type == "mobius" and param.inv_type == "cg"
            and param.solve_type == "normop-pc" and not param.num_offset
            and (param.cuda_prec == "single" or on_tpu)
            and _packed_enabled(on_tpu)
            and (on_tpu or _resolve_sloppy(param)
                 in ("single", "half", "quarter")))


def _mobius_term_keys(param: InvertParam, on_tpu: bool) -> tuple:
    """(what the resident Möbius operators' LINKS depend on, what
    their BLOCKS depend on): the gauge generation, matpc, the fermion
    boundary, Ls and the kernel route (pallas or not, interpreted or
    not, what the hop set-up reads from the environment and the hop
    form's knob); and (b5, c5, M5, mf), which a new value of rebuilds
    four (Ls, Ls) block pairs and nothing else."""
    from ..models.wilson import hop_route_knobs
    from ..utils import config as qconf
    matpc = EVEN if param.matpc_type == "even-even" else ODD
    return ((_ctx["gauge_epoch"], matpc, _antiperiodic(), int(param.Ls),
             _pallas_enabled(on_tpu), _pallas_interpret(on_tpu),
             hop_route_knobs(),
             str(qconf.get("QUDA_TPU_DWF_FORM", fresh=True))),
            (float(param.b5), float(param.c5), float(param.m5),
             float(param.mass)))


@partial(jax.jit, static_argnames=("geom", "static", "stores"))
def _mobius_term_program(gauge, blocks, geom, static, stores):
    """The resident Möbius pair operators of ``stores`` from the
    resident gauge, ONE program: boundary fold, even-odd split, packing,
    pairs at each storage dtype and the pre-shifted backward links.
    The operators are pytrees, so they are what it returns."""
    from ..models.domain_wall import DiracMobiusPCPairs
    from ..ops import wilson as wops
    from ..ops import wilson_packed as wpk
    from ..ops.boundary import apply_t_boundary
    matpc, ap, ls, use_pallas, interpret = static
    links = wpk.pack_gauge_eo(wops.split_gauge_eo(
        apply_t_boundary(gauge, geom, -1 if ap else 1), geom))
    return {st: DiracMobiusPCPairs.from_packed(
        geom, links, ls, blocks, matpc, st, use_pallas=use_pallas,
        pallas_interpret=interpret, tb_sign=ap) for st in stores}


def _resident_mobius(param: InvertParam, stores=()) -> dict:
    """The Möbius packed pair operators of (resident gauge, matpc,
    boundary, Ls, kernel route; b5, c5, M5, mf) at the storage dtypes
    ``stores`` (f32 always).  The resident ones when both keys match
    (``reused``); with the same links under other (b5, c5, M5, mf) the
    four block pairs are made anew on the host and the links stay
    (``rebuilt``); else everything is built from the resident gauge by
    one program (``built`` when nothing was resident, ``rebuilt``
    otherwise) and kept in ``_ctx``.  No canonical DiracMobius* is
    constructed and no hop form is raced
    (models/domain_wall.served_ls_hop_form)."""
    from ..models import domain_wall as mdw
    from ..obs import memory as omem
    from ..obs import metrics as omet
    from ..obs import trace as otr
    on_tpu = jax.default_backend() == "tpu"
    hop_key, block_key = _mobius_term_keys(param, on_tpu)
    term = _ctx.get("mobius")
    outcome = ("built" if term is None else "reused"
               if (term["key"], term["blocks"]) == (hop_key, block_key)
               else "rebuilt")
    with otr.span("mobius_term", cat="setup", outcome=outcome):
        stores = tuple(dict.fromkeys(
            jnp.dtype(s) for s in (jnp.float32,) + tuple(stores)))
        if outcome != "reused":
            b5, c5, m5, mf = block_key
            blocks = mdw.m5_block_pairs(hop_key[3], -m5, mf, b5, c5)
            if term is not None and term["key"] == hop_key:
                term = {"key": hop_key, "blocks": block_key, "sops": blocks,
                        "ops": {st: op.with_blocks(blocks)
                                for st, op in term["ops"].items()}}
            else:
                _drop_resident("mobius")
                term = {"key": hop_key, "blocks": block_key,
                        "sops": blocks, "ops": {}}
        missing = tuple(st for st in stores if st not in term["ops"])
        if missing:
            _, matpc, ap, ls, use_pallas, interpret, _, _ = hop_key
            built = _mobius_term_program(
                _ctx["gauge"], term["sops"], _ctx["geom"],
                (matpc, ap, ls, use_pallas, interpret), missing)
            term["ops"].update(jax.block_until_ready(built))
        if outcome != "reused" or missing:
            _ctx["mobius"] = term
            omem.track("mobius", _MOBIUS_FIELD, term["ops"])
    omet.inc("mobius_term_total", outcome=outcome)
    return term


def _set_resident_ks(fat, long_links):
    """Every change of the resident fat / long links goes through here:
    the pair operators built from the old ones no longer match their
    key (``rebuilt`` at their next use) and go at once where the links
    are freed."""
    from ..obs import memory as omem
    _ctx["fat"], _ctx["long"] = fat, long_links
    _ctx["ks_epoch"] += 1
    if fat is None or long_links is None:
        _drop_resident("ks")
    for field, g in (("fat_links", fat), ("long_links", long_links)):
        if g is None:
            omem.release("fat_naik", field)
        else:
            omem.track("fat_naik", field, g)


def _ks_links_loaded(param: InvertParam) -> bool:
    """An improved-staggered solve with both link fields resident: what
    the pair routes need to solve on ``_resident_staggered``."""
    return (param.dslash_type in ("asqtad", "hisq")
            and _ctx["fat"] is not None and _ctx["long"] is not None)


def _ks_term_key(param: InvertParam, on_tpu: bool) -> tuple:
    """What the resident KS pair operators depend on: the generation of
    the fat / long pair, matpc, the fermion boundary and the kernel
    route (pallas or not, interpreted or not: the operator's set-up
    decides its kernel forms from these, models/staggered.served_forms,
    and reads no knob).  The mass is NOT part of it: it is a leaf of
    the operators."""
    matpc = EVEN if param.matpc_type == "even-even" else ODD
    return (_ctx["ks_epoch"], matpc, _antiperiodic(),
            _pallas_enabled(on_tpu), _pallas_interpret(on_tpu))


def _resident_staggered(param: InvertParam, stores=()) -> dict:
    """The improved-staggered pair operators of (resident fat and long
    links, matpc, boundary, kernel route) at the storage dtypes
    ``stores`` (f32 always), under mass 0: callers take them
    ``with_mass``.  The resident ones when their key matches
    (``reused``), else built lattice-minor from the resident links
    (ops/staggered_packed.ks_links_eo_pairs: phases and boundary
    folded, even-odd split, pairs) and kept in ``_ctx``: ``built`` when
    nothing was resident, ``rebuilt`` when matpc, boundary or route
    differ.  No canonical DiracStaggered* is constructed.  Phases land
    on the ``load_fat_long_quda`` profile whoever calls."""
    from ..models.staggered import DiracStaggeredPCPairs
    from ..obs import memory as omem
    from ..obs import metrics as omet
    from ..obs import trace as otr
    from ..ops import staggered_packed as spk
    on_tpu = jax.default_backend() == "tpu"
    key = _ks_term_key(param, on_tpu)
    term = _ctx.get("ks")
    outcome = ("reused" if term is not None and term["key"] == key
               else "built" if term is None else "rebuilt")
    prof = "load_fat_long_quda"
    with otr.span("ks_term", cat="setup", outcome=outcome):
        if outcome != "reused":
            _drop_resident("ks")
            term = {"key": key, "ops": {}}
        missing = [st for st in dict.fromkeys(
            jnp.dtype(s) for s in (jnp.float32,) + tuple(stores))
            if st not in term["ops"]]
        if missing:
            _, matpc, ap, use_pallas, interpret = key
            geom = _ctx["geom"]
            dims = tuple(geom.lattice_shape)
            with otr.phase("fold_split", prof):
                fat, lng = jax.block_until_ready(tuple(
                    spk.ks_links_eo_pairs(_ctx[name], dims, ap, nhop)
                    for name, nhop in (("fat", 1), ("long", 3))))
            with otr.phase("pack", prof):
                for st in missing:
                    term["ops"][st] = DiracStaggeredPCPairs.from_packed(
                        geom, tuple(g.astype(st) for g in fat),
                        tuple(g.astype(st) for g in lng), 0.0, matpc,
                        st, use_pallas=use_pallas,
                        pallas_interpret=interpret)
                jax.block_until_ready([term["ops"][st] for st in missing])
            _ctx["ks"] = term
            omem.track("ks", _KS_FIELD, term["ops"])
    omet.inc("ks_term_total", outcome=outcome)
    return term


def _clover_term_key(param: InvertParam, on_tpu: bool) -> tuple:
    """What a resident clover term depends on: the gauge generation,
    kappa*csw and matpc (the blocks), the fermion boundary and the
    kernel route (the hop arrays the pair operators hold beside them).
    kappa alone is NOT part of it: it is a leaf of the operators."""
    matpc = EVEN if param.matpc_type == "even-even" else ODD
    return (_ctx["gauge_epoch"], float(param.kappa) * float(param.csw),
            matpc, _antiperiodic(), _pallas_enabled(on_tpu),
            _pallas_interpret(on_tpu))


def _clover_pair_ops(term: dict, stores, blocks=None) -> None:
    """Build the pair operators of ``stores`` (dtypes) a term lacks
    from the resident links and the packed complex blocks (A_p,
    A_q^-1): ``blocks`` at construction, later read back from the f32
    operator's pairs (exact)."""
    from ..models.clover import DiracCloverPCPairs
    from ..ops import wilson_packed as wpk
    missing = [st for st in dict.fromkeys(jnp.dtype(s) for s in stores)
               if st not in term["ops"]]
    if not missing:
        return
    _, _, matpc, ap, use_pallas, interpret = term["key"]
    geom = _ctx["geom"]
    if blocks is None:
        hi = term["ops"][jnp.dtype(jnp.float32)]
        blocks = tuple(wpk.from_packed_pairs(b) for b in
                       (hi.clover_p_pp, hi.clover_inv_q_pp))
    links = _resident_packed_links(ap)
    for st in missing:
        term["ops"][st] = DiracCloverPCPairs.from_packed(
            geom, links, 0.0, matpc, *blocks, st, use_pallas=use_pallas,
            pallas_interpret=interpret, tb_sign=ap)


def _resident_clover(param: InvertParam, stores) -> dict:
    """The clover term of (resident gauge, kappa*csw, matpc) with pair
    operators at the storage dtypes ``stores`` (f32 always): the
    resident one when its key matches (``reused``), else constructed
    lattice-minor (ops/clover_packed) from the PHYSICAL links, before
    the fermion boundary phase, and kept in ``_ctx``: ``built`` when
    nothing was resident, ``rebuilt`` when the coefficient, gauge or
    route differs.  Phases land on the ``load_clover_quda`` profile
    whoever calls."""
    from ..obs import memory as omem
    from ..obs import metrics as omet
    from ..obs import trace as otr
    from ..ops import clover_packed as cpk
    from ..ops import wilson_packed as wpk
    on_tpu = jax.default_backend() == "tpu"
    key = _clover_term_key(param, on_tpu)
    term = _ctx.get("clover")
    outcome = ("reused" if term is not None and term["key"] == key
               else "built" if term is None else "rebuilt")
    prof = "load_clover_quda"
    stores = (jnp.float32,) + tuple(stores)
    with otr.span("clover_term", cat="setup", outcome=outcome,
                  kappa_csw=key[1]):
        blocks = None
        if outcome != "reused":
            _drop_resident("clover")
            dims, p = _ctx["geom"].lattice_shape, key[2]
            wait = jax.block_until_ready
            with otr.phase("field_strength", prof):
                f = wait(cpk.field_strength_eo(_ctx["gauge"], dims))
            with otr.phase("blocks", prof):
                a = wait(tuple(cpk.clover_blocks_packed(fp, key[1] / 2.0)
                               for fp in f))
            del f
            with otr.phase("invert", prof):
                blocks = (a[p], wait(cpk.invert_blocks_packed(a[1 - p])))
            term = {"key": key, "ops": {},
                    "a_q_pp": wpk.to_packed_pairs(a[1 - p], jnp.float32)}
        with otr.phase("pack", prof):
            _clover_pair_ops(term, stores, blocks)
            jax.block_until_ready(
                ([jax.tree_util.tree_leaves(op)
                  for op in term["ops"].values()], term["a_q_pp"]))
        _ctx["clover"] = term
        omem.track("clover", _CLOVER_FIELD, term)
    omet.inc("clover_term_total", outcome=outcome)
    return term


def load_clover_quda(param: InvertParam):
    """loadCloverQuda with ``compute_clover`` and
    ``compute_clover_inverse``: the clover term A = 1 + (kappa csw / 2)
    sum sigma F and the inverse of the other parity, computed once from
    the resident gauge and kept on the device as the packed pair blocks
    of the solve operators (precise f32, and the sloppy storage
    ``param`` resolves to).  ``invert_quda`` with
    ``dslash_type="clover"`` uses them; a solve whose kappa*csw, matpc
    or gauge differs rebuilds; ``load_gauge_quda`` invalidates."""
    _require_init()
    param.validate()
    if _ctx["gauge"] is None:
        qlog.errorq("load_clover_quda: load_gauge_quda first")
    from ..obs import trace as otr
    with otr.api_span("load_clover_quda", csw=param.csw,
                      kappa=param.kappa):
        _resident_clover(param, (_pair_store(_resolve_sloppy(param)),))


def free_clover_quda():
    """freeCloverQuda: drop the resident clover term."""
    _drop_resident("clover")


def _build_dirac(p: InvertParam, pc: bool):
    from ..models import clover as mclover
    from ..models import domain_wall as mdw
    from ..models import staggered as mstag
    from ..models import twisted as mtw
    from ..models import wilson as mwil

    geom = _ctx["geom"]
    g = _ctx["gauge"]
    ap = _antiperiodic()
    matpc = EVEN if p.matpc_type == "even-even" else ODD
    t = p.dslash_type
    if t == "wilson":
        return (mwil.DiracWilsonPC(g, geom, p.kappa, ap, matpc) if pc
                else mwil.DiracWilson(g, geom, p.kappa, ap))
    if t == "clover":
        return (mclover.DiracCloverPC(g, geom, p.kappa, p.csw, ap, matpc)
                if pc else mclover.DiracClover(g, geom, p.kappa, p.csw, ap))
    if t == "twisted-mass":
        return (mtw.DiracTwistedMassPC(g, geom, p.kappa, p.mu, ap, matpc)
                if pc else mtw.DiracTwistedMass(g, geom, p.kappa, p.mu, ap))
    if t == "twisted-clover":
        return (mtw.DiracTwistedCloverPC(g, geom, p.kappa, p.mu, p.csw, ap,
                                         matpc) if pc
                else mtw.DiracTwistedClover(g, geom, p.kappa, p.mu, p.csw,
                                            ap))
    if t == "ndeg-twisted-mass":
        return (mtw.DiracNdegTwistedMassPC(g, geom, p.kappa, p.mu,
                                           p.epsilon, ap, matpc)
                if pc else
                mtw.DiracNdegTwistedMass(g, geom, p.kappa, p.mu, p.epsilon,
                                         ap))
    if t == "ndeg-twisted-clover":
        return (mtw.DiracNdegTwistedCloverPC(g, geom, p.kappa, p.mu,
                                             p.epsilon, p.csw, ap, matpc)
                if pc else
                mtw.DiracNdegTwistedClover(g, geom, p.kappa, p.mu,
                                           p.epsilon, p.csw, ap))
    if t in ("staggered", "asqtad", "hisq"):
        improved = t != "staggered"
        fat = _ctx["fat"] if improved else g
        lng = _ctx["long"] if improved else None
        if improved and fat is None:
            qlog.errorq("asqtad/hisq invert requires compute_ks_link_quda "
                        "or load_fat_long_quda first")
        return (mstag.DiracStaggeredPC(fat, geom, p.mass, improved, lng,
                                       matpc, antiperiodic_t=ap) if pc
                else mstag.DiracStaggered(fat, geom, p.mass, improved, lng,
                                          antiperiodic_t=ap))
    if t in ("domain-wall", "domain-wall-4d", "mobius"):
        b5, c5 = (1.0, 0.0) if t != "mobius" else (p.b5, p.c5)
        m5 = -p.m5  # QUDA passes m5 negative
        if pc:
            if t == "domain-wall":
                # QUDA convention: plain "domain-wall" preconditions with
                # the 5-d checkerboard (lib/dirac_domain_wall.cpp:124)
                return mdw.DiracDomainWall5DPC(g, geom, p.Ls, m5, p.mass,
                                               ap, matpc)
            return mdw.DiracMobiusPC(g, geom, p.Ls, m5, p.mass, b5, c5, ap,
                                     matpc)
        return mdw.DiracMobius(g, geom, p.Ls, m5, p.mass, b5, c5, ap)
    if t == "mobius-eofa":
        m5 = -p.m5
        kw = dict(mq1=p.eofa_mq1, mq2=p.eofa_mq2, mq3=p.eofa_mq3,
                  eofa_pm=p.eofa_pm, eofa_shift=p.eofa_shift)
        if pc:
            return mdw.DiracMobiusEofaPC(g, geom, p.Ls, m5, p.mass, p.b5,
                                         p.c5, antiperiodic_t=ap,
                                         matpc=matpc, **kw)
        return mdw.DiracMobiusEofa(g, geom, p.Ls, m5, p.mass, p.b5, p.c5,
                                   antiperiodic_t=ap, **kw)
    if t == "laplace":
        from ..ops.laplace import laplace

        class _Lap:
            def M(self, psi):
                return laplace(g, psi, ndim=p.laplace3D, mass=p.mass)

            Mdag = M

            def MdagM(self, psi):
                return self.M(self.M(psi))

        return _Lap()
    qlog.errorq(f"dslash_type {t} not wired into invert yet")


_DWF_TYPES = ("domain-wall", "domain-wall-4d", "mobius", "mobius-eofa")

# BiCGStab(L) ladder depth — ONE constant shared by the solver call and
# the flops accounting so the two can never desynchronise.
_BICGSTAB_L = 4


def _split(b, p, d=None):
    geom = _ctx["geom"]
    if d is not None and hasattr(d, "split5"):
        return d.split5(b)      # 5d checkerboard (slice-aligned layout)
    if p.dslash_type in _DWF_TYPES:
        be = jax.vmap(lambda v: even_odd_split(v, geom)[0])(b)
        bo = jax.vmap(lambda v: even_odd_split(v, geom)[1])(b)
        return be, bo
    return even_odd_split(b, geom)


def _join(xe, xo, p, d=None):
    geom = _ctx["geom"]
    if d is not None and hasattr(d, "join5"):
        return d.join5(xe, xo)
    if p.dslash_type in _DWF_TYPES:
        return jax.vmap(lambda e, o: even_odd_join(e, o, geom))(xe, xo)
    return even_odd_join(xe, xo, geom)


def _resolve_sloppy(param: InvertParam) -> str:
    """Resolve cuda_prec_sloppy="auto": bf16 ("half") on TPU — where
    "single/single" would never mix and the bf16 HBM/MXU path would go
    unused — and = cuda_prec elsewhere.  Any explicitly pinned value
    (including sloppy == prec for a pure-precision solve) is honored."""
    if param.cuda_prec_sloppy != "auto":
        return param.cuda_prec_sloppy
    from ..utils import config as qconf
    env = qconf.get("QUDA_TPU_SLOPPY_PRECISION", fresh=True)
    if env:
        qlog.printq(f"cuda_prec_sloppy=auto -> {env} "
                    "(QUDA_TPU_SLOPPY_PRECISION)", qlog.VERBOSE)
        return env
    if jax.default_backend() == "tpu":
        qlog.printq("cuda_prec_sloppy=auto -> half (bf16) on TPU",
                    qlog.VERBOSE)
        return "half"
    return param.cuda_prec


def _pair_refined_solve(mv, sys_rhs, dtype, param, inner_solver,
                        max_cycles: int = 10):
    """Shared defect-correction harness for the pair-sloppy bicgstab/gcr
    paths: run the sloppy inner solver per cycle, track TOTAL inner
    iterations (so param.iter_count/gflops reflect real work, not cycle
    count)."""
    from .. import solvers
    inner_iters = []

    def inner(r):
        ri = inner_solver(r)
        inner_iters.append(int(ri.iters))
        return ri.x

    res = solvers.solve_refined(mv, inner, sys_rhs, dtype, tol=param.tol,
                                max_cycles=max_cycles)
    return res._replace(iters=jnp.int32(sum(inner_iters)))


class _StaggeredPairsSolve:
    """Solve-loop adapter presenting DiracStaggeredPCPairs through the
    generic invert flow (prepare/M/reconstruct), so every Krylov iterate
    stays complex-free (pair representation), with the pallas eo stencil
    on real TPU.  The mixed-precision hooks (sloppy/codec) hand back a
    bf16 pair operator + plain-cast codec on the SAME layout."""

    hermitian = True

    def __init__(self, dpc, use_pallas: bool,
                 pallas_interpret: bool = False):
        self._dpc = dpc
        self._pallas_interpret = pallas_interpret
        self.op = dpc.pairs(jnp.float32, use_pallas=use_pallas,
                            pallas_interpret=pallas_interpret)

    def prepare(self, b_even, b_odd):
        return self.op.prepare_pairs(b_even, b_odd)

    def M(self, x_pp):
        return self.op.M_pairs(x_pp)

    Mdag = M

    def MdagM(self, x_pp):
        return self.op.M_pairs(self.op.M_pairs(x_pp))

    def reconstruct(self, x_pp, b_even, b_odd):
        return self.op.reconstruct_pairs(x_pp, b_even, b_odd)

    def sloppy(self, prec: str = "half"):
        return self._dpc.pairs(jnp.bfloat16,
                               use_pallas=self.op.use_pallas,
                               pallas_interpret=self._pallas_interpret)

    def codec(self, precise_dtype, store_dtype):
        from ..solvers.mixed import pair_inplace_codec
        return pair_inplace_codec(store_dtype)

    def flops_per_site_M(self) -> int:
        return getattr(self._dpc, "flops_per_site_M", lambda: 0)()


class _PairOpSolve(_StaggeredPairsSolve):
    """Solve-loop adapter presenting a non-Hermitian pair operator
    (DiracMobiusPCPairs incl. EOFA, DiracCloverPCPairs) through the
    generic invert flow.  Same shape as the staggered adapter (which it
    subclasses) except Mdag is the genuine adjoint and cg routes
    through the normal equations, whose coefficients are real (norms
    and real dots are representation-exact on pair arrays)."""

    hermitian = False

    def Mdag(self, x_pp):
        return self.op.Mdag_pairs(x_pp)

    def MdagM(self, x_pp):
        return self.op.MdagM_pairs(x_pp)

    def __getattr__(self, name):
        # 5d-PC split/join hooks pass through when the wrapped pair
        # operator provides them (hasattr stays False otherwise, so the
        # generic DWF vmap split applies to the 4d-PC families)
        if name in ("split5", "join5"):
            return getattr(self.op, name)
        raise AttributeError(name)


class _ResidentPairSolve(_PairOpSolve):
    """A solve on operators kept in ``_ctx`` (``_resident_clover``,
    ``_resident_wilson``): the pair operators are the resident ones
    under this call's kappa, nothing is built from a canonical
    operator."""

    def __init__(self, term: dict, kappa: float):
        self._term = term
        self._kappa = kappa
        self.op = term["ops"][jnp.dtype(jnp.float32)].with_kappa(kappa)

    def sloppy(self, prec: str = "half"):
        return self._term["ops"][jnp.dtype(_pair_store(prec))].with_kappa(
            self._kappa)


class _CloverResidentSolve(_ResidentPairSolve):
    """The clover solve on the resident term (``_resident_clover``)."""

    def full(self):
        """The full M = A - kappa D of the verified-exit check."""
        from ..models.clover import DiracCloverFullPairs
        return DiracCloverFullPairs(self.op, self._term["a_q_pp"])

    def with_full_diag(self):
        """``op`` with the term's A_q blocks beside its own: the
        operand of a verified exit that is a program
        (``DiracCloverPCPairs.verified_exit_pairs``)."""
        return self.op.with_full_diag(self._term["a_q_pp"])

    def flops_per_site_M(self) -> int:
        return 2 * 1320 + 2 * 504 + 48      # DiracCloverPC's count


class _WilsonPairsSolve(_ResidentPairSolve):
    """Pallas-dslash-in-solver routing for the Wilson PC family: the
    whole Krylov loop (prepare, MdagM, reconstruct) runs on the packed
    pair representation with the pallas eo stencil, so the kernel
    executes INSIDE the compiled solve (QUDA analog: the policy-tuned
    dslash inside the CG hot loop, lib/inv_cg_quda.cpp +
    dslash_policy.hpp).

    CG routes through the normal equations (coefficients real — exact
    on pairs), as _PairOpSolve; the mixed-precision hooks hand back the
    bf16 pair operator + the in-place pair codec on the SAME layout, so
    reliable updates stay complex-free too.

    The operators are the resident ones (``_resident_wilson``), and the
    verified exit runs on ``op`` itself (solvers/program.verified_exit):
    no canonical Wilson operator is built on this route."""

    def flops_per_site_M(self) -> int:
        return 2 * 1320 + 48                # DiracWilsonPC's count


class _StaggeredResidentSolve(_StaggeredPairsSolve):
    """The improved-staggered solve on the resident KS term
    (``_resident_staggered``): the pair operators are the resident ones
    under this call's mass; prepare, the Hermitian PC solve and the
    verified exit are cached programs on them (solvers/program.py), and
    no canonical staggered operator is built on this route."""

    def __init__(self, term: dict, mass: float):
        self._term = term
        self._mass = mass
        self.op = term["ops"][jnp.dtype(jnp.float32)].with_mass(mass)

    def prepare_source(self, b):
        """Parity split and ``prepare`` of the full-lattice source, one
        program."""
        from ..solvers import program as sprog
        return sprog.prepare(self.op, b)[0]

    def sloppy(self, prec: str = "half"):
        return self._term["ops"][jnp.dtype(_pair_store(prec))].with_mass(
            self._mass)

    def flops_per_site_M(self) -> int:
        return 2 * 1146 + 24                # DiracStaggeredPC's count


def _invert_wilson_df64(b, param: InvertParam, d, sloppy_prec: str,
                        on_tpu: bool, t0: float):
    """Deep-tolerance Wilson PC CG with a df64 (float32-pair) precise
    side — reaches 1e-10-class true residuals with no f64 and no complex
    execution (reference contract: fp64 matPrecise lib/inv_cg_quda.cpp:63
    + dbldbl reductions include/dbldbl.h; see ops/wilson_df64.py).

    Returns the f32-rounded solution; the lo word of the full-lattice
    solution is published as ``param.x_df64_lo`` (x + x_df64_lo is the
    full-precision solution — the analog of QUDA returning fp64 x)."""
    import numpy as np

    from .. import solvers
    from ..models.wilson import DiracWilsonPCPacked
    from ..obs import convergence as oconv
    from ..obs import trace as otr
    from ..ops import df64 as dfm
    from ..ops import wilson_df64 as wdf

    recording = otr.enabled()
    with otr.phase("setup", "invert_quda"):
        dpk = d if isinstance(d, DiracWilsonPCPacked) else d.packed()
        op = wdf.WilsonPCDF64(dpk)
        be, bo = _split(b, param)
        rhs_df = op.prepare_df(be, bo)

        # 'quarter' sloppy: int8 block-float LINKS under the df64
        # reliable-update correction (the QUDA quarter-precision gauge
        # bet — int8 mantissas + per-link f32 scales, decompressed at
        # link load; spinor iterates stay bf16, there is no int8 pair
        # codec).  The df64 precise side re-anchors the residual every
        # reliable-update cycle, so the quantisation error never
        # accumulates into the true residual (benched at 1e-10 —
        # tests/test_blockfloat.py acceptance drill).
        store = jnp.bfloat16 if sloppy_prec in ("half", "quarter") \
            else jnp.float32
        sl = dpk.pairs(store, use_pallas=_pallas_enabled(on_tpu),
                       pallas_interpret=_pallas_interpret(on_tpu),
                       precision_form=("int8"
                                       if sloppy_prec == "quarter"
                                       else None))
        codec = solvers.pair_inplace_codec(store)
    t_solve0 = time.perf_counter()
    with otr.phase("compute", "invert_quda"), \
            otr.span("solve:cg_reliable_df64", cat="solver",
                     tol=param.tol):
        res = solvers.cg_reliable_df(
            op, sl.MdagM_pairs, rhs_df, codec, tol=param.tol,
            maxiter=param.maxiter, delta=param.reliable_delta,
            record=recording)
    t_solve = time.perf_counter() - t_solve0

    xe_df, xo_df = op.reconstruct_df(res.x, be, bo)
    fr2 = float(dfm.to_f32(op.full_residual_norm2(xe_df, xo_df, be, bo)))
    b2 = float(blas.norm2_comp(b))
    param.true_res = float(np.sqrt(fr2 / b2))

    xe_hi, xe_lo = op.from_df(xe_df, b.dtype)
    xo_hi, xo_lo = op.from_df(xo_df, b.dtype)
    x_full = _join(xe_hi, xo_hi, param)
    param.x_df64_lo = _join(xe_lo, xo_lo, param)
    param.iter_count = int(res.iters)
    param.secs = time.perf_counter() - t0
    _record_solve_metrics("invert_quda", "wilson_df64",
                          "cg-reliable-df64", t_solve,
                          param.dslash_type, param.cuda_prec)
    flops = getattr(dpk, "flops_per_site_M", lambda: 0)()
    # PC operator: flops_per_site_M counts per UPDATED site, and a PC
    # operator updates one parity — volume/2 sites (see invert_quda's
    # accounting note)
    sites = _ctx["geom"].volume // 2
    param.gflops = (param.iter_count * 2.0 * flops * sites) / 1e9
    # param.true_res above is the df64 full-lattice residual — the
    # deepest-precision verification this route can state
    _solve_supervision(param, "invert_quda", res.converged,
                       getattr(res, "breakdown", None))
    if recording:
        # the recorded curve is the normal-equation residual and the
        # solver ships its own |Mdag b|^2 in the history dict, which
        # harvest prefers over this direct-system fallback
        b2_sys = float(dfm.to_f32(dfm.norm2(rhs_df)))
        oconv.publish(oconv.harvest("cg-reliable-df64", res,
                                    tol=param.tol, b2=b2_sys), param)
    qlog.printq(
        f"invert_quda[wilson/cg/df64]: {param.iter_count} iters, "
        f"true_res {param.true_res:.2e}, {param.secs:.2f} s")
    return x_full


def _solve_supervision(param, api: str, converged=None, breakdown=None,
                       converged_multi=None):
    """The verified-exit epilogue shared by every API solve.

    ALWAYS (robust on or off): maintain ``param.converged`` (and
    ``converged_multi``) from the solver's own convergence claim — a
    solve that exits at maxiter without meeting tol is flagged and
    warned about ONCE per (api, solver), never silently returned
    (reference: invert_test reports per-solve convergence; a serving
    fleet treats silence as success).  No new device ops: the flags are
    host conversions of results every solver already computes.

    With QUDA_TPU_ROBUST != off additionally record ``verified_res``
    (the caller has already recomputed param.true_res against the
    hi-precision reference operator at the API boundary — this is that
    number, plus the fault-injection seam) and classify
    ``solve_status``; breakdown/verification events land in the trace
    stream (breakdown_detected / verify_mismatch)."""
    import math

    import numpy as np

    from ..obs import metrics as omet
    from ..obs import trace as otr
    from ..robust import faultinject as finj
    from ..robust import sentinel as rsent
    from ..utils import config as qconf

    def _count_solve():
        # fleet solve accounting (metrics off -> single-global-load
        # no-ops): one solves_total increment per supervised attempt,
        # labeled by the FINAL status (solve_status when robust
        # classified the exit, the convergence claim otherwise)
        status = (getattr(param, "solve_status", None)
                  or ("converged" if param.converged else "unconverged"))
        omet.inc("solves_total", api=api, family=param.dslash_type,
                 status=status)
        omet.inc("solve_iterations_total",
                 float(getattr(param, "iter_count", 0) or 0),
                 api=api, family=param.dslash_type)

    if converged_multi is not None:
        param.converged_multi = [bool(c) for c in
                                 np.asarray(converged_multi).reshape(-1)]
        conv = all(param.converged_multi)
    else:
        conv = bool(np.asarray(jax.device_get(converged)).all())
    param.converged = conv
    bk = 0 if breakdown is None else int(np.asarray(breakdown))
    if not conv and not bk:
        qlog.warn_once(
            f"unconverged:{api}:{param.inv_type}",
            f"{api}[{param.dslash_type}/{param.inv_type}]: solve "
            f"exited without meeting tol {param.tol:g} (achieved "
            f"true_res {param.true_res:.2e}); InvertParam.converged="
            "False — further occurrences are flagged silently on the "
            "param")
    if not rsent.active():
        _count_solve()
        return
    vres = finj.inflated_residual(float(param.true_res))
    param.verified_res = vres
    margin = float(qconf.get("QUDA_TPU_ROBUST_VERIFY_MARGIN",
                             fresh=True))
    from ..obs import postmortem as opm
    if bk:
        param.solve_status = f"breakdown:{rsent.reason(bk)}"
        param.converged = False
        otr.event("breakdown_detected", cat="robust", api=api,
                  reason=rsent.reason(bk), solver=param.inv_type,
                  iters=param.iter_count)
        omet.inc("breakdowns_total", api=api, reason=rsent.reason(bk))
        # failure capture AFTER classification: the bundle records the
        # attempt param with its final solve_status, so a replay's
        # status comparison is against the classified exit
        opm.capture(f"breakdown:{rsent.reason(bk)}", api=api,
                    param=param)
        qlog.warn_once(
            f"breakdown:{api}:{rsent.reason(bk)}",
            f"{api}: breakdown sentinel tripped "
            f"({rsent.reason(bk)}) after {param.iter_count} "
            "iterations — clean exit, no NaN spin; see "
            "InvertParam.solve_status")
    elif not conv:
        param.solve_status = "unconverged"
    elif not (math.isfinite(vres) and vres <= margin * param.tol):
        param.solve_status = "unverified"
        param.converged = False
        otr.event("verify_mismatch", cat="robust", api=api,
                  verified_res=vres, tol=param.tol, margin=margin)
        opm.capture("verify_mismatch", api=api, param=param)
        qlog.warn_once(
            f"unverified:{api}",
            f"{api}: solver claimed convergence but the recomputed "
            f"true residual {vres:.2e} exceeds "
            f"{margin:g} * tol — status 'unverified'")
    else:
        param.solve_status = "converged"
    _count_solve()


def _solve_form(d) -> str:
    """Kernel-form label for roofline attribution (obs/roofline.py):
    conservative — only forms whose PERF.md traffic model provably
    matches the executing kernel get a specific label; everything else
    is 'generic' (flop attribution only, no bandwidth claim)."""
    op = getattr(d, "op", d)
    name = type(op).__name__.lower()
    if "wilson" in name and getattr(op, "use_pallas", False):
        # reconstruct-12 storage is visible in the resident link shape
        # (rows kept: 2 instead of 3 — models/wilson.to_recon12), which
        # is authoritative even if QUDA_TPU_RECONSTRUCT changed after
        # operator construction; it shrinks the gauge traffic the
        # roofline model charges, so the label must carry it
        gpp = getattr(op, "gauge_eo_pp", None)
        r12 = (gpp is not None and len(gpp) > 0
               and gpp[0].shape[1] == 2)
        suffix = "_r12" if r12 else ""
        if getattr(op, "_mesh", None) is not None:
            return f"wilson_sharded_v2{suffix}"
        # precision storage forms (PERF.md round 16) carry their own
        # traffic models; the label is read off the authoritative
        # operator attribute, with bf16 storage distinguished where the
        # tile economics differ (full-tile fold / bz=Z admission exist
        # BECAUSE of the bf16 (16,128) tile shape)
        form = getattr(op, "_precision_form", None)
        bf16 = (getattr(op, "store_dtype", None) is not None
                and jnp.dtype(op.store_dtype) == jnp.dtype(jnp.bfloat16))
        if form == "int8":
            return "wilson_v2_int8"
        if form == "r12f":
            return "wilson_v2_r12f"
        if form == "fold":
            return f"wilson_v2{'_bf16' if bf16 else ''}_fold"
        if form == "bzfull" and bf16:
            return "wilson_v2_bf16_bzfull"
        # f32 bzfull moves the same bytes as the baseline v2 block
        # schedule — same model row, no separate label
        return f"wilson_v2{suffix}"
    if "wilson" in name:
        return "wilson_xla"
    if "staggered" in name:
        # base traffic model keyed on the hop-set count: 'fat' = plain
        # staggered (one hop set), 'fat_naik' = improved (fat + Naik)
        base = ("fat_naik" if getattr(op, "long_eo_pp", None) is not None
                else "fat")
        if getattr(op, "use_pallas", False):
            form = getattr(op, "_pallas_form", None)
            if getattr(op, "_mesh", None) is not None:
                # mesh pins the two-pass interior today (see
                # models/staggered.py); the halo transport is
                # policy-dependent O(surface) and lives in the trace
                return f"staggered_sharded_{base}"
            if form == "v3":
                return f"staggered_{base}_v3"
            if form == "two_pass":
                # the PERF.md round-8 model name predates the form knob
                return ("staggered_fat_naik" if base == "fat_naik"
                        else "staggered_fat")
        return "staggered_xla"
    # operator zoo (PERF.md round 18).  The fused/staged split is read
    # off the authoritative construction-time attribute (_op_form,
    # models/formsel resolution); r12 off the resident link shape as in
    # the wilson branch.  Order matters: 'ndeg' before 'twisted'
    # (doublet classes contain 'twisted'), 'twisted' before 'clover'
    # (DiracTwistedCloverPCPairs contains both).
    gpp = getattr(op, "gauge_eo_pp", None)
    r12 = (gpp is not None and len(gpp) > 0 and gpp[0].shape[1] == 2)
    suffix = "_r12" if r12 else ""
    fused = getattr(op, "_op_form", None) == "pallas"
    if "ndeg" in name:
        # doublet operators keep the staged composition permanently
        # (flavor mixing is not an epilogue term) — flops-only label
        return "twisted_xla"
    if "twistedclover" in name:
        return (f"twisted_clover_pallas{suffix}" if fused
                else "twisted_clover_xla")
    if "twisted" in name:
        return (f"twisted_mass_pallas{suffix}" if fused
                else "twisted_xla")
    if "clover" in name:
        return f"clover_pallas{suffix}" if fused else "clover_xla"
    if "mobius" in name or "domainwall" in name:
        if fused:
            ls = getattr(op, "ls", None)
            # only Ls in {4, 8} carry traffic models (roofline.py);
            # other Ls report honest flops-only via 'dwf_pallas'
            return (f"dwf_ls{ls}_pallas" if ls in (4, 8)
                    else "dwf_pallas")
        return "dwf_xla"
    return "generic"


@_pm_api("invert_quda", payload="source")
def invert_quda(source, param: InvertParam):
    """invertQuda: solve M x = b per param; returns x, mutates param
    result fields (true_res, iter_count, secs, gflops, converged; with
    QUDA_TPU_TRACE also res_history/events — obs/convergence.py; with
    QUDA_TPU_ROBUST also verified_res/solve_status/solve_attempts —
    quda_tpu/robust)."""
    _require_init()
    param.validate()
    from ..obs import trace as otr
    from ..robust import escalate as resc
    with otr.api_span("invert_quda", dslash=param.dslash_type,
                      inv=param.inv_type, tol=param.tol,
                      **_serve_rid_attrs()), \
            _hbm_sampled("invert_quda"):
        if resc.enabled():
            # QUDA_TPU_ROBUST=escalate: drive the attempt through the
            # bounded retry ladder (robust/escalate.py) — breakdown,
            # verification mismatch, or operator-construction failure
            # escalates pallas -> XLA -> df64/BiCGStab
            return resc.run_ladder(_invert_quda_body, source, param,
                                   api="invert_quda")
        return _invert_quda_body(source, param)


import contextlib

# ledger families whose fields live only for the duration of one API
# call (eig workspaces handed to the caller at return) — released when
# the call exits so "resident now" stays honest while the family
# HIGH-WATER keeps the peak signal.  gauge/fat_naik/mg are genuinely
# resident (_ctx) and are NOT listed; of the clover family the resident
# term's row (load_clover_quda) stays and the rows of operators built
# per call (twisted clover, the canonical classes) go.
_TRANSIENT_FAMILIES = ("eig",)


@contextlib.contextmanager
def _hbm_sampled(api: str):
    """HBM sampling around an API solve (metrics-gated: zero work when
    QUDA_TPU_METRICS is off): all-local-device memory_stats snapshots
    on entry and exit feed the per-device gauges and the session
    high-water marks of the memory ledger (obs/memory.py).  Transient
    per-call ledger families are released on exit."""
    from ..obs import memory as omem
    from ..obs import metrics as omet
    if omet.enabled():
        omem.sample(f"{api}:enter")
    try:
        yield
    finally:
        for fam in _TRANSIENT_FAMILIES:
            omem.release_family(fam)
        omem.release_family("clover", keep=(_CLOVER_FIELD,))
        if omet.enabled():
            omem.sample(f"{api}:exit")


def _op_mesh(d):
    """The jax.sharding.Mesh a solve operator runs on, walked through
    the adapter wrappers (_WilsonPairsSolve and friends hold the pairs
    op on ``.op``); None for single-device operators.  Drives the
    per-device trace tracks and the ICI solve attribution."""
    seen = set()
    o = d
    while o is not None and id(o) not in seen:
        seen.add(id(o))
        m = getattr(o, "_mesh", None)
        if m is not None:
            return m
        o = getattr(o, "op", None) or getattr(o, "dirac", None)
    return None


def _record_solve_metrics(api: str, form: str, solver: str,
                          secs: float, family: str, prec: str):
    """The ONE home for per-route compile/execution accounting: first
    execution of a distinct (api, form, shape, prec, solver) key
    counts a compile (obs/metrics.record_execution), every execution
    lands a solve_seconds sample.  INVARIANT carried here so no route
    can drift: ``secs`` is the COMPUTE-PHASE time of the route (never
    the full API wall incl. setup), or cross-form histogram
    comparisons — the compile/race-storm instrument — are skewed.
    No-op when QUDA_TPU_METRICS is off."""
    from ..obs import metrics as omet
    if not omet.enabled():
        return
    geom = _ctx["geom"]
    shape = geom.lattice_shape if geom is not None else ()
    omet.record_execution(api, form, shape, prec, solver, secs)
    omet.observe("solve_seconds", secs, api=api, family=family)


def _note_solve_program(span, api: str, form: str, solver: str,
                        hit: bool):
    """A call went through a cached solve program (solvers/program.py):
    hit or miss onto the solve span and the ``solve_program_total``
    counter.  A miss's span also says what the build took: the seconds
    of the build records (obs/build.py) under ``span``, which the caller
    still holds open, in this call; the counter that owns those seconds
    is ``program_build_seconds{program, stage}``."""
    from ..obs import build as obuild
    from ..obs import metrics as omet
    if hit:
        span.set(program="hit")
    else:
        span.set(program="miss",
                 build_seconds=round(obuild.seconds_here(), 6))
    omet.record_solve_program(api, form, solver, "hit" if hit else "miss")


def _verified_exit(api: str, form: str, op, b, x_pp):
    """The verified exit of a resident pair route, one cached program on
    the resident f32 pair operator ``op`` (solvers/program.py): the
    canonical solution(s) and, on the host, the true residual(s) of
    what is returned.  The one host read ends the epilogue phase on the
    program's device time.  Hit or miss as ``_note_solve_program``."""
    import numpy as np

    from ..obs import trace as otr
    from ..solvers import program as sprog
    with otr.span("verified_exit", cat="epilogue") as span:
        (x_full, true_res), hit = sprog.verified_exit(op, b, x_pp)
        _note_solve_program(span, api, form, "verified-exit", hit)
        with otr.span("exit_read", cat="epilogue"):
            return x_full, np.asarray(true_res)


def _invert_mobius_resident(source, param: InvertParam):
    """The Möbius 4d-PC CG solve on the resident pair operators
    (``_resident_mobius``): entry (the 5-d source split by 4d parity,
    ``prepare`` and ``Mdag``), the reliable-update CG on the normal
    equations and the verified exit (reconstruction and the full 5-d
    residual of what is returned) are one cached program each
    (solvers/program.py), the links and the (Ls, Ls) blocks operands:
    every (mf, M5, b5, c5) of one Ls shares the executables.  The first
    trace of each stands on a stack chunk of its own (PERF.md section 7
    (22))."""
    from ..obs import convergence as oconv
    from ..obs import trace as otr
    from ..solvers import program as sprog
    from ..utils import timer as qtimer
    from ..utils.frames import on_a_stack_chunk_of_its_own as footed
    api, inv = "invert_quda", param.inv_type
    recording = otr.enabled()
    b = jnp.asarray(source, complex_dtype(param.cuda_prec))
    t0 = time.perf_counter()
    with otr.phase("setup", api):
        store = _pair_store(_resolve_sloppy(param))
        term = _resident_mobius(param, (store,))
        op = term["ops"][jnp.dtype(jnp.float32)]
        form = _solve_form(op)
        with otr.span("prepare", cat="setup") as span:
            rhs, hit = footed(lambda: sprog.prepare(op, b))
            _note_solve_program(span, api, form, "prepare", hit)
    t_solve0 = time.perf_counter()
    with otr.phase("compute", api), \
            otr.span(f"solve:{inv}", cat="solver", tol=param.tol,
                     maxiter=param.maxiter) as solve_span:
        with otr.span("dispatch", cat="solver"):
            res, hit = footed(lambda: sprog.cg_reliable(
                op, term["ops"][jnp.dtype(store)], rhs, tol=param.tol,
                maxiter=param.maxiter, delta=param.reliable_delta,
                record=recording))
        _note_solve_program(solve_span, api, form, inv, hit)
        with otr.span("wait", cat="solver"):
            jax.block_until_ready(res)
    t_solve = time.perf_counter() - t_solve0
    _record_solve_metrics(api, form, inv, t_solve, param.dslash_type,
                          param.cuda_prec)
    with otr.phase("epilogue", api):
        x_full, true_res = footed(
            lambda: _verified_exit(api, form, op, b, res.x))
        param.iter_count = int(res.iters)
        param.true_res = float(true_res)
        param.secs = time.perf_counter() - t0
        # DiracMobiusPC's count per updated site, two M an iteration
        flops = 2 * 1320 + 3 * 96 * op.ls
        sites = op.ls * _ctx["geom"].volume // 2
        param.gflops = param.iter_count * 2.0 * flops * sites / 1e9
        _solve_supervision(param, api, res.converged,
                           getattr(res, "breakdown", None))
    qtimer.add_flops(param.gflops * 1e9)
    if recording:
        oconv.publish(oconv.harvest(inv, res, tol=param.tol,
                                    b2=float(blas.norm2(rhs))), param)
    qlog.printq(
        f"invert_quda[{param.dslash_type}/{inv}]: {param.iter_count} "
        f"iters, true_res {param.true_res:.2e}, {param.secs:.2f} s")
    return x_full


def _clover_batch_route(param: InvertParam) -> bool:
    """Whether ``invert_multi_src_quda`` solves this batch on
    ``_resident_clover``: Wilson-clover, CG on the normal equations of
    the even-odd system (``normop-pc``) at tol >= 5e-8, on the packed
    pair representation in f32, the lanes independent.  Everything else
    of the family (other solvers, ``direct-pc``, true block CG, twisted
    mass and twisted clover) keeps the batched route that builds its
    operator per call; a deeper tolerance the per-source fallback."""
    from ..utils import config as qconf
    on_tpu = jax.default_backend() == "tpu"
    return (param.dslash_type == "clover" and param.inv_type == "cg"
            and param.solve_type == "normop-pc" and param.tol >= 5e-8
            and (param.cuda_prec == "single" or on_tpu)
            and _packed_enabled(on_tpu)
            and str(qconf.get("QUDA_TPU_MULTI_SRC_BLOCK",
                              fresh=True)) != "1")


def _invert_clover_batch_resident(B, param: InvertParam, t0: float):
    """The batched Wilson-clover CG solve on the resident clover term
    (``_resident_clover``: reused after ``load_clover_quda``, built on
    first use): entry (the parity split of the (N, T, Z, Y, X, 4, 3)
    batch, ``prepare`` and ``Mdag``), the pure-f32 batched CG on the
    normal equations and the verified exit (reconstruction and the full
    M = A - kappa D residual of every returned solution, one host read)
    are one cached program each (solvers/program.py), the links, the
    blocks of both parities and kappa operands: every kappa, csw and
    gauge of one (lattice, N) shares the executables, and no canonical
    DiracClover* is built.  The first trace of each stands on a stack
    chunk of its own (PERF.md section 7 (22))."""
    import numpy as np

    from ..obs import convergence as oconv
    from ..obs import trace as otr
    from ..solvers import program as sprog
    from ..utils.frames import on_a_stack_chunk_of_its_own as footed
    api, solver = "invert_multi_src_quda", "batched-cg-pairs"
    form_b = "clover_batched_pairs"     # the solve programs' label
    recording = otr.enabled()
    n_src = B.shape[0]
    with otr.phase("setup", api):
        d = _CloverResidentSolve(_resident_clover(param, ()), param.kappa)
        op = d.with_full_diag()
        with otr.span("prepare", cat="setup") as span:
            rhs, hit = footed(lambda: sprog.prepare(op, B))
            _note_solve_program(span, api, form_b, "prepare", hit)
    t_solve0 = time.perf_counter()
    with otr.phase("compute", api), \
            otr.span(f"solve:{solver}", cat="solver", nrhs=n_src,
                     tol=param.tol) as solve_span:
        with otr.span("dispatch", cat="solver"):
            res, hit = footed(lambda: sprog.batched_cg_pairs(
                op, rhs, tol=param.tol, maxiter=param.maxiter,
                record=recording))
        _note_solve_program(solve_span, api, form_b, solver, hit)
        with otr.span("wait", cat="solver"):
            iters = np.asarray(res.iters)
    t_solve = time.perf_counter() - t_solve0
    _record_solve_metrics(api, form_b, solver, t_solve, param.dslash_type,
                          param.cuda_prec)
    conv = np.asarray(res.converged)
    if not conv.all():
        qlog.warningq(
            f"invert_multi_src_quda: {int((~conv).sum())} of {n_src} "
            f"sources did not reach tol {param.tol:g} within "
            f"{param.maxiter} iterations; per-RHS true_res_multi holds "
            "the achieved residuals")
    with otr.phase("epilogue", api):
        x_full, true_res = footed(
            lambda: _verified_exit(api, form_b, op, B, res.x))
        param.iter_count_multi = [int(i) for i in iters]
        param.true_res_multi = [float(r) for r in true_res]
        param.iter_count = int(sum(param.iter_count_multi))
        # np.max propagates a NaN lane into the headline
        param.true_res = float(np.max(true_res))
        param.secs = time.perf_counter() - t0
        # per-RHS accounting as the other batched routes: each lane's
        # own converged count, two M an iteration
        param.gflops = (param.iter_count * 2.0 * d.flops_per_site_M()
                        * (_ctx["geom"].volume // 2)) / 1e9
        _solve_supervision(param, api,
                           breakdown=getattr(res, "breakdown", None),
                           converged_multi=conv)
    qlog.printq(
        f"invert_multi_src_quda[{param.dslash_type}/{param.inv_type}]: "
        f"{n_src} sources, iters {param.iter_count_multi}, worst "
        f"true_res {param.true_res:.2e}, {param.secs:.2f} s")
    if recording:
        from ..obs import roofline as orf
        from ..solvers.block import _per_rhs_dot
        oconv.publish(oconv.harvest(
            solver, res, tol=param.tol,
            b2=np.asarray(_per_rhs_dot(rhs, rhs))), param)
        orf.record(_clover_mrhs_roofline_form(op), _ctx["geom"].volume // 2,
                   float(np.max(iters)) * 2.0, t_solve, nrhs=n_src,
                   flops_per_site=d.flops_per_site_M(),
                   dslash_per_apply=2.0,
                   label=f"invert_multi_src_quda:{solver}")
    return x_full


def _clover_mrhs_roofline_form(op) -> str:
    """The roofline model (obs/roofline) of a clover batch: by the form
    the batched operator SERVES (``_mrhs_form``), which is not always
    the single-source ``_op_form``."""
    if not getattr(op, "use_pallas", False):
        return "generic"
    return ("clover_pallas_mrhs" if op._mrhs_form() == "pallas"
            else "clover_xla")


def _invert_quda_body(source, param: InvertParam):
    if _mobius_resident_route(param):
        return _invert_mobius_resident(source, param)
    from .. import solvers
    from ..obs import convergence as oconv
    from ..obs import trace as otr

    recording = otr.enabled()
    dtype = complex_dtype(param.cuda_prec)
    b = jnp.asarray(source, dtype)
    t0 = time.perf_counter()
    pc = param.solve_type.endswith("-pc")
    inv = param.inv_type
    with otr.phase("setup", "invert_quda"):
        # Mixed-precision gate (computed early: the layout choice below
        # must not apply to representation combinations it cannot serve).
        # QUDA threads matSloppy through every solver
        # (include/invert_quda.h:369); the TPU ladder
        # (utils/precision.py) has two genuinely distinct sloppy levels:
        # a lower complex dtype (double->single, CPU only) and bf16/int8
        # pair storage ("half"/"quarter" — ops/pair.py).
        sloppy_prec = _resolve_sloppy(param)
        on_tpu = jax.default_backend() == "tpu"
        # complex-free staggered pair adapter: CG-family solves only (its
        # coefficients are real on the Hermitian PC operator, so the pair
        # representation is exact; bicgstab/gcr would feed pair residuals
        # into the complex wrappers), and never silently degrade an f64
        # solve to the f32 pair representation (on TPU f64 does not
        # exist, so the adapter is the only executable path there)
        # shared pair-adapter gate: CG-family solves only (their
        # coefficients are real — exact on the pair representation),
        # never silently degrading an f64 solve to f32 pairs
        pairs_ok = (pc
                    and param.inv_type in ("cg", "pcg", "cg3", "cgne",
                                           "cgnr")
                    and (param.cuda_prec == "single" or on_tpu)
                    and _packed_enabled(on_tpu))
        stag_pairs = pairs_ok and param.dslash_type in ("staggered",
                                                        "asqtad", "hisq")
        # complex-free adapter for the non-Hermitian PC families (cg
        # routes through the normal equations, whose coefficients are
        # real)
        pair_op = pairs_ok and param.dslash_type in (
            "domain-wall", "domain-wall-4d", "mobius", "mobius-eofa",
            "clover", "twisted-mass", "twisted-clover",
            "ndeg-twisted-mass", "ndeg-twisted-clover")
        # pallas-dslash-in-solver routing for Wilson PC (QUDA_TPU_PALLAS
        # gates it on/off).  'quarter' keeps the canonical int8-codec
        # path.
        wil_pairs = (pairs_ok and param.dslash_type == "wilson"
                     and _pallas_enabled(on_tpu)
                     and sloppy_prec != "quarter")
        pair_sloppy = (sloppy_prec in ("half", "quarter")
                       and ((param.dslash_type == "wilson" and pc)
                            or stag_pairs or pair_op))
        dtype_sloppy = (sloppy_prec != param.cuda_prec
                        and complex_dtype(sloppy_prec) != complex_dtype(
                            param.cuda_prec))
        mixed = (param.inv_type == "cg" and (pair_sloppy or dtype_sloppy))
        # a canonical dtype-sloppy operator cannot consume pair iterates
        # (same exclusion as the wilson packed gate below)
        pair_excluded = mixed and dtype_sloppy and not pair_sloppy
        stag_pairs = stag_pairs and not pair_excluded
        pair_op = pair_op and not pair_excluded
        wil_pairs = wil_pairs and not pair_excluded

        # Extended-precision (df64) route: deep-tolerance Wilson CG where
        # no f64 backend serves (TPU always; CPU when the precise dtype
        # is f32).  The fp64-matPrecise + dbldbl-reduction analog
        # (lib/inv_cg_quda.cpp:63, include/dbldbl.h): precise side in
        # float32-pair arithmetic, sloppy loop unchanged.
        # QUDA_TPU_DF64: '' auto / '1' force / '0' off.
        from ..utils import config as qconf
        df64_mode = str(qconf.get("QUDA_TPU_DF64", fresh=True))
        # precision guard even when forced: the route certifies the
        # residual of the f32-valued system, so an f64 source (CPU double
        # path, which the native f64 solve already serves) must never be
        # silently rounded into a false 1e-10 certificate; packed opt-out
        # honored because the df64 stencil lives on the packed layout
        df64_able = (param.dslash_type == "wilson" and pc
                     and param.inv_type == "cg" and not param.num_offset
                     and (on_tpu or param.cuda_prec == "single")
                     and _packed_enabled(on_tpu))
        df64_route = df64_able and df64_mode != "0" and (
            df64_mode == "1" or param.tol < 5e-8)

        # the clover and Wilson pair routes solve on what is resident
        # (load_clover_quda / built on first use): no canonical operator
        # is constructed, for the solve or for the verified-exit check
        clover_resident = pair_op and param.dslash_type == "clover"
        wilson_resident = wil_pairs and not df64_route
        # so does the improved-staggered pair route, on the fat and
        # long links as loaded (load_fat_long_quda builds the term)
        ks_resident = stag_pairs and _ks_links_loaded(param)
        stores = (_pair_store(sloppy_prec),) if mixed else ()
        if ks_resident:
            d = _StaggeredResidentSolve(
                _resident_staggered(param, stores), param.mass)
            d_full = None
        elif clover_resident:
            d = _CloverResidentSolve(_resident_clover(param, stores),
                                     param.kappa)
            d_full = d.full()
        elif wilson_resident:
            # the hand-tuned eo kernel runs inside the compiled Krylov
            # loop (interpret-mode off TPU so the routing is testable
            # on CPU hosts); the verified exit is d.op's own program
            d = _WilsonPairsSolve(_resident_wilson(param, stores),
                                  param.kappa)
            d_full = None
        else:
            d = _build_dirac(param, pc)
            d_full = _build_dirac(param, False)

        # TPU-native packed device order for the Wilson PC solve path
        # (QUDA keeps solver fields in native FloatN order the same way);
        # default on TPU, opt-in/out anywhere via QUDA_TPU_PACKED=1/0.
        # Skipped for the dtype-sloppy mixed path (its canonical sloppy
        # operator cannot consume packed iterates) and for 'quarter'
        # (the int8 gauge codec lives on the canonical layout).
        if (param.dslash_type == "wilson" and pc
                and not wilson_resident
                and _packed_enabled(on_tpu)
                and not (mixed and dtype_sloppy and not pair_sloppy)
                and sloppy_prec != "quarter"):
            d = d.packed()

        if not df64_route:
            if stag_pairs and not ks_resident:
                # complex-free staggered solve loop (pair representation
                # end to end; the pallas eo stencil on real TPU).
                # 'quarter' storage has no staggered int8 codec — the
                # sloppy op falls back to bf16.
                d = _StaggeredPairsSolve(d, _pallas_enabled(on_tpu),
                                         _pallas_interpret(on_tpu))
            elif pair_op and not clover_resident:
                d = _PairOpSolve(d, _pallas_enabled(on_tpu),
                                 _pallas_interpret(on_tpu))

            if ks_resident:
                with otr.span("prepare", cat="setup"):
                    rhs = d.prepare_source(b)
            elif pc:
                with otr.span("source_split", cat="setup"):
                    be, bo = _split(b, param, d)
                with otr.span("prepare", cat="setup"):
                    rhs = d.prepare(be, bo)
            else:
                rhs = b

            normop = param.solve_type.startswith("normop")
            hermitian_pc = getattr(d, "hermitian", False)

            if param.num_offset:
                qlog.errorq("use invert_multishift_quda for shifted "
                            "solves")

            if hermitian_pc:   # staggered PC: already the normal operator
                mv = d.M
                sys_rhs = rhs
                back = lambda x: x
                mv_applies = 1.0
            elif normop:
                mv = lambda v: d.Mdag(d.M(v))
                with otr.span("mdag", cat="setup"):
                    sys_rhs = d.Mdag(rhs)
                back = lambda x: x
                mv_applies = 2.0
            else:
                mv = d.M
                sys_rhs = rhs
                back = lambda x: x
                mv_applies = 1.0

            if inv == "cg" and not (hermitian_pc or normop):
                # QUDA's solve-type matrix (lib/solve.cpp:180): CG +
                # direct solve is routed through the normal RESIDUAL
                # equations (CGNR).  Users wanting the normal-ERROR form
                # should pick inv_type="cgne".
                qlog.warningq("cg on a non-normal system; using CGNR "
                              "(normal-residual) semantics")
                mv = lambda v: d.Mdag(d.M(v))
                with otr.span("mdag", cat="setup"):
                    sys_rhs = d.Mdag(rhs)
                mv_applies = 2.0

            # direct-route solvers that internally apply the operator
            # more than once per counted iteration (cgne/cgnr compose
            # Mdag themselves, BiCGStab does two mat-vecs per iteration).
            # Hermitian-PC systems run these as plain one-apply CG — no
            # bump.  cg3's recursion is one apply per counted iteration.
            if (mv_applies == 1.0 and not hermitian_pc
                    and inv in ("cgne", "cgnr", "bicgstab")):
                mv_applies = 2.0
            # BiCGStab(L) needs NO bump: solvers/bicgstab.bicgstab_l
            # counts MATVEC APPLICATIONS as iterations (k += 2L per cycle
            # = exactly the 2L operator applies the cycle performs), so
            # each counted iteration is already one mv apply.  The old
            # flat 2.0 treated the count as cycles and over-reported its
            # gflops 2x; charging L+1 per counted iteration would
            # over-report (L+1)x.

    if df64_route:
        return _invert_wilson_df64(b, param, d, sloppy_prec, on_tpu, t0)

    t_solve0 = time.perf_counter()
    with otr.phase("compute", "invert_quda"), \
            otr.span(f"solve:{inv}", cat="solver", mesh=_op_mesh(d),
                     tol=param.tol, maxiter=param.maxiter) as solve_span:
        # keyword-only at the call site: four adjacent bools among 18
        # parameters — a positional transposition would type-check and
        # silently pick the wrong solve route
        with otr.span("dispatch", cat="solver"):
            res = _invert_dispatch(param=param, d=d, d_full=d_full, b=b,
                                   rhs=rhs, sys_rhs=sys_rhs, mv=mv,
                                   mv_applies=mv_applies, inv=inv,
                                   mixed=mixed, pair_sloppy=pair_sloppy,
                                   hermitian_pc=hermitian_pc,
                                   normop=normop, sloppy_prec=sloppy_prec,
                                   dtype=dtype, pc=pc, t0=t0,
                                   recording=recording,
                                   solve_span=solve_span)
        # the cached solve program returns at dispatch: wait here, so
        # the phase, t_solve and the span hold the solve's device time
        # and not the epilogue's first host read
        with otr.span("wait", cat="solver"):
            jax.block_until_ready(res)
    if not isinstance(res, tuple):
        return res             # gcr-mg handled everything itself
    res, publish_sys_rhs = res
    t_solve = time.perf_counter() - t_solve0

    # compile/executable-cache accounting: the first compute phase of a
    # distinct (form, shape, prec, solver) key paid the XLA compile
    # inside t_solve
    _record_solve_metrics("invert_quda", _solve_form(d), inv, t_solve,
                          param.dslash_type, param.cuda_prec)

    with otr.phase("epilogue", "invert_quda"):
        x_sys = back(res.x)
        if wilson_resident or ks_resident:
            x_full, true_res = _verified_exit(
                "invert_quda", _solve_form(d), d.op, b, x_sys)
        else:
            if pc:
                xe, xo = d.reconstruct(x_sys, be, bo)
                x_full = _join(xe, xo, param, d)
            else:
                x_full = x_sys
            r = b - d_full.M(x_full)
            true_res = jnp.sqrt(blas.norm2(r) / blas.norm2(b))

        param.iter_count = int(res.iters)
        param.true_res = float(true_res)
        param.secs = time.perf_counter() - t0
        flops = getattr(d, "flops_per_site_M", lambda: 0)()
        # GFLOPS convention: flops_per_site_M counts flops per site the
        # operator UPDATES, and an even/odd-preconditioned operator
        # updates one parity — volume/2 sites (the reference's
        # Dirac*PC::flops are per-parity counts, include/dslash.h:475).
        # Charging the FULL volume overstated every PC gflops ~2x
        # (round-5 logs predate this fix).  mv_applies follows the SOLVE
        # ROUTE (1 for direct/Hermitian-PC operators AND BiCGStab(L),
        # whose iteration counter already counts matvec applications;
        # 2 for normal-equation forms), set where mv is built.
        sites = _ctx["geom"].volume // 2 if pc else _ctx["geom"].volume
        param.gflops = (param.iter_count * mv_applies * flops
                        * sites) / 1e9
        # verified exit: param.true_res above IS the recomputed
        # full-lattice residual of x_full at the precise dtype (d_full.M,
        # or the pair program on the resident f32 operator) — the
        # supervision epilogue records it as verified_res and
        # classifies the exit (robust/), and ALWAYS maintains
        # param.converged + the one-time unconverged warning
        _solve_supervision(param, "invert_quda", res.converged,
                           getattr(res, "breakdown", None))

    from ..utils import timer as qtimer
    qtimer.add_flops(param.gflops * 1e9)
    if recording:
        # convergence history -> InvertParam.res_history/events + trace
        # residual events; roofline attribution of the compute phase
        rec = oconv.harvest(inv, res, tol=param.tol,
                            b2=float(blas.norm2(publish_sys_rhs)))
        oconv.publish(rec, param)
        from ..obs import roofline as orf
        # applies counts M applications; a PC M runs TWO dslash
        # invocations per apply, and the KERNEL_MODELS traffic side is
        # per invocation — dslash_per_apply keeps the BW column honest
        orf.record(_solve_form(d), sites,
                   param.iter_count * mv_applies, t_solve,
                   flops_per_site=flops,
                   dslash_per_apply=2.0 if pc else 1.0,
                   label=f"invert_quda:{param.dslash_type}/{inv}")
    from ..obs import comms as ocomms
    if ocomms.enabled() and _op_mesh(d) is not None:
        # ICI attribution: the comms ledger's per-invocation halo model
        # x this solve's measured applies, emitted as the roofline.tsv
        # sibling row.  Gated on the LEDGER (which rides either
        # trace or metrics knob), not on `recording` — a metrics-only
        # session must still see ici_bytes_total.  The site prefix
        # confines the model to this operator's family so another
        # form's stencils traced earlier in the session cannot leak in.
        form = _solve_form(d)
        ocomms.attribute_solve(
            form, param.iter_count * mv_applies, 2.0 if pc else 1.0,
            t_solve, label=f"invert_quda:{param.dslash_type}/{inv}",
            site_prefix=form.split("_")[0])
    qlog.printq(
        f"invert_quda[{param.dslash_type}/{inv}]: {param.iter_count} "
        f"iters, true_res {param.true_res:.2e}, {param.secs:.2f} s")
    return x_full


def _invert_dispatch(param, d, d_full, b, rhs, sys_rhs, mv, mv_applies,
                     inv, mixed, pair_sloppy, hermitian_pc, normop,
                     sloppy_prec, dtype, pc, t0, recording, solve_span):
    """The solver dispatch chain of invert_quda.  Returns
    ``(SolverResult, system_rhs_for_history)`` — or the finished
    solution array for the gcr-mg route, which completes its own
    epilogue/accounting."""
    from .. import solvers
    from ..solvers import program as sprog

    if mixed and inv == "cg":
        if pair_sloppy:
            sl = d.sloppy(sloppy_prec)
            if hasattr(d, "op") and sprog.presents(d.op, sl):
                # the loop traced once per process: the operators
                # present (registered pytrees with a program_signature:
                # the Wilson, clover and staggered packed pair operators
                # off a mesh); here mv IS d.op.MdagM_pairs (cg on a
                # non-Hermitian pair adapter always runs the normal
                # equations) or, where the operators are ``hermitian``,
                # d.op.M_pairs, and d.codec the in-place pair codec,
                # which the program rebuilds inside its trace
                res, hit = sprog.cg_reliable(
                    d.op, sl, sys_rhs, tol=param.tol,
                    maxiter=param.maxiter, delta=param.reliable_delta,
                    record=recording)
                _note_solve_program(solve_span, "invert_quda",
                                    _solve_form(d), inv, hit)
            else:
                # each operator representation (canonical / packed)
                # supplies the codec matching its sloppy storage
                # layout; the storage dtype comes from the BUILT sloppy
                # operator so the two can never desynchronise
                codec = (d.codec(dtype, sl.store_dtype)
                         if hasattr(d, "codec")
                         else solvers.pair_codec(sl.store_dtype, dtype))
                # staggered PC is already the (Hermitian) normal
                # operator
                mv_lo = sl.M_pairs if hermitian_pc else sl.MdagM_pairs
                res = solvers.cg_reliable(
                    mv, mv_lo, sys_rhs, tol=param.tol,
                    maxiter=param.maxiter, delta=param.reliable_delta,
                    codec=codec, record=recording)
        else:
            sl = _build_sloppy(param, pc, sloppy_prec)
            if hermitian_pc:
                mv_lo = sl.M
            else:
                mv_lo = lambda v: sl.Mdag(sl.M(v))
            res = solvers.cg_reliable(
                mv, mv_lo, sys_rhs, complex_dtype(sloppy_prec),
                tol=param.tol, maxiter=param.maxiter,
                delta=param.reliable_delta, record=recording)
    elif inv in ("cg", "pcg", "cg3"):
        fn = solvers.create(inv)
        kw = {"tol_hq": param.tol_hq} if inv == "cg" else {}
        if inv in ("cg", "pcg"):
            kw["record"] = recording
        res = fn(mv, sys_rhs, tol=param.tol, maxiter=param.maxiter, **kw)
    elif inv in ("cgne", "cgnr"):
        # explicit normal-error / normal-residual solves on the DIRECT
        # system (lib/solve.cpp CGNE/CGNR rows): cgne solves M Mdag y = b
        # then x = Mdag y (error-norm minimising); cgnr solves
        # Mdag M x = Mdag b (residual-norm minimising)
        if hermitian_pc:
            res = solvers.cg(d.M, rhs, tol=param.tol,
                             maxiter=param.maxiter, record=recording)
        else:
            fn = solvers.cgne if inv == "cgne" else solvers.cgnr
            res = fn(d.M, d.Mdag, rhs, tol=param.tol, maxiter=param.maxiter)
    elif inv == "bicgstab":
        if pair_sloppy:
            # defect-correction outer at precise, bf16-internal BiCGStab
            # inner (QUDA's sloppy-solve + reliable-residual pattern for
            # non-Hermitian systems).  The inner operator must match the
            # OUTER system: MdagM when solving the normal equations.
            sl = d.sloppy(sloppy_prec)
            mv_in = sl.MdagM if normop else sl.M
            res = _pair_refined_solve(
                mv, sys_rhs, dtype, param,
                jax.jit(lambda r: solvers.bicgstab(
                    mv_in, r, tol=1e-3, maxiter=param.maxiter)))
        else:
            res = solvers.bicgstab(mv, sys_rhs, tol=param.tol,
                                   maxiter=param.maxiter,
                                   record=recording)
    elif inv == "bicgstab-l":
        res = solvers.bicgstab_l(mv, sys_rhs, L=_BICGSTAB_L,
                                 tol=param.tol, maxiter=param.maxiter,
                                 record=recording)
    elif inv == "gcr":
        if pair_sloppy:
            sl = d.sloppy(sloppy_prec)
            mv_in = sl.MdagM if normop else sl.M
            # NOTE: gcr is a host-driven restart loop (it jits its own
            # cycles internally) — wrapping it in jax.jit would trace the
            # float() convergence checks.  The inner budget honors
            # param.maxiter across the refinement cycles.
            cycles = 10
            inner_budget = max(1, param.maxiter
                               // (cycles * param.gcrNkrylov))
            res = _pair_refined_solve(
                mv, sys_rhs, dtype, param,
                lambda r: solvers.gcr(
                    mv_in, r, tol=1e-3, nkrylov=param.gcrNkrylov,
                    max_restarts=inner_budget),
                max_cycles=cycles)
        else:
            res = solvers.gcr(mv, sys_rhs, tol=param.tol,
                              nkrylov=param.gcrNkrylov,
                              max_restarts=max(1, param.maxiter
                                               // param.gcrNkrylov))
    elif inv in ("ca-cg", "ca-gcr"):
        fn = solvers.create(inv)
        res = fn(mv, sys_rhs, tol=param.tol,
                 max_cycles=max(1, param.maxiter // 8))
    elif inv == "gcr-mg":
        t_mg0 = time.perf_counter()
        res, pair_true_res = _solve_mg(d_full, b, param)
        t_mg = time.perf_counter() - t_mg0
        x_full = res.x
        param.iter_count = int(res.iters)
        param.secs = time.perf_counter() - t0
        # this route returns before _invert_quda_body's shared
        # accounting call — record here or MG (the costliest compile in
        # the system) stays invisible to the compile/race-storm
        # instrument; t_mg is the setup+solve call only
        _record_solve_metrics("invert_quda", "gcr_mg", inv, t_mg,
                              param.dslash_type, param.cuda_prec)
        # fine-operator work only (V-cycle smoother/coarse flops not
        # charged — same convention as QUDA's outer-solver gflops)
        param.gflops = (param.iter_count
                        * getattr(d_full, "flops_per_site_M", lambda: 0)()
                        * _ctx["geom"].volume) / 1e9
        if pair_true_res is not None:
            # the pair route already measured it complex-free; re-deriving
            # it here with d_full.M would put a complex op on the device
            param.true_res = pair_true_res
        else:
            r = b - d_full.M(x_full)
            param.true_res = float(jnp.sqrt(blas.norm2(r) / blas.norm2(b)))
        _solve_supervision(param, "invert_quda", res.converged,
                           getattr(res, "breakdown", None))
        return x_full
    else:
        qlog.errorq(f"inv_type {inv} not wired")

    # the cgne/cgnr branch solves against the DIRECT rhs; everything
    # else iterated on sys_rhs — the history relres must normalise
    # against the system the recorded residuals belong to
    return res, (rhs if inv in ("cgne", "cgnr") else sys_rhs)


@_pm_api("invert_multi_src_quda", payload="source")
def invert_multi_src_quda(sources, param: InvertParam):
    """invertMultiSrcQuda analog: solve M x_i = b_i for a batch of
    sources (lib/interface_quda.cpp:3064 callMultiSrcQuda).

    sources: (n_src, T, Z, Y, X, 4, 3) host/device batch.  Returns the
    (n_src, ...) solution batch and mutates param: ``true_res_multi`` /
    ``iter_count_multi`` hold per-RHS results, ``iter_count`` their sum,
    and ``gflops`` charges each RHS its own converged iterations at the
    round-6 PC convention (flops per UPDATED site x volume/2).

    Routing (QUDA's split_key decision re-derived for one-process TPU):

    * >1 device and the batch divides the device count -> SPLIT GRID
      (parallel/split.py): sources sharded over the mesh src axis,
      gauge replicated, one independent PC solve per sub-grid.
    * otherwise, Wilson PC or staggered/HISQ PC + CG family on the
      packed representation -> the BATCHED PAIRS pipeline: every Krylov
      iterate is a packed pair batch ((n_src, 4, 3, 2, T, Z, Y*Xh)
      Wilson / (n_src, 3, 2, T, Z, Y*Xh) staggered) and the stencil is
      the MRHS pallas eo kernel (link tiles loaded once per
      (t, z-block), all RHS streamed through them) or its vmapped XLA
      form off-TPU.  The staggered PC operator is Hermitian, so its
      batch runs direct CG (one M per iteration); Wilson runs CGNR.
      QUDA_TPU_MULTI_SRC_BLOCK=1 swaps the independent per-RHS lanes
      for true block CG (shared Krylov space, real Gram matmuls).
    * anything else falls back to a per-source invert_quda loop (same
      results, no amortisation) so the entry point serves every
      operator the single-source API serves.

    QUDA_TPU_MULTI_SRC_SPLIT forces ('1') or forbids ('0') the
    split-grid route.
    """
    _require_init()
    param.validate()
    from ..obs import trace as otr
    from ..robust import escalate as resc
    with otr.api_span("invert_multi_src_quda", dslash=param.dslash_type,
                      inv=param.inv_type, n_src=len(sources),
                      **_serve_rid_attrs()), \
            _hbm_sampled("invert_multi_src_quda"):
        if resc.enabled():
            return resc.run_ladder(_invert_multi_src_body, sources,
                                   param, api="invert_multi_src_quda")
        return _invert_multi_src_body(sources, param)


def _invert_multi_src_body(sources, param: InvertParam):
    import numpy as np

    from ..obs import convergence as oconv
    from ..obs import trace as otr
    from ..utils import config as qconf
    from ..solvers.block import _check_nrhs

    recording = otr.enabled()
    dtype = complex_dtype(param.cuda_prec)
    B = jnp.asarray(sources, dtype)
    n_src = B.shape[0]
    _check_nrhs(n_src)
    t0 = time.perf_counter()
    pc = param.solve_type.endswith("-pc")
    on_tpu = jax.default_backend() == "tpu"
    geom = _ctx["geom"]

    if param.num_offset:
        qlog.errorq("invert_multi_src_quda does not serve multishift; "
                    "use invert_multishift_quda per source")

    cg_family = param.inv_type in ("cg", "pcg", "cgnr", "cgne")
    # f32 pair storage cannot certify tolerances below the f32 floor —
    # deep-tol batches take the per-source fallback, whose invert_quda
    # engages the df64 route (same 5e-8 threshold it uses)
    tol_ok = param.tol >= 5e-8
    stag_family = param.dslash_type in ("staggered", "asqtad", "hisq")
    # the batched pairs pipeline, decided against ``mesh is None`` at
    # the route decision below, AFTER the split-grid gate may have
    # released an unusable mesh back to this route: Wilson and the
    # improved-staggered family on their resident pair operators
    # (entry, solve and exit cached programs; plain staggered builds
    # its operator per call); clover CG on the resident clover term,
    # a function of its own (_invert_clover_batch_resident); what is
    # left of the Schur families (twisted mass, twisted clover, clover
    # under another solver) on a _SchurPairOpBase operator built per
    # call, eager entry and a canonical exit.  Doublet (ndeg) and DWF
    # operators stay per-source: the doublet flavor axis and the Ls
    # axis already occupy the batch dimension their kernels lead with.
    zoo_family = param.dslash_type in ("clover", "twisted-mass",
                                       "twisted-clover")
    batched_able = (pc
                    and (param.dslash_type == "wilson" or stag_family
                         or zoo_family)
                    and cg_family and tol_ok
                    and (param.cuda_prec == "single" or on_tpu)
                    and _packed_enabled(on_tpu))
    # per-UPDATED-site flops of one PC M apply (round-6 convention)
    if stag_family:
        flops_m = 2 * (1146 if param.dslash_type != "staggered"
                       else 570) + 24
    elif param.dslash_type in ("clover", "twisted-clover"):
        flops_m = 2 * 1320 + 2 * 504 + 48
    elif param.dslash_type == "twisted-mass":
        flops_m = 2 * 1320 + 192
    else:
        flops_m = 2 * 1320 + 48

    # split-vs-batched dispatch, resolved in its one home
    # (parallel/split.multi_src_route — the serve/ batcher consults the
    # same function to label coalesced batches with their route)
    from ..parallel.split import multi_src_route
    split_mode = str(qconf.get("QUDA_TPU_MULTI_SRC_SPLIT", fresh=True))
    try:
        route, mesh, split_gated = multi_src_route(
            n_src, split_mode=split_mode,
            split_gate=(pc and param.dslash_type == "wilson"
                        and cg_family and tol_ok),
            batched_gate=batched_able)
    except ValueError as e:
        qlog.errorq(str(e))

    def _finish(x_full, iters_rhs, res_rhs, mv_applies,
                converged_rhs=None, breakdown=None):
        import math
        param.iter_count_multi = [int(i) for i in iters_rhs]
        param.true_res_multi = [float(r) for r in res_rhs]
        param.iter_count = int(sum(param.iter_count_multi))
        # np.max propagates a NaN lane into the headline (python max
        # would silently skip it when NaN is not the last element)
        param.true_res = float(np.max(np.asarray(param.true_res_multi)))
        param.secs = time.perf_counter() - t0
        if converged_rhs is None:
            # the route surfaced no per-lane convergence claim: the
            # honest maxiter criterion (a lockstep solve that ran out
            # of budget did NOT converge), plus a finiteness screen on
            # the recomputed per-lane residual
            converged_rhs = [int(i) < param.maxiter
                             and math.isfinite(float(r))
                             for i, r in zip(iters_rhs, res_rhs)]
        # the per-RHS res_rhs above are recomputed with the full
        # hi-precision operator (d_chk.M) — the verified exit
        _solve_supervision(param, "invert_multi_src_quda",
                           breakdown=breakdown,
                           converged_multi=converged_rhs)
        flops = flops_m              # PC M cost (per updated site)
        sites = geom.volume // 2 if pc else geom.volume
        # per-RHS accounting, QUDA's per-source gflops convention.  The
        # batched route records each lane's OWN converged iteration
        # count (its extra lockstep applies past convergence are idle-
        # lane work, not charged); the split route's vmapped while_loop
        # runs every sub-grid to the slowest lane's stop, so its
        # per-RHS counts are the executed lockstep iterations — equal
        # across lanes by construction
        param.gflops = (param.iter_count * mv_applies * flops
                        * sites) / 1e9
        qlog.printq(
            f"invert_multi_src_quda[{param.dslash_type}/"
            f"{param.inv_type}]: {n_src} sources, "
            f"iters {param.iter_count_multi}, worst true_res "
            f"{param.true_res:.2e}, {param.secs:.2f} s")
        return x_full

    if split_gated:
        # a usable src mesh exists but this operator/solver/tolerance
        # is outside the split route's CG-family Wilson-PC gate: say so
        # (an env knob or auto decision must never lose effect without
        # a trace — the round-6 wilson.py notice rule) and fall through
        # to a route that honors the request
        qlog.printq(
            f"invert_multi_src_quda: split-grid route serves Wilson PC "
            f"CG-family solves at tol >= 5e-8 only; "
            f"{param.dslash_type}/{param.inv_type} (tol {param.tol:g}) "
            "falls back to the batched-pairs/per-source routes",
            qlog.SUMMARIZE)

    if route == "split":
        # split grid: shard sources over the src mesh axis, replicate
        # the gauge, one full PC solve per sub-grid.  Each sub-grid runs
        # the f32 PAIR operator on the packed layout (the XLA pair
        # stencil: vmappable, GSPMD-partitionable over src) — the
        # canonical complex operator's (...,3,3)/(...,4,3) einsum
        # temporaries tile-pad ~57x on a TPU, and at 24^4 the compiler
        # refused the solve loop outright (24.9 GB of 15.75 GB HBM on a
        # v5e, AOT-compiled for the described chip, PR 22); a single
        # device takes the batched pallas pair route instead
        from ..models.wilson import DiracWilsonPC
        from ..parallel.split import split_grid_solve
        from ..solvers.fused_iter import fused_cg
        ap = _antiperiodic()
        matpc = EVEN if param.matpc_type == "even-even" else ODD
        kappa, tol, maxiter = param.kappa, param.tol, param.maxiter

        def solve_one(g_raw, b):
            op = DiracWilsonPC(g_raw, geom, kappa, ap,
                               matpc).packed().pairs(jnp.float32)
            be, bo = even_odd_split(b, geom)
            rhs = op.prepare_pairs(be, bo)
            res = fused_cg(op.MdagM_pairs, op.Mdag_pairs(rhs), tol=tol,
                           maxiter=maxiter)
            # the pair-form verified exit in the lane's own trace, on
            # its own device: reconstruction, join and the true residual
            # of what is returned (no canonical full operator)
            x, true_res = op.verified_exit_pairs(b, res.x)
            # thread the solver's OWN convergence claim (and sentinel
            # code) out of the vmapped lane: the maxiter heuristic
            # cannot see a mid-solve breakdown exit, whose iters <
            # maxiter would otherwise read as converged
            return x, true_res, res.iters, res.converged, res.breakdown

        # pass the RAW resident gauge; each sub-grid folds the boundary
        # phase inside its own trace (DiracWilsonPC does it)
        t_solve0 = time.perf_counter()
        with otr.phase("compute", "invert_multi_src_quda", mesh=mesh,
                       route="split_grid"):
            x_full, res_rhs, iters, conv_l, bk_l = split_grid_solve(
                solve_one, _ctx["gauge"], B, mesh)
        _record_solve_metrics("invert_multi_src_quda",
                              "wilson_split_grid", param.inv_type,
                              time.perf_counter() - t_solve0,
                              param.dslash_type, param.cuda_prec)
        with otr.phase("epilogue", "invert_multi_src_quda"):
            res_rhs = np.asarray(res_rhs)
            bk = (None if bk_l is None
                  else int(np.max(np.asarray(bk_l))))
            return _finish(x_full, np.asarray(iters), res_rhs, 2.0,
                           converged_rhs=np.asarray(conv_l),
                           breakdown=bk)

    if route == "batched" and _clover_batch_route(param):
        return _invert_clover_batch_resident(B, param, t0)
    if route == "batched":
        from ..solvers import program as sprog
        from ..solvers.block import (_per_rhs_dot, batched_cg_pairs,
                                     block_cg_pairs)
        with otr.phase("setup", "invert_multi_src_quda"):
            # the improved-staggered batch solves on the resident KS
            # term, as invert_quda's ks_resident route
            ks_resident = _ks_links_loaded(param)
            if param.dslash_type == "wilson":
                # the resident f32 pair operator, as invert_quda's
                # wil_pairs route; it presents off a mesh, and then the
                # verified exit is its own program
                op = _WilsonPairsSolve(_resident_wilson(param),
                                       param.kappa).op
            elif ks_resident:
                op = _StaggeredResidentSolve(_resident_staggered(param),
                                             param.mass).op
            else:
                # plain staggered has no resident term: built per call,
                # on the gather form its MRHS kernel reads
                kw = ({"form": "two_pass"} if stag_family else {})
                op = _build_dirac(param, True).pairs(
                    jnp.float32, use_pallas=_pallas_enabled(on_tpu),
                    pallas_interpret=_pallas_interpret(on_tpu), **kw)
            form_b = ("staggered" if stag_family
                      else param.dslash_type.replace("-", "_")
                      if zoo_family else "wilson") + "_batched_pairs"
            pair_exit = ((param.dslash_type == "wilson" or ks_resident)
                         and sprog.presents(op))
            if ks_resident and pair_exit:
                # parity split and prepare of the batch: one program
                with otr.span("prepare", cat="setup") as span:
                    rhs_b, hit = sprog.prepare(op, B)
                    _note_solve_program(span, "invert_multi_src_quda",
                                        form_b, "prepare", hit)
            else:
                with otr.span("source_split", cat="setup"):
                    halves = [even_odd_split(B[i], geom)
                              for i in range(n_src)]
                    be = jnp.stack([h[0] for h in halves])
                    bo = jnp.stack([h[1] for h in halves])
                    del halves
                with otr.span("prepare", cat="setup"):
                    rhs_b = op.prepare_pairs_mrhs(be, bo)
                if pair_exit:
                    del be, bo      # the verified exit splits B itself
            if stag_family:
                # the staggered PC operator is already the (Hermitian
                # positive definite) normal operator — the batched CG
                # runs it directly, one M apply per counted iteration
                nrm_b = rhs_b
                mv_b = op.M_pairs_mrhs
                mv_applies = 1.0
            else:
                # CGNR on the batched normal equations (coefficients
                # real — exact on pairs; same route as the
                # single-source wil_pairs cg)
                with otr.span("mdag", cat="setup"):
                    nrm_b = op.Mdag_pairs_mrhs(rhs_b)
                mv_b = op.MdagM_pairs_mrhs
                mv_applies = 2.0
            use_block = str(qconf.get("QUDA_TPU_MULTI_SRC_BLOCK",
                                      fresh=True)) == "1"
        solver_name = "block-cg-pairs" if use_block else \
            "batched-cg-pairs"
        t_solve0 = time.perf_counter()
        with otr.phase("compute", "invert_multi_src_quda"), \
                otr.span(f"solve:{solver_name}", cat="solver",
                         nrhs=n_src, tol=param.tol) as solve_span:
            if use_block:
                res = block_cg_pairs(mv_b, nrm_b,
                                     tol=param.tol,
                                     maxiter=param.maxiter,
                                     record=recording)
                iters_rhs = np.full(n_src, int(res.iters))
            elif sprog.presents(op):
                # the loop traced once per process: the program applies
                # what mv_b is, the operator's MdagM_pairs_mrhs, or its
                # M_pairs_mrhs where it is Hermitian (staggered), or the
                # operator's own *_cg_step_pairs_mrhs where it has one
                # (Wilson: pAp, the new r and |r|^2 out of the kernels)
                with otr.span("dispatch", cat="solver"):
                    res, hit = sprog.batched_cg_pairs(
                        op, nrm_b, tol=param.tol, maxiter=param.maxiter,
                        record=recording)
                _note_solve_program(solve_span, "invert_multi_src_quda",
                                    form_b, solver_name, hit)
                # the first host read of the program's result: the wait
                # for its device time
                with otr.span("wait", cat="solver"):
                    iters_rhs = np.asarray(res.iters)
            else:
                res = batched_cg_pairs(mv_b, nrm_b,
                                       tol=param.tol,
                                       maxiter=param.maxiter,
                                       record=recording)
                iters_rhs = np.asarray(res.iters)
        t_solve = time.perf_counter() - t_solve0
        _record_solve_metrics(
            "invert_multi_src_quda", form_b,
            solver_name, t_solve, param.dslash_type, param.cuda_prec)
        conv = np.asarray(res.converged)
        if not conv.all():
            qlog.warningq(
                f"invert_multi_src_quda: {int((~conv).sum())} of "
                f"{n_src} sources did not reach tol {param.tol:g} "
                f"within {param.maxiter} iterations (block-CG Gram "
                "breakdown reports lanes unconverged too); per-RHS "
                "true_res_multi holds the achieved residuals")
        with otr.phase("epilogue", "invert_multi_src_quda"):
            if pair_exit:
                x_full, res_rhs = _verified_exit(
                    "invert_multi_src_quda", form_b, op, B, res.x)
            else:
                xe_b, xo_b = op.reconstruct_pairs_mrhs(res.x, be, bo)
                x_full = jax.vmap(
                    lambda e, o: even_odd_join(e, o, geom))(xe_b, xo_b)
                d_chk = _build_dirac(param, False)
                res_rhs = [float(jnp.sqrt(blas.norm2(B[i]
                                                     - d_chk.M(x_full[i]))
                                          / blas.norm2(B[i])))
                           for i in range(n_src)]
            x_out = _finish(x_full, iters_rhs, res_rhs, mv_applies,
                            converged_rhs=conv,
                            breakdown=getattr(res, "breakdown", None))
        if recording:
            # per-lane convergence histories (worst relative lane is
            # the headline; each lane normalized against its OWN b2)
            # + MRHS roofline attribution of the batch solve
            b2_rhs = np.asarray(_per_rhs_dot(nrm_b, nrm_b))
            rec = oconv.harvest(solver_name, res, tol=param.tol,
                                b2=b2_rhs)
            oconv.publish(rec, param)
            from ..obs import roofline as orf
            zoo_fused = getattr(op, "_op_form", None) == "pallas"
            if not getattr(op, "use_pallas", False):
                form = "generic"
            elif param.dslash_type == "clover":
                form = _clover_mrhs_roofline_form(op)
            elif param.dslash_type == "twisted-mass":
                form = ("twisted_mass_pallas_mrhs" if zoo_fused
                        else "twisted_xla")
            elif param.dslash_type == "twisted-clover":
                form = ("twisted_clover_pallas_mrhs" if zoo_fused
                        else "twisted_clover_xla")
            elif not stag_family:
                form = "wilson_mrhs"
            else:
                form = ("staggered_mrhs"
                        if getattr(op, "long_eo_pp", None) is not None
                        else "staggered_fat_mrhs")
            orf.record(form, geom.volume // 2,
                       float(np.max(iters_rhs)) * mv_applies, t_solve,
                       nrhs=n_src, flops_per_site=flops_m,
                       dslash_per_apply=2.0,
                       label=f"invert_multi_src_quda:{solver_name}")
        return x_out

    # generic fallback: per-source invert_quda loop (correct everywhere,
    # no gauge amortisation) — keeps the multi-source surface total
    import copy
    xs, iters_rhs, res_rhs, gflops, conv_rhs = [], [], [], 0.0, []
    for i in range(n_src):
        p_i = copy.copy(param)
        xs.append(invert_quda(B[i], p_i))
        iters_rhs.append(p_i.iter_count)
        res_rhs.append(p_i.true_res)
        gflops += p_i.gflops
        conv_rhs.append(p_i.converged)
    x_full = jnp.stack(xs)
    param.iter_count_multi = list(iters_rhs)
    param.true_res_multi = [float(r) for r in res_rhs]
    param.iter_count = int(sum(iters_rhs))
    param.true_res = float(np.max(np.asarray(param.true_res_multi)))
    param.secs = time.perf_counter() - t0
    param.gflops = gflops
    # the inner invert_quda calls already ran their own supervision
    # (and, under 'escalate', their own ladders) — roll their verdicts
    # up onto the batch param
    _solve_supervision(param, "invert_multi_src_quda",
                       converged_multi=conv_rhs)
    qlog.printq(
        f"invert_multi_src_quda[{param.dslash_type}/{param.inv_type}] "
        f"(per-source fallback): {n_src} sources, iters "
        f"{param.iter_count_multi}, worst true_res "
        f"{param.true_res:.2e}, {param.secs:.2f} s")
    return x_full


def _build_sloppy(p: InvertParam, pc: bool, sloppy_prec: str = None):
    import copy
    sloppy_prec = sloppy_prec or _resolve_sloppy(p)
    sl = copy.copy(p)
    sl.cuda_prec = sloppy_prec
    dt = complex_dtype(sloppy_prec)
    saved = {k: _ctx[k] for k in ("gauge", "fat", "long")}
    for k, v in saved.items():
        if v is not None:
            _ctx[k] = v.astype(dt)
    try:
        d = _build_dirac(sl, pc)
    finally:
        _ctx.update(saved)
    return d


def _mg_level_params(mp: "MultigridParamAPI"):
    """MultigridParamAPI -> per-level MGLevelParam list (one mapping for
    both the resident-setup and the solve path, so user smoothing knobs
    are never silently dropped)."""
    from ..mg.mg import MGLevelParam
    return [MGLevelParam(block=tuple(mp.geo_block_size[i]),
                         n_vec=mp.n_vec[i],
                         setup_iters=mp.setup_iters[i]
                         if i < len(mp.setup_iters) else 150,
                         setup_tol=mp.setup_tol[i]
                         if i < len(mp.setup_tol) else 5e-6,
                         pre_smooth=mp.nu_pre[i] if i < len(mp.nu_pre)
                         else 0,
                         post_smooth=mp.nu_post[i] if i < len(mp.nu_post)
                         else 4,
                         smoother_omega=mp.smoother_omega,
                         coarse_solver_iters=mp.coarse_solver_iters)
            for i in range(mp.n_level - 1)]


def _mg_pairs_enabled(d, param: InvertParam, on_tpu: bool) -> bool:
    """Pair-hierarchy gate: Wilson or staggered — including IMPROVED
    staggered, where the hierarchy is fat-only and mg_solve_pairs runs
    the outer Krylov on the full fat+Naik operator (defect correction;
    mg/pair.PairStaggeredLevelOp.M_std_full) — and, like every other
    pair gate in this file, never silently degrade an f64 solve to f32
    pairs."""
    family_ok = type(d).__name__ in ("DiracWilson", "DiracStaggered")
    return (_packed_enabled(on_tpu) and family_ok
            and (param.cuda_prec == "single" or on_tpu))


def _solve_mg(d_full, b, param: InvertParam, mg_param=None):
    """Returns (SolverResult, true_res or None): the pair route computes
    the true residual complex-free itself (the caller's complex check
    cannot execute on runtimes without complex support)."""
    from ..mg.mg import MG, mg_solve
    mp = mg_param or MultigridParamAPI()
    params = _mg_level_params(mp)
    mg = _ctx["mg"]
    if mg is not None and _ctx["mg_epoch"] != _ctx["gauge_epoch"]:
        # resident hierarchy was built for a different gauge — rebuild
        # (updateMultigridQuda semantics, interface_quda.cpp:2789; a stale
        # hierarchy silently degrades to a wrong preconditioner)
        qlog.printq("gauge changed since MG setup; rebuilding hierarchy",
                    qlog.VERBOSE)
        mg = None
    on_tpu = jax.default_backend() == "tpu"
    from ..mg.pair import PairMG
    if _mg_pairs_enabled(d_full, param, on_tpu):
        # complex-free hierarchy (mg/pair.py): the only MG that can
        # execute on TPU runtimes without complex64 support.  Boundary
        # conversions run host-side in numpy so no complex op ever
        # reaches the device.
        import numpy as np
        from ..mg.pair import mg_solve_pairs
        if mg is not None and not isinstance(mg, PairMG):
            qlog.printq("resident MG is complex; rebuilding as pair "
                        "hierarchy for the packed path", qlog.VERBOSE)
            mg = None
        b_np = np.asarray(b)
        b_pairs = jnp.asarray(
            np.stack([b_np.real, b_np.imag], -1).astype(np.float32))
        res, mg = mg_solve_pairs(d_full, _ctx["geom"], b_pairs, params,
                                 tol=param.tol, nkrylov=param.gcrNkrylov,
                                 mg=mg)
        _ctx["mg"] = mg
        _ctx["mg_epoch"] = _ctx["gauge_epoch"]
        from ..obs import memory as omem
        omem.track("mg", "hierarchy", mg)
        # true residual in pair arithmetic (no complex op on device) —
        # measured against the operator the outer solve targeted
        # (M_std_full = fat+Naik for improved staggered)
        outer_m = getattr(mg.adapter, "M_std_full", mg.adapter.M_std)
        r_pairs = b_pairs - outer_m(res.x)
        true_res = float(jnp.sqrt(blas.norm2(r_pairs)
                                  / blas.norm2(b_pairs)))
        x_np = np.asarray(res.x)
        return res._replace(x=jnp.asarray(
            (x_np[..., 0] + 1j * x_np[..., 1]).astype(b_np.dtype))), \
            true_res
    if isinstance(mg, PairMG):
        mg = None
    res, mg = mg_solve(d_full, _ctx["geom"], b, params, tol=param.tol,
                       nkrylov=param.gcrNkrylov, mg=mg)
    _ctx["mg"] = mg
    _ctx["mg_epoch"] = _ctx["gauge_epoch"]
    from ..obs import memory as omem
    omem.track("mg", "hierarchy", mg)
    return res, None


def new_multigrid_quda(mg_param: MultigridParamAPI, invert_param: InvertParam):
    """newMultigridQuda: run setup, keep hierarchy resident."""
    _require_init()
    mg_param.validate()
    from ..mg.mg import MG
    d = _build_dirac(invert_param, False)
    params = _mg_level_params(mg_param)
    on_tpu = jax.default_backend() == "tpu"
    if _mg_pairs_enabled(d, invert_param, on_tpu):
        # resident hierarchy in the complex-free representation so the
        # subsequent packed invert_quda reuses it (mg/pair.py)
        from ..mg.pair import PairMG
        _ctx["mg"] = PairMG(d, _ctx["geom"], params)
    else:
        _ctx["mg"] = MG(d, _ctx["geom"], params)
    _ctx["mg_epoch"] = _ctx["gauge_epoch"]
    from ..obs import memory as omem
    omem.track("mg", "hierarchy", _ctx["mg"])
    return _ctx["mg"]


def update_multigrid_quda(mg_param: MultigridParamAPI,
                          invert_param: InvertParam):
    """updateMultigridQuda (interface_quda.cpp:2789): refresh the resident
    hierarchy against the CURRENT resident gauge (after an HMC update or
    a new configuration load)."""
    _require_init()
    _ctx["mg"] = None
    return new_multigrid_quda(mg_param, invert_param)


def destroy_multigrid_quda():
    _ctx["mg"] = None
    from ..obs import memory as omem
    omem.release("mg", "hierarchy")


@_pm_api("invert_multishift_quda", payload="source")
def invert_multishift_quda(source, param: InvertParam):
    """invertMultiShiftQuda: (A + offset_i) x_i = b on the PC normal op."""
    _require_init()
    param.validate()
    from ..obs import trace as otr
    from ..robust import escalate as resc
    with otr.api_span("invert_multishift_quda",
                      dslash=param.dslash_type,
                      n_shifts=len(param.offset),
                      **_serve_rid_attrs()), \
            _hbm_sampled("invert_multishift_quda"):
        if resc.enabled():
            return resc.run_ladder(_invert_multishift_body, source,
                                   param, api="invert_multishift_quda")
        return _invert_multishift_body(source, param)


def _publish_multishift(res, rhs, param, tol=None, stage_note=None):
    """Convergence history for a multishift route: base-system residuals
    + per-shift lanes/converged-at events (obs/convergence.py).

    ``tol`` is the tolerance the RECORDED stage actually ran at (the
    dtype-sloppy route clamps to 1e-4; labeling that history with
    param.tol would produce a record that looks 6 orders short of a
    tolerance nothing was judged against).  ``stage_note`` marks a
    record that covers only part of the route (e.g. unrecorded
    per-shift refinement CGs follow)."""
    from ..obs import convergence as oconv
    if getattr(res, "history", None) is None:
        return
    rec = oconv.harvest("multi-shift-cg", res,
                        tol=param.tol if tol is None else tol,
                        b2=float(blas.norm2(rhs)))
    if rec is not None and stage_note is not None:
        rec.events.insert(0, {"type": "stage", "note": stage_note})
    oconv.publish(rec, param)


def _account_multishift(param: InvertParam, d):
    """Populate param.gflops like invert_quda does (monitor parity,
    lib/monitor.cpp solver fields).  Hermitian PC (staggered): the
    shifted solves apply M once per iteration; otherwise the normal
    equations cost MdagM = 2 applies.  PC convention: flops_per_site_M
    is per UPDATED site, so the PC operator charges volume/2 (see
    invert_quda's accounting note)."""
    flops = getattr(d, "flops_per_site_M", lambda: 0)()
    sites = _ctx["geom"].volume // 2
    mv_per_iter = 1.0 if getattr(d, "hermitian", False) else 2.0
    param.gflops = (param.iter_count * mv_per_iter * flops * sites) / 1e9
    _record_solve_metrics("invert_multishift_quda", _solve_form(d),
                          "multishift-cg", param.secs,
                          param.dslash_type, param.cuda_prec)


def _read_shift_iterations(param: InvertParam, res):
    """ONE host read of a MultiShiftResult's counts: ``iter_count`` the
    loop's iterations, ``iter_count_offset`` the iterations each shift
    was updated in (a converged shift leaves the update).  A function
    of its own, as ``_note_shift_iterations`` is: their locals in the
    API function's frame lengthened the lowering of the exit program
    under it by 2-6 s (PERF.md section 7 (22))."""
    iters, shift_iters = jax.device_get((res.iters, res.shift_iters))
    param.iter_count = int(iters)
    param.iter_count_offset = [int(n) for n in shift_iters]


def _note_shift_iterations(param: InvertParam, solve_span):
    """``active_share`` on the solve span and
    ``multishift_shift_iterations_total{state}``: the share of the
    N x iters shifted updates the loop made."""
    from ..obs import metrics as omet
    updated = sum(param.iter_count_offset)
    total = len(param.offset) * param.iter_count
    solve_span.set(active_share=round(updated / max(total, 1), 6))
    for state, n in (("updated", updated), ("skipped", total - updated)):
        if n:
            omet.inc("multishift_shift_iterations_total", float(n),
                     state=state)


def _shift_residuals(param: InvertParam, mv, rhs, xs, res=None):
    """The eager routes' per-shift exit: ``true_res_offset[i]`` =
    |rhs - (mv + offset_i) x_i| / |rhs| for EVERY shift, one more
    application of ``mv`` each; ``true_res`` stays shift 0's, as in
    QUDA; ``iter_res_offset`` the loop's analytic zeta_i |r| and
    ``iter_count_offset`` the iterations each shift was updated in,
    where ``res`` (a MultiShiftResult) carries them."""
    import numpy as np
    b2 = blas.norm2(rhs)
    param.true_res_offset = [
        float(jnp.sqrt(blas.norm2(rhs - (mv(xs[i]) + s * xs[i])) / b2))
        for i, s in enumerate(param.offset)]
    param.true_res = param.true_res_offset[0]
    param.iter_res_offset = (
        [] if res is None else
        [float(v) for v in np.sqrt(np.asarray(res.shift_r2)
                                   / float(b2))])
    param.iter_count_offset = (
        [] if res is None else
        [int(v) for v in np.asarray(res.shift_iters)])


def _invert_multishift_resident(b, param: InvertParam, recording: bool):
    """The improved-staggered multi-shift solve on the resident KS term
    (``_resident_staggered``, as invert_quda's ks_resident route):
    prepare, the shared-Krylov loop and the exit are one cached program
    each (solvers/program.py), the offsets operands of the last two.
    The exit verifies EVERY shift: the N solutions are a batch for the
    operator's batched hop, and a shift counts as converged when the
    loop claimed it and its true residual is within the verified-exit
    margin (``QUDA_TPU_ROBUST_VERIFY_MARGIN`` x tol), never on the
    loop's zeta |r| alone."""
    import numpy as np

    from ..obs import metrics as omet
    from ..obs import trace as otr
    from ..solvers import program as sprog
    from ..utils import config as qconf
    api = "invert_multishift_quda"
    t0 = time.perf_counter()
    with otr.phase("setup", api):
        d = _StaggeredResidentSolve(_resident_staggered(param),
                                    param.mass)
        form = _solve_form(d)
        # a host array: an operand of the two programs, no eager op
        shifts = np.asarray(param.offset, np.float32)
        with otr.span("prepare", cat="setup") as span:
            rhs, hit = sprog.prepare(d.op, b)
            _note_solve_program(span, api, form, "prepare", hit)
    with otr.phase("compute", api), \
            otr.span("solve:multishift-cg", cat="solver",
                     n_shifts=len(param.offset), tol=param.tol,
                     maxiter=param.maxiter) as solve_span:
        with otr.span("dispatch", cat="solver"):
            res, hit = sprog.multishift_cg(
                d.op, rhs, shifts, tol=param.tol, maxiter=param.maxiter,
                record=recording)
        _note_solve_program(solve_span, api, form, "multishift-cg", hit)
        # the first host read of the program's result: the wait for
        # its device time (the per-shift counts ride the same read)
        with otr.span("wait", cat="solver"):
            _read_shift_iterations(param, res)
        _note_shift_iterations(param, solve_span)
    param.secs = time.perf_counter() - t0
    _account_multishift(param, d)
    with otr.phase("epilogue", api):
        margin = float(qconf.get("QUDA_TPU_ROBUST_VERIFY_MARGIN",
                                 fresh=True))
        with otr.span("verified_exit", cat="epilogue") as span:
            (xs, *numbers), hit = sprog.verified_exit_shifts(
                d.op, rhs, res, shifts, margin * param.tol)
            _note_solve_program(span, api, form, "verified-exit", hit)
            with otr.span("exit_read", cat="epilogue"):
                true_res, iter_res, ok = jax.device_get(numbers)
        param.true_res_offset = [float(r) for r in true_res]
        param.iter_res_offset = [float(r) for r in iter_res]
        param.true_res = param.true_res_offset[0]
        for outcome, n in (("converged", int(ok.sum())),
                           ("failed", int((~ok).sum()))):
            if n:
                omet.inc("multishift_shift_total", float(n),
                         outcome=outcome)
        _solve_supervision(param, api,
                           breakdown=getattr(res, "breakdown", None),
                           converged_multi=ok)
    _publish_multishift(res, rhs, param)
    return xs.astype(b.dtype)


def _clover_shift_route(param: InvertParam) -> bool:
    """Whether ``invert_multishift_quda`` solves these shifted systems
    on ``_resident_clover``, given the packed pair representation
    serves the call: Wilson-clover, the multi-shift CG on the normal
    equations of the even-odd system (``normop-pc``), no explicit
    ``half`` / ``quarter`` sloppy request (the loop is pure f32).
    Everything else (Wilson, the twisted family, another solve type)
    keeps the branch it has in ``_invert_multishift_body``."""
    return (param.dslash_type == "clover"
            and param.inv_type == "multi-shift-cg"
            and param.solve_type == "normop-pc"
            and param.cuda_prec_sloppy not in ("half", "quarter"))


def _invert_clover_multishift_resident(b, param: InvertParam,
                                       recording: bool):
    """The Wilson-clover multi-shift solve (Mdag M + sigma_i) x_i =
    Mdag b_p on the resident clover term (``_resident_clover``: reused
    after ``load_clover_quda``, built on first use), M the even-odd
    operator of ``matpc`` and b_p what ``prepare`` makes of the source:
    entry (parity split, ``prepare`` and ``Mdag``), the shared-Krylov
    loop (two M an iteration, pure f32) and the exit are one cached
    program each (solvers/program.py), the links, the blocks, kappa and
    the offsets operands: every kappa, csw, gauge and set of offsets of
    one (lattice, N) shares the executables, and no canonical
    DiracClover* is built.  The exit verifies EVERY shift as
    ``_invert_multishift_resident`` does: the N solutions are one batch
    for the batched operator, and a shift counts as converged when the
    loop claimed it and its true residual is within the verified-exit
    margin.  The solutions live on the p sites (no reconstruction).
    The first trace of entry and exit stands on a stack chunk of its
    own, as the loop's does (PERF.md section 7 (22))."""
    import numpy as np

    from ..obs import metrics as omet
    from ..obs import trace as otr
    from ..solvers import program as sprog
    from ..utils import config as qconf
    from ..utils.frames import on_a_stack_chunk_of_its_own as footed
    api = "invert_multishift_quda"
    t0 = time.perf_counter()
    with otr.phase("setup", api):
        d = _CloverResidentSolve(_resident_clover(param, ()), param.kappa)
        form = _solve_form(d)
        # a host array: an operand of the two programs, no eager op
        shifts = np.asarray(param.offset, np.float32)
        with otr.span("prepare", cat="setup") as span:
            rhs, hit = footed(lambda: sprog.prepare(d.op, b))
            _note_solve_program(span, api, form, "prepare", hit)
    with otr.phase("compute", api), \
            otr.span("solve:multishift-cg", cat="solver",
                     n_shifts=len(param.offset), tol=param.tol,
                     maxiter=param.maxiter) as solve_span:
        with otr.span("dispatch", cat="solver"):
            res, hit = sprog.multishift_cg(
                d.op, rhs, shifts, tol=param.tol, maxiter=param.maxiter,
                record=recording)
        _note_solve_program(solve_span, api, form, "multishift-cg", hit)
        # the first host read of the program's result: the wait for
        # its device time (the per-shift counts ride the same read)
        with otr.span("wait", cat="solver"):
            _read_shift_iterations(param, res)
        _note_shift_iterations(param, solve_span)
    param.secs = time.perf_counter() - t0
    _account_multishift(param, d)
    with otr.phase("epilogue", api):
        margin = float(qconf.get("QUDA_TPU_ROBUST_VERIFY_MARGIN",
                                 fresh=True))
        with otr.span("verified_exit", cat="epilogue") as span:
            (xs, *numbers), hit = footed(
                lambda: sprog.verified_exit_shifts(
                    d.op, rhs, res, shifts, margin * param.tol))
            _note_solve_program(span, api, form, "verified-exit", hit)
            with otr.span("exit_read", cat="epilogue"):
                true_res, iter_res, ok = jax.device_get(numbers)
        param.true_res_offset = [float(r) for r in true_res]
        param.iter_res_offset = [float(r) for r in iter_res]
        param.true_res = param.true_res_offset[0]
        for outcome, n in (("converged", int(ok.sum())),
                           ("failed", int((~ok).sum()))):
            if n:
                omet.inc("multishift_shift_total", float(n),
                         outcome=outcome)
        _solve_supervision(param, api,
                           breakdown=getattr(res, "breakdown", None),
                           converged_multi=ok)
    _publish_multishift(res, rhs, param)
    return xs.astype(b.dtype)


def _invert_multishift_body(source, param: InvertParam):
    from ..obs import trace as otr
    from ..solvers.multishift import multishift_cg
    recording = otr.enabled()
    b = jnp.asarray(source, complex_dtype(param.cuda_prec))
    on_tpu = jax.default_backend() == "tpu"
    pairs_ok = ((param.cuda_prec == "single" or on_tpu)
                and _packed_enabled(on_tpu))
    if pairs_ok and _ks_links_loaded(param):
        return _invert_multishift_resident(b, param, recording)
    if pairs_ok and _clover_shift_route(param):
        return _invert_clover_multishift_resident(b, param, recording)
    d = _build_dirac(param, True)
    be, bo = _split(b, param, d)

    if (param.dslash_type in ("staggered", "asqtad", "hisq")
            and pairs_ok):
        # complex-free multishift without a resident KS term (plain
        # staggered, or improved links that were never loaded): the
        # operator is built per call and the shared-Krylov loop runs
        # eagerly on pair arrays (CG coefficients on the Hermitian PC
        # operator are real, so the pair representation is exact),
        # pallas eo stencil on real TPU
        t0 = time.perf_counter()
        ad = _StaggeredPairsSolve(d, _pallas_enabled(on_tpu),
                                  _pallas_interpret(on_tpu))
        rhs_pp = ad.prepare(be, bo)
        with otr.phase("compute", "invert_multishift_quda"):
            res = multishift_cg(ad.M, rhs_pp, tuple(param.offset),
                                tol=param.tol, maxiter=param.maxiter,
                                record=recording)
        param.iter_count = int(res.iters)
        param.secs = time.perf_counter() - t0
        _account_multishift(param, d)
        _publish_multishift(res, rhs_pp, param)
        _shift_residuals(param, ad.M, rhs_pp,
                         res.x.astype(jnp.float32), res)
        _solve_supervision(param, "invert_multishift_quda",
                           breakdown=getattr(res, "breakdown", None),
                           converged_multi=res.converged)
        return jnp.stack([ad.op._from_pairs(res.x[i], b.dtype)
                          for i in range(len(param.offset))])

    if param.dslash_type == "wilson" and pairs_ok:
        # complex-free Wilson multishift: shared-Krylov CGNR on the
        # packed pair representation end to end (coefficients of the
        # shifted normal-equation solves are real — exact on pairs)
        if param.cuda_prec_sloppy in ("half", "quarter"):
            # EXPLICIT sloppy request (not an 'auto' resolution): served
            # at f32 pairs (>= requested quality) — say so instead of
            # silently ignoring it
            qlog.printq(
                f"multishift: cuda_prec_sloppy="
                f"'{param.cuda_prec_sloppy}' served at f32 pair storage "
                "on the complex-free route", qlog.VERBOSE)
        t0 = time.perf_counter()
        sl = d.packed().pairs(jnp.float32,
                              use_pallas=_pallas_enabled(on_tpu),
                              pallas_interpret=_pallas_interpret(on_tpu))
        rhs_pp = sl.prepare_pairs(be, bo)
        nrm_rhs = sl.Mdag_pairs(rhs_pp)
        with otr.phase("compute", "invert_multishift_quda"):
            res = multishift_cg(sl.MdagM_pairs, nrm_rhs,
                                tuple(param.offset), tol=param.tol,
                                maxiter=param.maxiter, record=recording)
        param.iter_count = int(res.iters)
        param.secs = time.perf_counter() - t0
        _account_multishift(param, d)
        _publish_multishift(res, nrm_rhs, param)
        _shift_residuals(param, sl.MdagM_pairs, nrm_rhs,
                         res.x.astype(jnp.float32), res)
        _solve_supervision(param, "invert_multishift_quda",
                           breakdown=getattr(res, "breakdown", None),
                           converged_multi=res.converged)
        return jnp.stack([sl.solution_from_pairs(res.x[i], b.dtype)
                          for i in range(len(param.offset))])

    rhs = d.prepare(be, bo)
    if getattr(d, "hermitian", False):
        mv = d.M
    else:
        mv = lambda v: d.Mdag(d.M(v))
        rhs = d.Mdag(rhs)
    t0 = time.perf_counter()
    shifts = tuple(param.offset)
    sloppy_prec = _resolve_sloppy(param)
    pair_sloppy = (sloppy_prec in ("half", "quarter")
                   and param.dslash_type == "wilson")
    if pair_sloppy:
        # QUDA's multi-shift strategy (lib/inv_multi_cg_quda.cpp final
        # phase): run the shared-Krylov solve at sloppy precision, then
        # polish each shift with a short precise-level CG seeded by the
        # sloppy solution.
        from ..solvers.cg import cg as cg_solve
        sl = d.sloppy(sloppy_prec)
        with otr.phase("compute", "invert_multishift_quda"):
            res = multishift_cg(sl.MdagM, rhs.astype(jnp.complex64),
                                shifts, tol=max(param.tol, 1e-4),
                                maxiter=param.maxiter, record=recording)
        _publish_multishift(
            res, rhs, param, tol=max(param.tol, 1e-4),
            stage_note="sloppy shared-Krylov stage (tol clamped to "
                       "1e-4); per-shift precise refinement CGs follow "
                       "and are not recorded, so param.iter_count "
                       "exceeds this history's length")
        xs, iters, conv_s = [], int(res.iters), []
        for i, s in enumerate(shifts):
            mv_s = (lambda sig: lambda v: mv(v) + sig * v)(s)
            ref = cg_solve(mv_s, rhs, x0=res.x[i].astype(rhs.dtype),
                           tol=param.tol, maxiter=param.maxiter)
            xs.append(ref.x)
            iters += int(ref.iters)
            conv_s.append(bool(ref.converged))
        param.iter_count = iters
        param.secs = time.perf_counter() - t0
        _account_multishift(param, d)
        _shift_residuals(param, mv, rhs, xs)
        # convergence judged on the precise-level per-shift polish CGs
        _solve_supervision(param, "invert_multishift_quda",
                           converged_multi=conv_s)
        return jnp.stack(xs)
    with otr.phase("compute", "invert_multishift_quda"):
        res = multishift_cg(mv, rhs, shifts, tol=param.tol,
                            maxiter=param.maxiter, record=recording)
    param.iter_count = int(res.iters)
    param.secs = time.perf_counter() - t0
    _account_multishift(param, d)
    _publish_multishift(res, rhs, param)
    _shift_residuals(param, mv, rhs, res.x, res)
    _solve_supervision(param, "invert_multishift_quda",
                       breakdown=getattr(res, "breakdown", None),
                       converged_multi=res.converged)
    return res.x


def dslash_quda(psi, param: InvertParam, parity: int):
    """dslashQuda: apply the PC hop D_{parity, 1-parity}."""
    _require_init()
    d = _build_dirac(param, True)
    return d.D_to(jnp.asarray(psi, complex_dtype(param.cuda_prec)), parity)


def mat_quda(psi, param: InvertParam):
    """MatQuda: full operator application."""
    _require_init()
    d = _build_dirac(param, False)
    return d.M(jnp.asarray(psi, complex_dtype(param.cuda_prec)))


def mat_dag_mat_quda(psi, param: InvertParam):
    _require_init()
    d = _build_dirac(param, False)
    return d.MdagM(jnp.asarray(psi, complex_dtype(param.cuda_prec)))


@_pm_api("eigensolve_quda")
def eigensolve_quda(eig_param: EigParamAPI, invert_param: InvertParam):
    """eigensolveQuda: returns (evals, evecs)."""
    _require_init()
    eig_param.validate()
    from ..obs import trace as otr
    with otr.api_span("eigensolve_quda", eig_type=eig_param.eig_type,
                      n_ev=eig_param.n_ev,
                      dslash=invert_param.dslash_type), \
            _hbm_sampled("eigensolve_quda"):
        return _eigensolve_body(eig_param, invert_param)


def _eigensolve_body(eig_param: EigParamAPI, invert_param: InvertParam):
    from ..eig.iram import iram
    from ..eig.lanczos import EigParam, trlm
    from ..obs import trace as otr
    with otr.phase("setup", "eigensolve_quda"):
        pc = invert_param.solve_type.endswith("-pc")
        d = _build_dirac(invert_param, pc)
    geom = _ctx["geom"]
    dtype = complex_dtype(invert_param.cuda_prec)
    shape = (geom.half_lattice_shape if pc else geom.lattice_shape) + (4, 3)
    if invert_param.dslash_type in ("staggered", "asqtad", "hisq"):
        shape = shape[:-2] + (1, 3)
    if invert_param.dslash_type in ("ndeg-twisted-mass",
                                    "ndeg-twisted-clover"):
        shape = shape[:-2] + (2, 4, 3)   # flavor doublet axis
    if invert_param.dslash_type in _DWF_TYPES:
        shape = (invert_param.Ls,) + shape
    p = EigParam(n_ev=eig_param.n_ev, n_kr=eig_param.n_kr,
                 tol=eig_param.tol, max_restarts=eig_param.max_restarts,
                 use_poly_acc=eig_param.use_poly_acc,
                 poly_deg=eig_param.poly_deg, a_min=eig_param.a_min,
                 a_max=eig_param.a_max, spectrum=eig_param.spectrum)
    on_tpu = jax.default_backend() == "tpu"
    if (eig_param.eig_type == "trlm" and eig_param.use_norm_op and pc
            and _packed_enabled(on_tpu)
            and (invert_param.cuda_prec == "single" or on_tpu)
            and invert_param.dslash_type in ("wilson", "staggered",
                                             "asqtad", "hisq")):
        # complex-free TRLM (eig/pair_eig.py): the only eigensolve that
        # executes on TPU runtimes without complex64.  Realified
        # Hermitian Lanczos on the pair operator; kept vectors convert
        # to complex at the host boundary.  Dispatched BEFORE the
        # complex example/operator construction below so no complex
        # device array is materialised on this path.
        import numpy as np
        from ..eig.pair_eig import trlm_pairs
        T, Z, Y, X = geom.lattice_shape
        if invert_param.dslash_type == "wilson":
            sl = d.packed().pairs(
                jnp.float32, use_pallas=_pallas_enabled(on_tpu),
                pallas_interpret=_pallas_interpret(on_tpu))
            mv = sl.MdagM_pairs
            ex_pp = jnp.zeros((4, 3, 2, T, Z, Y * X // 2), jnp.float32)
            pair_axis = 2
            conv = sl.solution_from_pairs
        else:
            ad = _StaggeredPairsSolve(d, _pallas_enabled(on_tpu),
                                      _pallas_interpret(on_tpu))
            mv = ad.M
            ex_pp = jnp.zeros((3, 2, T, Z, Y * X // 2), jnp.float32)
            pair_axis = 1
            conv = ad.op._from_pairs
        t_eig0 = time.perf_counter()
        with otr.phase("compute", "eigensolve_quda",
                       solver="trlm_pairs"):
            res = trlm_pairs(mv, ex_pp, p, pair_axis)
        from ..obs import memory as omem
        from ..obs import metrics as omet
        _record_solve_metrics("eigensolve_quda", "trlm_pairs",
                              eig_param.eig_type,
                              time.perf_counter() - t_eig0,
                              invert_param.dslash_type,
                              invert_param.cuda_prec)
        omet.inc("eigensolves_total", family=invert_param.dslash_type,
                 eig_type=eig_param.eig_type)
        omem.track("eig", "evecs_trlm_pairs", res.evecs)
        if res.evecs.shape[0] < eig_param.n_ev:
            qlog.printq(
                f"eigensolve (pair route): only {res.evecs.shape[0]} of "
                f"{eig_param.n_ev} eigenpairs converged/deduplicated — "
                "raise n_kr/max_restarts or loosen tol",
                qlog.SUMMARIZE)
        evecs_h = np.stack([np.asarray(conv(res.evecs[i], dtype))
                            for i in range(res.evecs.shape[0])])
        # host-side modified Gram-Schmidt: converged non-degenerate
        # vectors are already orthonormal (the rotation is ~identity);
        # within DEGENERATE eigenspaces the realified dedup only
        # guarantees |overlap| < 0.5, and deflation consumers assume an
        # orthonormal basis
        for i in range(evecs_h.shape[0]):
            for k in range(i):
                ov = np.vdot(evecs_h[k], evecs_h[i])
                evecs_h[i] = evecs_h[i] - ov * evecs_h[k]
            evecs_h[i] /= np.sqrt(np.vdot(evecs_h[i],
                                          evecs_h[i]).real)
        evecs = jnp.asarray(evecs_h)
        if eig_param.vec_outfile:
            from ..utils.io import save_vectors
            save_vectors(eig_param.vec_outfile, evecs, res.evals)
        return res.evals, evecs
    example = jnp.zeros(shape, dtype)
    if eig_param.use_norm_op:
        # staggered PC: M already IS the (Hermitian) normal operator
        op = d.M if getattr(d, "hermitian", False) else d.MdagM
    else:
        op = d.M
    t_eig0 = time.perf_counter()
    with otr.phase("compute", "eigensolve_quda",
                   solver=eig_param.eig_type):
        if eig_param.eig_type == "trlm":
            res = trlm(op, example, p)
        elif eig_param.eig_type == "arpack":
            # host ARPACK bridge (lib/arpack_interface.cpp analog)
            from ..eig.arpack_bridge import arpack_solve
            res = arpack_solve(op, example, p,
                               hermitian=eig_param.use_norm_op)
        else:
            res = iram(op, example, p)
    from ..obs import memory as omem
    from ..obs import metrics as omet
    _record_solve_metrics("eigensolve_quda", eig_param.eig_type,
                          eig_param.eig_type,
                          time.perf_counter() - t_eig0,
                          invert_param.dslash_type,
                          invert_param.cuda_prec)
    omet.inc("eigensolves_total", family=invert_param.dslash_type,
             eig_type=eig_param.eig_type)
    omem.track("eig", f"evecs_{eig_param.eig_type}", res.evecs)
    if eig_param.vec_outfile:
        from ..utils.io import save_vectors
        save_vectors(eig_param.vec_outfile, res.evecs, res.evals)
    return res.evals, res.evecs


# -- gauge utilities -------------------------------------------------------

def plaq_quda():
    from ..gauge.observables import plaquette
    _require_init()
    m, s, t = plaquette(_ctx["gauge"])
    return float(m), float(s), float(t)


def gauge_observables_quda():
    from ..gauge.observables import energy, plaquette, polyakov_loop, qcharge
    _require_init()
    g = _ctx["gauge"]
    return {
        "plaquette": tuple(float(x) for x in plaquette(g)),
        "polyakov_loop": complex(polyakov_loop(g)),
        "qcharge": float(qcharge(g)),
        "energy": tuple(float(x) for x in energy(g)),
    }


def gauss_gauge_quda(seed: int, sigma: float):
    """gaussGaugeQuda: randomise the resident gauge field."""
    from ..ops.su3 import random_su3
    _require_init()
    key = jax.random.PRNGKey(seed)
    _set_resident_gauge(random_su3(key, (4,) + _ctx["geom"].lattice_shape,
                                   _ctx["gauge"].dtype, scale=sigma))


def perform_gauge_smear_quda(smear_type: str, n_steps: int, **kw):
    """performGaugeSmearQuda: ape|stout|ovrimp-stout|hyp on resident gauge."""
    from ..gauge import smear as gsm
    _require_init()
    g = _ctx["gauge"]
    if smear_type == "ape":
        g = gsm.ape_smear(g, kw.get("alpha", 0.6), n_steps=n_steps)
    elif smear_type == "stout":
        g = gsm.stout_smear(g, kw.get("rho", 0.1), n_steps=n_steps)
    elif smear_type == "ovrimp-stout":
        g = gsm.stout_smear(g, kw.get("rho", 0.08), n_steps=n_steps,
                            epsilon=kw.get("epsilon", -0.25))
    elif smear_type == "hyp":
        g = gsm.hyp_smear(g, n_steps=n_steps)
    else:
        qlog.errorq(f"unknown smear type {smear_type}")
    _set_resident_gauge(g)


def perform_wflow_quda(n_steps: int, eps: float, smear_type="wilson",
                       measure=None):
    from ..gauge.smear import symanzik_flow_step, wilson_flow_step
    _require_init()
    step = wilson_flow_step if smear_type == "wilson" else symanzik_flow_step
    hist = []
    g = _ctx["gauge"]
    for i in range(n_steps):
        g = step(g, eps)
        if measure:
            hist.append(measure(g, (i + 1) * eps))
    _set_resident_gauge(g)
    return hist


def compute_gauge_fixing_ovr_quda(gauge_dirs: int = 4, **kw):
    from ..gauge.fix import gaugefix_ovr
    _require_init()
    g, iters, theta = gaugefix_ovr(_ctx["gauge"], _ctx["geom"],
                                   gauge_dirs=gauge_dirs, **kw)
    _set_resident_gauge(g)
    return iters, theta


def compute_gauge_fixing_fft_quda(gauge_dirs: int = 4, **kw):
    from ..gauge.fix import gaugefix_fft
    _require_init()
    g, iters, theta = gaugefix_fft(_ctx["gauge"], _ctx["geom"],
                                   gauge_dirs=gauge_dirs, **kw)
    _set_resident_gauge(g)
    return iters, theta


def compute_ks_link_quda(naik_eps: float = 0.0):
    """computeKSLinkQuda: HISQ fatten the resident gauge; keep fat/long
    resident for staggered inverts."""
    from ..gauge.hisq import hisq_fattening
    _require_init()
    links = hisq_fattening(_ctx["gauge"], naik_eps)
    _set_resident_ks(links.fat, links.long)
    return links


def load_fat_long_quda(fat, long_links):
    """loadGaugeQuda with QUDA_ASQTAD_FAT_LINKS / QUDA_ASQTAD_LONG_LINKS
    (MILC's qudaLoadKSLink): the application's fat and long links,
    canonical (4,T,Z,Y,X,3,3), become the resident ones, and where the
    pair route is the platform's solve path (``_packed_enabled``) the
    pair operators ``invert_quda`` solves on are built from them now,
    once (``_resident_staggered`` for a single-precision HISQ solve,
    even-even, sloppy ``auto``: precise f32 and the sloppy storage that
    resolves to).  A solve whose matpc or kernel route differs, or new
    links, rebuild; ``load_gauge_quda`` drops the operators (not the
    links)."""
    _require_init()
    from ..obs import trace as otr
    with otr.api_span("load_fat_long_quda"):
        dtype = _ctx["gauge"].dtype if _ctx["gauge"] is not None else None
        _set_resident_ks(jnp.asarray(fat, dtype),
                         jnp.asarray(long_links, dtype))
        if (_ctx["gauge_param"] is not None
                and _packed_enabled(jax.default_backend() == "tpu")):
            p = InvertParam(dslash_type="hisq", cuda_prec="single")
            _resident_staggered(p, (_pair_store(_resolve_sloppy(p)),))


def save_gauge_field_quda(path: str, precision: int = 64):
    """Write the resident gauge as a SciDAC/ILDG lime file
    (lib/qio_field.cpp write path analog).  The anisotropy folded in at
    load time is UNDONE so the file holds the original links (QUDA
    saveGaugeQuda semantics)."""
    from ..utils.lime import save_gauge_lime
    _require_init()
    if _ctx["gauge"] is None:
        qlog.errorq("no resident gauge to save")
    g = _ctx["gauge"]
    gp = _ctx["gauge_param"]
    if gp is not None and gp.anisotropy != 1.0:
        scale = jnp.ones((4, 1, 1, 1, 1, 1, 1), g.real.dtype)
        scale = scale.at[:3].set(gp.anisotropy)
        g = g * scale.astype(g.dtype)
    save_gauge_lime(path, g, _ctx["geom"], precision=precision)


def load_gauge_field_quda(path: str, param: GaugeParam = None):
    """Read a SciDAC/ILDG lime file and make it the resident gauge
    (lib/qio_field.cpp read path analog).  Returns the gauge array.

    The caller's param is copied, its X replaced by the file geometry,
    and gauge_order forced canonical (file data is always canonical)."""
    import dataclasses

    from ..utils.lime import load_gauge_lime
    _require_init()
    gauge, meta = load_gauge_lime(path)
    gp = dataclasses.replace(param or GaugeParam(), X=meta["dims"],
                             gauge_order="canonical")
    load_gauge_quda(gauge, gp)
    return gauge


def compute_gauge_force_quda(beta: float, c1: float = 0.0):
    from ..gauge.action import gauge_force, improved_action, wilson_action
    _require_init()
    act = (lambda u: wilson_action(u, beta)) if c1 == 0.0 else \
        (lambda u: improved_action(u, beta, c1))
    return gauge_force(act, _ctx["gauge"])


def compute_gauge_force_paths_quda(mom, input_path_buf, loop_coeff,
                                   dt: float):
    """computeGaugeForceQuda (quda.h:1393): arbitrary user path tables.

    input_path_buf[mu][i] = i-th path (MILC encoding, backward = 7-mu)
    completing a loop with the initial U_mu; loop_coeff the per-path
    coefficients.  Returns mom - dt * F with F the su(3)-projected force
    of the path action (AD; staple math of gauge_force.cuh subsumed).
    """
    from ..gauge.paths import gauge_path_force
    _require_init()
    f = gauge_path_force(_ctx["gauge"], input_path_buf, loop_coeff)
    return jnp.asarray(mom) - dt * f


def gauge_loop_trace_quda(paths, coeffs, factor: float = 1.0):
    """gaugeLoopTraceQuda (quda.h:1420, lib/gauge_loop_trace.cu:74):
    returns one complex trace per loop, factor * c_i * sum_x tr W_i(x),
    as a (num_paths,) array (matching the C API's traces[] output)."""
    from ..gauge.paths import gauge_loop_trace
    _require_init()
    return factor * gauge_loop_trace(_ctx["gauge"], paths, coeffs)


def update_gauge_field_quda(mom, dt: float, reunitarize: bool = True):
    from ..gauge.action import update_gauge
    from ..ops.su3 import project_su3
    _require_init()
    g = update_gauge(_ctx["gauge"], mom, dt)
    if reunitarize:
        g = project_su3(g)
    _set_resident_gauge(g)


def mom_action_quda(mom):
    from ..gauge.action import mom_action
    return float(mom_action(mom))


def perform_wuppertal_n_step(psi, n_steps: int, alpha: float = 3.0):
    """performWuppertalnStep (interface_quda.cpp:4935)."""
    from ..gauge.quark_smear import wuppertal_smear
    _require_init()
    return wuppertal_smear(_ctx["gauge"], jnp.asarray(psi), alpha, n_steps)


def perform_two_link_gaussian_smear(psi, n_steps: int, omega: float = 2.0):
    """performTwoLinkGaussianSmearNStep: two-link staggered smearing."""
    from ..gauge.hisq import two_link
    from ..gauge.quark_smear import gaussian_smear
    _require_init()
    tl = two_link(_ctx["gauge"])
    return gaussian_smear(_ctx["gauge"], jnp.asarray(psi), omega, n_steps,
                          two_link_gauge=tl)


def laph_sink_project_quda(evecs, psi):
    """laphSinkProject (quda.h:1859)."""
    from ..ops.contract import laph_sink_project
    return laph_sink_project(jnp.asarray(evecs), jnp.asarray(psi))


def perform_gflow_quda(phi, n_steps: int, eps: float):
    """performGFlowQuda: joint gauge+fermion gradient flow; updates the
    resident gauge and returns the flowed fermion."""
    from ..gauge.smear import fermion_flow
    _require_init()
    g, p = fermion_flow(_ctx["gauge"], jnp.asarray(phi), eps, n_steps)
    _set_resident_gauge(g)
    return p


def contract_quda(x, y, contract_type: str = "open", momenta=None):
    from ..ops.contract import contract_dr, contract_ft, contract_open_spin
    if contract_type == "open":
        return contract_open_spin(jnp.asarray(x), jnp.asarray(y))
    if contract_type == "dr":
        return contract_dr(jnp.asarray(x), jnp.asarray(y))
    if contract_type == "ft":
        return contract_ft(jnp.asarray(x), jnp.asarray(y),
                           momenta or [(0, 0, 0)])
    qlog.errorq(f"unknown contract type {contract_type}")
