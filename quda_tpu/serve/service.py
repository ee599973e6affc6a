"""The solve service: a long-lived multi-tenant worker.

Reference behavior: QUDA itself is a library — the serving daemon
around it (MILC/Chroma production drivers, multi-source batch runners)
owns queuing, batching, and residency.  ``SolveService`` is that daemon
for the TPU build: ONE worker thread owns the interface context (the
resident gauge, the MG hierarchy, the tuner) and drains a thread-safe
request queue; any number of client threads submit and wait on
tickets.

Lifecycle::

    svc = SolveService()
    svc.start()                       # init_quda (if needed) + warm start
    svc.load_gauge("cfgA", gauge, GaugeParam(X=...))
    t = svc.submit(source, InvertParam(...), gauge_id="cfgA")
    out = t.result(timeout=300)       # SolveOutcome: x, status, iters...
    svc.stop()                        # drain, persist warm keys, end_quda

Behavior contracts:

* requests coalesce into MRHS batches per (gauge, solve configuration)
  within the batch window (serve/batcher.py) and run through
  ``invert_multi_src_quda`` — per-request iters/residuals fan back out
  of ``iter_count_multi``/``true_res_multi``;
* gauges live under the residency manager's ledger-driven HBM budget
  (serve/residency.py); an evicted gauge reloads transparently from the
  host copy the service retains;
* a failing or degraded request NEVER kills the worker: the robust
  escalation ladder and postmortem capture ride along through the
  normal invert path, and whatever still fails lands on the ticket as
  a ``failed`` outcome plus a ``serve_availability`` event — the fleet
  pages on ``serve_availability_events_total``, not on stack traces;
* ``start`` runs serve/persist.py's warm start (persistent compilation
  cache + executable-key index) so a fresh worker's first solve is
  compile-storm free; ``stop`` persists the session's keys and flushes
  every artifact through ``end_quda`` when the service owns the
  session.
"""

from __future__ import annotations

import copy
import dataclasses
import itertools
import os
import queue as _queue
import threading
import time
from typing import Any, List, Optional

from . import batcher, persist
from .residency import GaugeResidency


@dataclasses.dataclass
class SolveOutcome:
    """What a ticket resolves to.  ``status`` is the supervised
    ``solve_status`` (converged / unconverged / unverified /
    breakdown:* / degraded:*) or ``failed`` when execution raised —
    inspect it instead of catching exceptions."""
    x: Any
    status: str
    converged: bool
    iter_count: int
    true_res: float
    secs: float                   # submit -> delivery (queue + solve)
    batch_size: int
    gauge_id: str
    error: Optional[str] = None
    param: Any = None             # the executed param copy (results)
    request_id: str = ""          # the ticket's id — grep key into
    #                               trace spans, availability events,
    #                               and postmortem manifests


class SolveTicket:
    """Future-style handle for one submitted request.

    Deliberately NOT concurrent.futures.Future: the contract differs —
    result() never raises for a failed solve (failure is a delivered
    SolveOutcome, the availability contract), there is no cancellation
    (an accepted request is always served, including the stop() drain),
    and the timeout raises the BUILTIN TimeoutError on every supported
    Python (futures.TimeoutError is a distinct class before 3.11)."""

    def __init__(self, request_id: str = ""):
        self.request_id = request_id
        self._event = threading.Event()
        self._outcome: Optional[SolveOutcome] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> SolveOutcome:
        """Block until the request is served; raises TimeoutError on
        expiry.  A failed/degraded solve RETURNS (status/error say
        why) — delivery is the service's availability contract."""
        if not self._event.wait(timeout):
            raise TimeoutError("solve request still queued/running")
        return self._outcome

    def _deliver(self, outcome: SolveOutcome):
        self._outcome = outcome
        self._event.set()


class SolveService:
    """The worker.  One instance per process is the intended shape
    (it owns the module-level interface context); constructor knobs
    override the serve env-knob defaults (QUDA_TPU_SERVE_BATCH_WINDOW_MS,
    QUDA_TPU_SERVE_MAX_BATCH, QUDA_TPU_SERVE_HBM_BUDGET_MB)."""

    def __init__(self, batch_window_ms: Optional[float] = None,
                 max_batch: Optional[int] = None,
                 hbm_budget_mb: Optional[float] = None):
        self._window_s = (None if batch_window_ms is None
                          else max(0.0, batch_window_ms) / 1e3)
        self._cap = max_batch
        self._queue: "_queue.Queue" = _queue.Queue()
        self._gauges: dict = {}          # id -> (host_gauge, GaugeParam)
        self._gauge_versions: dict = {}  # id -> registration counter
        self.residency = GaugeResidency(hbm_budget_mb)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # submit/stop atomicity: _stopped flips under _lifecycle BEFORE
        # stop() drains stragglers, so every accepted request is either
        # in the queue when the drain runs or refused at submit — no
        # check-then-put window can strand a ticket
        self._lifecycle = threading.Lock()
        self._stopped = False
        self._owns_init = False
        self._pending = 0
        self._pending_cv = threading.Condition()
        self._peak_depth = 0
        self.warm: Optional[dict] = None
        # request-id mint: pid-qualified so ids stay grep-unique when
        # several workers share one resource path (the fleet setup that
        # also pid-qualifies postmortem bundle dirs)
        self._rid_seq = itertools.count(1)

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "SolveService":
        """Idempotent: init_quda when no session is active (the service
        then owns it and stop() will end it), warm-start the
        compilation cache + executable-key index, start the worker."""
        with self._lifecycle:
            # check-then-spawn under the lock: two racing start()
            # calls must not create two workers both mutating the
            # single resident-gauge interface context
            if self._thread is not None:
                return self
            from ..interfaces import quda_api as api
            if not api._ctx["initialized"]:
                api.init_quda()
                self._owns_init = True
            self.warm = persist.warm_start()
            self._stop.clear()
            self._stopped = False
            self._thread = threading.Thread(target=self._run,
                                            name="quda-serve",
                                            daemon=True)
            self._thread.start()
        # live telemetry plane: init_quda's maybe_start covers the
        # service-owned-session path; an already-initialized session
        # gets its chance here, and either way /healthz //readyz now
        # answer for THIS worker (one global load each when off)
        from ..obs import live as olive
        olive.maybe_start()
        olive.attach(self)
        return self

    def stop(self, end_session: Optional[bool] = None):
        """Drain the queue, stop the worker, persist the executable-key
        index, release cached gauges, and (when this service owns the
        session, or ``end_session=True``) flush every artifact through
        ``end_quda`` — metrics.prom, fleet_report.txt with the Service
        section, trace, flight, the artifacts manifest."""
        with self._lifecycle:
            # refuse new submissions BEFORE the straggler drain below:
            # anything put() under the lock earlier is already in the
            # queue, anything later raises at submit
            self._stopped = True
        if self._thread is not None:
            self._stop.set()
            self._thread.join()
            self._thread = None
        # shutdown-race guard: a submit racing stop() can land a
        # request just after the worker's final empty-queue check —
        # serve stragglers on this thread (the worker is dead, so the
        # single-owner contract on the interface context holds) so
        # every accepted ticket is delivered, never stranded
        leftovers = []
        while True:
            try:
                leftovers.append(self._queue.get_nowait())
            except _queue.Empty:
                break
        try:
            for grp in batcher.group(leftovers, self._cap):
                self._execute(grp)
        except Exception as e:   # noqa: BLE001 — same guard as _run:
            # a batching-time error fails the stragglers' tickets; it
            # must not strand them or skip the shutdown flush below
            self._fail(leftovers, f"{type(e).__name__}: {e}",
                       len(leftovers))
        persist.save_warm_keys()
        self.residency.drop_all()
        from ..obs import live as olive
        olive.detach(self)
        end = self._owns_init if end_session is None else end_session
        if end:
            from ..interfaces import quda_api as api
            api.end_quda()
            self._owns_init = False

    def drain(self, timeout: Optional[float] = None):
        """Block until every submitted request has been delivered."""
        with self._pending_cv:
            if not self._pending_cv.wait_for(
                    lambda: self._pending == 0, timeout):
                raise TimeoutError(
                    f"{self._pending} request(s) still in flight")

    # -- client surface ------------------------------------------------------

    def load_gauge(self, gauge_id: str, gauge, gauge_param) -> str:
        """Register a configuration under an id.  Host-side only — the
        worker runs the actual ``load_gauge_quda`` path (validation,
        conversion, screens) on first use, and the retained host copy
        lets an evicted gauge reload transparently.  Re-registering an
        id bumps its version: the residency manager sees the mismatch
        at next use and reloads instead of serving the stale device
        copy (all residency mutation stays on the worker thread)."""
        self._gauges[gauge_id] = (gauge, gauge_param)
        self._gauge_versions[gauge_id] = \
            self._gauge_versions.get(gauge_id, 0) + 1
        return gauge_id

    def submit(self, source, param, gauge_id: str) -> SolveTicket:
        """Enqueue one solve against a registered gauge; returns the
        ticket its SolveOutcome will be delivered on.  ``param`` is a
        template — the service copies it per executed batch, so one
        template may back many concurrent submissions.  The ticket's
        ``request_id`` is the correlation key: it labels the request's
        availability events, rides the batch into the API span/flight
        stream, and lands in any postmortem bundle's manifest — failed
        ticket to bundle in one grep."""
        if gauge_id not in self._gauges:
            raise KeyError(f"gauge {gauge_id!r} is not registered; "
                           "call load_gauge first")
        rid = f"rq-{os.getpid()}-{next(self._rid_seq):06d}"
        ticket = SolveTicket(request_id=rid)
        req = batcher.SolveRequest(source=source, param=param,
                                   gauge_id=gauge_id, ticket=ticket,
                                   submitted=time.monotonic(),
                                   request_id=rid)
        with self._lifecycle:
            if self._stopped:
                raise RuntimeError(
                    "service is stopped; submissions before start() "
                    "queue up, but a stopped worker never drains")
            with self._pending_cv:
                self._pending += 1
            self._queue.put(req)
        # peak tracked host-side ALWAYS (the metrics session may open
        # after early submissions); the worker mirrors it into the
        # gauge at each collection
        self._peak_depth = max(self._peak_depth, self._queue.qsize())
        return ticket

    def health(self) -> dict:
        """Liveness/readiness signals for the telemetry plane
        (obs/live.py /healthz //readyz) — host-side reads only."""
        t = self._thread
        return {
            "worker_alive": bool(t is not None and t.is_alive()),
            "stopped": self._stopped,
            "warm_start_complete": self.warm is not None,
            # a registered host gauge can be served (residency loads
            # it on first use); resident ids cover the already-active
            # case after drop/eviction churn
            "gauge_present": bool(self._gauges)
                             or bool(self.residency.resident_ids()),
            "queue_depth": self._queue.qsize(),
            "pending": self._pending,
        }

    # -- worker --------------------------------------------------------------

    def _run(self):
        from ..obs import metrics as omet
        while True:
            batch = batcher.collect(self._queue,
                                    window_s=self._window_s)
            if not batch:
                if self._stop.is_set() and self._queue.empty():
                    return
                continue
            depth_now = len(batch) + self._queue.qsize()
            self._peak_depth = max(self._peak_depth, depth_now)
            omet.set_gauge("serve_queue_depth", depth_now,
                           scope="last")
            omet.set_gauge("serve_queue_depth", self._peak_depth,
                           scope="peak")
            try:
                groups = batcher.group(batch, self._cap)
            except Exception as e:   # noqa: BLE001 — worker survives
                # a batching-time error (exotic request content) must
                # fail the collected requests, never the worker: a
                # dead thread strands every pending and future ticket
                self._fail(batch, f"{type(e).__name__}: {e}",
                           len(batch))
                continue
            for grp in groups:
                self._execute(grp)

    def _loader(self, gauge_id: str):
        entry = self._gauges.get(gauge_id)
        return None if entry is None else (lambda: entry)

    def _mesh_route(self, n: int) -> str:
        """The split-vs-batched dispatch this batch will enter
        (parallel/split.multi_src_route) — recorded on the serve_batch
        event; the operator-level gates inside the API may still
        demote it."""
        if n == 1:
            return "single"
        from ..parallel.split import multi_src_route
        from ..utils import config as qconf
        try:
            route, _, _ = multi_src_route(
                n, split_mode=str(qconf.get("QUDA_TPU_MULTI_SRC_SPLIT",
                                            fresh=True)))
        except ValueError:
            return "per_source"
        return route

    def _execute(self, grp: List[batcher.SolveRequest]):
        from ..obs import metrics as omet
        from ..obs import trace as otr
        from ..utils import logging as qlog
        gid = grp[0].gauge_id
        n = len(grp)
        param = copy.copy(grp[0].param)
        t0 = time.monotonic()
        try:
            xs, statuses, conv, iters, res = self._solve(grp, gid,
                                                         param)
        except Exception as e:    # noqa: BLE001 — worker must survive
            err = f"{type(e).__name__}: {e}"
            qlog.warningq(f"serve: batch of {n} on gauge {gid!r} "
                          f"failed ({err}); worker continues")
            self._fail(grp, err, n)
            return
        omet.inc("serve_batches_total", size=n)
        # route label computed only for a live trace session: it costs
        # an env read + device enumeration, wasted on a no-op sink
        otr.event("serve_batch", cat="serve", gauge=gid, size=n,
                  route=self._mesh_route(n) if otr.enabled() else "",
                  secs=round(time.monotonic() - t0, 6))
        now = time.monotonic()
        for i, r in enumerate(grp):
            st = statuses[i]
            secs_req = now - r.submitted
            omet.observe("serve_request_seconds", secs_req,
                         family=param.dslash_type)
            omet.inc("serve_requests_total",
                     family=param.dslash_type, status=st)
            if st != "converged":
                kind = st.split(":", 1)[0]
                omet.inc("serve_availability_events_total", kind=kind)
                otr.event("serve_availability", cat="serve", kind=kind,
                          gauge=gid, status=st,
                          request_id=r.request_id)
            self._deliver(r, SolveOutcome(
                x=xs[i], status=st, converged=bool(conv[i]),
                iter_count=int(iters[i]), true_res=float(res[i]),
                secs=secs_req, batch_size=n, gauge_id=gid,
                param=param, request_id=r.request_id))

    def _fail(self, reqs, err: str, batch_size: int):
        """Deliver a failed outcome (+ the availability accounting) to
        every request in ``reqs`` — failed outcomes ARE deliveries:
        they belong in the SLO histogram, or the percentiles overstate
        compliance exactly when the fleet is unhealthy."""
        from ..obs import metrics as omet
        from ..obs import trace as otr
        for r in reqs:
            if r.ticket.done():
                # already delivered by an earlier group of the same
                # drain — a second delivery would overwrite a good
                # outcome and double-decrement _pending (hanging
                # drain() forever)
                continue
            # getattr: the param that BROKE batching (not a dataclass,
            # exotic fields) must still fail cleanly — the guard path
            # cannot afford its own AttributeError
            family = getattr(r.param, "dslash_type", "?")
            secs_req = time.monotonic() - r.submitted
            omet.inc("serve_requests_total",
                     family=family, status="failed")
            omet.observe("serve_request_seconds", secs_req,
                         family=family)
            omet.inc("serve_availability_events_total", kind="failed")
            otr.event("serve_availability", cat="serve", kind="failed",
                      gauge=r.gauge_id, error=err[:200],
                      request_id=getattr(r, "request_id", ""))
            self._deliver(r, SolveOutcome(
                x=None, status="failed", converged=False,
                iter_count=0, true_res=float("nan"), secs=secs_req,
                batch_size=batch_size, gauge_id=r.gauge_id, error=err,
                request_id=getattr(r, "request_id", "")))

    def _solve(self, grp, gid, param):
        """Activate the gauge and run the group as ONE solve: the MRHS
        batch route for n > 1, plain invert_quda for singletons.  The
        whole API call runs inside the postmortem serve-request scope
        so every span/flight attribute and any bundle captured on a
        failure path carries the batch's request ids (the flight-
        capture analysis rule pins this wrapping)."""
        import jax.numpy as jnp
        import numpy as np

        from ..interfaces import quda_api as api
        from ..obs import postmortem as opm
        self.residency.ensure_active(
            gid, loader=self._loader(gid),
            version=self._gauge_versions.get(gid))
        n = len(grp)
        with opm.serve_requests([r.request_id for r in grp]):
            if n == 1:
                # multishift singletons (never batched —
                # batcher.solve_key) take their own API entry point; x
                # is the stacked per-shift solution batch, results are
                # the batch-level param fields (converged_multi holds
                # the per-shift claims) and the LARGEST per-shift true
                # residual: param.true_res is shift 0's, and a request
                # is not good on one shift
                if getattr(param, "num_offset", 0):
                    x = api.invert_multishift_quda(grp[0].source,
                                                   param)
                    # np.max: a NaN shift is the largest
                    true_res = float(np.max(param.true_res_offset))
                else:
                    x = api.invert_quda(grp[0].source, param)
                    true_res = param.true_res
                st = (getattr(param, "solve_status", None)
                      or ("converged" if param.converged
                          else "unconverged"))
                return ([x], [st], [param.converged],
                        [param.iter_count], [true_res])
            B = jnp.stack([jnp.asarray(r.source) for r in grp])
            X = api.invert_multi_src_quda(B, param)
        conv = list(getattr(param, "converged_multi", None)
                    or [param.converged] * n)
        batch_st = getattr(param, "solve_status", None)
        statuses = ["converged" if c else
                    (batch_st if batch_st and batch_st != "converged"
                     else "unconverged")
                    for c in conv]
        return ([X[i] for i in range(n)], statuses, conv,
                list(param.iter_count_multi),
                list(param.true_res_multi))

    def _deliver(self, req, outcome: SolveOutcome):
        req.ticket._deliver(outcome)
        with self._pending_cv:
            self._pending -= 1
            self._pending_cv.notify_all()
