"""Canonical observability schema: every trace-event and metric name.

Dashboards, scrape configs and trend queries key on NAMES.  A renamed
or ad-hoc event/metric breaks them silently — the exact failure mode
the env-knob registry (utils/config.py) exists to kill for knobs.  This
module is the same discipline for the telemetry surface:

* ``TRACE_EVENTS`` — every instant-event name the package may emit into
  the JSONL/chrome stream (obs/trace.event), with the category it
  belongs to and a one-line meaning;
* ``METRICS``      — every metric the registry (obs/metrics.py) may
  record, with its type (counter | gauge | histogram) and help string
  (exported verbatim into the Prometheus ``# HELP`` lines);
* ``SPAN_ATTRS``   — every attribute a span gains after its work has
  run (obs/trace ``span.set``, validated there), with the spans that
  carry it.

``tests/test_obs_schema_lint.py`` AST-harvests every emission site in
the package and asserts BOTH directions: no emitted name missing here,
and no registered name that nothing emits (schema rot).  The metrics
registry additionally validates at record time, so an unregistered
name fails the first time its code path runs even outside CI.
"""

from __future__ import annotations

# -- trace events (obs/trace.event instant events) --------------------------

TRACE_EVENTS: dict[str, dict] = {
    # convergence recording (obs/convergence.py)
    "residual": {"cat": "residual",
                 "doc": "per-iteration solver residual (headline lane)"},
    # roofline attribution (obs/roofline.py)
    "roofline": {"cat": "roofline",
                 "doc": "one achieved-GFLOPS/BW attribution row"},
    # bench harness (bench.py record_row)
    "bench_row": {"cat": "bench", "doc": "gate-passing bench row"},
    "bench_row_rejected": {"cat": "bench",
                           "doc": "bench row refused by gate_row"},
    # autotuner (utils/tune.py)
    "tune_cached": {"cat": "tune", "doc": "race served from the cache"},
    "tune_candidate": {"cat": "tune", "doc": "one candidate timing"},
    "tune_candidate_failed": {"cat": "tune",
                              "doc": "candidate raised mid-race"},
    "tune_winner": {"cat": "tune", "doc": "race winner cached"},
    "tune_race_all_failed": {"cat": "tune",
                             "doc": "every candidate raised; static "
                                    "default served uncached"},
    "tune_cache_invalidated": {"cat": "tune",
                               "doc": "stale-schema entries dropped at "
                                      "load"},
    "tune_cache_loaded": {"cat": "tune",
                          "doc": "warm-start load stats (init_quda)"},
    # solve supervision (quda_tpu/robust + interfaces/quda_api)
    "solve_retry": {"cat": "robust",
                    "doc": "escalation-ladder rung transition"},
    "solve_degraded": {"cat": "robust",
                       "doc": "solve served from a fallback rung"},
    "breakdown_detected": {"cat": "robust",
                           "doc": "in-loop breakdown sentinel tripped"},
    "verify_mismatch": {"cat": "robust",
                        "doc": "claimed convergence failed the "
                               "recomputed-residual check"},
    "gauge_rejected": {"cat": "robust",
                       "doc": "non-finite gauge refused at load"},
    "gauge_unitarity": {"cat": "robust",
                        "doc": "unitarity screen exceeded tolerance"},
    "fault_injected": {"cat": "robust",
                       "doc": "QUDA_TPU_FAULT arm fired (drill)"},
    # ICI comms ledger (obs/comms.py)
    "ici_exchange": {"cat": "comms",
                     "doc": "one halo-exchange seam recorded into the "
                            "ledger (per trace, bytes from the traced "
                            "slab shapes)"},
    "ici_solve": {"cat": "comms",
                  "doc": "per-solve ICI attribution row (ledger model "
                         "x measured applies, vs nominal link BW)"},
    # cost-model cross-check (obs/costmodel.py)
    "cost_drift": {"cat": "costmodel",
                   "doc": "one KERNEL_MODELS drift verdict (analytic "
                          "vs XLA reference flops + footprint floor)"},
    # serving-grade accounting (obs/metrics.py / obs/memory.py)
    "compile": {"cat": "metrics",
                "doc": "first execution of a (api, form, shape, dtype, "
                       "solver) key — compile time included in seconds"},
    "hbm_field_tracked": {"cat": "memory",
                          "doc": "resident field (re)registered in the "
                                 "HBM ledger"},
    "hbm_field_released": {"cat": "memory",
                           "doc": "resident field freed from the HBM "
                                  "ledger"},
    # solve service (quda_tpu/serve)
    "serve_batch": {"cat": "serve",
                    "doc": "one coalesced batch executed by the solve-"
                           "service worker (gauge, size, route, queue "
                           "depth at collection)"},
    "serve_gauge_evicted": {"cat": "serve",
                            "doc": "residency manager evicted an LRU "
                                   "gauge to fit the HBM budget"},
    "serve_availability": {"cat": "serve",
                           "doc": "a request finished degraded/"
                                  "unverified/failed — the availability "
                                  "event a fleet pages on instead of a "
                                  "stack trace"},
    "serve_warm_start": {"cat": "serve",
                         "doc": "worker warm start: persisted "
                                "compilation-cache dir + executable-key "
                                "index load stats"},
    # live telemetry plane (obs/live.py)
    "live_started": {"cat": "live",
                     "doc": "telemetry HTTP server bound (port + "
                            "flusher interval) — the scrape plane is "
                            "answering while the worker drains"},
    "live_flush": {"cat": "live",
                   "doc": "one periodic artifact flush window "
                          "completed (QUDA_TPU_METRICS_FLUSH_SEC): "
                          "metrics/fleet/flight/roofline rewritten "
                          "under the resource path"},
    # failure capture (obs/postmortem.py / obs/flight.py)
    "postmortem_written": {"cat": "postmortem",
                           "doc": "one failure-capture bundle written "
                                  "under the postmortem path (trigger "
                                  "+ api + bundle dir)"},
    "flight_dropped": {"cat": "flight",
                       "doc": "the flight-recorder ring wrapped: "
                              "oldest events were dropped (count "
                              "reported at session stop)"},
}

# -- span attributes set after the fact (obs/trace span.set) ----------------

SPAN_ATTRS: dict[str, dict] = {
    "program": {"spans": ("solve:cg", "solve:batched-cg-pairs",
                          "verified_exit", "prepare"),
                "doc": "'hit' | 'miss': whether the cached solve "
                       "program (solvers/program.py) served the call "
                       "from the in-process executable cache or traced "
                       "anew; absent on an eager solve"},
    "build_seconds": {"spans": ("solve:cg", "solve:batched-cg-pairs",
                                "verified_exit", "prepare"),
                      "doc": "on a 'miss': the seconds jax spent tracing, "
                             "lowering and compiling (or fetching) under "
                             "this span in this call, summed from the "
                             "build accounting's records (obs/build.py), "
                             "which program_build_seconds counts by "
                             "program"},
    "active_share": {"spans": ("solve:multishift-cg",),
                     "doc": "the share of the N x iters shifted updates "
                            "the multi-shift loop made: a converged "
                            "shift leaves the update "
                            "(MultiShiftResult.shift_iters summed over "
                            "N x iters; 1.0 = no shift retired early)"},
}

# -- metrics (obs/metrics.py registry) --------------------------------------

COUNTER, GAUGE, HISTOGRAM = "counter", "gauge", "histogram"

METRICS: dict[str, dict] = {
    # fleet solve accounting (interfaces/quda_api._solve_supervision;
    # under 'escalate' every ladder ATTEMPT counts — retries are visible
    # as extra attempts next to solve_retries_total)
    "solves_total": {
        "type": COUNTER,
        "help": "API solve attempts by api/family/status"},
    "solve_iterations_total": {
        "type": COUNTER,
        "help": "solver iterations executed, by api/family"},
    "solve_seconds": {
        "type": HISTOGRAM,
        "help": "wall seconds per API solve attempt, by api/family"},
    "eigensolves_total": {
        "type": COUNTER,
        "help": "eigensolve_quda calls by family/eig_type"},
    # compile / executable-cache accounting
    "compiles_total": {
        "type": COUNTER,
        "help": "first executions (compile included) per distinct "
                "(api, operator form, shape, dtype, solver) key, "
                "by api/form"},
    "compile_seconds": {
        "type": HISTOGRAM,
        "help": "first-execution wall seconds (compile + run), by api"},
    "executions_total": {
        "type": COUNTER,
        "help": "compute-phase executions per api/form (warm "
                "executable after the first)"},
    "solve_program_total": {
        "type": COUNTER,
        "help": "calls through a cached program (solvers/program.py: "
                "the solve loops, solver='verified-exit' the Wilson, "
                "staggered, Möbius, batched clover and shifted clover "
                "pair routes' verified exit, and solver='prepare' the "
                "entry of the staggered, Möbius, batched clover and "
                "shifted clover routes), "
                "by api/form/solver/outcome: 'miss' traced (and lowered, "
                "compiled or fetched) the program, 'hit' was an "
                "in-process executable lookup"},
    "program_build_seconds": {
        "type": COUNTER,
        "help": "seconds jax spent building programs (obs/build.py, from "
                "jax.monitoring), by program (the jitted function's name; "
                "eager operations are jit(<primitive>) programs of their "
                "own) and stage: 'trace' Python to jaxpr, 'lower' jaxpr "
                "to MLIR (a pallas kernel's Mosaic lowering included), "
                "'compile' XLA's compile or the fetch from the "
                "persistent cache; a trace nested in another program's "
                "is part of that program's and not counted twice"},
    "clover_term_total": {
        "type": COUNTER,
        "help": "uses of the resident clover term (load_clover_quda, "
                "clover invert_quda, the batched clover route of "
                "invert_multi_src_quda and the clover route of "
                "invert_multishift_quda) by outcome: 'built' nothing was "
                "resident, 'reused' the resident term served, "
                "'rebuilt' another kappa*csw, matpc, gauge or kernel "
                "route replaced it"},
    "wilson_term_total": {
        "type": COUNTER,
        "help": "uses of the resident Wilson pair operators (invert_quda "
                "and invert_multi_src_quda on the packed pair routes) by "
                "outcome: 'built' nothing was resident, 'reused' the "
                "resident operators served, 'rebuilt' another matpc, "
                "boundary or kernel route replaced them"},
    "ks_term_total": {
        "type": COUNTER,
        "help": "uses of the resident KS pair operators "
                "(load_fat_long_quda, asqtad / hisq invert_quda, "
                "invert_multi_src_quda and invert_multishift_quda on the "
                "pair routes) by outcome: "
                "'built' nothing was resident, "
                "'reused' the resident operators served, 'rebuilt' "
                "another matpc, boundary or kernel route replaced them; "
                "new fat / long links or a new gauge drop them"},
    "mobius_term_total": {
        "type": COUNTER,
        "help": "uses of the resident Möbius pair operators (mobius "
                "invert_quda on the 4d-PC CG pair route) by outcome: "
                "'built' nothing was resident, 'reused' the resident "
                "operators served, 'rebuilt' another (b5, c5, M5, mf) "
                "replaced the four (Ls, Ls) block pairs on the same "
                "links, or another matpc, boundary, Ls or kernel route "
                "replaced everything; a new gauge drops them"},
    "dwf_hop_route_total": {
        "type": COUNTER,
        "help": "traced calls of the Möbius pair operator's 4-d hop over "
                "its s-slices "
                "(models/domain_wall.DiracMobiusPCPairs._hop_to_pairs) "
                "by form: 'pallas' the multi-RHS Wilson kernel with Ls "
                "on its source axis (counted by route in "
                "wilson_mrhs_route_total too), 'xla' jax.vmap of the "
                "single-slice stencil; and by ls, the planes a call "
                "had"},
    "dwf_sblock_route_total": {
        "type": COUNTER,
        "help": "traced applications of the Möbius pair operator's real "
                "(Ls, Ls) chirality blocks "
                "(models/domain_wall.DiracMobiusPCPairs._apply_blocks) "
                "by form: 'pallas' the VPU kernel on the hop's layout "
                "(ops/dwf_pallas.mobius_sblock_pallas or its accumulate "
                "form), served wherever the hop is the Ls-batched "
                "kernel, 'einsum' XLA's f32 einsum (the CPU, "
                "interpreted kernels, QUDA_TPU_DWF_FORM=xla); and by "
                "ls"},
    "wilson_mrhs_route_total": {
        "type": COUNTER,
        "help": "traced calls of the multi-RHS Wilson kernel "
                "(ops/wilson_pallas_packed._mrhs_route) by route: "
                "'fullz' whole-Z tiles, bt time-slices a step, each "
                "spinor tile read (bt + 2) / bt times, 'zblock' z-blocks "
                "with two z-neighbour tiles besides (five reads); and by "
                "epilogue: 'combine' the store writes [g5] (xc + coeff * "
                "hop) (the second hop of the batched PC operator), "
                "'residual' it writes rc - alpha * that, alpha per "
                "source (the last hop of a batched CG iteration: the "
                "new r), 'none' the bare hop sum; and by reduce: 'norm2' "
                "the epilogue also sums the squares of what it stores, "
                "per source (every combine and residual call; from the "
                "first M's second hop it is the batched CG's pAp = "
                "|g5 M p|^2, from the residual hop its new |r|^2), "
                "'none' the bare hop"},
    "clover_mrhs_route_total": {
        "type": COUNTER,
        "help": "traced applications of a Schur pair operator to a batch "
                "(models/wilson._SchurPairOpBase._M_sign_pairs_mrhs and "
                "the two M of its MdagM_cg_step_pairs_mrhs: clover, and "
                "the twisted families on the same template) "
                "by form: 'pallas' the fused MRHS kernels of "
                "ops/clover_pallas (links AND blocks read once for all "
                "sources), 'xla' the bare MRHS Wilson hop and XLA's "
                "block products; and by stage: 'post' the first hop "
                "with Ainv_q behind it, 'diag_hop' the second with the "
                "diagonal and the combine; an M counts one of each "
                "where it is traced; and by route, the fused call's own "
                "from its shapes (ops/clover_pallas.mrhs_route): "
                "'fullz' whole-Z tiles of links, blocks and spinors, "
                "three psi operands a step, the epilogue per chunk of "
                "the hop's loop, where they fit the full-Z VMEM cap "
                "(24^4 f32: one time-slice a step), 'zblock' the "
                "single-source call's z-blocks and five psi operands "
                "(larger local volumes, a caller's block_z), 'none' the "
                "'xla' form; and by epilogue, what the call's store "
                "does: 'none' on 'post'; on 'diag_hop' 'combine' (A x - "
                "kappa^2 D t), 'norm2' (gamma5 in the store and its "
                "squares summed per source: the batched CG's pAp = "
                "|g5 M p|^2) or 'residual' (r - alpha g5 of that written "
                "over r and summed: the new r and |r|^2).  One solve "
                "program on the fused form counts post 2, norm2 1, "
                "residual 1; with a dslash fault armed, or in the 'xla' "
                "form, the loop takes solvers/block.cg_step and every "
                "diag_hop is 'combine'"},
    "clover_route_total": {
        "type": COUNTER,
        "help": "traced applications of a Schur pair operator to one "
                "source (models/wilson._SchurPairOpBase._M_sign_pairs "
                "and the two M of its MdagM_cg_step_pairs), the "
                "single-source sibling of clover_mrhs_route_total "
                "under the same labels, without a route (one source is "
                "z-blocks): by form 'pallas' / 'xla', by stage 'post' / "
                "'diag_hop', and by epilogue: 'none' on 'post'; on "
                "'diag_hop' 'combine' (A x - kappa^2 D t), 'norm2' "
                "(gamma5 in the store and its squares summed: the "
                "mixed-precision CG's pAp = |g5 M p|^2) or 'residual' "
                "(r - alpha g5 of that written over r and summed).  "
                "One cg_reliable program on the fused form counts, for "
                "its sloppy operator, post 2, norm2 1, residual 1; with "
                "a dslash fault armed, or in the 'xla' form, the loop "
                "takes solvers/mixed.cg_step and every diag_hop is "
                "'combine'"},
    "multishift_shift_total": {
        "type": COUNTER,
        "help": "shifts of invert_multishift_quda calls on the resident "
                "KS and clover routes by outcome of the verified exit: "
                "'converged' "
                "the loop claimed the shift and its true residual, "
                "recomputed by the exit program, is within the "
                "verified-exit margin x tol; 'failed' anything else"},
    "multishift_shift_iterations_total": {
        "type": COUNTER,
        "help": "shift-iterations of invert_multishift_quda calls on "
                "the resident KS and clover routes by state: 'updated' "
                "the loop "
                "updated the shift's x and p in that iteration "
                "(MultiShiftResult.shift_iters), 'skipped' the shift "
                "had converged and left the update; updated + skipped "
                "= N x iterations"},
    "staggered_mrhs_route_total": {
        "type": COUNTER,
        "help": "traced calls of the batched staggered hop "
                "(models/staggered.DiracStaggeredPCPairs._d_to_mrhs) by "
                "form: 'gather_two_pass' the gather MRHS kernel on "
                "pre-shifted backward links, 'scatter_two_pass' the v3 "
                "scatter pass with the RHS axis innermost, 'vmap_<form>' "
                "jax.vmap of the single-source hop (the pallas form, or "
                "'xla')"},
    # tuner warm-cache accounting (utils/tune.py)
    "tune_cache_hits_total": {
        "type": COUNTER,
        "help": "tune() decisions served from the warm cache, by kernel"},
    "tune_cache_misses_total": {
        "type": COUNTER,
        "help": "tune() keys not in the warm cache, by kernel"},
    "tune_races_total": {
        "type": COUNTER,
        "help": "candidate races actually timed, by kernel"},
    "tune_race_failures_total": {
        "type": COUNTER,
        "help": "races whose every candidate raised (static default "
                "served), by kernel"},
    "tune_cache_entries": {
        "type": GAUGE,
        "help": "persistent tunecache entries at warm start, by scope "
                "(total | usable_here | stale_dropped)"},
    # robust subsystem (robust/escalate.py + _solve_supervision)
    "solve_retries_total": {
        "type": COUNTER,
        "help": "escalation-ladder rung transitions, by api/reason"},
    "solve_degraded_total": {
        "type": COUNTER,
        "help": "solves served from a fallback rung (or best-effort "
                "after ladder exhaustion), by api"},
    "breakdowns_total": {
        "type": COUNTER,
        "help": "breakdown-sentinel exits, by api/reason"},
    # HBM field ledger (obs/memory.py)
    "hbm_family_bytes": {
        "type": GAUGE,
        "help": "resident bytes per field family"},
    "hbm_family_high_water_bytes": {
        "type": GAUGE,
        "help": "session high-water resident bytes per field family"},
    # VMEM budget audit (obs/memory.py vs QUDA_TPU_PALLAS_VMEM_MB*)
    "vmem_budget_bytes": {
        "type": GAUGE,
        "help": "configured single-buffer pallas VMEM budget, by knob"},
    "vmem_block_bytes": {
        "type": GAUGE,
        "help": "selected z-block working-set bytes (last _pick_bz "
                "decision), by knob"},
    # ICI comms ledger (obs/comms.py)
    "ici_bytes_total": {
        "type": COUNTER,
        "help": "interconnect bytes attributed to solves (halo model x "
                "applies) and split-grid replications, by axis/policy"},
    # MG setup attribution (mg/mg.py _setup phase breakdown)
    "mg_setup_phase_seconds_total": {
        "type": COUNTER,
        "help": "MG setup wall seconds per hierarchy level and phase "
                "(null_vectors | transfer_build | coarse_probe), by "
                "level/phase"},
    "mg_setup_seconds_total": {
        "type": COUNTER,
        "help": "total MG setup wall seconds per hierarchy build, by "
                "levels"},
    # failure capture (obs/postmortem.py)
    "postmortems_total": {
        "type": COUNTER,
        "help": "postmortem bundles captured, by trigger (breakdown:*, "
                "verify_mismatch, construct_error:*, ladder_exhausted:"
                "*, gauge_rejected, exception:*; 'suppressed' counts "
                "captures past the per-session bundle cap)"},
    # solve service (quda_tpu/serve)
    "serve_requests_total": {
        "type": COUNTER,
        "help": "solve-service requests completed, by family/status "
                "(status is the supervised solve_status, or 'failed' "
                "for requests whose execution raised)"},
    "serve_batches_total": {
        "type": COUNTER,
        "help": "coalesced MRHS batches executed by the solve-service "
                "worker, by batch size — the batch-size histogram of "
                "the fleet report's Service section"},
    "serve_request_seconds": {
        "type": HISTOGRAM,
        "help": "wall seconds from request submission to result "
                "delivery (queue wait + batch solve), by family — the "
                "solve_seconds SLO surface of the Service section"},
    "serve_queue_depth": {
        "type": GAUGE,
        "help": "solve-service queue depth, by scope (last = at the "
                "most recent batch collection, peak = session maximum)"},
    "serve_gauge_hits_total": {
        "type": COUNTER,
        "help": "requests served with their gauge already the active "
                "resident one (no residency switch), by gauge"},
    "serve_gauge_activations_total": {
        "type": COUNTER,
        "help": "residency switches: a cached gauge installed as the "
                "active resident one for a batch, by gauge"},
    "serve_gauge_evictions_total": {
        "type": COUNTER,
        "help": "gauges evicted by the residency manager to fit the "
                "HBM budget (LRU order, never the active one), by "
                "gauge"},
    "serve_availability_events_total": {
        "type": COUNTER,
        "help": "requests that finished degraded / unverified / "
                "breakdown / unconverged / failed, by kind — the "
                "Service section's availability row"},
    "serve_warm_keys": {
        "type": GAUGE,
        "help": "persisted executable-key index at worker warm start, "
                "by scope (loaded = keys seeded into compile "
                "accounting, saved = keys written at shutdown)"},
    # live telemetry plane (obs/live.py)
    "live_scrapes_total": {
        "type": COUNTER,
        "help": "telemetry-endpoint requests answered, by endpoint "
                "(metrics | healthz | readyz | fleet | slo) and HTTP "
                "status class"},
    "live_flushes_total": {
        "type": COUNTER,
        "help": "periodic background artifact flushes completed by "
                "the live plane (QUDA_TPU_METRICS_FLUSH_SEC windows)"},
    "slo_burn_rate": {
        "type": GAUGE,
        "help": "serve_request_seconds error-budget burn rate at the "
                "last /slo evaluation, by family ('all' = every "
                "family pooled): (1 - compliance) / "
                "(1 - QUDA_TPU_SLO_OBJECTIVE) against "
                "QUDA_TPU_SLO_TARGET_MS"},
    # static analysis (quda_tpu/analysis; bench_suite --artifacts-dir
    # runs the engine and mirrors per-rule counts here for the fleet
    # report's Static analysis section)
    "analysis_findings": {
        "type": GAUGE,
        "help": "static-analysis findings at the last engine run, by "
                "rule/status (unsuppressed findings fail tier-1 and "
                "the CLI; suppressed ones carry a mandatory reason)"},
}


def metric_type(name: str) -> str:
    return METRICS[name]["type"]
