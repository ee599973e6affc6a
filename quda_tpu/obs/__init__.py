"""Observability layer: span tracing, convergence recording, roofline
attribution.

The reference ships its performance story as instrumentation built INTO
the product — per-API ``TimeProfile`` statics (lib/timer.cpp), the
autotuner doubling as a profiler (profile_N.tsv, lib/tune.cpp:450-474),
and per-solve convergence reporting.  This package is the TPU-native
home for that surface:

* ``obs.trace``       — nestable named spans + instant events, exported
                        as chrome-trace/perfetto JSON and a JSONL event
                        stream (QUDA_TPU_TRACE / QUDA_TPU_TRACE_PATH;
                        off = zero-overhead no-op spans, safe under jit).
* ``obs.convergence`` — per-iteration residual histories and solver
                        events (reliable updates, restarts, breakdowns,
                        per-RHS lanes) harvested from SolverResult
                        histories and surfaced on InvertParam.
* ``obs.roofline``    — the PERF.md per-site flops/bytes models joined
                        with measured wall-times into achieved-GFLOPS /
                        achieved-BW / %-of-demonstrated-peak rows per
                        kernel form, replacing hand arithmetic in the
                        bench harness and the round logs.
* ``obs.history``     — committed BENCH_*/MULTICHIP_* artifacts parsed
                        into canonical (metric, unit, platform, lattice,
                        form, mesh) time series with best-credible
                        (gate_row-passing) baselines and the trends.tsv
                        table PERF.md cites.
* ``obs.regress``     — the ``bench_suite --compare`` perf gate: diffs
                        a run against the history baselines, fails
                        loudly (rejection JSON rows + nonzero exit) on
                        >tol throughput regression or solver-iteration
                        inflation.
* ``obs.metrics``     — serving-grade labeled counter/gauge/histogram
                        registry (QUDA_TPU_METRICS; off = zero-overhead
                        no-op calls): solves by family/status, compile
                        vs warm-executable accounting, tuner warm-cache
                        hit/miss, retry-ladder counters; exported as
                        Prometheus text + metrics.tsv by end_quda.
* ``obs.build``       — build accounting: every program jax traces,
                        lowers and compiles (``jax.monitoring``), with
                        its seconds by stage, the persistent cache's
                        answer and the span and API call it was built
                        under; always on, read by the benchmark's
                        first-call metrics.
* ``obs.memory``      — HBM field ledger (every resident field tracked
                        at load/free with per-family bytes + high-water),
                        all-local-device memory_stats sampling around
                        solve phases, and the pallas VMEM budget audit.
* ``obs.report``      — the human-readable end-of-session fleet report
                        (fleet_report.txt) rendered from the two above.
* ``obs.comms``       — the ICI comms ledger (rides QUDA_TPU_TRACE /
                        QUDA_TPU_METRICS): every halo-exchange seam
                        records (site, axis, direction, bytes/device,
                        policy, dtype, mesh); per-solve ICI roofline
                        rows emitted alongside the HBM rows.
* ``obs.costmodel``   — the KERNEL_MODELS cross-check: analytic
                        flops/bytes vs Compiled.cost_analysis() of the
                        XLA reference stencils and the operand-footprint
                        floors; drift lint + per-session cost_drift.tsv.
* ``obs.schema``      — the canonical registry of every trace-event and
                        metric name (linted bidirectionally by
                        tests/test_obs_schema_lint.py; the metrics
                        registry also validates names at record time).
* ``obs.flight``      — the black-box flight recorder
                        (QUDA_TPU_FLIGHT; off = zero-overhead no-op):
                        a bounded ring buffer of structured events —
                        API entries/exits, tuner decisions, escalation
                        rungs, sentinel codes, gauge rejections —
                        tapped off the trace.event emission sites,
                        flushed as flight.jsonl and into every
                        postmortem bundle.
* ``obs.postmortem``  — failure-capture bundles (QUDA_TPU_POSTMORTEM):
                        on breakdown / verify mismatch / ladder
                        exhaustion / gauge rejection / API-boundary
                        exceptions, one self-contained directory —
                        knob + topology snapshot, consulted tunecache,
                        metrics + HBM snapshots, the flight tail, full
                        param provenance, size-capped content-hashed
                        field dumps, manifest.json — plus the
                        session-wide artifacts_manifest.json index.
* ``obs.replay``      — deterministic solve replay from a bundle
                        (``python -m quda_tpu.obs.replay <dir>``):
                        reconstructs fields/params, re-runs through
                        the normal invert_quda path under the recorded
                        knobs, reports reproduced / recovered /
                        diverged and appends replay.json for the fleet
                        report's replay-verified column.
"""

# obs.replay is deliberately NOT imported eagerly: it is the
# ``python -m quda_tpu.obs.replay`` entry point, and runpy warns when a
# -m target is already resident from its package import
from . import (build, comms, convergence, costmodel,  # noqa: F401
               flight, history, memory, metrics, postmortem, regress,
               report, roofline, schema, trace)
