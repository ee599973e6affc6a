"""Central environment-flag registry — the QUDA_* config system analog.

Reference behavior: the reference scatters ~40 ``getenv("QUDA_...")``
calls across tune.cpp, malloc.cpp, monitor.cpp, util_quda.cpp,
milc_interface.cpp, dslash_policy.hpp etc. (e.g. QUDA_ENABLE_TUNING,
QUDA_RESOURCE_PATH, QUDA_ENABLE_MONITOR, QUDA_DETERMINISTIC_REDUCE,
QUDA_MAX_MULTI_RHS, QUDA_ENABLE_DEVICE_MEMORY_POOL).  This module is the
single TPU-native home for that surface:

* every knob is REGISTERED with a type, default, and doc string;
* reads go through typed accessors (`flag`, `intval`, `strval`) with
  caching and validation;
* ``describe()`` prints the full table (the analog of the reference's
  documented env list);
* ``check_environment()`` warns about unrecognised ``QUDA_TPU_*``
  variables — a typoed knob silently doing nothing is the worst failure
  mode of env-var config (fail-fast model, SURVEY §5.6).

CUDA-specific knobs with no TPU meaning (memory pools, MPS, GDR,
NVSHMEM, peer-to-peer) are intentionally NOT accepted: XLA/PJRT owns
allocation and collectives.  They are listed in ``SUBSUMED`` with the
subsystem that replaces them so ``describe()`` can answer "where did
QUDA_ENABLE_DEVICE_MEMORY_POOL go?".
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

_PREFIX = "QUDA_TPU_"


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str                 # full env-var name
    kind: str                 # "bool" | "int" | "float" | "str" | "choice"
    default: object
    doc: str
    choices: tuple = ()
    reference: str = ""       # the reference knob this replaces
    # Legal to read inside a traced function (jit/while_loop/scan/
    # shard_map bodies)?  Almost never: a knob read under trace freezes
    # into the compiled executable (stale-knob/recompile hazard), so
    # knobs are read at OPERATOR CONSTRUCTION and closed over.  The
    # static trace-safety pass (quda_tpu/analysis) reads its policy
    # from this field — flipping it to True is a reviewed statement
    # that trace-time freezing is the intended semantics for that knob.
    trace_safe: bool = False


_REGISTRY: dict[str, Knob] = {}


def _register(name, kind, default, doc, choices=(), reference="",
              trace_safe=False):
    _REGISTRY[name] = Knob(name, kind, default, doc, tuple(choices),
                           reference, bool(trace_safe))


# -- logging / verbosity ----------------------------------------------------
_register("QUDA_TPU_VERBOSITY", "choice", "summarize",
          "global log verbosity", ("silent", "summarize", "verbose",
                                   "debug"), "QUDA_VERBOSITY (setVerbosity)")
_register("QUDA_TPU_RANK_VERBOSITY", "str", "0",
          "which process indices print ('all' or a rank number)",
          reference="QUDA_RANK_VERBOSITY")
_register("QUDA_TPU_PROCESS_INDEX", "int", 0,
          "this process's index for rank-gated printing",
          reference="comm rank")

# -- autotuner --------------------------------------------------------------
_register("QUDA_TPU_ENABLE_TUNING", "bool", True,
          "enable the implementation-choice autotuner",
          reference="QUDA_ENABLE_TUNING")
_register("QUDA_TPU_RESOURCE_PATH", "str", "",
          "directory for tunecache.json and profile output",
          reference="QUDA_RESOURCE_PATH")
_register("QUDA_TPU_TUNE_VERSION_CHECK", "bool", True,
          "invalidate tunecache entries recorded by a different "
          "jax/backend version", reference="QUDA_TUNE_VERSION_CHECK")

# -- dslash implementation selection ---------------------------------------
_register("QUDA_TPU_PACKED", "choice", "",
          "force ('1') or forbid ('0') the TPU-native packed device "
          "order in API solves; empty = platform default (on for TPU)",
          ("", "0", "1"),
          reference="native FloatN field orders")
_register("QUDA_TPU_PALLAS", "choice", "",
          "force ('1') or forbid ('0') pallas dslash kernels in API "
          "solves; empty = autotuned choice",
          ("", "0", "1"),
          reference="QUDA_ENABLE_DSLASH_POLICY")
_register("QUDA_TPU_MG_EMBED", "choice", "",
          "apply pair-MG coarse links as single interleaved-embedding "
          "matmuls ('1') instead of 4-einsum pair products; empty/'0' "
          "= pair einsums (flip after chip measurement)",
          ("", "0", "1"),
          reference="coarse-dslash MMA path (lib/dslash_coarse.cu)")
_register("QUDA_TPU_MG_SETUP", "choice", "",
          "MG setup pipeline: ''/'fast' = MRHS null-vector block solve "
          "(one tolerance-stopped batched BiCGStab on the direct "
          "system over all n_vec sources, "
          "solvers/block.batched_bicgstab_pairs; MGLevelParam."
          "setup_solver='cg' selects batched_cg_pairs on MdagM) + "
          "GEMM-built coarse stencil (mg/gemm.py: 9 batched "
          "contractions instead of the ~34*n_vec-dispatch masked "
          "probe loop); 'legacy' = the "
          "pre-round-15 chunked-vmap fixed-iteration CG and probe loop "
          "(kept for the A/B the mg_setup_phase_seconds_total counters "
          "arbitrate)",
          ("", "fast", "legacy"),
          reference="MG::reset setup pipeline (lib/multigrid.cpp:91, "
                    "generateNullVectors :1249, calculateY)")
_register("QUDA_TPU_MG_NULL_CHUNK", "int", 0,
          "cap on simultaneously-batched null-vector solves in MG "
          "setup: 0 = one full-width block solve over all n_vec "
          "sources (the fast-path default; big-HBM chips keep it), "
          "k > 0 = chunk the batch at width k (a full-width batch "
          "holds n_vec concurrent (x, r, p, Ap) Krylov states — an "
          "OOM valve on fine lattices).  The legacy pipeline "
          "(QUDA_TPU_MG_SETUP=legacy) treats 0 as its historical "
          "hard-coded min(n_vec, 4)",
          reference="QUDA_MAX_MULTI_RHS / setup batching "
                    "(lib/multigrid.cpp generateNullVectors)")
_register("QUDA_TPU_MG_COARSE_CHUNK", "int", 0,
          "cap on simultaneously-contracted coarse-stencil columns in "
          "the GEMM coarse build (mg/gemm.py): 0 = all 2*n_vec null-"
          "vector columns in one batch (one fine-field batch of 2*n_vec "
          "resident at once), k > 0 = process k columns per pass — the "
          "HBM valve for fine lattices where 2*n_vec fine fields "
          "exceed residency",
          reference="calculateY batching (lib/coarse_op.in.cu)")
_register("QUDA_TPU_MG_COARSE_FORM", "choice", "auto",
          "pair-MG coarse-operator apply form: 'einsum' = 4-einsum "
          "pair products per link, 'embed' = interleaved-embedding "
          "matmuls, 'pallas' = the fused single-pass coarse stencil "
          "kernel (ops/coarse_pallas.py: diag + 8 hops in one launch, "
          "links read once), 'auto' = race all forms via utils.tune at "
          "hierarchy construction on chip (static einsum/embed default "
          "off-chip, honoring QUDA_TPU_MG_EMBED) — A/B'd, not assumed, "
          "like every other kernel form",
          ("", "auto", "einsum", "embed", "pallas"),
          reference="coarse-dslash MMA/policy selection "
                    "(lib/dslash_coarse.cu + tune.cpp:862)")
_register("QUDA_TPU_RECONSTRUCT", "choice", "18",
          "gauge link storage for the pallas kernels: '18' = full, "
          "'12' = two rows + in-kernel third-row reconstruction "
          "(192 B/site instead of 288; SU(3) links only)",
          ("18", "12"),
          reference="QUDA_RECONSTRUCT / gauge_field_order.h "
                    "Reconstruct<12>")
_register("QUDA_TPU_PRECISION_FORM", "choice", "",
          "link storage / precision form for the packed pallas Wilson "
          "operator (PERF.md round 16): 'full' = resident 18-real "
          "links; 'r12' = two rows + in-kernel third-row recon "
          "(192 B/site, single chip and the sharded path); "
          "'r12f' = r12 storage + scatter backward (no resident "
          "backward-link copy) on the gather psi "
          "path; 'fold' = re/im interleaved into sublanes "
          "((...,2,T,Z,YX) -> (...,T,2Z,YX)) so bf16 (16,128) tiles "
          "fill exactly; 'bzfull' = full-Z block admission (single-"
          "buffered under the 16 MB scoped window when the budget knob "
          "rejects double buffering); 'int8' = block-float resident "
          "links (int8 mantissas + one f32 scale per direction/site, "
          "decompressed in-kernel) — changes the operator's floats, so "
          "it must be served under the df64 reliable-update correction "
          "for deep tolerances; 'auto' = race the numerics-preserving "
          "forms via utils.tune (int8 NEVER races); '' = legacy "
          "resolution via QUDA_TPU_RECONSTRUCT.  Read at operator "
          "construction only (storage layout is baked into the "
          "resident arrays), hence NOT trace-safe",
          ("", "auto", "full", "bzfull", "fold", "r12", "r12f", "int8"),
          reference="QUDA_RECONSTRUCT x QUDA_PRECISION link-storage "
                    "matrix (gauge_field_order.h Reconstruct<12> + "
                    "quarter-precision block-float norm arrays)",
          trace_safe=False)
_register("QUDA_TPU_SHARDED_POLICY", "str", "auto",
          "multi-chip dslash halo policy, PER MESH AXIS since round "
          "18: 'xla_facefix' = lax.ppermute face fixes around the "
          "pallas interior (GSPMD collective-permute transport, serves "
          "every axis including the strided x column faces); "
          "'fused_halo' = in-kernel RDMA strip exchange, both "
          "directions behind one neighbour barrier (parallel/"
          "pallas_halo.slab_exchange_bidir, the NVSHMEM analog — "
          "contiguous t/z slabs and y row strips only); 'auto' = race "
          "each partitioned axis per (volume, mesh, form, axis) via "
          "utils.tune at construction and cache the winners "
          "(QUDA-policy-engine style).  A per-axis spec pins axes "
          "separately, e.g. 't=fused_halo,z=fused_halo,y=xla_facefix' "
          "(unlisted axes get xla_facefix); a bare policy name is the "
          "LEGACY single-value form — it maps onto all axes (x keeps "
          "xla_facefix under fused_halo) with a one-time deprecation-"
          "style notice.  Read at operator construction only, hence "
          "NOT trace-safe",
          reference="dslash policy engine lib/dslash_policy.hpp:"
                    "365-560,1566-1675 + QUDA_ENABLE_NVSHMEM",
          trace_safe=False)
_register("QUDA_TPU_PALLAS_VMEM_MB", "float", 6.0,
          "single-buffer VMEM budget (MB) for pallas z-block selection "
          "(_pick_bz).  Default 6 leaves half the 16 MB scoped limit "
          "for Mosaic's double buffering; raise it to admit bz=Z "
          "blocks (e.g. the bf16 full-Z 'equal-to-dim' experiment at "
          "Z=24 needs ~12) — measure before pinning",
          reference="tune.cpp shared-bytes tuning axis")
_register("QUDA_TPU_PALLAS_VMEM_MB_STAGGERED", "float", 9.0,
          "per-kernel single-buffer VMEM budget (MB) for the STAGGERED "
          "pallas z-block selection, overriding QUDA_TPU_PALLAS_VMEM_MB "
          "for that family only: the raised default is what the "
          "staggered z-blocks were read on the chip with, while the "
          "Wilson kernels keep the measured-proven 6 MB",
          reference="tune.cpp shared-bytes tuning axis (per-kernel)")
_register("QUDA_TPU_CLOVER_FORM", "choice", "",
          "clover PC pair-operator form: 'pallas' = the fused v2 "
          "kernel with the resident 2x6x6 chiral clover blocks applied "
          "in the kernel epilogue (ops/clover_pallas — diag+hop one "
          "VMEM pass), 'xla' = the staged hop + einsum composition, "
          "'auto' = race both via utils.tune at operator construction "
          "and cache the winner per (volume, dtype), '' (default) = "
          "the form measured faster on the chip, fused, without a race "
          "(models/formsel.MEASURED; interpret mode: staged).  Read at "
          "operator construction only, hence NOT trace-safe",
          ("", "auto", "pallas", "xla"),
          reference="dslash policy selection; tune.cpp:862 — policies "
                    "are timed, never assumed "
                    "(dslash_wilson_clover_preconditioned.cu)",
          trace_safe=False)
_register("QUDA_TPU_TWISTED_FORM", "choice", "auto",
          "twisted-mass / twisted-clover PC pair-operator form: "
          "'pallas' = the fused v2 kernel with the in-register i mu "
          "gamma5 twist (plus dense twisted-clover blocks) in the "
          "kernel epilogue, 'xla' = the staged composition, 'auto' = "
          "race and cache per (volume, dtype).  Nondegenerate "
          "flavor-doublet operators always take the XLA composition "
          "(the -b tau1 flavor mixing is not an epilogue term).  Read "
          "at operator construction only, hence NOT trace-safe",
          ("", "auto", "pallas", "xla"),
          reference="dslash policy selection; tune.cpp:862 "
                    "(dslash_twisted_clover_preconditioned.cu)",
          trace_safe=False)
_register("QUDA_TPU_DWF_FORM", "choice", "auto",
          "domain-wall / Möbius 4d-hop form: 'pallas' = the Ls-batched "
          "v2 kernel (ops/dwf_pallas — Ls innermost, gauge tile "
          "fetched once per (t, z-block) while Ls spinor planes stream "
          "through: 576+576/Ls B/site/plane), 'xla' = the vmap-over-s "
          "stencil, 'auto' = race and cache per (volume, dtype, Ls) "
          "where the operator is built per call from canonical arrays; "
          "the API's resident Möbius route never races: 'pallas' / "
          "'xla' pin, anything else serves the chip's measured winner "
          "(models/domain_wall.MEASURED_LS_HOP_FORM). "
          "The dense (Ls,Ls) m5 algebra stays XLA-batched either way. "
          "Read at operator construction only, hence NOT trace-safe",
          ("", "auto", "pallas", "xla"),
          reference="dslash policy selection; tune.cpp:862 "
                    "(dslash_domain_wall_m5.cuh batches s like rhs)",
          trace_safe=False)
_register("QUDA_TPU_DF64", "choice", "",
          "extended-precision (float32-pair) precise path for deep-tol "
          "Wilson CG: '1' = force, '0' = off, empty = auto (engaged when "
          "tol is below the f32 floor and no f64 backend serves)",
          ("", "0", "1"),
          reference="fp64 matPrecise + dbldbl reductions "
                    "(include/dbldbl.h)")
_register("QUDA_TPU_SLOPPY_PRECISION", "choice", "",
          "override cuda_prec_sloppy='auto' resolution",
          ("", "single", "half", "quarter"),
          reference="QudaInvertParam::cuda_prec_sloppy")

# -- solvers ----------------------------------------------------------------
_register("QUDA_TPU_CG_CHECK_EVERY", "int", 1,
          "fused-iteration CG convergence-check cadence: the while_loop "
          "body fuses this many CG iterations per convergence check, "
          "amortising the cond branch and the heavy-quark reduction over "
          "k dslash applies (solvers/fused_iter.py).  The solve reaches "
          "the same final residual as cadence 1 but may run up to k-1 "
          "iterations past convergence — and past maxiter, which is "
          "also only checked at cadence boundaries",
          reference="lib/inv_cg_quda.cpp per-iteration convergence check")
_register("QUDA_TPU_MAX_MULTI_RHS", "int", 32,
          "cap on simultaneously batched right-hand sides in block "
          "solvers", reference="QUDA_MAX_MULTI_RHS")
_register("QUDA_TPU_MULTI_SRC_SPLIT", "choice", "",
          "invert_multi_src_quda routing: '1' = force the split-grid "
          "path (sources sharded over the mesh src axis, gauge "
          "replicated), '0' = force the single-device batched MRHS "
          "pipeline, empty = auto by mesh size (split when >1 device "
          "divides the batch)",
          ("", "0", "1"),
          reference="callMultiSrcQuda split_key "
                    "(lib/interface_quda.cpp:3064)")
_register("QUDA_TPU_MULTI_SRC_BLOCK", "choice", "",
          "batched multi-source solver: '1' = true block CG (shared "
          "Krylov space, real Gram matmuls), empty/'0' = independent "
          "per-RHS lanes (batched CG) — the default matches QUDA's "
          "per-source multi-RHS solves",
          ("", "0", "1"),
          reference="QUDA block-CG solver family (inv_cg_quda.cpp "
                    "block variants)")
_register("QUDA_TPU_DETERMINISTIC_REDUCE", "bool", True,
          "accepted for compatibility: XLA reductions are deterministic "
          "per compiled executable already",
          reference="QUDA_DETERMINISTIC_REDUCE")

# -- monitoring / profiling / tracing ---------------------------------------
_register("QUDA_TPU_TRACE", "bool", False,
          "enable the observability layer (quda_tpu/obs): nestable "
          "span tracing of every API solve (chrome-trace JSON + JSONL "
          "event stream), per-iteration convergence recording surfaced "
          "on InvertParam.res_history, and roofline attribution rows; "
          "off (default) = zero-overhead no-op spans and unmodified "
          "solver loop carries",
          reference="pushProfile spans + profile_N.tsv (lib/tune.cpp:"
                    "450-474)")
_register("QUDA_TPU_TRACE_PATH", "str", "",
          "directory for trace artifacts (trace.json / "
          "trace_events.jsonl); empty = QUDA_TPU_RESOURCE_PATH, else "
          "the working directory",
          reference="QUDA_PROFILE_OUTPUT_BASE")
_register("QUDA_TPU_TRACE_EVENTS_MAX", "int", 200000,
          "cap on buffered trace events per session; events past the "
          "cap are dropped and counted in the flushed trace's "
          "otherData.dropped_events",
          reference="bounded profiling buffers")
_register("QUDA_TPU_METRICS", "bool", False,
          "enable the serving-grade metrics registry (obs/metrics.py): "
          "labeled solve/compile/tuner-cache/retry counters, the HBM "
          "field ledger + all-device memory sampling, and the "
          "end_quda export (metrics.prom Prometheus text, metrics.tsv, "
          "fleet_report.txt under the resource path); off (default) = "
          "zero-overhead no-op recording calls and bit-identical "
          "compiled solves (pinned by raising-stub test)",
          reference="tunecache/profile accounting (lib/tune.cpp:"
                    "450-610) + device_malloc ledger (lib/malloc.cpp)")
_register("QUDA_TPU_ENABLE_MONITOR", "bool", False,
          "periodically sample device/host memory into the monitor log",
          reference="QUDA_ENABLE_MONITOR")
_register("QUDA_TPU_MONITOR_PERIOD", "float", 1.0,
          "monitor sampling period in seconds",
          reference="QUDA_ENABLE_MONITOR_PERIOD")
_register("QUDA_TPU_PROFILE_OUTPUT_BASE", "str", "profile",
          "basename for timer/profile dumps under the resource path",
          reference="QUDA_PROFILE_OUTPUT_BASE")
_register("QUDA_TPU_DO_NOT_PROFILE", "bool", False,
          "disable the global TimeProfile accumulation",
          reference="QUDA_DO_NOT_PROFILE")
_register("QUDA_TPU_ENABLE_FORCE_MONITOR", "bool", False,
          "log per-step force norms during HMC momentum updates",
          reference="QUDA_ENABLE_FORCE_MONITOR")

# -- flight recorder / postmortem bundles (obs/flight.py, obs/postmortem.py)
_register("QUDA_TPU_FLIGHT", "bool", False,
          "enable the in-process flight recorder (obs/flight.py): a "
          "bounded host-side ring buffer of structured events (API "
          "entries/exits, tuner decisions, escalation rungs, sentinel "
          "codes, gauge loads/rejections, exchange-policy picks) whose "
          "tail lands in every postmortem bundle and in flight.jsonl "
          "at end_quda; off (default) = zero-overhead no-op appends "
          "and bit-identical compiled solves (pinned by raising-stub "
          "test)",
          reference="persistent tunecache/profile artifacts "
                    "(lib/tune.cpp:450-610) as the always-on black box")
_register("QUDA_TPU_FLIGHT_EVENTS_MAX", "int", 4096,
          "flight-recorder ring capacity: the newest this many events "
          "are kept; older ones are dropped (counted, reported as a "
          "flight_dropped trace event and in the bundle manifest)",
          reference="bounded profiling buffers")
_register("QUDA_TPU_POSTMORTEM", "choice", "",
          "postmortem bundle capture on solve failure paths "
          "(obs/postmortem.py): '1' = always capture, '0' = never, "
          "empty = follow QUDA_TPU_FLIGHT (a bundle without the ring "
          "tail is half blind, so capture defaults to riding the "
          "recorder).  Triggers: sentinel breakdown, verification "
          "mismatch, exhausted escalation ladder, gauge rejection, and "
          "uncaught exceptions crossing an interfaces/quda_api.py "
          "boundary",
          ("", "0", "1"),
          reference="QUDA_RESOURCE_PATH persistent artifacts as the "
                    "production failure-capture surface")
_register("QUDA_TPU_POSTMORTEM_PATH", "str", "",
          "directory receiving postmortem bundle directories (one "
          "pm_<stamp>_<trigger> dir per capture); empty = "
          "<QUDA_TPU_RESOURCE_PATH>/postmortems, else the working "
          "directory's ./postmortems",
          reference="QUDA_RESOURCE_PATH")
_register("QUDA_TPU_POSTMORTEM_MAX_MB", "float", 64.0,
          "size cap (MB) on the field dumps inside one postmortem "
          "bundle: fields are dumped in replay-priority order (gauge, "
          "source, fat, long) until the budget is spent; fields past "
          "the cap appear in manifest.json as omitted entries with "
          "shape/dtype/sha256 only (a replay then reports what is "
          "missing)",
          reference="bounded artifact size for fleet log collection")
_register("QUDA_TPU_POSTMORTEM_MAX_BUNDLES", "int", 8,
          "cap on postmortem bundles written per session: a repeating "
          "failure (e.g. every solve of a poisoned gauge breaking "
          "down) must not fill the disk; past the cap, captures are "
          "counted (postmortems_total{trigger=suppressed}) but not "
          "written",
          reference="bounded retry: a serving fleet must fail fast, "
                    "not loop")

# -- benchmark harness (bench.py / bench_suite.py) --------------------------
for _n, _k, _d, _doc in (
        ("QUDA_TPU_BENCH_CPU", "bool", False,
         "run the benchmark on the CPU backend (without it bench.py / "
         "bench_suite.py fail when jax finds no accelerator)"),
        ("QUDA_TPU_BENCH_L", "int", 0,
         "benchmark lattice extent (0 = platform default)"),
        ("QUDA_TPU_BENCH_N1", "int", 8, "short timing-chain length"),
        ("QUDA_TPU_BENCH_N2", "int", 200, "long timing-chain length"),
        ("QUDA_TPU_BENCH_REPS", "int", 5, "timing repetitions"),
        ("QUDA_TPU_BENCH_DEADLINE_S", "float", 1200.0,
         "wall-clock budget: on expiry bench.py prints the partial "
         "record accumulated so far and exits NON-ZERO (0 disables)"),
        ("QUDA_TPU_BENCH_SOLVER_L", "int", 16,
         "solver-suite lattice extent"),
        ("QUDA_TPU_BENCH_SOLVER_L_CHIP", "int", 24,
         "chip-sized solver-suite lattice for the TPU-only end-to-end "
         "rows (pallas-in-solver CG, multishift, bf16-reliable); "
         "0 disables them")):
    _register(_n, _k, _d, _doc, reference="tests/ benchmark CLI flags")

# -- perf-regression gate (bench_suite --compare / obs.regress) --------------
_register("QUDA_TPU_BENCH_COMPARE_TOL", "float", 0.10,
          "throughput tolerance of the bench-history compare gate: a "
          "current gflops/gbps row more than this fraction below its "
          "best-credible committed baseline fails bench_suite "
          "--compare with a rejection row and nonzero exit",
          reference="cross-version perf tracking (arXiv:1408.5925 "
                    "regression discipline)")
_register("QUDA_TPU_BENCH_COMPARE_ITERS_TOL", "float", 0.10,
          "solver-iteration tolerance of the compare gate: an iters "
          "row more than this fraction ABOVE its baseline fails "
          "(convergence regressions hide easily inside a wall-time "
          "budget)",
          reference="invert_test iteration-count reporting")
_register("QUDA_TPU_BENCH_HISTORY_DIR", "str", "",
          "directory holding the committed BENCH_*.json / "
          "MULTICHIP_*.json history the compare gate baselines "
          "against; empty = the repo root (next to bench.py)",
          reference="QUDA_RESOURCE_PATH-style state directory")

_register("QUDA_TPU_FORCE_CPU", "bool", False,
          "pin the CPU backend (and enable x64) in the embedded C-API "
          "interpreter", reference="QUDA_CPU_FIELD_LOCATION-style hosts")

# -- solve supervision (quda_tpu/robust) ------------------------------------
_register("QUDA_TPU_ROBUST", "choice", "off",
          "solve supervision level (quda_tpu/robust): 'off' = the "
          "compiled solves are bit-identical to the unguarded loops "
          "(zero ops added — pinned by test); 'verify' = in-loop "
          "breakdown sentinels (non-finite residual, pivot/Gram "
          "breakdown, stagnation) thread the solver while_loops and "
          "every API solve records verified_res + a solve_status on "
          "InvertParam; 'escalate' = verify plus the bounded retry "
          "ladder (pallas -> XLA stencil form; f32 sloppy -> df64 "
          "reliable; CG -> BiCGStab) on breakdown, verification "
          "mismatch, or operator-construction failure",
          ("off", "verify", "escalate"),
          reference="reliable updates + invert_test true-residual "
                    "checks (arXiv:1408.5925 production discipline)")
_register("QUDA_TPU_ROBUST_STAGNATION", "int", 0,
          "breakdown-sentinel stagnation window: flag a solve whose "
          "residual has not improved for this many consecutive "
          "convergence checks as a 'stagnation' breakdown (0 = "
          "disabled; stagnation is workload-dependent, so it is opt-in "
          "unlike the always-on finiteness/pivot predicates)",
          reference="solver convergence monitoring (lib/solver.cpp "
                    "PrintStats discipline)")
_register("QUDA_TPU_ROBUST_VERIFY_MARGIN", "float", 100.0,
          "verified-exit acceptance margin: a solve whose recomputed "
          "true residual exceeds margin * tol is recorded 'unverified' "
          "(and retried under 'escalate').  The margin absorbs the "
          "legitimate gap between the iterated system's stopping "
          "criterion (e.g. the normal equations) and the direct-system "
          "true residual",
          reference="invert_test residual verification")
_register("QUDA_TPU_ROBUST_MAX_RETRIES", "int", 3,
          "bound on escalation-ladder attempts per API solve "
          "(including the as-requested first attempt)",
          reference="bounded retry: a serving fleet must fail fast, "
                    "not loop")
_register("QUDA_TPU_FAULT", "str", "",
          "deterministic fault injection (quda_tpu/robust/faultinject):"
          " comma-separated <site>:<trigger> arms, e.g. 'dslash:5' "
          "(poison the dslash output at iteration 5 of the next "
          "solve), 'pallas_build:1' (raise on the next pallas operator"
          " construction), 'gauge:1' (poison a link at the next gauge "
          "load), 'residual:1e3' (inflate the next verified residual "
          "by 1e3).  Faults are one-shot: each arm fires once, then "
          "disarms — so an escalation retry sees a healthy system, "
          "modeling a transient fault.  TEST/DRILL KNOB: never set in "
          "production",
          reference="fault-injection testing of the reliable-update/"
                    "autotuner failure paths")
_register("QUDA_TPU_GAUGE_UNITARITY_TOL", "float", 0.0,
          "load_gauge_quda unitarity screen: warn (trace event "
          "gauge_unitarity) when any link's max |U Udag - I| exceeds "
          "this tolerance (0 = disabled).  Non-finite links are "
          "ALWAYS rejected loudly regardless of this knob; a "
          "deviating-but-finite gauge can be repaired with "
          "update_gauge_field_quda's reunitarize (ops/su3.project_su3)",
          reference="checkGauge / unitarize_links_quda tolerance "
                    "(include/svd_quda.h)")

# -- solve service (quda_tpu/serve) -----------------------------------------
_register("QUDA_TPU_SERVE_BATCH_WINDOW_MS", "float", 2.0,
          "solve-service coalescing window (milliseconds): after the "
          "first queued request is picked up, the worker keeps "
          "draining the queue for this long so requests targeting the "
          "same resident gauge coalesce into one MRHS batch "
          "(invert_multi_src_quda).  0 disables waiting — whatever is "
          "already queued still batches",
          reference="invertMultiSrcQuda batching "
                    "(lib/interface_quda.cpp:3064) + PLQCD queue-drain "
                    "overlap (arXiv:1405.0700)")
_register("QUDA_TPU_SERVE_MAX_BATCH", "int", 8,
          "cap on requests coalesced into one solve-service MRHS "
          "batch; also clamped by QUDA_TPU_MAX_MULTI_RHS.  Larger "
          "batches amortise gauge reads further (PERF.md round-7 "
          "curve) at the cost of per-request latency",
          reference="QUDA_MAX_MULTI_RHS")
_register("QUDA_TPU_SERVE_HBM_BUDGET_MB", "float", 0.0,
          "HBM budget (MB) for the solve-service gauge residency "
          "manager: when the obs/memory ledger's 'gauge' family "
          "exceeds it, least-recently-used non-active gauges are "
          "evicted (serve_gauge_evictions_total) until it fits.  "
          "0 = unlimited (single-tenant behavior)",
          reference="device_malloc ledger-driven residency "
                    "(lib/malloc.cpp) for gaugePrecise et al.")
_register("QUDA_TPU_SERVE_COMPILE_CACHE", "choice", "",
          "persistent XLA compilation cache (utils/compile_cache.py, "
          "wired by init_quda and the solve-service warm start): '0' "
          "off, anything else on.  The cache lives where "
          "JAX_COMPILATION_CACHE_DIR says (jax reads it; nothing is set "
          "in code), else at the fixed <checkout>/.jax_cache — the path "
          "is part of the cache key, so it never follows the working "
          "directory — and a fresh process deserialises already-built "
          "executables instead of recompiling (the compile-storm half "
          "of ROADMAP item 2; the tunecache warm start is the "
          "race-storm half)",
          ("", "0", "1"),
          reference="QUDA_RESOURCE_PATH persistent tunecache as the "
                    "cross-process warm-start surface")

# -- live telemetry plane (quda_tpu/obs/live.py) ----------------------------
_register("QUDA_TPU_LIVE", "bool", False,
          "serve the live telemetry HTTP plane (obs/live.py): a "
          "loopback ThreadingHTTPServer answering /metrics (Prometheus "
          "text from a lock-consistent registry snapshot, no reset), "
          "/healthz, /readyz, /fleet (live fleet_report.txt render), "
          "and /slo (serve_request_seconds burn rate) while the solve "
          "service keeps draining; off (default) = no server thread, "
          "no socket, and bit-identical compiled solves (pinned by "
          "raising-stub test)",
          reference="NVTX-annotated wrappers + QUDA_RESOURCE_PATH "
                    "artifacts (lib/generate/wrap.py) as the fleet-"
                    "introspection analog")
_register("QUDA_TPU_LIVE_PORT", "int", 0,
          "TCP port for the live telemetry endpoint, bound on "
          "127.0.0.1; 0 (default) = OS-assigned ephemeral port "
          "(obs.live.port() reports the bound one)",
          reference="pull-based Prometheus scrape discipline")
_register("QUDA_TPU_METRICS_FLUSH_SEC", "float", 0.0,
          "interval (seconds) for the live plane's background flusher: "
          "rewrites metrics.prom/metrics.tsv, fleet_report.txt, "
          "flight.jsonl, and roofline.tsv under the resource path "
          "every window so a crashed worker loses at most one "
          "interval of telemetry; 0 (default) disables the flusher "
          "(artifacts export at end_quda only)",
          reference="tunecache.tsv incremental persistence "
                    "(lib/tune.cpp:450-610)")
_register("QUDA_TPU_SLO_TARGET_MS", "float", 1000.0,
          "request-latency SLO target (milliseconds) the /slo endpoint "
          "grades serve_request_seconds against: a request is 'good' "
          "when its histogram bucket's upper bound is within the "
          "target",
          reference="fleet availability accounting (ROADMAP item 2)")
_register("QUDA_TPU_SLO_OBJECTIVE", "float", 0.99,
          "SLO objective: the fraction of requests required under "
          "QUDA_TPU_SLO_TARGET_MS.  /slo reports burn rate = "
          "(1 - compliance) / (1 - objective) — burn > 1 means the "
          "error budget is being spent faster than provisioned",
          reference="fleet availability accounting (ROADMAP item 2)")
_register("QUDA_TPU_SERVE_SLO_BUCKETS", "str", "",
          "comma-separated histogram bucket upper bounds (seconds) for "
          "serve_request_seconds, e.g. '0.05,0.1,0.25,0.5,1'; empty "
          "(default) = the registry-wide HIST_BUCKETS.  Set this when "
          "the SLO target sits inside one default bucket — percentile "
          "upper bounds and the /slo burn rate can only be as sharp "
          "as the bucket grid",
          reference="pull-based Prometheus scrape discipline")

# Knobs deliberately not accepted (CUDA-runtime ones never carried over,
# and this package's own retired ones): what replaces each answers
# "where did it go".
SUBSUMED = {
    "QUDA_ENABLE_DEVICE_MEMORY_POOL": "XLA/PJRT allocator",
    "QUDA_ENABLE_PINNED_MEMORY_POOL": "XLA/PJRT allocator",
    "QUDA_ENABLE_MANAGED_MEMORY": "XLA/PJRT allocator",
    "QUDA_ENABLE_MANAGED_PREFETCH": "XLA/PJRT allocator",
    "QUDA_ENABLE_P2P": "XLA collectives over ICI",
    "QUDA_ENABLE_GDR": "XLA collectives over ICI",
    "QUDA_ENABLE_GDR_BLACKLIST": "XLA collectives over ICI",
    "QUDA_ENABLE_NVSHMEM": "QUDA_TPU_SHARDED_POLICY=fused_halo "
                           "(in-kernel RDMA halo)",
    "QUDA_ENABLE_MPS": "single-process PJRT runtime",
    "QUDA_ENABLE_ZERO_COPY": "device_put / donation semantics",
    "QUDA_REORDER_LOCATION": "host<->device packing in fields/",
    "QUDA_ENABLE_DSLASH_POLICY": "QUDA_TPU_PALLAS + utils.tune",
    "QUDA_TPU_PALLAS_VERSION":  # quda-lint: disable=env-knob  reason=the retired knob's own entry: named so that a user who still sets it is told
        "the one Wilson pallas kernel generation (the v2 gather kernel; "
        "v1 and v3 were deleted in PR 30)",
    "QUDA_TPU_STAGGERED_FORM":  # quda-lint: disable=env-knob  reason=the retired knob's own entry: named so that a user who still sets it is told
        "models/staggered.served_forms: one hop form per operator shape "
        "(fat+Naik on one chip serves v3; the fused kernels were deleted "
        "in PR 45)",
    "QUDA_TPU_FUSED_TAIL":  # quda-lint: disable=env-knob  reason=the retired knob's own entry: named so that a user who still sets it is told
        "the XLA-fused CG tail, the only one (the pallas update+reduce "
        "kernels were deleted in PR 45)",
    "QUDA_ALLOW_JIT": "jit is the only execution model",
    "QUDA_DEVICE_RESET": "PJRT owns device lifetime",
}

_cache: dict[str, object] = {}

# Scoped override stack (robust/escalate.py retry rungs): each layer maps
# knob name -> raw string value and WINS over os.environ while pushed, so
# a ladder rung can demote e.g. QUDA_TPU_PALLAS without mutating the
# process environment (and without racing other readers of it).
_overrides: list = []


def overrides(**kv):
    """Context manager: push a layer of knob overrides (raw string
    values, validated like env input) that takes precedence over
    os.environ until the context exits.  Unknown knob names raise
    immediately — an override silently doing nothing is the same
    failure mode the registry exists to kill."""
    import contextlib

    for name in kv:
        if name not in _REGISTRY:
            raise KeyError(f"override of unregistered knob {name!r}")

    @contextlib.contextmanager
    def _ctx():
        _overrides.append({k: str(v) for k, v in kv.items()})
        _cache.clear()
        try:
            yield
        finally:
            _overrides.pop()
            _cache.clear()

    return _ctx()


def _parse(knob: Knob, raw: str):
    if knob.kind == "bool":
        if raw.lower() in ("1", "true", "yes", "on"):
            return True
        if raw.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"{knob.name}={raw!r} is not a boolean "
                         "(use 0/1)")
    if knob.kind == "int":
        return int(raw)
    if knob.kind == "float":
        return float(raw)
    if knob.kind == "choice":
        if raw not in knob.choices:
            raise ValueError(f"{knob.name}={raw!r} not in "
                             f"{knob.choices}")
        return raw
    return raw


def get(name: str, *, fresh: bool = False):
    """Typed value of a registered knob (env override or default)."""
    if name not in _REGISTRY:
        raise KeyError(f"unregistered config knob {name!r}; "
                       f"known: {sorted(_REGISTRY)}")
    if not fresh and name in _cache:
        return _cache[name]
    knob = _REGISTRY[name]
    raw = os.environ.get(name)
    for layer in reversed(_overrides):
        if name in layer:
            raw = layer[name]
            break
    val = knob.default if raw is None or raw == "" else _parse(knob, raw)
    _cache[name] = val
    return val


def flag(name: str) -> bool:
    v = get(name)
    assert isinstance(v, bool), f"{name} is not a bool knob"
    return v


def intval(name: str) -> int:
    return int(get(name))


def floatval(name: str) -> float:
    return float(get(name))


def strval(name: str) -> str:
    return str(get(name))


def reset_cache():
    """Drop cached values (tests mutate os.environ)."""
    _cache.clear()


def knobs() -> dict[str, Knob]:
    return dict(_REGISTRY)


def snapshot_raw() -> dict:
    """Raw-string view of every knob currently steered away from its
    default (env value or scoped-override layer, overrides winning) —
    the replay-facing half of describe(): feeding these back through
    :func:`overrides` reproduces this moment's configuration
    (obs/postmortem.py records it in every bundle manifest)."""
    out = {}
    for name in _REGISTRY:
        raw = os.environ.get(name)
        for layer in reversed(_overrides):
            if name in layer:
                raw = layer[name]
                break
        if raw:
            out[name] = raw
    return out


def snapshot_values() -> dict:
    """Resolved typed value of every registered knob (the human half of
    the postmortem snapshot; a malformed env value reads as None rather
    than aborting a failure capture)."""
    out = {}
    for name in _REGISTRY:
        try:
            out[name] = get(name, fresh=True)
        except ValueError:
            out[name] = None
    return out


def describe() -> str:
    """Human-readable table of every knob (value, default, doc) plus the
    subsumed knobs — the analog of the reference's documented
    environment-variable list."""
    lines = ["# quda_tpu environment configuration"]
    for name in sorted(_REGISTRY):
        k = _REGISTRY[name]
        cur = get(name)
        src = "env" if os.environ.get(name) else "default"
        ref = f"  [ref: {k.reference}]" if k.reference else ""
        lines.append(f"{name} = {cur!r} ({src}; default {k.default!r}) "
                     f"— {k.doc}{ref}")
    lines.append("# subsumed knobs (CUDA-era and retired)")
    for name in sorted(SUBSUMED):
        lines.append(f"{name} -> {SUBSUMED[name]}")
    return "\n".join(lines)


def check_environment(warn=None) -> list:
    """Return (and warn about) environment variables that LOOK like
    quda_tpu knobs but are not registered — typos silently doing nothing
    are the classic env-config failure."""
    from . import logging as qlog
    warn = warn or qlog.warningq
    unknown = [v for v in os.environ
               if v.startswith(_PREFIX) and v not in _REGISTRY
               and v not in SUBSUMED]
    for v in unknown:
        warn(f"warning: unrecognised environment variable {v} "
             "(see quda_tpu.utils.config.describe())")
    legacy = [v for v in os.environ if v in SUBSUMED]
    for v in legacy:
        warn(f"warning: {v} has no effect — subsumed by "
             f"{SUBSUMED[v]}")
    bad = []
    for name in _REGISTRY:
        if os.environ.get(name):
            try:
                get(name, fresh=True)
            except ValueError as e:
                bad.append(name)
                warn(f"warning: {e}")
    return unknown + legacy + bad
