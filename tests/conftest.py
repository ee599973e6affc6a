"""Test harness config: CPU backend, 8 virtual devices, float64 enabled.

Mirrors QUDA's test strategy (SURVEY.md §4): correctness runs against host
references with double precision available, and multi-"chip" paths are
exercised on a virtual 8-device CPU mesh (the strictly-better analog of
QUDA's single no-op communicator + mpirun -np N on one node).
"""

import faulthandler
import os

# Must be set before the backend initialises.
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# One BLAS thread: the numpy/ARPACK oracles are 4^4-sized, and OpenBLAS's
# idle threads spin (test_iram_nonhermitian alone: 144 s of CPU for 39 s
# of wall, 42 s of CPU with one thread and the same wall), which six
# xdist workers on eight cores pay for many times over.  OpenBLAS reads
# these when it loads: scipy's copy loads after this line, and numpy's
# (a pytest plugin imports numpy first) in the xdist workers, which
# inherit the environment of the controller that ran this line.
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")
os.environ.setdefault("OMP_NUM_THREADS", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# init_quda wires the persistent compilation cache at the fixed
# <checkout>/.jax_cache (utils/compile_cache.py).  The tests check
# results, not warm starts: keep hundreds of interpret-mode CPU
# executables from being serialised into the checkout.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from _pytest.faulthandler import (  # noqa: E402
    fault_handler_stderr_fd_key)

# -- smoke tier --------------------------------------------------------------
# One fast, representative case per subsystem (reference: ctest labels,
# tests/CMakeLists.txt:414-470 tier quick checks the same way).  Run with
#   python -m pytest tests/ -m smoke -q
SMOKE = {
    "test_wilson.py": None,                 # whole file is fast oracles
    "test_core.py": None,
    "test_config.py": None,
    "test_blas_api.py": None,
    "test_utils.py": None,
    "test_packed.py": ["test_pack_round_trips",
                       "test_packed_eo_dslash_matches_canonical"],
    "test_cg.py": ["test_cg_even_odd_preconditioned"],
    "test_staggered.py": ["test_dslash_matches_host"],
    "test_clover.py": ["test_clover_apply_matches_host"],
    "test_twisted.py": ["test_twisted_mass_adjoint"],
    "test_domain_wall.py": ["test_mobius_matches_host"],
    "test_hisq.py": ["test_unitarize", "test_hisq_pipeline"],
    "test_gauge_hmc.py": ["test_force_matches_finite_difference",
                          "test_plaquette_random_range"],
    "test_pair_gauge.py": ["test_su3_primitives_match",
                           "test_observables_and_actions_match"],
    "test_pair_mg.py": ["test_cholqr2_orthonormal"],
    "test_eig.py": ["test_trlm_smallest_vs_arpack"],
    "test_multishift.py": ["test_multishift_matches_individual_solves"],
    "test_mixed.py": ["test_pair_stencil_matches_complex"],
    "test_parallel.py": ["test_gspmd_dslash_matches_single_device"],
    "test_interface.py": ["test_mat_and_dslash"],
    "test_lime_io.py": ["test_lime_record_framing",
                        "test_gauge_lime_round_trip"],
    "test_blockfloat.py": ["test_bf16_roundtrip_accuracy",
                           "test_int8_roundtrip_accuracy"],
}


# -- mid tier ----------------------------------------------------------------
# Structural/consistency coverage of the HEAVY files (MG hierarchies, pair
# sector, df64) that smoke skips, while leaving the long end-to-end solves
# to the full suite.  `pytest -m "smoke or mid"` is the review tier: any
# single file run with that filter completes well inside a review
# window (VERDICT r4 item 8 — the unfiltered 4-file pair-MG slice blew
# a 9.5-minute budget).
MID = {
    "test_pair_mg.py": ["test_pair_transfer_matches_complex",
                        "test_pair_coarse_links_match_complex",
                        "test_realified_vcycle_matches_complex"],
    "test_pair_eig.py": ["test_trlm_pairs_matches_complex_trlm"],
    "test_pair_gauge.py": ["test_gauge_force_matches",
                           "test_momentum_and_update_match"],
    "test_mg.py": ["test_transfer_orthonormal",
                   "test_galerkin_exactness"],
    "test_staggered_mg.py": ["test_staggered_hop_decomposition",
                             "test_staggered_chiral_adapter_round_trip"],
    "test_df64.py": ["test_error_free_transforms_exact",
                     "test_df64_mul_accuracy",
                     "test_compensated_sum_adversarial",
                     "test_compensated_blas_reductions"],
    "test_madwf.py": ["test_transfer_shapes_and_adjoint"],
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "smoke: fast one-per-subsystem tier")
    config.addinivalue_line(
        "markers", "mid: structural coverage of the heavy files; "
                   "'smoke or mid' is the review tier")
    config.addinivalue_line(
        "markers", "slow: over a minute alone on an idle host, or a "
                   "production-volume harness; tier-1 runs -m 'not slow'")


def pytest_collection_modifyitems(config, items):
    for item in items:
        fname = os.path.basename(str(item.fspath))
        sel = SMOKE.get(fname, False)
        if sel is None or (sel and any(item.name.startswith(n)
                                       for n in sel)):
            item.add_marker(pytest.mark.smoke)
        msel = MID.get(fname)
        if msel and any(item.name.startswith(n) for n in msel):
            item.add_marker(pytest.mark.mid)


# -- one file, one worker ----------------------------------------------------
# Tier-1 runs `-n 6 --dist load`, which deals consecutive tests out in
# ever smaller hands: a module's tests end up on every worker, and each
# of them pays the module's fixtures (test_solve_program.py's warm-up
# is two 80 s compiles of interpreted kernels) and traces and compiles
# the module's programs again.  The five heaviest files took 2,595 s of
# test time dealt out and 1,744 s each in a process of its own, and the
# whole run 1,407 s dealt out and 1,105 s file by file (same machine,
# same hour).  So under `--dist load` the scheduler is xdist's own
# loadfile one, made to hand out the heaviest file that is left: longest
# first keeps the last worker's tail short.  FILE_SECONDS is each
# file's test time in PR 43's whole run (1,194 s, 7,035 test-seconds on
# six workers), to the nearest ten, for files of 50 s and more (PR 45
# took interpreted kernels out of test_solve_program.py,
# test_staggered_pallas.py, test_precision_forms.py and
# test_fused_iter.py, now under 50 s: theirs are from PR 45's whole
# run, 966 s and 5,601 test-seconds, scaled to that run's; PR 46 added
# ~35 s of batched-route cases to test_clover_resident.py and ~13 s of
# described-chip compiles to test_chip_compile.py; PR 47 ~65 s of
# interpreted fused MRHS kernels to test_clover_pallas.py, which had
# none in tier-1, and ~10 s to test_chip_compile.py; PR 48 ~100 s of
# the K2 kernel's norm2 and residual forms and the clover operator's
# own CG step to test_clover_pallas.py; PR 49 added
# test_clover_multishift_resident.py, ~70 s: three programs and the
# plain reference on the XLA stencil, no interpreted kernel, and ~10 s
# of described-chip compiles to test_chip_compile.py; PR 50 ~100 s of
# the single-source K2 forms and the mixed CG's step to
# test_clover_pallas.py (seven interpreted kernels in two fixtures:
# bf16 ~65 s, f32 ~35 s), ~10 s of traced programs to
# test_clover_resident.py and a ~10 s compile to
# test_chip_compile.py).  It
# only orders the hand-out: a stale or missing entry costs balance and
# nothing else.
FILE_SECONDS = {
    "test_solve_program.py": 550, "test_multirhs.py": 470,
    "test_multirhs_kernels.py": 400, "test_pallas.py": 360,
    "test_pair_mg.py": 360, "test_staggered_pallas.py": 360,
    "test_domain_wall.py": 270, "test_clover_resident.py": 290,
    "test_chip_compile.py": 250, "test_precision_forms.py": 160,
    "test_mixed.py": 210, "test_wilson_resident.py": 170,
    "test_interface.py": 170, "test_pair_gauge.py": 170,
    "test_twisted.py": 160, "test_serve.py": 150, "test_pair_eig.py": 130,
    "test_ks_resident.py": 120, "test_mobius_resident.py": 110,
    "test_live.py": 100, "test_staggered_mg.py": 90, "test_madwf.py": 90,
    "test_eig.py": 90, "test_mg_3level.py": 90, "test_milc_rhmc.py": 80,
    "test_mg_gemm_coarse.py": 80, "test_packed.py": 80,
    "test_pallas_sharded.py": 80, "test_clover.py": 80,
    "test_clover_pallas.py": 290,
    "test_heatbath.py": 70, "test_build_accounting.py": 70,
    "test_schwarz.py": 70, "test_smear_force.py": 60,
    "test_clover_multishift_resident.py": 70,
    "test_parallel.py": 60, "test_solvers.py": 60,
    "test_multishift_resident.py": 60, "test_multishift.py": 60,
    "test_metrics.py": 50, "test_eigcg_gmresdr.py": 50,
}
OTHER_FILE_SECONDS = 20


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    if config.getvalue("dist") != "load":
        return None
    from xdist.scheduler import LoadFileScheduling

    class HeaviestFileFirst(LoadFileScheduling):
        def _assign_work_unit(self, node):
            heaviest = max(self.workqueue, key=lambda scope: FILE_SECONDS.get(
                os.path.basename(scope), OTHER_FILE_SECONDS))
            self.workqueue.move_to_end(heaviest, last=False)
            super()._assign_work_unit(node)
    return HeaviestFileFirst(config, log)


# -- per-test limit -----------------------------------------------------------
# Tier-1 runs under the driver's wall clock, and under `--dist load` a
# worker that hangs keeps the tests already handed to it until that
# clock kills the whole run.  A test stuck inside a C call with the GIL
# released (the interpret-mode halo deadlock was one) never runs a
# Python signal handler, so the watchdog is faulthandler's: every
# thread's traceback goes to the real stderr (pytest's own dup of it:
# sys.stderr is captured while a test runs), the worker exits, xdist
# reports that one test as failed and starts a new worker.  180 s is
# twice the 90 s no sound non-slow test may take in a full run, so
# load alone never trips it.  Fixtures are not under the limit.
LIMIT_S = 180


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    faulthandler.dump_traceback_later(
        LIMIT_S, exit=True,
        file=item.config.stash[fault_handler_stderr_fd_key])
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def key():
    return jax.random.PRNGKey(7)


# A full-suite run performs thousands of jit compilations per process;
# the accumulated XLA:CPU (LLVM JIT) state eventually segfaults inside
# backend_compile (observed 2026-07-30 at ~350 compilations in, in
# whichever module ran there — the same module passes standalone).
# Dropping the pjit caches bounds the resident compiled code, at the
# cost of re-tracing: under `--dist load` a worker changes module every
# few tests, and a drop at every change cost a sixth of the CPU time
# (four files in one process: 313 s with, 259 s without).  So drop at a
# module boundary only once COMPILES_PER_DROP programs were compiled
# since the last drop; test_twisted.py alone compiles more than that.
COMPILES_PER_DROP = 500
_compiles = 0


def _count_compile(event, duration_secs, **kwargs):
    global _compiles
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


@pytest.fixture(autouse=True, scope="module")
def _drop_jax_caches_between_modules():
    yield
    global _compiles
    if _compiles >= COMPILES_PER_DROP:
        _compiles = 0
        jax.clear_caches()
