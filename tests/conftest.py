"""Test harness config: CPU backend, 8 virtual devices, float64 enabled.

Mirrors QUDA's test strategy (SURVEY.md §4): correctness runs against host
references with double precision available, and multi-"chip" paths are
exercised on a virtual 8-device CPU mesh (the strictly-better analog of
QUDA's single no-op communicator + mpirun -np N on one node).
"""

import os

# Must be set before the backend initialises.
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

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

# -- smoke tier --------------------------------------------------------------
# One fast, representative case per subsystem (reference: ctest labels,
# tests/CMakeLists.txt:414-470 tier quick checks the same way).  Run with
#   python -m pytest tests/ -m smoke -q        (~4 minutes)
# The full suite remains the default (no marker filter).
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
# to the full suite.  `pytest -m "smoke or mid"` is the review tier: it
# must finish in ~10 minutes on this CPU, and any single file run with
# that filter completes well inside a review window (VERDICT r4 item 8 —
# the unfiltered 4-file pair-MG slice blew a 9.5-minute budget).
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
        "markers", "smoke: fast one-per-subsystem tier (~4 min total)")
    config.addinivalue_line(
        "markers", "mid: structural coverage of the heavy files; "
                   "'smoke or mid' is the ~10-minute review tier")
    config.addinivalue_line(
        "markers", "slow: multi-minute end-to-end runs (production-"
                   "volume harnesses); included in the default full run")


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


# -- slow-marker audit --------------------------------------------------------
# Tier-1 runs `-m "not slow"` under a hard wall clock (ROADMAP); the
# recurring budget leak is an interpret-mode pallas test (a ~20-60 s
# interpreter compile per kernel shape) landing in the fast tier
# unmarked.  Any non-slow test whose call phase exceeds the budget is
# listed in the terminal summary so the next PR marks it — an audit
# aid, not a failure.
SLOW_AUDIT_BUDGET_S = float(os.environ.get("QUDA_TPU_TEST_SLOW_BUDGET_S",
                                           "30"))
_SLOW_AUDIT: list = []


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    import time
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    if dt > SLOW_AUDIT_BUDGET_S and "slow" not in item.keywords:
        _SLOW_AUDIT.append((item.nodeid, dt))


def pytest_terminal_summary(terminalreporter):
    if _SLOW_AUDIT:
        terminalreporter.section("slow-marker audit")
        terminalreporter.write_line(
            f"non-slow tests over the {SLOW_AUDIT_BUDGET_S:.0f}s budget "
            "(mark slow or shrink; tier-1 runs -m 'not slow' under a "
            "hard timeout):")
        for nodeid, dt in sorted(_SLOW_AUDIT, key=lambda x: -x[1]):
            terminalreporter.write_line(f"  {dt:7.1f}s  {nodeid}")


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def key():
    return jax.random.PRNGKey(7)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Release compiled executables between test modules.

    A full-suite run performs ~450 jit compilations in one process; the
    accumulated XLA:CPU (LLVM JIT) state eventually segfaults inside
    backend_compile (observed 2026-07-30 at ~350 compilations in, in
    whichever module ran there — the same module passes standalone).
    Dropping the pjit caches after each module keeps the resident
    compiled-code footprint bounded at the cost of some re-tracing."""
    yield
    jax.clear_caches()
