"""The batched clover cell's yardstick can fail: the configuration is
``clover24_single``'s but for entry, batch and ``reduced``, a sound run
of eight sources a call is correct under the cell's own limits, an
altered or stale answer in ONE of the eight is not, the lower-precision
control is not, the needed-bytes counts of the two batched kernels are
the stated ones, and each new pattern reads its own kernel.

CPU, the configuration's rehearsal lattice (8^4), the program on its
packed pair route (``QUDA_TPU_PACKED=1``: the batched route on the
resident clover term, XLA stencil), as ``test_hisq_mrhs.py`` for the
HISQ batch.
"""

import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "clover24_mrhs8.light"
N_SRC = 8
LATTICE = (24, 24, 24, 24)
SITES = 165888

from benchmark.tests.test_correct import _run  # noqa: E402

KERNELS = {
    "dslash_eo_pallas_post_mrhs.4 f32<-f32,f32":
        {"count": 3000, "seconds": 3000 * 1500e-6},
    "dslash_eo_pallas_diag_hop_mrhs.4 f32<-f32,f32":
        {"count": 3000, "seconds": 3000 * 1800e-6},
    # the single-source cell's kernels and the bare hops of the exit
    "dslash_eo_pallas_post.3 bf16<-bf16,bf16":
        {"count": 100, "seconds": 0.03},
    "dslash_eo_pallas_diag_hop.3 f32<-bf16,bf16":
        {"count": 100, "seconds": 0.03},
    "dslash_eo_pallas_packed_mrhs.2 f32<-f32,f32":
        {"count": 6, "seconds": 0.006},
    "while.2": {"count": 2, "seconds": 12.0},
}
METRICS = {"clover_mrhs_post": ("dslash_eo_pallas_post_mrhs.", 2688),
           "clover_mrhs_diag_hop": ("dslash_eo_pallas_diag_hop_mrhs.",
                                    3456)}


def spec(metric):
    with open(os.path.join(ROOT, "benchmark", "per_layer",
                           metric + ".json")) as fh:
        return json.load(fh)


def ctx(kernels):
    return {"trace": {"kernels": kernels}, "package": "benchmark",
            "config": {"sources_per_call": N_SRC}, "lattice": LATTICE,
            "device_kind": "TPU v5 lite"}


@pytest.fixture
def packed(monkeypatch):
    from quda_tpu.utils import config as qconf
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    qconf.reset_cache()
    yield
    qconf.reset_cache()


def _rehearse(seed):
    return _run(["--workload", CELL, "--seed", str(seed), "--seconds",
                 "2", "--trace", "0", "--rehearse"])


def test_configuration_is_the_single_cells_but_for_entry_and_batch():
    run = importlib.import_module("benchmark.run")
    bench, cell, config, traffic, lattice = run.load_cell(CELL)
    single_cell = run.load_cell("clover24_single.light")
    single, single_traffic = single_cell[2], single_cell[3]
    assert cell["chips"] == 1 and lattice == LATTICE
    assert config["sources_per_call"] == N_SRC
    assert config["entry"] == "invert_multi_src_quda_clover"
    for key in ("reference", "gauge_param", "invert_param", "widths",
                "lattice", "rehearse_lattice", "control_precision"):
        assert config[key] == single[key], key
    assert config["reduced"] == ["sources_per_call"]
    assert "12" in config["reduced_why"]["sources_per_call"]
    assert set(single["assumed"]) | {"sources_per_call"} == set(
        config["assumed"])
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] != single["source"]
    assert "--nsrc" in entry["source"] and len(entry["source"]) < 200
    assert "invertMultiSrcQuda" in entry["source"]
    # the traffic is the single cell's but for its own residual limit
    ref = importlib.import_module("benchmark.reference.clover")
    assert traffic["kappa"] == single_traffic["kappa"] == 0.32
    assert config["invert_param"]["csw"] == ref.CSW == 1.0
    for key in ("link_scale", "gauge_seed", "sources", "agree_bound"):
        assert traffic[key] == single_traffic[key], key
    assert traffic["res_bound"] <= 1e-4
    mine = sorted(m["name"] for m in bench["per_layer"]
                  if m.get("workloads") == [CELL])
    assert mine == sorted("clover_mrhs_" + n for n in (
        "post_us", "diag_hop_us", "post_roofline", "diag_hop_roofline",
        "loop_rest_us", "loop_rest_share_pct", "iters",
        "compute_phase_s", "outside_solver_s"))
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]} - set(mine)
    assert listed == {
        "device_idle_pct", "hbm_peak_gib", "first_call_s",
        "first_call_trace_s", "first_call_lower_s", "first_call_compile_s",
        "first_call_solve_program_s", "first_call_exit_program_s",
        "first_call_eager_s", "first_call_eager_programs",
        "window_programs_built", "entry_prepare_s", "solve_dispatch_s",
        "solve_wait_s", "exit_read_s", "clover_load_s"}


def test_sound_rehearsal_run_of_eight_sources_is_correct(packed):
    rc, result, lines = _rehearse(21)
    assert rc == 0 and result["correct"] is True, result
    assert result["failed"] == 0
    assert result["attempted"] >= N_SRC and result["attempted"] % N_SRC == 0
    assert set(result["metrics"]) == {"call_s", "src_per_chip_h",
                                      "setup_s"}
    # every source of the warm-up and of the sampled calls was checked
    checks = [ln for ln in lines if ln.startswith("check warm-up")]
    assert len(checks) == N_SRC


@pytest.mark.parametrize("fault", ["answer_altered", "stale_answer"])
def test_one_broken_source_of_eight_is_not_correct(monkeypatch, packed,
                                                   fault):
    """One site of ONE of the eight solutions scaled, or one of the
    eight returned again from the previous call."""
    entry = importlib.import_module(
        "benchmark.entry.invert_multi_src_quda_clover")
    real = entry.call
    last = {}

    def broken(state, sources):
        x, info = real(state, sources)
        if fault == "answer_altered":
            x = x.at[5, 0, 0, 0, 0].multiply(1.5)
        elif "x" in last:
            x = x.at[5].set(last["x"][5])
        last.setdefault("x", x)
        return x, info
    monkeypatch.setattr(entry, "call", broken)
    rc, result, lines = _rehearse(22)
    assert rc == 0 and result["correct"] is False, result
    assert any(ln.startswith("compare ") and ln.endswith("OVER")
               for ln in lines)


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_in_lower_precision_is_not_correct(packed, seed):
    control = importlib.import_module("benchmark.control")
    run = importlib.import_module("benchmark.run")
    row = control.one_seed(run, CELL, seed, rehearse=True, control=1,
                           control_maxiter=600, out=lambda *_: None)
    assert row["program"]["correct"], row
    assert len(row["program"]["iters"]) == N_SRC
    assert not row["control"]["correct"], row
    bound = run.load_cell(CELL)[3]["res_bound"]
    assert row["control"]["res_max"] > 3 * bound, row


def test_needed_bytes_of_the_two_batched_kernels():
    post = importlib.import_module("benchmark.kernel_models.clover_eo_post")
    diag = importlib.import_module(
        "benchmark.kernel_models.clover_eo_diag_hop")
    # links and blocks once, eight spinors in and out (and eight centre
    # spinors more): 336 and 432 B a source against 1,344 and 1,440
    assert post.needed(LATTICE, n_rhs=N_SRC)["bytes_per_site"] == 2688
    assert diag.needed(LATTICE, n_rhs=N_SRC)["bytes_per_site"] == 3456
    assert post.needed(LATTICE, n_rhs=N_SRC)["sites"] == SITES
    for metric, (_, per_site) in METRICS.items():
        args = spec(metric + "_roofline")["args"]
        assert args["rhs_from_config"] == "sources_per_call"
        model = importlib.import_module(
            "benchmark.kernel_models." + args["model"])
        assert model.needed(LATTICE, n_rhs=N_SRC)[
            "bytes_per_site"] == per_site


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_pattern_reads_its_own_kernel_and_no_other(metric):
    prefix = METRICS[metric][0]
    for kind in ("_roofline", "_us"):
        rx = re.compile(spec(metric + kind)["args"]["pattern"])
        hits = [n for n in KERNELS if rx.search(n)]
        assert hits == [n for n in KERNELS if n.startswith(prefix)], hits
        assert len(hits) == 1
    # and the single-source cell's patterns do not read the batch's
    for single in ("clover_post_roofline", "clover_diag_hop_roofline",
                   "clover_dslash_bf16_us"):
        rx = re.compile(spec(single)["args"]["pattern"])
        assert not [n for n in KERNELS if rx.search(n) and "_mrhs" in n]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_share_of_the_roofline_by_hand_is_the_readers(metric):
    trace_roofline = importlib.import_module(
        "benchmark.readers.trace_roofline")
    trace_kernel = importlib.import_module("benchmark.readers.trace_kernel")
    prefix, per_site = METRICS[metric]
    value = trace_roofline.read(ctx(KERNELS),
                                **spec(metric + "_roofline")["args"])
    (k,) = [k for n, k in KERNELS.items() if n.startswith(prefix)]
    by_hand = 100.0 * (k["count"] * SITES * per_site / 819e9) / k["seconds"]
    assert value == pytest.approx(by_hand, rel=1e-12)
    assert 30.0 < value < 40.0
    us = trace_kernel.read(ctx(KERNELS), **spec(metric + "_us")["args"])
    assert us == pytest.approx(k["seconds"] / k["count"] * 1e6)


def test_loop_rest_is_the_while_less_the_four_kernels():
    rest = importlib.import_module("benchmark.readers.trace_loop_rest")
    per_iter = rest.read(ctx(KERNELS),
                         **spec("clover_mrhs_loop_rest_us")["args"])
    share = rest.read(ctx(KERNELS),
                      **spec("clover_mrhs_loop_rest_share_pct")["args"])
    kernels = 3000 * (1500e-6 + 1800e-6)
    assert per_iter == pytest.approx((12.0 - kernels) / 1500 * 1e6)
    assert share == pytest.approx(100.0 * (12.0 - kernels) / 12.0)
    # nothing to read where the route served another form: no metric
    bare = {n: k for n, k in KERNELS.items() if "_mrhs." not in n
            or "packed" in n}
    assert rest.read(ctx(bare),
                     **spec("clover_mrhs_loop_rest_us")["args"]) is None
