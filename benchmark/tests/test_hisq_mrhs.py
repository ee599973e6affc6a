"""The batched HISQ cell's yardstick can fail: the configuration is
``hisq24_single``'s but for the batch, a sound run of eight sources a
call is correct under the cell's own limits, an altered or stale answer
in ONE of the eight is not, the lower-precision control is not, and the
needed-bytes count of a batched pass is the stated one.

CPU, the configuration's rehearsal lattice (8^4), the program on its
packed pair route (``QUDA_TPU_PACKED=1``: the batched route on the
resident KS term, XLA stencil), as ``test_hisq.py`` for the
single-source cell.
"""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "hisq24_mrhs8.strange"
N_SRC = 8

from benchmark.tests.test_correct import _run  # noqa: E402


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


def test_configuration_is_the_single_cells_but_for_the_batch():
    run = importlib.import_module("benchmark.run")
    bench, cell, config, traffic, lattice = run.load_cell(CELL)
    single = run.load_cell("hisq24_single.strange")[2]
    assert cell["chips"] == 1 and lattice == (24,) * 4
    assert config["sources_per_call"] == N_SRC
    assert config["entry"] == "invert_multi_src_quda_hisq"
    for key in ("reference", "gauge_param", "invert_param", "widths",
                "lattice", "rehearse_lattice", "control_precision"):
        assert config[key] == single[key], key
    assert config["reduced"] == []
    assert set(single["assumed"]) | {"sources_per_call"} == set(
        config["assumed"])
    assert traffic["mass"] == 0.04 and traffic["res_bound"] <= 1e-4
    mine = sorted(m["name"] for m in bench["per_layer"]
                  if m.get("workloads") == [CELL])
    assert mine == sorted("hisq_mrhs_" + n for n in (
        "dslash_us", "dslash_roofline", "iters", "compute_phase_s",
        "outside_solver_s"))
    for name in ("device_idle_pct", "hbm_peak_gib", "first_call_s"):
        m = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert CELL in m["workloads"]


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
        "benchmark.entry.invert_multi_src_quda_hisq")
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


def test_needed_bytes_of_a_batched_pass_and_hop():
    one = importlib.import_module(
        "benchmark.kernel_models.staggered_eo_hopset")
    hop = importlib.import_module(
        "benchmark.kernel_models.staggered_eo_fat_naik")
    lat = (24,) * 4
    # links once, eight colour vectors in and out
    assert one.needed(lat, n_rhs=N_SRC)["bytes_per_site"] == 576 + 8 * 48
    assert hop.needed(lat, n_rhs=N_SRC)["bytes_per_site"] == 1536
    with open(os.path.join(ROOT, "benchmark", "per_layer",
                           "hisq_mrhs_dslash_roofline.json")) as fh:
        spec = json.load(fh)
    assert spec["args"]["rhs_from_config"] == "sources_per_call"
    assert spec["args"]["model"] == "staggered_eo_hopset"
