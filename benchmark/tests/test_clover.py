"""The clover configuration's yardstick can fail, and states what it
measures: the lower-precision control is not correct, a sound run is,
the reference's CSW is the configuration's, and the needed-bytes counts
of the two fused kernels are the stated ones.

CPU, the configuration's rehearsal lattice (8^4).  The control runs at
the heavy traffic's kappa (``control.one_seed``'s override: a light
solve in bfloat16 takes minutes on a CPU) under the cell's own limits;
the sound run is the cell as it stands (about a minute here).
"""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "clover24_single.light"

from benchmark.tests.test_correct import _run  # noqa: E402


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_in_lower_precision_is_not_correct(seed):
    control = importlib.import_module("benchmark.control")
    run = importlib.import_module("benchmark.run")
    row = control.one_seed(run, CELL, seed, rehearse=True, kappa=0.124,
                           control=1, out=lambda *_: None)
    assert row["program"]["correct"], row
    assert not row["control"]["correct"], row
    bound = run.load_cell(CELL)[3]["res_bound"]
    assert row["control"]["res_max"] > 3 * bound, row


def test_sound_rehearsal_run_is_correct():
    rc, result, _ = _run(["--workload", CELL, "--seed", "21", "--seconds",
                          "2", "--trace", "0", "--rehearse"])
    assert rc == 0 and result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"call_s", "src_per_chip_h",
                                      "setup_s"}


def test_reference_csw_is_the_configurations():
    run = importlib.import_module("benchmark.run")
    ref = importlib.import_module("benchmark.reference.clover")
    config = run.load_cell(CELL)[2]
    assert config["reference"] == "clover"
    assert ref.CSW == config["invert_param"]["csw"]


def test_needed_bytes_of_the_fused_kernels():
    post = importlib.import_module("benchmark.kernel_models.clover_eo_post")
    diag = importlib.import_module(
        "benchmark.kernel_models.clover_eo_diag_hop")
    lat = (24,) * 4
    assert post.needed(lat)["bytes_per_site"] == 1344
    assert diag.needed(lat)["bytes_per_site"] == 1440
    half = dict(link_bytes=2, in_bytes=2, out_bytes=2)
    assert post.needed(lat, **half)["bytes_per_site"] == 672
    assert diag.needed(lat, **half)["bytes_per_site"] == 720
    # the sloppy operator's call: bf16 in, f32 out
    assert diag.needed(lat, link_bytes=2, in_bytes=2,
                       out_bytes=4)["bytes_per_site"] == 768
    assert post.needed(lat)["sites"] == 165888
    assert post.needed(lat)["flops"] == 165888 * (1320 + 504)
