"""The comparison that decides ``correct`` can fail: the lower-precision
control fails it, and so does a run whose timed path is broken.

CPU, the configurations' rehearsal lattice (8^4), the heavy traffic (a
light solve takes minutes on a CPU).  On the chip the same control runs
at the cells' own size through ``benchmark/control.py`` (PERF.md gives
the readings the limits were set from).
"""

import importlib
import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "wilson24_single.heavy"


def _run(argv):
    run = importlib.import_module("benchmark.run")
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(argv)
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    return rc, json.loads(lines[-1]), lines


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_in_lower_precision_is_not_correct(seed):
    """The reference in the program's place with every field stored in
    bfloat16 fails ``res_max``; the program on the same seed passes."""
    control = importlib.import_module("benchmark.control")
    run = importlib.import_module("benchmark.run")
    row = control.one_seed(run, CELL, seed, rehearse=True, control=1,
                           out=lambda *_: None)
    assert row["program"]["correct"], row
    assert not row["control"]["correct"], row
    bound = run.load_json(ROOT, "benchmark", "traffic",
                          "heavy.json")["res_bound"]
    assert row["control"]["res_max"] > 3 * bound, row


def test_sound_rehearsal_run_is_correct():
    rc, result, _ = _run(["--workload", CELL, "--seed", "21", "--seconds",
                          "2", "--trace", "0", "--rehearse"])
    assert rc == 0 and result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"call_s", "src_per_chip_h",
                                      "setup_s"}


@pytest.mark.parametrize("fault", ["answer_altered", "stale_answer"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    """The harness's look for a chip skipped (--rehearse), the rest of a
    run driven with the answer altered where it is produced: one site of
    the solution scaled, or the previous call's solution returned again
    (a step that does not do its work).  ``correct`` comes out false."""
    entry = importlib.import_module("benchmark.entry.invert_quda")
    real = entry.call
    last = {}

    def broken(state, sources):
        x, info = real(state, sources)
        if fault == "answer_altered":
            x = x.at[0, 0, 0, 0, 0].multiply(1.5)
        elif "x" in last:
            x = last["x"]
        last.setdefault("x", x)
        return x, info
    monkeypatch.setattr(entry, "call", broken)
    rc, result, lines = _run(["--workload", CELL, "--seed", "22",
                              "--seconds", "2", "--trace", "0",
                              "--rehearse"])
    assert rc == 0 and result["correct"] is False, result
    assert any(ln.startswith("compare ") and ln.endswith("OVER")
               for ln in lines)


def test_no_accelerator_exits_nonzero_without_a_result(capsys):
    run = importlib.import_module("benchmark.run")
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct"' not in out
