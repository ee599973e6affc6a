"""Compare-engine tests (bench_suite --compare / obs.regress):
synthetic BENCH_*.json fixtures in the driver wrapper format — a clean
run, an injected 15% regression, an ungated garbage row, a platform
mismatch, and solver-iteration inflation — asserting exit codes,
rejection text, and trend-table content.  Pure Python (no jax):
tier-1 safe."""

import json

import pytest

import bench
import bench_suite
from quda_tpu.obs import history as qhist
from quda_tpu.obs import regress as qreg
from quda_tpu.utils import config as qconf


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    qconf.reset_cache()
    bench.reset_recorded_rows()
    yield
    qconf.reset_cache()
    bench.reset_recorded_rows()


def _dslash_row(gflops, name="wilson_pallas_packed", platform="tpu",
                **extra):
    return dict({"suite": "dslash", "name": name, "gflops": gflops,
                 "gbps": round(gflops * 0.85, 1),
                 "secs_per_call": 8e-05, "platform": platform,
                 "lattice": [24, 24, 24, 24]}, **extra)


def _solver_row(iters, gflops=2500.0, name="cg_wilson_pc_pallas_24",
                platform="tpu"):
    return {"suite": "solver", "name": name, "iters": iters,
            "secs": 0.8, "gflops": gflops, "converged": True,
            "platform": platform, "lattice": [24, 24, 24, 24]}


def _write_round(dirpath, n, rows):
    """One committed round in the driver wrapper format: JSON rows in
    the captured-stdout tail, log junk included (the real tails carry
    jax WARNING lines on the same stream)."""
    tail = "WARNING: fixture log line without json\n" + "".join(
        json.dumps(r) + "\n" for r in rows)
    (dirpath / f"BENCH_r{n:02d}.json").write_text(json.dumps(
        {"n": n, "cmd": "python bench_suite.py", "rc": 0, "tail": tail}))


def _run(histdir, tmp_path, extra=()):
    trends = tmp_path / "trends.tsv"
    rc = qreg.main(["--latest", f"--history={histdir}",
                    f"--trends={trends}", *extra])
    return rc, trends


def test_clean_history_exits_zero(tmp_path, capsys):
    d = tmp_path / "hist"
    d.mkdir()
    _write_round(d, 1, [_dslash_row(4800.0)])
    _write_round(d, 2, [_dslash_row(5000.0)])
    _write_round(d, 3, [_dslash_row(4950.0)])   # within 10% of best
    rc, trends = _run(d, tmp_path)
    assert rc == 0
    out = capsys.readouterr().out
    assert "rejected" not in out
    body = trends.read_text()
    assert "dslash/wilson_pallas_packed" in body
    assert "r01:4800" in body and "r02:5000" in body
    # the best-credible baseline column names round 2
    line = next(ln for ln in body.splitlines()
                if ln.startswith("dslash/wilson_pallas_packed\tgflops"))
    cols = line.split("\t")
    assert cols[7] == "5000" and cols[8] == "r02"


def test_injected_regression_fails_loudly(tmp_path, capsys):
    d = tmp_path / "hist"
    d.mkdir()
    _write_round(d, 1, [_dslash_row(5000.0)])
    _write_round(d, 2, [_dslash_row(4250.0)])   # injected 15% regression
    rc, trends = _run(d, tmp_path)
    assert rc != 0
    out = capsys.readouterr().out
    rej = [json.loads(ln) for ln in out.splitlines()
           if '"rejected"' in ln]
    assert rej, out
    assert rej[0]["compare"] == "regression"
    assert "throughput regression" in rej[0]["rejected"]
    assert "15.0% below" in rej[0]["rejected"]
    assert rej[0]["baseline_source"] == "BENCH_r01.json"
    assert trends.exists()


def test_garbage_row_never_becomes_baseline(tmp_path, capsys):
    """The round-5 failure mode as history: a physically impossible row
    in a committed file must be refused as a baseline — otherwise every
    honest later round 'regresses' against garbage."""
    d = tmp_path / "hist"
    d.mkdir()
    _write_round(d, 1, [_dslash_row(5000.0)])
    _write_round(d, 2, [_dslash_row(5100.0),
                        _dslash_row(1.27e11, name="wilson_pallas_packed")])
    _write_round(d, 3, [_dslash_row(4950.0)])
    rc, _ = _run(d, tmp_path)
    assert rc == 0      # 4950 vs credible best 5100, NOT vs 1.27e11
    hist = qhist.load_history(str(d))
    assert hist.stats.get("ungated", 0) >= 1
    key = next(k for k in hist.series
               if k[0] == "dslash/wilson_pallas_packed")
    assert hist.best(key)["value"] == 5100.0


def test_platform_mismatch_is_a_separate_series(tmp_path):
    """A CPU run never regresses against a TPU baseline (or vice
    versa): platform is part of the series key, so the cross-platform
    'comparison' is no_baseline, not a false rejection."""
    d = tmp_path / "hist"
    d.mkdir()
    _write_round(d, 1, [_dslash_row(5000.0, platform="tpu")])
    hist = qhist.load_history(str(d))
    cur = qhist.rows_from_suite_row(_dslash_row(1.5, platform="cpu"),
                                    source="current")
    failures, verdicts = qreg.compare(cur, hist)
    assert failures == 0
    assert {v["compare"] for v in verdicts} == {"no_baseline"}
    # and a platform-LESS row is legacy: counted, never recorded
    stats = {}
    rows = qhist.rows_from_suite_row(
        {"suite": "dslash", "name": "x", "gflops": 5.0}, stats=stats)
    assert rows == [] and stats["legacy"] == 1


def test_iteration_inflation_fails(tmp_path, capsys):
    d = tmp_path / "hist"
    d.mkdir()
    _write_round(d, 1, [_solver_row(100)])
    _write_round(d, 2, [_solver_row(120, gflops=2510.0)])  # +20% iters
    rc, _ = _run(d, tmp_path)
    assert rc != 0
    out = capsys.readouterr().out
    rej = [json.loads(ln) for ln in out.splitlines()
           if '"rejected"' in ln]
    assert any(v["compare"] == "iteration_inflation" for v in rej)
    v = next(v for v in rej if v["compare"] == "iteration_inflation")
    assert "solver-iteration inflation" in v["rejected"]
    assert v["current"] == 120 and v["baseline"] == 100


def test_tolerance_knob_is_respected(tmp_path, monkeypatch):
    d = tmp_path / "hist"
    d.mkdir()
    _write_round(d, 1, [_dslash_row(5000.0)])
    _write_round(d, 2, [_dslash_row(4250.0)])   # -15%
    rc, _ = _run(d, tmp_path, extra=["--tol=0.2"])
    assert rc == 0                               # inside 20%
    monkeypatch.setenv("QUDA_TPU_BENCH_COMPARE_TOL", "0.2")
    rc2, _ = _run(d, tmp_path)                   # knob route
    assert rc2 == 0


def test_headline_record_and_carried_last_tpu_dedupe(tmp_path):
    """bench.py headline wrappers parse too, and the carried last_tpu
    record (repeated verbatim each CPU round until a fresh chip number
    lands) collapses to ONE observation per series."""
    d = tmp_path / "hist"
    d.mkdir()
    chip = {"metric": "wilson_dslash_gflops_chip", "value": 5673.1,
            "unit": "GFLOPS", "platform": "tpu",
            "path": "pallas_packed", "lattice": [24] * 4,
            "paths": {"pallas_packed": 5673.1, "pallas_v3": 1767.5,
                      "pallas_v3_error": "gate failed"},
            "measured_at": "2026-07-31 06:58:44"}
    for n in (1, 2):
        rec = {"metric": "wilson_dslash_gflops_chip", "value": 1.2,
               "unit": "GFLOPS", "platform": "cpu", "path": "xla_pairs",
               "lattice": [8] * 4, "paths": {"xla_pairs": 1.2},
               "last_tpu": chip}
        (d / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "rc": 0, "tail": json.dumps(rec) + "\n",
             "parsed": rec}))
    hist = qhist.load_history(str(d))
    key = next(k for k in hist.series
               if k[0] == "wilson_dslash_gflops_chip" and k[2] == "tpu")
    assert len(hist.series[key]) == 1            # deduped carried copy
    assert hist.best(key)["value"] == 5673.1
    # *_error path entries never become series
    assert not any(k[0].endswith("_error") for k in hist.series)


def test_record_row_accumulates_for_compare(tmp_path):
    """bench.record_row feeds the gate: accepted rows (and only those)
    canonicalize into compare() input."""
    bench.record_row("dslash", _dslash_row(4000.0),
                     banner_platform="tpu", log=lambda s: None)
    bench.record_row("dslash", _dslash_row(1.27e11, name="garbage"),
                     banner_platform="tpu", log=lambda s: None)
    assert len(bench.recorded_rows()) == 1
    assert len(bench.rejected_rows()) == 1
    cur = qreg.canonicalize_recorded(bench.recorded_rows())
    assert {r["metric"] for r in cur} == {"dslash/wilson_pallas_packed"}
    d = tmp_path / "hist"
    d.mkdir()
    _write_round(d, 1, [_dslash_row(5000.0)])
    failures, verdicts = qreg.compare(cur, qhist.load_history(str(d)))
    assert failures >= 1                         # 4000 vs 5000 = -20%


def test_dry_gate_on_committed_history(tmp_path, capsys):
    """Tier-1 enforcement of bench-history consumability: the dry
    compare gate runs over a history in the COMMITTED formats (one
    legacy-schema round, gated rounds, a MULTICHIP round, and one round
    with a documented dip — written here; the root's old records are
    gone) — every file parses, every verdict row is well-formed, and
    any gate failure is the KNOWN dip, so a change that silently breaks
    the history format (or flags an undocumented regression) fails
    here, not on the next chip run.

    When the written history legitimately changes the failure set,
    update _KNOWN_DIPS with it."""
    _KNOWN_DIPS = {"dslash/wilson_pallas_packed"}
    d = tmp_path / "hist"
    d.mkdir()
    legacy = {"metric": "wilson_dslash_gflops_chip", "value": 0.8,
              "unit": "GFLOPS", "vs_baseline": 0.001}   # no platform
    (d / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "rc": 0, "tail": json.dumps(legacy) + "\n",
         "parsed": legacy}))
    _write_round(d, 2, [_dslash_row(5000.0), _solver_row(100)])
    _write_round(d, 3, [_dslash_row(5100.0), _solver_row(101)])
    (d / "MULTICHIP_r03.json").write_text(json.dumps(
        {"n": 3, "rc": 0, "tail": json.dumps(dict(
            _dslash_row(3000.0, name="wilson_sharded"),
            suite="sharded", mesh="t2z2")) + "\n"}))
    _write_round(d, 4, [_dslash_row(4250.0),          # the documented dip
                        _solver_row(100)])
    trends = tmp_path / "trends.tsv"
    rc = bench_suite.main(["--compare", "--dry", f"--trends={trends}",
                           f"--history={d}"])
    out = capsys.readouterr().out
    rows = [json.loads(ln) for ln in out.splitlines() if ln.strip()]
    summary = [r for r in rows if "history_files" in r]
    assert summary, f"no compare summary row in: {out[:500]}"
    s = summary[0]
    # every round loaded and parsed (nothing unparseable, nothing
    # skipped): the dry gate saw the full history
    assert s["history_files"] >= 5
    assert s["current_rows"] > 0
    assert not s["history_stats"].get("unparseable")
    # verdict rows are well-formed and failures stay within the
    # documented set
    verdicts = [r for r in rows
                if r.get("suite") == "compare" and "metric" in r]
    failing = {r["metric"] for r in verdicts if "rejected" in r}
    assert failing == _KNOWN_DIPS, (
        f"dry gate flags {failing}, expected exactly the documented "
        f"dip {_KNOWN_DIPS}")
    assert rc == min(len([r for r in verdicts if "rejected" in r]), 120)
    assert trends.exists() and "metric" in trends.read_text()


def test_bench_suite_dry_compare_delegates(tmp_path, capsys):
    """`bench_suite.py --compare --dry` is the measurement-free gate:
    newest committed round vs the rest, no jax, trends written."""
    d = tmp_path / "hist"
    d.mkdir()
    _write_round(d, 1, [_dslash_row(5000.0)])
    _write_round(d, 2, [_dslash_row(4900.0)])
    trends = tmp_path / "trends.tsv"
    rc = bench_suite.main(["--compare", "--dry", f"--history={d}",
                           f"--trends={trends}"])
    assert rc == 0 and trends.exists()
    _write_round(d, 3, [_dslash_row(4000.0)])
    rc2 = bench_suite.main(["--compare", "--dry", f"--history={d}",
                            f"--trends={trends}"])
    assert rc2 != 0
    assert '"rejected"' in capsys.readouterr().out


def test_ici_and_drift_units_trended_never_gated(tmp_path, capsys):
    """The round-13 metric families: ici_gb (sharded rows' analytic
    comms volume) and cost_drift_ratio become canonical TRENDED series
    — a large move in either direction starts a trend line but never
    fails the gate (the drift LINT owns pass/fail for the ratio)."""
    d = tmp_path / "hist"
    d.mkdir()
    sh = {"suite": "sharded", "name": "wilson_eo_sharded_v2_facefix_24",
          "gflops": 4000.0, "secs_per_call": 1e-3, "platform": "tpu",
          "lattice": [24] * 4, "mesh": [1, 2], "ici_gb": 0.05}
    cm = {"suite": "costmodel", "name": "cost_drift_wilson_v2",
          "form": "wilson_v2", "cost_drift_ratio": 1.5,
          "platform": "cpu", "lattice": [4] * 4}
    _write_round(d, 1, [sh, cm])
    # round 2: comms volume doubles, drift ratio moves — trended only
    _write_round(d, 2, [dict(sh, ici_gb=0.1),
                        dict(cm, cost_drift_ratio=1.9)])
    rc, trends = _run(d, tmp_path)
    assert rc == 0                      # nothing gated
    out = capsys.readouterr().out
    assert "rejected" not in out
    assert '"compare": "trended"' in out
    body = trends.read_text()
    # --latest: round 2 plays "current" (column 11), round 1 is history
    ici = next(ln for ln in body.splitlines() if "\tici_gb\t" in ln)
    assert "r01:0.05" in ici and ici.split("\t")[11] == "0.1"
    drift = next(ln for ln in body.splitlines()
                 if "\tdrift_ratio\t" in ln)
    assert "r01:1.5" in drift and drift.split("\t")[11] == "1.9"
    # a genuine gflops regression in the same rows still gates
    _write_round(d, 3, [dict(sh, gflops=3000.0, ici_gb=0.1)])
    rc3, _ = _run(d, tmp_path)
    assert rc3 != 0
