"""CI lint over a bench history in the COMMITTED formats: every
BENCH_*.json / MULTICHIP_*.json round must stay consumable by the
compare engine (obs/history.py) FOREVER — each file parses, every
recorded row carries a platform and passed ``bench.gate_row``, and
platform-less legacy rows are confined to a frozen allowlist of
pre-gate rounds so no new round can quietly regress the history schema.

The history is written by the test itself (the root's old records, which
carried numbers from a measurement route that no longer exists, are
gone): one legacy-schema round, one gated round, one round with a
documented dip — the three shapes the committed history had.

Style of tests/test_env_knob_lint.py: a grep-level/static check with
teeth, pure Python, tier-1 safe."""

import json
import math
import os

import pytest

from quda_tpu.obs import history as qhist
from tests.test_bench_history import _dslash_row, _write_round

# Rounds written before the platform/gate schema existed.  FROZEN:
# new files must never join this set — record rows through
# bench.record_row (which stamps platform and gates) and they won't.
LEGACY_OK = {"BENCH_r01.json"}


@pytest.fixture(scope="module")
def histdir(tmp_path_factory):
    d = tmp_path_factory.mktemp("hist")
    # round 1: the pre-gate headline wrapper — no platform anywhere
    legacy = {"metric": "wilson_dslash_gflops_chip", "value": 0.8,
              "unit": "GFLOPS", "vs_baseline": 0.001}
    (d / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "python bench.py", "rc": 0,
         "tail": "WARNING: log line\n" + json.dumps(legacy) + "\n",
         "parsed": legacy}))
    _write_round(d, 2, [_dslash_row(5000.0)])        # gated round
    _write_round(d, 3, [_dslash_row(4250.0)])        # the documented dip
    return str(d)


@pytest.fixture
def _files(histdir):
    return lambda: qhist.history_files(histdir)


def test_history_files_exist_and_parse(_files):
    files = _files()
    assert files, "no BENCH_*/MULTICHIP_* history found"
    for path in files:
        rows, stats = qhist.parse_file(path)
        assert not stats.get("unparseable"), (
            f"{os.path.basename(path)} is not consumable by the "
            "compare engine (obs/history.py)")


def test_recorded_rows_are_platform_keyed_and_gated(_files):
    total = 0
    for path in _files():
        base = os.path.basename(path)
        rows, stats = qhist.parse_file(path)
        total += len(rows)
        if base not in LEGACY_OK:
            assert stats.get("legacy", 0) == 0, (
                f"{base}: {stats['legacy']} recorded row(s) without a "
                "platform — new rounds must record through "
                "bench.record_row so history stays attributable; the "
                "legacy allowlist is frozen")
            assert stats.get("ungated", 0) == 0, (
                f"{base}: {stats['ungated']} row(s) fail "
                "bench.gate_row — impossible rates must die at record "
                "time, never enter committed history")
        for r in rows:
            assert r["platform"], r
            assert isinstance(r["value"], float)
            assert math.isfinite(r["value"]) and r["value"] >= 0, r
    assert total > 0, "committed history yields zero canonical rows"


def test_history_yields_credible_baselines(histdir):
    """The compare gate has something to stand on: at least one series
    with a best-credible baseline exists in the history."""
    hist = qhist.load_history(histdir)
    assert hist.series
    key = next(iter(sorted(hist.series, key=str)))
    best = hist.best(key)
    assert best is not None and best["value"] > 0


def test_legacy_allowlist_is_not_growing(_files):
    """Every allowlisted file still exists (a stale allowlist entry
    hides a rename that silently re-opens the legacy hole)."""
    existing = {os.path.basename(p) for p in _files()}
    assert LEGACY_OK <= existing
