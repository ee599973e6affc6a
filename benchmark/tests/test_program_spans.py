"""``program_spans`` and the two readers of the program's own
instrumentation, held to a small recorded fixture.

``tests/fixtures/program_spans_small.json`` (not ``benchmark/fixtures/``:
``selfcheck.py`` reduces every file there as a device trace) holds
host-plane events of a chip capture (two traced calls of
``wilson24_single.heavy``: the program's spans, the harness's
``bench_call``, one span put outside the calls by hand) and build
records of a chip run's first call, each with the numbers worked out by
hand from them.  CPU, milliseconds: nothing here runs the program.
"""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program_spans                        # noqa: E402
from benchmark.readers import program_build, trace_span    # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "fixtures",
                       "program_spans_small.json")) as fh:
    FIXTURE = json.load(fh)
EVENTS = [tuple(e) for e in FIXTURE["events"]]
RECORDS = FIXTURE["records"]
WANT = FIXTURE["expected"]


@pytest.mark.parametrize("span", sorted(WANT["per_call_s"]))
def test_per_call_seconds_of_a_span(span):
    loaded = program_spans.from_events(EVENTS, [span])
    assert len(loaded["calls"]) == WANT["calls"]
    assert program_spans.per_call(loaded, span) == pytest.approx(
        WANT["per_call_s"][span], rel=1e-9)


def test_a_span_shorter_than_5_ms_is_found():
    """``trace_reduce.load_xplane`` drops host spans under 5 ms; these
    are what the entry path is made of."""
    short = WANT["shorter_than_5_ms"]
    durs = [d for n, _, d in EVENTS if n == short]
    assert durs and max(durs) < 5e6
    assert program_spans.per_call(
        program_spans.from_events(EVENTS, [short]), short) > 0


def test_a_span_outside_the_calls_is_not_counted():
    name = WANT["outside"]["span"]
    loaded = program_spans.from_events(EVENTS, [name])
    calls = loaded["calls"]
    outside = [d for s, d in loaded["spans"][name]
               if not any(c0 <= s < c1 for c0, c1 in calls)]
    assert len(outside) == WANT["outside"]["events"]
    every = sum(d for _, d in loaded["spans"][name])
    assert program_spans.per_call(loaded, name) == pytest.approx(
        (every - sum(outside)) / len(calls) / 1e9)


@pytest.mark.parametrize("events,span", [
    (EVENTS, "no_such_span"),                      # a missing name
    ([e for e in EVENTS if e[0] != "bench_call"], "prepare"),  # no call
    ([], "prepare")])
def test_nothing_to_read_gives_none(events, span):
    assert program_spans.per_call(
        program_spans.from_events(events, [span]), span) is None


def test_newest_xplane_is_the_newest_of_any_cell(tmp_path):
    assert program_spans.newest_xplane(str(tmp_path)) is None
    for i, cell in enumerate(("a.light", "b.heavy")):
        d = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        f = d / "vm.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (1000 + i, 1000 + i))
    assert program_spans.newest_xplane(str(tmp_path)).endswith(
        os.path.join("b.heavy", "plugins", "profile", "2026_01_01",
                     "vm.xplane.pb"))


def test_trace_span_reader(monkeypatch):
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(program_spans, "host_events",
                        lambda path: EVENTS if path == "capture" else ())
    calls = program_spans.from_events(EVENTS, [])["calls"]
    # what trace_reduce.reduce hands the readers of the same capture
    traced = {"trace": {"n_spans": len(calls),
                        "window_s": (calls[-1][1] - calls[0][0]) / 1e9}}
    for span, want in WANT["per_call_s"].items():
        assert trace_span.read(traced, span=span) == pytest.approx(want)
    assert trace_span.read(traced, span="no_such_span") is None
    assert trace_span.read({"trace": None}, span="prepare") is None
    # the newest capture on disk is another run's: not this cell's numbers
    for other in ({"n_spans": len(calls) + 1,
                   "window_s": traced["trace"]["window_s"]},
                  {"n_spans": len(calls),
                   "window_s": traced["trace"]["window_s"] + 1e-3}):
        assert trace_span.read({"trace": other}, span="prepare") is None
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: None)
    assert trace_span.read(traced, span="prepare") is None


@pytest.fixture
def recorded(monkeypatch):
    """The program's build accounting answering with the fixture's
    records."""
    import importlib
    real = importlib.import_module

    def fake(name, *a, **kw):
        if name == "quda_tpu.obs.build":
            return types.SimpleNamespace(
                snapshot=lambda: [dict(r) for r in RECORDS])
        return real(name, *a, **kw)
    monkeypatch.setattr(program_build.importlib, "import_module", fake)


@pytest.mark.parametrize("metric", sorted(WANT["program_build"]))
def test_program_build_reader(recorded, metric):
    case = WANT["program_build"][metric]
    got = program_build.read({}, **case["args"])
    assert got == pytest.approx(case["value"], rel=1e-9)


def test_stages_add_up_to_the_programs(recorded):
    stages = sum(program_build.read({}, calls="first", stage=st)
                 for st in ("trace", "lower", "compile"))
    programs = {r["program"] for r in RECORDS}
    by_program = sum(program_build.read({}, calls="first", programs=[p])
                     or 0.0 for p in programs)
    assert stages == pytest.approx(by_program)
    assert stages == pytest.approx(program_build.read({}, calls="first"))


@pytest.mark.parametrize("repeats, built", [(None, 1), (0, 1), (16, 17)])
def test_a_folded_record_counts_for_every_trace_it_stands_for(
        monkeypatch, repeats, built):
    """The program folds a later call's repeated traces into one record
    with ``repeats`` (the batched Wilson entry: 17 a call); records of an
    older program carry no such key."""
    import importlib
    real = importlib.import_module
    later = [dict(r) for r in RECORDS
             if r["ordinal"] > 1 and r["stage"] == "trace"]
    assert len(later) == 1
    if repeats is not None:
        later[0]["repeats"] = repeats

    def fake(name, *a, **kw):
        if name == "quda_tpu.obs.build":
            return types.SimpleNamespace(snapshot=lambda: later)
        return real(name, *a, **kw)
    monkeypatch.setattr(program_build.importlib, "import_module", fake)
    assert program_build.read({}, calls="later", stage="trace",
                              count="records") == built
    assert program_build.read({}, calls="later", stage="trace") \
        == pytest.approx(later[0]["seconds"])


def test_program_build_reader_finds_nothing(monkeypatch):
    """A program without the accounting (a parent commit), and one that
    recorded nothing under a solve call."""
    import importlib
    real = importlib.import_module

    def missing(name, *a, **kw):
        if name == "quda_tpu.obs.build":
            raise ImportError(name)
        return real(name, *a, **kw)
    monkeypatch.setattr(program_build.importlib, "import_module", missing)
    assert program_build.read({}, calls="first", stage="trace") is None

    def outside(name, *a, **kw):
        if name == "quda_tpu.obs.build":
            return types.SimpleNamespace(snapshot=lambda: [
                dict(r, api="none", ordinal=0) for r in RECORDS])
        return real(name, *a, **kw)
    monkeypatch.setattr(program_build.importlib, "import_module", outside)
    assert program_build.read({}, calls="later", stage="trace",
                              count="records") is None


def test_every_new_metric_names_a_reader_that_is_there():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {c["name"] for c in bench["workloads"]}
    mine = [m for m in bench["per_layer"]
            if m["name"] in WANT["metrics"]]
    assert [m["name"] for m in mine] == WANT["metrics"]
    for m in mine:
        with open(os.path.join(ROOT, "benchmark", "per_layer",
                               m["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
        assert set(m["workloads"]) <= cells and m["workloads"]
