"""Observability-schema lint: every trace-event and metric name emitted
anywhere in the package must appear in the canonical registry
(quda_tpu/obs/schema.py), and the registry must carry no name nothing
emits — dashboards and scrape configs key on names, and a renamed or
ad-hoc one breaks them silently.

Since round 17 the AST harvest lives in the unified static-analysis
engine (quda_tpu/analysis, rule ``obs-schema``: unknown-name findings
per emission line, orphan findings anchored at the schema entry) —
this module keeps its historical test names as thin wrappers over the
shared single-parse run, plus the registry-object hygiene half.  The
metrics registry also validates names at RECORD time
(obs/metrics._Registry._check), so the dynamic half is covered even
off-CI."""

import pytest

from quda_tpu import analysis
from quda_tpu.obs import schema as osch


def _findings(substr):
    return [f for f in analysis.run_package().by_rule("obs-schema")
            if not f.suppressed and substr in f.message]


def test_every_emitted_trace_event_is_registered():
    bad = _findings("trace event")
    assert not bad, (
        "trace events emitted without a schema entry (register them "
        "in quda_tpu/obs/schema.py TRACE_EVENTS — cat + doc; an "
        "unregistered event name breaks dashboards silently):\n  "
        + "\n  ".join(f.render() for f in bad))


def test_no_registered_trace_event_is_orphaned():
    bad = _findings("TRACE_EVENTS entry")
    assert not bad, ("schema rot — delete the entry or restore the "
                     "emission site:\n  "
                     + "\n  ".join(f.render() for f in bad))


def test_every_recorded_metric_is_registered():
    bad = _findings("metric ")
    assert not bad, (
        "metrics recorded without a schema entry (register them in "
        "quda_tpu/obs/schema.py METRICS — type + help):\n  "
        + "\n  ".join(f.render() for f in bad))


def test_no_registered_metric_is_orphaned():
    """Gauges the ledger mirrors internally count as emitted through
    their module-level set_gauge literals, so a truly orphaned name
    means dead schema."""
    bad = _findings("METRICS entry")
    assert not bad, ("schema rot — delete the entry or restore the "
                     "recording site:\n  "
                     + "\n  ".join(f.render() for f in bad))


def test_schema_entries_carry_docs():
    for name, meta in osch.TRACE_EVENTS.items():
        assert meta.get("cat") and len(meta.get("doc", "")) > 5, name
    for name, meta in osch.METRICS.items():
        assert meta["type"] in (osch.COUNTER, osch.GAUGE,
                                osch.HISTOGRAM), name
        assert len(meta["help"]) > 10, name
    for name, meta in osch.SPAN_ATTRS.items():
        assert meta["spans"] and len(meta["doc"]) > 10, name


def test_solve_program_counter_and_span_attribute_are_registered(tmp_path):
    """The cached solve program's two signals (solvers/program.py):
    the hit/miss counter and the ``program`` attribute of the solve
    span.  A span attribute set after the fact is validated at record
    time like a metric name."""
    from quda_tpu.obs import trace as otr
    assert osch.metric_type("solve_program_total") == osch.COUNTER
    assert set(osch.SPAN_ATTRS["program"]["spans"]) == {
        "solve:cg", "solve:batched-cg-pairs", "verified_exit", "prepare"}
    otr.stop(flush_files=False)
    otr.span("solve:cg").set(anything="ignored: tracing is off")
    otr.start(str(tmp_path))
    try:
        with otr.span("solve:cg", cat="solver") as sp:
            sp.set(program="hit")
            with pytest.raises(KeyError, match="unregistered span"):
                sp.set(progam="hit")
        assert otr._session.jsonl[-1]["program"] == "hit"
    finally:
        otr.stop(flush_files=False)
