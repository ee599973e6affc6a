"""Build accounting (obs/build.py) and the always-on spans (obs/trace.py).

What jax traces, lowers and compiles is recorded by program and stage
under the span and API call that caused it; a span opens a profiler
annotation of its name whether or not a QUDA_TPU_TRACE session is open,
and none under QUDA_TPU_DO_NOT_PROFILE; the cached programs' misses
carry the seconds of their build; the API's calls name their own parts
(``source_split``, ``prepare``, ``mdag``, ``dispatch``, ``wait``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.obs import build as obuild
from quda_tpu.obs import metrics as omet
from quda_tpu.obs import schema as osch
from quda_tpu.obs import trace as otr
from quda_tpu.solvers import program as sprog
from quda_tpu.utils import config as qconf
from quda_tpu.utils import timer as qtimer


@pytest.fixture(autouse=True)
def _fresh_accounting(monkeypatch):
    """Listeners installed, no records, no session, the profile on."""
    monkeypatch.delenv("QUDA_TPU_DO_NOT_PROFILE", raising=False)
    qconf.reset_cache()
    otr.stop(flush_files=False)
    omet.stop(flush_files=False)
    obuild.install()
    obuild.reset()
    yield
    otr.stop(flush_files=False)
    omet.stop(flush_files=False)
    obuild.reset()
    qconf.reset_cache()


def _programs(tag):
    """Two jitted programs no other test has traced: ``outer_<tag>``
    calls ``inner_<tag>``; ``k`` is static."""
    def inner(x):
        return jnp.sin(x) + 1.0

    def outer(x, k=1):
        return inner_j(x) * k

    inner.__name__ = inner.__qualname__ = f"inner_{tag}"
    outer.__name__ = outer.__qualname__ = f"outer_{tag}"
    inner_j = jax.jit(inner)
    return jax.jit(outer, static_argnames=("k",)), outer.__name__


def _top(records, program):
    return [r for r in records
            if r["program"] == program and r["inside"] is None]


def test_three_stages_under_the_innermost_span_and_api_ordinal():
    prog, name = _programs("stages")
    x = jnp.ones(8)
    with otr.api_span("invert_quda"):
        with otr.phase("setup", "invert_quda"):
            with otr.span("prepare", cat="setup"):
                prog(x).block_until_ready()
    recs = _top(obuild.snapshot(), name)
    assert [r["stage"] for r in recs] == ["trace", "lower", "compile"]
    for r in recs:
        assert r["seconds"] > 0
        assert (r["span"], r["api"], r["ordinal"]) == (
            "prepare", "invert_quda", 1)
        assert r["path"] == "invert_quda > setup > prepare"
    # conftest.py turns the persistent cache off
    assert recs[-1]["cache"] == "off"
    # the jitted function traced inside is part of the outer's trace
    nested = [r for r in obuild.snapshot()
              if r["program"] == "inner_stages"]
    assert [(r["stage"], r["inside"]) for r in nested] == [("trace", name)]
    assert nested[0]["seconds"] <= recs[0]["seconds"]
    rows = [row for row in obuild.by_program(obuild.snapshot())
            if row["program"] in (name, "inner_stages")]
    assert len(rows) == 1 and rows[0]["builds"] == 1
    assert rows[0]["seconds"] == pytest.approx(
        sum(r["seconds"] for r in recs))
    assert name in obuild.summary()


def test_same_key_builds_nothing_and_a_new_static_key_builds_in_call_2():
    prog, name = _programs("keys")
    x, y = jnp.ones(8), jnp.zeros(8)
    with otr.api_span("invert_quda"):
        prog(x)
        built = len(obuild.snapshot())
        prog(y)                          # same key: an executable lookup
        assert len(obuild.snapshot()) == built
    with otr.api_span("load_gauge_quda"):
        pass                             # ordinals count per API name
    with otr.api_span("invert_quda"):
        prog(x, k=2)
    second = _top(obuild.snapshot()[built:], name)
    assert [r["stage"] for r in second] == ["trace", "lower", "compile"]
    assert {(r["api"], r["ordinal"], r["span"]) for r in second} == {
        ("invert_quda", 2, "invert_quda")}


def test_work_outside_any_api_span_reads_none_and_nested_apis_the_outer():
    prog, name = _programs("outside")
    prog(jnp.ones(8))
    assert {(r["api"], r["ordinal"], r["span"], r["path"])
            for r in _top(obuild.snapshot(), name)} == {
        ("none", 0, "none", "")}
    with otr.api_span("invert_multi_src_quda"):
        with otr.api_span("invert_quda"):
            prog(jnp.ones(8), k=3)
    last = _top(obuild.snapshot(), name)[-1]
    assert (last["api"], last["ordinal"], last["span"]) == (
        "invert_multi_src_quda", 1, "invert_quda")


@pytest.mark.parametrize("events,cache", [
    (("compile_requests_use_cache", "cache_hits"), "hit"),
    (("compile_requests_use_cache", "cache_misses"), "miss"),
    ((), "off")])
def test_compile_records_say_what_the_persistent_cache_answered(events,
                                                                cache):
    """jax fires the cache's events inside the compile it times: the
    listeners are driven by hand, as jax drives them."""
    for ev in events:
        obuild._on_event("/jax/compilation_cache/" + ev)
    obuild._on_duration("/jax/core/compile/backend_compile_duration", 0.25,
                        fun_name="jit(prog_cache)")
    obuild._on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                        fun_name="jit(prog_cache)")
    obuild._on_duration("/jax/unrelated/duration", 9.0)
    recs = obuild.snapshot()
    assert [(r["program"], r["stage"], r["cache"]) for r in recs] == [
        ("prog_cache", "compile", cache), ("prog_cache", "compile", "off")]
    row, = obuild.by_program(recs)
    assert row["cache"] == ({cache: 1, "off": 1} if cache != "off"
                            else {"off": 2})
    assert row["builds"] == 2 and row["compile"] == 0.75


def test_records_past_the_cap_are_counted_and_dropped(monkeypatch):
    monkeypatch.setattr(obuild, "MAX_RECORDS", 2)
    for _ in range(5):
        obuild._on_duration("/jax/core/compile/backend_compile_duration",
                            0.1, fun_name="jit(capped)")
    assert len(obuild.snapshot()) == 2 and obuild.dropped() == 3
    assert "3 records dropped" in obuild.summary()


def _trace(program, seconds=1e-4):
    """One trace event of a program, as jax fires it (the start as a
    scalar, then the duration)."""
    obuild._on_start(obuild.TRACE_EVENT, 0.0, fun_name=program)
    obuild._on_duration(obuild.TRACE_EVENT, seconds, fun_name=program)


def test_a_later_calls_repeated_traces_fold_into_one_record():
    """The eager entry of the batched Wilson route traces the same small
    programs again in every call: one record a (program, path), its
    ``repeats`` and ``seconds`` keeping count; a lowering is never
    folded, nor anything of the first call."""
    for call in range(1, 6):
        with otr.api_span("invert_multi_src_quda"):
            with otr.span("prepare", cat="setup"):
                _trace("transpose")
                _trace("transpose")
            with otr.span("mdag", cat="setup"):
                _trace("transpose")
            if call == 4:
                obuild._on_duration(
                    "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5,
                    fun_name="jit(transpose)")
    recs = obuild.snapshot()
    assert [(r["ordinal"], r["span"], r["stage"], r["repeats"])
            for r in recs] == [
        (1, "prepare", "trace", 0), (1, "prepare", "trace", 0),
        (1, "mdag", "trace", 0),
        (2, "prepare", "trace", 7), (2, "mdag", "trace", 3),
        (4, "invert_multi_src_quda", "lower", 0)]
    assert sum(r["seconds"] for r in recs) == pytest.approx(15e-4 + 0.5)
    later = [r for r in recs if r["ordinal"] >= 2 and r["stage"] == "trace"]
    assert sum(1 + r["repeats"] for r in later) == 12
    assert obuild.dropped() == 0


def test_folded_traces_do_not_reach_the_cap(monkeypatch):
    monkeypatch.setattr(obuild, "MAX_RECORDS", 3)
    for _ in range(50):
        with otr.api_span("invert_quda"):
            _trace("reshape")
    assert len(obuild.snapshot()) == 2 and obuild.dropped() == 0
    assert obuild.snapshot()[1]["repeats"] == 48


def test_a_cached_program_miss_finds_the_seconds_of_its_build():
    """``solvers/program._run`` says hit or miss; the seconds of a miss
    are the build records' under the span the caller holds open, in
    this call."""
    def body(x):
        sprog._traces[0] += 1
        return jnp.cos(x) * 3.0
    body.__name__ = body.__qualname__ = "cached_program_under_test"
    program = jax.jit(body)
    other, _ = _programs("elsewhere")
    x, y = jnp.ones(16), jnp.zeros(16)
    assert obuild.seconds_here() == 0.0            # no span open
    with otr.api_span("invert_quda"):
        with otr.span("prepare", cat="setup"):
            other(x)                               # another span's build
        with otr.span("solve:cg", cat="solver"):
            with otr.span("dispatch", cat="solver"):
                _, hit = sprog._run(program, x)
            assert hit is False
            recs = _top(obuild.snapshot(), "cached_program_under_test")
            assert [r["stage"] for r in recs] == ["trace", "lower",
                                                  "compile"]
            assert obuild.seconds_here() == pytest.approx(
                sum(r["seconds"] for r in recs))
            assert obuild.seconds_here() > 0
    with otr.api_span("invert_quda"):
        with otr.span("solve:cg", cat="solver"):
            _, hit = sprog._run(program, y)
            assert hit is True
            assert obuild.seconds_here() == 0.0    # nothing in this call


def test_miss_seconds_reach_the_counter_and_the_span(tmp_path):
    from quda_tpu.interfaces.quda_api import _note_solve_program
    prog, name = _programs("metrics")
    omet.start(str(tmp_path))
    otr.start(str(tmp_path))
    for call in range(2):
        with otr.api_span("invert_quda"):
            with otr.span("solve:cg", cat="solver") as sp:
                n0 = len(obuild.snapshot())
                prog(jnp.ones(8))
                _note_solve_program(sp, "invert_quda", "wilson_xla", "cg",
                                    len(obuild.snapshot()) == n0)
    counters = {(n, dict(lab).get("stage") or dict(lab).get("outcome")):
                (dict(lab), v)
                for (n, lab), v in omet.snapshot()["counters"].items()}
    recs = _top(obuild.snapshot(), name)
    assert len(recs) == 3
    for r in recs:
        lab, v = counters[("program_build_seconds", r["stage"])]
        assert lab["program"] == name and v == pytest.approx(r["seconds"])
    assert not any(dict(lab).get("program") == "inner_metrics"
                   for (n, lab) in omet.snapshot()["counters"])
    # one home for the seconds: the per-program counter
    assert {n for (n, lab) in omet.snapshot()["counters"]} == {
        "program_build_seconds", "solve_program_total"}
    assert counters[("solve_program_total", "miss")][1] == 1.0
    assert counters[("solve_program_total", "hit")][1] == 1.0
    assert osch.metric_type("program_build_seconds") == osch.COUNTER
    spans = [json.loads(ln) for ln in open(otr.stop()["jsonl"])]
    assert [(s["program"], s.get("build_seconds")) for s in spans
            if s["name"] == "solve:cg"] == [
        ("miss", round(sum(r["seconds"] for r in recs), 6)), ("hit", None)]


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation: which names were
    opened and closed, in order."""
    log = []

    def __init__(self, name, **kw):
        assert not kw, "name only: no keyword arguments"
        self.name = name

    def __enter__(self):
        self.log.append(("open", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(_Annotation, "log", [])
    monkeypatch.setattr(otr, "TraceAnnotation", _Annotation)
    return _Annotation.log


def _three_kinds_of_span():
    with otr.api_span("invert_quda", dslash="wilson"):
        with otr.phase("setup", "invert_quda"):
            with otr.span("prepare", cat="setup") as sp:
                sp.set(program="hit")
                where = obuild.where()
    return where


@pytest.mark.parametrize("session", [False, True])
def test_spans_open_their_annotation_with_or_without_a_session(
        annotations, tmp_path, session):
    if session:
        otr.start(str(tmp_path))
    where = _three_kinds_of_span()
    names = ["invert_quda", "setup", "prepare"]
    assert annotations == ([("open", n) for n in names]
                           + [("close", n) for n in reversed(names)])
    assert where == {"span": "prepare", "api": "invert_quda", "ordinal": 1,
                     "path": "invert_quda > setup > prepare"}
    assert obuild.where()["path"] == ""          # every frame popped
    assert otr.enabled() is session
    if not session:
        assert otr._session is None              # nothing was buffered


@pytest.mark.parametrize("session", [False, True])
def test_do_not_profile_silences_the_annotations_unless_a_session_is_open(
        annotations, tmp_path, monkeypatch, session):
    monkeypatch.setenv("QUDA_TPU_DO_NOT_PROFILE", "1")
    qconf.reset_cache()
    if session:
        otr.start(str(tmp_path))
    seconds0 = dict(qtimer.get_profile("invert_quda").seconds)
    where = _three_kinds_of_span()
    assert dict(qtimer.get_profile("invert_quda").seconds) == seconds0
    if session:     # the opt-in session records, and annotates, as before
        assert len(annotations) == 6 and where["api"] == "invert_quda"
    else:
        assert annotations == []
        assert otr.span("a") is otr.span("b", cat="x", k=1) is otr._NOOP
        assert where == {"span": "none", "api": "none", "ordinal": 0,
                         "path": ""}


def test_the_session_writes_what_it_wrote_before(tmp_path):
    """Chrome rows and JSONL lines of a session: the same keys and
    values as before the spans became always-on annotations."""
    otr.start(str(tmp_path))
    with otr.api_span("invert_quda", dslash="wilson"):
        with otr.phase("compute", "invert_quda", route="r"):
            with otr.span("solve:cg", cat="solver", tol=1e-6) as sp:
                sp.set(program="hit")
    otr.event("compile", cat="metrics", api="invert_quda")
    paths = otr.stop()
    lines = [json.loads(ln) for ln in open(paths["jsonl"])]
    assert [{k: v for k, v in ln.items() if k not in ("ts_us", "dur_us")}
            for ln in lines] == [
        {"kind": "span", "name": "solve:cg", "cat": "solver", "depth": 3,
         "tol": 1e-6, "program": "hit"},
        {"kind": "span", "name": "compute", "cat": "compute", "depth": 2,
         "route": "r"},
        {"kind": "span", "name": "invert_quda", "cat": "api", "depth": 1,
         "dslash": "wilson"},
        {"kind": "event", "name": "compile", "cat": "metrics",
         "api": "invert_quda"}]
    assert all(set(ln) >= {"ts_us"} for ln in lines)
    rows = json.load(open(paths["chrome"]))["traceEvents"]
    assert [(e["name"], e["ph"], e["pid"], e["tid"]) for e in rows] == [
        ("solve:cg", "X", 0, 0), ("compute", "X", 0, 0),
        ("invert_quda", "X", 0, 0), ("compile", "i", 0, 0)]
    assert rows[0]["args"] == {"tol": 1e-6, "program": "hit", "depth": 3}
    assert set(rows[0]) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                            "args"}


# -- the API's calls name their parts ---------------------------------------

@pytest.fixture(scope="module")
def api_calls():
    """Two clover invert_quda calls on the resident term and one Wilson
    invert_multi_src_quda call on the resident pair operators (4^3 x 6,
    the XLA stencil: both routes run cached programs), a recording
    annotation class in place of the profiler's: per call, the spans in
    the order they opened and the build records of the call."""
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.interfaces.params import GaugeParam, InvertParam
    mp = pytest.MonkeyPatch()
    mp.setenv("QUDA_TPU_PACKED", "1")
    mp.setenv("QUDA_TPU_PALLAS", "0")
    mp.setenv("QUDA_TPU_MULTI_SRC_SPLIT", "0")
    mp.delenv("QUDA_TPU_DO_NOT_PROFILE", raising=False)
    mp.setattr(_Annotation, "log", [])
    mp.setattr(otr, "TraceAnnotation", _Annotation)
    qconf.reset_cache()
    otr.stop(flush_files=False)
    obuild.install()
    obuild.reset()
    # a lattice of this file's own: what another file of the same
    # worker process has built is not built again, and not recorded
    T, L = 6, 4
    rng = np.random.default_rng(11)

    def field(*lead):
        shape = lead + (T, L, L, L, 4, 3)
        return (rng.standard_normal(shape)
                + 1j * rng.standard_normal(shape)).astype(np.complex64)

    def param(dslash, **kw):
        return InvertParam(dslash_type=dslash, inv_type="cg",
                           solve_type="normop-pc", kappa=0.11, tol=1e-5,
                           maxiter=300, cuda_prec="single",
                           cuda_prec_sloppy="half", **kw)
    gauge = np.broadcast_to(np.eye(3, dtype=np.complex64),
                            (4, T, L, L, L, 3, 3)).copy()
    out = []
    try:
        api.init_quda()
        api.load_gauge_quda(gauge, GaugeParam(X=(L, L, L, T),
                                              cuda_prec="single"))
        api.load_clover_quda(param("clover", csw=1.0))
        for call in (
                lambda: api.invert_quda(field(), param("clover", csw=1.0)),
                lambda: api.invert_quda(field(), param("clover", csw=1.0)),
                lambda: api.invert_multi_src_quda(field(2),
                                                  param("wilson"))):
            del _Annotation.log[:]
            built = len(obuild.snapshot())
            call()
            out.append({
                "opened": [n for what, n in _Annotation.log
                           if what == "open"],
                "closed": sum(w == "close" for w, _ in _Annotation.log),
                "records": obuild.snapshot()[built:]})
        api.end_quda()
    finally:
        mp.undo()
        qconf.reset_cache()
    return out


_SINGLE = ["invert_quda", "setup", "clover_term", "source_split", "prepare",
           "mdag", "compute", "solve:cg", "dispatch", "wait", "epilogue"]


@pytest.mark.parametrize("call,want", [
    (0, _SINGLE), (1, _SINGLE),
    (2, ["invert_multi_src_quda", "setup", "wilson_term", "source_split",
         "prepare", "mdag", "compute", "solve:batched-cg-pairs", "dispatch",
         "wait", "epilogue", "verified_exit", "exit_read"])])
def test_an_api_call_opens_its_named_parts_in_order(api_calls, call, want):
    opened = api_calls[call]["opened"]
    # the resident term's span may open phases of its own (``pack``)
    assert [n for n in opened if n in want] == want
    assert api_calls[call]["closed"] == len(opened)


def test_the_first_call_builds_under_its_spans_and_the_second_nothing(
        api_calls):
    first, second, batch = (c["records"] for c in api_calls)
    assert second == []
    assert {(r["api"], r["ordinal"]) for r in first} == {("invert_quda", 1)}
    assert {(r["api"], r["ordinal"]) for r in batch} == {
        ("invert_multi_src_quda", 1)}
    where = {r["program"]: r["path"] for r in first + batch
             if r["inside"] is None}
    assert where["_cg_reliable_program"] == (
        "invert_quda > compute > solve:cg > dispatch")
    assert where["_verified_exit_program"] == (
        "invert_multi_src_quda > epilogue > verified_exit")
    solve = [r for r in batch if r["program"] == "_batched_cg_pairs_program"]
    assert [r["stage"] for r in solve] == ["trace", "lower", "compile"]
    assert {r["span"] for r in solve} == {"dispatch"}
    # the eager entry work is charged to the span it runs under
    for span in ("source_split", "prepare", "mdag"):
        assert any(r["span"] == span for r in first), span
