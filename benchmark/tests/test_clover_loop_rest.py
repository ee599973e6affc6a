"""``clover_loop_rest_us`` (PR 50) and its reader,
``readers/trace_outer_loop_rest.py``: the solve's loop outside its four
sloppy fused kernels an iteration, read from the OUTERMOST ``while``
where the iterations run in a loop inside another, and from the one
``while`` where they do not (the parent's capture: then the number is
``trace_loop_rest``'s own).  Hand counts on made-up captures.  CPU."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "clover24_single.light"

from benchmark.readers import trace_loop_rest  # noqa: E402
from benchmark.readers import trace_outer_loop_rest  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "per_layer",
                       "clover_loop_rest_us.json")) as fh:
    SPEC = json.load(fh)

# two traced calls of 1,000 iterations: four bf16 kernels an iteration
# at 200 us, twenty reliable updates of four f32 kernels at 400 us
KERNELS = {
    "dslash_eo_pallas_post.30 bf16<-bf16,bf16":
        {"count": 2000, "seconds": 0.4},
    "dslash_eo_pallas_post.31 bf16<-bf16,bf16":
        {"count": 2000, "seconds": 0.4},
    "dslash_eo_pallas_diag_hop.30 bf16<-bf16,bf16":
        {"count": 2000, "seconds": 0.4},
    "dslash_eo_pallas_diag_hop.31 bf16<-bf16,bf16":
        {"count": 2000, "seconds": 0.4},
    "dslash_eo_pallas_post.12 f32<-f32,f32": {"count": 80, "seconds": 0.032},
    "dslash_eo_pallas_diag_hop.12 f32<-f32,f32":
        {"count": 80, "seconds": 0.032},
    "fusion.140": {"count": 2000, "seconds": 0.024},
}


def _read(reader, kernels):
    return reader.read({"trace": {"kernels": kernels}}, **SPEC["args"])


def test_one_loop_reads_as_the_accepted_reader_does():
    """The parent's capture: one ``while``, the f32-result K2 events
    (``f32<-bf16,bf16``) among the four kernels."""
    ops = {n.replace("diag_hop.3", "diag_hop.2").replace(
        "bf16<-bf16,bf16", "f32<-bf16,bf16") if "diag_hop.3" in n else n: v
        for n, v in KERNELS.items()}
    ops["while.2"] = {"count": 2, "seconds": 1.99}
    want = (1.99 - 1.6) / 2000 * 1e6
    assert _read(trace_outer_loop_rest, ops) == pytest.approx(want)
    assert _read(trace_loop_rest, ops) == pytest.approx(want)


def test_nested_loops_are_counted_once_by_the_outermost():
    """Stretches of iterations in ``while.39`` inside ``while.38``: the
    outer holds the inner and the reliable updates; the accepted reader
    would add the two."""
    ops = dict(KERNELS, **{"while.38": {"count": 2, "seconds": 1.70},
                           "while.39": {"count": 22, "seconds": 1.63}})
    assert _read(trace_outer_loop_rest, ops) == pytest.approx(
        (1.70 - 1.6) / 2000 * 1e6)
    assert _read(trace_loop_rest, ops) == pytest.approx(
        (1.70 + 1.63 - 1.6) / 2000 * 1e6)


@pytest.mark.parametrize("trace", [
    None, {"kernels": {"fusion.1": {"count": 3, "seconds": 0.1}}},
    {"kernels": {"while.2": {"count": 2, "seconds": 1.0},
                 "dslash_eo_pallas_packed.24 bf16<-bf16,bf16":
                     {"count": 4000, "seconds": 0.6}}}],
    ids=["no-trace", "no-loop", "another-cells-kernels"])
def test_nothing_to_read_is_none(trace):
    assert trace_outer_loop_rest.read({"trace": trace},
                                      **SPEC["args"]) is None


def test_the_metric_is_listed_for_the_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    mine = [m for m in bench["per_layer"]
            if m["name"] == "clover_loop_rest_us"]
    assert mine == [{"name": "clover_loop_rest_us", "unit": "us",
                     "better": "lower", "source": "device_trace",
                     "layer": "solver", "moves": "call_s",
                     "workloads": [CELL]}]
    assert SPEC["reader"] == "trace_outer_loop_rest"
