"""The s-block kernel's needed bytes and its two per-layer readers
(PR 44): ``kernel_models/mobius_sblock.py`` states Ls planes of a
24-real spinor in and out a 4-d site at the widths each traced call
really had and nothing else; ``mobius_sblock_kernel_us`` reads the plain
kernel's bf16 -> bf16 events, ``mobius_sblock_kernel_roofline`` every
signature of the plain kernel; the accumulate kernel, whose bytes
differ, is matched by neither; a capture without the kernel (the parent
commit's) reads nothing and raises nothing.  ``test_mobius.py`` pins the
cell's own metrics to PR 42's eight (``conftest.py``: a strict xfail
until a ``benchmark`` PR appends the two names): its every assertion
runs here against the listing without them.  CPU."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "mobius24_single.strange"

from benchmark.kernel_models import mobius_sblock  # noqa: E402
from benchmark.readers import trace_kernel, trace_roofline  # noqa: E402


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "per_layer",
                           metric + ".json")) as fh:
        return json.load(fh)


def _ctx(kernels):
    run = importlib.import_module("benchmark.run")
    _, _, config, _, lattice = run.load_cell(CELL)
    return {"trace": {"kernels": kernels}, "config": config,
            "lattice": lattice, "device_kind": "TPU v5 lite",
            "package": "benchmark"}


@pytest.mark.parametrize("widths,per_site", [
    ((2, 2), 1152), ((4, 4), 2304), ((2, 4), 1728), ((4, 2), 1728)])
def test_needed_bytes_are_the_planes_in_and_out(widths, per_site):
    """24^4, Ls 12: 165,888 4-d sites, 12 x 24 reals in and out; the
    width of the last operand (the f32 blocks) moves nothing."""
    for link_bytes in (2, 4):
        need = mobius_sblock.needed((24, 24, 24, 24), link_bytes=link_bytes,
                                    in_bytes=widths[0], out_bytes=widths[1],
                                    n_rhs=12)
        assert need["sites"] == 165888
        assert need["bytes_per_site"] == per_site
        assert need["bytes"] == 165888 * per_site
        assert need["flops"] == 165888 * 2 * 24 * 144


def test_the_two_readers_return_the_hand_count():
    """Two traced calls of 200 iterations: four bf16 -> bf16 products an
    iteration at 450 us, 30 reliable updates of four f32 products and
    five in the exits at 800 us, one product of the entry f32 -> bf16;
    the accumulate kernel, a hop and a fusion beside them."""
    name = "mobius_sblock_pallas"
    kernels = {
        f"{name}.40 bf16<-bf16,f32": {"count": 1000, "seconds": 0.45},
        f"{name}.41 bf16<-bf16,f32": {"count": 600, "seconds": 0.27},
        f"{name}.32 f32<-f32,f32": {"count": 130, "seconds": 0.104},
        f"{name}.3 bf16<-f32,f32": {"count": 2, "seconds": 0.0013},
        "mobius_sblock_axpy_pallas.20 bf16<-f32,f32":
            {"count": 800, "seconds": 8.0},
        "dslash_eo_pallas_packed_mrhs.32 bf16<-bf16,bf16":
            {"count": 1600, "seconds": 2.4},
        "fusion.12": {"count": 400, "seconds": 0.5},
    }
    ctx = _ctx(kernels)
    read = lambda reader, metric: reader.read(ctx, **_spec(metric)["args"])
    assert read(trace_kernel, "mobius_sblock_kernel_us") == pytest.approx(
        450.0)
    sites, bw = 165888, 819e9
    floor = sites * (1600 * 1152 + 130 * 2304 + 2 * 1728) / bw
    assert read(trace_roofline,
                "mobius_sblock_kernel_roofline") == pytest.approx(
        100 * floor / (0.45 + 0.27 + 0.104 + 0.0013))
    assert _spec("mobius_sblock_kernel_roofline")["args"][
        "rhs_from_config"] == "Ls" and ctx["config"]["Ls"] == 12


@pytest.mark.parametrize("trace", [
    None,
    {"kernels": {"dslash_eo_pallas_packed_mrhs.32 bf16<-bf16,bf16":
                 {"count": 1600, "seconds": 2.4},
                 "fusion.377": {"count": 400, "seconds": 0.5}}}],
    ids=["no-trace", "the-parents-capture"])
def test_without_the_kernel_the_readers_return_nothing(trace):
    ctx = dict(_ctx({}), trace=trace)
    for reader, metric in ((trace_kernel, "mobius_sblock_kernel_us"),
                           (trace_roofline,
                            "mobius_sblock_kernel_roofline")):
        assert reader.read(ctx, **_spec(metric)["args"]) is None


NEW = ("mobius_sblock_kernel_us", "mobius_sblock_kernel_roofline")


def test_the_accepted_listing_test_holds_without_the_two_new_names(
        monkeypatch):
    from benchmark.tests import test_mobius
    run = importlib.import_module("benchmark.run")
    load_cell = run.load_cell

    def as_accepted(*args):
        bench, *rest = load_cell(*args)
        return (dict(bench, per_layer=[m for m in bench["per_layer"]
                                       if m["name"] not in NEW]), *rest)
    monkeypatch.setattr(run, "load_cell", as_accepted)
    test_mobius.test_the_action_is_the_configurations_and_the_cell_is_listed()


def test_the_stale_registry_is_closed():
    """``conftest.STALE`` xfails one accepted test, for PR 44's forced
    conflict alone; it is deleted by ROADMAP B9's PR, never added to."""
    from benchmark.tests import conftest
    assert list(conftest.STALE) == [
        "test_mobius.py::"
        "test_the_action_is_the_configurations_and_the_cell_is_listed"]


def test_both_metrics_are_listed_for_the_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for metric, unit in zip(NEW, ("us", "%")):
        m = listed[metric]
        assert (m["unit"], m["layer"], m["moves"], m["source"],
                m["workloads"]) == (unit, "kernels", "call_s",
                                    "device_trace", [CELL])
