"""The needed-bytes model of the MRHS hop with the residual form of the
combine epilogue (the last hop of a batched CG iteration, PR 39) and the
three roofline shares of ``wilson24_mrhs8.light``, each held to the
kernel events it is meant to read: the bare hops by ``wilson_eo_dslash``
(2,112 B a site for eight sources), the first ``M``'s fused hop by
``wilson_eo_dslash_combine`` (2,880), the last hop by
``wilson_eo_dslash_residual`` (3,648).  The bare and combine times are
those of a chip capture of PR 38 (two traced calls, there two combine
kernels an iteration); the residual hop's is ISSUE 39's prediction,
1,325 us an event: nothing here runs the program, and no number here
is a chip reading of PR 39.
"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.kernel_models import (wilson_eo_dslash,     # noqa: E402
                                     wilson_eo_dslash_combine,
                                     wilson_eo_dslash_residual)
from benchmark.readers import (trace_kernel,               # noqa: E402
                               trace_roofline)

LATTICE = (24, 24, 24, 24)
SITES = 165888
PREDICTED_US = 1325.0
KERNELS = {
    "dslash_eo_pallas_packed_mrhs.10 f32<-f32,f32":
        {"count": 2457, "seconds": 2.397889888},
    "dslash_eo_pallas_packed_mrhs.11 f32<-f32,f32":
        {"count": 2457, "seconds": 2.398071218},
    "dslash_eo_pallas_packed_mrhs_combine.5 f32<-f32,f32":
        {"count": 2457, "seconds": 2.9068376},
    "dslash_eo_pallas_packed_mrhs_residual.5 f32<-f32,f32":
        {"count": 2457, "seconds": 2457 * PREDICTED_US * 1e-6},
    "multiply_add_fusion.3": {"count": 2457, "seconds": 3.08202218},
}
METRICS = {"dslash_mrhs": ("dslash_eo_pallas_packed_mrhs.1", 2112),
           "dslash_mrhs_combine":
               ("dslash_eo_pallas_packed_mrhs_combine.", 2880),
           "dslash_mrhs_residual":
               ("dslash_eo_pallas_packed_mrhs_residual.", 3648)}


def spec(metric):
    with open(os.path.join(ROOT, "benchmark", "per_layer",
                           metric + ".json")) as fh:
        return json.load(fh)


def ctx(kernels):
    return {"trace": {"kernels": kernels}, "package": "benchmark",
            "config": {"sources_per_call": 8}, "lattice": LATTICE,
            "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("widths, n_rhs, per_site", [
    ((4, 4, 4), 1, 768 + 2 * 96),
    ((4, 4, 4), 8, 3648),
    ((2, 2, 2), 8, 1824),
    ((2, 2, 4), 8, 288 + 8 * (3 * 48 + 96)),
])
def test_residual_model_is_the_combine_hop_and_one_more_tile_a_source(
        widths, n_rhs, per_site):
    link, psi, out = widths
    kw = dict(link_bytes=link, in_bytes=psi, out_bytes=out, n_rhs=n_rhs)
    n = wilson_eo_dslash_residual.needed(LATTICE, **kw)
    fused = wilson_eo_dslash_combine.needed(LATTICE, **kw)
    bare = wilson_eo_dslash.needed(LATTICE, **kw)
    assert n["bytes_per_site"] == per_site
    assert n["bytes_per_site"] - fused["bytes_per_site"] == 24 * n_rhs * psi
    assert n["bytes_per_site"] - bare["bytes_per_site"] \
        == 2 * 24 * n_rhs * psi
    assert n["sites"] == SITES and n["bytes"] == SITES * per_site
    assert n["flops"] > fused["flops"] > bare["flops"]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_pattern_reads_its_own_kernel_and_no_other(metric):
    prefix = METRICS[metric][0]
    for kind in ("_roofline", "_us"):
        rx = re.compile(spec(metric + kind)["args"]["pattern"])
        hits = [n for n in KERNELS if rx.search(n)]
        assert hits and all(n.startswith(prefix) for n in hits), hits
        assert hits == [n for n in KERNELS if n.startswith(prefix)]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_share_of_the_roofline_by_hand_is_the_readers(metric):
    prefix, per_site = METRICS[metric]
    value = trace_roofline.read(ctx(KERNELS),
                                **spec(metric + "_roofline")["args"])
    hits = [k for n, k in KERNELS.items() if n.startswith(prefix)]
    by_hand = 100.0 * (sum(k["count"] for k in hits) * SITES * per_site
                       / 819e9) / sum(k["seconds"] for k in hits)
    assert value == pytest.approx(by_hand, rel=1e-12)
    assert value < 100.0


@pytest.mark.parametrize("us, percent", [
    (PREDICTED_US, 55.77),          # ISSUE 39's 1,300-1,350 us
    (PREDICTED_US / 1.2, 66.92),    # a hop a fifth faster than that
])
def test_residual_share_stays_under_the_guard(us, percent):
    name = "dslash_eo_pallas_packed_mrhs_residual.5 f32<-f32,f32"
    kernels = dict(KERNELS, **{name: {"count": 2457,
                                      "seconds": 2457 * us * 1e-6}})
    args = spec("dslash_mrhs_residual_roofline")["args"]
    value = trace_roofline.read(ctx(kernels), **args)
    assert value == pytest.approx(percent, abs=0.01) and value < 105.0
    assert value == pytest.approx(
        100.0 * SITES * 3648 / 819e9 / (us * 1e-6), rel=1e-12)


def test_no_such_kernel_reads_nothing():
    """The parent of PR 39 (two combine hops an iteration, no residual
    form), or a route whose last hop falls back to the combine kernel
    and XLA's update: both new metrics are absent, nothing raises."""
    parent = {n: k for n, k in KERNELS.items() if "_residual" not in n}
    for c in (ctx(parent), dict(ctx(KERNELS), trace=None)):
        assert trace_roofline.read(
            c, **spec("dslash_mrhs_residual_roofline")["args"]) is None
        assert trace_kernel.read(
            c, **spec("dslash_mrhs_residual_us")["args"]) is None
    assert trace_kernel.read(
        ctx(KERNELS), **spec("dslash_mrhs_residual_us")["args"]
    ) == pytest.approx(PREDICTED_US)
