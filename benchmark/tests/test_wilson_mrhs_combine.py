"""The needed-bytes model of the MRHS hop with the combine epilogue and
the two roofline shares of ``wilson24_mrhs8.light``, each held to the
kernel events it is meant to read: the bare hops by ``wilson_eo_dslash``
(2,112 B a site for eight sources), the hops with the epilogue by
``wilson_eo_dslash_combine`` (2,880).  The kernel times are those of a
chip capture of PR 38 (two traced calls); nothing here runs the
program.
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
                                     wilson_eo_dslash_combine)
from benchmark.readers import trace_roofline               # noqa: E402

LATTICE = (24, 24, 24, 24)
# device_ops of the traced run of the final tree (chiprun_out/C7, PR 38)
KERNELS = {
    "dslash_eo_pallas_packed_mrhs.10 f32<-f32,f32":
        {"count": 2457, "seconds": 2.397889888},
    "dslash_eo_pallas_packed_mrhs.11 f32<-f32,f32":
        {"count": 2457, "seconds": 2.398071218},
    "dslash_eo_pallas_packed_mrhs_combine.10 f32<-f32,f32":
        {"count": 2457, "seconds": 2.9068376},
    "dslash_eo_pallas_packed_mrhs_combine.11 f32<-f32,f32":
        {"count": 2457, "seconds": 2.916252008},
    "multiply_add_fusion.3": {"count": 2457, "seconds": 3.08202218},
}


def spec(metric):
    with open(os.path.join(ROOT, "benchmark", "per_layer",
                           metric + ".json")) as fh:
        return json.load(fh)


def ctx(kernels):
    return {"trace": {"kernels": kernels}, "package": "benchmark",
            "config": {"sources_per_call": 8}, "lattice": LATTICE,
            "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("widths, n_rhs, per_site", [
    ((4, 4, 4), 1, 768 + 96),
    ((4, 4, 4), 8, 2880),
    ((2, 2, 2), 8, 1440),
    ((2, 2, 4), 8, 288 + 8 * (48 + 48 + 96)),
])
def test_combine_model_is_the_bare_hop_and_one_more_tile_a_source(
        widths, n_rhs, per_site):
    link, psi, out = widths
    n = wilson_eo_dslash_combine.needed(
        LATTICE, link_bytes=link, in_bytes=psi, out_bytes=out, n_rhs=n_rhs)
    bare = wilson_eo_dslash.needed(
        LATTICE, link_bytes=link, in_bytes=psi, out_bytes=out, n_rhs=n_rhs)
    assert n["bytes_per_site"] == per_site
    assert n["bytes_per_site"] - bare["bytes_per_site"] == 24 * n_rhs * psi
    assert n["sites"] == 165888 and n["bytes"] == 165888 * per_site
    assert n["flops"] > bare["flops"]


@pytest.mark.parametrize("metric, names", [
    ("dslash_mrhs_roofline", ["dslash_eo_pallas_packed_mrhs.10",
                              "dslash_eo_pallas_packed_mrhs.11"]),
    ("dslash_mrhs_combine_roofline",
     ["dslash_eo_pallas_packed_mrhs_combine.10",
      "dslash_eo_pallas_packed_mrhs_combine.11"]),
])
def test_each_share_reads_its_own_kernel_and_not_the_other(metric, names):
    rx = re.compile(spec(metric)["args"]["pattern"])
    assert [n.split(" ")[0] for n in KERNELS if rx.search(n)] == names
    # the microseconds beside each share read the same events
    us = re.compile(spec(metric.replace("_roofline", "_us"))
                    ["args"]["pattern"])
    assert [n for n in KERNELS if us.search(n)] \
        == [n for n in KERNELS if rx.search(n)]


@pytest.mark.parametrize("metric, per_site, percent", [
    ("dslash_mrhs_roofline", 2112, 43.83),
    ("dslash_mrhs_combine_roofline", 2880, 49.23),
])
def test_share_of_the_roofline_from_the_recorded_times(metric, per_site,
                                                       percent):
    value = trace_roofline.read(ctx(KERNELS), **spec(metric)["args"])
    rx = re.compile(spec(metric)["args"]["pattern"])
    hits = [k for n, k in KERNELS.items() if rx.search(n)]
    by_hand = 100.0 * (sum(k["count"] for k in hits) * 165888 * per_site
                       / 819e9) / sum(k["seconds"] for k in hits)
    assert value == pytest.approx(by_hand, rel=1e-12)
    assert value == pytest.approx(percent, abs=0.01)
    assert value < 100.0


def test_no_such_kernel_reads_nothing():
    """The parent of PR 38 (one name for both hops), or a route whose
    second hop falls back to the bare kernel and XLA's combine."""
    parent = {n.replace("_combine", ""): k for n, k in KERNELS.items()
              if "_combine" not in n}
    assert trace_roofline.read(
        ctx(parent), **spec("dslash_mrhs_combine_roofline")["args"]) is None
    untraced = dict(ctx(KERNELS), trace=None)
    assert trace_roofline.read(
        untraced, **spec("dslash_mrhs_combine_roofline")["args"]) is None
