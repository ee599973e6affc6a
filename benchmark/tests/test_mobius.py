"""The Möbius configuration's yardstick can fail, and states what it
measures: the reference's whole-lattice M (a loop over s, no matrix) is
the program's ``DiracMobius.M``, its action is the configuration's and
the traffic file's, the wall source is built from the 4-d source alone,
all Ls x 4 rows of a solution are held (an altered row is not correct),
the lower-precision control is not correct and a sound run is, and the
per-layer readers return what a hand count gives on this cell's kernel
names.

CPU; the operator checks at 4^4 and one lattice of four extents at
Ls 4, the control and the sound run at the configuration's rehearsal
lattice (4^4 x Ls 12) on the pair route (QUDA_TPU_PACKED=1, the XLA
stencil: the route the chip takes, without its kernels) under the
cell's own limits; the control's CG is cut at 300 iterations: in
bfloat16 it stalls orders above the limit long before.
"""

import importlib
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "mobius24_single.strange"

from benchmark import correct, data  # noqa: E402
from benchmark.reference import mobius as ref  # noqa: E402
from benchmark.readers import (trace_kernel, trace_loop_rest,  # noqa: E402
                               trace_roofline)
from benchmark.tests.test_correct import _run  # noqa: E402


@pytest.fixture(autouse=True)
def pair_route(monkeypatch):
    from quda_tpu.utils import config as qconf
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    qconf.reset_cache()
    yield
    monkeypatch.undo()
    qconf.reset_cache()


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "per_layer",
                           metric + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("lat", [(4, 4, 4, 4), (4, 6, 2, 8)],
                         ids=["4x4x4x4", "4x6x2x8"])
@pytest.mark.parametrize("ap", [True, False],
                         ids=["antiperiodic", "periodic"])
def test_reference_is_the_programs_full_operator(lat, ap):
    """M and M^dag of the reference against the canonical DiracMobius at
    the module's action and Ls 4, to 1e-6."""
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.domain_wall import DiracMobius
    ls = 4
    u = data.su3_field(data.key_of(2 ** 31 + 5, 0), (4,), lat, 0.7)
    d = DiracMobius(data.to_canonical_gauge(u, lat),
                    LatticeGeometry(tuple(reversed(lat))), ls, ref.M5,
                    ref.MF, ref.B5, ref.C5, antiperiodic_t=ap)
    psi = data.gaussian_sources(data.key_of(7, 1), lat, ls)
    canon = data.to_canonical_spinors(psi, lat)
    links = ref.fold_boundary(u, ap)
    for dagger, prog in ((False, d.M), (True, d.Mdag)):
        mine = ref.apply_m(links, psi, ref.KAPPA_B, lat[3], dagger=dagger)
        assert _rel(data.to_canonical_spinors(mine, lat),
                    prog(canon)) < 1e-6
    # the harness's row layout is the same field
    rows = psi.reshape((-1,) + psi.shape[2:])
    np.testing.assert_array_equal(
        np.asarray(ref.apply_m(links, rows, 0.0, lat[3])),
        np.asarray(ref.apply_m(links, psi, 0.0, lat[3]).reshape(rows.shape)))


def test_mdag_is_the_adjoint():
    lat, ls = (4, 4, 4, 4), 4
    links = ref.fold_boundary(
        data.su3_field(data.key_of(3, 0), (4,), lat, 0.7), True)
    a = data.gaussian_sources(data.key_of(1, 1), lat, ls)
    b = data.gaussian_sources(data.key_of(2, 1), lat, ls)
    dot = lambda x, y: complex(jnp.sum(jnp.conj(x) * y))
    lhs = dot(a, ref.apply_m(links, b, 0.0, 4))
    rhs = dot(ref.apply_m(links, a, 0.0, 4, dagger=True), b)
    assert abs(lhs - rhs) < 1e-5 * abs(lhs)


def test_the_action_is_the_configurations_and_the_cell_is_listed():
    run = importlib.import_module("benchmark.run")
    bench, cell, config, traffic, _ = run.load_cell(CELL)
    ip, widths = config["invert_param"], config["widths"]
    assert (ref.LS, ref.B5, ref.C5, ref.M5) == (
        ip["Ls"], ip["b5"], ip["c5"], -ip["m5"])
    assert (ref.LS, ref.B5, ref.C5, ref.M5) == (
        widths["Ls"], widths["b5"], widths["c5"], widths["m5"])
    assert config["Ls"] == ref.LS == 12
    assert ref.MF == traffic["mass"] == 0.03
    assert abs(traffic["kappa"] - ref.KAPPA_B) < 1e-15
    assert config["reference"] == "mobius" and cell["chips"] == 1
    assert config["entry"] == "invert_quda_mobius"
    assert config["sources_per_call"] == 1 and config["reduced"] == []
    assert ip["dslash_type"] == "mobius" and ip["solve_type"] == "normop-pc"
    assert {"action", "volume", "mass", "tol", "links",
            "source"} <= set(config["assumed"])
    assert len(config["source"]) <= 200
    assert traffic["res_bound"] <= 1e-4 and traffic["agree_bound"] == 0.1
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted("mobius_" + n for n in (
        "iters", "compute_phase_s", "outside_solver_s", "hop_bf16_us",
        "hop_us", "hop_roofline", "sblock_us", "sblock_share_pct"))
    shared = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in mine]
    assert sorted(shared) == sorted((
        "device_idle_pct", "hbm_peak_gib", "first_call_s",
        "entry_prepare_s", "solve_dispatch_s", "solve_wait_s",
        "exit_read_s", "first_call_trace_s", "first_call_lower_s",
        "first_call_compile_s", "first_call_solve_program_s",
        "first_call_exit_program_s", "first_call_eager_s",
        "first_call_eager_programs", "window_programs_built"))


def test_wall_source_and_every_row_are_read():
    lat = (4, 4, 4, 4)
    links = ref.fold_boundary(
        data.su3_field(data.key_of(3, 0), (4,), lat, 0.7), True)
    b = data.gaussian_sources(data.key_of(1, 1), lat, 1)[0]
    wall = ref.wall_source(b)
    assert wall.shape == (ref.LS,) + b.shape
    assert float(jnp.abs(wall[1:-1]).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(wall[0, :2]), np.asarray(b[:2]))
    np.testing.assert_array_equal(np.asarray(wall[-1, 2:]),
                                  np.asarray(b[2:]))
    assert float(jnp.abs(wall[0, 2:]).max()) == 0.0
    assert float(jnp.abs(wall[-1, :2]).max()) == 0.0
    x = data.gaussian_sources(data.key_of(2, 1), lat, ref.LS)
    rows = x.reshape((-1,) + x.shape[2:])
    want = ref.rel_residual(links, ref.KAPPA_B, 4, b, rows)
    assert want > 0.1
    assert ref.rel_residual(links, 0.0, 4, b, x) == want   # kappa not read
    for row in (0, 5, 4 * ref.LS - 1):
        worse = rows.at[row].multiply(50.0)
        assert ref.rel_residual(links, 0.0, 4, b, worse) > 2 * want
    assert np.isnan(ref.rel_residual(links, 0.0, 4, b,
                                     rows.at[9].set(jnp.nan)))
    with pytest.raises(ValueError, match="s-slices"):
        ref.rel_residual(links, 0.0, 4, b, rows[:16])


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_in_lower_precision_is_not_correct(seed):
    control = importlib.import_module("benchmark.control")
    run = importlib.import_module("benchmark.run")
    row = control.one_seed(run, CELL, seed, rehearse=True, control=1,
                           control_maxiter=300, out=lambda *_: None)
    assert row["program"]["correct"], row
    assert not row["control"]["correct"], row
    bound = run.load_cell(CELL)[3]["res_bound"]
    assert row["control"]["res_max"] > 3 * bound, row


@pytest.mark.parametrize("row", [0, 23, 47])
def test_an_altered_row_is_not_correct(row):
    """A sound call's 4 Ls rows pass ``correct.compare`` under the
    cell's limits; with ONE row scaled by 1 + 1e-2 (any s, any spin)
    the same call does not."""
    run = importlib.import_module("benchmark.run")
    _, _, config, traffic, lattice = run.load_cell(CELL, rehearse=True)
    entry = run.module("entry", config["entry"])
    links = data.links_for(31, traffic, lattice)
    state = entry.open(config, traffic,
                       data.to_canonical_gauge(links, lattice))
    try:
        b = data.gaussian_sources(data.key_of(31, 1000), lattice, 1)
        x, info = entry.call(state, data.to_canonical_spinors(b, lattice))
    finally:
        entry.close(state)
    assert x.shape == (1,) + tuple(lattice) + (4 * ref.LS, 3)
    assert info["converged"] == [True]
    kept = data.from_canonical_spinors(x)
    ref_links = run.folded_links(ref, config, links)
    quiet = lambda *_: None

    def check(solutions):
        return correct.compare(
            ref, ref_links, float(traffic["kappa"]), lattice[3],
            [{"label": "call", "sources": b, "solutions": solutions,
              "true_res": info["true_res"]}], traffic, out=quiet)
    assert check(kept)["correct"]
    assert not check(kept.at[0, row].multiply(1.01))["correct"]


def test_sound_rehearsal_run_is_correct():
    rc, result, _ = _run(["--workload", CELL, "--seed", str(2 ** 31 + 21),
                          "--seconds", "2", "--trace", "0", "--rehearse"])
    assert rc == 0 and result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"call_s", "src_per_chip_h",
                                      "setup_s"}


def test_the_cells_readers_return_the_hand_count():
    """Two traced calls of 200 iterations: a 4.0 s ``while`` each, four
    bf16 hops an iteration at 1,500 us (three bf16 out, one f32 out),
    a reliable update's four f32 hops every 20 iterations and five a
    call in the entry and exit at 1,400 us.  By hand: bf16 1,600 events
    1,500 us; f32 2 x (40 + 5) = 90 events 1,400 us; the loop's rest
    8.0 - (2.4 + 0.126) = 5.474 s over 1,690 / 4 iterations; needed
    bytes a site 1,440 (bf16 -> bf16), 2,016 (bf16 -> f32), 2,880 (f32)
    on 165,888 sites at 819 GB/s."""
    name = "dslash_eo_pallas_packed_mrhs"
    kernels = {
        "while.3": {"count": 2, "seconds": 8.0},
        f"{name}.32 bf16<-bf16,bf16": {"count": 1200, "seconds": 1.8},
        f"{name}.33 f32<-bf16,bf16": {"count": 400, "seconds": 0.6},
        f"{name}.7 f32<-f32,f32": {"count": 90, "seconds": 0.126},
        f"{name}_combine.2 f32<-f32,f32": {"count": 9, "seconds": 9.0},
        "dslash_eo_pallas_packed.5 bf16<-bf16,bf16":
            {"count": 7, "seconds": 7.0},
        "fusion.377": {"count": 400, "seconds": 0.5},
    }
    run = importlib.import_module("benchmark.run")
    _, _, config, _, lattice = run.load_cell(CELL)
    ctx = {"trace": {"kernels": kernels}, "config": config,
           "lattice": lattice, "device_kind": "TPU v5 lite",
           "package": "benchmark"}
    read = lambda reader, metric: reader.read(ctx, **_spec(metric)["args"])
    assert read(trace_kernel, "mobius_hop_bf16_us") == pytest.approx(1500.0)
    assert read(trace_kernel, "mobius_hop_us") == pytest.approx(1400.0)
    rest = 8.0 - 1.8 - 0.6 - 0.126
    assert read(trace_loop_rest, "mobius_sblock_us") == pytest.approx(
        rest / (1690 / 4) * 1e6)
    assert read(trace_loop_rest, "mobius_sblock_share_pct") == pytest.approx(
        100 * rest / 8.0)
    sites, bw = 165888, 819e9
    floor = sites * (1200 * 1440 + 400 * 2016 + 90 * 2880) / bw
    assert read(trace_roofline, "mobius_hop_roofline") == pytest.approx(
        100 * floor / (1.8 + 0.6 + 0.126))
    empty = dict(ctx, trace=None)
    for reader, metric in ((trace_kernel, "mobius_hop_bf16_us"),
                           (trace_loop_rest, "mobius_sblock_us"),
                           (trace_roofline, "mobius_hop_roofline")):
        assert reader.read(empty, **_spec(metric)["args"]) is None
