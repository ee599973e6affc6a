"""The multi-shift HISQ configuration's yardstick can fail, and states
what it measures: the reference's A = 4m^2 - D_eo D_oe (the full-lattice
D and a site mask) is the program's even-odd pair operator, its shifts
are the traffic file's, only spin row 0 and the even sites are read,
every one of the fourteen shift rows is held (an altered row is not
correct), the lower-precision control is not correct and a sound run
is, and the reader of the loop's rest returns what a hand count gives.

CPU; the operator checks at 4^4 and one lattice of four extents, the
control and the sound run at the configuration's rehearsal lattice
(8^4) on the pair route (QUDA_TPU_PACKED=1, the XLA stencil: the route
the chip takes, without its kernels) under the cell's own limits; the
control's CG is cut at 400 iterations: in bfloat16 it stalls three
orders above the limit long before.
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
CELL = "hisq24_multishift.strange"
KAPPA, MASS = 1.0 / (2.0 * 4.04), 0.04
N = 14                                  # the configuration's shifts

from benchmark import correct, data  # noqa: E402
from benchmark.reference import hisq_shifted as ref  # noqa: E402
from benchmark.readers import (program_build_api,  # noqa: E402
                               trace_loop_rest)
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


def _rows(lat, seed=7, n=1):
    """Colour vectors (4 n, 3, T, Z, Y*X) from the harness's generator."""
    s = data.gaussian_sources(data.key_of(seed, 1), lat, n)
    return s.reshape((-1,) + s.shape[2:])


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "per_layer",
                           metric + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("lat", [(4, 4, 4, 4), (4, 6, 2, 8)],
                         ids=["4x4x4x4", "4x6x2x8"])
@pytest.mark.parametrize("ap", [True, False],
                         ids=["antiperiodic", "periodic"])
def test_reference_is_the_programs_pair_operator(lat, ap):
    """A x of the reference (mask, D, D, mask) against M_pairs of the
    operator the resident term assembles, on the even sites; and the
    mask is the program's even parity."""
    from quda_tpu.fields.geometry import EVEN, LatticeGeometry
    from quda_tpu.fields.spinor import even_odd_join, even_odd_split
    from quda_tpu.models.staggered import DiracStaggeredPCPairs
    from quda_tpu.ops import staggered_packed as spk
    from benchmark.entry.invert_quda_hisq import naik_links
    geom = LatticeGeometry(tuple(reversed(lat)))
    u = data.su3_field(data.key_of(2 ** 31 + 5, 0), (4,), lat, 0.7)
    g = data.to_canonical_gauge(u, lat)
    op = DiracStaggeredPCPairs.from_packed(
        geom, spk.ks_links_eo_pairs(g, lat, ap, 1),
        spk.ks_links_eo_pairs(naik_links(g, lat), lat, ap, 3), MASS, EVEN)
    psi = _rows(lat)[:2]
    mine = ref.apply_m(ref.fold_boundary(u, ap), psi, KAPPA, lat[3])
    for row in range(2):
        canon = lambda v: data.to_canonical_spinors(
            jnp.broadcast_to(v, (1, 4) + v.shape), lat)[0][..., 0:1, :]
        even, odd = even_odd_split(canon(psi[row]), geom)
        prog = op.M(even)
        got_e, got_o = even_odd_split(canon(mine[row]), geom)
        assert _rel(got_e, prog) < 1e-6
        assert float(jnp.max(jnp.abs(got_o))) == 0.0
        # the mask keeps exactly what the program calls even
        masked = psi[row] * ref.even_mask(psi.shape[-3:], lat[3])
        assert float(jnp.max(jnp.abs(
            canon(masked) - even_odd_join(even, jnp.zeros_like(odd),
                                          geom)))) == 0.0


def test_apply_m_is_hermitian_and_positive():
    lat = (4, 4, 4, 4)
    links = ref.fold_boundary(
        data.su3_field(data.key_of(3, 0), (4,), lat, 0.7), True)
    a, b = _rows(lat, 1)[:1], _rows(lat, 2)[:1]
    e = ref.even_mask(a.shape[-3:], 4)
    dot = lambda x, y: complex(jnp.sum(jnp.conj(x) * y))
    lhs = dot(e * a, ref.apply_m(links, b, KAPPA, 4))
    rhs = dot(ref.apply_m(links, a, KAPPA, 4), e * b)
    assert abs(lhs - rhs) < 1e-5 * abs(lhs)
    aa = dot(e * a, ref.apply_m(links, a, KAPPA, 4))
    assert aa.real > 4 * MASS * MASS * dot(e * a, e * a).real
    assert abs(aa.imag) < 1e-5 * aa.real


def test_traffic_offsets_are_the_reference_law_and_the_cell_is_listed():
    run = importlib.import_module("benchmark.run")
    bench, cell, config, traffic, _ = run.load_cell(CELL)
    assert tuple(traffic["offsets"]) == ref.OFFSETS
    assert len(ref.OFFSETS) == N
    assert all(abs(s - 0.01 * i * i) < 1e-15
               for i, s in enumerate(ref.OFFSETS))
    assert config["widths"]["shifts"] == len(ref.OFFSETS)
    assert config["reference"] == "hisq_shifted" and cell["chips"] == 1
    assert config["sources_per_call"] == 1 and config["reduced"] == []
    assert {"shifts", "offsets", "links"} <= set(config["assumed"])
    assert abs(ref.mass_of(traffic["kappa"]) - traffic["mass"]) < 1e-12
    assert traffic["res_bound"] <= 1e-4
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted("hisq_shift_" + n for n in (
        "iters", "compute_phase_s", "outside_solver_s", "dslash_us",
        "dslash_roofline", "update_us", "update_share_pct",
        "window_programs_built", "first_call_solve_program_s",
        "first_call_exit_program_s"))
    shared = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in mine]
    assert sorted(shared) == sorted((
        "device_idle_pct", "hbm_peak_gib", "first_call_s",
        "entry_prepare_s", "solve_dispatch_s", "solve_wait_s",
        "exit_read_s"))


def test_row_zero_even_sites_and_every_shift_row_are_read():
    lat = (4, 4, 4, 4)
    links = ref.fold_boundary(
        data.su3_field(data.key_of(3, 0), (4,), lat, 0.7), True)
    b = _rows(lat, 1)
    x = _rows(lat, 2, n=4)[:N]
    want = ref.rel_residual(links, KAPPA, 4, b, x)
    assert want > 0.1
    odd = 1.0 - ref.even_mask(b.shape[-3:], 4)
    # rows 1-3 and the odd sites of the source, and the odd sites of
    # the solution, are not read
    assert ref.rel_residual(links, KAPPA, 4, b.at[1:].set(0.0), x) == want
    assert ref.rel_residual(links, KAPPA, 4, b + 5.0 * odd * b, x) == want
    assert ref.rel_residual(links, KAPPA, 4, b, x + 5.0 * odd * x) == want
    # every row is: the number is the largest of the fourteen
    per = np.asarray(ref.shift_residuals(links, KAPPA, 4, b, x))
    assert per.shape == (N,) and want == float(per.max())
    for row in range(N):
        worse = x.at[row].multiply(50.0)
        assert ref.rel_residual(links, KAPPA, 4, b, worse) > 5 * want
    assert np.isnan(ref.rel_residual(links, KAPPA, 4, b,
                                     x.at[4].set(jnp.nan)))
    with pytest.raises(ValueError, match="one row a shift"):
        ref.rel_residual(links, KAPPA, 4, b, x[:4])


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_in_lower_precision_is_not_correct(seed):
    control = importlib.import_module("benchmark.control")
    run = importlib.import_module("benchmark.run")
    row = control.one_seed(run, CELL, seed, rehearse=True, control=1,
                           control_maxiter=400, out=lambda *_: None)
    assert row["program"]["correct"], row
    assert not row["control"]["correct"], row
    bound = run.load_cell(CELL)[3]["res_bound"]
    assert row["control"]["res_max"] > 3 * bound, row


@pytest.mark.parametrize("row", [0, 6, 13])
def test_an_altered_shift_row_is_not_correct(row):
    """A sound call's fourteen rows pass ``correct.compare`` under the
    cell's limits; with ONE row scaled by 1 + 1e-3 (any shift) the same
    call does not."""
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
    assert x.shape == (1,) + tuple(lattice) + (N, 3)
    assert len(info["true_res_offset"]) == N and info["converged"] == [True]
    kept = data.from_canonical_spinors(x)
    ref_links = run.folded_links(ref, config, links)
    quiet = lambda *_: None

    def check(solutions):
        return correct.compare(
            ref, ref_links, float(traffic["kappa"]), lattice[3],
            [{"label": "call", "sources": b, "solutions": solutions,
              "true_res": info["true_res"]}], traffic, out=quiet)
    assert check(kept)["correct"]
    assert not check(kept.at[0, row].multiply(1.001))["correct"]


def test_sound_rehearsal_run_is_correct():
    rc, result, _ = _run(["--workload", CELL, "--seed", str(2 ** 31 + 21),
                          "--seconds", "2", "--trace", "0", "--rehearse"])
    assert rc == 0 and result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"call_s", "src_per_chip_h",
                                      "setup_s"}


def test_build_reader_counts_one_api_by_hand(monkeypatch):
    """Six records: the reader sums the first call's seconds of the
    named programs, counts the later calls' traces with their repeats,
    leaves out nested traces and other APIs, and returns None where the
    API has no record."""
    from quda_tpu.obs import build
    api = "invert_multishift_quda"
    rec = lambda program, stage, seconds, ordinal, **kw: {
        "program": program, "stage": stage, "seconds": seconds,
        "api": api, "ordinal": ordinal, "inside": None, **kw}
    records = [
        rec("_multishift_program", "trace", 1.0, 1),
        rec("_multishift_program", "lower", 2.0, 1),
        rec("dslash_staggered_eo_pallas_v3", "trace", 0.5, 1,
            inside="_multishift_program"),
        rec("_verified_exit_shifts_program", "compile", 4.0, 1),
        rec("convert_element_type", "trace", 0.001, 2, repeats=3),
        rec("_cg_reliable_program", "trace", 9.0, 1, api="invert_quda"),
    ]
    monkeypatch.setattr(build, "snapshot", lambda: records)
    read = lambda metric: program_build_api.read({}, **_spec(metric)["args"])
    assert read("hisq_shift_first_call_solve_program_s") == 3.0
    assert read("hisq_shift_first_call_exit_program_s") == 4.0
    assert read("hisq_shift_window_programs_built") == 4
    assert program_build_api.read({}, api=api, calls="first") == 7.0
    assert program_build_api.read({}, api="no_such_api",
                                  calls="first") is None
    records[4:5] = []
    assert read("hisq_shift_window_programs_built") == 0


def test_loop_rest_reader_returns_the_hand_count():
    """Two traced calls of 200 iterations: a 0.1 s ``while`` each, four
    f32 passes an iteration at 100 us (fat) and 110 us (Naik), two more
    passes a call outside the loop, the exit's batched passes under
    another name.  By hand: kernels 2 x (400 x 100e-6 + 400 x 110e-6)
    + 4 x 105e-6 = 0.16842 s of 1,604 events = 401 iterations; the rest
    0.2 - 0.16842 = 0.03158 s: 78.753 us an iteration, 15.79 %."""
    kernels = {
        "while.7": {"count": 2, "seconds": 0.2},
        "dslash_staggered_eo_pallas_v3.20 f32<-f32,f32":
            {"count": 802, "seconds": 802 * 100e-6 + 2 * 5e-6},
        "dslash_staggered_eo_pallas_v3.21 f32<-f32,f32":
            {"count": 802, "seconds": 802 * 110e-6 - 2 * 5e-6},
        "dslash_staggered_eo_pallas_v3_mrhs.3 f32<-f32,f32":
            {"count": 8, "seconds": 8 * 700e-6},
        "multiply_add_fusion.4": {"count": 400, "seconds": 0.024},
        "while_cond": {"count": 1, "seconds": 1.0},
    }
    ctx = {"trace": {"kernels": kernels}}
    us = trace_loop_rest.read(ctx, **_spec("hisq_shift_update_us")["args"])
    pct = trace_loop_rest.read(
        ctx, **_spec("hisq_shift_update_share_pct")["args"])
    assert us == pytest.approx((0.2 - 0.16842) / 401 * 1e6, rel=1e-9)
    assert pct == pytest.approx(15.79, abs=0.005)
    for missing in ("while.7", "dslash_staggered_eo_pallas_v3.20 "
                    "f32<-f32,f32"):
        cut = {k: v for k, v in kernels.items()
               if not k.startswith(missing[:30])}
        assert trace_loop_rest.read(
            {"trace": {"kernels": cut}},
            **_spec("hisq_shift_update_us")["args"]) is None
    assert trace_loop_rest.read(
        {"trace": None}, **_spec("hisq_shift_update_us")["args"]) is None
