"""The multi-shift clover configuration's yardstick can fail, and states
what it measures: the reference inverts the clover term itself (A A^-1
= 1 on every site), its shifts and CSW are the traffic file's and
``reference/clover.py``'s, only the even sites are read and every one of
the fourteen shifts' four rows is held (an altered shift is not
correct), the lower-precision control is not correct and a sound run
is, the cell is listed as ISSUE 49 states it, and the reader of the
update kernel's roofline share returns what a hand count gives.

CPU; the operator check at 4^4 (the program's canonical operator is
held to the reference in tests/test_clover_multishift_resident.py), the
control and the sound run at the configuration's rehearsal lattice
(8^4) on the pair route (QUDA_TPU_PACKED=1, the XLA stencil: the route
the chip takes, without its kernels) under the cell's own limits; the
control's CG is cut at 40 iterations where the program takes 300: in
bfloat16 it stalls at 4-5e-2 on the base shift from iteration 20 on
(read at 20, 40, 60, 100 and 150 on this lattice), orders above the
limit.
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
CELL = "clover24_multishift.light"
N = 14                                  # the configuration's shifts

from benchmark import correct, data  # noqa: E402
from benchmark.reference import clover as clover_ref  # noqa: E402
from benchmark.reference import clover_shifted as ref  # noqa: E402
from benchmark.readers import trace_shift_update_roofline  # noqa: E402
from benchmark.tests.test_correct import _run  # noqa: E402

run = importlib.import_module("benchmark.run")


@pytest.fixture(autouse=True)
def pair_route(monkeypatch):
    from quda_tpu.utils import config as qconf
    monkeypatch.setenv("QUDA_TPU_PACKED", "1")
    qconf.reset_cache()
    yield
    monkeypatch.undo()
    qconf.reset_cache()


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "per_layer",
                           metric + ".json")) as fh:
        return json.load(fh)


def _fields(lat, seed=7, n=1):
    """Spinor fields (n, 4, 3, T, Z, Y*X) from the harness's generator."""
    return data.gaussian_sources(data.key_of(seed, 1), lat, n)


def test_reference_inverts_the_clover_term_itself():
    """A A^-1 = 1 to 1e-5 on every site at the cell's kappa (the
    reference's own elimination, entry by entry), and its A is
    ``reference/clover.py``'s diagonal: M + kappa D."""
    lat = (4, 4, 4, 4)
    kappa = run.load_cell(CELL)[3]["kappa"]
    links = ref.fold_boundary(
        data.su3_field(data.key_of(101, 0), (4,), lat, 0.7), True)
    u, a, ainv = ref.terms(links, kappa, 4, "single")
    prod = sum(a[:, j][:, None] * ainv[j][None, :] for j in range(12))
    eye = jnp.eye(12)[:, :, None, None, None]
    assert float(jnp.max(jnp.abs(prod - eye))) < 1e-5
    v = _fields(lat)[0]
    want = (clover_ref.apply_m(links, v, kappa, 4)
            + kappa * ref._hop(u, v, 4))
    got = ref._site_apply(a, v[None])[0]
    assert float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel())) < 1e-6


def test_apply_m_is_hermitian_and_positive():
    lat = (4, 4, 4, 4)
    links = ref.fold_boundary(
        data.su3_field(data.key_of(3, 0), (4,), lat, 0.7), True)
    a, b = _fields(lat, 1), _fields(lat, 2)
    e = ref.parity_mask(a.shape[-3:], 4, ref.PARITY)
    dot = lambda x, y: complex(jnp.sum(jnp.conj(x) * y))
    lhs = dot(e * a, ref.apply_m(links, b, 0.32, 4))
    rhs = dot(ref.apply_m(links, a, 0.32, 4), e * b)
    assert abs(lhs - rhs) < 1e-5 * abs(lhs)
    aa = dot(e * a, ref.apply_m(links, a, 0.32, 4))
    assert aa.real > 0 and abs(aa.imag) < 1e-5 * aa.real


def test_traffic_offsets_are_the_reference_law_and_the_cell_is_listed():
    bench, cell, config, traffic, _ = run.load_cell(CELL)
    single = run.load_cell("clover24_single.light")
    assert tuple(traffic["offsets"]) == ref.OFFSETS
    assert len(ref.OFFSETS) == N == config["widths"]["shifts"]
    floor = ref.OFFSETS[0]
    assert floor in (0.0064, 0.0016)    # ISSUE 49's rule on the count
    assert all(abs(s - (floor + 0.01 * i * i)) < 1e-15
               for i, s in enumerate(ref.OFFSETS))
    assert ref.CSW == clover_ref.CSW == config["invert_param"]["csw"] == 1.0
    assert ref.PARITY == 0 and "matpc_type" not in config["invert_param"]
    assert config["reference"] == "clover_shifted" and cell["chips"] == 1
    assert config["entry"] == "invert_multishift_quda_clover"
    assert config["sources_per_call"] == 1 and config["reduced"] == []
    assert {"csw", "tol", "links", "shifts", "offsets", "rhs",
            "volume"} <= set(config["assumed"])
    assert config["lattice"] == single[2]["lattice"] == [24] * 4
    # the system of both other clover cells
    for key in ("kappa", "link_scale", "gauge_seed", "sources",
                "agree_bound"):
        assert traffic[key] == single[3][key], key
    assert traffic["kappa"] == 0.32 and traffic["res_bound"] <= 1e-4
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == [] and len(entry["source"]) < 200
    for word in ("--multishift 14", "invertMultiShiftQuda",
                 "loadCloverQuda", "qudaCloverMultishiftInvert"):
        assert word in entry["source"], word
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted("clover_shift_" + n for n in (
        "iters", "compute_phase_s", "outside_solver_s", "dslash_us",
        "update_us", "update_share_pct", "update_kernel_us",
        "update_kernel_roofline", "window_programs_built",
        "first_call_solve_program_s", "first_call_exit_program_s"))
    shared = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in mine]
    assert sorted(shared) == sorted((
        "device_idle_pct", "hbm_peak_gib", "first_call_s",
        "entry_prepare_s", "solve_dispatch_s", "solve_wait_s",
        "exit_read_s", "clover_load_s", "clover_post_roofline",
        "clover_diag_hop_roofline"))
    for m in bench["per_layer"]:
        if m["name"] in mine:
            spec = _spec(m["name"])
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "readers", spec["reader"] + ".py"))


def test_even_sites_and_every_shift_row_are_read():
    lat = (4, 4, 4, 4)
    links = ref.fold_boundary(
        data.su3_field(data.key_of(3, 0), (4,), lat, 0.7), True)
    b = _fields(lat, 1)[0]
    x = _fields(lat, 2, n=N).reshape((4 * N, 3) + b.shape[-3:])
    want = ref.rel_residual(links, 0.32, 4, b, x)
    assert want > 0.1
    odd = 1.0 - ref.parity_mask(b.shape[-3:], 4, 0)
    # the odd sites of the source and of the solution are not read
    assert ref.rel_residual(links, 0.32, 4, b + 5.0 * odd * b, x) == want
    assert ref.rel_residual(links, 0.32, 4, b, x + 5.0 * odd * x) == want
    # every row is: the number is the largest of the fourteen
    per = np.asarray(ref.shift_residuals(links, 0.32, 4, b, x))
    assert per.shape == (N,) and want == float(per.max())
    for row in (0, 4 * 6 + 1, 4 * N - 1):
        worse = x.at[row].multiply(50.0)
        assert ref.rel_residual(links, 0.32, 4, b, worse) > 5 * want
    assert np.isnan(ref.rel_residual(links, 0.32, 4, b,
                                     x.at[17].set(jnp.nan)))
    with pytest.raises(ValueError, match="four rows a shift"):
        ref.rel_residual(links, 0.32, 4, b, x[:16])


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_in_lower_precision_is_not_correct(seed):
    control = importlib.import_module("benchmark.control")
    row = control.one_seed(run, CELL, seed, rehearse=True, control=1,
                           control_maxiter=40, out=lambda *_: None)
    assert row["program"]["correct"], row
    assert not row["control"]["correct"], row
    bound = run.load_cell(CELL)[3]["res_bound"]
    assert row["control"]["res_max"] > 3 * bound, row


@pytest.mark.parametrize("shift", [0, 6, 13])
def test_an_altered_shift_is_not_correct(shift):
    """A sound call's fourteen shifts pass ``correct.compare`` under the
    cell's limits; with ONE row of one shift scaled by 1 + 1e-3 the same
    call does not."""
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
    assert x.shape == (1,) + tuple(lattice) + (4 * N, 3)
    assert len(info["true_res_offset"]) == N and info["converged"] == [True]
    assert info["iters"][0] <= info["shift_iters_sum"] <= N * info["iters"][0]
    kept = data.from_canonical_spinors(x)
    ref_links = run.folded_links(ref, config, links)
    quiet = lambda *_: None

    def check(solutions):
        return correct.compare(
            ref, ref_links, float(traffic["kappa"]), lattice[3],
            [{"label": "call", "sources": b, "solutions": solutions,
              "true_res": info["true_res"]}], traffic, out=quiet)
    assert check(kept)["correct"]
    assert not check(kept.at[0, 4 * shift + 2].multiply(1.001))["correct"]


def test_sound_rehearsal_run_is_correct():
    rc, result, _ = _run(["--workload", CELL, "--seed", str(2 ** 31 + 21),
                          "--seconds", "2", "--trace", "0", "--rehearse"])
    assert rc == 0 and result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"call_s", "src_per_chip_h",
                                      "setup_s"}


def test_update_roofline_reader_returns_the_hand_count():
    """Three calls of 300, 305 and 295 iterations, the last two traced:
    600 kernel events in 0.3 s.  Those two calls made 1,140 + 1,100
    shifted updates; by hand a parity 4-spinor at 24^4 is 165,888 x 24
    x 4 B = 15,925,248 B and an update moves four: 2,240 x 63,700,992 B
    = 142.69 GB, 0.17422 s at 819 GB/s, 58.07 % of 0.3 s."""
    model = importlib.import_module(
        "benchmark.kernel_models.multishift_update")
    assert model.needed((24,) * 4)["bytes"] == 4 * 15925248
    assert model.needed((24,) * 4, n_rhs=14)["bytes_per_site"] == 14 * 384
    assert model.needed((24,) * 4, spins=1)["bytes"] == 15925248
    calls = [{"iters": [300], "shift_iters_sum": 1200},
             {"iters": [305], "shift_iters_sum": 1140},
             {"iters": [295], "shift_iters_sum": 1100}]
    kernels = {
        "while.3": {"count": 2, "seconds": 1.3},
        "multishift_update_pallas.6 f32<-s32,f32":
            {"count": 600, "seconds": 0.3},
        "dslash_eo_pallas_post.12 f32<-f32,f32":
            {"count": 1204, "seconds": 0.4},
    }
    ctx = {"trace": {"kernels": kernels}, "calls": calls,
           "lattice": (24,) * 4, "device_kind": "TPU v5 lite",
           "package": "benchmark",
           "config": {"widths": {"spins": 4, "colours": 3}}}
    args = _spec("clover_shift_update_kernel_roofline")["args"]
    got = trace_shift_update_roofline.read(ctx, **args)
    assert got == pytest.approx(
        100.0 * 2240 * 4 * 15925248 / 819e9 / 0.3, rel=1e-9)
    assert got == pytest.approx(58.07, abs=0.01)
    # one traced call; events no run of calls accounts for; no count
    one = dict(kernels, **{"multishift_update_pallas.6 f32<-s32,f32":
                           {"count": 305, "seconds": 0.15}})
    assert trace_shift_update_roofline.read(
        dict(ctx, trace={"kernels": one}), **args) == pytest.approx(
        100.0 * 1140 * 4 * 15925248 / 819e9 / 0.15, rel=1e-9)
    odd = dict(kernels, **{"multishift_update_pallas.6 f32<-s32,f32":
                           {"count": 599, "seconds": 0.3}})
    assert trace_shift_update_roofline.read(
        dict(ctx, trace={"kernels": odd}), **args) is None
    bare = [{"iters": c["iters"]} for c in calls]
    assert trace_shift_update_roofline.read(
        dict(ctx, calls=bare), **args) is None
    gone = {k: v for k, v in kernels.items() if "update" not in k}
    assert trace_shift_update_roofline.read(
        dict(ctx, trace={"kernels": gone}), **args) is None
    assert trace_shift_update_roofline.read(
        dict(ctx, trace=None), **args) is None

