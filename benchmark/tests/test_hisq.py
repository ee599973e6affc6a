"""The HISQ configuration's yardstick can fail, and states what it
measures: the reference is the program's full-lattice operator, D is
anti-Hermitian, the (fat, long) pair rotates with the seed's gauge
transformation, the lower-precision control is not correct, a sound run
is, only spin row 0 of the harness's four-row fields is read, and the
needed-bytes counts of the hop and of one of its two passes are the
stated ones.

CPU; the operator checks at 4^4 and one lattice of four extents, the
control and the sound run at the configuration's rehearsal lattice
(8^4) under the cell's own limits (the control's CG is cut at 600
iterations: in bfloat16 it stalls two orders above the limit long
before).
"""

import importlib
import json
import os
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "hisq24_single.strange"
KAPPA, MASS = 1.0 / (2.0 * 4.04), 0.04

from benchmark import data  # noqa: E402
from benchmark.reference import hisq as ref  # noqa: E402
from benchmark.tests.test_correct import _run  # noqa: E402


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _rows(lat, seed=7):
    """Four colour vectors (4, 3, T, Z, Y*X), as the harness's source."""
    return data.gaussian_sources(data.key_of(seed, 1), lat, 1)[0]


def _canonical_vec(v, lat):
    """(3, T, Z, Y*X) -> (T, Z, Y, X, 1, 3)."""
    return jnp.transpose(v.reshape((3,) + tuple(lat)),
                         (1, 2, 3, 4, 0))[..., None, :]


@pytest.mark.parametrize("lat", [(4, 4, 4, 4), (4, 6, 2, 8)],
                         ids=["4x4x4x4", "4x6x2x8"])
@pytest.mark.parametrize("ap", [True, False],
                         ids=["antiperiodic", "periodic"])
def test_reference_is_the_programs_full_operator(lat, ap):
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.staggered import DiracStaggered
    from benchmark.entry.invert_quda_hisq import naik_links
    u = data.su3_field(data.key_of(2 ** 31 + 5, 0), (4,), lat, 0.7)
    psi = _rows(lat)
    g = data.to_canonical_gauge(u, lat)
    d = DiracStaggered(g, LatticeGeometry(tuple(reversed(lat))), MASS,
                       improved=True, long_links=naik_links(g, lat),
                       antiperiodic_t=ap)
    mine = ref.apply_m(ref.fold_boundary(u, ap), psi, KAPPA, lat[3])
    for row in range(2):
        prog = d.M(_canonical_vec(psi[row], lat))
        assert _rel(_canonical_vec(mine[row], lat), prog) < 1e-6


def test_d_is_anti_hermitian_and_mdag_is_2m_minus_d():
    lat = (4, 4, 4, 4)
    links = ref.fold_boundary(
        data.su3_field(data.key_of(3, 0), (4,), lat, 0.7), True)
    a, b = _rows(lat, 1), _rows(lat, 2)
    dot = lambda x, y: jnp.sum(jnp.conj(x) * y)
    d = lambda v: ref.apply_m(links, v, KAPPA, 4) - 2.0 * MASS * v
    lhs, rhs = dot(a, d(b)), -jnp.conj(dot(b, d(a)))
    assert abs(complex(lhs - rhs)) < 1e-5 * abs(complex(lhs))
    mdag = ref.apply_m(links, b, KAPPA, 4, dagger=True)
    assert _rel(mdag, 2.0 * MASS * b - d(b)) < 1e-6


def test_fat_long_pair_is_gauge_covariant():
    """M[U'] (g psi) = g (M[U] psi) with the long links built from U':
    every seed solves one configuration in another gauge.  Reads
    3.0-3.2e-6 (the Wilson hop 1.4e-6 under 1e-5, selfcheck): g(x) of
    data.su3_field is unitary to 1e-5, and a long link takes it four
    times."""
    lat = (4, 6, 2, 8)
    u = data.su3_field(data.key_of(5, 0), (4,), lat, 0.7)
    g = data.su3_field(data.key_of(11, 1), (), lat, 1.0)
    psi = _rows(lat)
    rot = lambda v: sum(g[:, b][None, :] * v[:, b][:, None]
                        for b in range(3))
    lhs = ref.apply_m(ref.fold_boundary(
        data.gauge_rotate(u, g, lat[3]), True), rot(psi), KAPPA, lat[3])
    rhs = rot(ref.apply_m(ref.fold_boundary(u, True), psi, KAPPA, lat[3]))
    assert _rel(lhs, rhs) < 5e-6


def test_traffic_kappa_is_the_mass():
    run = importlib.import_module("benchmark.run")
    _, cell, config, traffic, _ = run.load_cell(CELL)
    assert config["reference"] == "hisq" and cell["chips"] == 1
    assert abs(ref.mass_of(traffic["kappa"]) - traffic["mass"]) < 1e-12
    assert config["reduced"] == [] and "links" in config["assumed"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted(
        "hisq_" + n for n in ("dslash_bf16_us", "dslash_roofline",
                              "iters", "compute_phase_s",
                              "outside_solver_s", "load_s"))


def test_only_row_zero_is_read():
    lat = (4, 4, 4, 4)
    links = ref.fold_boundary(
        data.su3_field(data.key_of(3, 0), (4,), lat, 0.7), True)
    b, x = _rows(lat, 1), _rows(lat, 2)
    want = ref.rel_residual(links, KAPPA, 4, b, x)
    assert want > 0.1
    zeroed = x.at[1:].set(0.0)
    other = x.at[1:].multiply(3.0)
    assert ref.rel_residual(links, KAPPA, 4, b, zeroed) == want
    assert ref.rel_residual(links, KAPPA, 4, b, other) == want
    assert ref.rel_residual(links, KAPPA, 4, b.at[1:].set(0.0), x) == want


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_control_in_lower_precision_is_not_correct(seed):
    control = importlib.import_module("benchmark.control")
    run = importlib.import_module("benchmark.run")
    row = control.one_seed(run, CELL, seed, rehearse=True, control=1,
                           control_maxiter=600, out=lambda *_: None)
    assert row["program"]["correct"], row
    assert not row["control"]["correct"], row
    bound = run.load_cell(CELL)[3]["res_bound"]
    assert row["control"]["res_max"] > 3 * bound, row


def test_sound_rehearsal_run_is_correct():
    rc, result, _ = _run(["--workload", CELL, "--seed", "21", "--seconds",
                          "2", "--trace", "0", "--rehearse"])
    assert rc == 0 and result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"call_s", "src_per_chip_h",
                                      "setup_s"}


def test_needed_bytes_of_the_hop_and_of_one_pass():
    km = importlib.import_module(
        "benchmark.kernel_models.staggered_eo_fat_naik")
    one = importlib.import_module(
        "benchmark.kernel_models.staggered_eo_hopset")
    assert one.needed((24,) * 4)["bytes_per_site"] == 624
    assert one.needed((24,) * 4, link_bytes=2, in_bytes=2,
                      out_bytes=4)["bytes_per_site"] == 324
    assert 2 * one.needed((24,) * 4)["flops"] == 165888 * 1146
    lat = (24,) * 4
    assert km.needed(lat)["bytes_per_site"] == 1200
    assert km.needed(lat, link_bytes=2, in_bytes=2,
                     out_bytes=2)["bytes_per_site"] == 600
    # the sloppy operator's call: bf16 in, f32 out
    assert km.needed(lat, link_bytes=2, in_bytes=2,
                     out_bytes=4)["bytes_per_site"] == 612
    assert km.needed(lat)["sites"] == 165888
    assert km.needed(lat)["flops"] == 165888 * 1146
