"""``invert_multishift_quda`` on the resident KS term (the RHMC's shifted
solve): prepare, the multi-shift CG and the exit as cached programs
(solvers/program.py), every shift verified by the exit, held to the
benchmark's plain reference ``benchmark/reference/hisq_shifted.py``
shift by shift.

CPU, 4^3 x 8, the benchmark's own seeded hot links (fat = U, long =
-(1/24) UUU as its HISQ entry makes them), mass 0.04 and the reference's
fourteen offsets.  Everything here runs the XLA pair stencil, as
tests/test_ks_resident.py: what is compared is the route, the programs
and the exit, not the kernels (tests/test_staggered_pallas.py holds
those, the MRHS pass among them; interpreted they compile 10-30 s a
shape, and the route traces five).  One module-scoped session and ONE
solve serve the per-shift cases.  f32 throughout: a pure-f32 CG on a
system of condition ~10^3 leaves a true residual of 1-2e-5 on the base
shift where its recurrence says 1e-6 (less on the better conditioned
shifted ones), which is why the exit recomputes every shift's.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.spinor import even_odd_join
from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.interfaces import milc
from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.obs import build as obuild
from quda_tpu.obs import metrics as omet
from quda_tpu.robust import faultinject as finj
from quda_tpu.solvers import program as sprog
from quda_tpu.solvers.multishift import multishift_cg
from quda_tpu.utils import config as qconf

LAT = (8, 4, 4, 4)                      # array order (T, Z, Y, X)
GEOM = LatticeGeometry(tuple(reversed(LAT)))
MASS = 0.04
KAPPA = 1.0 / (2.0 * (4.0 + MASS))
API = "invert_multishift_quda"
PROGRAMS = ("prepare", "multishift-cg", "verified-exit")
N = 14                                  # the reference's shifts


def _param(offsets, **kw):
    d = dict(dslash_type="hisq", inv_type="multi-shift-cg",
             solve_type="normop-pc", mass=MASS, tol=1e-6, maxiter=2000,
             cuda_prec="single", num_offset=len(offsets),
             offset=tuple(offsets))
    d.update(kw)
    return InvertParam(**d)


def _counts(name, key):
    out = {}
    for (n, labels), v in omet.snapshot()["counters"].items():
        lb = dict(labels)
        if n == name and lb.get("api", API) == API:
            k = tuple(lb[i] for i in key)
            out[k] = out.get(k, 0) + int(v)
    return out


def _delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _rows(xs):
    """The API's (N, T, Z, Y, X/2, 1, 3) even-site solutions as the
    reference's rows (N, 3, T, Z, Y*X), odd sites zero."""
    full = jax.vmap(lambda e: even_odd_join(e, jnp.zeros_like(e),
                                            GEOM))(xs)
    return jnp.transpose(full[..., 0, :], (0, 5, 1, 2, 3, 4)).reshape(
        (xs.shape[0], 3, LAT[0], LAT[1], -1))


@pytest.fixture(scope="module")
def solved(tmp_path_factory):
    """init, the benchmark's links resident as (fat, long), a metrics
    session; one solve of the reference's fourteen offsets, and the
    reference's own answer: N plain CG solves on its operator, one a
    shift, in complex128."""
    data = importlib.import_module("benchmark.data")
    ref = importlib.import_module("benchmark.reference.hisq_shifted")
    naik = importlib.import_module(
        "benchmark.entry.invert_quda_hisq").naik_links
    mp = pytest.MonkeyPatch()
    mp.setenv("QUDA_TPU_PACKED", "1")
    for knob in ("QUDA_TPU_PALLAS", "QUDA_TPU_PRECISION_FORM",
                 "QUDA_TPU_ROBUST", "QUDA_TPU_FAULT"):
        mp.delenv(knob, raising=False)
    qconf.reset_cache()
    finj.reset()
    api.init_quda()
    omet.start(str(tmp_path_factory.mktemp("multishift_resident")))
    u = data.su3_field(data.key_of(101, 0), (4,), LAT, 0.7)
    gauge = data.to_canonical_gauge(u, LAT)
    api.load_gauge_quda(np.asarray(gauge), GaugeParam(
        X=tuple(reversed(LAT)), cuda_prec="single"))
    api.load_fat_long_quda(gauge, naik(gauge, LAT))
    links = ref.fold_boundary(u, True)
    # spin row 0 of a harness source, odd sites emptied: MILC's even
    # parity, as the benchmark's entry hands it to the API
    b = data.gaussian_sources(data.key_of(2 ** 31 + 7, 1000), LAT, 1)[0]
    b = (b * ref.even_mask(b.shape[-3:], LAT[3])).astype(jnp.complex64)
    src = data.to_canonical_spinors(b[None], LAT)[0][..., 0:1, :]
    p = _param(ref.OFFSETS)
    before = _counts("solve_program_total", ("solver", "outcome"))
    xs = api.invert_multishift_quda(src, p)
    first = _delta(before, _counts("solve_program_total",
                                   ("solver", "outcome")))
    oracle, _ = ref.solve_normal(links, b.astype(jnp.complex128), KAPPA,
                                 LAT[3], 1e-10, 4000)
    yield {"ref": ref, "links": links, "b": b, "src": src, "p": p,
           "xs": xs, "first": first, "oracle": oracle[:, 0],
           "checked": np.asarray(ref.shift_residuals(
               links, KAPPA, LAT[3], b, _rows(xs)))}
    omet.stop(flush_files=False)
    api.end_quda()
    finj.reset()
    mp.undo()
    qconf.reset_cache()


# (a) + (b) every shift under the plain reference -----------------------------

def test_first_call_goes_through_three_programs(solved):
    assert solved["first"] == {(s, "miss"): 1 for s in PROGRAMS}
    p = solved["p"]
    assert p.converged and p.converged_multi == [True] * N
    assert p.true_res == p.true_res_offset[0]
    assert 100 < p.iter_count < 1000
    assert len(solved["ref"].OFFSETS) == N
    assert solved["xs"].shape == (N, 8, 4, 4, 2, 1, 3)
    ref, rows = solved["ref"], _rows(solved["xs"])
    assert ref.rel_residual(solved["links"], KAPPA, LAT[3], solved["b"],
                            rows) == float(solved["checked"].max())


@pytest.mark.parametrize("shift", range(N))
def test_every_shift_under_the_plain_reference(solved, shift):
    """Shift by shift: the reference confirms the residual the exit
    program reported (to 10 %, the configuration's agree_bound; they
    read within a percent), the loop's own zeta |r| is under tol, and
    the solution is the reference's own plain CG solve of that shifted
    system (complex128, tol 1e-10) to 1e-5 relative (f32: it reads
    2e-7 on the largest shift, 1e-6 on the base system)."""
    p, checked = solved["p"], float(solved["checked"][shift])
    api_res = p.true_res_offset[shift]
    assert 0 < checked < 4e-5
    assert abs(api_res - checked) / checked < 0.1
    assert p.iter_res_offset[shift] <= p.tol
    want = solved["oracle"][shift]
    got = _rows(solved["xs"])[shift]
    err = float(jnp.linalg.norm((got - want).ravel())
                / jnp.linalg.norm(want.ravel()))
    assert err < 1e-5, err


# (c) other offsets, tol and maxiter: the same programs -----------------------

@pytest.mark.parametrize("change", ["offsets", "tol_maxiter", "mass"])
def test_second_call_hits_and_builds_nothing(solved, change):
    offsets = tuple(0.02 + 0.005 * i * i for i in range(N))
    kw = {"offsets": {}, "tol_maxiter": dict(tol=2e-6, maxiter=3000),
          "mass": dict(mass=0.06)}[change]
    before = _counts("solve_program_total", ("solver", "outcome"))
    terms = _counts("ks_term_total", ("outcome",))
    built = len(obuild.snapshot())
    p = _param(offsets, **kw)
    xs = api.invert_multishift_quda(solved["src"], p)
    assert _delta(before, _counts("solve_program_total",
                                  ("solver", "outcome"))) == {
        (s, "hit"): 1 for s in PROGRAMS}
    assert _delta(terms, _counts("ks_term_total", ("outcome",))) == {
        ("reused",): 1}
    assert [r for r in obuild.snapshot()[built:]
            if r["api"] == API] == []
    assert p.converged and all(p.converged_multi)
    # the exit's residuals are of THESE offsets: the reference's
    # operator plus sigma_i confirms them
    ref, rows = solved["ref"], _rows(xs)
    kappa = 1.0 / (2.0 * (4.0 + p.mass))
    rhs = ref.rhs_of(solved["b"], kappa, LAT[3])
    for i in (0, N - 1):
        r = rhs - (ref.apply_m(solved["links"], rows[i:i + 1], kappa,
                               LAT[3]) + offsets[i] * rows[i:i + 1])
        checked = float(jnp.linalg.norm(r.ravel())
                        / jnp.linalg.norm(rhs.ravel()))
        assert abs(p.true_res_offset[i] - checked) / checked < 0.1


# (d) a fault in the operator: the shifts report failed -----------------------

def test_armed_dslash_fault_fails_every_shift(solved):
    shifts = _counts("multishift_shift_total", ("outcome",))
    finj.arm("dslash", "3")
    try:
        p = _param(solved["ref"].OFFSETS)
        api.invert_multishift_quda(solved["src"], p)
    finally:
        finj.reset()
    # A p went NaN at iteration 3 and every shift's x with it: the
    # loop's claim is false and the exit's residuals are not finite
    assert p.converged_multi == [False] * N and not p.converged
    assert not np.isfinite(p.true_res_offset).any()
    assert _delta(shifts, _counts("multishift_shift_total",
                                  ("outcome",))) == {("failed",): N}
    # the next call traces clean and is a hit of the first program
    before = _counts("solve_program_total", ("solver", "outcome"))
    p = _param(solved["ref"].OFFSETS)
    api.invert_multishift_quda(solved["src"], p)
    assert p.converged_multi == [True] * N
    assert _delta(before, _counts("solve_program_total",
                                  ("solver", "outcome"))) == {
        (s, "hit"): 1 for s in PROGRAMS}


def test_a_shift_over_the_exit_margin_is_failed_not_dropped(solved,
                                                            monkeypatch):
    """The loop claims every shift; with the verified-exit margin under
    what f32 leaves on the base shift, that shift alone is reported
    failed, with its residual, and the others converged."""
    monkeypatch.setenv("QUDA_TPU_ROBUST_VERIFY_MARGIN", "5")
    p = _param(solved["ref"].OFFSETS)
    xs = api.invert_multishift_quda(solved["src"], p)
    assert xs.shape[0] == N and len(p.true_res_offset) == N
    assert p.true_res_offset[0] > 5e-6 > p.true_res_offset[N - 1]
    assert p.converged_multi[0] is False and p.converged_multi[-1] is True
    assert not p.converged


# (e) the cached program is the eager solver ----------------------------------

@jax.tree_util.register_pytree_node_class
class _Diagonal:
    """A diagonal Hermitian operator as a solve program's operand."""
    program_signature = ("diagonal",)
    hermitian = True

    def __init__(self, d):
        self.d = d

    def tree_flatten(self):
        return (self.d,), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)

    def M_pairs(self, v):
        return self.d * v


@pytest.mark.parametrize("shifts", [(0.0, 0.1, 0.7, 2.0),
                                    (0.05, 0.3, 0.31, 5.0)])
def test_cached_program_equals_the_eager_solver_bit_for_bit(shifts):
    d = jnp.linspace(0.01, 3.0, 256).astype(jnp.float32)
    b = jnp.asarray(np.random.default_rng(5).standard_normal(256),
                    jnp.float32)
    op = _Diagonal(d)
    sprog.multishift_cg(op, b, np.arange(4, dtype=np.float32), tol=1e-3,
                        maxiter=5)
    traces = sprog._traces[0]
    cached, hit = sprog.multishift_cg(
        op, b, np.asarray(shifts, np.float32), tol=1e-6, maxiter=500)
    # other shifts of the same count, tol and maxiter: nothing traced
    assert hit and sprog._traces[0] == traces
    eager = multishift_cg(op.M_pairs, b, shifts, tol=1e-6, maxiter=500)
    assert bool(cached.converged.all()) and 10 < int(cached.iters) < 500
    assert int(cached.iters) == int(eager.iters)
    for name in ("x", "r2", "shift_r2", "converged"):
        np.testing.assert_array_equal(np.asarray(getattr(cached, name)),
                                      np.asarray(getattr(eager, name)))
    want = b[None] / (d[None] + jnp.asarray(shifts, jnp.float32)[:, None])
    np.testing.assert_allclose(np.asarray(cached.x), np.asarray(want),
                               rtol=2e-3, atol=2e-4)


def test_shift_zero_must_be_the_smallest():
    with pytest.raises(ValueError, match="smallest"):
        multishift_cg(lambda v: v, jnp.ones(8, jnp.float32), (0.1, 0.0))
    from quda_tpu.utils.logging import QudaError
    with pytest.raises(QudaError, match="smallest"):
        _param((0.1, 0.0)).validate()
    with pytest.raises(QudaError, match="tol_offset"):
        _param((0.0, 0.1), tol_offset=(1e-6, 1e-7)).validate()
    _param((0.0, 0.1), tol_offset=(1e-6, 1e-6)).validate()


# (f) MILC's entry point hands the per-shift residuals back -------------------

def test_milc_multishift_returns_the_per_shift_residuals(solved):
    info = {}
    offsets = solved["ref"].OFFSETS
    xs = milc.qudaMultishiftInvert(
        MASS, offsets, solved["src"], tol=1e-6, maxiter=2000,
        prec="single", tol_offset=(1e-6,) * N, info=info)
    np.testing.assert_array_equal(np.asarray(xs),
                                  np.asarray(solved["xs"]))
    assert info["true_res_offset"] == solved["p"].true_res_offset
    assert info["iter_res_offset"] == solved["p"].iter_res_offset
    assert info["converged_multi"] == [True] * N
    assert info["iters"] == solved["p"].iter_count
    assert info["iter_count_offset"] == solved["p"].iter_count_offset


# (g) converged shifts leave the update ---------------------------------------

def test_shift_iterations_reach_the_param_and_the_counter(solved):
    """The reference's fourteen ascending offsets on the 4^3 x 8 HISQ
    operator: every shifted system retires before the base one, each
    with the residual its own recurrence read at that iteration (a
    claim just under tol, not one polished on for nothing)."""
    p = solved["p"]
    n = p.iter_count_offset
    assert len(n) == N and n[0] == p.iter_count
    assert all(a >= b for a, b in zip(n, n[1:])) and n[-1] < n[0] // 4
    assert all(0.1 * p.tol < r <= p.tol for r in p.iter_res_offset)
    before = _counts("multishift_shift_iterations_total", ("state",))
    q = _param(solved["ref"].OFFSETS)
    api.invert_multishift_quda(solved["src"], q)
    assert q.iter_count_offset == n
    assert _delta(before, _counts("multishift_shift_iterations_total",
                                  ("state",))) == {
        ("updated",): sum(n), ("skipped",): N * p.iter_count - sum(n)}


def test_traced_call_carries_the_active_share(solved, tmp_path):
    """Under a trace session the loop records its history through the
    same body: the solve span carries the share of the N x iters
    updates that were made, and each shift's lane says where it went
    under tol, the iteration it was last updated in."""
    import json
    from quda_tpu.obs import trace as otr
    otr.start(str(tmp_path))
    try:
        p = _param(solved["ref"].OFFSETS)
        xs = api.invert_multishift_quda(solved["src"], p)
    finally:
        spans = [json.loads(ln) for ln in open(otr.stop()["jsonl"])]
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(solved["xs"]))
    n = solved["p"].iter_count_offset
    assert p.iter_count_offset == n
    share = [s["active_share"] for s in spans
             if s.get("name") == "solve:multishift-cg"]
    assert share == [round(sum(n) / (N * p.iter_count), 6)]
    assert 0.1 < share[0] < 0.6
    at = {e["shift"]: e["iter"] for e in p.events
          if e["type"] == "shift_converged"}
    assert at == dict(enumerate(n))


def test_solve_program_is_traced_from_a_stack_chunk_of_its_own(monkeypatch):
    """The loop (and with it the operator's kernels) is traced under
    utils/frames.on_a_stack_chunk_of_its_own, whatever stands above the
    program (PERF.md section 7 (22): 0.8 s against 6-9 on the chip)."""
    import sys
    from quda_tpu.solvers import multishift, program as sprog
    from quda_tpu.utils.frames import on_a_stack_chunk_of_its_own as call

    def loop(matvec, *args):
        f, above = sys._getframe(1), []
        while f is not None and len(above) < 3:
            above.append(f.f_code)
            f = f.f_back
        return matvec, args[-1], above
    monkeypatch.setattr(multishift, "multishift_cg_loop", loop)

    class Op:
        M_pairs, MdagM_pairs = "M", "MdagM"
    key = (sprog._LoopKnobs(False, 0, None, None), True, "xla")
    matvec, update, above = sprog._multishift_program.__wrapped__(
        Op(), None, None, 1e-6, 10, key)
    assert (matvec, update) == ("M", "xla")
    assert call.__code__ in above
    assert call.__code__.co_nlocals * 8 > 2 * 16 * 1024
