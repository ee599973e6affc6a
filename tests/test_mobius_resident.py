"""The resident Möbius pair operators (``_resident_mobius``) and the
Möbius entry and verified-exit programs against what they replaced:
``DiracMobiusPC(...).pairs(...)`` built per call from canonical arrays
(the operator ``_PairOpSolve`` hands the eager loop: the same arrays,
so the same system), and ``reconstruct_pairs`` + join + the canonical
complex64 ``DiracMobius.M``.

CPU, seeded random SU(3) links, 4^4 x Ls 4 and one lattice of four
extents at Ls 6.  Everything here runs the XLA pair stencil: what is
compared is the term, the programs and the API's routing, not the
kernels (tests/test_domain_wall.py holds the Ls-batched hop against the
vmapped one, tests/test_chip_compile.py compiles it for the chip).  The
API cases share one module-scoped session; f32 throughout, so agreement
is held to 1e-5.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.geometry import EVEN, ODD, LatticeGeometry
from quda_tpu.fields.spinor import even_odd_join, even_odd_split
from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.models import domain_wall as mdw
from quda_tpu.obs import build as obuild
from quda_tpu.obs import memory as omem
from quda_tpu.obs import metrics as omet
from quda_tpu.ops import wilson as wops
from quda_tpu.ops import wilson_packed as wpk
from quda_tpu.ops.boundary import apply_t_boundary
from quda_tpu.solvers import program as sprog
from quda_tpu.utils import config as qconf

M5, MF, B5, C5 = 1.8, 0.03, 1.5, 0.5
CASES = [((4, 4, 4, 4), 4), ((4, 6, 2, 8), 6)]     # (dims x,y,z,t; Ls)


def _gauge(seed, dims):
    return GaugeField.random(jax.random.PRNGKey(seed),
                             LatticeGeometry(dims)).data.astype(
                                 jnp.complex64)


def _field(seed, shape):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape)
                       + 1j * rng.standard_normal(shape), jnp.complex64)


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _from_links(gauge, geom, ls, matpc, ap, store=jnp.float32, mf=MF):
    """The operator as the resident term assembles it."""
    links = wpk.pack_gauge_eo(wops.split_gauge_eo(
        apply_t_boundary(gauge, geom, -1 if ap else 1), geom))
    return mdw.DiracMobiusPCPairs.from_packed(
        geom, links, ls, mdw.m5_block_pairs(ls, M5, mf, B5, C5), matpc,
        store, tb_sign=ap)


def _pairs_field(seed, geom, ls, store=jnp.float32):
    T, Z, Y, X = geom.lattice_shape
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (ls, 4, 3, 2, T, Z, Y * X // 2)), store)


# (a) the term's operator is the one built per call ---------------------------

@pytest.mark.parametrize("store", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("ap", [True, False],
                         ids=["antiperiodic", "periodic"])
def test_from_packed_is_the_per_call_operator(store, ap):
    dims, ls = CASES[0]
    geom, gauge = LatticeGeometry(dims), _gauge(7, dims)
    old = mdw.DiracMobiusPC(gauge, geom, ls, M5, MF, B5, C5,
                            antiperiodic_t=ap, matpc=ODD).pairs(store)
    new = _from_links(gauge, geom, ls, ODD, ap, store)
    assert new.program_signature == old.program_signature
    assert new.program_signature[-2:] == (ls, "xla")
    assert (jax.tree_util.tree_structure(new)
            == jax.tree_util.tree_structure(old))
    # the same arrays under the same signature: the same operator
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def test_pytree_round_trip_and_with_blocks_share_arrays_and_key():
    dims, ls = CASES[0]
    geom = LatticeGeometry(dims)
    op = _from_links(_gauge(8, dims), geom, ls, EVEN, True)
    leaves, tree = jax.tree_util.tree_flatten(op)
    back = jax.tree_util.tree_unflatten(tree, leaves)
    assert type(back) is type(op)
    assert back.program_signature == op.program_signature
    assert (back.ls, back.matpc, back._op_form) == (ls, EVEN, "xla")
    for a, b in zip(jax.tree_util.tree_leaves(back), leaves):
        assert a is b
    assert len(leaves) == 2 + 4 * 2     # (even, odd) links, four block pairs
    other = op.with_blocks(mdw.m5_block_pairs(ls, 1.4, 0.1, 1.0, 0.0))
    assert other.gauge_eo_pp is op.gauge_eo_pp
    assert other.program_signature == op.program_signature
    assert (jax.tree_util.tree_structure(other)
            == jax.tree_util.tree_structure(op))
    assert not np.array_equal(np.asarray(other._m5i[0]),
                              np.asarray(op._m5i[0]))
    assert sprog.presents(op, other) and not op.hermitian
    # the served hop form is the knob's pin or the measured winner,
    # never a race; interpreted kernels keep the vmapped stencil
    assert mdw.MEASURED_LS_HOP_FORM in ("pallas", "xla")
    assert mdw.served_ls_hop_form(op) == "xla"


# (b) the entry and exit programs against the path they replaced --------------

def test_verified_exit_equals_reconstruct_and_canonical_m():
    """Uneven extents, odd parity.  Any pair-form field in (not a
    solution: the residual is then O(1), and f32 rounding is 1e-7 of
    it): the exit's solution is reconstruct + join and its residual the
    canonical DiracMobius.M's.  (The entry is held by the API cases: a
    wrong right-hand side leaves a true residual of O(1).)"""
    dims, ls = CASES[1]
    geom, gauge = LatticeGeometry(dims), _gauge(5, dims)
    op = _from_links(gauge, geom, ls, ODD, True)
    T, Z, Y, X = geom.lattice_shape
    b = _field(1, (ls, T, Z, Y, X, 4, 3))
    x_pp = _pairs_field(2, geom, ls)
    (x, res), _ = sprog.verified_exit(op, b, x_pp)
    assert x.shape == b.shape and x.dtype == b.dtype
    be, bo = jax.vmap(lambda v: even_odd_split(v, geom))(b)
    xe, xo = op.reconstruct_pairs(x_pp, be, bo)
    old = jax.vmap(lambda e, o: even_odd_join(e, o, geom))(xe, xo)
    assert _rel(x, old) < 1e-6
    full = mdw.DiracMobius(gauge, geom, ls, M5, MF, B5, C5)
    want = float(jnp.linalg.norm((b - full.M(x)).ravel())
                 / jnp.linalg.norm(b.ravel()))
    assert abs(float(res) - want) < 1e-5 * want


def test_wall_source_in_is_what_the_entry_builds():
    """The benchmark's entry module and its plain reference build the
    same 5-d wall source from one 4-d source, each by itself: P_+ b on
    s = 0, P_- b on s = Ls - 1 (the q = P_- psi(0) + P_+ psi(Ls-1) of
    ops/dwf.py's s-hop), nothing between."""
    entry = importlib.import_module("benchmark.entry.invert_quda_mobius")
    ref = importlib.import_module("benchmark.reference.mobius")
    data = importlib.import_module("benchmark.data")
    lat, ls = (4, 6, 2, 8), 6
    b = data.gaussian_sources(data.key_of(3, 1000), lat, 1)
    mine = entry._wall_source(data.to_canonical_spinors(b, lat), ls)
    theirs = data.to_canonical_spinors(ref.wall_source(b[0], ls), lat)
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    assert float(jnp.abs(mine[1:-1]).max()) == 0.0
    assert float(jnp.abs(mine[0][..., 2:, :]).max()) == 0.0
    assert float(jnp.abs(mine[-1][..., :2, :]).max()) == 0.0
    # the s-hop's wrap couples exactly these two halves through -mf
    from quda_tpu.ops.dwf import m5_sop
    hop = m5_sop(ls, 0.0, 1.0, MF)
    assert hop.ap[0, ls - 1] == -MF and hop.am[ls - 1, 0] == -MF
    # and the embed puts x(s)[spin] on row 4 s + spin
    x5 = _field(4, (ls, 8, 2, 6, 4, 4, 3))
    rows = entry._embed(x5)
    assert rows.shape == (1, 8, 2, 6, 4, 4 * ls, 3)
    for s, spin in ((0, 0), (2, 3), (ls - 1, 1)):
        np.testing.assert_array_equal(
            np.asarray(rows[0, ..., 4 * s + spin, :]),
            np.asarray(x5[s, ..., spin, :]))


# (c) the term and the programs in the API's context --------------------------

L, LS = 4, 4


@pytest.fixture(scope="module")
def quda(tmp_path_factory):
    """init + a resident 4^4 gauge + a metrics session on the pair route
    (XLA stencil)."""
    mp = pytest.MonkeyPatch()
    mp.setenv("QUDA_TPU_PACKED", "1")
    for knob in ("QUDA_TPU_PALLAS", "QUDA_TPU_PRECISION_FORM",
                 "QUDA_TPU_DWF_FORM", "QUDA_TPU_SLOPPY_PRECISION"):
        mp.delenv(knob, raising=False)
    qconf.reset_cache()
    api.init_quda()
    # a worker that ran interpreted kernels before has filled the build
    # records (MAX_RECORDS): this module reads its own
    obuild.reset()
    omet.start(str(tmp_path_factory.mktemp("mobius_resident")))
    gauge = _gauge(9, (L,) * 4)
    api.load_gauge_quda(np.asarray(gauge),
                        GaugeParam(X=(L,) * 4, cuda_prec="single"))
    yield gauge
    omet.stop(flush_files=False)
    api.end_quda()
    mp.undo()
    qconf.reset_cache()


def _param(**kw):
    d = dict(dslash_type="mobius", Ls=LS, b5=B5, c5=C5, m5=-M5, mass=MF,
             inv_type="cg", solve_type="normop-pc", tol=1e-6,
             maxiter=2000, cuda_prec="single", cuda_prec_sloppy="half")
    d.update(kw)
    return InvertParam(**d)


def _counts(name, key):
    out = {}
    for (n, labels), v in omet.snapshot()["counters"].items():
        if n == name:
            lb = dict(labels)
            k = tuple(lb[i] for i in key)
            out[k] = out.get(k, 0) + int(v)
    return out


def _delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _true_residual(gauge, b, x, p):
    full = mdw.DiracMobius(gauge, LatticeGeometry((L,) * 4), p.Ls, -p.m5,
                           p.mass, p.b5, p.c5)
    return float(jnp.linalg.norm((b - full.M(x)).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _programs():
    return _counts("solve_program_total", ("solver", "outcome"))


def test_first_call_builds_then_every_action_reuses_links_and_programs(
        quda, monkeypatch):
    gauge = quda
    api._drop_resident("mobius")
    built = []
    for cls in (mdw.DiracMobius, mdw.DiracMobiusPC):
        monkeypatch.setattr(cls, "__init__", lambda *a, **k: built.append(a))
    t0, p0 = _counts("mobius_term_total", ("outcome",)), _programs()
    b = _field(11, (LS, L, L, L, L, 4, 3))
    p = _param()
    x = api.invert_quda(b, p)
    monkeypatch.undo()
    assert not built, "a canonical DiracMobius* was built on the route"
    assert p.converged and p.true_res < 1e-5 and p.iter_count > 10
    assert abs(_true_residual(gauge, b, x, p) - p.true_res) < 0.01 * p.true_res
    assert _delta(t0, _counts("mobius_term_total", ("outcome",))) == {
        ("built",): 1}
    assert _delta(p0, _programs()) == {
        ("prepare", "miss"): 1, ("cg", "miss"): 1,
        ("verified-exit", "miss"): 1}
    term = api._ctx["mobius"]
    assert set(term["ops"]) == {jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)}
    assert ("mobius", "resident_mobius") in {
        (r["family"], r["field"]) for r in omem.ledger()}
    assert _counts("dwf_hop_route_total", ("form", "ls"))[
        ("xla", str(LS))] >= 1
    # ... and the s-blocks follow the hop's form: XLA's einsum here
    assert set(_counts("dwf_sblock_route_total", ("form", "ls"))) == {
        ("einsum", str(LS))}
    names = {r["program"] for r in obuild.snapshot()}
    assert "_mobius_term_program" in names
    # the same action, another source: the term, the programs
    t1, p1, n1 = (_counts("mobius_term_total", ("outcome",)), _programs(),
                  sprog._traces[0])
    links = term["ops"][jnp.dtype(jnp.float32)].gauge_eo_pp
    p = _param()
    api.invert_quda(_field(12, b.shape), p)
    assert p.converged
    assert _delta(t1, _counts("mobius_term_total", ("outcome",))) == {
        ("reused",): 1}
    assert api._ctx["mobius"] is term
    # another mf, another M5, another b5 / c5: four block pairs a time,
    # the links and the three executables stay
    for kw in (dict(mass=0.1), dict(m5=-1.4), dict(b5=1.0, c5=0.0)):
        t2 = _counts("mobius_term_total", ("outcome",))
        b2 = _field(13, b.shape)
        p = _param(**kw)
        x2 = api.invert_quda(b2, p)
        assert p.converged
        assert abs(_true_residual(gauge, b2, x2, p) - p.true_res) < (
            0.01 * p.true_res)
        assert _delta(t2, _counts("mobius_term_total", ("outcome",))) == {
            ("rebuilt",): 1}
        now = api._ctx["mobius"]["ops"][jnp.dtype(jnp.float32)]
        assert now.gauge_eo_pp is links
    assert sprog._traces[0] == n1
    assert _delta(p1, _programs()) == {
        ("prepare", "hit"): 4, ("cg", "hit"): 4, ("verified-exit", "hit"): 4}
    # no program was built under any call after the first (the cell's
    # window_programs_built)
    late = [r for r in obuild.snapshot()
            if r["api"] == "invert_quda" and r["ordinal"] > 1
            and r["program"].startswith("_")]
    assert not late, late


def test_f32_solve_is_a_program_of_its_own_key(quda):
    """cuda_prec_sloppy single (what ``auto`` resolves to off the
    chip): the f32 operator in both places of the reliable-update
    program, held by the canonical operator's residual."""
    gauge = quda
    b = _field(21, (LS, L, L, L, L, 4, 3))
    p0 = _programs()
    p = _param(cuda_prec_sloppy="single")
    x = api.invert_quda(b, p)
    assert p.converged and p.true_res < 1e-5
    assert abs(_true_residual(gauge, b, x, p) - p.true_res) < 0.01 * p.true_res
    assert _delta(p0, _programs()) == {
        ("prepare", "hit"): 1, ("cg", "miss"): 1, ("verified-exit", "hit"): 1}


def test_a_new_gauge_or_matpc_drops_the_term(quda):
    gauge = quda
    api._resident_mobius(_param())
    term = api._ctx["mobius"]
    t0 = _counts("mobius_term_total", ("outcome",))
    new = api._resident_mobius(_param(matpc_type="odd-odd"))
    assert _delta(t0, _counts("mobius_term_total", ("outcome",))) == {
        ("rebuilt",): 1}
    assert new is not term
    assert new["ops"][jnp.dtype(jnp.float32)].matpc == ODD
    api.load_gauge_quda(np.asarray(gauge),
                        GaugeParam(X=(L,) * 4, cuda_prec="single"))
    assert api._ctx["mobius"] is None
    assert ("mobius", "resident_mobius") not in {
        (r["family"], r["field"]) for r in omem.ledger()}


@pytest.mark.parametrize("kw", [
    dict(solve_type="direct-pc"), dict(inv_type="bicgstab"),
    dict(dslash_type="mobius-eofa"), dict(dslash_type="domain-wall"),
    dict(cuda_prec="double"), dict(num_offset=2, offset=(0.0, 0.1))],
    ids=["direct-pc", "bicgstab", "eofa", "5d-pc", "double", "shifts"])
def test_every_other_route_keeps_the_canonical_classes(quda, kw):
    assert api._mobius_resident_route(_param())
    assert not api._mobius_resident_route(_param(**kw))
