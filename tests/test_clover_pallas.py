"""Fused clover pallas kernels (ops/clover_pallas) vs the staged XLA
composition — the operator-zoo bit-match pins (interpret mode).

The fused forms reproduce the STAGED rounding by construction (the K1
hop accumulator round-trips through the out tile at the store dtype
before the inverse blocks apply), so agreement is at the f32
reduction-order level: the in-kernel unrolled block matvec and the XLA
einsum sum in different orders, hence tight allclose rather than exact
equality (the DWF kernels, which reuse ONE hop kernel, pin exactly —
tests/test_dwf_pallas.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import EVEN, ODD, LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
from quda_tpu.models.clover import (DiracCloverPC, apply_clover_pairs,
                                    pack_clover_pairs)
from quda_tpu.ops import blas
from quda_tpu.ops import wilson_packed as wpk
from quda_tpu.ops import wilson_pallas_packed as wpp
from quda_tpu.ops.clover import clover_blocks
from quda_tpu.ops.clover_pallas import clover_pallas_packed

GEOM = LatticeGeometry((4, 4, 4, 4))
KAPPA = 0.12
CSW = 1.1


@pytest.fixture(scope="module")
def cfg():
    g = GaugeField.random(jax.random.PRNGKey(30), GEOM).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(31),
                                    GEOM).data.astype(jnp.complex64)
    return g, psi


def _rel(a, b):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return float(jnp.sqrt(blas.norm2(a - b) / blas.norm2(b)))


def _pair_ops(g, matpc, **kw):
    """(fused, staged) interpret-mode pair operators of the same PC."""
    dpc = DiracCloverPC(g, GEOM, KAPPA, CSW, matpc=matpc)
    op_p = dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                     form="pallas", **kw)
    op_x = dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                     form="xla", **kw)
    return op_p, op_x


@pytest.mark.slow
def test_k1_post_kernel_matches_staged(cfg):
    """The K1 fused kernel alone: E(D psi) == blocks applied to the
    staged hop.  Slow with the rest of the kernel pins: every fused
    interpret compile costs >15s and tier-1 runs the whole suite under
    a hard wall-clock budget — the non-slow tier keeps the pure-wiring
    pins (formsel gates, knob validation, labels, ledger) and the
    shared gather kernel stays covered by the wilson suites."""
    from quda_tpu.ops import clover_pallas as clp
    from quda_tpu.ops.wilson import split_gauge_eo
    g, psi = cfg
    T, Z, Y, X = GEOM.lattice_shape
    dims = (T, Z, Y, X)
    parity = 0
    gauge_eo_pp = tuple(
        wpk.to_packed_pairs(wpk.pack_gauge(geo), jnp.float32)
        for geo in split_gauge_eo(g, GEOM))
    pe, po = even_odd_split(psi, GEOM)
    src_pp = wpk.to_packed_pairs(wpk.pack_spinor(po), jnp.float32)
    rng = np.random.default_rng(7)
    blk = jnp.asarray(rng.standard_normal(
        (2, 6, 6, 2, T, Z, Y * X // 2)).astype(np.float32))
    u_bw = wpp.backward_gauge_eo(gauge_eo_pp[1 - parity], dims, parity)
    got = clp.dslash_eo_pallas_post(
        gauge_eo_pp[parity], u_bw, src_pp, dims, parity, blk_pl=blk,
        interpret=True, out_dtype=jnp.float32)
    hop = wpk.dslash_eo_packed_pairs(gauge_eo_pp, src_pp, dims, parity)
    ref = apply_clover_pairs(blk, hop.astype(jnp.float32))
    assert _rel(got, ref) < 1e-6


@pytest.mark.parametrize("matpc", [EVEN, ODD])
@pytest.mark.slow
def test_fused_schur_matches_staged(cfg, matpc):
    """K1+K2 fused (E(D psi), A x - kappa^2 D t) == the staged
    composition, both parities, M and Mdag."""
    g, psi = cfg
    op_p, op_x = _pair_ops(g, matpc)
    assert op_p._op_form == "pallas" and op_x._op_form == "xla"
    pe, po = even_odd_split(psi, GEOM)
    x = pe if matpc == EVEN else po
    for fn in ("M_pairs", "Mdag_pairs"):
        xp = wpk.to_packed_pairs(wpk.pack_spinor(x), jnp.float32)
        got = getattr(op_p, fn)(xp)
        ref = getattr(op_x, fn)(xp)
        assert _rel(got, ref) < 1e-6, fn


@pytest.mark.slow
def test_fused_schur_matches_staged_r12(cfg, monkeypatch):
    """Reconstruct-12 resident links through the fused kernels (the
    240-plane gauge tile) == the staged r12 composition."""
    from quda_tpu.utils import config as qconf
    g, psi = cfg
    monkeypatch.setenv("QUDA_TPU_RECONSTRUCT", "12")
    qconf.reset_cache()
    try:
        op_p, op_x = _pair_ops(g, EVEN)
    finally:
        monkeypatch.delenv("QUDA_TPU_RECONSTRUCT")
        qconf.reset_cache()
    assert op_p.gauge_eo_pp[0].shape[1] == 2  # rows kept: r12 storage
    pe, _ = even_odd_split(psi, GEOM)
    xp = wpk.to_packed_pairs(wpk.pack_spinor(pe), jnp.float32)
    assert _rel(op_p.M_pairs(xp), op_x.M_pairs(xp)) < 1e-6


@pytest.mark.slow
def test_fused_schur_mrhs_matches_staged(cfg):
    """MRHS fused kernels (RHS-innermost grid, gauge+block tiles
    resident across the stream) == vmapped staged, per lane."""
    g, psi = cfg
    op_p, op_x = _pair_ops(g, EVEN)
    pe, _ = even_odd_split(psi, GEOM)
    xp = wpk.to_packed_pairs(wpk.pack_spinor(pe), jnp.float32)
    xb = jnp.stack([xp, 2.0 * xp, xp[::-1]])
    got = op_p.M_pairs_mrhs(xb)
    ref = op_x.M_pairs_mrhs(xb)
    assert _rel(got, ref) < 1e-6


@pytest.mark.parametrize("diag_twist", [None, 0.17])
@pytest.mark.slow
def test_full_lattice_fused_matches_staged(cfg, diag_twist):
    """Full-lattice clover_pallas_packed (diagonal read from the center
    psi tile, no extra operand): A psi (+ i c g5 psi) - kappa D psi ==
    the staged pair composition."""
    from quda_tpu.models.twisted import _ig5_rot_pairs
    g, psi = cfg
    blocks = clover_blocks(g, KAPPA * CSW / 2)
    eye = jnp.eye(6, dtype=blocks.dtype)
    blocks = blocks + eye  # A = 1 + clover term (models/clover.DiracClover)
    blk_pl = pack_clover_pairs(blocks, jnp.float32)
    g_pl = wpp.to_pallas_layout(wpk.pack_gauge(g))
    p_pl = wpp.to_pallas_layout(wpk.pack_spinor(psi))
    T, Z, Y, X = GEOM.lattice_shape
    got = clover_pallas_packed(g_pl, blk_pl, p_pl, X, KAPPA,
                               diag_twist=diag_twist, interpret=True)
    ref = (apply_clover_pairs(blk_pl, p_pl)
           - KAPPA * wpk.dslash_packed_pairs(g_pl, p_pl, X, Y))
    if diag_twist is not None:
        ref = ref + _ig5_rot_pairs(p_pl, diag_twist)
    assert _rel(got, ref) < 1e-6


@pytest.mark.slow
def test_fused_pc_cg_solves(cfg):
    """End to end: CGNR on the fused operator solves M x = b (the
    interpret-mode stand-in for the chip acceptance drill)."""
    from quda_tpu.fields.spinor import even_odd_join
    from quda_tpu.models.clover import DiracClover
    from quda_tpu.solvers.cg import cg
    g, psi = cfg
    op_p, _ = _pair_ops(g, EVEN)
    pe, po = even_odd_split(psi, GEOM)
    rhs = op_p.prepare_pairs(pe, po)
    res = cg(op_p.MdagM_pairs, op_p.Mdag_pairs(rhs), tol=1e-7,
             maxiter=800)
    assert bool(res.converged)
    xe, xo = op_p.reconstruct_pairs(res.x, pe, po)
    x = even_odd_join(xe, xo, GEOM)
    d = DiracClover(g, GEOM, KAPPA, CSW)
    rel = float(jnp.sqrt(blas.norm2(psi - d.M(x)) / blas.norm2(psi)))
    assert rel < 1e-4


def test_formsel_capability_gates(cfg):
    """resolve_form degrades to the staged composition whenever the op
    cannot host the fused epilogue — and says so once."""
    from quda_tpu.models import formsel
    g, _ = cfg
    dpc = DiracCloverPC(g, GEOM, KAPPA, CSW)
    formsel._reset_notices()
    # no pallas at all -> xla even when pallas is requested
    op = dpc.pairs(jnp.float32, use_pallas=False, form="pallas")
    assert op._op_form == "xla"


def test_form_knob_validation(cfg):
    g, _ = cfg
    dpc = DiracCloverPC(g, GEOM, KAPPA, CSW)
    with pytest.raises(ValueError, match="QUDA_TPU_CLOVER_FORM"):
        dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                  form="bogus")


def test_solve_form_labels(cfg):
    """Roofline labels read off the authoritative operator state."""
    from quda_tpu.interfaces.quda_api import _solve_form
    from quda_tpu.obs.roofline import KERNEL_MODELS
    g, _ = cfg
    op_p, op_x = _pair_ops(g, EVEN)
    assert _solve_form(op_p) == "clover_pallas"
    assert _solve_form(op_x) == "clover_xla"
    assert _solve_form(op_p) in KERNEL_MODELS
    assert _solve_form(op_x) in KERNEL_MODELS


def test_clover_blocks_in_hbm_ledger(cfg):
    """The packed clover pair blocks are tracked in the HBM ledger
    (obs/memory) under the clover family — the round-18 coverage pin."""
    from quda_tpu.obs import memory as omem
    g, _ = cfg
    _pair_ops(g, EVEN)
    rows = {(r["family"], r["field"]): r["bytes"] for r in omem.ledger()}
    assert ("clover", "clover_pair_blocks") in rows
    # two block arrays (A_p, A_q^{-1}), each 2x6x6 complex f32 per odd/
    # even site: 2 x 576 B/site x vol/2
    vol = 4 ** 4
    assert rows[("clover", "clover_pair_blocks")] == 2 * 576 * vol // 2


# -- the fused MRHS calls' two routes (ops/clover_pallas.mrhs_route) --------
#
# PR 47.  Full-Z tiles (three psi operands, the epilogue per chunk of
# the hop's loop) where links, chiral blocks, spinors and ``xc`` fit
# the full-Z VMEM cap, z-blocks (the single-source call's five) where
# they do not or ``block_z`` < Z asks; per source both are bitwise the
# single-source kernel.  The single-source side is held to block_z = 8,
# so it really splices rows of its z-neighbour tiles.

_PROD = (4, 24, 24, 24)     # the cell's (Z, YXh) = (24, 288) tile: with
                            # blocks, one time-slice a step, three chunks
_SMALL = (4, 16, 2, 4)      # two f32 sublane tiles: two slices a step


def _fused_problem(dims, nrhs, dtype, with_blk, seed=47):
    T, Z, Y, X = dims
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape + (T, Z, Y * X // 2)),
                           jnp.float32).astype(dtype)
    return (draw(4, 3, 3, 2), draw(4, 3, 3, 2), draw(nrhs, 4, 3, 2),
            draw(nrhs, 4, 3, 2), draw(2, 6, 6, 2) if with_blk else None)


def _fused_mrhs_case(stage, dims, parity, nrhs, dtype=jnp.float32,
                     with_blk=True, zblock=True):
    """fullz, zblock (``block_z`` = one sublane tile) and the vmapped
    single-source kernel of one stage on one problem."""
    from quda_tpu.ops import clover_pallas as cp
    u, ub, psi, xc, blk = _fused_problem(dims, nrhs, dtype, with_blk)
    bz = wpp._sublane_rows(dtype)
    kw = dict(blk_pl=blk, interpret=True)
    if stage == "post":
        kw.update(twist=None if with_blk else (0.3, 0.9), out_dtype=dtype)
        mrhs = lambda **k: cp.dslash_eo_pallas_post_mrhs(
            u, ub, psi, dims, parity, **kw, **k)
        single = jax.vmap(lambda p: cp.dslash_eo_pallas_post(
            u, ub, p, dims, parity, block_z=bz, **kw))(psi)
    else:
        kw.update(diag_twist=None if with_blk else 0.3, hop_coeff=-0.0144,
                  out_dtype=jnp.float32)
        mrhs = lambda **k: cp.dslash_eo_pallas_diag_hop_mrhs(
            u, ub, psi, xc, dims, parity, **kw, **k)
        single = jax.vmap(lambda p, x: cp.dslash_eo_pallas_diag_hop(
            u, ub, p, x, dims, parity, block_z=bz, **kw))(psi, xc)
    route = cp.mrhs_route(u, psi, None if stage == "post" else xc, blk,
                          kw["out_dtype"])[0]
    return route, mrhs(), mrhs(block_z=bz) if zblock else None, single


@pytest.mark.parametrize("stage,dims,parity,nrhs,dtype,with_blk,zblock", [
    # tier-1: the cell's tile with one slice a step against both, and
    # two slices a step with eight sources against the single kernel
    ("diag_hop", _PROD, 0, 2, jnp.float32, True, True),
    ("post", _SMALL, 1, 8, jnp.float32, True, False),
    pytest.param("post", _PROD, 1, 2, jnp.float32, True, True,
                 marks=pytest.mark.slow),
    pytest.param("post", _PROD, 0, 8, jnp.float32, True, True,
                 marks=pytest.mark.slow),
    pytest.param("diag_hop", _PROD, 1, 8, jnp.float32, True, True,
                 marks=pytest.mark.slow),
    pytest.param("diag_hop", _SMALL, 1, 2, jnp.float32, True, True,
                 marks=pytest.mark.slow),
    pytest.param("post", _SMALL, 0, 2, jnp.float32, True, True,
                 marks=pytest.mark.slow),
    pytest.param("diag_hop", _SMALL, 0, 8, jnp.float32, True, True,
                 marks=pytest.mark.slow),
    # bf16 storage (two 16-row tiles) and the twist-only shapes of the
    # twisted-mass operator (no blocks: the Wilson batch's VMEM sums)
    pytest.param("post", (4, 32, 2, 4), 0, 2, jnp.bfloat16, True, True,
                 marks=pytest.mark.slow),
    pytest.param("diag_hop", (4, 32, 2, 4), 1, 2, jnp.bfloat16, True, True,
                 marks=pytest.mark.slow),
    pytest.param("post", _SMALL, 1, 2, jnp.float32, False, True,
                 marks=pytest.mark.slow),
    pytest.param("diag_hop", _SMALL, 0, 2, jnp.float32, False, True,
                 marks=pytest.mark.slow)])
def test_fused_mrhs_fullz_bitmatches_zblock_and_single_source(
        stage, dims, parity, nrhs, dtype, with_blk, zblock):
    route, fullz, zb, single = _fused_mrhs_case(
        stage, dims, parity, nrhs, dtype, with_blk, zblock)
    assert route == "fullz"
    assert fullz.dtype == (dtype if stage == "post" else jnp.float32)
    assert bool(jnp.all(fullz == single))
    assert zb is None or bool(jnp.all(fullz == zb))


_MIB = 2 ** 20


@pytest.mark.parametrize("case,want", [
    # (T, Z, YXh, dtype, out dtype, R, block_z, block dtype, xc dtype)
    # 24^4 f32 with the chiral blocks: one slice a step, 32.1 MiB for
    # post and 33.8 with xc for diag_hop (two would need 55.7 / 59.1)
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None, jnp.float32, None),
     ("fullz", 24, 1, 33619968)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ("fullz", 24, 1, 35389440)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 24, jnp.float32,
      jnp.float32), ("fullz", 24, 1, 35389440)),
    # bf16 storage and the twist-only calls hold two slices
    ((24, 24, 288, jnp.bfloat16, jnp.float32, 3, None, jnp.bfloat16,
      jnp.bfloat16), ("fullz", 24, 2, None)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None, None, jnp.float32),
     ("fullz", 24, 2, None)),
    # a caller's z-block, and local volumes whose full-Z tiles pass the
    # cap: twist-only, 32^4 still holds one slice and Z = 40 finds a
    # z-block; with the 144 block planes no z-block fits _pick_bz's
    # budget either (as on the parent)
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 8, jnp.float32,
      jnp.float32), ("zblock", 8, 1, None)),
    ((32, 32, 512, jnp.float32, jnp.float32, 3, None, None, None),
     ("fullz", 32, 1, None)),
    ((40, 40, 640, jnp.float32, jnp.float32, 3, None, None, None),
     ("zblock", 8, 1, None)),
    ((32, 32, 512, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ValueError),
    ((40, 40, 640, jnp.float32, jnp.float32, 3, None, jnp.float32, None),
     ValueError),
    # the shapes the interpreted cases above run on
    ((4, 24, 288, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ("fullz", 24, 1, 35389440)),
    ((4, 16, 4, jnp.float32, jnp.float32, 3, None, jnp.float32, None),
     ("fullz", 16, 2, None)),
    ((4, 32, 4, jnp.bfloat16, jnp.bfloat16, 3, None, jnp.bfloat16, None),
     ("fullz", 32, 2, None)),
    ((4, 16, 4, jnp.float32, jnp.float32, 3, 8, jnp.float32, None),
     ("zblock", 8, 1, None))])
def test_fused_mrhs_route_follows_the_shapes(case, want):
    """The fused MRHS call's route is the Wilson batch's arithmetic with
    the epilogue's blocks in the sums (144 planes of chiral blocks, 24
    of ``xc``, a slice, double-buffered like the rest): no kernel runs
    here.  models/wilson labels its counter with the same call."""
    from quda_tpu.obs import memory as omem
    from quda_tpu.ops import clover_pallas as cp
    T, Z, YXh, dt, odt, R, block_z, blk_dt, xc_dt = case
    S = jax.ShapeDtypeStruct
    extra = [(n, d) for n, d in ((144, blk_dt), (24, xc_dt))
             if d is not None]
    operands = (
        S((4, R, 3, 2, T, Z, YXh), dt), S((8, 4, 3, 2, T, Z, YXh), dt),
        None if xc_dt is None else S((8, 4, 3, 2, T, Z, YXh), xc_dt),
        None if blk_dt is None else S((2, 6, 6, 2, T, Z, YXh), blk_dt),
        odt, block_z)
    omem.reset()
    if want is ValueError:
        assert wpp._mrhs_fullz_fit(T, Z, YXh, dt, odt, R, block_z,
                                   extra=extra) is None
        with pytest.raises(ValueError, match="fits the VMEM budget"):
            cp.mrhs_route(*operands)
        return
    route, bz, bt, limit = cp.mrhs_route(*operands)
    assert (route, bz, bt) == want[:3]
    rows = {r["knob"]: r for r in omem.audit_vmem_budgets()}
    if route == "zblock":
        assert limit is None
        assert "QUDA_TPU_PALLAS_VMEM_MB[fullz]" not in rows
        return
    blocks, need = wpp._mrhs_fullz_vmem(Z, YXh, dt, odt, R, bt, extra=extra)
    assert limit == max(need, 16 * _MIB) <= wpp._MRHS_FULLZ_VMEM_CAP
    assert want[3] is None or limit == want[3]
    # bt tiles of every block the epilogue brings, padded like the rest
    def plane(d):
        sub = wpp._sublane_rows(d)
        return -(-Z // sub) * sub * -(-YXh // 128) * 128 \
            * jnp.dtype(d).itemsize
    assert blocks - wpp._mrhs_fullz_vmem(Z, YXh, dt, odt, R, bt)[0] == bt * (
        sum(n * plane(d) for n, d in extra))
    if bt == 1 and T % 2 == 0:
        assert wpp._mrhs_fullz_vmem(Z, YXh, dt, odt, R, 2, extra=extra)[1] \
            > wpp._MRHS_FULLZ_VMEM_CAP
    row = rows["QUDA_TPU_PALLAS_VMEM_MB[fullz]"]
    assert row["last_bz"] == Z and row["last_block_bytes"] == blocks


# -- the K2 kernel's gamma5, norm2 and residual forms, and the step --------
#
# PR 48.  ``dslash_eo_pallas_diag_hop_mrhs`` stores gamma5 of its value,
# sums the squares of what it stores per source, and in the residual
# form writes ``rc - alpha[n] * g5 v`` over ``rc``:
# ``_SchurPairOpBase.MdagM_cg_step_pairs_mrhs`` makes the first half of
# a batched CG iteration of them.  One operator and one batch of eight
# serve the kernel cases and the step, with exactly the step's static
# arguments, so each form is lowered once in this file (~15 s a form
# interpreted, whatever the lattice).

N_STEP = 8


def _g5(x):
    return x * jnp.asarray([1, 1, -1, -1], jnp.float32).reshape(
        (4,) + (1,) * 5)


def _per_source(a):
    return jnp.sum((a * a).reshape(a.shape[0], -1), axis=1)


def _k2(op, t, x, sign=+1, parity=None, **kw):
    """The K2 call as ``_M_sign_fused_mrhs`` makes it."""
    from quda_tpu.ops import clover_pallas as cp
    p = op.matpc if parity is None else parity
    blk, twist = op._fused_k2_params(sign)
    return cp.dslash_eo_pallas_diag_hop_mrhs(
        op.gauge_eo_pp[p], op._u_bw[p], t, x, tuple(op.dims), p,
        hop_coeff=-(op.kappa ** 2), blk_pl=blk, diag_twist=twist,
        interpret=True, out_dtype=jnp.float32, tb_sign=op._tb_sign,
        **{"block_z": None, **kw})


def _step_batch(seed=48):
    rng = np.random.default_rng(seed)
    draw = lambda: jnp.asarray(rng.standard_normal(
        (N_STEP, 4, 3, 2, 4, 4, 8)), jnp.float32)
    alpha = jnp.asarray(0.2 + 0.17 * np.arange(N_STEP), jnp.float32)
    return draw(), draw(), draw(), alpha


@pytest.fixture(scope="module")
def k2_forms(cfg):
    """The fused and the XLA-stencil clover operator on one gauge, a
    batch ``(t, x, rc, alpha)`` of eight, and the three full-Z K2 calls
    on it: ``combine`` (the plain one), ``norm2``, ``residual``."""
    g, _ = cfg
    dpc = DiracCloverPC(g, GEOM, KAPPA, CSW, matpc=EVEN)
    op = dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   form="pallas")
    xla = dpc.pairs(jnp.float32, form="xla")
    t, x, rc, alpha = _step_batch()
    return {"op": op, "xla": xla, "t": t, "x": x, "rc": rc, "alpha": alpha,
            "combine": _k2(op, t, x),
            "norm2": _k2(op, t, x, g5=True, nrm=True),
            "residual": _k2(op, t, x, g5=True, rc=rc, alpha=alpha)}


def test_k2_norm2_form_is_gamma5_of_the_plain_call_and_sums_it(k2_forms):
    v, n2 = k2_forms["norm2"]
    assert v.dtype == jnp.float32 and n2.shape == (N_STEP,)
    assert n2.dtype == jnp.float32
    # a sign in the store: bitwise XLA's gamma5 pass over the plain call
    assert bool(jnp.all(v == _g5(k2_forms["combine"])))
    np.testing.assert_allclose(np.asarray(n2), np.asarray(_per_source(v)),
                               rtol=2e-6)


def test_k2_residual_form_updates_rc_with_a_source_s_own_alpha(k2_forms):
    r, r2 = k2_forms["residual"]
    alpha = k2_forms["alpha"]
    assert len({float(a) for a in alpha}) == N_STEP
    want = k2_forms["rc"] - alpha.reshape((N_STEP,) + (1,) * 6) \
        * k2_forms["norm2"][0]
    assert r.dtype == jnp.float32 and r.shape == want.shape
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(r - want))) <= 4e-7 * scale
    np.testing.assert_allclose(np.asarray(r2), np.asarray(_per_source(want)),
                               rtol=2e-6)
    # another order of the sources is another alpha a source
    assert float(jnp.max(jnp.abs(r[::-1] - want))) > 1e-2 * scale


@pytest.mark.parametrize("form,parity,block_z", [
    # tier-1: the residual form (gamma5, rc, alpha and the sums) on
    # z-blocks at the other parity; the rest of the table is slow
    ("residual", ODD, 2),
    pytest.param("norm2", ODD, 2, marks=pytest.mark.slow),
    pytest.param("residual", EVEN, 2, marks=pytest.mark.slow),
    pytest.param("norm2", EVEN, 2, marks=pytest.mark.slow),
    pytest.param("residual", ODD, None, marks=pytest.mark.slow),
    pytest.param("norm2", ODD, None, marks=pytest.mark.slow)])
def test_k2_forms_on_both_routes_and_parities(cfg, k2_forms, form, parity,
                                              block_z):
    """Against XLA's composition on the XLA stencil (the hop's sums in
    another order: f32 round-off), per route (``block_z`` < Z asks for
    z-blocks) and parity; where the shared fixture holds the same form
    on full-Z tiles of the same parity, the spinor bitwise against it."""
    from quda_tpu.ops import clover_pallas as cp
    g, _ = cfg
    if parity == EVEN:
        op, xla = k2_forms["op"], k2_forms["xla"]
    else:
        dpc = DiracCloverPC(g, GEOM, KAPPA, CSW, matpc=ODD)
        op = dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                       form="pallas")
        xla = dpc.pairs(jnp.float32, form="xla")
    t, x, rc, alpha = (k2_forms[k] for k in ("t", "x", "rc", "alpha"))
    blk = op._fused_k2_params(+1)[0]
    u = op.gauge_eo_pp[parity]
    name, route = cp.mrhs_form(u, t, x, blk, jnp.float32, block_z, True,
                               rc if form == "residual" else None)
    assert name == form
    assert route[:2] == (("zblock", block_z) if block_z else ("fullz", 4))
    v = _g5(xla._diag_sign_pairs_mrhs(x, +1, jnp.float32)
            - (xla.kappa ** 2) * xla._d_to_mrhs(t, parity, jnp.float32))
    if form == "residual":
        got, sums = _k2(op, t, x, g5=True, rc=rc, alpha=alpha,
                        block_z=block_z)
        want = rc - alpha.reshape((N_STEP,) + (1,) * 6) * v
    else:
        got, sums = _k2(op, t, x, g5=True, nrm=True, block_z=block_z)
        want = v
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 4e-6 * scale
    np.testing.assert_allclose(np.asarray(sums),
                               np.asarray(_per_source(want)), rtol=1e-5)
    if parity == EVEN:
        assert bool(jnp.all(got == k2_forms[form][0]))


def _step_close(got, want, rtol=1e-5):
    assert [(v.shape, v.dtype) for v in got] == [
        (v.shape, v.dtype) for v in want]
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol)
    scale = float(jnp.max(jnp.abs(want[0])))
    assert float(jnp.max(jnp.abs(got[0] - want[0]))) <= rtol * scale


def test_cg_step_of_the_clover_pair_operator_is_the_generic_step(k2_forms):
    """``MdagM_cg_step_pairs_mrhs`` on the fused form (``pAp`` out of
    the first M's K2 epilogue, the new ``r`` and ``|r|^2`` out of the
    second's) against ``block.cg_step`` of the XLA-stencil operator's
    ``MdagM_pairs_mrhs``, to f32 round-off; in the ``xla`` form the
    method IS that generic step, bit for bit."""
    from quda_tpu.solvers.block import cg_step
    op, xla = k2_forms["op"], k2_forms["xla"]
    p, r = k2_forms["x"], k2_forms["rc"]
    rz = jnp.asarray(0.7 + 0.9 * np.arange(N_STEP), jnp.float32)
    assert op._mrhs_form() == "pallas" and xla._mrhs_form() == "xla"
    want = cg_step(xla.MdagM_pairs_mrhs)(p, r, rz, 0)
    _step_close(op.MdagM_cg_step_pairs_mrhs(p, r, rz), want)
    assert len({float(a) for a in want[2]}) == N_STEP
    for g, w in zip(xla.MdagM_cg_step_pairs_mrhs(p, r, rz, 0), want):
        assert bool(jnp.all(g == w))


@pytest.fixture
def clover_route_counts(tmp_path):
    """A metrics session of the test's own; calling the fixture reads
    ``clover_mrhs_route_total`` as {(form, stage, route, epilogue): n}."""
    from quda_tpu.obs import memory as omem
    from quda_tpu.obs import metrics as omet
    omet.stop(flush_files=False)
    omem.reset()
    omet.start(str(tmp_path))

    def read():
        return {tuple(dict(lab)[k] for k in ("form", "stage", "route",
                                             "epilogue")): int(v)
                for (n, lab), v in omet.snapshot()["counters"].items()
                if n == "clover_mrhs_route_total"}
    yield read
    omet.stop(flush_files=False)
    omem.reset()


def test_cg_step_counts_its_kernels_by_epilogue(cfg, k2_forms,
                                                clover_route_counts):
    """Traced, not run: the fused f32 step is two ``post``, one
    ``norm2`` and one ``residual`` K2 call; sloppy storage keeps the
    generic step (the kernel's sums would be of f32 values the next
    hop does not read) and every K2 call is ``combine``, as in the
    ``xla`` form."""
    g, _ = cfg
    p, r = k2_forms["x"], k2_forms["rc"]
    rz = jnp.ones((N_STEP,), jnp.float32)
    jax.eval_shape(k2_forms["op"].MdagM_cg_step_pairs_mrhs, p, r, rz)
    fused = {("pallas", "post", "fullz", "none"): 2,
             ("pallas", "diag_hop", "fullz", "norm2"): 1,
             ("pallas", "diag_hop", "fullz", "residual"): 1}
    assert clover_route_counts() == fused
    lo = DiracCloverPC(g, GEOM, KAPPA, CSW, matpc=EVEN).pairs(
        jnp.bfloat16, use_pallas=True, pallas_interpret=True, form="pallas")
    out = jax.eval_shape(lo.MdagM_cg_step_pairs_mrhs,
                         p.astype(jnp.bfloat16), r.astype(jnp.bfloat16), rz)
    assert out[0].dtype == jnp.bfloat16
    assert clover_route_counts() == {
        **fused, ("pallas", "post", "fullz", "none"): 4,
        ("pallas", "diag_hop", "fullz", "combine"): 2}
    jax.eval_shape(k2_forms["xla"].MdagM_cg_step_pairs_mrhs, p, r, rz, 0)
    assert {k: v for k, v in clover_route_counts().items()
            if k[0] == "xla"} == {("xla", "post", "none", "none"): 2,
                                  ("xla", "diag_hop", "none", "combine"): 2}


@pytest.mark.slow
def test_cg_step_of_a_twisted_clover_operator_changes_the_twist_sign(cfg):
    """(36 s: two more K2 forms with the twist compiled in; no served
    path runs it.)  The step is written on ``Mdag = g5 M(-s) g5``: the second M of a
    twisted-clover operator takes the other twist sign and the other
    inverse blocks.  With a twist that matters (mu 0.3), against the
    generic step of the XLA-stencil operator."""
    from quda_tpu.models.twisted import DiracTwistedCloverPC
    from quda_tpu.solvers.block import cg_step
    g, _ = cfg
    dpc = DiracTwistedCloverPC(g, GEOM, KAPPA, 0.3, CSW)
    op = dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   form="pallas")
    xla = dpc.pairs(jnp.float32, form="xla")
    assert op._mrhs_form() == "pallas" and op.a != 0
    _, p, r, _ = _step_batch(49)
    rz = jnp.asarray(0.7 + 0.9 * np.arange(N_STEP), jnp.float32)
    want = cg_step(xla.MdagM_pairs_mrhs)(p, r, rz, 0)
    _step_close(op.MdagM_cg_step_pairs_mrhs(p, r, rz), want)
    # the twist is felt: the same sign in the second M is ten times the
    # tolerance off
    wrong = cg_step(lambda x: xla._g5_pairs_mrhs(xla._M_sign_pairs_mrhs(
        xla._g5_pairs_mrhs(xla._M_sign_pairs_mrhs(x, +1)), +1)))(p, r, rz, 0)
    assert float(jnp.max(jnp.abs(wrong[0] - want[0]))) \
        > 1e-4 * float(jnp.max(jnp.abs(want[0])))


@pytest.mark.slow
def test_batched_cg_loop_on_the_clover_operators_own_step(k2_forms):
    """(80 s: the loop's module holds four interpreted kernels; on the
    chip the benchmark's ``correct`` holds every call of the cell to
    the plain reference, and tests/test_chip_compile.py the loop's
    shape.)  ``batched_cg_pairs_loop`` on the fused operator's own step against
    the loop on the generic step of the XLA-stencil operator: the same
    iterations to a source within one check, the same solutions to the
    solver's tolerance."""
    from quda_tpu.solvers.block import batched_cg_pairs_loop, cg_step
    op, xla, b = k2_forms["op"], k2_forms["xla"], k2_forms["rc"][:3]
    tol = 1e-6
    solve = lambda step: jax.jit(lambda b: batched_cg_pairs_loop(
        step, b, tol, 400, 1, False, None))(b)
    got = solve(op.MdagM_cg_step_pairs_mrhs)
    want = solve(cg_step(xla.MdagM_pairs_mrhs))
    assert bool(jnp.all(got.converged)) and bool(jnp.all(want.converged))
    assert np.all(np.abs(np.asarray(got.iters) - np.asarray(want.iters)) <= 1)
    for i in range(3):
        assert _rel(got.x[i], want.x[i]) < 10 * tol
        assert _rel(xla.MdagM_pairs_mrhs(got.x)[i], b[i]) < 5 * tol


# -- one source: the mixed-precision CG's step (PR 50) ---------------------
#
# ``dslash_eo_pallas_diag_hop`` has its ``_mrhs`` twin's forms, and
# where it stores narrower than f32 the hop sum stays in an f32 VMEM
# scratch: ``_SchurPairOpBase.MdagM_cg_step_pairs`` makes the first half
# of a ``cg_reliable_loop`` iteration of them, in any storage.  Three
# interpreted kernels a storage (``post``, the ``norm2`` and the
# ``residual`` K2 form) and, in bf16, the parent's f32-out K2 call on
# the step's own operands, all paid in the fixture.

@pytest.fixture(scope="module", params=[
    pytest.param(jnp.bfloat16, id="bf16"),
    pytest.param(jnp.float32, id="f32")])
def one_source_step(cfg, request):
    """The fused and the XLA-stencil clover operator at one storage,
    ``(p, r, r2)`` in it, the fused operator's own step on them, the
    generic step of the XLA-stencil operator's ``MdagM_pairs`` and
    what the step's first K2 call (the ``norm2`` form) returned; in
    bf16 also ``parent``: that call again on the same operands as the
    parent made it, an f32 result that XLA casts."""
    from quda_tpu.ops import clover_pallas as cp
    from quda_tpu.solvers import mixed
    store = request.param
    g, _ = cfg
    dpc = DiracCloverPC(g, GEOM, KAPPA, CSW, matpc=EVEN)
    op = dpc.pairs(store, use_pallas=True, pallas_interpret=True,
                   form="pallas")
    xla = dpc.pairs(store, form="xla")
    rng = np.random.default_rng(50)
    p, r = (jnp.asarray(rng.standard_normal((4, 3, 2, 4, 4, 8)),
                        jnp.float32).astype(store) for _ in range(2))
    r2 = jnp.float32(3.7)
    want = mixed.cg_step(xla.MdagM_pairs,
                         mixed.pair_inplace_codec(store))(p, r, r2, 0)
    k2, calls = cp.dslash_eo_pallas_diag_hop, []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cp, "dslash_eo_pallas_diag_hop", lambda *a, **k: (
            calls.append((a, k, k2(*a, **k))), calls[-1][2])[1])
        got = op.MdagM_cg_step_pairs(p, r, r2)
    (a, k, (q, pAp)), _ = calls
    assert k["g5"] and k["nrm"] and k["out_dtype"] == store
    parent = None
    if store == jnp.bfloat16:
        parent = k2(*a, **dict(k, g5=False, nrm=False,
                               out_dtype=jnp.float32)).astype(store)
    return {"store": store, "xla": xla, "p": p, "r": r, "r2": r2,
            "want": want, "got": got, "q": q, "pAp": pAp, "parent": parent,
            "eps": float(jnp.finfo(store).eps)}


def _f32(v):
    return np.asarray(v.astype(jnp.float32))


def test_cg_step_of_one_source_on_the_fused_form_is_the_generic_step(
        one_source_step):
    """``MdagM_cg_step_pairs`` on the fused form against
    ``mixed.cg_step`` of the XLA-stencil operator's ``MdagM_pairs``
    and the in-place pair codec on the same operands: ``pAp``
    (``|q|^2`` where the generic step takes ``p . A p``), ``alpha``,
    the new ``r`` (rounded once from f32 where the generic step rounds
    ``A p`` first) and its sum within the storage's rounding.  In the
    ``xla`` form the operator offers no step (the program takes the
    generic one)."""
    s = one_source_step
    got, want, eps = s["got"], s["want"], s["eps"]
    assert [(v.shape, v.dtype) for v in got] == [
        (v.shape, v.dtype) for v in want]
    for g_, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w),
                                   rtol=max(2e-2 * eps, 1e-5))
    assert np.max(np.abs(_f32(got[0]) - _f32(want[0]))) \
        <= max(eps, 4e-6) * np.max(np.abs(_f32(want[0])))
    assert s["xla"].MdagM_cg_step_pairs is None


def test_norm2_form_of_one_source_stores_what_the_parent_stored(
        one_source_step):
    """The step's first ``M``: ``q`` in storage, ``pAp`` the f32 sum
    of its squares as stored, ``q`` gamma5 of the XLA-stencil
    operator's ``M p`` to the storage's rounding; in bf16 BITWISE
    gamma5 of what the parent stored, the f32-out K2 call cast by XLA:
    the f32 scratch under the bf16 out tile changes no value."""
    s = one_source_step
    q, pAp, store, eps = s["q"], s["pAp"], s["store"], s["eps"]
    assert q.dtype == store and pAp.dtype == jnp.float32
    assert float(pAp) == float(s["got"][3])
    np.testing.assert_allclose(
        float(pAp), float(np.sum(_f32(q).astype(np.float64) ** 2)),
        rtol=2e-6)
    qx = _f32(_g5(s["xla"].M_pairs(s["p"]).astype(jnp.float32)))
    assert np.max(np.abs(_f32(q) - qx)) <= max(eps, 4e-6) * np.max(np.abs(qx))
    if store == jnp.bfloat16:
        assert bool(jnp.all(
            q == _g5(s["parent"].astype(jnp.float32)).astype(store)))
