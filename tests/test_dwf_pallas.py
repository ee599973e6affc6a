"""Ls-batched DWF/Möbius 4d hop kernels (ops/dwf_pallas) vs the
vmap-over-s stencil (interpret mode).

The fused form changes ONLY the batching — Ls rides the MRHS grid axis
of the UNCHANGED v2 Wilson kernel, so each gauge tile is fetched once
per (t, z-block) while Ls spinor planes stream through it.  Same
kernel, same reduction order: the pins here are EXACT equality, not
allclose (contrast tests/test_clover_pallas.py, where the fused epilogue
reorders the block-matvec reduction).  The dense (Ls, Ls) m5 chirality
blocks follow the hop's form since PR 44 (the VPU kernel
``mobius_sblock_pallas`` beside the Ls-batched hop, XLA's einsum beside
the vmapped one), f32 sums in another order: a whole Möbius operator is
compared with itself under the vmapped hop alone (``_mobius_both``), so
both sides run the same s-block form and stay bit-equal, and the kernel
is held to the einsum, to a tolerance, in
``test_sblock_kernel_matches_the_einsum`` only."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import EVEN, LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.models.domain_wall import (DiracDomainWall5DPC,
                                         DiracMobiusPC)

GEOM = LatticeGeometry((4, 4, 4, 4))
M5 = -1.8
MF = 0.04


@pytest.fixture(scope="module")
def gauge():
    return GaugeField.random(jax.random.PRNGKey(50), GEOM).data.astype(
        jnp.complex64)


def _both(dpc):
    op_p = dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                     form="pallas")
    op_x = dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                     form="xla")
    assert op_p._op_form == "pallas" and op_x._op_form == "xla"
    return op_p, op_x


def _mobius_both(dpc):
    """The Möbius pair operator on the Ls-batched hop, and itself with
    the hop alone pinned to the vmapped stencil: ``_op_form`` picks the
    s-block form too, so both sides keep it and the Ls-batched hop is
    the one difference."""
    op_p = dpc.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                     form="pallas")
    assert op_p._op_form == "pallas"
    op_x = copy.copy(op_p)
    hop = op_x._hop_to_pairs
    op_x._hop_to_pairs = lambda *a, **k: hop(*a, form="xla", **k)
    return op_p, op_x


def _rand_pairs(op, ls, seed=0):
    yxh = op.gauge_eo_pp[0].shape[-1]
    T, Z, _, _ = op.dims
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(
        (ls, 4, 3, 2, T, Z, yxh)).astype(np.float32))


def _check_exact(op_p, op_x, x, fns=("M_pairs", "Mdag_pairs")):
    for fn in fns:
        got = getattr(op_p, fn)(x)
        ref = getattr(op_x, fn)(x)
        assert jnp.array_equal(got, ref), fn


def _blocks_ref(blk, x, adjoint, axpy):
    """The einsum of DiracMobiusPCPairs._apply_blocks in f64, from the
    values the storage holds."""
    ap, am = (np.asarray(b, np.float64) for b in blk)
    if adjoint:
        ap, am = ap.T, -am.T
    f = np.asarray(x.astype(jnp.float32), np.float64)
    out = np.concatenate([np.einsum("st,t...->s...", ap, f[:, :2]),
                          np.einsum("st,t...->s...", am, f[:, 2:])], axis=1)
    if axpy is not None:
        y, a = axpy
        out = np.asarray(y.astype(jnp.float32), np.float64) + a * out
    return out


@pytest.mark.parametrize("form", ["plain", "adjoint", "accumulate"])
@pytest.mark.parametrize("store", ["f32", "bf16"])
@pytest.mark.parametrize("ls", [4, 12])
def test_sblock_kernel_matches_the_einsum(gauge, ls, store, form):
    """The s-block kernel (interpreted) through ``_apply_blocks`` against
    the einsum form of the same call and against f64: the plain product,
    the adjoint with gamma5 in the blocks (Mdag's first product), the
    accumulate form ``y - 1/4 B x`` from f32 sums.  f32 to 1e-6 of the
    result's scale; bf16 storage to one rounding of the result."""
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[store]
    op_e = DiracMobiusPC(gauge, GEOM, ls, M5, MF, b5=1.5, c5=0.5).pairs(dt)
    assert op_e._op_form == "xla"
    op_k = copy.copy(op_e)
    op_k._op_form, op_k._pallas_interpret = "pallas", True
    x = _rand_pairs(op_e, ls, seed=ls).astype(dt)
    kw = {"plain": dict(),
          "adjoint": dict(adjoint=True, g5=True),
          "accumulate": dict(axpy=(_rand_pairs(op_e, ls, seed=1).astype(dt),
                                   -0.25))}[form]
    blk = op_e._mix if form == "plain" else op_e._m5i
    got = op_k._apply_blocks(blk, x, **kw)
    ein = op_e._apply_blocks(blk, x, **kw)
    assert got.dtype == ein.dtype == dt and got.shape == x.shape
    ref = _blocks_ref(blk, x, form == "adjoint", kw.get("axpy"))
    scale = np.abs(ref).max()
    for out in (got, ein):
        err = np.abs(np.asarray(out.astype(jnp.float32), np.float64) - ref)
        if store == "f32":
            assert err.max() <= 1e-6 * scale
        else:   # neighbouring bf16 values lie 2^-7 of their size apart
            assert (err <= 2.0 ** -8 * np.abs(ref) + 1e-6 * scale).all()
    # the storage width changes nothing in the arithmetic: f32 out of
    # bf16 in is the f32 kernel on the widened values
    if store == "bf16" and form == "plain":
        wide = op_k._apply_blocks(blk, x, out_dtype=jnp.float32)
        assert jnp.array_equal(wide, op_k._apply_blocks(
            blk, x.astype(jnp.float32), out_dtype=jnp.float32))


def test_sblock_slices_fit_the_vmem_budget_or_raise():
    """``_sblock_tb``: the time-slices a grid step holds, from the
    budget and tile arithmetic of ``blas_pallas._pick_rows``; the need
    is what the call hands Mosaic as its limit, so a slice that does not
    fit raises (the einsum serves it) and is never asked for."""
    from quda_tpu.ops import blas_pallas as bpl
    from quda_tpu.ops import dwf_pallas as dwp
    bf, f32 = jnp.bfloat16, jnp.float32
    # 24^4, Ls 12: a (24, 288) plane is (32, 384) bf16, (24, 384) f32
    assert bpl._tile_bytes(24, 288, 2, 16) == 32 * 384 * 2
    assert dwp._sblock_tb(24, 24, 288, 12, [bf, bf]) == (
        8, 2 * 8 * 12 * 2 * 32 * 384 * 2)
    assert dwp._sblock_tb(24, 24, 288, 12, [f32, f32])[0] == 6
    assert dwp._sblock_tb(24, 24, 288, 12, [f32, f32, f32])[0] == 4
    for args in [(24, 24, 288, 12, [f32, bf, bf]), (4, 4, 8, 4, [f32] * 3)]:
        tb, need = dwp._sblock_tb(*args)
        assert args[0] % tb == 0 and need <= 2 * bpl._vmem_budget()
    with pytest.raises(ValueError, match="QUDA_TPU_DWF_FORM=xla"):
        dwp._sblock_tb(96, 48, 1152, 12, [f32] * 3)


@pytest.mark.slow
def test_ls_batched_kernel_bitmatches_per_slice(gauge):
    """The Ls-batched kernel alone vs the per-slice v2 kernel it wraps.
    Identical kernel body, identical reduction order: exact equality.
    Slow like every MRHS-wrap interpret compile (tests/test_multirhs.py
    precedent); tier-1 keeps the cheap label/ledger wiring pins below,
    and the underlying kernel is pinned by the wilson suites."""
    from quda_tpu.ops import dwf_pallas as dwp
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.ops import wilson_pallas_packed as wpp
    from quda_tpu.ops.wilson import split_gauge_eo
    T, Z, Y, X = GEOM.lattice_shape
    dims = (T, Z, Y, X)
    parity = 0
    gauge_eo_pp = tuple(
        wpk.to_packed_pairs(wpk.pack_gauge(geo), jnp.float32)
        for geo in split_gauge_eo(gauge, GEOM))
    u_bw = wpp.backward_gauge_eo(gauge_eo_pp[1 - parity], dims, parity)
    rng = np.random.default_rng(9)
    psi5 = jnp.asarray(rng.standard_normal(
        (4, 4, 3, 2, T, Z, Y * X // 2)).astype(np.float32))
    got = dwp.dslash_eo_pallas_packed_ls(
        gauge_eo_pp[parity], u_bw, psi5, dims, parity, interpret=True)
    ref = jnp.stack([wpp.dslash_eo_pallas_packed(
        gauge_eo_pp[parity], u_bw, psi5[s], dims, parity,
        interpret=True) for s in range(4)])
    assert jnp.array_equal(got, ref)


@pytest.mark.slow
def test_mobius_ls4_fused_hop_bitmatches(gauge):
    op_p, op_x = _mobius_both(DiracMobiusPC(gauge, GEOM, 4, M5, MF,
                                            b5=1.5, c5=0.5))
    _check_exact(op_p, op_x, _rand_pairs(op_p, 4))


@pytest.mark.slow
def test_mobius_ls8_fused_hop_bitmatches(gauge):
    op_p, op_x = _mobius_both(DiracMobiusPC(gauge, GEOM, 8, M5, MF,
                                            b5=1.5, c5=0.5))
    _check_exact(op_p, op_x, _rand_pairs(op_p, 8))


@pytest.mark.slow
def test_mobius_prepare_path_bitmatches(gauge):
    """prepare_pairs runs the m5-inverse blocks AND one fused hop —
    the solve entry path must route the same kernel."""
    from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
    op_p, op_x = _mobius_both(DiracMobiusPC(gauge, GEOM, 4, M5, MF,
                                            b5=1.5, c5=0.5))
    b = jnp.stack([ColorSpinorField.gaussian(
        jax.random.PRNGKey(60 + s), GEOM).data.astype(jnp.complex64)
        for s in range(4)])
    be = jax.vmap(lambda v: even_odd_split(v, GEOM)[0])(b)
    bo = jax.vmap(lambda v: even_odd_split(v, GEOM)[1])(b)
    assert jnp.array_equal(op_p.prepare_pairs(be, bo),
                           op_x.prepare_pairs(be, bo))


@pytest.mark.slow
def test_dw5d_ls4_fused_hop_bitmatches(gauge):
    """The 5d-checkerboard hop groups s-slices by 5d parity; each group
    rides the Ls-batched kernel at its own 4d target parity."""
    op_p, op_x = _both(DiracDomainWall5DPC(gauge, GEOM, 4, M5, MF))
    _check_exact(op_p, op_x, _rand_pairs(op_p, 4, seed=1))


@pytest.mark.slow
def test_dw5d_ls8_fused_hop_bitmatches(gauge):
    op_p, op_x = _both(DiracDomainWall5DPC(gauge, GEOM, 8, M5, MF))
    _check_exact(op_p, op_x, _rand_pairs(op_p, 8, seed=2))


@pytest.mark.slow
def test_mobius_fused_pc_cg_solves(gauge):
    """End to end: CGNR on the fused Möbius PC operator solves
    M x = rhs in pair space (interpret mode)."""
    from quda_tpu.ops import blas
    from quda_tpu.solvers.cg import cg
    op_p, _ = _both(DiracMobiusPC(gauge, GEOM, 4, M5, MF,
                                  b5=1.5, c5=0.5))
    rhs = _rand_pairs(op_p, 4, seed=3)
    res = cg(op_p.MdagM_pairs, op_p.Mdag_pairs(rhs), tol=1e-7,
             maxiter=600)
    assert bool(res.converged)
    r = rhs - op_p.M_pairs(res.x)
    rel = float(jnp.sqrt(blas.norm2(r) / blas.norm2(rhs)))
    assert rel < 1e-5


def test_solve_form_labels(gauge):
    """dwf labels: registered Ls get traffic rows, other Ls fall back
    to the honest flops-only 'dwf_pallas', staged lands on 'dwf_xla'."""
    from quda_tpu.interfaces.quda_api import _solve_form
    from quda_tpu.obs.roofline import KERNEL_MODELS
    op4_p, op4_x = _both(DiracMobiusPC(gauge, GEOM, 4, M5, MF,
                                       b5=1.5, c5=0.5))
    op6_p, _ = _both(DiracMobiusPC(gauge, GEOM, 6, M5, MF,
                                   b5=1.5, c5=0.5))
    assert _solve_form(op4_p) == "dwf_ls4_pallas"
    assert _solve_form(op4_x) == "dwf_xla"
    assert _solve_form(op6_p) == "dwf_pallas"
    for lbl in ("dwf_ls4_pallas", "dwf_xla", "dwf_pallas"):
        assert lbl in KERNEL_MODELS


def test_m5_blocks_in_hbm_ledger(gauge):
    """The Ls-resident m5 factor blocks live in the HBM ledger under
    the dwf family — round-18 coverage pin."""
    from quda_tpu.obs import memory as omem
    _both(DiracMobiusPC(gauge, GEOM, 4, M5, MF, b5=1.5, c5=0.5))
    rows = {(r["family"], r["field"]) for r in omem.ledger()}
    assert ("dwf", "m5_pair_blocks") in rows
