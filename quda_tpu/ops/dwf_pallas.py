"""Ls-batched 4d hop kernels for domain-wall / Möbius fermions.

The tentpole observation (ISSUE 20, mirroring QUDA's
dslash_domain_wall_m5.cuh split): the 4d hop of a 5d operator is
EXACTLY the MRHS Wilson problem with Ls playing the RHS role.  The
(Ls, 4, 3, 2, T, Z, YXh) pair layout produced by
models/domain_wall._LsPairIOMixin IS the (N, ...) MRHS layout of
ops/wilson_pallas_packed.dslash_eo_pallas_packed_mrhs, whose gauge
BlockSpec index maps ignore the batch index — so each gauge tile is
fetched once per (t, z-block) while all Ls spinor planes stream
through it, instead of the links re-fetched for every s plane of a
vmap-over-s launch (batch OUTERMOST).

What a plane moves is the MRHS kernel's account (the comment above
``_LeadAxisRef`` there): where two time-slices of whole (Z, YXh) tiles
fit VMEM the full-Z route reads each spinor plane twice, 288 + 576/Ls
bytes per site per plane (three times, 384 + 576/Ls, where only one
slice fits); larger local volumes fall back to z-blocks and five
reads, 576 + 576/Ls.  The route follows the shapes, Ls included
nowhere: these wrappers pass ``block_z`` through and nothing else.  The
benchmark's cell ``mobius24_single.strange`` runs this seam at 24^4 x 12
(PERF.md section 6, PR 42: twelve bf16 planes 1,467 us a hop in the CG
loop, 1,669 alone; the vmapped stencil 1,995, in f32 4,179 against
1,855): the API's resident Möbius route serves it without a race
(``models/domain_wall.MEASURED_LS_HOP_FORM``).

The dense (Ls, Ls) m5 algebra (ops/dwf.py SOp blocks) is a kernel of
this file too where the hop is (``mobius_sblock_pallas`` and its
accumulate form, PR 44): VPU multiply-adds on the hop's own layout.
As an f32 ``einsum`` XLA ran it on the MXU in six bf16 passes over a
K = Ls contraction and laid the CG's 5-d vectors out with s second-minor
to feed it, so ``copy`` fusions stood around every hop: 13.3 of the
cell's 19.9 ms an iteration (PERF.md section 6, PR 44).

The hop wrappers only validate the 5d layout and delegate; they exist so
the family dispatch and the costmodel/roofline rows have a stable,
testable seam (and so the DW5D hop — which batches contiguous Ls/2
groups per parity-5 step — shares it)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import blas_pallas as bpl
from . import wilson_pallas_packed as wpp

F32 = jnp.float32


def _check_psi5(psi_pl):
    if psi_pl.ndim != 7 or psi_pl.shape[1:4] != (4, 3, 2):
        raise ValueError(
            "expected Ls-major packed pairs (Ls,4,3,2,T,Z,YXh), got "
            f"{psi_pl.shape}")


def dslash_eo_pallas_packed_ls(u_here_pl, u_bw_pl, psi_pl, dims,
                               target_parity, interpret=False,
                               block_z=None, out_dtype=None,
                               tb_sign=True):
    """Apply the eo 4d hop to every s plane of an (Ls,4,3,2,T,Z,YXh)
    spinor with Ls as the innermost grid axis (gauge tile resident)."""
    _check_psi5(psi_pl)
    return wpp.dslash_eo_pallas_packed_mrhs(
        u_here_pl, u_bw_pl, psi_pl, tuple(dims), target_parity,
        interpret=interpret, block_z=block_z, out_dtype=out_dtype,
        tb_sign=tb_sign)


def dslash_eo_pallas_packed_ls_mrhs(u_here_pl, u_bw_pl, psi_pl, dims,
                                    target_parity, interpret=False,
                                    block_z=None, out_dtype=None,
                                    tb_sign=True):
    """Multi-source variant: (N, Ls, 4,3,2,T,Z,YXh) flattened to an
    (N*Ls)-deep batch — sources AND s planes share one resident gauge
    tile, so the per-plane link traffic drops to 576/(N*Ls) B/site."""
    if psi_pl.ndim != 8 or psi_pl.shape[2:5] != (4, 3, 2):
        raise ValueError(
            "expected (N,Ls,4,3,2,T,Z,YXh) packed pairs, got "
            f"{psi_pl.shape}")
    n, ls = psi_pl.shape[:2]
    flat = psi_pl.reshape((n * ls,) + psi_pl.shape[2:])
    out = wpp.dslash_eo_pallas_packed_mrhs(
        u_here_pl, u_bw_pl, flat, tuple(dims), target_parity,
        interpret=interpret, block_z=block_z, out_dtype=out_dtype,
        tb_sign=tb_sign)
    return out.reshape(psi_pl.shape[:2] + out.shape[1:])


# -- the (Ls, Ls) chirality blocks on the hop's layout ---------------------

def _sblock_tb(T: int, Z: int, YX: int, ls: int, dtypes) -> tuple[int, int]:
    """(time-slices a grid step, VMEM need in bytes) of an s-block call
    whose operands and result have ``dtypes``: the largest divisor of T
    whose blocks, Ls (Z, YX) planes a slice and operand as tiled at the
    dtype's width, fit the budget of ``blas_pallas._pick_rows``; the
    need is those blocks double-buffered by the pipeline.  Raises like
    it when one slice does not fit."""
    per_slice = ls * sum(
        bpl._tile_bytes(Z, YX, jnp.dtype(dt).itemsize,
                        wpp._sublane_rows(dt)) for dt in dtypes)
    fitting = [d for d in range(1, T + 1)
               if T % d == 0 and d * per_slice <= bpl._vmem_budget()]
    if not fitting:
        raise ValueError(
            f"one time-slice of Ls={ls} (Z={Z}, YX={YX}) planes "
            f"({per_slice / 2 ** 20:.1f} MB) does not fit the VMEM "
            "budget; QUDA_TPU_DWF_FORM=xla keeps the s-blocks an einsum")
    return max(fitting), 2 * max(fitting) * per_slice


def _sblock_call(name: str, x, y, a, blocks, out_dtype, interpret):
    """``out[s] = [y[s] + a *] sum_t blocks[c, s, t] x[t]`` on
    (Ls, 4, 3, 2, T, Z, YXh), c = 0 on spin rows 0, 1 and 1 on rows 2, 3.

    A grid step holds all Ls planes of one (spin, colour, re/im) row for
    ``tb`` time-slices, through a 7-d BlockSpec on the array itself (no
    reshape: merging Z or YXh into another axis is a relayout of the
    padded tiles).  The body walks a slice in 128-lane columns: Ls
    columns of x in f32 (3 vregs each at Z = 24), Ls x Ls scalar
    multiply-adds from SMEM, one store a plane; f32 arithmetic whatever
    the storage.  The Ls x Ls products are unrolled equations, not a
    loop over the result's planes: rolled, the bf16 kernel reads 628 us
    on the chip against 386 (f32 771 against 767: that one waits for its
    DMA), for 0.2 s less trace and lowering a signature (PERF.md section
    6, PR 44)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _check_psi5(x)
    ls, T, Z, YX = x.shape[0], *x.shape[4:]
    if blocks.shape != (2, ls, ls):
        raise ValueError(f"{name}: expected (2, {ls}, {ls}) chirality "
                         f"blocks, got {blocks.shape}")
    odt = jnp.dtype(out_dtype or x.dtype)
    axpy = y is not None
    dtypes = [x.dtype, odt] + ([y.dtype] if axpy else [])
    tb, need = _sblock_tb(T, Z, YX, ls, dtypes)

    def kernel(x_ref, *refs):
        y_ref, a_ref = refs[:2] if axpy else (None, None)
        b_ref, o_ref = refs[-2:]
        c = pl.program_id(0) // 2
        coef = [[b_ref[c, s, t] for t in range(ls)] for s in range(ls)]
        scale = a_ref[0] if axpy else None

        def one_slice(tt, carry):
            for l0 in range(0, YX, 128):
                lanes = slice(l0, min(l0 + 128, YX))
                xs = [x_ref[t, tt, :, lanes].astype(F32)
                      for t in range(ls)]
                for s in range(ls):
                    acc = coef[s][0] * xs[0]
                    for t in range(1, ls):
                        acc = acc + coef[s][t] * xs[t]
                    if axpy:
                        acc = (y_ref[s, tt, :, lanes].astype(F32)
                               + scale * acc)
                    o_ref[s, tt, :, lanes] = acc.astype(o_ref.dtype)
            return carry
        jax.lax.fori_loop(0, tb, one_slice, 0)

    rows = pl.BlockSpec((ls, None, None, None, tb, Z, YX),
                        lambda sp, c, r, t: (0, sp, c, r, t, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands, in_specs = [x], [rows]
    if axpy:
        operands += [y, jnp.asarray(a, F32).reshape(1)]
        in_specs += [rows, smem]
    return pl.pallas_call(
        kernel,
        grid=(4, 3, 2, T // tb),
        in_specs=in_specs + [smem],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(x.shape, odt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 4,
            vmem_limit_bytes=max(16 * 2 ** 20, need + 4 * 2 ** 20)),
        interpret=interpret,
    )(*operands, blocks.astype(F32))


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def mobius_sblock_pallas(x, blocks, out_dtype=None, interpret=False):
    """The real (Ls, Ls) chirality blocks of a Möbius s-operator on an
    (Ls, 4, 3, 2, T, Z, YXh) pair array: ``out[s] = sum_t B_c[s, t]
    x[t]`` with ``B_c = blocks[0]`` on spin rows 0, 1 and ``blocks[1]``
    on rows 2, 3.  ``blocks`` (2, Ls, Ls) f32 is a traced operand in
    SMEM (the LAST one: a capture names a kernel by its result, first
    and last operand), dense: one executable for every (mf, M5, b5, c5)
    of one Ls, EOFA's corrected blocks included; the adjoint is the
    transposed blocks handed in.  Loads at the storage width, multiplies
    and accumulates in f32, stores at ``out_dtype`` (default: x's)."""
    return _sblock_call("mobius_sblock_pallas", x, None, None, blocks,
                        out_dtype, interpret)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def mobius_sblock_axpy_pallas(x, y, a, blocks, out_dtype=None,
                              interpret=False):
    """The accumulate form, ``out = y + a * B x`` (``y`` a pair array of
    x's shape at any storage width, ``a`` a float or an f32 scalar, in
    SMEM): the ``x - 1/4 M5^-1 t`` of the Möbius PC operator in the
    pass that applies the block.  A kernel of its own name: its bytes
    are not the plain product's."""
    if y.shape != x.shape:
        raise ValueError(f"mobius_sblock_axpy_pallas: y {y.shape} is not "
                         f"of x's shape {x.shape}")
    return _sblock_call("mobius_sblock_axpy_pallas", x, y, a, blocks,
                        out_dtype, interpret)
