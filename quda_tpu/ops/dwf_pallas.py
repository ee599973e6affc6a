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

The dense (Ls, Ls) m5 algebra (ops/dwf.py SOp blocks, applied as
einsum GEMMs in models/domain_wall) stays in XLA: it is
MXU-batched already and carries no gauge traffic to amortise.

These wrappers only validate the 5d layout and delegate; they exist so
the family dispatch and the costmodel/roofline rows have a stable,
testable seam (and so the DW5D hop — which batches contiguous Ls/2
groups per parity-5 step — shares it)."""

from __future__ import annotations

from . import wilson_pallas_packed as wpp


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
