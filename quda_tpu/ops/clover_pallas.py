"""Fused clover / twisted-mass / twisted-clover pallas kernels.

The operator-zoo fast path (ROADMAP item 5): the proven v2 Wilson
gather kernel (ops/wilson_pallas_packed._make_kernel) with the family
diagonal folded into the kernel epilogue, so diag+hop is ONE VMEM pass
over the spinor tile instead of a hop launch followed by an XLA
einsum/rotation pass re-reading the hop output from HBM.

Two fused shapes cover every Schur-preconditioned family member
(QUDA fuses the same way: dslash_wilson_clover*.cu apply the A-block
or the twist in the kernel epilogue, never as a second pass):

* ``dslash_eo_pallas_post``: E(D_{p<-q} psi) — the K1 stage of the PC
  operator, with E the q-parity inverse diagonal (clover^-1 blocks, the
  twisted inverse rotation, or the dense twisted-clover inverse
  blocks).  The hop accumulator is written to the out tile at the out
  dtype FIRST and read back before E is applied, so the staged rounding
  matches the XLA composition (hop -> store_dtype -> A^{-1}) exactly.
* ``dslash_eo_pallas_diag_hop``: diag(x) + hop_coeff * D_{q<-p} t —
  the K2 stage: the second hop plus the p-parity diagonal (A_p blocks
  and/or the +i a g5 twist of the ORIGINAL x) and the -kappa^2 combine,
  one pass.  The extra center operand x rides a sixth psi-layout input
  whose BlockSpec matches the center spinor block; ``hop_coeff`` is a
  one-element f32 OPERAND in SMEM, not a compiled-in constant, so a
  solve program that takes kappa as an operand (solvers/program.py)
  serves every mass with one executable.  The combine is made of f32
  values: an f32 out tile holds the hop sum, and under a narrower one
  (the sloppy operator's bf16) the single-source call keeps it in an
  f32 VMEM scratch and writes the tile once, rounded.

Each of the two is its own jitted function, so a profiler trace names
the kernel event after it (``dslash_eo_pallas_post.N`` /
``dslash_eo_pallas_diag_hop.N``, with the element types of result,
spinor and blocks), as it does the Wilson kernel.

The clover term enters as the resident packed pair blocks of
models/clover.pack_clover_pairs — (2,6,6,2,T,Z,YXh), 576 B/site at f32
(288 at bf16) — streamed tile by tile exactly like the gauge tiles (a
(t, z-block) tile a step of the single-source calls); spins
(0,1)/(2,3) map to chirality block rows i = 3*(s%2)+c.
The twist is two STATIC floats (c = sign*a and a scale), compiled into
the kernel — in-register, zero bytes.

MRHS variants batch RHS innermost via the same _mrhs_wrap adapter as
the Wilson kernels (gauge AND block index maps ignore the RHS index,
so both stay tile-resident across the RHS stream).  A batch streams
from HBM, so they take their route from their shapes as the Wilson
batch does (``mrhs_route``, PR 47): whole-Z tiles of ``bt``
time-slices and three psi operands a step, the epilogue per chunk of
the hop body's loop (``fullz``: the cell's 24^4 with one slice a step
beside the 144 block planes), or the single-source call's z-blocks and
five psi operands where those tiles do not fit (``zblock``).

The K2 call has four forms under its one name, single-source
(``dslash_eo_pallas_diag_hop``) and MRHS
(``dslash_eo_pallas_diag_hop_mrhs``) alike (call-time keywords, off by
default, links then blocks still the last operands), counted by
``clover_route_total{epilogue}`` / ``clover_mrhs_route_total{epilogue}``:
``combine``, the value above;
``norm2``, gamma5 of it in the store (``g5``) and the squares of what
is stored summed per source into a second, small f32 result (``nrm``);
``residual``, ``rc - alpha[n] * g5`` of it written over ``rc`` and
summed (``rc``, ``alpha``).  With them the first half of a CG
iteration on ``MdagM = g5 M(-s) g5 M(+s)`` is four kernels and no XLA
pass over the vectors (models/wilson
``_SchurPairOpBase.MdagM_cg_step_pairs_mrhs``, PR 48, for a batch in
f32; ``MdagM_cg_step_pairs``, PR 50, for one source in any storage,
the mixed-precision CG's bf16 among them): ``pAp = |g5 M
p|^2`` comes out of the first M's K2 call, the new ``r`` and ``|r|^2``
out of the second's.  The full-lattice
``clover_pallas_packed`` serves the unpreconditioned M = A - kappa D
with the diagonal read from the center psi tile itself (no extra
operand at all).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import wilson_pallas_packed as wpp

F32 = jnp.float32

# extra resident (bz, YXh) planes the epilogue operands add to the
# _pick_bz working-set estimate: a pair-form chiral block array is
# 2*6*6*2 = 144 planes; a sixth psi-layout center input is 4*3*2 = 24
_BLK_PLANES = 144
_XC_PLANES = 24


def _load_sc(ref):
    """(4,3,2,1,bz,YXh) tile -> 4x3 grid of (re, im) f32 tiles."""
    return [[(ref[s, c, 0, 0].astype(F32), ref[s, c, 1, 0].astype(F32))
             for c in range(3)] for s in range(4)]


def _store_sc(ref, vals):
    odt = ref.dtype
    for s in range(4):
        for c in range(3):
            ref[s, c, 0, 0] = vals[s][c][0].astype(odt)
            ref[s, c, 1, 0] = vals[s][c][1].astype(odt)


def _blk_mul(blk_ref, vals):
    """A v with A the resident chiral 6x6 pair blocks
    ((2,6,6,2,1,bz,YXh) tile): spins (0,1) -> chirality 0, (2,3) -> 1,
    block row i = 3*(s%2) + c — the in-kernel form of
    models/clover.apply_clover_pairs."""
    out = [[None] * 3 for _ in range(4)]
    for ch in range(2):
        for i in range(6):
            acc = None
            for j in range(6):
                a = (blk_ref[ch, i, j, 0, 0].astype(F32),
                     blk_ref[ch, i, j, 1, 0].astype(F32))
                m = wpp._cmul(a, vals[2 * ch + j // 3][j % 3])
                acc = m if acc is None else wpp._cadd(acc, m)
            out[2 * ch + i // 3][i % 3] = acc
    return out


def _ig5_rot(vals, c: float):
    """i c gamma5 v: (re,im) -> (-c g5 im, c g5 re), g5 = (+,+,-,-)
    in DeGrand-Rossi (models/twisted._ig5_rot_pairs in-register)."""
    out = []
    for s in range(4):
        g5c = c if s < 2 else -c
        out.append([(-g5c * v[1], g5c * v[0]) for v in vals[s]])
    return out


def _add_sc(a, b):
    return [[wpp._cadd(a[s][c], b[s][c]) for c in range(3)]
            for s in range(4)]


def _scale_sc(vals, k):
    return [[(k * v[0], k * v[1]) for v in row] for row in vals]


def _sub_sc(a, b):
    return [[wpp._csub(a[s][c], b[s][c]) for c in range(3)]
            for s in range(4)]


def _sum_sq_sc(vals, dtype):
    """One f32 tile: the sum over the 24 planes of their squares as
    ``dtype`` stores them."""
    sq = None
    for row in vals:
        for v in row:
            for w in v:
                w = w.astype(dtype).astype(F32)
                sq = w * w if sq is None else sq + w * w
    return sq


def _epilogue_kernel(X, bz, eo, T, tb_sign, *, xc_mode, with_blk,
                     twist, diag_twist, with_coeff, z_rows="tiles",
                     g5=False, nrm=False, residual=False, src_axis=2,
                     hop_scratch=False):
    """v2 hop kernel + family epilogue over the out tile.

    z_rows: ``"tiles"``, the five psi refs of a (t, z-block) step and
    the epilogue over its out tile; ``"centre"``, the three of a full-Z
    step (wilson_pallas_packed._make_kernel), ``bz`` the rows of a
    chunk: the epilogue then runs per chunk inside the hop body's one
    loop, on the chunk's views of out, the diagonal operand and the
    blocks, so its values stay a z-block step's.

    xc_mode: None (no diagonal operand), 'input' (sixth psi-layout
    ref), or 'center' (diagonal of the hop INPUT itself — the
    full-lattice M = A - kappa D shape).
    twist: (c, scale) post-rotation scale*(v + i c g5 v) applied to the
    hop result (the twisted-mass A^{-1}); diag_twist: c of the +i c g5
    rotation of the ORIGINAL x added to the diagonal term.
    with_coeff: False = E(hop) only; True = diag(x) + hop_coeff * hop,
    hop_coeff read from a one-element f32 SMEM ref that follows the
    spinor refs (links, then blocks, stay the last inputs: the
    benchmark's trace reduction names a kernel event by the element
    types of its result, first and LAST operand).
    g5, nrm, residual (the K2 stage, a CG step's:
    models/wilson._SchurPairOpBase.MdagM_cg_step_pairs and its
    ``_mrhs`` twin): ``g5`` negates spin rows 2, 3 of v = diag(x) +
    hop_coeff * hop before the store (a sign: bit-exact against a
    gamma5 pass over the stored values); ``residual`` brings ``rc`` (a
    spinor block on the centre spec, after ``xc``) and ``alpha`` (one
    f32 a source in SMEM, after ``hop_coeff``; the source is the
    grid's axis ``src_axis``, None for a single-source call and its
    one alpha; read outside the chunk loop) and the store writes ``rc
    - alpha[n] * [g5] v``; ``nrm`` gives the kernel a second, small
    f32 output after the spinor's, one (BZ, YXh) block a grid step,
    zeroed here: the sum over the step's planes (and chunks) of the
    squares of what it stores, after the rounding to the out dtype,
    which the caller sums per source
    (wilson_pallas_packed._make_kernel's combine epilogue).
    hop_scratch (``tiles`` only): the last ref is an f32 VMEM scratch
    of the out tile's shape that takes the hop sum in the out tile's
    place, so that an out tile narrower than f32 is written once, from
    f32 values: ``round([g5] (diag(x) + c * hop))``, bit for bit what
    a cast of the f32 call's result stores.
    """
    from jax.experimental import pallas as pl

    base = wpp._make_kernel(X, bz, eo=eo, T=T, tb_sign=tb_sign,
                            z_rows=z_rows)
    n_psi = 3 if z_rows == "centre" else 5

    def kernel(*refs):
        hop_ref = None
        if hop_scratch:
            *refs, hop_ref = refs
        k = n_psi
        xc_ref = None
        if xc_mode == "input":
            xc_ref = refs[k]
            k += 1
        elif xc_mode == "center":
            xc_ref = refs[0]
        rc_ref = None
        if residual:
            rc_ref = refs[k]
            k += 1
        coeff_ref = None
        if with_coeff:
            coeff_ref = refs[k]
            k += 1
        alpha = None
        if residual:
            alpha = refs[k][0 if src_axis is None
                            else pl.program_id(src_axis)]
            k += 1
        g_c, g_m = refs[k], refs[k + 1]
        blk_ref = refs[k + 2] if with_blk else None
        out_ref, nrm_ref = (refs[-2], refs[-1]) if nrm else (refs[-1], None)
        if nrm:
            nrm_ref[...] = jnp.zeros(nrm_ref.shape, F32)

        def epilogue(out_ref, xc_ref, blk_ref, rc_ref=None, hop_ref=None):
            hop = _load_sc(out_ref if hop_ref is None else hop_ref)
            if not with_coeff:
                v = _blk_mul(blk_ref, hop) if with_blk else hop
                if twist is not None:
                    c, scale = twist
                    v = _add_sc(v, _ig5_rot(v, c))
                    if scale != 1.0:
                        v = _scale_sc(v, scale)
            else:
                x = _load_sc(xc_ref)
                d = _blk_mul(blk_ref, x) if with_blk else x
                if diag_twist is not None:
                    d = _add_sc(d, _ig5_rot(x, diag_twist))
                v = _add_sc(d, _scale_sc(hop, coeff_ref[0]))
                if g5:
                    v = v[:2] + [[(-re, -im) for re, im in row]
                                 for row in v[2:]]
                if residual:
                    v = _sub_sc(_load_sc(rc_ref), _scale_sc(v, alpha))
            _store_sc(out_ref, v)
            if nrm:
                nrm_ref[...] += _sum_sq_sc(v, out_ref.dtype)

        # the unchanged v2 hop body writes its accumulator to the out
        # tile (VMEM); the epilogue reads it straight back — for the
        # post kernels that write/read at the store dtype, which IS the
        # staged rounding of the XLA composition it replaces
        if z_rows == "centre":
            base(*refs[:3], g_c, g_m, out_ref,
                 epilogue=(epilogue, (xc_ref, blk_ref, rc_ref)))
        else:
            base(*refs[:5], g_c, g_m,
                 out_ref if hop_ref is None else hop_ref)
            epilogue(out_ref, xc_ref, blk_ref, rc_ref, hop_ref)

    return kernel


def _planes(R: int, xc_mode, with_blk: bool, with_rc: bool = False) -> int:
    return ((288 if R == 3 else 240)
            + (_BLK_PLANES if with_blk else 0)
            + (_XC_PLANES if xc_mode == "input" else 0)
            + (_XC_PLANES if with_rc else 0))


def _coeff_operand(hop_coeff):
    """The K2 combine coefficient as the kernel takes it: (1,) f32."""
    return jnp.asarray(hop_coeff, F32).reshape(1)


def mrhs_route(u_pl, psi_pl, xc_pl, blk_pl, out_dtype=None, block_z=None,
               rc_pl=None):
    """(route, bz, bt, vmem_limit_bytes) of a fused MRHS call on these
    operands (arrays or abstract values), from their shapes:
    wilson_pallas_packed._mrhs_route's rule with the epilogue's blocks
    in the sums.  ``"fullz"`` where ``_mrhs_fullz_fit`` finds room
    (24^4 f32 with the chiral blocks: one time-slice a step, 32.1 MiB
    for ``post``, 33.8 with ``xc`` for ``diag_hop``, 35.5 with the
    residual form's ``rc`` besides; two slices would need 55.7 / 59.1
    of the 48 the route may ask for; the epilogue's 12 KiB of sums are
    inside the body's allowance), the blocks and
    the limit filed with the VMEM audit; ``"zblock"`` (``_pick_bz``'s
    z-block, or the caller's) where it does not or ``block_z`` < Z
    asks for z-blocks.  models/wilson labels
    ``clover_mrhs_route_total`` with the same decision where it traces
    the call."""
    T, Z, YXh = psi_pl.shape[-3:]
    R = u_pl.shape[1]
    fit = wpp._mrhs_fullz_fit(
        T, Z, YXh, psi_pl.dtype, out_dtype or psi_pl.dtype, R, block_z,
        rc_dtype=None if rc_pl is None else rc_pl.dtype,
        extra=[(n, v.dtype) for n, v in ((_BLK_PLANES, blk_pl),
                                         (_XC_PLANES, xc_pl))
               if v is not None])
    if fit:
        bt, blocks, need = fit
        return "fullz", Z, bt, wpp._fullz_vmem_limit(blocks, need, Z)
    bz = block_z if block_z is not None else wpp._pick_bz(
        Z, YXh, psi_pl.dtype, planes=_planes(
            R, None if xc_pl is None else "input", blk_pl is not None,
            rc_pl is not None))
    if Z % bz != 0:
        raise ValueError(f"block_z={bz} does not divide Z={Z}")
    return "zblock", bz, 1, None


def mrhs_form(u_pl, psi_pl, xc_pl, blk_pl, out_dtype=None, block_z=None,
              nrm=False, rc_pl=None):
    """(epilogue, ``mrhs_route``'s tuple) of a fused MRHS call on these
    operands: what its store does, by the name
    ``clover_mrhs_route_total`` counts it under, and the route it
    takes.  ``none`` for K1 (no ``xc_pl``); for K2 ``combine``,
    ``norm2`` (``nrm``) or ``residual`` (``rc_pl``), and ``norm2`` too
    where no route holds the ``rc`` block beside ``xc`` and the chiral
    blocks: the call is then that form and XLA makes the update
    (``dslash_eo_pallas_diag_hop_mrhs``)."""
    if rc_pl is not None:
        try:
            return "residual", mrhs_route(u_pl, psi_pl, xc_pl, blk_pl,
                                          out_dtype, block_z, rc_pl)
        except ValueError:
            nrm = True
    return ("none" if xc_pl is None else "norm2" if nrm else "combine",
            mrhs_route(u_pl, psi_pl, xc_pl, blk_pl, out_dtype, block_z))


def _fused_eo_call(u_here_pl, u_bw_pl, psi_pl, xc_pl, blk_pl, coeff, dims,
                   target_parity, *, name, mrhs=False, twist=None,
                   diag_twist=None, interpret=False, block_z=None,
                   out_dtype=None, tb_sign=True, g5=False, nrm=False,
                   rc_pl=None, alpha=None):
    """The one pallas_call behind the four eo entry points.  ``coeff``
    (a (1,) f32 array) makes it the K2 stage; ``mrhs`` gives every
    spinor operand a leading RHS axis, streamed innermost: gauge AND
    block index maps ignore the RHS index, so both stay tile-resident
    across the RHS stream (the MRHS amortisation carries over to the
    576 B/site clover blocks, not just the links).

    A batch does not sit on chip as a single source does in a CG loop:
    every psi operand of every step is a fresh DMA (the MRHS comment of
    ops/wilson_pallas_packed).  So the MRHS call takes its route from
    its shapes, as the Wilson batch does.  ``fullz``, where
    ``_mrhs_fullz_fit`` says the blocks fit: grid (T/bt, 1, N), whole
    (bt, Z, YXh) tiles of links, chiral blocks, ``xc`` and out, THREE
    psi operands (the centre block and the single slices after and
    before it), the body ``_make_kernel``'s chunk loop with the
    epilogue per chunk, its own ``vmem_limit_bytes``.  ``zblock``, the
    single-source call with the RHS axis: five psi operands a (t,
    z-block) step, where full-Z does not fit or ``block_z`` < Z asks
    for it.  Per source the two bit-match each other and the
    single-source kernel.

    ``g5``, ``nrm``, ``rc_pl`` / ``alpha`` (the K2 stage):
    ``_epilogue_kernel``'s gamma5 store, sums of squares and residual
    form.  With ``nrm`` or ``rc_pl`` the call returns ``(v, |v|^2 per
    source)``, v what it wrote: the kernel's second output holds one
    block of f32 partial sums a grid step, (T/bt, Z/bz, N, rows, YXh),
    and XLA sums those few KB a source to (N,) f32; a single-source
    call has no N, one ``alpha`` ((1,) f32) and one f32 sum.  The
    residual form writes over ``rc_pl``'s own buffer (each step reads
    and writes the same centre block of it) where the types agree.

    Where a single-source K2 call stores narrower than f32 the hop sum
    stays on chip in an f32 VMEM scratch (24 planes of a z-block, not
    double-buffered) and the out tile is written once, rounded from the
    f32 combine: bit for bit the cast of the f32 call's result, without
    the f32 array in HBM and XLA's pass over it.  An f32 call and every
    MRHS call read the hop sum back from the out tile as before."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, Z, Y, X = dims
    Xh = X // 2
    R = u_here_pl.shape[1]
    YXh = psi_pl.shape[-1]
    with_blk = blk_pl is not None
    xc_mode = "input" if xc_pl is not None else None
    odt = out_dtype or psi_pl.dtype
    residual = rc_pl is not None
    nrm = nrm or residual
    if mrhs:
        form, (route, bz, bt, vmem_limit) = mrhs_form(
            u_here_pl, psi_pl, xc_pl, blk_pl, odt, block_z, nrm, rc_pl)
        if residual and form != "residual":
            # no route holds the rc block: XLA's update and sum
            v, _ = _fused_eo_call(
                u_here_pl, u_bw_pl, psi_pl, xc_pl, blk_pl, coeff, dims,
                target_parity, name=name, mrhs=True, diag_twist=diag_twist,
                interpret=interpret, block_z=block_z, out_dtype=F32,
                tb_sign=tb_sign, g5=g5, nrm=True)
            v = (rc_pl.astype(F32)
                 - alpha.reshape((-1,) + (1,) * 6) * v).astype(odt)
            w = v.astype(F32)
            return v, jnp.sum((w * w).reshape(w.shape[0], -1), axis=1)
    else:
        route, bt, vmem_limit = "zblock", 1, None
        bz = block_z if block_z is not None else wpp._pick_bz(
            Z, YXh, psi_pl.dtype, planes=_planes(R, xc_mode, with_blk,
                                                 residual))
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    nzb = Z // bz
    hop_scratch = (not mrhs and coeff is not None
                   and jnp.dtype(odt).itemsize < 4)
    lead = (1,) if mrhs else ()
    if route == "fullz":
        z_rows, body_rows = "centre", wpp._fullz_chunk(Z, psi_pl.dtype)

        def slice_spec(dt):
            # one time-slice, dt slices off the block's first
            return pl.BlockSpec(
                (1, 4, 3, 2, 1, Z, YXh),
                lambda tb, zb, n: (n, 0, 0, 0, (tb * bt + dt) % T, 0, 0))

        def site_spec(*idx):
            return pl.BlockSpec(
                idx + (bt, Z, YXh),
                lambda tb, zb, n: (0,) * len(idx) + (tb, 0, 0))

        centre_spec = pl.BlockSpec(
            (1, 4, 3, 2, bt, Z, YXh),
            lambda tb, zb, n: (n, 0, 0, 0, tb, 0, 0))
        in_specs = [centre_spec, slice_spec(bt), slice_spec(T - 1)]
    else:
        z_rows, body_rows = "tiles", bz

        def psi_spec(dt, dz):
            return pl.BlockSpec(
                lead + (4, 3, 2, 1, bz, YXh),
                lambda t, zb, *n: n + (0, 0, 0, (t + dt) % T,
                                       (zb + dz) % nzb, 0))

        def site_spec(*idx):
            return pl.BlockSpec(
                idx + (1, bz, YXh),
                lambda t, zb, *n: (0,) * len(idx) + (t, zb, 0))

        centre_spec = psi_spec(0, 0)
        in_specs = [centre_spec, psi_spec(+1, 0), psi_spec(-1, 0),
                    psi_spec(0, +1), psi_spec(0, -1)]

    kernel = _epilogue_kernel(X, body_rows, (target_parity, Xh), T, tb_sign,
                              xc_mode=xc_mode, with_blk=with_blk,
                              twist=twist, diag_twist=diag_twist,
                              with_coeff=coeff is not None, z_rows=z_rows,
                              g5=g5, nrm=nrm, residual=residual,
                              src_axis=2 if mrhs else None,
                              hop_scratch=hop_scratch)

    operands = [psi_pl] * len(in_specs)
    if xc_mode == "input":
        in_specs.append(centre_spec)
        operands.append(xc_pl)
    aliases = {}
    if residual:
        # the new r takes the old one's buffer where their types agree
        # (without the alias XLA copies the batch once an iteration to
        # carry it: PERF.md section 6, PR 39)
        if rc_pl.dtype == odt:
            aliases = {len(operands): 0}
        in_specs.append(centre_spec)
        operands.append(rc_pl)
    if mrhs:
        kernel = wpp._mrhs_wrap(kernel, n_psi=len(operands), n_out=1 + nrm)
    # the epilogue's scalars precede the links, in SMEM
    for k in (coeff, alpha if residual else None):
        if k is not None:
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.append(k)
    in_specs += [site_spec(4, R, 3, 2)] * 2
    operands += [u_here_pl, u_bw_pl]
    if with_blk:
        in_specs.append(site_spec(2, 6, 6, 2))
        operands.append(blk_pl)
    out_specs = centre_spec
    out_shape = jax.ShapeDtypeStruct(psi_pl.shape, odt)
    if nrm:
        # one (rows, YXh) block of partial sums a grid step
        src = (psi_pl.shape[0],) if mrhs else ()
        out_specs = [out_specs, pl.BlockSpec(
            (None,) * (2 + len(src)) + (body_rows, YXh),
            lambda tb, zb, *n: (tb, zb) + n + (0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (T // bt, nzb) + src + (body_rows, YXh), F32)]

    out = pl.pallas_call(
        kernel,
        grid=(T // bt, nzb) + ((psi_pl.shape[0],) if mrhs else ()),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((4, 3, 2, 1, bz, YXh), F32)]
        if hop_scratch else (),
        input_output_aliases=aliases,
        interpret=interpret,
        name=name,
        compiler_params=None if vmem_limit is None else
        pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
    )(*operands)
    if not nrm:
        return out
    return out[0], jnp.sum(out[1], axis=(0, 1, 3, 4) if mrhs else None)


# -- public entry points ----------------------------------------------------
# Each is its own jitted function: the trace names a kernel event after
# the function that wraps its pallas_call.

_POST_STATIC = ("dims", "target_parity", "twist", "interpret", "block_z",
                "out_dtype", "tb_sign")
_DIAG_HOP_STATIC = ("dims", "target_parity", "diag_twist", "interpret",
                    "block_z", "out_dtype", "tb_sign")


@functools.partial(jax.jit, static_argnames=_POST_STATIC)
def dslash_eo_pallas_post(u_here_pl, u_bw_pl, psi_pl, dims,
                          target_parity, *, blk_pl=None, twist=None,
                          interpret=False, block_z=None, out_dtype=None,
                          tb_sign=True):
    """E(D_{p<-q} psi) in one VMEM pass — the K1 stage of the fused PC
    operator.  E = the resident chiral blocks (``blk_pl``, e.g. the
    clover inverse or the dense twisted-clover inverse) and/or the
    static twist rotation ``twist=(c, scale)`` mapping
    v -> scale*(v + i c g5 v)."""
    return _fused_eo_call(u_here_pl, u_bw_pl, psi_pl, None, blk_pl, None,
                          tuple(dims), target_parity,
                          name="dslash_eo_pallas_post", twist=twist,
                          interpret=interpret, block_z=block_z,
                          out_dtype=out_dtype, tb_sign=tb_sign)


@functools.partial(jax.jit,
                   static_argnames=_DIAG_HOP_STATIC + ("g5", "nrm"))
def dslash_eo_pallas_diag_hop(u_here_pl, u_bw_pl, psi_pl, xc_pl, dims,
                              target_parity, *, hop_coeff, blk_pl=None,
                              diag_twist=None, interpret=False,
                              block_z=None, out_dtype=None,
                              tb_sign=True, g5=False, nrm=False, rc=None,
                              alpha=None):
    """diag(x) + hop_coeff * D_{p<-q} psi in one VMEM pass — the K2
    stage: diag(x) = blk x (+ i c g5 x with ``diag_twist=c``), x riding
    a sixth psi-layout operand whose BlockSpec is the center block;
    ``hop_coeff`` a float or an f32 scalar array (an operand either
    way).  The combine is made of f32 values whatever ``out_dtype``:
    an f32 out tile holds the hop sum itself, a narrower one is
    written once, rounded, from an f32 scratch (``_fused_eo_call``).
    ``g5``, ``nrm``, ``rc`` / ``alpha`` as the ``_mrhs`` twin has them,
    call-time and under this one name: gamma5 in the store; ``(v, its
    f32 squared norm as stored)`` (the ``norm2`` form); ``rc - alpha *
    [g5] v`` written in ``rc``'s place and summed, ``alpha`` one f32
    (the ``residual`` form): the first half of a mixed-precision CG
    iteration (models/wilson ``_SchurPairOpBase.MdagM_cg_step_pairs``)."""
    if rc is not None:
        alpha = jnp.asarray(alpha, F32).reshape(1)
    return _fused_eo_call(u_here_pl, u_bw_pl, psi_pl, xc_pl, blk_pl,
                          _coeff_operand(hop_coeff), tuple(dims),
                          target_parity,
                          name="dslash_eo_pallas_diag_hop",
                          diag_twist=diag_twist, interpret=interpret,
                          block_z=block_z, out_dtype=out_dtype,
                          tb_sign=tb_sign, g5=g5, nrm=nrm, rc_pl=rc,
                          alpha=alpha)


@functools.partial(jax.jit, static_argnames=_POST_STATIC)
def dslash_eo_pallas_post_mrhs(u_here_pl, u_bw_pl, psi_pl, dims,
                               target_parity, *, blk_pl=None,
                               twist=None, interpret=False,
                               block_z=None, out_dtype=None,
                               tb_sign=True):
    """MRHS ``dslash_eo_pallas_post``: psi (N,4,3,2,T,Z,YXh), RHS
    innermost, gauge and block tiles fetched once per (t, z-block)."""
    return _fused_eo_call(u_here_pl, u_bw_pl, psi_pl, None, blk_pl, None,
                          tuple(dims), target_parity,
                          name="dslash_eo_pallas_post_mrhs", mrhs=True,
                          twist=twist, interpret=interpret,
                          block_z=block_z, out_dtype=out_dtype,
                          tb_sign=tb_sign)


@functools.partial(jax.jit,
                   static_argnames=_DIAG_HOP_STATIC + ("g5", "nrm"))
def dslash_eo_pallas_diag_hop_mrhs(u_here_pl, u_bw_pl, psi_pl, xc_pl,
                                   dims, target_parity, *, hop_coeff,
                                   blk_pl=None, diag_twist=None,
                                   interpret=False, block_z=None,
                                   out_dtype=None, tb_sign=True,
                                   g5=False, nrm=False, rc=None,
                                   alpha=None):
    """MRHS ``dslash_eo_pallas_diag_hop`` (x batched like psi): the
    ``combine`` form, v = diag(x) + hop_coeff * D psi.  Three more, all
    call-time and under this one name (a capture tells a kernel by it):
    ``g5`` stores gamma5 v (``Mdag = g5 M(-s) g5``'s outer sign, or the
    inner one, in the store); ``nrm`` returns ``(that batch, its (N,)
    f32 squared norms per source)``, summed by the epilogue from what
    it stores (the ``norm2`` form: with ``g5`` on ``M p`` they are the
    batched CG's ``p . MdagM p``); ``rc`` (a batch as ``x``) and
    ``alpha`` ((N,) f32, one a source) make it the ``residual`` form:
    the store writes ``rc - alpha * [g5] v`` in ``rc``'s place and
    sums that, the new ``r`` and ``|r|^2`` of a batched CG iteration,
    and ``v`` never reaches HBM.  Where no route holds ``rc`` beside
    ``x`` and the blocks the call is the ``norm2`` form and XLA makes
    the update."""
    if rc is not None:
        alpha = jnp.asarray(alpha, F32).reshape(psi_pl.shape[0])
    return _fused_eo_call(u_here_pl, u_bw_pl, psi_pl, xc_pl, blk_pl,
                          _coeff_operand(hop_coeff), tuple(dims),
                          target_parity,
                          name="dslash_eo_pallas_diag_hop_mrhs",
                          mrhs=True, diag_twist=diag_twist,
                          interpret=interpret, block_z=block_z,
                          out_dtype=out_dtype, tb_sign=tb_sign, g5=g5,
                          nrm=nrm, rc_pl=rc, alpha=alpha)


@functools.partial(jax.jit, static_argnames=(
    "X", "kappa", "diag_twist", "interpret", "block_z", "tb_sign"))
def clover_pallas_packed(gauge_pl, blk_pl, psi_pl, X, kappa,
                         diag_twist=None, interpret=False, block_z=None,
                         gauge_bw=None, tb_sign=True):
    """Full-lattice fused M psi = A psi - kappa D psi (+ i c g5 psi
    with ``diag_twist``): the v2 full-lattice hop with the clover
    diagonal read from the CENTER psi tile — no extra spinor operand.
    gauge_pl (4,R,3,2,T,Z,YX), blk_pl (2,6,6,2,T,Z,YX), psi_pl
    (4,3,2,T,Z,YX); layouts as ops/wilson_pallas_packed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, _, T, Z, YX = psi_pl.shape
    R = gauge_pl.shape[1]
    bz = block_z if block_z is not None else wpp._pick_bz(
        Z, YX, psi_pl.dtype, planes=_planes(R, None, True))
    if Z % bz != 0:
        raise ValueError(f"block_z={bz} does not divide Z={Z}")
    nzb = Z // bz
    if gauge_bw is None:
        gauge_bw = wpp.backward_gauge(gauge_pl, X)

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (4, 3, 2, 1, bz, YX),
            lambda t, zb, dt=dt, dz=dz: (0, 0, 0, (t + dt) % T,
                                         (zb + dz) % nzb, 0))

    gauge_spec = pl.BlockSpec(
        (4, R, 3, 2, 1, bz, YX), lambda t, zb: (0, 0, 0, 0, t, zb, 0))
    blk_spec = pl.BlockSpec(
        (2, 6, 6, 2, 1, bz, YX), lambda t, zb: (0, 0, 0, 0, t, zb, 0))

    kernel = _epilogue_kernel(X, bz, None, T, tb_sign,
                              xc_mode="center", with_blk=True,
                              twist=None, diag_twist=diag_twist,
                              with_coeff=True)

    return pl.pallas_call(
        kernel,
        grid=(T, nzb),
        in_specs=[psi_spec(0, 0), psi_spec(+1, 0), psi_spec(-1, 0),
                  psi_spec(0, +1), psi_spec(0, -1),
                  pl.BlockSpec(memory_space=pltpu.SMEM), gauge_spec,
                  gauge_spec, blk_spec],
        out_specs=pl.BlockSpec((4, 3, 2, 1, bz, YX),
                               lambda t, zb: (0, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape, psi_pl.dtype),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, psi_pl, psi_pl,
      _coeff_operand(-float(kappa)), gauge_pl, gauge_bw, blk_pl)
