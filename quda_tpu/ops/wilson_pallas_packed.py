"""Pallas TPU Wilson dslash on the packed device layout — the hand-tuned
hot path.

It works on the PACKED order of ops/wilson_packed.py, split into float
re/im planes (trailing (4,3,2) axes would fight the (8,128) tiling):

    psi   (4, 3, 2, T, Z, Y*X)   float32
    gauge (4, 3, 3, 2, T, Z, Y*X) float32

so every (Z, Y*X) plane is a fully-utilised vector tile.  Grid =
(T, Z/BZ): each program owns one (t, z-block) tile of the lattice.
BlockSpec index maps deliver psi at (t, zb), its t+-1 and zb+-1
neighbour tiles, the forward gauge tile at (t, zb) and the PRE-SHIFTED
backward gauge tile (see below).  The spin algebra is the derived
projection-table project -> 3x3 color multiply -> reconstruct of
ops/wilson_packed.TABLES (reference include/kernels/dslash_wilson.cuh:84-162),
in explicit re/im-pair arithmetic on (BZ, Y*X) tiles.

Two design points keep the kernel off the VPU-issue wall (the first
version measured ~50% of its HBM roofline, instruction-bound):

1. **Project before shifting.**  The spin projection commutes with the
   site shift (it is pointwise in space), so each hop projects the
   4-spinor down to a half spinor FIRST and shifts 6 (spin,color) pairs
   instead of 12 — halving the roll/select traffic of the x/y/z shift
   network.  (QUDA's dslash reads shifted neighbours directly; on TPU
   the shift is vector ALU work, so minimising shifted planes matters.)
2. **Pre-shifted backward gauge.**  The backward hop needs
   U_mu(x-mu)^dag.  Instead of shifting 18 link planes per direction
   in-kernel, `backward_gauge(gauge_pl, X)` rolls the whole gauge field
   once OUTSIDE the kernel (per gauge load, amortised over the solve)
   and the kernel reads the pre-shifted tile — zero in-kernel link
   shifts, at the cost of one extra resident gauge copy (+288 B/site
   HBM read, a good trade while ALU-bound).

x/y shifts are lane rolls with an x-boundary mask built from an
in-kernel iota; z shifts splice one boundary row of the PROJECTED
neighbour tile; t neighbours arrive as whole tiles via the index map.

The z-block size BZ is chosen as the largest divisor of Z whose working
set fits the scoped-VMEM budget (~16 MB on v5e, halved for Mosaic's
double buffering).  Measured on a real v5e chip (2026-07-29): 1.49-1.65
TFLOPS f32 at 24^4 for the 5x-psi-fetch version — above the 1.4 TFLOPS
A100-class baseline (BASELINE.md); this version removes ~40% of its
vector shift instructions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the MRHS ``pallas_call`` is made from a frame that opens a chunk of
# CPython's frame stack of its own (PERF.md section 7 (22))
from ..utils.frames import (
    on_a_stack_chunk_of_its_own as _on_a_stack_chunk_of_its_own)
from .wilson_packed import TABLES

F32 = jnp.float32


# -- layout conversion ------------------------------------------------------

def to_pallas_layout(arr: jnp.ndarray) -> jnp.ndarray:
    """complex packed (..., T, Z, YX) -> f32 pairs (..., 2, T, Z, YX)
    (delegates to the single pair-layout converter in wilson_packed)."""
    from .wilson_packed import to_packed_pairs
    return to_packed_pairs(arr, F32)


def from_pallas_layout(arr: jnp.ndarray, dtype=jnp.complex64) -> jnp.ndarray:
    from .wilson_packed import from_packed_pairs
    return from_packed_pairs(arr, dtype)


def backward_gauge(gauge_pl: jnp.ndarray, X: int) -> jnp.ndarray:
    """Gauge field shifted one site backward in its own direction:
    out[mu](x) = U_mu(x - mu), on the pair layout (4,3,3,2,T,Z,YX).

    Computed once per gauge load (outside the kernel) so backward hops
    read links directly instead of shifting 18 planes per direction
    in-kernel.  Delegates to wilson_packed.shift_packed (sign=-1) so the
    packed-layout boundary logic lives in exactly one place.
    """
    from .wilson_packed import shift_packed
    Y = gauge_pl.shape[-1] // X
    return jnp.stack([shift_packed(gauge_pl[mu], mu, -1, X, Y)
                      for mu in range(4)])


# -- in-kernel complex helpers on (re, im) tuples of (BZ, YX) tiles --------

def _cmul(a, b):
    return (a[0] * b[0] - a[1] * b[1], a[0] * b[1] + a[1] * b[0])


def _cmul_conj(a, b):
    """conj(a) * b."""
    return (a[0] * b[0] + a[1] * b[1], a[0] * b[1] - a[1] * b[0])


def _cadd(a, b):
    return (a[0] + b[0], a[1] + b[1])


def _csub(a, b):
    return (a[0] - b[0], a[1] - b[1])


def _cscale(c: complex, x):
    cr, ci = float(c.real), float(c.imag)
    if ci == 0.0:
        return (cr * x[0], cr * x[1])
    if cr == 0.0:
        return (-ci * x[1], ci * x[0])
    return (cr * x[0] - ci * x[1], cr * x[1] + ci * x[0])


def _shift_xy(v, mu: int, sign: int, X: int, nhop: int = 1):
    """x/y shifts by nhop sites on a (BZ, YX) tile (fused Y*X axis):
    result(z, i) = v at site + sign*nhop*mu.  Also serves the staggered
    kernel's Naik 3-hop shifts (ops/staggered_pallas.py)."""
    if mu == 1:
        return (jnp.roll(v[0], -sign * nhop * X, axis=1),
                jnp.roll(v[1], -sign * nhop * X, axis=1))
    # x: lane roll + boundary-column fix (x arithmetic is mod X, as in
    # wilson_packed.shift_packed)
    n = nhop % X
    if n == 0:
        return v
    col = jax.lax.broadcasted_iota(jnp.int32, v[0].shape, 1) % X
    out = []
    if sign > 0:
        mask = col >= X - n
        for c in v:
            out.append(jnp.where(mask, jnp.roll(c, X - n, axis=1),
                                 jnp.roll(c, -n, axis=1)))
        return tuple(out)
    mask = col < n
    for c in v:
        out.append(jnp.where(mask, jnp.roll(c, -(X - n), axis=1),
                             jnp.roll(c, n, axis=1)))
    return tuple(out)


def _shift_x_eo(v, sign: int, Xh: int, mask_r0):
    """Checkerboarded x shift on a (BZ, Y*Xh) half-lattice tile.

    Mirrors wilson_packed.shift_eo_packed's x case: a half-site's x
    neighbour is either in the SAME fused-axis slot or the adjacent one,
    depending on whether the site occupies the even x slot (mask_r0,
    from the (t+z+y+parity) slot parity)."""
    col = jax.lax.broadcasted_iota(jnp.int32, v[0].shape, 1) % Xh
    out = []
    if sign > 0:
        wrap = col == Xh - 1
        for c in v:
            moved = jnp.where(wrap, jnp.roll(c, Xh - 1, axis=1),
                              jnp.roll(c, -1, axis=1))
            out.append(jnp.where(mask_r0, c, moved))
    else:
        wrap = col == 0
        for c in v:
            moved = jnp.where(wrap, jnp.roll(c, -(Xh - 1), axis=1),
                              jnp.roll(c, 1, axis=1))
            out.append(jnp.where(mask_r0, moved, c))
    return tuple(out)


def _shift_z(v, v_row, sign: int):
    """z shift on a (BZ, YX) tile, splicing boundary row ``v_row`` (a
    (1, YX) pair from the neighbouring z-block: its first row for
    sign>0, its last row for sign<0)."""
    bz = v[0].shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, v[0].shape, 0)
    if sign > 0:
        return tuple(jnp.where(row == bz - 1, n, jnp.roll(c, -1, axis=0))
                     for c, n in zip(v, v_row))
    return tuple(jnp.where(row == 0, n, jnp.roll(c, 1, axis=0))
                 for c, n in zip(v, v_row))


class _TileRef:
    """Trace-time view of one time-slice ``t`` and rows [z0, z0 + rows)
    of a pallas Ref whose block holds several time-slices of whole-Z
    tiles, (..., BT, Z, YX): the kernel body indexes it like a block of
    one slice and that many rows (its last index, the in-block time
    index 0, becomes ``t``).  The full-Z route walks its block so."""

    def __init__(self, ref, t: int, z0, rows: int):
        from jax.experimental import pallas as pl
        self._ref, self._rows = ref, rows
        self._tz = (t, pl.ds(z0, rows), slice(None))

    @property
    def shape(self):
        sh = tuple(self._ref.shape)
        return sh[:-3] + (1, self._rows, sh[-1])

    @property
    def dtype(self):
        return self._ref.dtype

    def __getitem__(self, idx):
        return self._ref[tuple(idx)[:-1] + self._tz]

    def __setitem__(self, idx, val):
        self._ref[tuple(idx)[:-1] + self._tz] = val


class _EitherRef:
    """Trace-time view that reads ``a`` where the scalar ``pred`` holds
    and ``b`` where it does not (two views of one shape; both are
    loaded, the value is selected): the full-Z body's t neighbour, a
    slice of its own block or the single-slice operand, by the slice
    the loop is at."""

    def __init__(self, pred, a, b):
        self._pred, self._a, self._b = pred, a, b
        self.shape, self.dtype = a.shape, a.dtype

    def __getitem__(self, idx):
        return jnp.where(self._pred, self._a[idx], self._b[idx])


def _make_kernel(X: int, bz: int, eo: tuple | None = None,
                 T: int | None = None, tb_sign: bool = True,
                 z_rows: str = "tiles", combine: bool = False,
                 g5: bool = False, residual: bool = False):
    """Kernel over one (t, z-block) tile.  Ref shapes (leading block dims
    of 1 squeezed by indexing; R = 3 link rows for full storage, 2 for
    reconstruct-12):
      psi refs:            (4, 3, 2, 1, BZ, YX) x5 (c, t+1, t-1, z+1, z-1)
      g_c / g_m refs:      (4, R, 3, 2, 1, BZ, YX)  (forward / pre-shifted
                           backward links)
    ``z_rows`` says where the z-boundary rows come from: ``"tiles"``,
    the two z-neighbour tiles above; ``"centre"``, the centre block
    itself: the refs then span the whole Z extent, the centre, link and
    out blocks hold BT time-slices, (.., BT, Z, YX), and the kernel
    takes three psi refs: c, and the single slices after and before
    the block's (t+BT, t-1).  It walks the block slice by slice in
    chunks of BZ rows, each chunk the body of a z-block whose z
    neighbours are the wrapped neighbouring chunks and whose t
    neighbours are the block's own slices where it has them.  The body
    is traced once, in one loop over (slice, chunk): with BT > 1 a t
    neighbour is loaded from both places and selected by the slice
    (``_EitherRef``); unrolling the slices instead doubles what every
    process lowers for 5 % of the kernel's time (PERF.md section 6,
    PR 31).  Same values per chunk, same hop algebra: the two bit-match.
    A caller that goes on from the hop sum hands that loop its
    ``epilogue=(fn, tiles)`` at call time (ops/clover_pallas: the
    chiral blocks and the diagonal operand): ``fn`` runs after each
    chunk's store on the chunk's views of out and of ``tiles``, still
    one traced body; without it the loop is the plain one, trace for
    trace.
    With ``eo = (target_parity, Xh)`` the tile is a checkerboarded half
    lattice (fused axis Y*Xh) and x shifts use the slot-parity select of
    wilson_packed.shift_eo_packed; g_c/g_m are then the target-parity
    forward links and the pre-shifted opposite-parity backward links.
    ``T``/``tb_sign`` drive the reconstruct-12 t-boundary row-2 sign
    (see _link_getter): the forward t-link boundary plane is t = T-1 on
    g_c, the PRE-SHIFTED backward one is t = 0 on g_m.
    ``combine`` gives the kernel a combine epilogue: two more refs
    follow the psi refs, ``xc`` (a spinor block of the out block's
    shape) and ``coeff`` (one f32 in SMEM), and the store writes
    ``xc + coeff[0] * hop`` from the f32 accumulators, under gamma5
    (the sign (+,+,-,-) by spin row) with ``g5``: rounded to the out
    dtype once, as the XLA pass it replaces rounds.  Without it the
    body is the plain hop's, trace for trace.
    The epilogue has a second, small f32 output after ``out_ref``, one
    (BZ, YX) block a grid step: the sum over the step's planes,
    time-slices and chunks of the squares of what it stores, taken
    after the rounding to the out dtype (the norm of the array the
    next hop reads).  Summed per source by the caller it is
    ``|g5 M p|^2``, which is the ``p . MdagM p`` of the batched CG
    (solvers/block.batched_cg_pairs_loop): no pass over the batch
    reads ``p`` and ``Ap`` only to sum them.  Every combine call
    carries it, used or not (48 flop a site on a 1,320-flop body): a
    variant without it is one more Mosaic lowering in every process
    for 0.02 % of a call (PERF.md section 6, PR 37).
    ``residual`` (with ``combine``) is the epilogue's third form, the
    batched CG's ``r - alpha A p``: two refs more, ``rc`` (a spinor
    block like ``xc``, after it) and ``alpha`` (one f32 a source in
    SMEM, after ``coeff``; the source is the grid's axis 2), and the
    store writes ``rc - alpha[n] * v``, v the combine form's value
    before its rounding, so that ``nrm`` sums the new ``|r|^2`` and
    ``A p`` never reaches HBM (PERF.md section 6, PR 39).
    """
    from jax.experimental import pallas as pl

    def kernel(psi_c, psi_tp, psi_tm, psi_zp, psi_zm, g_c, g_m, out_ref,
               z0=None, t_id=None, xc=None, coeff=None, nrm=None,
               rc=None, alpha=None):
        # z0 / t_id: the tile's first z row and its time-slice, where the
        # caller knows them better than the grid does (centre_kernel);
        # xc / coeff: the combine epilogue's operands; nrm: its (BZ, YX)
        # f32 block of partial sums of squares, zeroed by the caller;
        # rc / alpha: the residual form's block and its source's scalar
        if eo is not None:
            parity, Xh = eo
            t_eo = pl.program_id(0) if t_id is None else t_id
            zb_id = pl.program_id(1) if z0 is None else None
            shape = psi_c.shape[-2:]
            z = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                 + (zb_id * bz if z0 is None else z0))
            y = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // Xh
            mask_r0 = ((t_eo + z + y + parity) % 2) == 0

        def shift_x(v, sign):
            if eo is None:
                return _shift_xy(v, 0, sign, X)
            return _shift_x_eo(v, sign, eo[1], mask_r0)

        # loads cast storage dtype (f32 or bf16) to f32 compute
        def psi_at(ref, s, c):
            return (ref[s, c, 0, 0].astype(F32),
                    ref[s, c, 1, 0].astype(F32))

        def psi_row(ref, s, c, rows):
            return (ref[s, c, 0, 0][rows].astype(F32),
                    ref[s, c, 1, 0][rows].astype(F32))

        # reconstruct-12 t-boundary sign planes (None for full storage /
        # periodic t)
        if g_c.shape[1] == 2 and tb_sign:
            t_idx = pl.program_id(0) if t_id is None else t_id
            s_t_fwd = jnp.where(t_idx == T - 1, -1.0, 1.0).astype(F32)
            s_t_bwd = jnp.where(t_idx == 0, -1.0, 1.0).astype(F32)
        else:
            s_t_fwd = s_t_bwd = None

        # accumulators per (spin, color), f32
        acc = [[(jnp.zeros(psi_c.shape[-2:], F32),
                 jnp.zeros(psi_c.shape[-2:], F32))
                for _ in range(3)] for _ in range(4)]

        def project(get_psi, table):
            """Half-spinor h[a][color] from UNSHIFTED psi planes."""
            t = table
            return [[_cadd(get_psi(a, c),
                           _cscale(t[f"c{a}"], get_psi(t[f"j{a}"], c)))
                     for c in range(3)] for a in (0, 1)]

        def color_acc(h, get_link, table, adjoint):
            """3x3 color multiply of the (shifted) half spinor, then
            accumulate with spin reconstruction."""
            t = table
            uh = [[None] * 3 for _ in range(2)]
            for s in range(2):
                for a in range(3):
                    term = None
                    for b in range(3):
                        m = (_cmul_conj(get_link(b, a), h[s][b]) if adjoint
                             else _cmul(get_link(a, b), h[s][b]))
                        term = m if term is None else _cadd(term, m)
                    uh[s][a] = term
            for c in range(3):
                acc[0][c] = _cadd(acc[0][c], uh[0][c])
                acc[1][c] = _cadd(acc[1][c], uh[1][c])
                acc[2][c] = _cadd(acc[2][c],
                                  _cscale(t["d2"], uh[t["k2"]][c]))
                acc[3][c] = _cadd(acc[3][c],
                                  _cscale(t["d3"], uh[t["k3"]][c]))

        # x, y directions: project central psi, shift 6 half-spinor pairs
        for mu in (0, 1):
            for sign, adjoint, gref in ((+1, False, g_c), (-1, True, g_m)):
                t = TABLES[(mu, sign)]
                h = project(lambda s, c: psi_at(psi_c, s, c), t)
                if mu == 0:
                    h = [[shift_x(h[a][c], sign) for c in range(3)]
                         for a in (0, 1)]
                else:
                    h = [[_shift_xy(h[a][c], 1, sign,
                                    X if eo is None else eo[1])
                          for c in range(3)] for a in (0, 1)]
                color_acc(h, _link_getter(gref, mu), t, adjoint)
        # z direction: project central + the needed boundary row of the
        # neighbouring z-block, then splice
        for sign, adjoint, gref, nb in ((+1, False, g_c, psi_zp),
                                        (-1, True, g_m, psi_zm)):
            t = TABLES[(2, sign)]
            rows = slice(0, 1) if sign > 0 else slice(-1, None)
            h = project(lambda s, c: psi_at(psi_c, s, c), t)
            h_row = project(lambda s, c: psi_row(nb, s, c, rows), t)
            h = [[_shift_z(h[a][c], h_row[a][c], sign) for c in range(3)]
                 for a in (0, 1)]
            color_acc(h, _link_getter(gref, 2), t, adjoint)
        # t direction: whole neighbour tiles (index maps did the wrap),
        # no shift at all
        for sign, adjoint, gref, nb, r2s in (
                (+1, False, g_c, psi_tp, s_t_fwd),
                (-1, True, g_m, psi_tm, s_t_bwd)):
            t = TABLES[(3, sign)]
            h = project(lambda s, c, nb=nb: psi_at(nb, s, c), t)
            color_acc(h, _link_getter(gref, 3, r2s), t, adjoint)

        odt = out_ref.dtype
        if xc is not None:
            k = coeff[0]
            nk = -k
        sq = None
        for s in range(4):
            for c in range(3):
                for ri in (0, 1):
                    v = acc[s][c][ri]
                    if xc is not None:
                        x = xc[s, c, ri, 0].astype(F32)
                        # -(x + k v) is (-k) v - x to the bit
                        v = nk * v - x if g5 and s >= 2 else x + k * v
                    if rc is not None:
                        v = rc[s, c, ri, 0].astype(F32) - alpha * v
                    v = v.astype(odt)
                    out_ref[s, c, ri, 0] = v
                    if nrm is not None:
                        v = v.astype(F32)
                        sq = v * v if sq is None else sq + v * v
        if nrm is not None:
            nrm[...] += sq

    def centre_kernel(psi_c, psi_tp, psi_tm, g_c, g_m, out_ref,
                      xc=None, coeff=None, nrm=None, rc=None, alpha=None,
                      epilogue=None):
        # epilogue: (fn, tiles), see the docstring; a None among the
        # tiles stays None
        bt, Z = psi_c.shape[-3:-1]
        nzc = Z // bz
        t0 = pl.program_id(0) * bt    # not inside the loop's body

        def chunk(k, carry):
            # one trace of the body serves every slice and chunk
            i = k if nzc == 1 else jax.lax.div(k, nzc)
            zc = 0 if nzc == 1 else jax.lax.rem(k, nzc)

            def at(ref, t, dz=0):
                z0 = 0 if nzc == 1 else pl.multiple_of(
                    jax.lax.rem(zc + dz + nzc, nzc) * bz, bz)
                return _TileRef(ref, t, z0, bz)
            if bt == 1:
                up, dn = at(psi_tp, 0), at(psi_tm, 0)
            else:
                up = _EitherRef(i + 1 < bt,
                                at(psi_c, jnp.minimum(i + 1, bt - 1)),
                                at(psi_tp, 0))
                dn = _EitherRef(i > 0, at(psi_c, jnp.maximum(i - 1, 0)),
                                at(psi_tm, 0))
            kernel(at(psi_c, i), up, dn, at(psi_c, i, +1), at(psi_c, i, -1),
                   at(g_c, i), at(g_m, i), at(out_ref, i),
                   z0=zc * bz, t_id=t0 + i,
                   xc=None if xc is None else at(xc, i), coeff=coeff,
                   nrm=nrm, rc=None if rc is None else at(rc, i),
                   alpha=alpha)
            if epilogue is not None:
                fn, tiles = epilogue
                fn(at(out_ref, i),
                   *(None if r is None else at(r, i) for r in tiles))
            return carry
        if bt * nzc == 1:
            chunk(0, 0)
        else:
            jax.lax.fori_loop(0, bt * nzc, chunk, 0)

    body = centre_kernel if z_rows == "centre" else kernel
    if not combine:
        return body

    def combine_kernel(*refs):
        # operand order: psi refs, xc, coeff, then the links LAST (the
        # benchmark's trace reduction names a kernel event by the element
        # types of its result, first and last operand)
        *psi, xc, coeff, g_c, g_m, out_ref, nrm = refs
        nrm[...] = jnp.zeros(nrm.shape, F32)
        body(*psi, g_c, g_m, out_ref, xc=xc, coeff=coeff, nrm=nrm)

    def residual_kernel(*refs):
        # psi refs, xc, rc, coeff, alpha, then the links LAST
        *psi, xc, rc, coeff, alpha, g_c, g_m, out_ref, nrm = refs
        nrm[...] = jnp.zeros(nrm.shape, F32)
        # the source's alpha, read outside the body's loop
        body(*psi, g_c, g_m, out_ref, xc=xc, coeff=coeff, nrm=nrm, rc=rc,
             alpha=alpha[pl.program_id(2)])

    return residual_kernel if residual else combine_kernel


def _sublane_rows(dtype) -> int:
    """Rows of the dtype's (sublane, 128) tile: (8,128) f32, (16,128)
    bf16, (32,128) int8."""
    return {4: 8, 2: 16, 1: 32}[jnp.dtype(dtype).itemsize]


def _pick_bz(Z: int, YX: int, dtype=jnp.float32, planes: int = 288,
             min_bz: int = 1,
             vmem_knob: str = "QUDA_TPU_PALLAS_VMEM_MB",
             allow_bzfull: bool = False) -> int:
    """Divisor of Z maximising sublane-tile utilisation within the VMEM
    budget.

    Working set per grid step: 5 psi tiles (24 planes each) + forward
    and backward gauge tiles (72 each) + out (24) = 288 planes of
    (BZ, YX->lane-padded) storage, double-buffered by Mosaic across grid
    steps.  Budget the single-buffer set at 6 MB (< half the 16 MB
    scoped-VMEM limit).

    The z-block axis is the SUBLANE axis of every tile, so BZ pads to
    the dtype's sublane tile: 8 rows for f32, 16 for bf16.  A bz=8
    block of a bf16 array occupies a half-empty (16,128) tile — loads
    run at 50% utilisation (measured: bf16 SLOWER than f32 at bz=8) —
    so candidates are ranked by (utilisation, size), not size alone.

    HARDWARE LEGALITY (learned the hard way, round-5 chip run): the
    Mosaic TPU lowering requires the second-to-minor block extent to be
    divisible by 8 OR equal to the full array extent — interpret mode
    does not enforce this, so a utilisation-ranked bz=12 compiled on
    CPU and failed on the chip.  Candidates violating the rule are
    excluded here.

    ``vmem_knob`` names the registered budget knob — the Wilson kernels
    use the proven QUDA_TPU_PALLAS_VMEM_MB default; the staggered family
    passes its per-kernel override (QUDA_TPU_PALLAS_VMEM_MB_STAGGERED)
    with its raised default.

    ``allow_bzfull=True`` adds a LAST-RESORT full-block candidate: when
    no divisor fits the double-buffered knob budget, bz=Z is admitted if
    its working set fits the whole scoped-VMEM window SINGLE-buffered
    (Mosaic cannot double-buffer a block it can only hold once — the
    pipeline serialises, trading overlap for tile utilisation).  Callers
    that race forms (the bf16 full-tile path) opt in; the default keeps
    the long-standing fits-or-raises contract.

    Raises when even BZ=1 does not fit — callers fall back to the XLA
    packed path."""
    # sublane tile rows by itemsize: (8,128) f32, (16,128) bf16,
    # (32,128) int8 — the audit must charge the PADDED tile, not the
    # logical rows (a bf16 bz=24 block really holds 32 sublanes)
    sub = _sublane_rows(dtype)
    nbytes = jnp.dtype(dtype).itemsize
    yx_pad = -(-YX // 128) * 128
    from ..utils import config as qconf
    budget = int(float(qconf.get(vmem_knob, fresh=True)) * 2 ** 20)
    fitting = []
    for bz in sorted({d for d in range(min_bz, Z + 1)
                      if Z % d == 0}):
        if bz % 8 != 0 and bz != Z:
            continue               # illegal block on real TPU hardware
        bz_pad = -(-bz // sub) * sub
        if planes * bz_pad * yx_pad * nbytes <= budget:
            fitting.append((bz / bz_pad, bz, bz_pad))
    single_buffered = False
    if not fitting and allow_bzfull:
        from ..obs import memory as omem
        scoped = int(omem.SCOPED_VMEM_MB * 2 ** 20)
        bz_pad = -(-Z // sub) * sub
        if planes * bz_pad * yx_pad * nbytes <= scoped:
            fitting.append((Z / bz_pad, Z, bz_pad))
            single_buffered = True
    if not fitting:
        min_ws = planes * sub * yx_pad * nbytes / 2 ** 20
        hint = ("" if min_bz <= 1 else
                f" (candidates restricted to bz >= {min_bz} by the "
                "multi-hop z-splice)")
        raise ValueError(
            f"no z-block of Z={Z} fits the VMEM budget at YX={YX} "
            f"(min working set {min_ws:.1f} MB){hint}; fall back to the "
            "XLA packed stencil for this operator")
    _, bz, bz_pad = max(fitting)
    try:
        # audit the decision against its budget knob (obs/memory.py):
        # selected single-buffer working set -> vmem_block_bytes gauge
        # + the fleet report's VMEM section (no-op when metrics off)
        from ..obs import memory as omem
        omem.vmem_audit(vmem_knob, planes * bz_pad * yx_pad * nbytes,
                        budget, bz=bz, single_buffered=single_buffered)
    except Exception:
        pass
    return bz


@functools.partial(jax.jit,
                   static_argnames=("X", "interpret", "block_z",
                                    "tb_sign"))
def dslash_pallas_packed(gauge_pl: jnp.ndarray, psi_pl: jnp.ndarray,
                         X: int, interpret: bool = False,
                         block_z: int | None = None,
                         gauge_bw: jnp.ndarray | None = None,
                         tb_sign: bool = True) -> jnp.ndarray:
    """Wilson hop sum on pallas-layout pair arrays.

    gauge_pl: (4,R,3,2,T,Z,YX) f32 (phases folded; R = 3 rows, or 2 for
    reconstruct-12 storage, see ``to_recon12`` — ``tb_sign`` re-applies
    the folded antiperiodic-t phase to the reconstructed row);
    psi_pl: (4,3,2,T,Z,YX) f32.  Returns the same layout as psi_pl.
    ``block_z`` overrides the auto-chosen z-block size (must divide Z).
    ``gauge_bw`` is the pre-shifted backward gauge from
    ``backward_gauge``; pass it when applying the operator many times
    against a fixed gauge (solvers, benchmarks) so the rolls are not
    re-traced into every application.
    """
    from jax.experimental import pallas as pl

    _, _, _, T, Z, YX = psi_pl.shape
    R = gauge_pl.shape[1]
    bz = block_z if block_z is not None else _pick_bz(
        Z, YX, psi_pl.dtype, planes=288 if R == 3 else 240)
    if Z % bz != 0:
        raise ValueError(f"block_z={bz} does not divide Z={Z}")
    nzb = Z // bz
    if gauge_bw is None:
        gauge_bw = backward_gauge(gauge_pl, X)

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (4, 3, 2, 1, bz, YX),
            lambda t, zb, dt=dt, dz=dz: (0, 0, 0, (t + dt) % T,
                                         (zb + dz) % nzb, 0))

    gauge_spec = pl.BlockSpec(
        (4, R, 3, 2, 1, bz, YX), lambda t, zb: (0, 0, 0, 0, t, zb, 0))

    kernel = _make_kernel(X, bz, T=T, tb_sign=tb_sign)

    return pl.pallas_call(
        kernel,
        grid=(T, nzb),
        in_specs=[psi_spec(0, 0), psi_spec(+1, 0), psi_spec(-1, 0),
                  psi_spec(0, +1), psi_spec(0, -1), gauge_spec,
                  gauge_spec],
        out_specs=pl.BlockSpec((4, 3, 2, 1, bz, YX),
                               lambda t, zb: (0, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape, psi_pl.dtype),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, psi_pl, psi_pl, gauge_pl, gauge_bw)


# -- multi-RHS (MRHS) variants of the v2 kernels ---------------------------
#
# Production workloads (propagator inversions, RHMC pseudofermions, MG
# setup solves) apply the SAME gauge field to many right-hand sides.  The
# MRHS form keeps the kernel body BIT-IDENTICAL per RHS and changes only
# the pipeline: the RHS axis is the INNERMOST grid axis, psi/out
# BlockSpecs carry a leading size-1 RHS block, and the gauge BlockSpecs'
# index maps ignore n, so consecutive grid steps present the same gauge
# block index and Mosaic keeps the tile resident: N spinor tiles stream
# through one gauge load (576 B a site, once).
#
# A batch of spinors does not sit on chip the way XLA keeps a single
# source's in a CG loop (8 x 16 MB at 24^4), and with N innermost no two
# consecutive steps share a psi block index: every psi operand of every
# step is a fresh DMA from HBM, and the kernel's time follows the bytes
# it moves (PERF.md section 5, "bytes moved against time").  Per output
# site and call, f32, N sources:
#
#   route         psi operands            moved                N=8    needed
#   zblock        c, t+1, t-1, z+1, z-1   576 + N (480 + 96)   5,184  2,112
#   fullz, bt 1   c, t+1, t-1             576 + N (288 + 96)   3,648  2,112
#   fullz, bt 2   c (2 slices), t+2, t-1  576 + N (192 + 96)   2,880  2,112
#
# and the block-carrying calls of ops/clover_pallas on the same routes
# (576 B a site of chiral blocks beside the links, once for all sources;
# ``diag_hop`` reads its ``xc`` tile besides, 96 B a source):
#
#   post, zblock                          1,152 + N (480 + 96) 5,760  2,688
#   post, fullz bt 1                      1,152 + N (288 + 96) 4,224  2,688
#   diag_hop, zblock                      1,152 + N (576 + 96) 6,528  3,456
#   diag_hop, fullz bt 1                  1,152 + N (384 + 96) 4,992  3,456
#
# The z-blocked route DMAs a whole z-neighbour tile for the one row the
# body splices from it; at 24^4 (bz 8, 576 grid steps of 9 KB planes)
# the cell wilson24_mrhs8.light read 1,643 us a call, 26 % of the
# needed-bytes roofline (ledger, PR 30).  The full-Z route holds whole
# (Z, YX) tiles in VMEM, takes its z rows from the centre tile, its t
# neighbours from the block's own slices where it has them, and walks
# the block in chunks of one sublane tile, so the body's values stay
# the z-blocked body's size (the compiler's schedule for the described
# v5e: 1,937 bundles a chunk against 2,170 a z-block step).  With one
# time-slice a step the cell read 1,200 us, 35.6 % (PERF.md section 6,
# PR 31, which has the reading with two as well).  The planes' 288
# lanes are stored as 384 in HBM and in VMEM, so 75 % of the
# needed-bytes roofline is the most this layout can read.


class _LeadAxisRef:
    """Trace-time view of a pallas Ref whose block carries one extra
    LEADING singleton axis (the RHS block of the MRHS kernels): indexing
    is forwarded with a 0 prepended, so the single-RHS kernel body reads
    and writes it unchanged (bit-identical math by construction)."""

    def __init__(self, ref):
        self._ref = ref

    @property
    def shape(self):
        return self._ref.shape[1:]

    @property
    def dtype(self):
        return self._ref.dtype

    def __getitem__(self, idx):
        if not isinstance(idx, tuple):
            idx = (idx,)
        return self._ref[(0,) + idx]

    def __setitem__(self, idx, val):
        if not isinstance(idx, tuple):
            idx = (idx,)
        self._ref[(0,) + idx] = val


def _mrhs_wrap(kernel, n_psi: int = 5, n_out: int = 1):
    """Adapt a single-RHS kernel to MRHS blocks: the first ``n_psi`` refs
    and the first of the ``n_out`` output refs (the spinor) carry a
    leading size-1 RHS axis; gauge refs and any further output pass
    through untouched."""
    def wrapped(*refs):
        n_in = len(refs) - n_out
        psi = [_LeadAxisRef(r) for r in refs[:n_psi]]
        kernel(*psi, *refs[n_psi:n_in], _LeadAxisRef(refs[n_in]),
               *refs[n_in + 1:])
    return wrapped


# What the full-Z route may ask of a core's VMEM: three eighths of a
# v5e core's 128 MiB (Mosaic's scoped default is
# obs/memory.SCOPED_VMEM_MB = 16).  The call sets ``vmem_limit_bytes``
# to what it needs and runs only where that stays under this cap; 24^4
# in f32 needs 21.9 MiB with one time-slice a step and 35.2 with two.
_MRHS_FULLZ_VMEM_CAP = 48 * 2 ** 20


def _mrhs_fullz_vmem(Z: int, YX: int, dtype, out_dtype, R: int,
                     bt: int = 1, xc_dtype=None, rc_dtype=None,
                     extra: tuple = ()):
    """(block_bytes, need_bytes) of one full-Z MRHS step of ``bt``
    time-slices.  Blocks: bt + 2 psi tiles (24 planes each: the block's
    slices and the one after and before them), bt forward and backward
    link tiles (24 R each), bt out tiles (24) and, with the combine
    epilogue, bt tiles of its ``xc`` operand (24, of ``xc_dtype``) and,
    in its residual form, bt more of ``rc`` (``rc_dtype``),
    every (Z, YX) plane padded to its dtype's (sublane, 128) tile as
    ``_pick_bz`` pads it, and the f32 block of the epilogue's sums of
    squares, one chunk of the body's rows.  ``extra``: (planes, dtype)
    of each block a slice that a caller's own epilogue brings (the
    fused clover kernels of ops/clover_pallas: 144 planes of chiral
    blocks, 24 of their centre operand), bt of each.
    Need: the blocks double-buffered by the pipeline plus the body's
    own f32 tiles, which live in VMEM, not in vregs (accumulators, the
    loaded spinor, the hop's temporaries): six spinors' worth of full-Z
    planes.  The described-v5e compiles of 24^4 f32 ask for five where
    the body works on the whole tile (17.72 MiB with 13.5 of blocks at
    R = 2, bt = 1) and for 2.0 MiB where it walks 8-row chunks (18.85
    MiB with 16.9 of blocks at R = 3, bt = 1)."""
    yx_pad = -(-YX // 128) * 128

    def plane(dt):
        sub = _sublane_rows(dt)
        return -(-Z // sub) * sub * yx_pad * jnp.dtype(dt).itemsize

    blocks = (((bt + 2) * 24 + 2 * 24 * R * bt) * plane(dtype)
              + bt * 24 * plane(out_dtype))
    if xc_dtype is not None:
        rows = -(-_fullz_chunk(Z, dtype) // 8) * 8
        blocks += bt * 24 * plane(xc_dtype) + rows * yx_pad * 4
    if rc_dtype is not None:
        blocks += bt * 24 * plane(rc_dtype)
    blocks += sum(bt * n * plane(dt) for n, dt in extra)
    return blocks, 2 * blocks + 6 * 24 * plane(F32)


def _fullz_chunk(Z: int, dtype) -> int:
    """Rows the full-Z body works on at a time: one sublane tile of the
    storage dtype (8 of f32, 16 of bf16), so that its values are the
    z-blocked body's three vregs a plane and not Z/8 times as many (24
    accumulator planes alone are 72 of the 64 vregs at one tile);
    the whole tile where Z is no multiple of it (24 rows of bf16)."""
    sub = _sublane_rows(dtype)
    return sub if Z % sub == 0 else Z


def _mrhs_fullz_fit(T: int, Z: int, YX: int, dtype, out_dtype, R: int,
                    block_z: int | None, xc_dtype=None, rc_dtype=None,
                    extra: tuple = ()):
    """(bt, block_bytes, need_bytes) of the full-Z route where an MRHS
    call of these shapes takes it, else None: where a caller's
    ``block_z`` asks for z-blocks, or where ``_mrhs_fullz_vmem``'s need
    passes ``_MRHS_FULLZ_VMEM_CAP`` with one time-slice a step.  Two
    slices (``bt``) where they fit too and T is even.  A function of
    the shapes alone (the Wilson call's ``_mrhs_route`` and the
    block-carrying calls of ops/clover_pallas both ask it)."""
    if block_z not in (None, Z):
        return None
    for bt in (2, 1):
        if T % bt == 0:
            blocks, need = _mrhs_fullz_vmem(Z, YX, dtype, out_dtype, R, bt,
                                            xc_dtype, rc_dtype, extra)
            if need <= _MRHS_FULLZ_VMEM_CAP:
                return bt, blocks, need
    return None


def _fullz_vmem_limit(blocks: int, need: int, Z: int) -> int:
    """The ``vmem_limit_bytes`` of a full-Z call (its need, and not
    under Mosaic's scoped default), filed with the VMEM audit under the
    route's name beside ``_pick_bz``'s own decisions."""
    from ..obs import memory as omem
    limit = max(need, int(omem.SCOPED_VMEM_MB * 2 ** 20))
    omem.vmem_audit("QUDA_TPU_PALLAS_VMEM_MB", blocks, limit, bz=Z,
                    route="fullz")
    return limit


def _mrhs_route(T: int, Z: int, YX: int, dtype, out_dtype, R: int,
                block_z: int | None, xc_dtype=None, rc_dtype=None):
    """(route, bz, bt, vmem_limit_bytes) of an MRHS call, from its
    shapes.

    ``"fullz"``: one tile spans Z, the z shift wraps inside it and the
    call has three psi operands; taken where its VMEM need fits
    ``_MRHS_FULLZ_VMEM_CAP``, with two time-slices a step (``bt``)
    where they fit too and T is even: the block's own slices are then
    each other's t neighbours, and a spinor tile is read (bt + 2) / bt
    times.  ``"zblock"``: ``_pick_bz``'s z-block and the five psi
    operands of the single-RHS kernel, within the scoped default; taken
    where full-Z does not fit or a caller's ``block_z`` asks for
    z-blocks.  ``xc_dtype``: the call has the combine epilogue, whose
    ``xc`` operand is one more spinor block on either route, and its
    f32 block of sums of squares at most two planes of the storage
    dtype; ``rc_dtype``: the epilogue is the residual form, with its
    ``rc`` block besides.  Recorded at trace time: the VMEM audit gets
    the full-Z route's blocks and limit (``_pick_bz`` records the
    z-block's), ``wilson_mrhs_route_total`` counts the call by route,
    epilogue (``none``, ``combine``, ``residual``) and reduce
    (``norm2``: the epilogue's sums)."""
    from ..obs import metrics as omet
    fit = _mrhs_fullz_fit(T, Z, YX, dtype, out_dtype, R, block_z,
                          xc_dtype, rc_dtype)
    if fit:
        route, bz = "fullz", Z
        bt, blocks, need = fit
        limit = _fullz_vmem_limit(blocks, need, Z)
    else:
        route, bt, limit = "zblock", 1, None
        bz = block_z if block_z is not None else _pick_bz(
            Z, YX, dtype, planes=(288 if R == 3 else 240)
            + (24 + 4 // jnp.dtype(dtype).itemsize
               if xc_dtype is not None else 0)
            + (24 if rc_dtype is not None else 0))
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    omet.inc("wilson_mrhs_route_total", route=route,
             epilogue=("none" if xc_dtype is None else
                       "combine" if rc_dtype is None else "residual"),
             reduce="none" if xc_dtype is None else "norm2")
    return route, bz, bt, limit


def _mrhs_hop(g_c, g_m, psi_pl, X: int, eo, tb_sign: bool,
              block_z, out_dtype, interpret: bool, xc=None, coeff=None,
              g5: bool = False, rc=None, alpha=None):
    """The MRHS pallas_call shared by the full-lattice and the eo
    wrapper: grid (T/bt, Z/bz, N), RHS innermost, links indexed by
    (t, zb) alone.  With ``xc`` (an array of the result's shape) and
    ``coeff`` (a (1,) f32 array, in SMEM: an operand, so that a solve
    program serves every kappa) the call writes
    ``[g5] (xc + coeff * hop)``, ``_make_kernel``'s combine epilogue,
    and returns ``(v, |v|^2 per source)``, v what it wrote: the
    kernel's second output holds one block of f32 partial sums a grid
    step, (T/bt, Z/bz, N, rows, YX), and XLA sums those few KB a
    source to (N,) f32.  This is where the batched CG's ``pAp`` comes
    from.  With ``rc`` (as ``xc``) and ``alpha`` ((N,) f32, in SMEM)
    besides, the residual form: it writes ``rc - alpha[n] * [g5] (xc +
    coeff * hop)`` over ``rc``'s own buffer (each step reads and writes
    the same centre block of it) and sums that: the batched CG's new
    ``r`` and ``|r|^2`` (models/wilson.MdagM_cg_step_pairs_mrhs)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, _, _, _, T, Z, YX = psi_pl.shape
    R = g_c.shape[1]
    out_dtype = out_dtype or psi_pl.dtype
    combine, residual = xc is not None, rc is not None
    try:
        route, bz, bt, vmem_limit = _mrhs_route(
            T, Z, YX, psi_pl.dtype, out_dtype, R, block_z,
            xc.dtype if combine else None, rc.dtype if residual else None)
    except ValueError:
        if not combine:
            raise
        if residual:
            # no route holds the xc and the rc block: the combine hop
            # (or what it falls back to) and XLA's update and sum
            v, _ = _mrhs_hop(g_c, g_m, psi_pl, X, eo, tb_sign, block_z,
                             F32, interpret, xc, coeff, g5)
            v = rc.astype(F32) - alpha.reshape((N,) + (1,) * 6) * v
        else:
            # no route holds the xc block besides the hop's own (a
            # z-block at _pick_bz's budget): the bare hop, if that
            # fits, and XLA's passes over the batch, as without the
            # epilogue
            hop = _mrhs_hop(g_c, g_m, psi_pl, X, eo, tb_sign, block_z,
                            F32, interpret)
            v = xc.astype(F32) + coeff[0] * hop
            if g5:
                v = v * jnp.asarray([1, 1, -1, -1], F32).reshape(
                    (4,) + (1,) * 5)
        v = v.astype(out_dtype)
        w = v.astype(F32)
        return v, jnp.sum((w * w).reshape(N, -1), axis=1)
    nzb = Z // bz

    def psi_block(tb, zb, n):
        return (n, 0, 0, 0, tb, zb, 0)

    def psi_slice(dt, dz=0):
        # one time-slice, dt slices off the block's first: its block
        # index on the t axis is the slice itself
        return pl.BlockSpec(
            (1, 4, 3, 2, 1, bz, YX),
            lambda tb, zb, n: (n, 0, 0, 0, (tb * bt + dt) % T,
                               (zb + dz) % nzb, 0))

    # gauge index maps ignore n: the block index repeats across the
    # innermost RHS loop, so the pipeline re-uses the resident tile
    gauge_spec = pl.BlockSpec(
        (4, R, 3, 2, bt, bz, YX), lambda tb, zb, n: (0, 0, 0, 0, tb, zb, 0))

    def centre_spec():
        return pl.BlockSpec((1, 4, 3, 2, bt, bz, YX), psi_block)

    psi_specs = [centre_spec(), psi_slice(bt), psi_slice(T - 1)]
    if route == "fullz":
        body_rows, z_rows = _fullz_chunk(Z, psi_pl.dtype), "centre"
    else:
        body_rows, z_rows = bz, "tiles"
        psi_specs += [psi_slice(0, +1), psi_slice(0, -1)]
    # the epilogue's blocks (xc, rc) ride the out block's spec after
    # the psi operands; its scalars (the coefficient, alpha) precede
    # the links, in SMEM (combine_kernel's operand order)
    blocks = [v for v in (xc, rc) if v is not None]
    scalars = [k for k in (coeff, alpha) if k is not None]
    operands = [psi_pl] * len(psi_specs) + blocks
    psi_specs += [centre_spec() for _ in blocks]
    rest_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scalars)
                  + [gauge_spec, gauge_spec])
    out_specs = centre_spec()
    out_shape = jax.ShapeDtypeStruct(psi_pl.shape, out_dtype)
    if combine:
        # one (rows, YX) block of partial sums a grid step
        out_specs = [out_specs, pl.BlockSpec(
            (None, None, None, body_rows, YX),
            lambda tb, zb, n: (tb, zb, n, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (T // bt, nzb, N, body_rows, YX), F32)]
    kernel = _mrhs_wrap(
        _make_kernel(X, body_rows, eo=eo, T=T, tb_sign=tb_sign,
                     z_rows=z_rows, combine=combine, g5=g5,
                     residual=residual),
        n_psi=len(psi_specs), n_out=1 + combine)
    # the new r takes the old one's buffer where their types agree:
    # without the alias XLA copies the batch once an iteration to
    # carry it (call_s 8.07 against 7.43 s, PERF.md section 6, PR 39)
    aliases = ({len(operands) - 1: 0}
               if residual and rc.dtype == out_dtype else {})

    call = pl.pallas_call(
        kernel,
        grid=(T // bt, nzb, N),
        in_specs=psi_specs + rest_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )
    # the body's trace, wherever on the frame stack this call stands
    out = _on_a_stack_chunk_of_its_own(
        lambda: call(*operands, *scalars, g_c, g_m))
    if not combine:
        return out
    return out[0], jnp.sum(out[1], axis=(0, 1, 3, 4))


@functools.partial(jax.jit,
                   static_argnames=("X", "interpret", "block_z",
                                    "tb_sign"))
def dslash_pallas_packed_mrhs(gauge_pl: jnp.ndarray, psi_pl: jnp.ndarray,
                              X: int, interpret: bool = False,
                              block_z: int | None = None,
                              gauge_bw: jnp.ndarray | None = None,
                              tb_sign: bool = True) -> jnp.ndarray:
    """Multi-RHS Wilson hop sum on pallas-layout pair arrays.

    gauge_pl: (4,3,3,2,T,Z,YX); psi_pl: (N,4,3,2,T,Z,YX) — a leading
    RHS axis over the ``dslash_pallas_packed`` layout.  Returns the same
    batched layout.  Per-RHS results bit-match the single-RHS v2 kernel
    (same kernel body per grid step); the gauge tiles are loaded once
    per (t, z-block) and amortised over all N RHS by grid ordering.
    """
    if gauge_bw is None:
        gauge_bw = backward_gauge(gauge_pl, X)
    return _mrhs_hop(gauge_pl, gauge_bw, psi_pl, X, None, tb_sign,
                     block_z, None, interpret)


def _eo_mrhs_jit(name: str, epilogue: str):
    """The checkerboarded MRHS hop under a ``jax.jit`` of that name, the
    callers' own entry (no dispatching frame between them and the
    jit: the Python stack above a kernel is paid for in its tracing
    and lowering, PERF.md section 7 (22)).  A kernel event in a profiler
    capture is named after the jitted function that wraps its
    ``pallas_call``.  ``epilogue``: the operands the entry takes,
    ``"none"``, ``"combine"`` (xc, coeff) or ``"residual"`` (rc, alpha
    besides)."""
    def hop(u_here_pl, u_bw_pl, psi_pl, dims, target_parity,
            interpret=False, block_z=None, out_dtype=None, tb_sign=True,
            xc=None, coeff=None, g5=False, rc=None, alpha=None):
        given = ("none" if xc is None and rc is None else
                 "combine" if rc is None else "residual")
        if given != epilogue:
            raise ValueError(
                f"{name}: the hop with the combine epilogue (xc, coeff) is "
                "dslash_eo_pallas_packed_mrhs_combine, with the residual "
                "form (rc, alpha besides) "
                "dslash_eo_pallas_packed_mrhs_residual, the bare hop "
                "dslash_eo_pallas_packed_mrhs")
        X = dims[3]
        if xc is not None:
            coeff = jnp.asarray(coeff, F32).reshape(1)
        if rc is not None:
            alpha = jnp.asarray(alpha, F32).reshape(psi_pl.shape[0])
        return _mrhs_hop(u_here_pl, u_bw_pl, psi_pl, X,
                         (target_parity, X // 2), tb_sign, block_z,
                         out_dtype, interpret, xc, coeff, g5, rc, alpha)
    hop.__name__ = hop.__qualname__ = name
    hop.__doc__ = _EO_MRHS_DOC
    return jax.jit(hop, static_argnames=("dims", "target_parity",
                                         "interpret", "block_z",
                                         "out_dtype", "tb_sign", "g5"))


_EO_MRHS_DOC = """Multi-RHS checkerboarded Wilson hop — the batched-solver hot path
    (``dslash_eo_pallas_packed`` with a leading RHS axis on psi).

    u_here_pl/u_bw_pl as in the single-RHS eo kernel; psi_pl:
    (N,4,3,2,T,Z,Y*Xh) of parity 1-p.  Gauge tiles are fetched once per
    (t, z-block) and shared by all N RHS (RHS-innermost grid); the
    route (full-Z tiles or z-blocks) follows the shapes, ``_mrhs_route``.

    ``dslash_eo_pallas_packed_mrhs`` is the bare hop sum.
    ``dslash_eo_pallas_packed_mrhs_combine`` takes ``xc`` (a batch of
    parity p, the result's shape) and ``coeff`` (a float or an f32
    scalar array: an operand either way) besides: the kernel's epilogue
    writes ``xc + coeff * hop``, and ``g5`` puts gamma5 in front of it:
    the second hop of the preconditioned operator then hands back ``M x``
    (or ``g5 M x``) itself, and no XLA pass over the batch builds it
    from the bare hop sum.  Its result is the pair (that batch, its (N,)
    f32 squared norms per source), the norms summed by the same epilogue
    from what it stores.
    ``dslash_eo_pallas_packed_mrhs_residual`` takes ``rc`` (a batch as
    ``xc``) and ``alpha`` ((N,) f32, one a source) besides those: the
    epilogue writes ``rc - alpha * [g5] (xc + coeff * hop)`` and sums
    the squares of that.  With ``xc = q = g5 M p``, ``rc = r`` and
    ``coeff = -kappa^2`` it is the last hop of a batched CG iteration on
    ``MdagM = g5 M g5 M`` and hands back the new ``r`` and ``|r|^2``;
    ``A p`` is never stored.  Same body, three names: they are told
    apart in a capture, and the needed bytes of one are not charged to
    another (the argument structures traced and lowered apart before).
    """
dslash_eo_pallas_packed_mrhs = _eo_mrhs_jit(
    "dslash_eo_pallas_packed_mrhs", "none")
dslash_eo_pallas_packed_mrhs_combine = _eo_mrhs_jit(
    "dslash_eo_pallas_packed_mrhs_combine", "combine")
dslash_eo_pallas_packed_mrhs_residual = _eo_mrhs_jit(
    "dslash_eo_pallas_packed_mrhs_residual", "residual")


# -- hop algebra shared by the kernel bodies ---------------------------------
#
# Projection, colour multiply, spin reconstruction and the link accessors
# (full 18-real rows or in-kernel reconstruct-12) as functions of (re, im)
# tile pairs: the r12f, fold and int8 bodies below, the clover epilogue
# kernels (ops/clover_pallas.py) and the fused halo (parallel/pallas_halo)
# are built from them.


def _project(get_psi, table):
    """Half-spinor h[a][color] from unshifted psi planes."""
    t = table
    return [[_cadd(get_psi(a, c),
                   _cscale(t[f"c{a}"], get_psi(t[f"j{a}"], c)))
             for c in range(3)] for a in (0, 1)]


def _color_mul(h, get_link, adjoint):
    """uh[s][a] = sum_b U_ab h[s][b] (or U^dag for adjoint)."""
    uh = [[None] * 3 for _ in range(2)]
    for s in range(2):
        for a in range(3):
            term = None
            for b in range(3):
                m = (_cmul_conj(get_link(b, a), h[s][b]) if adjoint
                     else _cmul(get_link(a, b), h[s][b]))
                term = m if term is None else _cadd(term, m)
            uh[s][a] = term
    return uh


def _recon_acc(acc, uh, table):
    """Accumulate the 2-spinor product with spin reconstruction."""
    t = table
    for c in range(3):
        acc[0][c] = _cadd(acc[0][c], uh[0][c])
        acc[1][c] = _cadd(acc[1][c], uh[1][c])
        acc[2][c] = _cadd(acc[2][c], _cscale(t["d2"], uh[t["k2"]][c]))
        acc[3][c] = _cadd(acc[3][c], _cscale(t["d3"], uh[t["k3"]][c]))


def _recon12_wrap(stored, nrow: int, row2_sign=None):
    """Wrap a stored-element accessor (a, b) -> (re, im) with the
    reconstruct-12 row build (QUDA QUDA_RECONSTRUCT_12,
    gauge_field_order.h Reconstruct<12>): for ``nrow == 3`` the accessor
    passes through; for ``nrow == 2`` row 2 = conj(row0 x row1) is built
    on demand and memoised at trace time (each needed column computed
    once per direction-use).  The SINGLE home for the recon algebra —
    the full-link, folded-layout, and staggered accessors all wrap
    through here, so every storage variant reconstructs with identical
    float ops.

    ``row2_sign``: the t-boundary wrinkle — links are stored with the
    antiperiodic phase FOLDED IN, and for V = -U the cross product gives
    +u2 (the two -1s cancel), so the reconstructed row of a t-link on
    the boundary plane must be re-negated.  Pass a scalar (or
    broadcastable plane of) +-1 factors.
    """
    if nrow == 3:
        return stored

    cache = {}

    def get(a, b):
        if a < 2:
            return stored(a, b)
        if b not in cache:
            b1, b2 = (b + 1) % 3, (b + 2) % 3
            x = _csub(_cmul(stored(0, b1), stored(1, b2)),
                      _cmul(stored(0, b2), stored(1, b1)))
            re, im = x[0], -x[1]          # conjugate of the cross product
            if row2_sign is not None:
                re, im = re * row2_sign, im * row2_sign
            cache[b] = (re, im)
        return cache[b]

    return get


def _link_getter(ref, mu, row2_sign=None):
    """Accessor (a, b) -> (re, im) link element from a packed gauge ref.

    Dispatches on the ref's ROW extent via ``_recon12_wrap``: 3 = full
    18-real storage; 2 = in-kernel reconstruct-12."""

    def stored(a, b):
        # full-link blocks are (4,R,3,2,1,bz,YX); boundary-ROW gauge
        # inputs carry one extra singleton z axis (see psi_at)
        pad = (0,) * (len(ref.shape) - 7)
        return (ref[(mu, a, b, 0, 0) + pad].astype(F32),
                ref[(mu, a, b, 1, 0) + pad].astype(F32))

    return _recon12_wrap(stored, ref.shape[1], row2_sign)


def to_recon12(gauge_pl: jnp.ndarray) -> jnp.ndarray:
    """Packed links -> reconstruct-12 storage: keep rows 0-1 only.
    (4, 3, 3, 2, T, Z, YX) -> (4, 2, 3, 2, T, Z, YX); 192 B/site f32
    instead of 288.  Valid for SU(3) links (incl. folded antiperiodic-t:
    the kernels re-apply the boundary sign to the reconstructed row)."""
    return gauge_pl[:, :2]


# -- even/odd (checkerboarded) kernel: the solver hot path ------------------

def backward_gauge_eo(u_there_pl: jnp.ndarray, dims,
                      target_parity: int) -> jnp.ndarray:
    """Pre-shifted backward links on the half lattice:
    out[mu](x) = U_mu(x - mu) for parity-``target_parity`` sites x, where
    ``u_there_pl`` holds the opposite-parity links in the packed pair
    layout (4,3,3,2,T,Z,Y*Xh).  Computed once per gauge load."""
    from .wilson_packed import shift_eo_packed
    return jnp.stack([
        shift_eo_packed(u_there_pl[mu], dims, mu, -1, target_parity)
        for mu in range(4)])


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z",
                                             "out_dtype", "tb_sign"))
def dslash_eo_pallas_packed(u_here_pl: jnp.ndarray, u_bw_pl: jnp.ndarray,
                            psi_pl: jnp.ndarray, dims,
                            target_parity: int, interpret: bool = False,
                            block_z: int | None = None,
                            out_dtype=None,
                            tb_sign: bool = True) -> jnp.ndarray:
    """Checkerboarded Wilson hop on pallas-layout half-lattice pair
    arrays (the pallas analog of wilson_packed.dslash_eo_packed_pairs —
    the solver hot loop's stencil).

    u_here_pl: (4,R,3,2,T,Z,Y*Xh) forward links at target-parity sites
    (R = 2 selects in-kernel reconstruct-12, see ``to_recon12``;
    ``tb_sign`` re-applies the folded antiperiodic-t phase to the
    reconstructed row); u_bw_pl: pre-shifted backward links from
    ``backward_gauge_eo``; psi_pl: (4,3,2,T,Z,Y*Xh) parity-(1-p)
    spinor.  Returns the hop sum indexed by parity-``target_parity``
    sites, same layout as psi_pl.
    """
    from jax.experimental import pallas as pl

    T, Z, Y, X = dims
    Xh = X // 2
    R = u_here_pl.shape[1]
    _, _, _, _, _, YXh = psi_pl.shape
    bz = block_z if block_z is not None else _pick_bz(
        Z, YXh, psi_pl.dtype, planes=288 if R == 3 else 240)
    if Z % bz != 0:
        raise ValueError(f"block_z={bz} does not divide Z={Z}")
    nzb = Z // bz

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (4, 3, 2, 1, bz, YXh),
            lambda t, zb, dt=dt, dz=dz: (0, 0, 0, (t + dt) % T,
                                         (zb + dz) % nzb, 0))

    gauge_spec = pl.BlockSpec(
        (4, R, 3, 2, 1, bz, YXh), lambda t, zb: (0, 0, 0, 0, t, zb, 0))

    kernel = _make_kernel(X, bz, eo=(target_parity, Xh), T=T,
                          tb_sign=tb_sign)

    return pl.pallas_call(
        kernel,
        grid=(T, nzb),
        in_specs=[psi_spec(0, 0), psi_spec(+1, 0), psi_spec(-1, 0),
                  psi_spec(0, +1), psi_spec(0, -1), gauge_spec,
                  gauge_spec],
        out_specs=pl.BlockSpec((4, 3, 2, 1, bz, YXh),
                               lambda t, zb: (0, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape,
                                       out_dtype or psi_pl.dtype),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, psi_pl, psi_pl, u_here_pl, u_bw_pl)


# -- folded re/im storage: full bf16 sublane tiles --------------------------
#
# Round 5 measured bf16 storage LOSING 5x to f32 (1103 vs 5673 GFLOPS)
# for a layout reason, not a hardware one: no divisor of Z=24 fills a
# (16,128) bf16 sublane tile, so every bf16 block ran at 50% load
# utilisation.  The fold stores the re/im PAIR on the sublane axis —
# (..., 2, T, Z, YX) becomes (..., T, 2Z, YX) with row 2k = re(z=k) and
# row 2k+1 = im(z=k) — so a bz'=16 block holds 8 complete z-sites and
# fills the bf16 tile exactly; z-shifts become row-shifts by 2.  The
# kernel unfolds each tile into (re, im) f32 planes at load
# (x.reshape(n, 2, YX) -> [:, 0] / [:, 1]: a sublane DEINTERLEAVE, not
# a strided gather) and re-interleaves at the output write, so the hop
# algebra between load and store is the v2 kernel's, float op for
# float op — fold-vs-v2 at equal storage dtype is bitwise identical.


def to_fold(pp: jnp.ndarray) -> jnp.ndarray:
    """Pair layout (..., 2, T, Z, YX) -> folded (..., T, 2Z, YX): the
    re/im axis interleaved into the sublane (z) axis, row 2k = re of
    z=k, row 2k+1 = im.  Works for spinor pairs (4,3,2,T,Z,YX) and
    packed links (4,R,3,2,T,Z,YX) alike (the axis -4 is the pair axis
    in both)."""
    *lead, two, T, Z, YX = pp.shape
    if two != 2:
        raise ValueError(f"axis -4 must be the re/im pair axis, got {two}")
    m = jnp.moveaxis(pp, -4, -2)            # (..., T, Z, 2, YX)
    return m.reshape(*lead, T, 2 * Z, YX)


def from_fold(fp: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``to_fold``: (..., T, 2Z, YX) -> (..., 2, T, Z, YX)."""
    *lead, T, Z2, YX = fp.shape
    m = fp.reshape(*lead, T, Z2 // 2, 2, YX)
    return jnp.moveaxis(m, -2, -4)


def _unfold_tile(x):
    """(2n, YX) interleaved tile -> (re, im) f32 planes of (n, YX) via a
    sublane deinterleave (reshape + unit-index, no strided slicing)."""
    n2, yx = x.shape
    r = x.reshape(n2 // 2, 2, yx)
    return (r[:, 0].astype(F32), r[:, 1].astype(F32))


def _fold_tile(re, im, dtype):
    """(re, im) (n, YX) planes -> one interleaved (2n, YX) tile."""
    return jnp.stack([re, im], axis=1).reshape(
        2 * re.shape[0], re.shape[1]).astype(dtype)


def _fold_link_getter(ref, mu, row2_sign=None):
    """_link_getter for folded gauge blocks (4, R, 3, 1, bz2, YX):
    unfold each stored element, reconstruct row 2 in f32 if R == 2."""

    def stored(a, b):
        return _unfold_tile(ref[mu, a, b, 0])

    return _recon12_wrap(stored, ref.shape[1], row2_sign)


def _make_kernel_fold(X: int, bz2: int, eo: tuple | None = None,
                      T: int | None = None, tb_sign: bool = True):
    """v2 hop kernel on FOLDED tiles.  Ref shapes (bz2 = 2 * bz z-sites):
      psi refs:   (4, 3, 1, bz2, YX) x5 (c, t+1, t-1, z+1, z-1)
      g_c / g_m:  (4, R, 3, 1, bz2, YX) (forward / pre-shifted backward)
    Accessors unfold to (re, im) f32 planes of (bz, YX); between load
    and store the body is _make_kernel's, so same-storage results are
    bitwise identical to the v2 kernel."""
    from jax.experimental import pallas as pl

    bz = bz2 // 2

    def kernel(psi_c, psi_tp, psi_tm, psi_zp, psi_zm, g_c, g_m, out_ref):
        shape = (bz, psi_c.shape[-1])
        if eo is not None:
            parity, Xh = eo
            t_id = pl.program_id(0)
            zb_id = pl.program_id(1)
            z = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                 + zb_id * bz)
            y = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // Xh
            mask_r0 = ((t_id + z + y + parity) % 2) == 0

        def shift_x(v, sign):
            if eo is None:
                return _shift_xy(v, 0, sign, X)
            return _shift_x_eo(v, sign, eo[1], mask_r0)

        def psi_at(ref, s, c):
            return _unfold_tile(ref[s, c, 0])

        def psi_row(ref, s, c, rows):
            re, im = _unfold_tile(ref[s, c, 0])
            return (re[rows], im[rows])

        if g_c.shape[1] == 2 and tb_sign:
            t_idx = pl.program_id(0)
            s_t_fwd = jnp.where(t_idx == T - 1, -1.0, 1.0).astype(F32)
            s_t_bwd = jnp.where(t_idx == 0, -1.0, 1.0).astype(F32)
        else:
            s_t_fwd = s_t_bwd = None

        acc = [[(jnp.zeros(shape, F32), jnp.zeros(shape, F32))
                for _ in range(3)] for _ in range(4)]

        def project(get_psi, table):
            t = table
            return [[_cadd(get_psi(a, c),
                           _cscale(t[f"c{a}"], get_psi(t[f"j{a}"], c)))
                     for c in range(3)] for a in (0, 1)]

        def color_acc(h, get_link, table, adjoint):
            t = table
            uh = [[None] * 3 for _ in range(2)]
            for s in range(2):
                for a in range(3):
                    term = None
                    for b in range(3):
                        m = (_cmul_conj(get_link(b, a), h[s][b]) if adjoint
                             else _cmul(get_link(a, b), h[s][b]))
                        term = m if term is None else _cadd(term, m)
                    uh[s][a] = term
            for c in range(3):
                acc[0][c] = _cadd(acc[0][c], uh[0][c])
                acc[1][c] = _cadd(acc[1][c], uh[1][c])
                acc[2][c] = _cadd(acc[2][c],
                                  _cscale(t["d2"], uh[t["k2"]][c]))
                acc[3][c] = _cadd(acc[3][c],
                                  _cscale(t["d3"], uh[t["k3"]][c]))

        for mu in (0, 1):
            for sign, adjoint, gref in ((+1, False, g_c), (-1, True, g_m)):
                t = TABLES[(mu, sign)]
                h = project(lambda s, c: psi_at(psi_c, s, c), t)
                if mu == 0:
                    h = [[shift_x(h[a][c], sign) for c in range(3)]
                         for a in (0, 1)]
                else:
                    h = [[_shift_xy(h[a][c], 1, sign,
                                    X if eo is None else eo[1])
                          for c in range(3)] for a in (0, 1)]
                color_acc(h, _fold_link_getter(gref, mu), t, adjoint)
        for sign, adjoint, gref, nb in ((+1, False, g_c, psi_zp),
                                        (-1, True, g_m, psi_zm)):
            t = TABLES[(2, sign)]
            rows = slice(0, 1) if sign > 0 else slice(-1, None)
            h = project(lambda s, c: psi_at(psi_c, s, c), t)
            h_row = project(lambda s, c: psi_row(nb, s, c, rows), t)
            h = [[_shift_z(h[a][c], h_row[a][c], sign) for c in range(3)]
                 for a in (0, 1)]
            color_acc(h, _fold_link_getter(gref, 2), t, adjoint)
        for sign, adjoint, gref, nb, r2s in (
                (+1, False, g_c, psi_tp, s_t_fwd),
                (-1, True, g_m, psi_tm, s_t_bwd)):
            t = TABLES[(3, sign)]
            h = project(lambda s, c, nb=nb: psi_at(nb, s, c), t)
            color_acc(h, _fold_link_getter(gref, 3, r2s), t, adjoint)

        odt = out_ref.dtype
        for s in range(4):
            for c in range(3):
                out_ref[s, c, 0] = _fold_tile(acc[s][c][0], acc[s][c][1],
                                              odt)

    return kernel


def _fold_planes(R: int) -> int:
    # 5 psi tiles (12 folded planes each) + 2 gauge tiles (4*R*3 each)
    # + out (12), in (bz2, YX) planes
    return 60 + 2 * 4 * R * 3 + 12


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z2",
                                             "out_dtype", "tb_sign"))
def dslash_eo_pallas_packed_fold(u_here_f: jnp.ndarray,
                                 u_bw_f: jnp.ndarray,
                                 psi_f: jnp.ndarray, dims,
                                 target_parity: int,
                                 interpret: bool = False,
                                 block_z2: int | None = None,
                                 out_dtype=None,
                                 tb_sign: bool = True) -> jnp.ndarray:
    """Checkerboarded Wilson hop on FOLDED half-lattice arrays (see
    ``to_fold``): u_here_f/u_bw_f (4,R,3,T,2Z,Y*Xh) forward /
    pre-shifted backward links, psi_f (4,3,T,2Z,Y*Xh) parity-(1-p)
    spinor.  Returns the folded layout.  Same-storage results bit-match
    ``dslash_eo_pallas_packed``; at bf16 the folded blocks fill (16,128)
    sublane tiles exactly (bz2=16 = 8 z-sites) instead of half-filling
    them at bz=8."""
    from jax.experimental import pallas as pl

    T, Z, Y, X = dims
    Xh = X // 2
    R = u_here_f.shape[1]
    _, _, _, Z2, YXh = psi_f.shape
    bz2 = block_z2 if block_z2 is not None else _pick_bz(
        Z2, YXh, psi_f.dtype, planes=_fold_planes(R), min_bz=2,
        allow_bzfull=True)
    if Z2 % bz2 != 0 or bz2 % 2 != 0:
        raise ValueError(f"block_z2={bz2} must be even and divide 2Z={Z2}")
    nzb = Z2 // bz2

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (4, 3, 1, bz2, YXh),
            lambda t, zb, dt=dt, dz=dz: (0, 0, (t + dt) % T,
                                         (zb + dz) % nzb, 0))

    gauge_spec = pl.BlockSpec(
        (4, R, 3, 1, bz2, YXh), lambda t, zb: (0, 0, 0, t, zb, 0))

    kernel = _make_kernel_fold(X, bz2, eo=(target_parity, Xh), T=T,
                               tb_sign=tb_sign)

    return pl.pallas_call(
        kernel,
        grid=(T, nzb),
        in_specs=[psi_spec(0, 0), psi_spec(+1, 0), psi_spec(-1, 0),
                  psi_spec(0, +1), psi_spec(0, -1), gauge_spec,
                  gauge_spec],
        out_specs=pl.BlockSpec((4, 3, 1, bz2, YXh),
                               lambda t, zb: (0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_f.shape,
                                       out_dtype or psi_f.dtype),
        interpret=interpret,
    )(psi_f, psi_f, psi_f, psi_f, psi_f, u_here_f, u_bw_f)


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z2",
                                             "out_dtype", "tb_sign"))
def dslash_eo_pallas_packed_fold_mrhs(u_here_f: jnp.ndarray,
                                      u_bw_f: jnp.ndarray,
                                      psi_f: jnp.ndarray, dims,
                                      target_parity: int,
                                      interpret: bool = False,
                                      block_z2: int | None = None,
                                      out_dtype=None,
                                      tb_sign: bool = True) -> jnp.ndarray:
    """Multi-RHS folded checkerboarded hop: psi_f (N,4,3,T,2Z,Y*Xh);
    gauge tiles fetched once per (t, z-block) and shared by all N RHS
    (RHS-innermost grid, as dslash_eo_pallas_packed_mrhs)."""
    from jax.experimental import pallas as pl

    T, Z, Y, X = dims
    Xh = X // 2
    R = u_here_f.shape[1]
    N = psi_f.shape[0]
    _, _, _, _, Z2, YXh = psi_f.shape
    bz2 = block_z2 if block_z2 is not None else _pick_bz(
        Z2, YXh, psi_f.dtype, planes=_fold_planes(R), min_bz=2,
        allow_bzfull=True)
    if Z2 % bz2 != 0 or bz2 % 2 != 0:
        raise ValueError(f"block_z2={bz2} must be even and divide 2Z={Z2}")
    nzb = Z2 // bz2

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (1, 4, 3, 1, bz2, YXh),
            lambda t, zb, n, dt=dt, dz=dz: (n, 0, 0, (t + dt) % T,
                                            (zb + dz) % nzb, 0))

    gauge_spec = pl.BlockSpec(
        (4, R, 3, 1, bz2, YXh), lambda t, zb, n: (0, 0, 0, t, zb, 0))

    kernel = _mrhs_wrap(_make_kernel_fold(X, bz2,
                                          eo=(target_parity, Xh), T=T,
                                          tb_sign=tb_sign))

    return pl.pallas_call(
        kernel,
        grid=(T, nzb, N),
        in_specs=[psi_spec(0, 0), psi_spec(+1, 0), psi_spec(-1, 0),
                  psi_spec(0, +1), psi_spec(0, -1), gauge_spec,
                  gauge_spec],
        out_specs=pl.BlockSpec((1, 4, 3, 1, bz2, YXh),
                               lambda t, zb, n: (n, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_f.shape,
                                       out_dtype or psi_f.dtype),
        interpret=interpret,
    )(psi_f, psi_f, psi_f, psi_f, psi_f, u_here_f, u_bw_f)


# -- r12f: v2 gather pipeline, copy-free reconstruct-12 links ---------------
#
# The resident v2 reconstruct-12 path still materialises a PRE-SHIFTED
# backward link copy (backward_gauge_eo) — half the gauge HBM footprint
# again, and the array the sharded gauge-residency budget feels most.
# r12f keeps the v2 GATHER psi pipeline (whole z-neighbour tiles — the
# form the cells run) with a copy-free backward structure: backward
# x/y/z multiply the UNSHIFTED opposite-parity links pointwise and
# shift the product (scatter form — recon commutes with the shift, so
# reconstructing the local rows is bitwise identical to reconstructing
# pre-shifted rows), backward-t reads the U_t plane at t-1 via its
# index map.  HBM traffic equals
# wilson_v2_r12 (960 B/site: the backward links cost the same bytes
# read directly or via a copy) — what disappears is the resident copy
# itself and its backward_gauge_eo precompute.


def _make_kernel_r12f(X: int, bz: int, eo: tuple, T: int | None = None,
                      tb_sign: bool = True):
    """Copy-free v2-gather kernel over one (t, z-block) tile (eo only —
    the solver hot path).  Ref shapes:
      psi_c/tp/tm/zp/zm: (4, 3, 2, 1, bz, YX)   whole tiles (v2 gather)
      g_c:               (4, R, 3, 2, 1, bz, YX) forward links (parity p)
      g_there_xyz:       (3, R, 3, 2, 1, bz, YX) opposite-parity links
      g_t_tm:            (1, R, 3, 2, 1, bz, YX) U_t plane at t-1
      g_z_zm:            (1, R, 3, 2, 1, 1, YX)  U_z row at z-1
    """
    from jax.experimental import pallas as pl

    def kernel(*refs):
        (psi_c, psi_tp, psi_tm, psi_zp, psi_zm,
         g_c, g_there_xyz, g_t_tm, g_z_zm, out_ref) = refs
        parity, Xh = eo
        t_id = pl.program_id(0)
        zb_id = pl.program_id(1)
        shape = psi_c.shape[-2:]
        z = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + zb_id * bz
        y = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // Xh
        mask_r0 = ((t_id + z + y + parity) % 2) == 0

        def shift_x(v, sign):
            return _shift_x_eo(v, sign, Xh, mask_r0)

        def psi_at(ref, s, c):
            return (ref[s, c, 0, 0].astype(F32),
                    ref[s, c, 1, 0].astype(F32))

        def psi_row(ref, s, c, rows):
            return (ref[s, c, 0, 0][rows].astype(F32),
                    ref[s, c, 1, 0][rows].astype(F32))

        if g_c.shape[1] == 2 and tb_sign:
            t_idx = pl.program_id(0)
            s_fwd = jnp.where(t_idx == T - 1, -1.0, 1.0).astype(F32)
            s_bwd = jnp.where(t_idx == 0, -1.0, 1.0).astype(F32)
        else:
            s_fwd = s_bwd = None

        acc = [[(jnp.zeros(shape, F32), jnp.zeros(shape, F32))
                for _ in range(3)] for _ in range(4)]

        # x, y: forward = project center, shift h, multiply U(x);
        # backward = multiply U^dag(x) pointwise, shift the product
        for mu in (0, 1):
            tf = TABLES[(mu, +1)]
            h = _project(lambda s, c: psi_at(psi_c, s, c), tf)
            if mu == 0:
                h = [[shift_x(h[a][c], +1) for c in range(3)]
                     for a in (0, 1)]
            else:
                h = [[_shift_xy(h[a][c], 1, +1, Xh)
                      for c in range(3)] for a in (0, 1)]
            _recon_acc(acc, _color_mul(h, _link_getter(g_c, mu), False),
                       tf)

            tb = TABLES[(mu, -1)]
            h = _project(lambda s, c: psi_at(psi_c, s, c), tb)
            uh = _color_mul(h, _link_getter(g_there_xyz, mu), True)
            if mu == 0:
                uh = [[shift_x(uh[a][c], -1) for c in range(3)]
                      for a in (0, 1)]
            else:
                uh = [[_shift_xy(uh[a][c], 1, -1, Xh)
                       for c in range(3)] for a in (0, 1)]
            _recon_acc(acc, uh, tb)

        # z forward: splice the projected first row of the z+1 tile
        tf = TABLES[(2, +1)]
        h = _project(lambda s, c: psi_at(psi_c, s, c), tf)
        h_row = _project(lambda s, c: psi_row(psi_zp, s, c, slice(0, 1)),
                         tf)
        h = [[_shift_z(h[a][c], h_row[a][c], +1) for c in range(3)]
             for a in (0, 1)]
        _recon_acc(acc, _color_mul(h, _link_getter(g_c, 2), False), tf)

        # z backward: local product shifted down; the incoming row is
        # the z-1 product from the z-1 tile's LAST row and the U_z row
        tb = TABLES[(2, -1)]
        h = _project(lambda s, c: psi_at(psi_c, s, c), tb)
        uh = _color_mul(h, _link_getter(g_there_xyz, 2), True)
        h_b = _project(lambda s, c: psi_row(psi_zm, s, c,
                                            slice(-1, None)), tb)
        uh_b = _color_mul(h_b, _link_getter(g_z_zm, 0), True)
        uh = [[_shift_z(uh[a][c], uh_b[a][c], -1) for c in range(3)]
              for a in (0, 1)]
        _recon_acc(acc, uh, tb)

        # t forward / backward: whole neighbour planes, no shift
        tf = TABLES[(3, +1)]
        h = _project(lambda s, c: psi_at(psi_tp, s, c), tf)
        _recon_acc(acc, _color_mul(h, _link_getter(g_c, 3, s_fwd),
                                   False), tf)
        tb = TABLES[(3, -1)]
        h = _project(lambda s, c: psi_at(psi_tm, s, c), tb)
        _recon_acc(acc, _color_mul(h, _link_getter(g_t_tm, 0, s_bwd),
                                   True), tb)

        odt = out_ref.dtype
        for s in range(4):
            for c in range(3):
                out_ref[s, c, 0, 0] = acc[s][c][0].astype(odt)
                out_ref[s, c, 1, 0] = acc[s][c][1].astype(odt)

    return kernel


def _r12f_gz_rows(u_there_pl, R, T, nzb, bz, YXh):
    """Pre-gathered U_z boundary rows at z-1 (the previous block's last
    row of the mu=2 plane), shaped (1,R,3,2,T,nzb,1,YXh) so the block
    extent 1 legally equals the array extent (a 1-extent block on the
    sublane axis of a Z-extent array is refused by the hardware
    lowering: the second-to-minor block extent must divide by 8 or
    equal the array's)."""
    g_r = u_there_pl[2:3].reshape(1, R, 3, 2, T, nzb, bz, YXh)
    return jnp.roll(g_r[:, :, :, :, :, :, bz - 1, :], 1,
                    axis=5)[:, :, :, :, :, :, None, :]


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z",
                                             "out_dtype", "tb_sign"))
def dslash_eo_pallas_packed_r12f(u_here_pl: jnp.ndarray,
                                 u_there_pl: jnp.ndarray,
                                 psi_pl: jnp.ndarray, dims,
                                 target_parity: int,
                                 interpret: bool = False,
                                 block_z: int | None = None,
                                 out_dtype=None,
                                 tb_sign: bool = True) -> jnp.ndarray:
    """Checkerboarded Wilson hop, r12f form: the v2 gather pipeline with
    NO resident backward-gauge copy.  u_here_pl (4,R,3,2,T,Z,Y*Xh)
    forward links at target parity; u_there_pl the OPPOSITE-parity links
    (unshifted — scatter-form backward hops shift the product).  R = 2
    selects in-kernel reconstruct-12; results bit-match the resident
    v2 r12 path (recon commutes with the site shift)."""
    from jax.experimental import pallas as pl

    T, Z, Y, X = dims
    Xh = X // 2
    R = u_here_pl.shape[1]
    _, _, _, _, _, YXh = psi_pl.shape
    # 5 psi tiles (120 planes) + g_c (4R*6) + g_there_xyz (3R*6) +
    # g_t plane (R*6) + out (24)
    bz = block_z if block_z is not None else _pick_bz(
        Z, YXh, psi_pl.dtype, planes=144 + 48 * R)
    if Z % bz != 0:
        raise ValueError(f"block_z={bz} does not divide Z={Z}")
    nzb = Z // bz

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (4, 3, 2, 1, bz, YXh),
            lambda t, zb, dt=dt, dz=dz: (0, 0, 0, (t + dt) % T,
                                         (zb + dz) % nzb, 0))

    g_here_spec = pl.BlockSpec(
        (4, R, 3, 2, 1, bz, YXh), lambda t, zb: (0, 0, 0, 0, t, zb, 0))
    g_there_xyz_spec = pl.BlockSpec(
        (3, R, 3, 2, 1, bz, YXh), lambda t, zb: (0, 0, 0, 0, t, zb, 0))
    g_t_spec = pl.BlockSpec(
        (1, R, 3, 2, 1, bz, YXh),
        lambda t, zb: (3, 0, 0, 0, (t - 1) % T, zb, 0))
    g_z_spec = pl.BlockSpec(
        (1, R, 3, 2, 1, 1, 1, YXh),
        lambda t, zb: (0, 0, 0, 0, t, zb, 0, 0))

    g_rows_zm = _r12f_gz_rows(u_there_pl, R, T, nzb, bz, YXh)
    kernel = _make_kernel_r12f(X, bz, eo=(target_parity, Xh), T=T,
                               tb_sign=tb_sign)

    return pl.pallas_call(
        kernel,
        grid=(T, nzb),
        in_specs=[psi_spec(0, 0), psi_spec(+1, 0), psi_spec(-1, 0),
                  psi_spec(0, +1), psi_spec(0, -1),
                  g_here_spec, g_there_xyz_spec, g_t_spec, g_z_spec],
        out_specs=pl.BlockSpec((4, 3, 2, 1, bz, YXh),
                               lambda t, zb: (0, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape,
                                       out_dtype or psi_pl.dtype),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, psi_pl, psi_pl, u_here_pl, u_there_pl,
      u_there_pl, g_rows_zm)


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z",
                                             "out_dtype", "tb_sign"))
def dslash_eo_pallas_packed_r12f_mrhs(u_here_pl: jnp.ndarray,
                                      u_there_pl: jnp.ndarray,
                                      psi_pl: jnp.ndarray, dims,
                                      target_parity: int,
                                      interpret: bool = False,
                                      block_z: int | None = None,
                                      out_dtype=None,
                                      tb_sign: bool = True) -> jnp.ndarray:
    """Multi-RHS r12f hop: psi_pl (N,4,3,2,T,Z,Y*Xh); link tiles
    fetched once per (t, z-block) and shared by all N RHS."""
    from jax.experimental import pallas as pl

    T, Z, Y, X = dims
    Xh = X // 2
    R = u_here_pl.shape[1]
    N = psi_pl.shape[0]
    YXh = psi_pl.shape[-1]
    bz = block_z if block_z is not None else _pick_bz(
        Z, YXh, psi_pl.dtype, planes=144 + 48 * R)
    if Z % bz != 0:
        raise ValueError(f"block_z={bz} does not divide Z={Z}")
    nzb = Z // bz

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (1, 4, 3, 2, 1, bz, YXh),
            lambda t, zb, n, dt=dt, dz=dz: (n, 0, 0, 0, (t + dt) % T,
                                            (zb + dz) % nzb, 0))

    g_here_spec = pl.BlockSpec(
        (4, R, 3, 2, 1, bz, YXh),
        lambda t, zb, n: (0, 0, 0, 0, t, zb, 0))
    g_there_xyz_spec = pl.BlockSpec(
        (3, R, 3, 2, 1, bz, YXh),
        lambda t, zb, n: (0, 0, 0, 0, t, zb, 0))
    g_t_spec = pl.BlockSpec(
        (1, R, 3, 2, 1, bz, YXh),
        lambda t, zb, n: (3, 0, 0, 0, (t - 1) % T, zb, 0))
    g_z_spec = pl.BlockSpec(
        (1, R, 3, 2, 1, 1, 1, YXh),
        lambda t, zb, n: (0, 0, 0, 0, t, zb, 0, 0))

    g_rows_zm = _r12f_gz_rows(u_there_pl, R, T, nzb, bz, YXh)
    kernel = _mrhs_wrap(_make_kernel_r12f(X, bz,
                                          eo=(target_parity, Xh), T=T,
                                          tb_sign=tb_sign))

    return pl.pallas_call(
        kernel,
        grid=(T, nzb, N),
        in_specs=[psi_spec(0, 0), psi_spec(+1, 0), psi_spec(-1, 0),
                  psi_spec(0, +1), psi_spec(0, -1),
                  g_here_spec, g_there_xyz_spec, g_t_spec, g_z_spec],
        out_specs=pl.BlockSpec((1, 4, 3, 2, 1, bz, YXh),
                               lambda t, zb, n: (n, 0, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape,
                                       out_dtype or psi_pl.dtype),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, psi_pl, psi_pl, u_here_pl, u_there_pl,
      u_there_pl, g_rows_zm)


# -- int8 block-float resident links ----------------------------------------
#
# QUDA's quarter precision: links live in HBM as int8 mantissas with one
# f32 scale per (direction, site) (ops/blockfloat.to_int8_links) and are
# decompressed IN-KERNEL — q.astype(f32) * scale — so the link stream
# shrinks 288 -> 72+16 B/site.  Full 3-row storage (no recon on top:
# reconstructing from quantised rows would compound the quantisation
# error into the derived row).  Structure is the r12f kernel's (copy-
# free scatter backward), with each link ref paired to its scale-plane
# ref.  int8 sublane tiles are (32,128): the working set accounts f32
# planes at 8-row pads and int8 planes at 32-row pads separately
# (_pick_bz_int8), falling back to a single-buffered full block like
# the bf16 path when double-buffering cannot fit.


def _int8_link_getter(qref, sref, mu):
    """(a, b) -> (re, im) f32 link planes from an int8 mantissa ref and
    its f32 per-(direction, site) scale-plane ref."""
    pad_q = (0,) * (len(qref.shape) - 7)
    pad_s = (0,) * (len(sref.shape) - 4)
    s = sref[(mu, 0) + pad_s].astype(F32)

    def get(a, b):
        return (qref[(mu, a, b, 0, 0) + pad_q].astype(F32) * s,
                qref[(mu, a, b, 1, 0) + pad_q].astype(F32) * s)

    return get


def _make_kernel_int8(X: int, bz: int, eo: tuple):
    """int8-links kernel over one (t, z-block) tile (eo only).  Ref
    shapes (q = int8 mantissas, s = f32 scales):
      psi_c/tp/tm/zp/zm: (4, 3, 2, 1, bz, YX)  whole tiles (v2 gather)
      q_c / s_c:         (4, 3, 3, 2, 1, bz, YX) / (4, 1, bz, YX)
      q_there / s_there: (3, 3, 3, 2, 1, bz, YX) / (3, 1, bz, YX)
      q_t_tm / s_t_tm:   (1, 3, 3, 2, 1, bz, YX) / (1, 1, bz, YX)
      q_z_zm / s_z_zm:   (1, 3, 3, 2, 1, 1, 1, YX) / (1, 1, 1, 1, YX)
    Decompression happens at link load; backward hops shift the product
    AFTER the scale multiply, so each site's links use its own scale.
    t-boundary signs need no special casing: the folded phase lives in
    the stored rows (sign survives quantisation exactly)."""
    from jax.experimental import pallas as pl

    def kernel(*refs):
        (psi_c, psi_tp, psi_tm, psi_zp, psi_zm,
         q_c, s_c, q_there, s_there, q_t_tm, s_t_tm, q_z_zm, s_z_zm,
         out_ref) = refs
        parity, Xh = eo
        t_id = pl.program_id(0)
        zb_id = pl.program_id(1)
        shape = psi_c.shape[-2:]
        z = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + zb_id * bz
        y = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // Xh
        mask_r0 = ((t_id + z + y + parity) % 2) == 0

        def shift_x(v, sign):
            return _shift_x_eo(v, sign, Xh, mask_r0)

        def psi_at(ref, s, c):
            return (ref[s, c, 0, 0].astype(F32),
                    ref[s, c, 1, 0].astype(F32))

        def psi_row(ref, s, c, rows):
            return (ref[s, c, 0, 0][rows].astype(F32),
                    ref[s, c, 1, 0][rows].astype(F32))

        acc = [[(jnp.zeros(shape, F32), jnp.zeros(shape, F32))
                for _ in range(3)] for _ in range(4)]

        for mu in (0, 1):
            tf = TABLES[(mu, +1)]
            h = _project(lambda s, c: psi_at(psi_c, s, c), tf)
            if mu == 0:
                h = [[shift_x(h[a][c], +1) for c in range(3)]
                     for a in (0, 1)]
            else:
                h = [[_shift_xy(h[a][c], 1, +1, Xh)
                      for c in range(3)] for a in (0, 1)]
            _recon_acc(acc, _color_mul(h, _int8_link_getter(q_c, s_c, mu),
                                       False), tf)

            tb = TABLES[(mu, -1)]
            h = _project(lambda s, c: psi_at(psi_c, s, c), tb)
            uh = _color_mul(h, _int8_link_getter(q_there, s_there, mu),
                            True)
            if mu == 0:
                uh = [[shift_x(uh[a][c], -1) for c in range(3)]
                      for a in (0, 1)]
            else:
                uh = [[_shift_xy(uh[a][c], 1, -1, Xh)
                       for c in range(3)] for a in (0, 1)]
            _recon_acc(acc, uh, tb)

        tf = TABLES[(2, +1)]
        h = _project(lambda s, c: psi_at(psi_c, s, c), tf)
        h_row = _project(lambda s, c: psi_row(psi_zp, s, c, slice(0, 1)),
                         tf)
        h = [[_shift_z(h[a][c], h_row[a][c], +1) for c in range(3)]
             for a in (0, 1)]
        _recon_acc(acc, _color_mul(h, _int8_link_getter(q_c, s_c, 2),
                                   False), tf)

        tb = TABLES[(2, -1)]
        h = _project(lambda s, c: psi_at(psi_c, s, c), tb)
        uh = _color_mul(h, _int8_link_getter(q_there, s_there, 2), True)
        h_b = _project(lambda s, c: psi_row(psi_zm, s, c,
                                            slice(-1, None)), tb)
        uh_b = _color_mul(h_b, _int8_link_getter(q_z_zm, s_z_zm, 0), True)
        uh = [[_shift_z(uh[a][c], uh_b[a][c], -1) for c in range(3)]
              for a in (0, 1)]
        _recon_acc(acc, uh, tb)

        tf = TABLES[(3, +1)]
        h = _project(lambda s, c: psi_at(psi_tp, s, c), tf)
        _recon_acc(acc, _color_mul(h, _int8_link_getter(q_c, s_c, 3),
                                   False), tf)
        tb = TABLES[(3, -1)]
        h = _project(lambda s, c: psi_at(psi_tm, s, c), tb)
        _recon_acc(acc, _color_mul(h, _int8_link_getter(q_t_tm, s_t_tm, 0),
                                   True), tb)

        odt = out_ref.dtype
        for s in range(4):
            for c in range(3):
                out_ref[s, c, 0, 0] = acc[s][c][0].astype(odt)
                out_ref[s, c, 1, 0] = acc[s][c][1].astype(odt)

    return kernel


def _pick_bz_int8(Z: int, YX: int,
                  vmem_knob: str = "QUDA_TPU_PALLAS_VMEM_MB") -> int:
    """z-block pick for the int8-links kernel: MIXED dtype accounting.
    f32 planes (5 psi + out = 144, + 8 scale planes) pad to 8 sublane
    rows; int8 planes (q_c 72 + q_there 54 + q_t 18 = 144) pad to 32 —
    an int8 bz=8 block really occupies a quarter-full (32,128) tile, so
    candidates are ranked by int8-tile utilisation.  Falls back to a
    single-buffered bz=Z block under the scoped-VMEM window when
    double-buffering cannot fit (the bf16 full-tile admission rule)."""
    f32_planes, int8_planes, scale_planes = 144, 144, 8
    yx_pad = -(-YX // 128) * 128
    from ..utils import config as qconf
    budget = int(float(qconf.get(vmem_knob, fresh=True)) * 2 ** 20)

    def working_set(bz):
        pad8 = -(-bz // 8) * 8
        pad32 = -(-bz // 32) * 32
        return ((f32_planes + scale_planes) * pad8 * yx_pad * 4
                + int8_planes * pad32 * yx_pad)

    fitting = []
    for bz in sorted({d for d in range(1, Z + 1) if Z % d == 0}):
        if bz % 8 != 0 and bz != Z:
            continue
        if working_set(bz) <= budget:
            fitting.append((bz / (-(-bz // 32) * 32), bz))
    single_buffered = False
    if not fitting:
        from ..obs import memory as omem
        if working_set(Z) <= int(omem.SCOPED_VMEM_MB * 2 ** 20):
            fitting.append((Z / (-(-Z // 32) * 32), Z))
            single_buffered = True
    if not fitting:
        raise ValueError(
            f"no z-block of Z={Z} fits the VMEM budget at YX={YX} for "
            "the int8-links kernel; fall back to the XLA decompress "
            "path for this operator")
    _, bz = max(fitting)
    try:
        from ..obs import memory as omem
        omem.vmem_audit(vmem_knob, working_set(bz), budget, bz=bz,
                        single_buffered=single_buffered)
    except Exception:
        pass
    return bz


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z",
                                             "out_dtype"))
def dslash_eo_pallas_packed_int8(q_here, s_here, q_there, s_there,
                                 psi_pl: jnp.ndarray, dims,
                                 target_parity: int,
                                 interpret: bool = False,
                                 block_z: int | None = None,
                                 out_dtype=None) -> jnp.ndarray:
    """Checkerboarded Wilson hop with int8 block-float resident links.

    q_here/q_there: (4,3,3,2,T,Z,Y*Xh) int8 mantissas at the target /
    opposite parity; s_here/s_there: (4,T,Z,Y*Xh) f32 per-(direction,
    site) scales (see ops/blockfloat.to_int8_links); psi_pl:
    (4,3,2,T,Z,Y*Xh) parity-(1-p) spinor.  Matches the XLA operator
    built from from_int8_links(q, s) exactly (same decompressed floats,
    same hop algebra)."""
    from jax.experimental import pallas as pl

    T, Z, Y, X = dims
    Xh = X // 2
    _, _, _, _, _, YXh = psi_pl.shape
    bz = block_z if block_z is not None else _pick_bz_int8(Z, YXh)
    if Z % bz != 0:
        raise ValueError(f"block_z={bz} does not divide Z={Z}")
    nzb = Z // bz

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (4, 3, 2, 1, bz, YXh),
            lambda t, zb, dt=dt, dz=dz: (0, 0, 0, (t + dt) % T,
                                         (zb + dz) % nzb, 0))

    q_here_spec = pl.BlockSpec(
        (4, 3, 3, 2, 1, bz, YXh), lambda t, zb: (0, 0, 0, 0, t, zb, 0))
    s_here_spec = pl.BlockSpec(
        (4, 1, bz, YXh), lambda t, zb: (0, t, zb, 0))
    q_there_spec = pl.BlockSpec(
        (3, 3, 3, 2, 1, bz, YXh), lambda t, zb: (0, 0, 0, 0, t, zb, 0))
    s_there_spec = pl.BlockSpec(
        (3, 1, bz, YXh), lambda t, zb: (0, t, zb, 0))
    q_t_spec = pl.BlockSpec(
        (1, 3, 3, 2, 1, bz, YXh),
        lambda t, zb: (3, 0, 0, 0, (t - 1) % T, zb, 0))
    s_t_spec = pl.BlockSpec(
        (1, 1, bz, YXh), lambda t, zb: (3, (t - 1) % T, zb, 0))
    q_z_spec = pl.BlockSpec(
        (1, 3, 3, 2, 1, 1, 1, YXh),
        lambda t, zb: (0, 0, 0, 0, t, zb, 0, 0))
    s_z_spec = pl.BlockSpec(
        (1, 1, 1, 1, YXh), lambda t, zb: (0, t, zb, 0, 0))

    # pre-gathered z-1 boundary rows of the opposite-parity U_z mantissa
    # and scale planes (block extent 1 == array extent; _r12f_gz_rows)
    q_r = q_there[2:3].reshape(1, 3, 3, 2, T, nzb, bz, YXh)
    q_rows_zm = jnp.roll(q_r[:, :, :, :, :, :, bz - 1, :], 1,
                         axis=5)[:, :, :, :, :, :, None, :]
    s_r = s_there[2:3].reshape(1, T, nzb, bz, YXh)
    s_rows_zm = jnp.roll(s_r[:, :, :, bz - 1, :], 1,
                         axis=2)[:, :, :, None, :]

    kernel = _make_kernel_int8(X, bz, eo=(target_parity, Xh))

    return pl.pallas_call(
        kernel,
        grid=(T, nzb),
        in_specs=[psi_spec(0, 0), psi_spec(+1, 0), psi_spec(-1, 0),
                  psi_spec(0, +1), psi_spec(0, -1),
                  q_here_spec, s_here_spec, q_there_spec, s_there_spec,
                  q_t_spec, s_t_spec, q_z_spec, s_z_spec],
        out_specs=pl.BlockSpec((4, 3, 2, 1, bz, YXh),
                               lambda t, zb: (0, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape,
                                       out_dtype or psi_pl.dtype),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, psi_pl, psi_pl,
      q_here, s_here, q_there, s_there,
      q_there, s_there, q_rows_zm, s_rows_zm)
