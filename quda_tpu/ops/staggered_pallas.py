"""Pallas TPU staggered / improved-staggered dslash on the packed pair
layout — the hand-tuned hot path for the second headline family.

Reference behavior: include/kernels/dslash_staggered.cuh (fat 1-hop +
Naik long 3-hop, phases folded into the links).  Same design as the
Wilson kernel (ops/wilson_pallas_packed.py): grid (T, Z/BZ), (BZ, Y*X)
vector tiles, re/im-pair arithmetic, pre-shifted backward links
computed once per link load so the kernel does zero in-kernel link
shifts.  Staggered has no spin structure, so each hop is a bare 3x3
color multiply of the shifted color planes:

    out = sum_mu 0.5 * [ U_mu(x) psi(x+n mu) - U_mu(x-n mu)^dag psi(x-n mu) ]

The fat (nhop=1) and long (nhop=3) hop sets run as SEPARATE pallas
calls summed in XLA: together their working set (9 psi neighbour tiles
+ 4 link tiles) busts the VMEM budget at useful block sizes, while each
pass alone (5 psi tiles + 2 link tiles, 180 planes) fits comfortably —
and the extra psi re-read costs only 24 B/site against 576 B/site of
links.

Layouts:  psi (3, 2, T, Z, Y*X); links (4, 3, 3, 2, T, Z, Y*X).
A 3-hop z shift splices three boundary rows from the single adjacent
z-block tile, so the long pass requires BZ >= 3 (or one z-block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .wilson_pallas_packed import (_cadd, _cmul, _cmul_conj, _pick_bz,
                                   _shift_xy)

F32 = jnp.float32

# Per-kernel VMEM budget: the staggered family picks z-blocks against
# its OWN knob (raised default) while the Wilson kernels keep the proven
# 6 MB default.
_STAG_VMEM_KNOB = "QUDA_TPU_PALLAS_VMEM_MB_STAGGERED"


def _check_long_bz(Z: int, bz: int, with_long: bool, where: str):
    """Loud failure instead of silent corruption: the Naik 3-hop z
    splice reads its boundary rows from the SINGLE adjacent z-block, so
    a multi-block launch needs bz >= 3 (bz = Z reduces every z shift to
    an in-tile periodic roll and is always safe).  Checked at every
    entry point so an explicit ``block_z`` cannot bypass it."""
    if with_long and Z // bz > 1 and bz < 3:
        raise ValueError(
            f"{where}: block_z={bz} is illegal for the Naik 3-hop z "
            f"splice (needs block_z >= 3, or one z-block block_z={Z}): "
            "the splice only reaches the adjacent z-block, so 0 < bz < "
            "3 would silently corrupt the long-hop boundary rows")


def backward_links(links_pl: jnp.ndarray, X: int, nhop: int) -> jnp.ndarray:
    """Pre-shifted backward links: out[mu](x) = U_mu(x - nhop*mu), on the
    pair layout (4,3,3,2,T,Z,YX).  Computed once per link load
    (KS fat/long residency), like wilson_pallas_packed.backward_gauge."""
    from .wilson_packed import shift_packed
    Y = links_pl.shape[-1] // X
    return jnp.stack([shift_packed(links_pl[mu], mu, -1, X, Y, nhop)
                      for mu in range(4)])


def _shift_z_n(v, v_nb, sign: int, nhop: int):
    """z shift by nhop rows, splicing nhop boundary rows from the
    neighbouring z-block tile ``v_nb`` (requires nhop <= BZ)."""
    bz = v[0].shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, v[0].shape, 0)
    out = []
    if sign > 0:
        for c, n in zip(v, v_nb):
            spliced = jnp.roll(n, -nhop, axis=0)  # rows 0..nhop-1 -> tail
            out.append(jnp.where(row >= bz - nhop, spliced,
                                 jnp.roll(c, -nhop, axis=0)))
    else:
        for c, n in zip(v, v_nb):
            spliced = jnp.roll(n, nhop, axis=0)   # last nhop rows -> head
            out.append(jnp.where(row < nhop, spliced,
                                 jnp.roll(c, nhop, axis=0)))
    return tuple(out)


def _shift_x_eo_n(v, sign: int, Xh: int, mask_r0, nhop: int):
    """Checkerboarded x shift by nhop sites on a (BZ, Y*Xh) tile —
    in-kernel analog of wilson_packed.shift_eo_packed's x case: even
    hops are pure xh-slot rolls, odd hops add one slot-parity flip."""
    if nhop % 2 == 0:
        return _shift_xy(v, 0, sign, Xh, nhop // 2) if nhop else v
    k = (nhop - 1) // 2
    base = _shift_xy(v, 0, sign, Xh, k) if k else v
    moved = _shift_xy(base, 0, sign, Xh, 1)
    if sign > 0:
        return tuple(jnp.where(mask_r0, b, m) for b, m in zip(base, moved))
    return tuple(jnp.where(mask_r0, m, b) for b, m in zip(base, moved))


def _make_stag_kernel(X: int, nhop: int, bz: int, eo: tuple | None = None):
    """One hop-set pass over a (t, z-block) tile.  Ref shapes:
      psi refs:   (3, 2, 1, BZ, YX) x5 (central, t+n, t-n, z+n, z-n)
      u / u_bw:   (4, 3, 3, 2, 1, BZ, YX)
    With ``eo = (target_parity, Xh)`` the tile is a checkerboarded half
    lattice: x shifts use the slot-parity select, u is the target-parity
    forward links and u_bw the pre-shifted opposite-parity backward
    links (backward_links_eo).
    """
    from jax.experimental import pallas as pl

    def kernel(psi_c, psi_tp, psi_tm, psi_zp, psi_zm, u, u_bw, out_ref):
        def psi_at(ref, c):
            return (ref[c, 0, 0].astype(F32), ref[c, 1, 0].astype(F32))

        if eo is not None:
            parity, Xh = eo
            t_id = pl.program_id(0)
            zb_id = pl.program_id(1)
            shape = psi_c.shape[-2:]
            z = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                 + zb_id * bz)
            y = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // Xh
            mask_r0 = ((t_id + z + y + parity) % 2) == 0

        def shift_x(v, sign):
            if eo is None:
                return _shift_xy(v, 0, sign, X, nhop)
            return _shift_x_eo_n(v, sign, eo[1], mask_r0, nhop)

        def shift_y(v, sign):
            return _shift_xy(v, 1, sign, X if eo is None else eo[1],
                             nhop)

        def link(ref, mu, a, b):
            return (ref[mu, a, b, 0, 0].astype(F32),
                    ref[mu, a, b, 1, 0].astype(F32))

        acc = [(jnp.zeros(psi_c.shape[-2:], F32),
                jnp.zeros(psi_c.shape[-2:], F32)) for _ in range(3)]

        def hop(get_psi, mu, adjoint):
            gref = u_bw if adjoint else u
            for a in range(3):
                term = None
                for b in range(3):
                    m = (_cmul_conj(link(gref, mu, b, a), get_psi(b))
                         if adjoint else
                         _cmul(link(gref, mu, a, b), get_psi(b)))
                    term = m if term is None else _cadd(term, m)
                s = -0.5 if adjoint else 0.5
                acc[a] = (acc[a][0] + s * term[0],
                          acc[a][1] + s * term[1])

        # x, y: in-plane lane shifts of the central tile
        for sign, adjoint in ((+1, False), (-1, True)):
            hop(lambda c, sign=sign: shift_x(psi_at(psi_c, c), sign),
                0, adjoint)
            hop(lambda c, sign=sign: shift_y(psi_at(psi_c, c), sign),
                1, adjoint)
        # z: roll + nhop-row splice from the neighbour z-block tile
        hop(lambda c: _shift_z_n(psi_at(psi_c, c), psi_at(psi_zp, c),
                                 +1, nhop), 2, False)
        hop(lambda c: _shift_z_n(psi_at(psi_c, c), psi_at(psi_zm, c),
                                 -1, nhop), 2, True)
        # t: whole neighbour tiles via the index map
        hop(lambda c: psi_at(psi_tp, c), 3, False)
        hop(lambda c: psi_at(psi_tm, c), 3, True)

        odt = out_ref.dtype
        for c in range(3):
            out_ref[c, 0, 0] = acc[c][0].astype(odt)
            out_ref[c, 1, 0] = acc[c][1].astype(odt)

    return kernel


# working set per pass: 5 psi tiles (6 planes) + u + u_bw (72 each) +
# out (6) = 180 planes
_STAG_PLANES = 180


def _stag_pass(links_pl, links_bw_pl, psi_pl, X, nhop, bz, interpret,
               eo=None):
    from jax.experimental import pallas as pl

    _, _, T, Z, YX = psi_pl.shape
    nzb = Z // bz
    if nzb > 1 and bz < nhop:
        raise ValueError(
            f"block_z={bz} < nhop={nhop}: the z splice only reaches the "
            "adjacent z-block")

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (3, 2, 1, bz, YX),
            lambda t, zb, dt=dt, dz=dz: (0, 0, (t + dt) % T,
                                         (zb + dz) % nzb, 0))

    links_spec = pl.BlockSpec(
        (4, 3, 3, 2, 1, bz, YX), lambda t, zb: (0, 0, 0, 0, t, zb, 0))

    return pl.pallas_call(
        _make_stag_kernel(X, nhop, bz, eo),
        grid=(T, nzb),
        in_specs=[psi_spec(0, 0), psi_spec(+nhop, 0), psi_spec(-nhop, 0),
                  psi_spec(0, +1), psi_spec(0, -1), links_spec,
                  links_spec],
        out_specs=pl.BlockSpec((3, 2, 1, bz, YX),
                               lambda t, zb: (0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape, jnp.float32),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, psi_pl, psi_pl, links_pl, links_bw_pl)


@functools.partial(jax.jit, static_argnames=("X", "interpret", "block_z",
                                             "out_dtype"))
def dslash_staggered_pallas(fat_pl: jnp.ndarray, fat_bw_pl: jnp.ndarray,
                            psi_pl: jnp.ndarray, X: int,
                            long_pl: jnp.ndarray = None,
                            long_bw_pl: jnp.ndarray = None,
                            interpret: bool = False,
                            block_z: int | None = None,
                            out_dtype=None) -> jnp.ndarray:
    """Staggered (fat-only) or improved-staggered (fat+long) D psi on
    pallas-layout pair arrays; matches
    staggered_packed.dslash_staggered_packed_pairs.

    fat_pl/long_pl: (4,3,3,2,T,Z,YX) with phases folded; the _bw arrays
    are from ``backward_links`` (computed once per KS-link load —
    keep them out of solver loops, see PERF.md).  psi_pl: (3,2,T,Z,YX).
    """
    _, _, _, Z, YX = psi_pl.shape
    if block_z is not None:
        bz = block_z
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    else:
        bz = _pick_bz(Z, YX, psi_pl.dtype, planes=_STAG_PLANES,
                      min_bz=3 if (long_pl is not None and Z > 3) else 1,
                      vmem_knob=_STAG_VMEM_KNOB)
    _check_long_bz(Z, bz, long_pl is not None, "dslash_staggered_pallas")

    out = _stag_pass(fat_pl, fat_bw_pl, psi_pl, X, 1, bz, interpret)
    if long_pl is not None:
        out = out + _stag_pass(long_pl, long_bw_pl, psi_pl, X, 3, bz,
                               interpret)
    odt = out_dtype or psi_pl.dtype
    return out.astype(odt)


# -- v3: scatter-form backward hops (no backward-links copy) ----------------
#
# Same restructuring as wilson_pallas_packed v3: the backward hop
#     -0.5 U_mu(x-n mu)^dag psi(x-n mu)  =  m(x-n mu),
#     m(y) := -0.5 U_mu(y)^dag psi(y),
# is computed pointwise with the ALREADY-LOADED forward links and the
# product (3 color pairs) is shifted by -n mu — the pre-shifted
# backward-links array (288 B/site of reads + a resident copy PER HOP
# SET, so 576 B/site for improved staggered) disappears.  Boundary data:
# psi z-neighbours shrink from whole (bz, YX) tiles to nhop-row blocks,
# backward-t reads the U_t plane at t-nhop and psi at t-nhop directly,
# and the backward-z boundary product is built from nhop-row psi/U_z
# inputs.  Per-site traffic per pass drops from ~744 B to ~460 B.
#
# The nhop-row z inputs block the z axis in units of nhop, so the long
# pass (nhop=3) needs bz % 3 == 0 (checked; `_pick_bz_v3` below).


def _splice_z(v, rows, sign: int, nhop: int):
    """Shift a (BZ, YX) tile by nhop rows, splicing the nhop-row block
    ``rows`` in at the wrapping edge (sign>0: rows are the NEXT block's
    first nhop rows; sign<0: the PREVIOUS block's last nhop rows)."""
    out = []
    for c, r in zip(v, rows):
        if sign > 0:
            out.append(jnp.concatenate([c[nhop:], r], axis=0))
        else:
            out.append(jnp.concatenate([r, c[:c.shape[0] - nhop]], axis=0))
    return tuple(out)


def _psi_at(ref, c):
    """(re, im) f32 color planes from a psi ref.  Center blocks are
    (3,2,1,bz,YX); boundary-ROW inputs carry one extra singleton z axis
    (3,2,1,1,nhop,YX) — an nhop-extent block on the sublane axis of a
    Z-extent array is illegal on hardware, so rows arrive as separate
    arrays whose z extent IS nhop (block == dim is legal)."""
    pad = (0,) * (len(ref.shape) - 5)
    return (ref[(c, 0, 0) + pad].astype(F32),
            ref[(c, 1, 0) + pad].astype(F32))


def _link_at(ref, mu, a, b):
    """(re, im) f32 link-element planes from a link ref (pad-aware like
    _psi_at: boundary-row link inputs carry a singleton z axis)."""
    pad = (0,) * (len(ref.shape) - 7)
    return (ref[(mu, a, b, 0, 0) + pad].astype(F32),
            ref[(mu, a, b, 1, 0) + pad].astype(F32))


def _stag_link(ref, mu):
    """(a, b) -> (re, im) accessor of direction ``mu``'s stored link
    elements (full R=3 storage: fat links are smeared sums, never
    reconstructable, and the Naik links are served as stored)."""
    return lambda a, b: _link_at(ref, mu, a, b)


def _mul3(get_psi, get_link, adjoint, scale):
    """out[a] = scale * sum_b op(U)_ab psi_b as a list of 3 color pairs
    (no accumulate)."""
    res = []
    for a in range(3):
        term = None
        for b in range(3):
            m = (_cmul_conj(get_link(b, a), get_psi(b))
                 if adjoint else _cmul(get_link(a, b), get_psi(b)))
            term = m if term is None else _cadd(term, m)
        res.append((scale * term[0], scale * term[1]))
    return res


def _accumulate_hopset(acc, psi_c, psi_tp, psi_tm, psi_zp, psi_zm,
                       u, u_bwd, u_t_tm, u_z_zm, nhop: int,
                       shift_x, shift_y, single_zb: bool):
    """One scatter-form hop set (all 8 hops of one nhop) accumulated
    into ``acc`` (list of 3 f32 color pairs, mutated in place): the
    staggered scatter-form hop algebra, run once per launch by the v3
    passes.

    ``u_bwd`` supplies the backward x/y/z links (the forward array, or
    the opposite-parity array for the checkerboarded variant); ``u_t_tm``
    is the U_t plane at t-nhop; ``u_z_zm`` the U_z boundary rows at
    z-nhop (unread when ``single_zb``)."""
    def acc_add(vals):
        for a in range(3):
            acc[a] = _cadd(acc[a], vals[a])

    # x, y: forward = shift psi then multiply; backward = multiply
    # with LOCAL links then shift the product
    for mu, shifter in ((0, shift_x), (1, shift_y)):
        acc_add(_mul3(lambda c: shifter(_psi_at(psi_c, c), +1),
                      _stag_link(u, mu), False, 0.5))
        m = _mul3(lambda c: _psi_at(psi_c, c),
                  _stag_link(u_bwd, mu), True, -0.5)
        acc_add([shifter(mc, -1) for mc in m])

    # z forward: nhop-row splice of the shifted central tile (a pure
    # in-tile roll when the block covers the whole Z axis)
    if single_zb:
        acc_add(_mul3(
            lambda c: tuple(jnp.roll(p, -nhop, axis=0)
                            for p in _psi_at(psi_c, c)),
            _stag_link(u, 2), False, 0.5))
        m = _mul3(lambda c: _psi_at(psi_c, c),
                  _stag_link(u_bwd, 2), True, -0.5)
        acc_add([tuple(jnp.roll(p, nhop, axis=0) for p in mc)
                 for mc in m])
    else:
        acc_add(_mul3(lambda c: _splice_z(_psi_at(psi_c, c),
                                          _psi_at(psi_zp, c), +1, nhop),
                      _stag_link(u, 2), False, 0.5))
        # z backward: local product shifted down, boundary rows
        # built from the z-nhop psi/U_z row inputs
        m = _mul3(lambda c: _psi_at(psi_c, c),
                  _stag_link(u_bwd, 2), True, -0.5)
        m_b = _mul3(lambda c: _psi_at(psi_zm, c),
                    _stag_link(u_z_zm, 0), True, -0.5)
        acc_add([_splice_z(mc, mbc, -1, nhop)
                 for mc, mbc in zip(m, m_b)])

    # t: whole neighbour planes, no shift
    acc_add(_mul3(lambda c: _psi_at(psi_tp, c),
                  _stag_link(u, 3), False, 0.5))
    acc_add(_mul3(lambda c: _psi_at(psi_tm, c),
                  _stag_link(u_t_tm, 0), True, -0.5))


def _eo_mask_r0(pl, psi_c, bz, eo):
    """The checkerboard x-slot parity mask from the grid position (the
    first two grid axes are (t, z-block) in every staggered launch)."""
    parity, Xh = eo
    t_id = pl.program_id(0)
    zb_id = pl.program_id(1)
    shape = psi_c.shape[-2:]
    z = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + zb_id * bz
    y = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // Xh
    return ((t_id + z + y + parity) % 2) == 0


def _make_shifts(X: int, nhop: int, eo, mask_r0):
    """(shift_x, shift_y) closures for one hop count."""
    def shift_x(v, sign):
        if eo is None:
            return _shift_xy(v, 0, sign, X, nhop)
        return _shift_x_eo_n(v, sign, eo[1], mask_r0, nhop)

    def shift_y(v, sign):
        return _shift_xy(v, 1, sign, X if eo is None else eo[1], nhop)
    return shift_x, shift_y


def _make_stag_kernel_v3(X: int, nhop: int, bz: int,
                         eo: tuple | None = None,
                         single_zb: bool = False):
    """v3 hop-set pass.  Ref shapes:
      psi_c/tp/tm:   (3, 2, 1, bz, YX)
      psi_zp/zm:     (3, 2, 1, nhop, YX)   boundary row blocks
      u:             (4, 3, 3, 2, 1, bz, YX)  forward links
      u_t_tm:        (1, 3, 3, 2, 1, bz, YX)  U_t plane at t-nhop
      u_z_zm:        (1, 3, 3, 2, 1, nhop, YX) U_z rows at z-nhop
    With ``eo`` the backward links live on the opposite parity, carried
    by an extra u_there_xyz ref (odd nhop: both fat and Naik hops flip
    parity)."""
    from jax.experimental import pallas as pl

    def kernel(*refs):
        if eo is None:
            (psi_c, psi_tp, psi_tm, psi_zp, psi_zm,
             u, u_t_tm, u_z_zm, out_ref) = refs
            u_bwd = u
            mask_r0 = None
        else:
            (psi_c, psi_tp, psi_tm, psi_zp, psi_zm,
             u, u_there_xyz, u_t_tm, u_z_zm, out_ref) = refs
            u_bwd = u_there_xyz
            mask_r0 = _eo_mask_r0(pl, psi_c, bz, eo)

        shift_x, shift_y = _make_shifts(X, nhop, eo, mask_r0)

        acc = [(jnp.zeros(psi_c.shape[-2:], F32),
                jnp.zeros(psi_c.shape[-2:], F32)) for _ in range(3)]
        _accumulate_hopset(acc, psi_c, psi_tp, psi_tm, psi_zp, psi_zm,
                           u, u_bwd, u_t_tm, u_z_zm, nhop,
                           shift_x, shift_y, single_zb)

        odt = out_ref.dtype
        for c in range(3):
            out_ref[c, 0, 0] = acc[c][0].astype(odt)
            out_ref[c, 1, 0] = acc[c][1].astype(odt)

    return kernel


# v3 working set per pass: 3 psi tiles (6 planes) + u (72) + u_t plane
# (18) + out (6) = 114 bz-row planes (+ tiny nhop-row inputs); the EO
# variant carries an extra u_there_xyz ref (54 planes) -> 168
_STAG_PLANES_V3 = 120
_STAG_PLANES_V3_EO = 174


def _stag_pass_v3(links_pl, psi_pl, X, nhop, bz, interpret, eo=None,
                  links_there_pl=None):
    from jax.experimental import pallas as pl

    _, _, T, Z, YX = psi_pl.shape
    nzb = Z // bz
    if nzb > 1 and bz % nhop != 0:
        raise ValueError(
            f"block_z={bz} not a multiple of nhop={nhop}: the nhop-row "
            "z boundary inputs must align to row-block boundaries")

    def psi_spec(dt):
        return pl.BlockSpec(
            (3, 2, 1, bz, YX),
            lambda t, zb, dt=dt: (0, 0, (t + dt) % T, zb, 0))

    # Boundary z-rows as separate pre-gathered arrays whose z extent IS
    # nhop: an nhop-extent block on the sublane axis of a Z-extent array
    # is illegal on hardware (second-to-minor block extent must divide
    # by 8 or equal the array's), while block nhop == array extent nhop
    # is legal.  With a single z-block the kernel uses in-tile rolls and
    # the row refs are unread — pass minimal dummies (Z may not divide
    # nhop there).
    bwd_src = links_pl if links_there_pl is None else links_there_pl
    if nzb == 1:
        rows_zp = rows_zm = jnp.zeros((3, 2, T, 1, nhop, YX),
                                      psi_pl.dtype)
        u_rows_zm = jnp.zeros((1, 3, 3, 2, T, 1, nhop, YX),
                              bwd_src.dtype)
    else:
        q = bz // nhop
        psi_q = psi_pl.reshape(3, 2, T, nzb, q, nhop, YX)
        rows_zp = jnp.roll(psi_q[:, :, :, :, 0], -1, axis=3)
        rows_zm = jnp.roll(psi_q[:, :, :, :, q - 1], 1, axis=3)
        u_q = bwd_src[2:3].reshape(1, 3, 3, 2, T, nzb, q, nhop, YX)
        u_rows_zm = jnp.roll(u_q[:, :, :, :, :, :, q - 1], 1, axis=5)

    def psi_row_spec():
        return pl.BlockSpec((3, 2, 1, 1, nhop, YX),
                            lambda t, zb: (0, 0, t, zb, 0, 0))

    links_spec = pl.BlockSpec(
        (4, 3, 3, 2, 1, bz, YX), lambda t, zb: (0, 0, 0, 0, t, zb, 0))
    links_xyz_spec = pl.BlockSpec(
        (3, 3, 3, 2, 1, bz, YX), lambda t, zb: (0, 0, 0, 0, t, zb, 0))
    u_t_spec = pl.BlockSpec(
        (1, 3, 3, 2, 1, bz, YX),
        lambda t, zb: (3, 0, 0, 0, (t - nhop) % T, zb, 0))
    u_z_spec = pl.BlockSpec(
        (1, 3, 3, 2, 1, 1, nhop, YX),
        lambda t, zb: (0, 0, 0, 0, t, zb, 0, 0))

    in_specs = [psi_spec(0), psi_spec(+nhop), psi_spec(-nhop),
                psi_row_spec(), psi_row_spec(), links_spec]
    args = [psi_pl, psi_pl, psi_pl, rows_zp, rows_zm, links_pl]
    if links_there_pl is not None:
        in_specs.append(links_xyz_spec)
        args.append(links_there_pl)
    in_specs += [u_t_spec, u_z_spec]
    args += [bwd_src, u_rows_zm]

    return pl.pallas_call(
        _make_stag_kernel_v3(X, nhop, bz, eo, single_zb=(nzb == 1)),
        grid=(T, nzb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((3, 2, 1, bz, YX),
                               lambda t, zb: (0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape, jnp.float32),
        interpret=interpret,
    )(*args)


def _require_naik_z(Z: int, with_long: bool):
    """The Naik pass declares 3-row boundary BlockSpecs; on a Z < 3 axis
    those exceed the array dim (and a 3-hop on extent < 3 aliases to a
    shorter hop) — reject clearly instead of letting pallas fail
    opaquely.  The XLA stencil path serves such degenerate lattices.
    Checked in the entry points too so an explicit block_z cannot bypass
    it."""
    if with_long and Z < 3:
        raise ValueError(
            f"improved-staggered v3 pallas kernel needs Z >= 3 for the "
            f"3-hop Naik boundary rows; got Z={Z} (use the XLA stencil "
            f"path for degenerate extents)")


def _pick_bz_v3(Z, YX, dtype, with_long: bool, eo: bool = False):
    """z-block for the v3 passes: multiple of 3 when the Naik pass runs
    (so its 3-row boundary inputs align to block boundaries)."""
    planes = _STAG_PLANES_V3_EO if eo else _STAG_PLANES_V3
    _require_naik_z(Z, with_long)
    bz = _pick_bz(Z, YX, dtype, planes=planes,
                  min_bz=3 if (with_long and Z > 3) else 1,
                  vmem_knob=_STAG_VMEM_KNOB)
    if with_long and bz != Z and bz % 3 != 0:
        # Naik boundary inputs need bz % 3 == 0 (or a single z-block);
        # candidates must ALSO satisfy the hardware block-legality rule
        # (divide by 8 or equal Z — same filter as _pick_bz, else this
        # fallback reintroduces the illegal-block compile failure)
        cands = [d for d in range(3, bz + 1)
                 if Z % d == 0 and d % 3 == 0
                 and (d % 8 == 0 or d == Z)]
        if cands:
            bz = max(cands)
        else:
            # fall back to the whole-Z block; _pick_bz re-checks VMEM
            bz = _pick_bz(Z, YX, dtype, planes=planes, min_bz=Z,
                          vmem_knob=_STAG_VMEM_KNOB)
    return bz


@functools.partial(jax.jit, static_argnames=("X", "interpret", "block_z",
                                             "out_dtype"))
def dslash_staggered_pallas_v3(fat_pl: jnp.ndarray, psi_pl: jnp.ndarray,
                               X: int, long_pl: jnp.ndarray = None,
                               interpret: bool = False,
                               block_z: int | None = None,
                               out_dtype=None) -> jnp.ndarray:
    """Staggered / improved-staggered D psi, v3: scatter-form backward
    hops — no ``backward_links`` precompute or resident copies (saves
    576 B/site of HBM reads for the improved operator)."""
    _, _, _, Z, YX = psi_pl.shape
    _require_naik_z(Z, long_pl is not None)
    if block_z is not None:
        bz = block_z
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    else:
        bz = _pick_bz_v3(Z, YX, psi_pl.dtype, long_pl is not None)

    out = _stag_pass_v3(fat_pl, psi_pl, X, 1, bz, interpret)
    if long_pl is not None:
        out = out + _stag_pass_v3(long_pl, psi_pl, X, 3, bz, interpret)
    odt = out_dtype or psi_pl.dtype
    return out.astype(odt)


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z",
                                             "out_dtype"))
def dslash_staggered_eo_pallas_v3(fat_here_pl, fat_there_pl, psi_pl, dims,
                                  target_parity: int,
                                  long_here_pl=None, long_there_pl=None,
                                  interpret: bool = False,
                                  block_z: int | None = None,
                                  out_dtype=None) -> jnp.ndarray:
    """Checkerboarded v3 staggered hop: backward hops read the UNSHIFTED
    opposite-parity links (both hop sets flip parity — odd nhop), so no
    ``backward_links_eo`` copies are kept resident."""
    T, Z, Y, X = dims
    Xh = X // 2
    _, _, _, _, YXh = psi_pl.shape
    _require_naik_z(Z, long_here_pl is not None)
    if block_z is not None:
        bz = block_z
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    else:
        bz = _pick_bz_v3(Z, YXh, psi_pl.dtype, long_here_pl is not None,
                         eo=True)

    eo = (target_parity, Xh)
    out = _stag_pass_v3(fat_here_pl, psi_pl, X, 1, bz, interpret, eo,
                        links_there_pl=fat_there_pl)
    if long_here_pl is not None:
        out = out + _stag_pass_v3(long_here_pl, psi_pl, X, 3, bz,
                                  interpret, eo,
                                  links_there_pl=long_there_pl)
    odt = out_dtype or psi_pl.dtype
    return out.astype(odt)


# -- even/odd (checkerboarded) variant: the staggered CG hot path -----------

def backward_links_eo(u_there_pl: jnp.ndarray, dims, target_parity: int,
                      nhop: int) -> jnp.ndarray:
    """Pre-shifted backward links on the half lattice:
    out[mu](x) = U_mu(x - nhop*mu) for parity-``target_parity`` sites,
    where ``u_there_pl`` holds the opposite-parity links (odd nhop) in
    the packed pair layout (4,3,3,2,T,Z,Y*Xh)."""
    from .wilson_packed import shift_eo_packed
    return jnp.stack([
        shift_eo_packed(u_there_pl[mu], dims, mu, -1, target_parity, nhop)
        for mu in range(4)])


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z",
                                             "out_dtype"))
def dslash_staggered_eo_pallas(fat_here_pl, fat_bw_pl, psi_pl, dims,
                               target_parity: int,
                               long_here_pl=None, long_bw_pl=None,
                               interpret: bool = False,
                               block_z: int | None = None,
                               out_dtype=None) -> jnp.ndarray:
    """Checkerboarded staggered / improved-staggered hop on
    pallas-layout half-lattice pair arrays; matches
    staggered_packed.dslash_staggered_eo_packed_pairs.

    fat_here_pl/long_here_pl: (4,3,3,2,T,Z,Y*Xh) target-parity forward
    links; the _bw arrays come from ``backward_links_eo`` (once per KS
    link load).  psi_pl: (3,2,T,Z,Y*Xh) parity-(1-p) color planes.
    """
    T, Z, Y, X = dims
    Xh = X // 2
    _, _, _, _, YXh = psi_pl.shape
    if block_z is not None:
        bz = block_z
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    else:
        bz = _pick_bz(Z, YXh, psi_pl.dtype, planes=_STAG_PLANES,
                      min_bz=3 if (long_here_pl is not None and Z > 3)
                      else 1, vmem_knob=_STAG_VMEM_KNOB)
    _check_long_bz(Z, bz, long_here_pl is not None,
                   "dslash_staggered_eo_pallas")

    eo = (target_parity, Xh)
    out = _stag_pass(fat_here_pl, fat_bw_pl, psi_pl, X, 1, bz, interpret,
                     eo)
    if long_here_pl is not None:
        out = out + _stag_pass(long_here_pl, long_bw_pl, psi_pl, X, 3,
                               bz, interpret, eo)
    odt = out_dtype or psi_pl.dtype
    return out.astype(odt)


# -- multi-RHS (MRHS) variants: gauge-amortized staggered -------------------
#
# Same pipeline move as wilson_pallas_packed.dslash_pallas_packed_mrhs
# (PERF.md round 7): grid (T, Z/bz, N) with the RHS axis INNERMOST, psi
# and out BlockSpecs carrying a leading size-1 RHS block, and fat/long
# link BlockSpecs whose index maps IGNORE n — consecutive grid steps
# present the same link block index, so the Mosaic pipeline keeps the
# tiles resident and N spinor tiles stream through one link fetch.  The
# kernel body is the single-RHS two-pass gather kernel through a
# leading-axis Ref view (_mrhs_wrap), bit-identical per RHS.  Per-RHS
# traffic (two-pass improved): psi 2x5x24 + out 2x24 + sum 72 + links
# 1152/N = 360 + 1152/N B/site -> ~504 at N=8.


def _stag_pass_mrhs(links_pl, links_bw_pl, psi_pl, X, nhop, bz,
                    interpret, eo=None):
    from jax.experimental import pallas as pl

    from .wilson_pallas_packed import _mrhs_wrap

    N, _, _, T, Z, YX = psi_pl.shape
    nzb = Z // bz
    if nzb > 1 and bz < nhop:
        raise ValueError(
            f"block_z={bz} < nhop={nhop}: the z splice only reaches the "
            "adjacent z-block")

    def psi_spec(dt, dz):
        return pl.BlockSpec(
            (1, 3, 2, 1, bz, YX),
            lambda t, zb, n, dt=dt, dz=dz: (n, 0, 0, (t + dt) % T,
                                            (zb + dz) % nzb, 0))

    # link index maps ignore n: the block index repeats across the
    # innermost RHS loop, so the pipeline re-uses the resident tiles
    links_spec = pl.BlockSpec(
        (4, 3, 3, 2, 1, bz, YX), lambda t, zb, n: (0, 0, 0, 0, t, zb, 0))

    kernel = _mrhs_wrap(_make_stag_kernel(X, nhop, bz, eo), n_psi=5)

    return pl.pallas_call(
        kernel,
        grid=(T, nzb, N),
        in_specs=[psi_spec(0, 0), psi_spec(+nhop, 0), psi_spec(-nhop, 0),
                  psi_spec(0, +1), psi_spec(0, -1), links_spec,
                  links_spec],
        out_specs=pl.BlockSpec((1, 3, 2, 1, bz, YX),
                               lambda t, zb, n: (n, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape, jnp.float32),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, psi_pl, psi_pl, links_pl, links_bw_pl)


@functools.partial(jax.jit, static_argnames=("X", "interpret", "block_z",
                                             "out_dtype"))
def dslash_staggered_pallas_mrhs(fat_pl: jnp.ndarray, fat_bw_pl: jnp.ndarray,
                                 psi_pl: jnp.ndarray, X: int,
                                 long_pl: jnp.ndarray = None,
                                 long_bw_pl: jnp.ndarray = None,
                                 interpret: bool = False,
                                 block_z: int | None = None,
                                 out_dtype=None) -> jnp.ndarray:
    """Multi-RHS staggered / improved-staggered D psi: psi_pl carries a
    leading RHS axis (N,3,2,T,Z,YX) over the dslash_staggered_pallas
    layout; per-RHS results bit-match the single-RHS kernel, with the
    fat/long link tiles fetched once per (t, z-block) for all N."""
    _, _, _, _, Z, YX = psi_pl.shape
    if block_z is not None:
        bz = block_z
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    else:
        bz = _pick_bz(Z, YX, psi_pl.dtype, planes=_STAG_PLANES,
                      min_bz=3 if (long_pl is not None and Z > 3) else 1,
                      vmem_knob=_STAG_VMEM_KNOB)
    _check_long_bz(Z, bz, long_pl is not None,
                   "dslash_staggered_pallas_mrhs")

    out = _stag_pass_mrhs(fat_pl, fat_bw_pl, psi_pl, X, 1, bz, interpret)
    if long_pl is not None:
        out = out + _stag_pass_mrhs(long_pl, long_bw_pl, psi_pl, X, 3,
                                    bz, interpret)
    odt = out_dtype or psi_pl.dtype
    return out.astype(odt)


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z",
                                             "out_dtype"))
def dslash_staggered_eo_pallas_mrhs(fat_here_pl, fat_bw_pl, psi_pl, dims,
                                    target_parity: int,
                                    long_here_pl=None, long_bw_pl=None,
                                    interpret: bool = False,
                                    block_z: int | None = None,
                                    out_dtype=None) -> jnp.ndarray:
    """Multi-RHS checkerboarded staggered hop — the batched staggered
    solver hot path (dslash_staggered_eo_pallas with a leading RHS axis
    on psi: (N,3,2,T,Z,Y*Xh) of parity 1-p).  Link tiles are fetched
    once per (t, z-block) and shared by all N RHS."""
    T, Z, Y, X = dims
    Xh = X // 2
    YXh = psi_pl.shape[-1]
    if block_z is not None:
        bz = block_z
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    else:
        bz = _pick_bz(Z, YXh, psi_pl.dtype, planes=_STAG_PLANES,
                      min_bz=3 if (long_here_pl is not None and Z > 3)
                      else 1, vmem_knob=_STAG_VMEM_KNOB)
    _check_long_bz(Z, bz, long_here_pl is not None,
                   "dslash_staggered_eo_pallas_mrhs")

    eo = (target_parity, Xh)
    out = _stag_pass_mrhs(fat_here_pl, fat_bw_pl, psi_pl, X, 1, bz,
                          interpret, eo)
    if long_here_pl is not None:
        out = out + _stag_pass_mrhs(long_here_pl, long_bw_pl, psi_pl, X,
                                    3, bz, interpret, eo)
    odt = out_dtype or psi_pl.dtype
    return out.astype(odt)


# The scatter (v3) pass under the same wrap: no pre-shifted backward
# links (the backward hops read the opposite parity's links as they
# are), three full psi tiles a source instead of five.  Per pass and
# output site, N sources: links 576 B in f32 once (u 288, u_there_xyz
# 216, the U_t plane 72), psi 3 x 24 N in, 24 N out.


def _stag_pass_v3_mrhs(links_pl, links_there_pl, psi_pl, X, nhop, bz,
                       interpret, eo):
    """``_stag_pass_v3`` (checkerboarded) with a leading RHS axis on psi
    and out: grid (T, Z/bz, N), RHS innermost, link index maps ignore
    n."""
    from jax.experimental import pallas as pl

    from .wilson_pallas_packed import _mrhs_wrap

    N, _, _, T, Z, YX = psi_pl.shape
    nzb = Z // bz
    if nzb > 1 and bz % nhop != 0:
        raise ValueError(
            f"block_z={bz} not a multiple of nhop={nhop}: the nhop-row "
            "z boundary inputs must align to row-block boundaries")

    def psi_spec(dt):
        return pl.BlockSpec(
            (1, 3, 2, 1, bz, YX),
            lambda t, zb, n, dt=dt: (n, 0, 0, (t + dt) % T, zb, 0))

    # boundary z-rows pre-gathered as in _stag_pass_v3 (unread dummies
    # with a single z-block: the kernel rolls inside its tile)
    if nzb == 1:
        rows_zp = rows_zm = jnp.zeros((N, 3, 2, T, 1, nhop, YX),
                                      psi_pl.dtype)
        u_rows_zm = jnp.zeros((1, 3, 3, 2, T, 1, nhop, YX),
                              links_there_pl.dtype)
    else:
        q = bz // nhop
        psi_q = psi_pl.reshape(N, 3, 2, T, nzb, q, nhop, YX)
        rows_zp = jnp.roll(psi_q[:, :, :, :, :, 0], -1, axis=4)
        rows_zm = jnp.roll(psi_q[:, :, :, :, :, q - 1], 1, axis=4)
        u_q = links_there_pl[2:3].reshape(1, 3, 3, 2, T, nzb, q, nhop, YX)
        u_rows_zm = jnp.roll(u_q[:, :, :, :, :, :, q - 1], 1, axis=5)

    psi_row_spec = pl.BlockSpec((1, 3, 2, 1, 1, nhop, YX),
                                lambda t, zb, n: (n, 0, 0, t, zb, 0, 0))
    links_spec = pl.BlockSpec(
        (4, 3, 3, 2, 1, bz, YX), lambda t, zb, n: (0, 0, 0, 0, t, zb, 0))
    links_xyz_spec = pl.BlockSpec(
        (3, 3, 3, 2, 1, bz, YX), lambda t, zb, n: (0, 0, 0, 0, t, zb, 0))
    u_t_spec = pl.BlockSpec(
        (1, 3, 3, 2, 1, bz, YX),
        lambda t, zb, n: (3, 0, 0, 0, (t - nhop) % T, zb, 0))
    u_z_spec = pl.BlockSpec(
        (1, 3, 3, 2, 1, 1, nhop, YX),
        lambda t, zb, n: (0, 0, 0, 0, t, zb, 0, 0))

    kernel = _mrhs_wrap(
        _make_stag_kernel_v3(X, nhop, bz, eo, single_zb=(nzb == 1)),
        n_psi=5)
    return pl.pallas_call(
        kernel,
        grid=(T, nzb, N),
        in_specs=[psi_spec(0), psi_spec(+nhop), psi_spec(-nhop),
                  psi_row_spec, psi_row_spec, links_spec,
                  links_xyz_spec, u_t_spec, u_z_spec],
        out_specs=pl.BlockSpec((1, 3, 2, 1, bz, YX),
                               lambda t, zb, n: (n, 0, 0, t, zb, 0)),
        out_shape=jax.ShapeDtypeStruct(psi_pl.shape, jnp.float32),
        interpret=interpret,
    )(psi_pl, psi_pl, psi_pl, rows_zp, rows_zm, links_pl, links_there_pl,
      links_there_pl, u_rows_zm)


@functools.partial(jax.jit, static_argnames=("dims", "target_parity",
                                             "interpret", "block_z",
                                             "out_dtype"))
def dslash_staggered_eo_pallas_v3_mrhs(fat_here_pl, fat_there_pl, psi_pl,
                                       dims, target_parity: int,
                                       long_here_pl=None,
                                       long_there_pl=None,
                                       interpret: bool = False,
                                       block_z: int | None = None,
                                       out_dtype=None) -> jnp.ndarray:
    """Multi-RHS checkerboarded scatter hop:
    ``dslash_staggered_eo_pallas_v3`` with a leading RHS axis on psi
    ((N,3,2,T,Z,Y*Xh) of parity 1-p), bit for bit the single-RHS kernel
    per source; link tiles fetched once per (t, z-block) for all N."""
    T, Z, Y, X = dims
    Xh = X // 2
    YXh = psi_pl.shape[-1]
    _require_naik_z(Z, long_here_pl is not None)
    if block_z is not None:
        bz = block_z
        if Z % bz != 0:
            raise ValueError(f"block_z={bz} does not divide Z={Z}")
    else:
        bz = _pick_bz_v3(Z, YXh, psi_pl.dtype, long_here_pl is not None,
                         eo=True)

    eo = (target_parity, Xh)
    out = _stag_pass_v3_mrhs(fat_here_pl, fat_there_pl, psi_pl, X, 1, bz,
                             interpret, eo)
    if long_here_pl is not None:
        out = out + _stag_pass_v3_mrhs(long_here_pl, long_there_pl, psi_pl,
                                       X, 3, bz, interpret, eo)
    odt = out_dtype or psi_pl.dtype
    return out.astype(odt)
