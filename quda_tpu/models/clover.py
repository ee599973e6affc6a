"""Wilson-clover Dirac operator (full and even/odd preconditioned).

Reference behavior: lib/dirac_clover.cpp (DiracClover::M applies
A psi - kappa D psi; DiracCloverPC uses the asymmetric Schur complement
with the odd-block clover inverse).  Conventions:

    A(x) = 1 + (kappa * csw / 2) * sum_{mu<nu} sigma_{mu nu} F_{mu nu}(x)
    M = A - kappa * D

so csw=0 reduces exactly to Wilson.  PC operator on parity p:

    M_pc x = A_p x - kappa^2 D_{p q} A_q^{-1} D_{q p} x     (q = 1-p)
    prepare:      b_pc = b_p + kappa * D_{p q} A_q^{-1} b_q
    reconstruct:  x_q  = A_q^{-1} (b_q + kappa * D_{q p} x_p)
"""

from __future__ import annotations

import copy
from functools import partial

import jax
import jax.numpy as jnp

from ..fields.geometry import EVEN, LatticeGeometry
from ..fields.spinor import even_odd_join, even_odd_split
from ..ops import wilson as wops
from ..ops.boundary import apply_t_boundary
from ..ops.clover import apply_clover, clover_blocks, invert_clover
from . import formsel
from .dirac import Dirac, DiracPC, MATPC_EVEN_EVEN
from .wilson import _ProgramOperand, _SchurPairOpBase


class DiracClover(Dirac):
    """Full-lattice Wilson-clover operator M = A - kappa D."""

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, csw: float, antiperiodic_t: bool = True):
        self.geom = geom
        self.kappa = kappa
        self.csw = csw
        self.gauge = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        # F_munu leaves use the PHYSICAL links (no BC phase): QUDA computes
        # the clover term before applying fermion boundary conditions.
        self.clover = clover_blocks(gauge, kappa * csw / 2.0)
        from ..obs import memory as omem
        omem.track("clover", "clover_blocks", self.clover)

    def D(self, psi):
        return wops.dslash_full(self.gauge, psi)

    def A(self, psi):
        return apply_clover(self.clover, psi)

    def M(self, psi):
        return self.A(psi) - self.kappa * self.D(psi)

    # --- diag + hop decomposition (MG coarsening probes) ---
    def diag(self, psi):
        return self.A(psi)

    def hop(self, psi, mu, sign):
        from .wilson import DiracWilson
        return DiracWilson.hop(self, psi, mu, sign)

    def flops_per_site_M(self) -> int:
        return 1320 + 504 + 48  # dslash + clover (2x 6x6 matvec) + axpy


class DiracCloverPC(DiracPC):
    """Asymmetric even/odd preconditioned clover operator."""

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, csw: float, antiperiodic_t: bool = True,
                 matpc: int = MATPC_EVEN_EVEN):
        self.geom = geom
        self.kappa = kappa
        self.csw = csw
        self.matpc = matpc
        g = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.gauge_eo = wops.split_gauge_eo(g, geom)
        blocks = clover_blocks(gauge, kappa * csw / 2.0)
        a_e, a_o = even_odd_split(blocks, geom)
        self.clover = (a_e, a_o)
        q = 1 - matpc
        self.clover_inv_q = invert_clover(self.clover[q])
        from ..obs import memory as omem
        omem.track("clover", "clover_eo_blocks",
                   (self.clover, self.clover_inv_q))

    def D_to(self, psi, target_parity):
        return wops.dslash_eo(self.gauge_eo, psi, self.geom, target_parity)

    def A_p(self, x):
        return apply_clover(self.clover[self.matpc], x)

    def Ainv_q(self, x):
        return apply_clover(self.clover_inv_q, x)

    def M(self, x_p):
        p = self.matpc
        tmp = self.Ainv_q(self.D_to(x_p, 1 - p))
        return self.A_p(x_p) - (self.kappa ** 2) * self.D_to(tmp, p)

    def prepare(self, b_even, b_odd):
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        return b_p + self.kappa * self.D_to(self.Ainv_q(b_q), p)

    def reconstruct(self, x_p, b_even, b_odd):
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        x_q = self.Ainv_q(b_q + self.kappa * self.D_to(x_p, 1 - p))
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def flops_per_site_M(self) -> int:
        return 2 * 1320 + 2 * 504 + 48

    def pairs(self, store_dtype=jnp.float32, use_pallas: bool = False,
              pallas_interpret: bool = False,
              form: str | None = None) -> "DiracCloverPCPairs":
        """Complex-free packed companion (f32 = the precise TPU solve
        path; bf16 = the sloppy clover operator of mixed solves).
        ``form`` / QUDA_TPU_CLOVER_FORM picks fused-pallas vs staged-XLA
        (models/formsel)."""
        return DiracCloverPCPairs(self, store_dtype, use_pallas,
                                  pallas_interpret,
                                  form=form)


def pack_clover_pairs(blocks: jnp.ndarray, store_dtype) -> jnp.ndarray:
    """Chiral 6x6 blocks (T,Z,Y,Xh,2,6,6) -> packed pairs
    (2,6,6,2,T,Z,Y*Xh): block indices leading, re/im split, fused
    minor site axes — the clover analog of wilson_packed.pack_gauge."""
    from ..ops.wilson_packed import to_packed_pairs
    T, Z, Y, Xh = blocks.shape[:4]
    packed = jnp.transpose(blocks, (4, 5, 6, 0, 1, 2, 3)).reshape(
        2, 6, 6, T, Z, Y * Xh)
    return to_packed_pairs(packed, store_dtype)


def apply_clover_pairs(blk_pp: jnp.ndarray, x_pp: jnp.ndarray,
                       out_dtype=None) -> jnp.ndarray:
    """A psi on pair arrays: blk_pp (2,6,6,2,T,Z,YXh), x_pp
    (4,3,2,T,Z,YXh).  The (4,3) spin-color axes reshape to (2,6)
    chirality blocks (spins 0,1 -> chirality 0 in DeGrand-Rossi);
    complex matvec as four real einsums at f32."""
    odt = out_dtype or x_pp.dtype
    f = x_pp.astype(jnp.float32)
    chi = f.reshape((2, 6) + f.shape[2:])        # (2,6,2,T,Z,YXh)
    ar = blk_pp[:, :, :, 0].astype(jnp.float32)  # (2,6,6,T,Z,YXh)
    ai = blk_pp[:, :, :, 1].astype(jnp.float32)
    xr, xi = chi[:, :, 0], chi[:, :, 1]          # (2,6,T,Z,YXh)
    outr = (jnp.einsum("cij...,cj...->ci...", ar, xr)
            - jnp.einsum("cij...,cj...->ci...", ai, xi))
    outi = (jnp.einsum("cij...,cj...->ci...", ar, xi)
            + jnp.einsum("cij...,cj...->ci...", ai, xr))
    out = jnp.stack([outr, outi], axis=2)        # (2,6,2,T,Z,YXh)
    return out.reshape(x_pp.shape).astype(odt)


def apply_clover_pairs_mrhs(blk_pp: jnp.ndarray, x_b: jnp.ndarray,
                            out_dtype=None) -> jnp.ndarray:
    """``apply_clover_pairs`` on a batch x_b (N,4,3,2,T,Z,YXh), the
    blocks read once for all of it: the six products of a block row
    multiplied out and summed elementwise at f32, so that XLA keeps the
    lattice axes minor in one fusion.  The vmapped einsum becomes a
    dot_general whose minor axes are (N, 6): at 24^4 and eight sources
    every operand and result of it is a 1.27 GiB tile-padded copy,
    9.9 GiB of temporaries in the exit program (the described-chip
    compile, PR 46)."""
    odt = out_dtype or x_b.dtype
    f = x_b.astype(jnp.float32)
    chi = f.reshape((f.shape[0], 2, 1, 6) + f.shape[3:])
    a = blk_pp.astype(jnp.float32)[None]         # (1,2,6,6,2,T,Z,YXh)
    ar, ai = a[:, :, :, :, 0], a[:, :, :, :, 1]  # (1,2,6,6,T,Z,YXh)
    xr, xi = chi[:, :, :, :, 0], chi[:, :, :, :, 1]  # (N,2,1,6,T,Z,YXh)
    out = jnp.stack([jnp.sum(ar * xr - ai * xi, axis=3),
                     jnp.sum(ar * xi + ai * xr, axis=3)], axis=3)
    return out.reshape(x_b.shape).astype(odt)


class DiracCloverPCPairs(_ProgramOperand, _SchurPairOpBase):
    """Complex-free packed pair-form of DiracCloverPC — Wilson-clover
    solves on TPU runtimes without complex64 execution, and (bf16
    storage) the sloppy clover operator of mixed solves.

    The hop/Schur/prepare/reconstruct machinery is _SchurPairOpBase
    (models/wilson.py); this class supplies the two diagonal hooks: the
    clover term and its odd-parity inverse as resident pair-form chiral
    blocks applied as real einsums (MXU).  The PC operator is
    gamma5-hermitian, so the template's sign argument is ignored.

    A solve-program operand (_ProgramOperand): links, blocks and kappa
    are the leaves; the fused-or-staged form, resolved when the
    operator is built, is part of the static signature.  The blocks of
    the OTHER parity's term, A_q, are a leaf too, ``None`` except on
    the operator ``with_full_diag`` hands out: what
    ``verified_exit_pairs`` needs beyond the PC operator's own arrays
    to apply the full M = A - kappa D.

    Reference behavior: QUDA runs clover solves in native FloatN orders
    with the clover field in its own packed order
    (include/clover_field_order.h); this is that representation.
    """

    _PROGRAM_ARRAYS = _ProgramOperand._PROGRAM_ARRAYS + (
        "clover_p_pp", "clover_inv_q_pp", "clover_q_pp")
    # the form a batch (a leading source axis) is served in where the
    # single-source form is the fused one: read on the chip, not raced
    _MRHS_FORM = formsel.MEASURED_MRHS["clover"]
    _PROGRAM_STATIC = _ProgramOperand._PROGRAM_STATIC + ("_op_form",)

    def __init__(self, dpc: "DiracCloverPC", store_dtype=jnp.float32,
                 use_pallas: bool = False, pallas_interpret: bool = False,
                 form: str | None = None):
        from ..ops import wilson_packed as wpk
        self._assemble(
            dpc.geom, wpk.pack_gauge_eo(dpc.gauge_eo), dpc.kappa,
            dpc.matpc, pack_clover_pairs(dpc.clover[dpc.matpc],
                                         store_dtype),
            pack_clover_pairs(dpc.clover_inv_q, store_dtype),
            store_dtype, use_pallas, pallas_interpret,
            getattr(dpc, 'antiperiodic_t', True), form)
        from ..obs import memory as omem
        omem.track("clover", "clover_pair_blocks",
                   (self.clover_p_pp, self.clover_inv_q_pp))

    @classmethod
    def from_packed(cls, geom, gauge_eo_packed, kappa, matpc, clover_p,
                    clover_inv_q, store_dtype=jnp.float32,
                    use_pallas: bool = False,
                    pallas_interpret: bool = False, tb_sign: bool = True,
                    form: str | None = None) -> "DiracCloverPCPairs":
        """From what a resident clover term holds: the boundary-folded
        packed links (wilson_packed.pack_gauge_eo) and the packed
        complex blocks of ops/clover_packed, (2,6,6,T,Z,Y*Xh) — no
        canonical DiracCloverPC in between.  The owner of the term keeps
        its row in the HBM ledger."""
        from ..ops import wilson_packed as wpk
        op = object.__new__(cls)
        op._assemble(geom, gauge_eo_packed, kappa, matpc,
                     wpk.to_packed_pairs(clover_p, store_dtype),
                     wpk.to_packed_pairs(clover_inv_q, store_dtype),
                     store_dtype, use_pallas, pallas_interpret, tb_sign,
                     form)
        return op

    def _assemble(self, geom, gauge_eo_packed, kappa, matpc, clover_p_pp,
                  clover_inv_q_pp, store_dtype, use_pallas,
                  pallas_interpret, tb_sign, form):
        self._setup_hop(geom, gauge_eo_packed, store_dtype, use_pallas,
                        pallas_interpret, tb_sign=tb_sign)
        self.kappa = float(kappa)
        self.matpc = matpc
        self.clover_p_pp = clover_p_pp
        self.clover_inv_q_pp = clover_inv_q_pp
        self.clover_q_pp = None
        aux = jnp.dtype(store_dtype).name
        self._op_form = formsel.resolve_form(
            "clover", form, self,
            race=lambda: formsel.race_schur("clover", self, aux=aux),
            aux=aux)

    def _diag_sign_pairs(self, x, sign, out_dtype):
        return apply_clover_pairs(self.clover_p_pp, x, out_dtype)

    def _Ainv_q_sign_pairs(self, x, sign, out_dtype):
        return apply_clover_pairs(self.clover_inv_q_pp, x, out_dtype)

    def _diag_sign_pairs_mrhs(self, x, sign, out_dtype):
        return apply_clover_pairs_mrhs(self.clover_p_pp, x, out_dtype)

    def _Ainv_q_sign_pairs_mrhs(self, x, sign, out_dtype):
        return apply_clover_pairs_mrhs(self.clover_inv_q_pp, x, out_dtype)

    # fused-epilogue descriptors (ops/clover_pallas via _SchurPairOpBase):
    # K1 = Ainv_q blocks post-hop, K2 = A_p blocks on the original x —
    # both sign-independent (the clover PC operator is g5-hermitian)
    def _fused_k1_params(self, sign):
        return self.clover_inv_q_pp, None

    def _fused_k2_params(self, sign):
        return self.clover_p_pp, None

    # -- entry and verified exit as programs (solvers/program.py) --------
    def with_full_diag(self, clover_q_pp):
        """The same resident arrays with the A blocks of the other
        parity beside them (a leaf: the executables are shared), for
        ``verified_exit_pairs``."""
        op = copy.copy(self)
        op.clover_q_pp = clover_q_pp
        return op

    def prepare_normal_pairs(self, b):
        """The entry of a CGNR solve, meant to be traced
        (solvers/program.prepare): the canonical full-lattice source,
        or a batch of them on a leading axis, split by parity, through
        ``prepare`` and ``Mdag`` -> the normal equations' pair-form
        right-hand side."""
        if b.ndim == 7:
            return self.Mdag_pairs_mrhs(self.prepare_pairs_mrhs(
                *jax.vmap(lambda v: even_odd_split(v, self.geom))(b)))
        return self.Mdag_pairs(
            self.prepare_pairs(*even_odd_split(b, self.geom)))

    def verified_exit_pairs(self, b, x_pp):
        """The API's verified exit on the pair representation: the
        canonical full-lattice source ``b`` and the pair-form PC
        solution -> (canonical full-lattice solution, |b - M x| / |b|).
        x_q = Ainv_q (b_q + kappa D x_p) is the reconstruction; the
        residual is that of the RETURNED solution under the full
        M = A - kappa D, applied parity by parity in f32 with this
        operator's own hop, its A_p blocks and the A_q blocks of
        ``with_full_diag`` (nothing inverted: the q half holds A_q
        against its inverse).  With a leading source axis on both, the
        batched hop and one residual per source.  Meant to be traced
        (solvers/program.py) on the f32 operator."""
        f32, p, kappa = jnp.float32, self.matpc, self.kappa
        batched = b.ndim == 7
        per_src = jax.vmap if batched else (lambda f: f)
        hop = self._d_to_mrhs if batched else self._d_to
        blocks = partial(
            apply_clover_pairs_mrhs if batched else apply_clover_pairs,
            out_dtype=f32)
        to_pp = per_src(lambda v: self._to_pairs(v).astype(f32))
        from_pp = per_src(lambda v: self._from_pairs(v, b.dtype))
        norm2 = per_src(lambda v: jnp.sum(v * v))
        halves = per_src(lambda v: even_odd_split(v, self.geom))(b)
        b_p, b_q = (to_pp(h)
                    for h in (halves if p == EVEN else halves[::-1]))
        x_p = x_pp.astype(f32)
        # D x_p serves the reconstruction and the q half of M x
        d_xp = hop(x_p, 1 - p, f32)
        x_q = blocks(self.clover_inv_q_pp, b_q + kappa * d_xp)
        r_p = b_p - (blocks(self.clover_p_pp, x_p)
                     - kappa * hop(x_q, p, f32))
        r_q = b_q - (blocks(self.clover_q_pp, x_q) - kappa * d_xp)
        x_e, x_o = (from_pp(v)
                    for v in ((x_p, x_q) if p == EVEN else (x_q, x_p)))
        x = per_src(lambda e, o: even_odd_join(e, o, self.geom))(x_e, x_o)
        return x, jnp.sqrt((norm2(r_p) + norm2(r_q))
                           / (norm2(b_p) + norm2(b_q)))

    def verified_exit_shifts_pairs(self, b_pp, X_pp, shifts, claimed,
                                   shift_r2, bound):
        """The verified exit of a multi-shift solve (Mdag M + sigma_i)
        x_i = b' on the even-odd operator M: the pair-form right-hand
        side of the normal equations ``b_pp`` (what
        ``prepare_normal_pairs`` made), the N pair-form solutions
        ``X_pp`` (N, 4, 3, 2, T, Z, Y*Xh) and ``shifts`` (N,) ->
        (canonical parity solutions (N, T, Z, Y, Xh, 4, 3), the N true
        residuals |b' - (Mdag M + sigma_i) x_i| / |b'|, the loop's
        analytic residuals sqrt(``shift_r2``) / |b'|, N flags:
        ``claimed`` by the loop AND a true residual <= ``bound``; a NaN
        fails).  The N solutions are ONE batch for the batched operator
        (``MdagM_pairs_mrhs``): links and blocks read once a hop for
        all shifts.  The residual is the PC normal system's own, no
        reconstruction.  Meant to be traced (solvers/program.py) on the
        f32 operator."""
        f32 = jnp.float32
        b, X = b_pp.astype(f32), X_pp.astype(f32)
        sig = shifts.astype(f32).reshape((-1,) + (1,) * b.ndim)
        r = b[None] - (self.MdagM_pairs_mrhs(X).astype(f32) + sig * X)
        b2 = jnp.sum(b * b)
        true_res = jnp.sqrt(jnp.sum(r * r, axis=tuple(range(1, r.ndim)))
                            / b2)
        return (self.solution_from_pairs_mrhs(X), true_res,
                jnp.sqrt(shift_r2.astype(f32) / b2),
                jnp.logical_and(claimed, true_res <= bound))


jax.tree_util.register_pytree_node_class(DiracCloverPCPairs)


@jax.jit
def _full_m_pairs(op: DiracCloverPCPairs, clover_q_pp, psi):
    """M psi = A psi - kappa D psi on the full lattice through the f32
    pair operator: canonical complex in and out, pair arithmetic in
    between (the hop is the operator's own stencil, the diagonal its
    resident blocks plus those of the other parity)."""
    p = op.matpc
    pe, po = even_odd_split(psi, op.geom)
    x = [op._to_pairs(v) for v in ((pe, po) if p == EVEN else (po, pe))]
    blk = (op.clover_p_pp, clover_q_pp)
    out = [op._from_pairs(
        apply_clover_pairs(blk[i], x[i], jnp.float32)
        - op.kappa * op._d_to(x[1 - i], p if i == 0 else 1 - p,
                              jnp.float32), psi.dtype)
        for i in (0, 1)]
    oe, oo = out if p == EVEN else out[::-1]
    return even_odd_join(oe, oo, op.geom)


class DiracCloverFullPairs:
    """The full operator M = A - kappa D of a verified-exit check,
    applied with a resident f32 ``DiracCloverPCPairs`` and the A blocks
    of its other parity: no second clover_blocks, no canonical
    (...,2,6,6) einsum (interfaces/quda_api builds it from the
    resident clover term)."""

    def __init__(self, op: DiracCloverPCPairs, clover_q_pp):
        self.op = op
        self.clover_q_pp = clover_q_pp

    def M(self, psi):
        return _full_m_pairs(self.op, self.clover_q_pp, psi)

    def flops_per_site_M(self) -> int:
        return 1320 + 504 + 48
