"""Domain-wall (Shamir) and Möbius Dirac operators, full and 4d-even/odd
preconditioned.

Reference behavior: lib/dirac_domain_wall.cpp, lib/dirac_domain_wall_4d.cpp,
lib/dirac_mobius.cpp (740 LoC) and the m5 kernel family (see ops/dwf.py).

Formulation (b5, c5 Möbius parameters; Shamir is b5=1, c5=0):

    M psi = D_W (b5 psi + c5 chi) + psi - chi
          = M5 psi - 1/2 hop( M5' psi )

with chi(s) the P-+ s-hop with -mf boundary (ops/dwf.py), D_W the 4-d
Wilson operator at mass -M5 (diagonal 4 - M5 folded in), and

    M5  = [alpha = b5 (4 - M5) + 1,  beta = c5 (4 - M5) - 1]
    M5' = [alpha = b5,               beta = c5]

4d-PC (symmetric) Schur system on parity p (QUDA's QUDA_MATPC_EVEN_EVEN
with symmetric preconditioning for Möbius):

    M_pc = 1 - 1/4 M5i hop_pq M5" hop_qp M5"        (M5" = M5' M5^{-1})
    prepare:      b' = M5i b_p + 1/2 M5i hop_pq M5i b_q
    reconstruct:  x_q = M5i (b_q + 1/2 hop_qp M5' x_p)

where all s-operators are dense (Ls,Ls) chirality blocks (ops/dwf.py) and
hop is the parity-changing 4-d Wilson hop applied per s-slice.

Dagger: adjoints of the s-operators are explicit conj-transposes and
hop^dag = gamma5 hop gamma5, composed in reverse — no separate dagger
kernels needed.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from ..fields.geometry import EVEN, LatticeGeometry
from ..ops import wilson as wops
from ..ops.boundary import apply_t_boundary
from ..ops.dwf import SOp, apply_sop, identity_sop, m5_sop
from .dirac import Dirac, DiracPC, MATPC_EVEN_EVEN, apply_gamma5
from .wilson import _PackedHopMixin, _ProgramOperand

# The Ls-batched hop's two forms, read on the chip (one v5e, 24^4 x 12,
# in the CG loop; PERF.md section 6, PR 42): an operator built
# ``from_packed`` (the API's resident route) serves the winner WITHOUT
# a race; ``QUDA_TPU_DWF_FORM=pallas|xla`` still pins.  The canonical
# constructor keeps formsel's race (the eager route).
MEASURED_LS_HOP_FORM = "pallas"


def m5_block_pairs(ls: int, m5: float, mf: float, b5: float, c5: float):
    """The four real (Ls, Ls) chirality-block pairs of the 4d-PC Möbius
    operator as host arrays, (M5, M5', M5" = M5' M5^-1, M5^-1): what a
    resident operator's leaves are made from (``m5`` positive, this
    module's sign)."""
    dw_diag = 4.0 - m5
    s_m5 = m5_sop(ls, b5 * dw_diag + 1.0, c5 * dw_diag - 1.0, mf)
    s_m5p = m5_sop(ls, b5, c5, mf)
    s_m5i = s_m5.inv()
    return s_m5, s_m5p, s_m5p @ s_m5i, s_m5i


def _real_f32(block):
    """One (Ls, Ls) chirality block as an f32 array; a host block must
    be real (the pair-form s-operators assume it)."""
    import numpy as np
    if isinstance(block, np.ndarray) and np.iscomplexobj(block):
        assert np.allclose(block.imag, 0), \
            "pair-form s-ops assume real chirality blocks"
        block = block.real
    return jnp.asarray(block, jnp.float32)


class DiracMobius(Dirac):
    """Full (unpreconditioned) Möbius operator on (Ls,T,Z,Y,X,4,3) fields."""

    g5_hermitian = False  # uses Gamma5 = gamma5 * R (s-reflection) instead

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry, ls: int,
                 m5: float, mf: float, b5: float = 1.0, c5: float = 0.0,
                 antiperiodic_t: bool = True):
        self.geom = geom
        self.ls = ls
        self.m5 = m5
        self.mf = mf
        self.b5 = b5
        self.c5 = c5
        self.gauge = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        dw_diag = 4.0 - m5
        self.s_m5 = m5_sop(ls, b5 * dw_diag + 1.0, c5 * dw_diag - 1.0, mf)
        self.s_m5p = m5_sop(ls, b5, c5, mf)

    def _hop(self, psi):
        """4-d Wilson hop applied to every s-slice (vmapped over s)."""
        return jax.vmap(lambda v: wops.dslash_full(self.gauge, v))(psi)

    def M(self, psi):
        return apply_sop(self.s_m5, psi) - 0.5 * self._hop(
            apply_sop(self.s_m5p, psi))

    def Mdag(self, psi):
        # M^dag = M5^dag - 1/2 M5'^dag hop^dag;  hop^dag = g5 hop g5
        hop_dag = apply_gamma5(self._hop(apply_gamma5(psi)))
        return (apply_sop(self.s_m5.adj(), psi)
                - 0.5 * apply_sop(self.s_m5p.adj(), hop_dag))

    def flops_per_site_M(self) -> int:
        # per (s, 4d-site): Wilson hop + two dense (Ls,Ls) s-contractions
        # (12 components x Ls complex MACs x 8 flops each)
        return 1320 + 2 * 96 * self.ls


class DiracDomainWall(DiracMobius):
    """Shamir domain wall: Möbius with b5=1, c5=0
    (lib/dirac_domain_wall.cpp)."""

    def __init__(self, gauge, geom, ls, m5, mf, antiperiodic_t=True):
        super().__init__(gauge, geom, ls, m5, mf, 1.0, 0.0, antiperiodic_t)


class DiracMobiusPC(DiracPC):
    """Symmetric 4d-even/odd preconditioned Möbius operator."""

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry, ls: int,
                 m5: float, mf: float, b5: float = 1.0, c5: float = 0.0,
                 antiperiodic_t: bool = True, matpc: int = MATPC_EVEN_EVEN):
        self.geom = geom
        self.ls = ls
        self.mf = mf
        self.matpc = matpc
        g = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.gauge_eo = wops.split_gauge_eo(g, geom)
        # M5" = M5' M5^{-1} (they commute)
        self.s_m5, self.s_m5p, self.s_mix, self.s_m5i = m5_block_pairs(
            ls, m5, mf, b5, c5)

    def _hop_to(self, psi, target_parity):
        return jax.vmap(
            lambda v: wops.dslash_eo(self.gauge_eo, v, self.geom,
                                     target_parity))(psi)

    def _hop_to_dag(self, psi, target_parity):
        """Adjoint hop: (hop_to(., 1-q))^dag maps (1-q)-parity fields back to
        q = gamma5 hop_to(gamma5 ., q)."""
        return apply_gamma5(self._hop_to(apply_gamma5(psi), target_parity))

    # M_pc = 1 - 1/4 M5i . hop_to(.,p) . M5" . hop_to(.,1-p) . M5'
    def M(self, x_p):
        p = self.matpc
        t = self._hop_to(apply_sop(self.s_m5p, x_p), 1 - p)
        t = self._hop_to(apply_sop(self.s_mix, t), p)
        return x_p - 0.25 * apply_sop(self.s_m5i, t)

    def Mdag(self, x_p):
        p = self.matpc
        t = apply_sop(self.s_m5i.adj(), x_p)
        t = apply_sop(self.s_mix.adj(), self._hop_to_dag(t, 1 - p))
        t = apply_sop(self.s_m5p.adj(), self._hop_to_dag(t, p))
        return x_p - 0.25 * t

    def prepare(self, b_even, b_odd):
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        t = self._hop_to(apply_sop(self.s_mix, b_q), p)
        return apply_sop(self.s_m5i, b_p + 0.5 * t)

    def reconstruct(self, x_p, b_even, b_odd):
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        t = self._hop_to(apply_sop(self.s_m5p, x_p), 1 - p)
        x_q = apply_sop(self.s_m5i, b_q + 0.5 * t)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def flops_per_site_M(self) -> int:
        return 2 * 1320 + 3 * 96 * self.ls

    def pairs(self, store_dtype=jnp.float32, use_pallas: bool = False,
              pallas_interpret: bool = False,
              form: str | None = None) -> "DiracMobiusPCPairs":
        """Complex-free packed companion (f32 = the precise TPU solve
        path; bf16 = the sloppy operator) — also serves the EOFA
        subclass, whose corrected s-blocks it reads.  ``form`` /
        QUDA_TPU_DWF_FORM picks the Ls-batched 4d hop kernel vs the
        vmap-over-s stencil (models/formsel)."""
        return DiracMobiusPCPairs(self, store_dtype, use_pallas,
                                  pallas_interpret,
                                  form=form)


class _LsPairIOMixin:
    """Layout converters and gamma5 for Ls-leading pair fields
    (Ls, 4, 3, 2, T, Z, Y*Xh) — shared by the Möbius and 5d-PC pair
    operators (overrides _PackedHopMixin's single-slice converters)."""

    def _to_pairs(self, x5):
        from ..ops import wilson_packed as wpk
        packed = jax.vmap(wpk.pack_spinor)(x5)
        return wpk.to_packed_pairs(packed, self.store_dtype)

    def _from_pairs(self, x_pp, dtype=jnp.complex64):
        from ..ops import wilson_packed as wpk
        T, Z, Y, X = self.dims
        c = wpk.from_packed_pairs(x_pp, dtype)
        return jax.vmap(
            lambda v: wpk.unpack_spinor(v, (T, Z, Y, X // 2)))(c)

    def _g5(self, x):
        sign = jnp.asarray([1.0, 1.0, -1.0, -1.0], jnp.float32)
        return (x.astype(jnp.float32)
                * sign.reshape(1, 4, 1, 1, 1, 1, 1)).astype(x.dtype)


class DiracMobiusPCPairs(_ProgramOperand, _LsPairIOMixin, _PackedHopMixin):
    """Complex-free packed pair-form of DiracMobiusPC (incl. EOFA).

    The domain-wall/Möbius analog of DiracWilsonPCPackedSloppy /
    DiracStaggeredPCPairs — required end-to-end on TPU runtimes without
    complex64 execution (see bench.py), and with bf16 storage the sloppy
    Möbius operator of mixed solves.  Layouts: spinors
    (Ls, 4, 3, 2, T, Z, Y*Xh) re/im planes at ``store_dtype``, per-parity
    links (4, 3, 3, 2, T, Z, Y*Xh); compute f32.

    The 4-d hop is the packed eo Wilson stencil vmapped over the Ls axis
    (optionally the pallas kernel — jax.vmap turns its grid into
    (Ls, T, Z/bz)) or the Ls-batched kernel; the s-operators are the
    REAL dense (Ls, Ls) chirality blocks of ops/dwf.py, so no complex
    arithmetic remains anywhere: f32 einsums beside the vmapped hop,
    a VPU kernel on the hop's own layout beside the Ls-batched one
    (``_apply_blocks``), with gamma5 and the ``x - 1/4 ...`` in them.

    Reference behavior: QUDA's Möbius solves run in float2/half native
    orders with the fused m5 kernels (lib/dslash_mdw_fused.in.cu); here
    the s-block and the 4d-hop are two kernels on one layout, and the
    block in the hop's prologue / epilogue is ROADMAP A12's next step.

    A solve-program operand (solvers/program.py): the links and the
    four block pairs are the leaves, so one executable serves every
    (mf, M5, b5, c5) of one Ls; Ls, the kernel route and the served hop
    form are part of the static signature.
    """

    hermitian = False

    _PROGRAM_ARRAYS = ("gauge_eo_pp", "_u_bw", "_gauge_q", "_gauge_s",
                       "_m5", "_m5p", "_mix", "_m5i")
    _PROGRAM_STATIC = _ProgramOperand._PROGRAM_STATIC + ("ls", "_op_form")

    def __init__(self, dpc: DiracMobiusPC, store_dtype=jnp.float32,
                 use_pallas: bool = False, pallas_interpret: bool = False,
                 form: str | None = None):
        from ..ops import wilson_packed as wpk
        self._setup_hop(dpc.geom, wpk.pack_gauge_eo(dpc.gauge_eo),
                        store_dtype, use_pallas, pallas_interpret,
                        tb_sign=getattr(dpc, 'antiperiodic_t',
                                        True))
        self.ls = dpc.ls
        self.matpc = dpc.matpc
        self._set_blocks((dpc.s_m5, dpc.s_m5p, dpc.s_mix, dpc.s_m5i))
        from ..obs import memory as omem
        omem.track("dwf", "m5_pair_blocks",
                   self._m5p + self._mix + self._m5i)
        from . import formsel
        # "|mpairs": the race times M_pairs since PR 44 (the form picks
        # the s-blocks too); a winner cached from the hop alone is stale
        aux = f"{jnp.dtype(store_dtype).name}|ls{self.ls}|mpairs"
        self._op_form = formsel.resolve_form(
            "dwf", form, self,
            race=lambda: formsel.race_ls_hop("dwf", self, aux=aux),
            aux=aux)

    @classmethod
    def from_packed(cls, geom, gauge_eo_packed, ls, blocks, matpc,
                    store_dtype=jnp.float32, use_pallas: bool = False,
                    pallas_interpret: bool = False, tb_sign: bool = True
                    ) -> "DiracMobiusPCPairs":
        """From the boundary-folded packed links alone
        (wilson_packed.pack_gauge_eo) and the host block pairs
        (``m5_block_pairs``): what a resident Möbius term is built
        from, no canonical DiracMobiusPC in between.  The hop form is
        the knob's pin, else ``MEASURED_LS_HOP_FORM`` wherever the
        Ls-batched kernel can run (interpreted kernels keep the
        vmapped stencil, as formsel does): nothing is raced."""
        op = object.__new__(cls)
        op._setup_hop(geom, gauge_eo_packed, store_dtype, use_pallas,
                      pallas_interpret, tb_sign=tb_sign)
        op.ls = int(ls)
        op.matpc = matpc
        op._set_blocks(blocks)
        op._op_form = served_ls_hop_form(op)
        return op

    def _set_blocks(self, blocks):
        """(M5, M5', M5", M5^-1), each a (+, -) chirality pair of real
        (Ls, Ls) blocks (an SOp is one) -> the f32 leaves."""
        self._m5, self._m5p, self._mix, self._m5i = (
            tuple(_real_f32(m) for m in pair) for pair in blocks)

    def with_blocks(self, blocks):
        """The same resident links under other block pairs (leaves of
        the pytree: another mf, M5, b5 or c5 shares the executable)."""
        op = copy.copy(self)
        op._set_blocks(blocks)
        return op

    # -- building blocks ------------------------------------------------
    def _apply_blocks(self, blk, x, adjoint=False, out_dtype=None,
                      g5=False, axpy=None):
        """Apply real (Ls,Ls) chirality blocks to (Ls,4,3,2,T,Z,YXh):
        spins 0,1 through ap, spins 2,3 through am (chirality is
        spin-pair diagonal in the DeGrand-Rossi basis).  ``g5``: the
        product with gamma5 = diag(+,+,-,-), which commutes with every
        chirality-diagonal block, as a sign on am (exact).  ``axpy`` =
        (y, a): ``y + a * (blocks x)`` from the f32 sums.

        Where the hop is the Ls-batched kernel the product is one too
        (ops/dwf_pallas.mobius_sblock_pallas: VPU multiply-adds on the
        hop's own layout, f32 whatever the storage); the f32 einsum
        everywhere else (the CPU, interpreted kernels unless the form
        is pinned, QUDA_TPU_DWF_FORM=xla).  One form, ``_op_form``, for
        the hop and the blocks: where it is raced the race times the
        whole ``M_pairs`` (formsel.race_ls_hop), and the resident
        route's ``MEASURED_LS_HOP_FORM`` is the chip's reading of the
        whole solve (PERF.md section 6, PR 44)."""
        ap, am = blk
        if adjoint:
            ap, am = ap.T, am.T
        if g5:
            am = -am
        odt = jnp.dtype(out_dtype or self.store_dtype)
        form = "pallas" if self._op_form == "pallas" else "einsum"
        from ..obs import metrics as omet
        omet.inc("dwf_sblock_route_total", form=form, ls=str(self.ls))
        if form == "pallas":
            from ..ops import dwf_pallas as dwp
            blocks = jnp.stack([ap, am])
            if axpy is None:
                return dwp.mobius_sblock_pallas(
                    x, blocks, out_dtype=odt,
                    interpret=self._pallas_interpret)
            return dwp.mobius_sblock_axpy_pallas(
                x, *axpy, blocks, out_dtype=odt,
                interpret=self._pallas_interpret)
        f = x.astype(jnp.float32)
        up = jnp.einsum("st,t...->s...", ap, f[:, :2])
        dn = jnp.einsum("st,t...->s...", am, f[:, 2:])
        out = jnp.concatenate([up, dn], axis=1)
        if axpy is not None:
            y, a = axpy
            out = y.astype(jnp.float32) + a * out
        return out.astype(odt)

    def _hop_to_pairs(self, x, target_parity, out_dtype=None,
                      form=None):
        """The 4d hop on every s-slice.  form='pallas' (the resolved
        _op_form default on chip): the Ls-batched kernel — Ls is the
        innermost grid axis, each gauge tile fetched once per
        (t, z-block) while Ls spinor planes stream through it
        (576+576/Ls B/site/plane).  form='xla': the mixin's
        version-aware eo stencil vmapped over the leading Ls axis
        (batch outermost — links re-fetched per plane)."""
        odt = out_dtype or self.store_dtype
        form = form or self._op_form
        from ..obs import metrics as omet
        omet.inc("dwf_hop_route_total", form=form, ls=str(self.ls))
        if form == "pallas":
            from ..ops import dwf_pallas as dwp
            return dwp.dslash_eo_pallas_packed_ls(
                self.gauge_eo_pp[target_parity],
                self._u_bw[target_parity], x, tuple(self.dims),
                target_parity, interpret=self._pallas_interpret,
                block_z=getattr(self, "_block_z", None), out_dtype=odt,
                tb_sign=self._tb_sign)
        return jax.vmap(
            lambda v: self._d_to(v, target_parity, odt))(x)

    # -- the operator (mirrors DiracMobiusPC.M / .Mdag) -----------------
    def M_pairs(self, x):
        p = self.matpc
        t = self._hop_to_pairs(self._apply_blocks(self._m5p, x), 1 - p)
        t = self._hop_to_pairs(self._apply_blocks(self._mix, t), p,
                               out_dtype=jnp.float32)
        return self._apply_blocks(self._m5i, t, axpy=(x, -0.25))

    def Mdag_pairs(self, x):
        """1 - 1/4 (M5'^T g5) hop M5"^T hop (g5 M5^-1^T): hop^dag = g5
        hop g5, and the two gamma5 between the hops cancel through
        M5"^T, so the four sign passes are the sign of two blocks."""
        p = self.matpc
        t = self._apply_blocks(self._m5i, x, adjoint=True, g5=True)
        t = self._apply_blocks(self._mix, self._hop_to_pairs(t, 1 - p),
                               adjoint=True)
        return self._apply_blocks(self._m5p, self._hop_to_pairs(t, p),
                                  adjoint=True, g5=True,
                                  axpy=(x, -0.25))

    def MdagM_pairs(self, x):
        return self.Mdag_pairs(self.M_pairs(x))

    # -- complex wrappers (oracle tests, CPU paths) ---------------------
    def M(self, x):
        return self._from_pairs(self.M_pairs(self._to_pairs(x)), x.dtype)

    def Mdag(self, x):
        return self._from_pairs(self.Mdag_pairs(self._to_pairs(x)),
                                x.dtype)

    def MdagM(self, x):
        return self._from_pairs(self.MdagM_pairs(self._to_pairs(x)),
                                x.dtype)

    # -- prepare / reconstruct in pair space ----------------------------
    def _m5i_plus_half_hop(self, b_pp, blk, v_pp, parity):
        """M5i (b + 1/2 hop_to(parity) blk v) on pair arrays, f32: with
        (b_p, M5", b_q, p) it is ``prepare``, with (b_q, M5', x_p,
        1 - p) the other parity's solution."""
        t = self._hop_to_pairs(self._apply_blocks(blk, v_pp), parity,
                               out_dtype=jnp.float32)
        return self._apply_blocks(
            self._m5i, b_pp.astype(jnp.float32) + 0.5 * t,
            out_dtype=jnp.float32)

    def prepare_pairs(self, b_even, b_odd):
        """Canonical complex parity-split 5d sources -> pair-form PC rhs
        (mirrors DiracMobiusPC.prepare)."""
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        return self._m5i_plus_half_hop(
            self._to_pairs(b_p), self._mix, self._to_pairs(b_q),
            p).astype(self.store_dtype)

    def reconstruct_pairs(self, x_pp, b_even, b_odd):
        """Pair-form PC solution -> canonical complex (x_even, x_odd)
        (mirrors DiracMobiusPC.reconstruct)."""
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        xq_pp = self._m5i_plus_half_hop(self._to_pairs(b_q), self._m5p,
                                        x_pp, 1 - p)
        x_p = self._from_pairs(x_pp, b_q.dtype)
        x_q = self._from_pairs(xq_pp, b_q.dtype)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    # -- the API's entry and verified exit, each meant to be traced as
    # -- ONE program on the f32 operator (solvers/program.py) ----------
    def _split_pairs(self, b):
        """Canonical full 5d field (Ls,T,Z,Y,X,4,3) -> its (p, q)
        parity halves in pair form, f32."""
        from ..fields.spinor import even_odd_split
        halves = jax.vmap(lambda v: even_odd_split(v, self.geom))(b)
        b_p, b_q = halves if self.matpc == EVEN else halves[::-1]
        return (self._to_pairs(b_p).astype(jnp.float32),
                self._to_pairs(b_q).astype(jnp.float32))

    def prepare_normal_pairs(self, b):
        """The entry of a normal-equation solve: the canonical full 5d
        source, split by 4d parity, through ``prepare`` and ``Mdag``:
        the right-hand side of MdagM x_p = Mdag b', pair form."""
        b_p, b_q = self._split_pairs(b)
        return self.Mdag_pairs(self._m5i_plus_half_hop(
            b_p, self._mix, b_q, self.matpc).astype(self.store_dtype))

    def verified_exit_pairs(self, b, x_pp):
        """The API's verified exit on the pair representation: the
        canonical full 5d source ``b`` (Ls,T,Z,Y,X,4,3) and the
        pair-form PC solution -> (canonical full 5d solution,
        |b - M x| / |b|).  x_q = M5i (b_q + 1/2 hop M5' x_p) is the
        reconstruction; the residual is that of the RETURNED solution
        under the full M = M5 - 1/2 hop M5', parity by parity with this
        operator's own hop and blocks in f32: no canonical (...,4,3)
        temporary beyond the two boundaries."""
        from ..fields.spinor import even_odd_join
        f32, p = jnp.float32, self.matpc
        blk = lambda m, v: self._apply_blocks(m, v, out_dtype=f32)
        hop = lambda v, par: self._hop_to_pairs(v, par, out_dtype=f32)
        b_p, b_q = self._split_pairs(b)
        x_p = x_pp.astype(f32)
        # hop M5' x_p serves the reconstruction and the q half of M x
        h_p = hop(blk(self._m5p, x_p), 1 - p)
        x_q = blk(self._m5i, b_q + 0.5 * h_p)
        r_p = b_p - (blk(self._m5, x_p)
                     - 0.5 * hop(blk(self._m5p, x_q), p))
        r_q = b_q - (blk(self._m5, x_q) - 0.5 * h_p)
        norm2 = lambda v: jnp.sum(v * v)
        x_e, x_o = (self._from_pairs(v, b.dtype)
                    for v in ((x_p, x_q) if p == EVEN else (x_q, x_p)))
        x = jax.vmap(lambda e, o: even_odd_join(e, o, self.geom))(x_e, x_o)
        return x, jnp.sqrt((norm2(r_p) + norm2(r_q))
                           / (norm2(b_p) + norm2(b_q)))


jax.tree_util.register_pytree_node_class(DiracMobiusPCPairs)


def served_ls_hop_form(op) -> str:
    """The hop form a resident Möbius operator serves: the knob's pin
    (``QUDA_TPU_DWF_FORM=pallas|xla``), else the chip's measured winner
    wherever the Ls-batched kernel can run natively; never a race."""
    from ..utils import config as qconf
    from . import formsel
    req = str(qconf.get(formsel.KNOBS["dwf"], fresh=True))
    if formsel.fused_capable(op) is not None:
        return "xla"
    if req in ("pallas", "xla"):
        return req
    return "xla" if op._pallas_interpret else MEASURED_LS_HOP_FORM


# ---------------------------------------------------------------------------
# Möbius EOFA (exact one-flavor algorithm)
# ---------------------------------------------------------------------------

def eofa_rank_one(ls: int, b5: float, c5: float, m5: float,
                  mq1: float, mq2: float, mq3: float, eofa_pm: bool,
                  eofa_shift: float):
    """EOFA rank-one s-space correction in this module's normalisation.

    Reference math: lib/dirac_mobius.cpp:460-520 (DiracMobiusEofa ctor) —
    the u-vector of the one-flavor shift term Delta_pm = u (x) e_j on the
    pm chirality (j = Ls-1 for plus, 0 for minus).  QUDA's m5 is the
    negative of ours, so its (m5 + 4) is our dw_diag = 4 - m5; QUDA's
    kernel operator is ours divided by alpha = b5*dw_diag + 1, so the
    correction enters our M5 block scaled by alpha.  QUDA's eofa_x/eofa_y
    Sherman-Morrison closed-form inverse (include/kernels/
    dslash_mobius_eofa.cuh:232 eofa_dslash5inv) is unnecessary here: the
    (Ls,Ls) chirality blocks are inverted densely.
    """
    import numpy as np
    dw = 4.0 - m5
    al = b5 + c5
    eofa_norm = (al * (mq3 - mq2) * (al + 1.0) ** (2 * ls)
                 / ((al + 1.0) ** ls + mq2 * (al - 1.0) ** ls)
                 / ((al + 1.0) ** ls + mq3 * (al - 1.0) ** ls))
    N = ((+1.0 if eofa_pm else -1.0) * (2.0 * eofa_shift * eofa_norm)
         * ((al + 1.0) ** ls + mq1 * (al - 1.0) ** ls) / (b5 * dw + 1.0))
    u = np.zeros(ls)
    for s in range(ls):
        u[s if eofa_pm else ls - 1 - s] = (
            N * (-1.0) ** s * (al - 1.0) ** s / (al + 1.0) ** (ls + s + 1))
    alpha_m5 = b5 * dw + 1.0
    rank1 = np.zeros((ls, ls))
    j = ls - 1 if eofa_pm else 0
    rank1[:, j] = alpha_m5 * u
    return rank1


def _eofa_corrected_m5(obj, ls, b5, c5, m5, mf, mq1, mq2, mq3, eofa_pm,
                       eofa_shift) -> SOp:
    """Shared EOFA setup: default the mq's to mf, record the eofa params
    on ``obj``, and return obj.s_m5 with the rank-one correction added on
    the eofa_pm chirality block."""
    mq1 = mf if mq1 is None else mq1
    mq2 = mf if mq2 is None else mq2
    mq3 = mf if mq3 is None else mq3
    obj.eofa_pm = eofa_pm
    obj.eofa_shift = eofa_shift
    r1 = eofa_rank_one(ls, b5, c5, m5, mq1, mq2, mq3, eofa_pm, eofa_shift)
    if eofa_pm:
        return SOp(obj.s_m5.ap + r1, obj.s_m5.am)
    return SOp(obj.s_m5.ap, obj.s_m5.am + r1)


class DiracMobiusEofa(DiracMobius):
    """Full Möbius EOFA operator: Möbius at mass mf plus the one-flavor
    rank-one shift term on the eofa_pm chirality.

    Reference behavior: lib/dirac_mobius.cpp:546 (DiracMobiusEofa::M =
    M5_EOFA - kappa_b D4 D5pre), kernel include/kernels/
    dslash_mobius_eofa.cuh:154-168 (M5_EOFA = M5 + u (x) e_j P_pm).
    """

    def __init__(self, gauge, geom, ls, m5, mf, b5=1.0, c5=0.0,
                 mq1=None, mq2=None, mq3=None, eofa_pm=True,
                 eofa_shift=0.0, antiperiodic_t=True):
        super().__init__(gauge, geom, ls, m5, mf, b5, c5, antiperiodic_t)
        self.s_m5 = _eofa_corrected_m5(self, ls, b5, c5, m5, mf, mq1, mq2,
                                       mq3, eofa_pm, eofa_shift)
        # M() / Mdag() of DiracMobius use self.s_m5 — nothing else changes


class DiracMobiusEofaPC(DiracMobiusPC):
    """4d-even/odd preconditioned Möbius EOFA (symmetric form).

    Reference behavior: lib/dirac_mobius.cpp:626-704 — the Möbius PC
    composition with every M5 / M5^{-1} replaced by the EOFA-corrected
    block; QUDA's m5inv_eofa Sherman-Morrison kernel becomes a dense
    inverse of the corrected chirality blocks.
    """

    def __init__(self, gauge, geom, ls, m5, mf, b5=1.0, c5=0.0,
                 mq1=None, mq2=None, mq3=None, eofa_pm=True,
                 eofa_shift=0.0, antiperiodic_t=True,
                 matpc: int = MATPC_EVEN_EVEN):
        super().__init__(gauge, geom, ls, m5, mf, b5, c5, antiperiodic_t,
                         matpc)
        self.s_m5 = _eofa_corrected_m5(self, ls, b5, c5, m5, mf, mq1, mq2,
                                       mq3, eofa_pm, eofa_shift)
        self.s_m5i = self.s_m5.inv()
        self.s_mix = self.s_m5p @ self.s_m5i


# ---------------------------------------------------------------------------
# 5d-preconditioned (Shamir) domain wall
# ---------------------------------------------------------------------------

class DiracDomainWall5DPC(DiracPC):
    """5d-even/odd preconditioned Shamir domain wall.

    Reference behavior: lib/dirac_domain_wall.cpp:124-176 and
    lib/dslash_domain_wall_5d.cu (QUDA_5D_PC coords): the checkerboard
    parity includes the 5th coordinate, so BOTH the 4-d hops and the
    s-hops flip parity and the single hop operator

        D_5d = hop4 + 2 (P_- S^-(mf) + P_+ S^+(mf))

    appears in a standard Schur complement M_pc = 1 - kappa5^2 D_eo D_oe,
    kappa5 = 1/(2(5 - m5)) (our m5 sign; QUDA's 0.5/(5 + m5)).

    Layout: a 5d-parity-p field is stored (Ls, T, Z, Y, X//2, 4, 3) where
    slice s holds the 4d-parity (p + s) % 2 half-lattice in the standard
    checkerboard slot convention — s-neighbours of the other 5d parity
    then share the slot layout, so the s-hop is elementwise.
    """

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry, ls: int,
                 m5: float, mf: float, antiperiodic_t: bool = True,
                 matpc: int = MATPC_EVEN_EVEN):
        self.geom = geom
        self.ls = ls
        self.mf = mf
        self.matpc = matpc
        self.kappa5 = 0.5 / (5.0 - m5)
        self.m5 = m5
        g = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.gauge_eo = wops.split_gauge_eo(g, geom)

    @staticmethod
    def _p_minus(v):
        """(1 - gamma5)/2 v: lower chirality (spins 2,3)."""
        return v.at[..., 0:2, :].set(0.0)

    @staticmethod
    def _p_plus(v):
        return v.at[..., 2:4, :].set(0.0)

    def _shop(self, psi5, swap_pm: bool):
        """2 (P_- S^- + P_+ S^+) psi (swap_pm: the adjoint's P-swap)."""
        ls, mf = self.ls, self.mf
        up = jnp.roll(psi5, -1, axis=0)    # psi(s+1)
        dn = jnp.roll(psi5, +1, axis=0)    # psi(s-1)
        wrap_up = jnp.asarray([1.0] * (ls - 1) + [-mf], psi5.real.dtype)
        wrap_dn = jnp.asarray([-mf] + [1.0] * (ls - 1), psi5.real.dtype)
        sh = (1,) * 0 + (ls,) + (1,) * (psi5.ndim - 1)
        up = up * wrap_up.reshape(sh).astype(psi5.dtype)
        dn = dn * wrap_dn.reshape(sh).astype(psi5.dtype)
        if swap_pm:
            return 2.0 * (self._p_plus(up) + self._p_minus(dn))
        return 2.0 * (self._p_minus(up) + self._p_plus(dn))

    def _hop4(self, psi5, target_p5: int):
        outs = [wops.dslash_eo(self.gauge_eo, psi5[s], self.geom,
                               (target_p5 + s) % 2)
                for s in range(self.ls)]
        return jnp.stack(outs)

    def D_to(self, psi5, target_p5: int):
        """D_5d from 5d-parity (1-p) to p."""
        return self._hop4(psi5, target_p5) + self._shop(psi5, False)

    def _Ddag_to(self, chi5, target_p5: int):
        g5 = jnp.asarray([1.0, 1.0, -1.0, -1.0], chi5.real.dtype)
        g5 = g5[:, None].astype(chi5.dtype)
        h4 = g5 * self._hop4(g5 * chi5, target_p5)
        return h4 + self._shop(chi5, True)

    def M(self, x_p):
        p = self.matpc
        return x_p - (self.kappa5 ** 2) * self.D_to(
            self.D_to(x_p, 1 - p), p)

    def Mdag(self, x_p):
        p = self.matpc
        return x_p - (self.kappa5 ** 2) * self._Ddag_to(
            self._Ddag_to(x_p, 1 - p), p)

    def flops_per_site_M(self) -> int:
        return 2 * (1320 + 96) + 48  # two 5d hops (4d + s-hop) + axpy

    # -- full-system interface (fields (Ls,T,Z,Y,X,4,3)) ----------------
    def split5(self, psi5_full):
        """Full 5d field -> (even5, odd5) in the slice-aligned layout."""
        from ..fields.spinor import even_odd_split
        ev, od = [], []
        for s in range(self.ls):
            e4, o4 = even_odd_split(psi5_full[s], self.geom)
            if s % 2 == 0:
                ev.append(e4)
                od.append(o4)
            else:
                ev.append(o4)
                od.append(e4)
        return jnp.stack(ev), jnp.stack(od)

    def join5(self, x_even5, x_odd5):
        from ..fields.spinor import even_odd_join
        outs = []
        for s in range(self.ls):
            if s % 2 == 0:
                outs.append(even_odd_join(x_even5[s], x_odd5[s], self.geom))
            else:
                outs.append(even_odd_join(x_odd5[s], x_even5[s], self.geom))
        return jnp.stack(outs)

    def prepare(self, b_even5, b_odd5):
        """Schur rhs for the normalised system (1 - kappa5 D) x = b/(5-m5):
        src = b_p/(5-m5) + kappa5 D_pq b_q/(5-m5)."""
        p = self.matpc
        b_p, b_q = ((b_even5, b_odd5) if p == EVEN
                    else (b_odd5, b_even5))
        scale = 1.0 / (5.0 - self.m5)
        return scale * (b_p + self.kappa5 * self.D_to(b_q, p))

    def reconstruct(self, x_p, b_even5, b_odd5):
        p = self.matpc
        b_q = b_odd5 if p == EVEN else b_even5
        scale = 1.0 / (5.0 - self.m5)
        x_q = scale * b_q + self.kappa5 * self.D_to(x_p, 1 - p)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def pairs(self, store_dtype=jnp.float32, use_pallas: bool = False,
              pallas_interpret: bool = False,
              form: str | None = None
              ) -> "DiracDomainWall5DPCPairs":
        """Complex-free packed companion (the TPU solve path).
        ``form`` / QUDA_TPU_DWF_FORM picks the Ls/2-batched 4d hop
        kernel vs the vmap-over-s stencil (models/formsel)."""
        return DiracDomainWall5DPCPairs(self, store_dtype, use_pallas,
                                        pallas_interpret,
                                        form=form)


class DiracDomainWall5DPCPairs(_LsPairIOMixin, _PackedHopMixin):
    """Complex-free packed pair-form of DiracDomainWall5DPC — with this,
    every PC operator family (4d-PC and 5d-PC alike) solves on TPU
    runtimes without complex64 execution.

    Same slice-aligned 5d-checkerboard layout as the complex class,
    carried as (Ls, 4, 3, 2, T, Z, Y*Xh) pair planes: slice s of a
    5d-parity-p field holds the 4d-parity (p+s)%2 half lattice, so the
    s-hop stays elementwise (rolls + real wrap masks + chirality spin
    masks) and the 4d hop alternates target parity per slice.
    """

    hermitian = False

    def __init__(self, dpc: DiracDomainWall5DPC, store_dtype=jnp.float32,
                 use_pallas: bool = False, pallas_interpret: bool = False,
                 form: str | None = None):
        from ..ops import wilson_packed as wpk
        self._setup_hop(dpc.geom, wpk.pack_gauge_eo(dpc.gauge_eo),
                        store_dtype, use_pallas, pallas_interpret,
                        tb_sign=getattr(dpc, 'antiperiodic_t',
                                        True))
        self.ls = dpc.ls
        self.mf = float(dpc.mf)
        self.m5 = float(dpc.m5)
        self.kappa5 = float(dpc.kappa5)
        self.matpc = dpc.matpc
        from . import formsel
        aux = f"{jnp.dtype(store_dtype).name}|ls{self.ls}|5dpc"

        def _race():
            yxh = self.gauge_eo_pp[0].shape[-1]
            T, Z, _, _ = self.dims
            psi0 = jnp.zeros((self.ls, 4, 3, 2, T, Z, yxh),
                             self.store_dtype)
            cands = {
                "pallas": jax.jit(lambda v: self._hop4_pairs(
                    v, 0, jnp.float32, form="pallas")),
                "xla": jax.jit(lambda v: self._hop4_pairs(
                    v, 0, jnp.float32, form="xla")),
            }
            return formsel.race_forms("dwf", self, cands, (psi0,),
                                      aux=aux)

        self._op_form = formsel.resolve_form("dwf", form, self,
                                             race=_race, aux=aux)

    def _shop_pairs(self, x, swap_pm: bool):
        """2 (P_- S^- + P_+ S^+) on pair planes: s-rolls with the -mf
        wrap mask, chirality selection by spin masking (axis 1)."""
        ls, mf = self.ls, self.mf
        f = x.astype(jnp.float32)
        up = jnp.roll(f, -1, axis=0)
        dn = jnp.roll(f, +1, axis=0)
        sh = (ls, 1, 1, 1, 1, 1, 1)
        up = up * jnp.asarray([1.0] * (ls - 1) + [-mf],
                              jnp.float32).reshape(sh)
        dn = dn * jnp.asarray([-mf] + [1.0] * (ls - 1),
                              jnp.float32).reshape(sh)
        # P_-: keep spins 2,3; P_+: keep spins 0,1 (DeGrand-Rossi)
        lo = jnp.asarray([0.0, 0.0, 1.0, 1.0],
                         jnp.float32).reshape(1, 4, 1, 1, 1, 1, 1)
        hi = 1.0 - lo
        if swap_pm:
            return 2.0 * (hi * up + lo * dn)
        return 2.0 * (lo * up + hi * dn)

    def _hop4_pairs(self, x, target_p5: int, out_dtype, form=None):
        # (target_p5 + s) % 2 takes two values: group the s-slices by
        # parity and hop each group in ONE stencil call (2 launches per
        # hop instead of Ls).  form='pallas': each group rides the
        # Ls-batched kernel (batch INNERMOST, gauge tile resident);
        # form='xla': vmap of the per-slice stencil (batch outermost)
        out = jnp.zeros(x.shape, out_dtype)
        fused = (form or self._op_form) == "pallas"
        for r in (0, 1):
            tp = (target_p5 + r) % 2
            if fused:
                from ..ops import dwf_pallas as dwp
                grp = dwp.dslash_eo_pallas_packed_ls(
                    self.gauge_eo_pp[tp], self._u_bw[tp], x[r::2],
                    tuple(self.dims), tp,
                    interpret=self._pallas_interpret,
                    block_z=getattr(self, "_block_z", None),
                    out_dtype=out_dtype, tb_sign=self._tb_sign)
            else:
                grp = jax.vmap(
                    lambda v, tp=tp: self._d_to(v, tp,
                                                out_dtype))(x[r::2])
            out = out.at[r::2].set(grp)
        return out

    def D_to_pairs(self, x, target_p5: int, out_dtype=None):
        odt = out_dtype or self.store_dtype
        out = (self._hop4_pairs(x, target_p5, jnp.float32)
               + self._shop_pairs(x, False))
        return out.astype(odt)

    def _Ddag_to_pairs(self, x, target_p5: int, out_dtype=None):
        odt = out_dtype or self.store_dtype
        h4 = self._g5(self._hop4_pairs(self._g5(x), target_p5,
                                       jnp.float32))
        out = h4.astype(jnp.float32) + self._shop_pairs(x, True)
        return out.astype(odt)

    def M_pairs(self, x):
        p = self.matpc
        dd = self.D_to_pairs(self.D_to_pairs(x, 1 - p), p,
                             out_dtype=jnp.float32)
        out = x.astype(jnp.float32) - (self.kappa5 ** 2) * dd
        return out.astype(self.store_dtype)

    def Mdag_pairs(self, x):
        p = self.matpc
        dd = self._Ddag_to_pairs(self._Ddag_to_pairs(x, 1 - p), p,
                                 out_dtype=jnp.float32)
        out = x.astype(jnp.float32) - (self.kappa5 ** 2) * dd
        return out.astype(self.store_dtype)

    def MdagM_pairs(self, x):
        return self.Mdag_pairs(self.M_pairs(x))

    def M(self, x):
        return self._from_pairs(self.M_pairs(self._to_pairs(x)), x.dtype)

    def Mdag(self, x):
        return self._from_pairs(self.Mdag_pairs(self._to_pairs(x)),
                                x.dtype)

    def MdagM(self, x):
        return self._from_pairs(self.MdagM_pairs(self._to_pairs(x)),
                                x.dtype)

    def prepare_pairs(self, b_even5, b_odd5):
        """Slice-aligned complex 5d-parity sources -> pair-form rhs
        (mirrors DiracDomainWall5DPC.prepare)."""
        p = self.matpc
        b_p, b_q = ((b_even5, b_odd5) if p == EVEN
                    else (b_odd5, b_even5))
        scale = 1.0 / (5.0 - self.m5)
        t = self.D_to_pairs(self._to_pairs(b_q), p,
                            out_dtype=jnp.float32)
        rhs = scale * (self._to_pairs(b_p).astype(jnp.float32)
                       + self.kappa5 * t)
        return rhs.astype(self.store_dtype)

    def reconstruct_pairs(self, x_pp, b_even5, b_odd5):
        p = self.matpc
        b_q = b_odd5 if p == EVEN else b_even5
        scale = 1.0 / (5.0 - self.m5)
        t = self.D_to_pairs(x_pp, 1 - p, out_dtype=jnp.float32)
        xq_pp = (scale * self._to_pairs(b_q).astype(jnp.float32)
                 + self.kappa5 * t)
        x_p = self._from_pairs(x_pp, b_q.dtype)
        x_q = self._from_pairs(xq_pp, b_q.dtype)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    # the generic invert flow's 5d split/join hooks (see _split/_join)
    def split5(self, psi5_full):
        return DiracDomainWall5DPC.split5(self, psi5_full)

    def join5(self, x_even5, x_odd5):
        return DiracDomainWall5DPC.join5(self, x_even5, x_odd5)
