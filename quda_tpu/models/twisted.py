"""Twisted-mass and twisted-clover Dirac operators (degenerate and
non-degenerate doublet).

Reference behavior: lib/dirac_twisted_mass.cpp, lib/dirac_twisted_clover.cpp
(+ the ndeg variants).  Kappa normalisation with the twist folded into the
diagonal:

    degenerate:      M = (1 + i a gamma5) - kappa D,    a = 2 kappa mu
    non-degenerate:  M = (1 + i a gamma5 tau3 - b tau1) - kappa D,
                     a = 2 kappa mu, b = 2 kappa epsilon   (flavor doublet)
    twisted clover:  M = (A + i a gamma5) - kappa D       (A = clover term)

gamma5 is diag(+1,+1,-1,-1) in the DeGrand-Rossi basis, so the twist is a
per-chirality complex scale — on TPU it fuses into the surrounding
elementwise chain; the clover+twist diagonal stays two 6x6 blocks with
+-i*a added to the diagonal.

The twisted operators obey gamma5 M(mu) gamma5 = M(-mu)^dag, so MdagM for
CG uses the explicit Mdag (twist sign flip) rather than the g5 trick.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..fields.geometry import EVEN, LatticeGeometry
from ..fields.spinor import even_odd_split
from ..ops import wilson as wops
from ..ops.boundary import apply_t_boundary
from ..ops.clover import apply_clover, clover_blocks, invert_clover
from .dirac import Dirac, DiracPC, MATPC_EVEN_EVEN, apply_gamma5
from .wilson import _SchurPairOpBase


def _twist_apply(psi, a: float, sign: int = +1):
    """(1 + i sign a gamma5) psi."""
    return psi + (1j * sign * a) * apply_gamma5(psi)


def _twist_inv(psi, a: float, sign: int = +1):
    """(1 + i sign a gamma5)^{-1} psi = (1 - i sign a gamma5)/(1+a^2) psi."""
    return (psi - (1j * sign * a) * apply_gamma5(psi)) / (1.0 + a * a)


class DiracTwistedMass(Dirac):
    """Degenerate twisted-mass operator on full lattice."""

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, mu: float, antiperiodic_t: bool = True):
        self.geom = geom
        self.kappa = kappa
        self.mu = mu
        self.a = 2.0 * kappa * mu
        self.gauge = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t

    def D(self, psi):
        return wops.dslash_full(self.gauge, psi)

    def M(self, psi):
        return _twist_apply(psi, self.a) - self.kappa * self.D(psi)

    def Mdag(self, psi):
        # gamma5 M(mu) gamma5 = M(-mu)^dag  =>  Mdag = g5 M(-mu) g5
        out = _twist_apply(psi, self.a, -1) - self.kappa * apply_gamma5(
            self.D(apply_gamma5(psi)))
        return out

    def flops_per_site_M(self) -> int:
        return 1320 + 96  # dslash + twist scale + axpy


class DiracTwistedMassPC(DiracPC):
    """Even/odd preconditioned degenerate twisted mass.

    M_pc x = (1 + i a g5) x - kappa^2 D (1 + i a g5)^{-1} D x
    """

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, mu: float, antiperiodic_t: bool = True,
                 matpc: int = MATPC_EVEN_EVEN):
        self.geom = geom
        self.kappa = kappa
        self.mu = mu
        self.a = 2.0 * kappa * mu
        self.matpc = matpc
        g = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.gauge_eo = wops.split_gauge_eo(g, geom)

    def D_to(self, psi, target_parity):
        return wops.dslash_eo(self.gauge_eo, psi, self.geom, target_parity)

    def _M_sign(self, x_p, sign):
        p = self.matpc
        tmp = _twist_inv(self.D_to(x_p, 1 - p), self.a, sign)
        return (_twist_apply(x_p, self.a, sign)
                - (self.kappa ** 2) * self.D_to(tmp, p))

    def M(self, x_p):
        return self._M_sign(x_p, +1)

    def Mdag(self, x_p):
        return apply_gamma5(self._M_sign(apply_gamma5(x_p), -1))

    def prepare(self, b_even, b_odd):
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        return b_p + self.kappa * self.D_to(_twist_inv(b_q, self.a), p)

    def reconstruct(self, x_p, b_even, b_odd):
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        x_q = _twist_inv(b_q + self.kappa * self.D_to(x_p, 1 - p), self.a)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def flops_per_site_M(self) -> int:
        return 2 * 1320 + 192  # two hops + twist apply/inverse + axpy

    def pairs(self, store_dtype=jnp.float32, use_pallas: bool = False,
              pallas_interpret: bool = False,
              form: str | None = None) -> "DiracTwistedMassPCPairs":
        """Complex-free packed companion (f32 = the precise TPU solve
        path; bf16 = the sloppy operator).  ``form`` /
        QUDA_TPU_TWISTED_FORM picks the fused-twist pallas kernel vs
        the staged XLA composition (models/formsel)."""
        return DiracTwistedMassPCPairs(self, store_dtype, use_pallas,
                                       pallas_interpret,
                                       form=form)


def _ig5_rot_pairs(x_pp: jnp.ndarray, c: float) -> jnp.ndarray:
    """i c gamma5 on packed pair arrays (4,3,2,T,Z,YXh) at f32:
    i*gamma5 rotates (re,im) -> (-g5*im, g5*re) with g5 = (+,+,-,-)."""
    f = x_pp.astype(jnp.float32)
    g5 = jnp.asarray([1.0, 1.0, -1.0, -1.0],
                     jnp.float32).reshape(4, 1, 1, 1, 1)
    xr, xi = f[:, :, 0], f[:, :, 1]
    return jnp.stack([-c * g5 * xi, c * g5 * xr], axis=2)


def _twist_pairs(x_pp: jnp.ndarray, a: float, sign: int,
                 out_dtype=None) -> jnp.ndarray:
    """(1 + i sign a gamma5) on packed pair arrays."""
    out = x_pp.astype(jnp.float32) + _ig5_rot_pairs(x_pp, sign * a)
    return out.astype(out_dtype or x_pp.dtype)


def _twist_inv_pairs(x_pp: jnp.ndarray, a: float, sign: int,
                     out_dtype=None) -> jnp.ndarray:
    """(1 + i sign a gamma5)^{-1} on packed pair arrays."""
    inv = _twist_pairs(x_pp, a, -sign, out_dtype=jnp.float32)
    return (inv / (1.0 + a * a)).astype(out_dtype or x_pp.dtype)


class DiracTwistedMassPCPairs(_SchurPairOpBase):
    """Complex-free packed pair-form of DiracTwistedMassPC: the twist
    (1 + i a g5) is a pure (re,im) rotation per chirality — no complex
    arithmetic survives anywhere (TPU runtimes without complex64).
    Hop/Schur/prepare/reconstruct come from _SchurPairOpBase; the
    template's Mdag = g5 M(-s) g5 is exactly the twisted dagger."""

    def __init__(self, dpc: "DiracTwistedMassPC", store_dtype=jnp.float32,
                 use_pallas: bool = False, pallas_interpret: bool = False,
                 form: str | None = None):
        from ..ops import wilson_packed as wpk
        self._setup_hop(dpc.geom, wpk.pack_gauge_eo(dpc.gauge_eo),
                        store_dtype, use_pallas, pallas_interpret,
                        tb_sign=getattr(dpc, 'antiperiodic_t',
                                        True))
        self.kappa = float(dpc.kappa)
        self.a = float(dpc.a)
        self.matpc = dpc.matpc
        from . import formsel
        aux = jnp.dtype(store_dtype).name
        self._op_form = formsel.resolve_form(
            "twisted", form, self,
            race=lambda: formsel.race_schur("twisted", self, aux=aux),
            aux=aux)

    def _diag_sign_pairs(self, x, sign, out_dtype):
        return _twist_pairs(x, self.a, sign, out_dtype)

    def _Ainv_q_sign_pairs(self, x, sign, out_dtype):
        return _twist_inv_pairs(x, self.a, sign, out_dtype)

    # fused-epilogue descriptors: the twist is two STATIC scalars — K1
    # applies (1 + i s a g5)^{-1} = (v + i(-s a) g5 v)/(1+a^2) post-hop
    # in-register, K2 adds i (s a) g5 x to the original x (no blocks)
    def _fused_k1_params(self, sign):
        a = self.a
        return None, (-sign * a, 1.0 / (1.0 + a * a))

    def _fused_k2_params(self, sign):
        return None, sign * self.a


class DiracTwistedCloverPCPairs(_SchurPairOpBase):
    """Complex-free packed pair-form of DiracTwistedCloverPC: clover
    blocks and the +-sign twisted inverses live as resident pair-form
    chiral 6x6 blocks (models/clover.apply_clover_pairs)."""

    def __init__(self, dpc: "DiracTwistedCloverPC",
                 store_dtype=jnp.float32, use_pallas: bool = False,
                 pallas_interpret: bool = False,
                 form: str | None = None):
        from ..ops import wilson_packed as wpk
        from .clover import pack_clover_pairs
        self._setup_hop(dpc.geom, wpk.pack_gauge_eo(dpc.gauge_eo),
                        store_dtype, use_pallas, pallas_interpret,
                        tb_sign=getattr(dpc, 'antiperiodic_t',
                                        True))
        self.kappa = float(dpc.kappa)
        self.a = float(dpc.a)
        self.matpc = dpc.matpc
        self.clover_p_pp = pack_clover_pairs(dpc.clover[dpc.matpc],
                                             store_dtype)
        self.tw_inv_q_pp = {
            s: pack_clover_pairs(dpc.tw_inv_q[s], store_dtype)
            for s in (+1, -1)}
        from ..obs import memory as omem
        omem.track("clover", "tw_clover_pair_blocks",
                   (self.clover_p_pp,) + tuple(
                       self.tw_inv_q_pp[s] for s in (+1, -1)))
        from . import formsel
        aux = jnp.dtype(store_dtype).name
        self._op_form = formsel.resolve_form(
            "twisted", form, self,
            race=lambda: formsel.race_schur("twisted", self, aux=aux),
            aux=aux)

    def _diag_sign_pairs(self, x, sign, out_dtype):
        # A + i s a g5: clover matvec plus the direct twist rotation
        from .clover import apply_clover_pairs
        out = (apply_clover_pairs(self.clover_p_pp, x, jnp.float32)
               + _ig5_rot_pairs(x, sign * self.a))
        return out.astype(out_dtype)

    def _Ainv_q_sign_pairs(self, x, sign, out_dtype):
        from .clover import apply_clover_pairs
        return apply_clover_pairs(self.tw_inv_q_pp[sign], x, out_dtype)

    # fused-epilogue descriptors: K1 = the dense (A_q + i s a g5)^{-1}
    # blocks (the twist is already folded into them), K2 = A_p blocks
    # plus the in-register i (s a) g5 rotation of the original x
    def _fused_k1_params(self, sign):
        return self.tw_inv_q_pp[sign], None

    def _fused_k2_params(self, sign):
        return self.clover_p_pp, sign * self.a


class _NdegPairsBase(_SchurPairOpBase):
    """Flavor-doublet pair-form base: spinors (2, 4, 3, 2, T, Z, Y*Xh)
    with the flavor axis leading; the hop is the mixin's eo stencil
    vmapped over flavor, and gamma5 acts on spin axis 1.

    The doublet families keep the staged XLA composition (_op_form
    stays 'xla'): the -b tau1 flavor mixing couples the two flavor
    planes, which is not expressible as the per-plane epilogue the
    fused kernels implement — QUDA_TPU_TWISTED_FORM=pallas therefore
    only governs the degenerate operators."""

    _spin_axis = 1

    def _d_to(self, psi_pp, target_parity, out_dtype):
        import jax
        return jax.vmap(lambda v: super(_NdegPairsBase, self)._d_to(
            v, target_parity, out_dtype))(psi_pp)

    def _to_pairs(self, x):
        """Canonical (T,Z,Y,Xh,2,4,3) complex -> flavor-leading packed
        pairs."""
        import jax
        from ..ops import wilson_packed as wpk
        xf = jnp.moveaxis(x, -3, 0)            # (2,T,Z,Y,Xh,4,3)
        packed = jax.vmap(wpk.pack_spinor)(xf)
        return wpk.to_packed_pairs(packed, self.store_dtype)

    def _from_pairs(self, x, dtype):
        import jax
        from ..ops import wilson_packed as wpk
        T, Z, Y, X = self.dims
        c = wpk.from_packed_pairs(x, dtype)
        xf = jax.vmap(lambda v: wpk.unpack_spinor(v, (T, Z, Y, X // 2)))(c)
        return jnp.moveaxis(xf, 0, -3)


class DiracNdegTwistedMassPCPairs(_NdegPairsBase):
    """Complex-free pair-form of DiracNdegTwistedMassPC: the flavor 2x2
    diagonal (1 + i a g5 tau3 - b tau1) and its closed-form inverse are
    (re,im) rotations plus a real flavor swap."""

    def __init__(self, dpc: "DiracNdegTwistedMassPC",
                 store_dtype=jnp.float32, use_pallas: bool = False,
                 pallas_interpret: bool = False,
                 form: str | None = None):
        from ..ops import wilson_packed as wpk
        from . import formsel
        self._setup_hop(dpc.geom, wpk.pack_gauge_eo(dpc.gauge_eo),
                        store_dtype, use_pallas, pallas_interpret,
                        tb_sign=getattr(dpc, 'antiperiodic_t',
                                        True))
        self._op_form = formsel.resolve_ndeg(form)
        self.kappa = float(dpc.kappa)
        self.a = float(dpc.a)
        self.b = float(dpc.b)
        self.matpc = dpc.matpc

    def _diag_sign_pairs(self, x, sign, out_dtype):
        f = x.astype(jnp.float32)
        up, dn = f[0], f[1]
        out = jnp.stack(
            [up + _ig5_rot_pairs(up, sign * self.a) - self.b * dn,
             dn + _ig5_rot_pairs(dn, -sign * self.a) - self.b * up])
        return out.astype(out_dtype)

    def _Ainv_q_sign_pairs(self, x, sign, out_dtype):
        f = x.astype(jnp.float32)
        up, dn = f[0], f[1]
        det = 1.0 + self.a ** 2 - self.b ** 2
        out = jnp.stack(
            [up + _ig5_rot_pairs(up, -sign * self.a) + self.b * dn,
             self.b * up + dn + _ig5_rot_pairs(dn, sign * self.a)]) / det
        return out.astype(out_dtype)


class DiracNdegTwistedCloverPCPairs(_NdegPairsBase):
    """Complex-free pair-form of DiracNdegTwistedCloverPC: the clover
    term, and the commuting-6x6-block closed-form flavor inverse
    (A^2 + a^2 - b^2)^{-1} [[A - i s a g5, b], [b, A + i s a g5]], live
    as resident pair-form chiral blocks."""

    def __init__(self, dpc: "DiracNdegTwistedCloverPC",
                 store_dtype=jnp.float32, use_pallas: bool = False,
                 pallas_interpret: bool = False,
                 form: str | None = None):
        from ..ops import wilson_packed as wpk
        from . import formsel
        from .clover import pack_clover_pairs
        self._setup_hop(dpc.geom, wpk.pack_gauge_eo(dpc.gauge_eo),
                        store_dtype, use_pallas, pallas_interpret,
                        tb_sign=getattr(dpc, 'antiperiodic_t',
                                        True))
        self._op_form = formsel.resolve_ndeg(form)
        self.kappa = float(dpc.kappa)
        self.a = float(dpc.a)
        self.b = float(dpc.b)
        self.matpc = dpc.matpc
        self.clover_p_pp = pack_clover_pairs(dpc.clover[dpc.matpc],
                                             store_dtype)
        self.clover_q_pp = pack_clover_pairs(dpc.clover[1 - dpc.matpc],
                                             store_dtype)
        self.dinv_q_pp = pack_clover_pairs(dpc.dinv_q, store_dtype)

    def _diag_sign_pairs(self, x, sign, out_dtype):
        from .clover import apply_clover_pairs
        f = x.astype(jnp.float32)
        up, dn = f[0], f[1]
        out = jnp.stack(
            [apply_clover_pairs(self.clover_p_pp, up, jnp.float32)
             + _ig5_rot_pairs(up, sign * self.a) - self.b * dn,
             apply_clover_pairs(self.clover_p_pp, dn, jnp.float32)
             + _ig5_rot_pairs(dn, -sign * self.a) - self.b * up])
        return out.astype(out_dtype)

    def _Ainv_q_sign_pairs(self, x, sign, out_dtype):
        from .clover import apply_clover_pairs
        f = x.astype(jnp.float32)
        up, dn = f[0], f[1]
        nu = (apply_clover_pairs(self.clover_q_pp, up, jnp.float32)
              + _ig5_rot_pairs(up, -sign * self.a) + self.b * dn)
        nd = (self.b * up
              + apply_clover_pairs(self.clover_q_pp, dn, jnp.float32)
              + _ig5_rot_pairs(dn, sign * self.a))
        out = jnp.stack(
            [apply_clover_pairs(self.dinv_q_pp, nu, jnp.float32),
             apply_clover_pairs(self.dinv_q_pp, nd, jnp.float32)])
        return out.astype(out_dtype)


class DiracNdegTwistedMass(Dirac):
    """Non-degenerate twisted doublet; fields carry a flavor axis:
    (T,Z,Y,X, flavor=2, 4, 3).

    M = (1 + i a g5 tau3 - b tau1) - kappa D   (D flavor-diagonal).
    """

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, mu: float, epsilon: float,
                 antiperiodic_t: bool = True):
        self.geom = geom
        self.kappa = kappa
        self.a = 2.0 * kappa * mu
        self.b = 2.0 * kappa * epsilon
        self.gauge = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t

    def D(self, psi):
        # vmap over the flavor axis (axis -3)
        lat = psi.shape[:4]
        merged = jnp.moveaxis(psi, 4, 0)  # (2, T,Z,Y,X,4,3)
        out = jnp.stack([wops.dslash_full(self.gauge, merged[f])
                         for f in range(2)])
        return jnp.moveaxis(out, 0, 4)

    def _diag(self, psi, sign=+1):
        up = psi[..., 0, :, :]
        dn = psi[..., 1, :, :]
        up_out = up + (1j * sign * self.a) * apply_gamma5(up) - self.b * dn
        dn_out = dn - (1j * sign * self.a) * apply_gamma5(dn) - self.b * up
        return jnp.stack([up_out, dn_out], axis=-3)

    def M(self, psi):
        return self._diag(psi) - self.kappa * self.D(psi)

    def Mdag(self, psi):
        d5 = apply_gamma5(self.D(apply_gamma5(psi)))
        return self._diag(psi, -1) - self.kappa * d5


class DiracTwistedClover(Dirac):
    """Twisted clover: M = (A + i a gamma5) - kappa D."""

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, mu: float, csw: float,
                 antiperiodic_t: bool = True):
        self.geom = geom
        self.kappa = kappa
        self.a = 2.0 * kappa * mu
        self.gauge = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.clover = clover_blocks(gauge, kappa * csw / 2.0)
        from ..obs import memory as omem
        omem.track("clover", "tw_clover_blocks", self.clover)

    def D(self, psi):
        return wops.dslash_full(self.gauge, psi)

    def _A_tw(self, psi, sign=+1):
        return apply_clover(self.clover, psi) + (
            1j * sign * self.a) * apply_gamma5(psi)

    def M(self, psi):
        return self._A_tw(psi) - self.kappa * self.D(psi)

    def Mdag(self, psi):
        return self._A_tw(psi, -1) - self.kappa * apply_gamma5(
            self.D(apply_gamma5(psi)))


def twisted_clover_blocks(clover, a: float, sign: int = +1):
    """Chiral blocks of A + i sign a gamma5: gamma5 = +-1 per chirality."""
    eye = jnp.eye(6, dtype=clover.dtype)
    up = clover[..., 0, :, :] + (1j * sign * a) * eye
    dn = clover[..., 1, :, :] - (1j * sign * a) * eye
    return jnp.stack([up, dn], axis=-3)


class DiracTwistedCloverPC(DiracPC):
    """Even/odd preconditioned twisted clover (asymmetric):
    M_pc = (A_p + i a g5) - kappa^2 D (A_q + i a g5)^{-1} D.

    The twisted diagonal is NOT Hermitian, so its inverse uses the general
    6x6 solve rather than Cholesky (QUDA inverts the twisted clover with
    the same Cholesky trick on A^dag A; a direct batched inverse is simpler
    and XLA-batched).
    """

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, mu: float, csw: float,
                 antiperiodic_t: bool = True, matpc: int = MATPC_EVEN_EVEN):
        self.geom = geom
        self.kappa = kappa
        self.a = 2.0 * kappa * mu
        self.matpc = matpc
        g = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.gauge_eo = wops.split_gauge_eo(g, geom)
        blocks = clover_blocks(gauge, kappa * csw / 2.0)
        a_e, a_o = even_odd_split(blocks, geom)
        self.clover = (a_e, a_o)
        from ..obs import memory as omem
        omem.track("clover", "tw_clover_eo_blocks", self.clover)
        q = 1 - matpc
        self.tw_inv_q = {
            +1: jnp.linalg.inv(twisted_clover_blocks(self.clover[q],
                                                     self.a, +1)),
            -1: jnp.linalg.inv(twisted_clover_blocks(self.clover[q],
                                                     self.a, -1)),
        }

    def D_to(self, psi, target_parity):
        return wops.dslash_eo(self.gauge_eo, psi, self.geom, target_parity)

    def _A_p(self, x, sign=+1):
        return apply_clover(self.clover[self.matpc], x) + (
            1j * sign * self.a) * apply_gamma5(x)

    def _Ainv_q(self, x, sign=+1):
        return apply_clover(self.tw_inv_q[sign], x)

    def _M_sign(self, x_p, sign):
        p = self.matpc
        tmp = self._Ainv_q(self.D_to(x_p, 1 - p), sign)
        return self._A_p(x_p, sign) - (self.kappa ** 2) * self.D_to(tmp, p)

    def M(self, x_p):
        return self._M_sign(x_p, +1)

    def Mdag(self, x_p):
        return apply_gamma5(self._M_sign(apply_gamma5(x_p), -1))

    def prepare(self, b_even, b_odd):
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        return b_p + self.kappa * self.D_to(self._Ainv_q(b_q), p)

    def reconstruct(self, x_p, b_even, b_odd):
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        x_q = self._Ainv_q(b_q + self.kappa * self.D_to(x_p, 1 - p))
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def pairs(self, store_dtype=jnp.float32, use_pallas: bool = False,
              pallas_interpret: bool = False,
              form: str | None = None) -> "DiracTwistedCloverPCPairs":
        """Complex-free packed companion (f32 = the precise TPU solve
        path; bf16 = the sloppy operator).  ``form`` /
        QUDA_TPU_TWISTED_FORM picks the fused blocks+twist pallas
        kernel vs the staged XLA composition (models/formsel)."""
        return DiracTwistedCloverPCPairs(self, store_dtype, use_pallas,
                                         pallas_interpret,
                                         form=form)


class DiracNdegTwistedClover(Dirac):
    """Non-degenerate twisted clover on flavor-doublet fields
    (T,Z,Y,X,2,4,3):  M = (A + i a g5 tau3 - b tau1) - kappa D.

    Reference behavior: lib/dirac_twisted_clover.cpp (ndeg path) and
    lib/dslash_ndeg_twisted_clover.cu — the clover term A is flavor
    diagonal; the twist is +i a g5 on the up flavor, -i a g5 on down;
    -b tau1 swaps flavors.
    """

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, mu: float, epsilon: float, csw: float,
                 antiperiodic_t: bool = True):
        self.geom = geom
        self.kappa = kappa
        self.a = 2.0 * kappa * mu
        self.b = 2.0 * kappa * epsilon
        self.gauge = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.clover = clover_blocks(gauge, kappa * csw / 2.0)
        from ..obs import memory as omem
        omem.track("clover", "ndeg_tw_clover_blocks", self.clover)

    def D(self, psi):
        out = jnp.stack([wops.dslash_full(self.gauge, psi[..., f, :, :])
                         for f in range(2)])
        return jnp.moveaxis(out, 0, 4)

    def _diag(self, psi, sign=+1):
        up = psi[..., 0, :, :]
        dn = psi[..., 1, :, :]
        up_out = (apply_clover(self.clover, up)
                  + (1j * sign * self.a) * apply_gamma5(up) - self.b * dn)
        dn_out = (apply_clover(self.clover, dn)
                  - (1j * sign * self.a) * apply_gamma5(dn) - self.b * up)
        return jnp.stack([up_out, dn_out], axis=-3)

    def M(self, psi):
        return self._diag(psi) - self.kappa * self.D(psi)

    def Mdag(self, psi):
        # M(mu)^dag = g5 M(-mu) g5 flavor-wise (A Hermitian, tau1 real)
        d5 = apply_gamma5(self.D(apply_gamma5(psi)))
        return self._diag(psi, -1) - self.kappa * d5

    def flops_per_site_M(self) -> int:
        return 2 * (1320 + 504) + 144  # per flavor: dslash + clover


class DiracNdegTwistedCloverPC(DiracPC):
    """Even/odd preconditioned non-degenerate twisted clover (asymmetric):

        M_pc = Diag_p - kappa^2 D Diag_q^{-1} D

    with Diag = A + i a g5 tau3 - b tau1.  Because A commutes with g5
    (both chirality-block structured) the flavor 2x2 inverse closes over
    commuting 6x6 blocks:

        Diag^{-1} = [[A_s - i s a, b], [b, A_s + i s a]] (A_s^2 + a^2 - b^2)^{-1}

    per chirality s = +-1 — batched 6x6 inverses instead of QUDA's
    Cholesky-on-A^dag-A kernels (lib/clover_invert.cu ndeg path).
    """

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, mu: float, epsilon: float, csw: float,
                 antiperiodic_t: bool = True, matpc: int = MATPC_EVEN_EVEN):
        self.geom = geom
        self.kappa = kappa
        self.a = 2.0 * kappa * mu
        self.b = 2.0 * kappa * epsilon
        self.matpc = matpc
        g = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.gauge_eo = wops.split_gauge_eo(g, geom)
        blocks = clover_blocks(gauge, kappa * csw / 2.0)
        a_e, a_o = even_odd_split(blocks, geom)
        self.clover = (a_e, a_o)
        from ..obs import memory as omem
        omem.track("clover", "ndeg_tw_clover_eo_blocks", self.clover)
        q = 1 - matpc
        aq = self.clover[q]
        eye = jnp.eye(6, dtype=aq.dtype)
        denom = (jnp.einsum("...ij,...jk->...ik", aq, aq)
                 + (self.a ** 2 - self.b ** 2) * eye)
        self.dinv_q = jnp.linalg.inv(denom)

    def D_to(self, psi, target_parity):
        out = jnp.stack([
            wops.dslash_eo(self.gauge_eo, psi[..., f, :, :], self.geom,
                           target_parity) for f in range(2)])
        return jnp.moveaxis(out, 0, 4)

    def _diag_p(self, x, sign=+1):
        up = x[..., 0, :, :]
        dn = x[..., 1, :, :]
        ap = self.clover[self.matpc]
        up_out = (apply_clover(ap, up)
                  + (1j * sign * self.a) * apply_gamma5(up) - self.b * dn)
        dn_out = (apply_clover(ap, dn)
                  - (1j * sign * self.a) * apply_gamma5(dn) - self.b * up)
        return jnp.stack([up_out, dn_out], axis=-3)

    def _diag_inv_q(self, x, sign=+1):
        """Apply Diag_q^{-1}(sign * a) to a flavor-doublet parity field."""
        aq = self.clover[1 - self.matpc]
        up = x[..., 0, :, :]
        dn = x[..., 1, :, :]
        # numerator: [[A - i s a g5, b], [b, A + i s a g5]]
        nu = (apply_clover(aq, up)
              - (1j * sign * self.a) * apply_gamma5(up) + self.b * dn)
        nd = (self.b * up + apply_clover(aq, dn)
              + (1j * sign * self.a) * apply_gamma5(dn))
        out = jnp.stack([apply_clover(self.dinv_q, nu),
                         apply_clover(self.dinv_q, nd)], axis=-3)
        return out

    def _M_sign(self, x_p, sign):
        p = self.matpc
        tmp = self._diag_inv_q(self.D_to(x_p, 1 - p), sign)
        return self._diag_p(x_p, sign) - (self.kappa ** 2) * self.D_to(tmp, p)

    def M(self, x_p):
        return self._M_sign(x_p, +1)

    def Mdag(self, x_p):
        return apply_gamma5(self._M_sign(apply_gamma5(x_p), -1))

    def prepare(self, b_even, b_odd):
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        return b_p + self.kappa * self.D_to(self._diag_inv_q(b_q), p)

    def reconstruct(self, x_p, b_even, b_odd):
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        x_q = self._diag_inv_q(b_q + self.kappa * self.D_to(x_p, 1 - p))
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def pairs(self, store_dtype=jnp.float32, use_pallas: bool = False,
              pallas_interpret: bool = False,
              form: str | None = None
              ) -> "DiracNdegTwistedCloverPCPairs":
        """Complex-free packed companion (flavor-doublet pair form).
        ``form`` is validated but always resolves to the staged
        composition — the doublet has no fused kernel
        (models/formsel.resolve_ndeg)."""
        return DiracNdegTwistedCloverPCPairs(self, store_dtype,
                                             use_pallas,
                                             pallas_interpret,
                                             form=form)


class DiracNdegTwistedMassPC(DiracPC):
    """Even/odd preconditioned non-degenerate twisted mass (asymmetric):
    the flavor-diagonal inverse is closed-form elementwise,

        Diag^{-1} = [[1 - i a g5, b], [b, 1 + i a g5]] / (1 + a^2 - b^2)

    (lib/dslash_ndeg_twisted_mass_preconditioned.cu behavior; no clover
    machinery needed)."""

    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, mu: float, epsilon: float,
                 antiperiodic_t: bool = True, matpc: int = MATPC_EVEN_EVEN):
        self.geom = geom
        self.kappa = kappa
        self.a = 2.0 * kappa * mu
        self.b = 2.0 * kappa * epsilon
        self.matpc = matpc
        g = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.antiperiodic_t = antiperiodic_t
        self.gauge_eo = wops.split_gauge_eo(g, geom)

    def D_to(self, psi, target_parity):
        out = jnp.stack([
            wops.dslash_eo(self.gauge_eo, psi[..., f, :, :], self.geom,
                           target_parity) for f in range(2)])
        return jnp.moveaxis(out, 0, 4)

    def _diag(self, x, sign=+1):
        up = x[..., 0, :, :]
        dn = x[..., 1, :, :]
        return jnp.stack(
            [up + (1j * sign * self.a) * apply_gamma5(up) - self.b * dn,
             dn - (1j * sign * self.a) * apply_gamma5(dn) - self.b * up],
            axis=-3)

    def _diag_inv(self, x, sign=+1):
        up = x[..., 0, :, :]
        dn = x[..., 1, :, :]
        det = 1.0 + self.a ** 2 - self.b ** 2
        nu = up - (1j * sign * self.a) * apply_gamma5(up) + self.b * dn
        nd = self.b * up + dn + (1j * sign * self.a) * apply_gamma5(dn)
        return jnp.stack([nu, nd], axis=-3) / det

    def _M_sign(self, x_p, sign):
        p = self.matpc
        tmp = self._diag_inv(self.D_to(x_p, 1 - p), sign)
        return self._diag(x_p, sign) - (self.kappa ** 2) * self.D_to(tmp, p)

    def M(self, x_p):
        return self._M_sign(x_p, +1)

    def Mdag(self, x_p):
        return apply_gamma5(self._M_sign(apply_gamma5(x_p), -1))

    def prepare(self, b_even, b_odd):
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        return b_p + self.kappa * self.D_to(self._diag_inv(b_q), p)

    def reconstruct(self, x_p, b_even, b_odd):
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        x_q = self._diag_inv(b_q + self.kappa * self.D_to(x_p, 1 - p))
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def flops_per_site_M(self) -> int:
        return 2 * (2 * 1320) + 384  # two flavor hops each parity + twist

    def pairs(self, store_dtype=jnp.float32, use_pallas: bool = False,
              pallas_interpret: bool = False,
              form: str | None = None
              ) -> "DiracNdegTwistedMassPCPairs":
        """Complex-free packed companion (flavor-doublet pair form).
        ``form`` is validated but always resolves to the staged
        composition — the doublet has no fused kernel
        (models/formsel.resolve_ndeg)."""
        return DiracNdegTwistedMassPCPairs(self, store_dtype, use_pallas,
                                           pallas_interpret, form=form)
