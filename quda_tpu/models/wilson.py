"""Wilson Dirac operator (full and even/odd preconditioned).

Reference behavior: lib/dirac_wilson.cpp (DiracWilson::M at :112,
DiracWilsonPC prepare/reconstruct) with kappa normalisation
M = 1 - kappa * D.  PC operator on parity p:

    M_pc x_p = x_p - kappa^2 D_{p,1-p} D_{1-p,p} x_p

with source preparation b_pc = b_p + kappa D_{p,1-p} b_{1-p} and
reconstruction x_{1-p} = b_{1-p} + kappa D_{1-p,p} x_p
(QUDA DiracWilsonPC::prepare / reconstruct, lib/dirac_wilson.cpp:175-220).
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from ..fields.geometry import EVEN, LatticeGeometry
from ..ops import wilson as wops
from ..ops.boundary import apply_t_boundary
from .dirac import Dirac, DiracPC, MATPC_EVEN_EVEN


class DiracWilson(Dirac):
    """Full-lattice Wilson operator M = 1 - kappa D."""

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, antiperiodic_t: bool = True):
        self.geom = geom
        self.kappa = kappa
        self.gauge = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)

    def D(self, psi):
        return wops.dslash_full(self.gauge, psi)

    def M(self, psi):
        return psi - self.kappa * self.D(psi)

    # --- diag + per-direction hop decomposition (MG coarsening probes) ---
    def diag(self, psi):
        return psi

    def hop(self, psi, mu, sign):
        """-kappa * single-direction Wilson hop (M = diag + sum hops)."""
        from ..ops.gamma import PROJ_MINUS, PROJ_PLUS
        from ..ops.shift import shift
        from ..ops.su3 import dagger
        if sign > 0:
            u = self.gauge[mu]
            proj = jnp.asarray(PROJ_MINUS[mu], psi.dtype)
            h = jnp.einsum("...ab,...sb->...sa", u, shift(psi, mu, +1))
        else:
            u = shift(dagger(self.gauge[mu]), mu, -1)
            proj = jnp.asarray(PROJ_PLUS[mu], psi.dtype)
            h = jnp.einsum("...ab,...sb->...sa", u, shift(psi, mu, -1))
        return -self.kappa * jnp.einsum("st,...tc->...sc", proj, h)

    def flops_per_site_M(self) -> int:
        return 1320 + 48  # dslash + axpy (include/dslash.h:475 flop model)


class DiracWilsonPC(DiracPC):
    """Even/odd preconditioned Wilson operator on parity ``matpc``."""

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry,
                 kappa: float, antiperiodic_t: bool = True,
                 matpc: int = MATPC_EVEN_EVEN):
        self.geom = geom
        self.kappa = kappa
        self.matpc = matpc
        self.antiperiodic_t = antiperiodic_t
        g = apply_t_boundary(gauge, geom, -1 if antiperiodic_t else 1)
        self.gauge_eo = wops.split_gauge_eo(g, geom)

    @classmethod
    def from_eo(cls, gauge_eo, geom: LatticeGeometry, kappa: float,
                matpc: int = MATPC_EVEN_EVEN):
        """Construct from pre-split (even,odd) link storage (e.g. sharded
        arrays passed through a jit boundary)."""
        self = object.__new__(cls)
        self.geom = geom
        self.kappa = kappa
        self.matpc = matpc
        self.antiperiodic_t = True
        self.gauge_eo = gauge_eo
        return self

    def D_to(self, psi, target_parity):
        """Hop from parity (1-target) into target parity."""
        return wops.dslash_eo(self.gauge_eo, psi, self.geom, target_parity)

    def M(self, x_p):
        p = self.matpc
        tmp = self.D_to(x_p, 1 - p)
        return x_p - (self.kappa ** 2) * self.D_to(tmp, p)

    def prepare(self, b_even, b_odd):
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        return b_p + self.kappa * self.D_to(b_q, p)

    def reconstruct(self, x_p, b_even, b_odd):
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        x_q = b_q + self.kappa * self.D_to(x_p, 1 - p)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def flops_per_site_M(self) -> int:
        return 2 * 1320 + 48

    def sloppy(self, prec: str = "half") -> "DiracWilsonPCSloppy":
        """Build the low-precision companion operator (QUDA matSloppy,
        include/invert_quda.h:369): same links, bf16-pair ('half') or
        int8 block-float ('quarter') storage."""
        return DiracWilsonPCSloppy(self, prec)

    def packed(self) -> "DiracWilsonPCPacked":
        """Build the TPU-native packed-layout companion (QUDA native
        FloatN field order analog, ops/wilson_packed.py)."""
        return DiracWilsonPCPacked(self)

    def codec(self, precise_dtype, store_dtype=None):
        """StorageCodec matching this operator's sloppy representation
        (pass the built sloppy operator's store_dtype)."""
        from ..solvers.mixed import pair_codec
        return pair_codec(store_dtype or jnp.bfloat16, precise_dtype)


class _PairSloppyBase:
    """Shared pair-storage sloppy-operator algebra (QUDA matSloppy).

    Subclasses supply the representation: ``_d_to`` (the stencil),
    ``_to_pairs``/``_from_pairs`` (layout converters) and ``_spin_axis``
    (where the 4-spin axis lives in the pair layout).  Everything else
    — the Schur composition, gamma5 trick, complex wrappers — is written
    ONCE here so a numerics fix cannot diverge between layouts.
    """

    store_dtype = jnp.bfloat16
    _spin_axis: int

    def _d_to(self, psi_pairs, target_parity, out_dtype):
        raise NotImplementedError

    def _to_pairs(self, x):
        raise NotImplementedError

    def _from_pairs(self, x, dtype):
        raise NotImplementedError

    def M_pairs(self, x):
        p = self.matpc
        tmp = self._d_to(x, 1 - p, self.store_dtype)
        dd = self._d_to(tmp, p, jnp.float32)
        out = x.astype(jnp.float32) - (self.kappa ** 2) * dd
        return out.astype(self.store_dtype)

    def _g5_pairs(self, x):
        sign = jnp.asarray([1.0, 1.0, -1.0, -1.0], jnp.float32)
        ax = self._spin_axis % x.ndim
        shape = [1] * x.ndim
        shape[ax] = 4
        return (x.astype(jnp.float32)
                * sign.reshape(shape)).astype(x.dtype)

    def Mdag_pairs(self, x):
        return self._g5_pairs(self.M_pairs(self._g5_pairs(x)))

    def MdagM_pairs(self, x):
        return self.Mdag_pairs(self.M_pairs(x))

    # -- multi-RHS (leading batch axis) forms --------------------------
    # One home for the batched Schur composition so the MRHS solve path
    # (solvers/block.py, invert_multi_src_quda) cannot diverge from the
    # single-RHS math.  ``_d_to_mrhs`` defaults to a vmap of the
    # single-RHS stencil; representations with a hand-tuned batched
    # kernel (the packed pallas v2 hop) override it.

    def _d_to_mrhs(self, psi_b, target_parity, out_dtype):
        return jax.vmap(
            lambda p: self._d_to(p, target_parity, out_dtype))(psi_b)

    def _g5_pairs_mrhs(self, x):
        # vmap over the batch axis reuses _g5_pairs verbatim (each
        # per-example view has the single-RHS ndim), so the gamma-5
        # sign logic exists exactly once
        return jax.vmap(self._g5_pairs)(x)

    def M_pairs_mrhs(self, x):
        p = self.matpc
        tmp = self._d_to_mrhs(x, 1 - p, self.store_dtype)
        dd = self._d_to_mrhs(tmp, p, jnp.float32)
        out = x.astype(jnp.float32) - (self.kappa ** 2) * dd
        return out.astype(self.store_dtype)

    def Mdag_pairs_mrhs(self, x):
        return self._g5_pairs_mrhs(
            self.M_pairs_mrhs(self._g5_pairs_mrhs(x)))

    def MdagM_pairs_mrhs(self, x):
        return self.Mdag_pairs_mrhs(self.M_pairs_mrhs(x))

    # -- complex in/out path -------------------------------------------
    def M(self, x):
        return self._from_pairs(self.M_pairs(self._to_pairs(x)), x.dtype)

    def Mdag(self, x):
        return self._from_pairs(self.Mdag_pairs(self._to_pairs(x)),
                                x.dtype)

    def MdagM(self, x):
        return self._from_pairs(self.MdagM_pairs(self._to_pairs(x)),
                                x.dtype)


_SHARDED_NOTICED = False


def _notice_sharded_policy(policy: str, src: str,
                           ici_bytes: int | None = None):
    """One-time provenance notice naming the mesh dslash configuration
    actually selected (halo policy + how it was chosen: pinned, raced,
    or served from the chip-keyed tunecache warm cache) — a policy must
    never take effect without a trace (utils/config.py fail-fast
    model)."""
    global _SHARDED_NOTICED
    if _SHARDED_NOTICED:
        return
    _SHARDED_NOTICED = True
    from ..utils import logging as qlog
    # comms volume next to the timing winner (obs/comms.py model): the
    # policies move the SAME bytes — what the race times is transport
    comms = ("" if not ici_bytes
             else f"; ICI {ici_bytes / 1024:.1f} KB/device per dslash")
    qlog.printq(
        f"mesh dslash: pallas eo interior, halo policy "
        f"{policy} ({src}){comms}; pin via QUDA_TPU_SHARDED_POLICY",
        qlog.SUMMARIZE)


_PRECISION_NOTICED: set = set()


def _notice_precision_form(requested: str, served: str, why: str):
    """One-time notice per (requested, served) pair naming the precision
    storage form actually in effect (utils/config.py fail-fast model: a
    downgrade or race outcome must never take effect without a trace)."""
    key = (requested, served)
    if key in _PRECISION_NOTICED:
        return
    _PRECISION_NOTICED.add(key)
    from ..utils import logging as qlog
    qlog.printq(
        f"precision form: requested '{requested}', serving '{served}' "
        f"({why}); pin via QUDA_TPU_PRECISION_FORM", qlog.SUMMARIZE)


def hop_route_knobs() -> tuple:
    """What ``_PackedHopMixin._setup_hop`` resolves from the environment
    when its caller pins nothing: (precision form, legacy
    reconstruct-12).  An operator kept across calls is keyed by it, so
    that a flipped knob gives a new operator, never a stale one."""
    from ..utils import config as qconf
    return (str(qconf.get("QUDA_TPU_PRECISION_FORM", fresh=True)),
            str(qconf.get("QUDA_TPU_RECONSTRUCT", fresh=True)) == "12")


class _PackedHopMixin:
    """The packed eo Wilson hop on pair arrays, shared by every
    packed-layout pair operator (Wilson, clover, twisted, Möbius hops):
    gauge setup, the stencil dispatch by precision form, and the
    canonical<->packed spinor converters live ONCE here."""

    _spin_axis = 0

    def _setup_hop(self, geom, gauge_eo_packed, store_dtype,
                   use_pallas, pallas_interpret,
                   tb_sign: bool = True, mesh=None,
                   sharded_policy: str | None = None,
                   precision_form: str | None = None):
        """gauge_eo_packed: (even, odd) complex packed (4,3,3,T,Z,Y*Xh)
        links (wilson_packed.pack_gauge_eo output).  ``tb_sign``: whether
        the links carry a folded antiperiodic-t phase (drives the
        reconstruct-12 row-2 sign; see wilson_pallas_packed).
        ``sharded_policy`` pins the mesh halo policy programmatically
        (else QUDA_TPU_SHARDED_POLICY decides; 'auto' races).
        ``precision_form`` pins the link storage/kernel form (else
        QUDA_TPU_PRECISION_FORM; '' = legacy resolution via
        QUDA_TPU_RECONSTRUCT): full | r12 (resident 12-real links) |
        r12f (r12 + copy-free scatter backward, no resident backward
        links) | fold (re/im interleaved full-tile rows) | bzfull
        (full-Z block admission) | int8 (block-float links, in-kernel
        decompress) | auto (raced via utils.tune; int8 never races —
        it changes numerics)."""
        from ..ops import wilson_packed as wpk
        if use_pallas:
            # pallas-construction fault seam (robust/faultinject.py):
            # the pallas-compile / VMEM-budget / sharded-race failure
            # class surfaces HERE, where the escalation ladder can
            # catch it and fall back to the XLA stencil form
            from ..robust import faultinject as finj
            finj.maybe_raise("pallas_build")
        self.geom = geom
        self.dims = tuple(geom.lattice_shape)
        self.store_dtype = store_dtype
        self.gauge_eo_pp = tuple(
            wpk.to_packed_pairs(g, store_dtype) for g in gauge_eo_packed)
        self.use_pallas = use_pallas
        self._pallas_interpret = pallas_interpret
        self._tb_sign = tb_sign
        from ..utils import config as qconf
        env_form, legacy_r12 = hop_route_knobs()
        if mesh is not None and getattr(mesh, "size", 2) == 1:
            # single-chip escape: a 1-device mesh shards nothing — drop
            # it and resolve the kernel form exactly like the unsharded
            # path (no exterior fix passes on a trivial mesh)
            mesh = None
        # -- precision storage form (PERF.md round 16) ------------------
        # explicit kwarg > QUDA_TPU_PRECISION_FORM > legacy resolution
        # (QUDA_TPU_RECONSTRUCT=12 -> r12, else full); 'auto' races the
        # numerics-preserving forms via utils.tune.  int8 is NEVER part
        # of a race: block-float links change the operator's floats.
        form = env_form if precision_form is None else precision_form
        requested = form or ("r12" if legacy_r12 else "full")
        form = self._downgrade_precision_form(requested, use_pallas,
                                              mesh, legacy_r12)
        self._block_z = None
        if form == "auto":
            form = self._race_precision_form(store_dtype)
        self._precision_form = form
        if use_pallas:
            from ..ops import wilson_pallas_packed as wpp
            # in-kernel gauge compression (QUDA reconstruct-12 analog),
            # single chip and sharded: resident link arrays shrink
            # 288 -> 192 B/site.  r12f shares the R=2 storage; its
            # scatter backward reads the unshifted opposite-parity
            # links, so no backward copy exists.
            if form in ("r12", "r12f"):
                self.gauge_eo_pp = tuple(wpp.to_recon12(g)
                                         for g in self.gauge_eo_pp)
            elif form == "int8":
                # block-float resident links: int8 mantissas + one f32
                # scale per (direction, site), decompressed in-kernel
                from ..ops import blockfloat as qbf
                qs = [qbf.to_int8_links(g.astype(jnp.float32))
                      for g in self.gauge_eo_pp]
                self._gauge_q = tuple(q for q, _ in qs)
                self._gauge_s = tuple(s for _, s in qs)
                self.gauge_eo_pp = None
            elif form == "bzfull":
                # full-Z block admission (dtype-aware; single-buffered
                # under the scoped window when the knob budget rejects
                # double buffering) — raises when even that cannot fit,
                # surfacing through the pallas_build escalation seam
                Zd = self.dims[1]
                self._block_z = wpp._pick_bz(
                    Zd, gauge_eo_packed[0].shape[-1], store_dtype,
                    planes=288, min_bz=Zd, allow_bzfull=True)
        elif form == "int8":
            # XLA stencil: decompress at setup via the codec round-trip
            # — IDENTICAL floats to the in-kernel decompression, so the
            # two routes build the same operator (bit-match tests rely
            # on this)
            from ..ops import blockfloat as qbf
            self.gauge_eo_pp = tuple(
                qbf.from_int8_links(*qbf.to_int8_links(
                    g.astype(jnp.float32)))
                for g in self.gauge_eo_pp)
        # gather forms: resident pre-shifted backward links (the r12f
        # scatter kernel reads the unshifted opposite-parity links
        # directly, the int8 kernel its own mantissa and scale planes —
        # no resident copy).  Computed on the GLOBAL arrays: under a
        # mesh the shifts then already carry the cross-shard links, so
        # the sharded exterior exchanges only psi slabs
        # (parallel/pallas_dslash.dslash_eo_pallas_sharded).
        if use_pallas and form not in ("r12f", "int8"):
            from ..ops import wilson_pallas_packed as wpp
            self._u_bw = tuple(
                wpp.backward_gauge_eo(self.gauge_eo_pp[1 - p],
                                      tuple(self.dims), p)
                for p in (0, 1))
        if use_pallas and form == "fold":
            # re/im-into-sublane fold: (…,2,T,Z,YX) -> (…,T,2Z,YX) so
            # bf16 (16,128) tiles fill exactly; z shifts become row
            # shifts by 2 (wilson_pallas_packed.to_fold)
            from ..ops import wilson_pallas_packed as wpp
            self.gauge_eo_pp = tuple(wpp.to_fold(g)
                                     for g in self.gauge_eo_pp)
            self._u_bw = tuple(wpp.to_fold(g) for g in self._u_bw)
        # multi-chip: run the sharded eo pallas policy under shard_map;
        # the resident links move onto the mesh once here
        self._mesh = mesh
        self._mesh_yx = None
        if mesh is not None:
            if not use_pallas:
                raise ValueError(
                    "mesh-sharded packed hops need use_pallas=True "
                    "(the XLA pair stencil shards via GSPMD instead)")
            from ..parallel.pallas_dslash import (
                SHARDED_POLICIES, _mesh_counts, _policy_label,
                notice_legacy_single_policy, resolve_axis_policies)
            self._sharded_policy = (
                sharded_policy
                or str(qconf.get("QUDA_TPU_SHARDED_POLICY", fresh=True))
                or "auto")
            if self._sharded_policy in SHARDED_POLICIES:
                # bare single-value form: maps onto every partitioned
                # axis, with a one-time deprecation-style notice
                notice_legacy_single_policy(self._sharded_policy)
            # y/x-partitioned meshes need the block-contiguous fused
            # layout (parallel/mesh.fuse_block_layout): the trailing
            # Y·Xh axis is re-ordered ONCE here so the ("y","x")
            # PartitionSpec hands every shard whole local rows at the
            # LOCAL row width (identity when n_x == 1)
            _, _, n_y, n_x = _mesh_counts(mesh)
            self._mesh_yx = (n_y, n_x)
            if n_x > 1:
                from ..parallel import mesh as qmesh
                _, _, Y, X = self.dims
                self.gauge_eo_pp = tuple(
                    qmesh.fuse_block_layout(g, n_y, n_x, Y, X // 2)
                    for g in self.gauge_eo_pp)
                if getattr(self, "_u_bw", None) is not None:
                    self._u_bw = tuple(
                        qmesh.fuse_block_layout(g, n_y, n_x, Y, X // 2)
                        for g in self._u_bw)
            from jax.sharding import NamedSharding, PartitionSpec as P
            gspec = NamedSharding(
                mesh,
                P(None, None, None, None, "t", "z", ("y", "x")))
            self.gauge_eo_pp = tuple(jax.device_put(g, gspec)
                                     for g in self.gauge_eo_pp)
            if getattr(self, "_u_bw", None) is not None:
                self._u_bw = tuple(jax.device_put(g, gspec)
                                   for g in self._u_bw)
            if self._sharded_policy == "auto":
                # race EAGERLY, at construction: the first hop usually
                # fires inside a solver trace, where timing concrete
                # candidates is impossible (tune would stage pjit calls
                # into the surrounding trace instead of executing them)
                self._resolve_sharded_policy(0, None)
            else:
                pols = resolve_axis_policies(self._sharded_policy)
                self._sharded_policy = pols
                live = [a for a, n in zip(("t", "z", "y", "x"),
                                          _mesh_counts(mesh)) if n > 1]
                _notice_sharded_policy(_policy_label(pols, live),
                                       "pinned",
                                       ici_bytes=self._ici_model_bytes())

    def _downgrade_precision_form(self, form: str, use_pallas: bool,
                                  mesh, legacy_r12: bool) -> str:
        """Clamp a requested precision form to what the selected path
        can serve — every downgrade leaves a one-time notice (nothing
        takes effect silently).  The sharded mesh kernels speak full and
        r12 only; the XLA stencil has no in-kernel decompression (int8
        decompresses at setup instead; r12 storage stays full)."""
        choices = ("auto", "full", "bzfull", "fold", "r12", "r12f",
                   "int8")
        if form not in choices:
            raise ValueError(
                f"precision form {form!r} not in {choices} "
                "(QUDA_TPU_PRECISION_FORM)")
        if mesh is not None:
            served = {"auto": "r12" if legacy_r12 else "full",
                      "r12f": "r12", "int8": "r12", "fold": "full",
                      "bzfull": "full"}.get(form, form)
            if served != form:
                _notice_precision_form(
                    form, served, "mesh-sharded kernels serve full/r12")
            return served
        if not use_pallas:
            served = ("int8" if form == "int8" else "full")
            if served != form and form not in ("full", "r12"):
                # r12 -> full on XLA is the silent legacy behavior (the
                # stencil has no R=2); pallas-only forms get a notice
                _notice_precision_form(
                    form, served, "XLA stencil path (no pallas kernels)")
            return served
        return form

    def _race_precision_form(self, store_dtype) -> str:
        """QUDA_TPU_PRECISION_FORM=auto: race the numerics-preserving
        forms on concrete operands via utils.tune (QUDA's tune.cpp rule
        — forms are timed, never assumed) and cache the winner in the
        chip-keyed tunecache.  Candidate storages are built transiently
        from the resident full links and dropped after the race; the
        winner's storage is rebuilt by _setup_hop.  int8 never races —
        block-float links change the operator's numerics, so they must
        be an explicit opt-in."""
        from ..ops import wilson_pallas_packed as wpp
        from ..utils import tune as qtune
        dims = tuple(self.dims)
        T, Z, _, _ = dims
        YXh = self.gauge_eo_pp[0].shape[-1]
        itp, tb = self._pallas_interpret, self._tb_sign
        g = self.gauge_eo_pp
        ubw = tuple(wpp.backward_gauge_eo(g[1 - p], dims, p)
                    for p in (0, 1))
        g12 = tuple(wpp.to_recon12(x) for x in g)
        ubw12 = tuple(wpp.to_recon12(x) for x in ubw)
        gf = tuple(wpp.to_fold(x) for x in g)
        ubwf = tuple(wpp.to_fold(x) for x in ubw)
        cands = {
            "full": lambda p: wpp.dslash_eo_pallas_packed(
                g[0], ubw[0], p, dims, 0, interpret=itp, tb_sign=tb),
            "r12": lambda p: wpp.dslash_eo_pallas_packed(
                g12[0], ubw12[0], p, dims, 0, interpret=itp,
                tb_sign=tb),
            "r12f": lambda p: wpp.dslash_eo_pallas_packed_r12f(
                g12[0], g12[1], p, dims, 0, interpret=itp, tb_sign=tb),
            "fold": lambda p: wpp.from_fold(
                wpp.dslash_eo_pallas_packed_fold(
                    gf[0], ubwf[0], wpp.to_fold(p), dims, 0,
                    interpret=itp, tb_sign=tb)),
        }
        try:
            bzf = wpp._pick_bz(Z, YXh, store_dtype, planes=288,
                               min_bz=Z, allow_bzfull=True)
            cands["bzfull"] = lambda p: wpp.dslash_eo_pallas_packed(
                g[0], ubw[0], p, dims, 0, interpret=itp, block_z=bzf,
                tb_sign=tb)
        except ValueError:
            pass  # full-Z block busts even the scoped window: not a form
        psi0 = jnp.zeros((4, 3, 2, T, Z, YXh), store_dtype)
        aux = jnp.dtype(store_dtype).name
        warm = qtune.cached_param("wilson_eo_precision_form", dims,
                                  aux=aux)
        won = qtune.tune("wilson_eo_precision_form", dims, cands,
                         (psi0,), aux=aux)
        _notice_precision_form(
            "auto", won,
            "warm cache (chip-keyed tunecache)" if warm is not None
            else "raced (QUDA_TPU_PRECISION_FORM=auto)")
        return won

    def _d_to(self, psi_pp, target_parity, out_dtype):
        from ..ops import wilson_packed as wpk
        if self.use_pallas:
            from ..ops import wilson_pallas_packed as wpp
            if getattr(self, "_mesh", None) is not None:
                fn = self._sharded_d_to(target_parity, out_dtype)
                return fn(self.gauge_eo_pp[target_parity],
                          self._u_bw[target_parity], psi_pp)
            form = getattr(self, "_precision_form", None)
            if form == "r12f":
                return wpp.dslash_eo_pallas_packed_r12f(
                    self.gauge_eo_pp[target_parity],
                    self.gauge_eo_pp[1 - target_parity], psi_pp,
                    tuple(self.dims), target_parity,
                    interpret=self._pallas_interpret,
                    out_dtype=out_dtype, tb_sign=self._tb_sign)
            if form == "fold":
                out = wpp.dslash_eo_pallas_packed_fold(
                    self.gauge_eo_pp[target_parity],
                    self._u_bw[target_parity], wpp.to_fold(psi_pp),
                    tuple(self.dims), target_parity,
                    interpret=self._pallas_interpret,
                    out_dtype=out_dtype, tb_sign=self._tb_sign)
                return wpp.from_fold(out)
            if form == "int8":
                return wpp.dslash_eo_pallas_packed_int8(
                    self._gauge_q[target_parity],
                    self._gauge_s[target_parity],
                    self._gauge_q[1 - target_parity],
                    self._gauge_s[1 - target_parity], psi_pp,
                    tuple(self.dims), target_parity,
                    interpret=self._pallas_interpret,
                    out_dtype=out_dtype)
            return wpp.dslash_eo_pallas_packed(
                self.gauge_eo_pp[target_parity],
                self._u_bw[target_parity], psi_pp, tuple(self.dims),
                target_parity, interpret=self._pallas_interpret,
                block_z=getattr(self, "_block_z", None),
                out_dtype=out_dtype, tb_sign=self._tb_sign)
        return wpk.dslash_eo_packed_pairs(self.gauge_eo_pp, psi_pp,
                                          self.dims, target_parity,
                                          out_dtype=out_dtype)

    def _plain_mrhs(self) -> bool:
        """Whether the batched hop is the plain pallas MRHS kernel
        (``_hop_mrhs``): the pallas route off a mesh, in a storage form
        without a batched kernel or a vmapped fallback of its own."""
        return (self.use_pallas and getattr(self, "_mesh", None) is None
                and getattr(self, "_precision_form", None)
                not in ("r12f", "fold", "int8"))

    def _hop_mrhs(self, psi_b, target_parity, out_dtype, **epilogue):
        """The plain pallas MRHS kernel on this operator's links;
        ``epilogue``: its combine operands (``xc``, ``coeff``, ``g5``),
        with ``rc`` and ``alpha`` the residual form's; with them the
        result is ``(batch, its squared norms per source)``."""
        from ..ops import wilson_pallas_packed as wpp
        hop = (wpp.dslash_eo_pallas_packed_mrhs_residual
               if "rc" in epilogue
               else wpp.dslash_eo_pallas_packed_mrhs_combine if epilogue
               else wpp.dslash_eo_pallas_packed_mrhs)
        return hop(
            self.gauge_eo_pp[target_parity], self._u_bw[target_parity],
            psi_b, tuple(self.dims), target_parity,
            interpret=self._pallas_interpret, out_dtype=out_dtype,
            tb_sign=self._tb_sign, **epilogue)

    def _d_to_mrhs(self, psi_b, target_parity, out_dtype):
        """Batched packed eo hop: psi_b (N,4,3,2,T,Z,Y*Xh).  The
        pallas path routes the MRHS kernel (one gauge-tile fetch per
        (t, z-block), N spinor tiles streamed through it); r12f and
        fold route their own MRHS kernels; everything else (int8,
        mesh, XLA) falls back to the vmapped single-RHS stencil."""
        if self._plain_mrhs():
            return self._hop_mrhs(psi_b, target_parity, out_dtype)
        if self.use_pallas and getattr(self, "_mesh", None) is None:
            from ..ops import wilson_pallas_packed as wpp
            form = getattr(self, "_precision_form", None)
            if form == "r12f":
                return wpp.dslash_eo_pallas_packed_r12f_mrhs(
                    self.gauge_eo_pp[target_parity],
                    self.gauge_eo_pp[1 - target_parity], psi_b,
                    tuple(self.dims), target_parity,
                    interpret=self._pallas_interpret,
                    out_dtype=out_dtype, tb_sign=self._tb_sign)
            if form == "fold":
                out = wpp.dslash_eo_pallas_packed_fold_mrhs(
                    self.gauge_eo_pp[target_parity],
                    self._u_bw[target_parity], wpp.to_fold(psi_b),
                    tuple(self.dims), target_parity,
                    interpret=self._pallas_interpret,
                    out_dtype=out_dtype, tb_sign=self._tb_sign)
                return wpp.from_fold(out)
        return jax.vmap(
            lambda p: self._d_to(p, target_parity, out_dtype))(psi_b)

    def _ici_model_bytes(self):
        """Per-device ICI bytes of one sharded dslash invocation (the
        analytic halo model, obs/comms.py) — quoted by the one-time
        policy notice next to the timing winner; None off-mesh."""
        if getattr(self, "_mesh", None) is None:
            return None
        import numpy as np

        from ..obs import comms as ocomms
        from ..parallel.pallas_dslash import _mesh_counts
        return ocomms.wilson_eo_halo_model(
            tuple(self.dims), _mesh_counts(self._mesh),
            itemsize=np.dtype(self.store_dtype).itemsize)["per_device"]

    def _build_sharded_fn(self, target_parity, out_dtype, policy):
        """jitted shard_map of the sharded eo pallas policy for one
        (parity, out_dtype, halo policy) configuration; ``policy`` is
        anything resolve_axis_policies accepts (bare name, per-axis
        spec string, or {axis: policy} dict)."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.pallas_dslash import dslash_eo_pallas_sharded
        pspec = P(None, None, None, "t", "z", ("y", "x"))
        gspec = P(None, None, None, None, "t", "z", ("y", "x"))

        def local(uh, ub, p):
            return dslash_eo_pallas_sharded(
                uh, ub, p, tuple(self.dims), target_parity,
                self._mesh, interpret=self._pallas_interpret,
                out_dtype=out_dtype, tb_sign=self._tb_sign,
                policy=policy)
        return jax.jit(jax.shard_map(
            local, mesh=self._mesh, in_specs=(gspec, gspec, pspec),
            out_specs=pspec, check_vma=False))

    def _resolve_sharded_policy(self, target_parity, out_dtype):
        """The PER-AXIS policy engine (round 18): a pinned policy (bare
        name, per-axis spec, or dict) normalizes and passes through;
        'auto' races each PARTITIONED mesh axis independently on REAL
        shard-resident operands via utils.tune (QUDA's tune.cpp:862
        rule — policies are timed, never assumed), greedily: every axis
        starts at xla_facefix and each axis race pins its winner before
        the next axis races, cached per (volume, mesh, form, axis) in
        the tunecache.  A candidate that cannot run here (the fused
        RDMA path off-chip without the distributed interpreter) simply
        loses its race — tune skips failing candidates."""
        from ..parallel.pallas_dslash import (AXIS_NAMES,
                                              FUSED_HALO_AXES,
                                              SHARDED_POLICIES,
                                              _mesh_counts,
                                              _policy_label,
                                              resolve_axis_policies)
        pol = self._sharded_policy
        if pol != "auto":
            return resolve_axis_policies(pol)
        won = getattr(self, "_sharded_policy_winner", None)
        if won is not None:
            return won
        from ..utils import tune as qtune
        counts = _mesh_counts(self._mesh)
        live = [a for a, n in zip(AXIS_NAMES, counts) if n > 1]
        # concrete dummy operands at the solve shapes/shardings (the
        # race may be triggered from inside a solver trace, where psi is
        # a tracer — the links are resident concrete arrays already)
        from jax.sharding import NamedSharding, PartitionSpec as P
        uh = self.gauge_eo_pp[target_parity]
        ub = self._u_bw[target_parity]
        T, Z, _, _ = self.dims
        psi0 = jax.device_put(
            jnp.zeros((4, 3, 2, T, Z, uh.shape[-1]), self.store_dtype),
            NamedSharding(self._mesh,
                          P(None, None, None, "t", "z", ("y", "x"))))
        mesh_shape = tuple(int(self._mesh.shape[a])
                           for a in self._mesh.axis_names)
        aux = f"mesh{mesh_shape}|{jnp.dtype(self.store_dtype).name}"
        pols = {a: "xla_facefix" for a in AXIS_NAMES}
        # warm-cache provenance: winners already raced on THIS chip
        # (tune_key carries the platform component) for EVERY live axis
        # are served without re-racing; the notice says which happened
        warm, seeded = True, None
        for ax in live:
            axis_cands = [p for p in SHARDED_POLICIES
                          if p == "xla_facefix" or ax in FUSED_HALO_AXES]
            if len(axis_cands) < 2:
                continue    # x: only the facefix transport serves it
            cands = {p: self._build_sharded_fn(
                        target_parity, out_dtype, dict(pols, **{ax: p}))
                     for p in axis_cands}
            name = f"wilson_eo_sharded_policy_{ax}"
            warm = warm and (qtune.cached_param(
                name, tuple(self.dims), aux=aux) is not None)
            pols[ax] = qtune.tune(name, tuple(self.dims), cands,
                                  (uh, ub, psi0), aux=aux)
            seeded = cands[pols[ax]]
        self._sharded_policy_winner = pols
        # the last race's winning candidate is already traced+compiled
        # and equals the final joint configuration (later axes never
        # change an earlier race's pinned values) — seed the hop cache
        # with it so the first real application does not pay an
        # identical second XLA compilation of the distributed dslash
        # (out_dtype=None means "psi dtype" = store_dtype here, so the
        # key must normalize or real lookups can never hit the seed)
        key = (target_parity,
               jnp.dtype(out_dtype or self.store_dtype).name)
        if seeded is None:
            seeded = self._build_sharded_fn(target_parity, out_dtype,
                                            dict(pols))
        self.__dict__.setdefault("_sharded_fns", {})[key] = seeded
        _notice_sharded_policy(
            _policy_label(pols, live),
            "warm cache (chip-keyed tunecache)" if warm
            else "raced+cached (QUDA_TPU_SHARDED_POLICY=auto)",
            ici_bytes=self._ici_model_bytes())
        return pols

    def _sharded_d_to(self, target_parity, out_dtype):
        """Memoized shard_map of the sharded eo pallas policy (a fresh
        wrapper per call would defeat the pjit cache — it is keyed on
        callable identity)."""
        cache = self.__dict__.setdefault("_sharded_fns", {})
        key = (target_parity,
               jnp.dtype(out_dtype or self.store_dtype).name)
        if key not in cache:
            policy = self._resolve_sharded_policy(target_parity,
                                                  out_dtype)
            cache[key] = self._build_sharded_fn(target_parity,
                                                out_dtype, policy)
        return cache[key]

    def _yx_block_pairs(self, x, inverse: bool = False):
        """x-sharded meshes keep the resident links AND the solver
        spinors in the block-contiguous fused layout
        (parallel/mesh.fuse_block_layout) — a pure site relabeling the
        packed solver algebra (elementwise + reductions over the fused
        axis) never observes, so the conversion happens ONLY at the
        canonical<->packed boundary.  Identity off-mesh and whenever
        the x mesh axis is unpartitioned."""
        yx = getattr(self, "_mesh_yx", None)
        if yx is None or yx[1] == 1:
            return x
        from ..parallel import mesh as qmesh
        _, _, Y, X = self.dims
        f = (qmesh.unfuse_block_layout if inverse
             else qmesh.fuse_block_layout)
        return f(x, yx[0], yx[1], Y, X // 2)

    def _to_pairs(self, x):
        """Canonical (T,Z,Y,Xh,4,3) complex -> packed pairs."""
        from ..ops import wilson_packed as wpk
        return self._yx_block_pairs(
            wpk.to_packed_pairs(wpk.pack_spinor(x), self.store_dtype))

    def _from_pairs(self, x, dtype):
        """Packed pairs -> canonical (T,Z,Y,Xh,4,3) complex."""
        from ..ops import wilson_packed as wpk
        T, Z, Y, X = self.dims
        return wpk.unpack_spinor(
            wpk.from_packed_pairs(self._yx_block_pairs(x, inverse=True),
                                  dtype), (T, Z, Y, X // 2))


class _ProgramOperand:
    """A packed pair operator as a solve-program operand
    (solvers/program.py).  The operator crosses a jit boundary as a
    pytree: the resident arrays and kappa are the LEAVES (operands of
    the compiled solve: a new configuration or a new mass reuses the
    executable, and no field is baked into it), everything that changes
    the traced computation is the static aux (part of jit's cache key).
    A class lists every attribute its dispatch reads in one tuple or
    the other (below: what the packed hop of _PackedHopMixin reads; a
    class with more state extends them) and registers itself as a
    pytree node."""

    _PROGRAM_ARRAYS: tuple = ("gauge_eo_pp", "_u_bw", "_gauge_q",
                              "_gauge_s", "kappa")
    _PROGRAM_STATIC: tuple = ("geom", "dims", "matpc", "store_dtype",
                              "use_pallas", "_pallas_interpret",
                              "_tb_sign", "_precision_form",
                              "_block_z")

    @property
    def program_signature(self):
        """The hashable static half of this operator as a solve-program
        operand, or None when it cannot be one: a mesh operator races
        its halo policy on concrete operands and memoises the shard_map
        on the instance, so it keeps the eager solve."""
        if getattr(self, "_mesh", None) is not None:
            return None
        static = {n: getattr(self, n) for n in self._PROGRAM_STATIC}
        static["store_dtype"] = jnp.dtype(self.store_dtype)
        return tuple(static.values())

    def tree_flatten(self):
        sig = self.program_signature
        if sig is None:
            raise TypeError("a mesh-sharded packed pair operator is not "
                            "a solve-program operand")
        return (tuple(getattr(self, n, None)
                      for n in self._PROGRAM_ARRAYS), sig)

    @classmethod
    def tree_unflatten(cls, sig, arrays):
        op = object.__new__(cls)
        vars(op).update(zip(cls._PROGRAM_STATIC, sig),
                        _mesh=None, _mesh_yx=None)
        vars(op).update(zip(cls._PROGRAM_ARRAYS, arrays))
        return op

    def with_kappa(self, kappa: float):
        """The same resident arrays under another hopping parameter (a
        leaf of the pytree: a program's executable is shared)."""
        op = copy.copy(self)
        op.kappa = float(kappa)
        return op


class _SchurPairOpBase(_PackedHopMixin, _PairSloppyBase):
    """Template for clover-type Schur pair operators

        M_pc(s) = diag_p(s) - kappa^2 D Ainv_q(s) D
        prepare:      b_p + kappa D Ainv_q b_q
        reconstruct:  x_q = Ainv_q (b_q + kappa D x_p)

    written ONCE over two hooks (``_diag_sign_pairs``,
    ``_Ainv_q_sign_pairs``; the twist sign s is ignored by the
    g5-hermitian clover family).  Mdag = g5 M(-s) g5 is the general
    form: for sign-symmetric operators it reduces to the g5 trick.
    """

    # pallas-vs-xla family form (models/formsel.resolve_form sets it at
    # family construction; 'pallas' routes _M_sign_pairs through the
    # fused epilogue kernels of ops/clover_pallas)
    _op_form = "xla"
    # the form a batch takes where ``_op_form`` is 'pallas' and the
    # family's batched forms have been read on the chip
    # (formsel.MEASURED_MRHS); None: the batch follows ``_op_form``
    _MRHS_FORM = None

    def _diag_sign_pairs(self, x, sign, out_dtype):
        raise NotImplementedError

    def _Ainv_q_sign_pairs(self, x, sign, out_dtype):
        raise NotImplementedError

    # -- fused-epilogue hooks (ops/clover_pallas) -----------------------
    # A family that can fold its diagonals into the v2 kernel epilogue
    # describes them here: K1 applies E = Ainv_q as a post-hop epilogue
    # (resident chiral blocks and/or a static (c, scale) twist
    # rotation); K2 adds the p-parity diagonal (blocks and/or an
    # i c g5 rotation of the ORIGINAL x) to the -kappa^2-scaled second
    # hop.  Raising here means the family has no fused form.

    def _fused_k1_params(self, sign):
        """-> (blk_pl or None, twist (c, scale) or None)."""
        raise NotImplementedError

    def _fused_k2_params(self, sign):
        """-> (blk_pl or None, diag_twist c or None)."""
        raise NotImplementedError

    def _count_route(self, form, stage, epilogue="none"):
        """clover_route_total, counted where a single-source ``M`` is
        traced: the ``_count_mrhs`` labels without a route (one source
        is z-blocks, always)."""
        from ..obs import metrics as omet
        omet.inc("clover_route_total", form=form, stage=stage,
                 epilogue=epilogue)

    def _M_sign_fused(self, x, sign, **epilogue):
        """``M(sign) x`` by the two fused kernels, at the storage dtype.
        ``epilogue``: ``g5``, ``nrm``, ``rc`` / ``alpha`` of
        ops/clover_pallas.dslash_eo_pallas_diag_hop, the second kernel;
        with ``nrm`` or ``rc`` the result is the pair (spinor, its
        squared norm)."""
        from ..ops import clover_pallas as clp
        p = self.matpc
        k1_blk, k1_twist = self._fused_k1_params(sign)
        k2_blk, k2_twist = self._fused_k2_params(sign)
        dims = tuple(self.dims)
        itp = self._pallas_interpret
        bz = getattr(self, "_block_z", None)
        self._count_route("pallas", "post")
        # K1: Ainv_q(D_{q<-p} x) in one pass; the hop accumulator
        # rounds to store_dtype through the out-tile read-back, so
        # the staged rounding of the XLA composition is preserved
        t = clp.dslash_eo_pallas_post(
            self.gauge_eo_pp[1 - p], self._u_bw[1 - p], x, dims,
            1 - p, blk_pl=k1_blk, twist=k1_twist, interpret=itp,
            block_z=bz, out_dtype=self.store_dtype,
            tb_sign=self._tb_sign)
        self._count_route(
            "pallas", "diag_hop",
            "residual" if epilogue.get("rc") is not None
            else "norm2" if epilogue.get("nrm") else "combine")
        # K2: diag_p(x) - kappa^2 D_{p<-q} t, combined in f32 (the hop
        # sum in the f32 out tile, or in an f32 scratch under a
        # narrower one) and rounded to storage once, in the store, as
        # the staged composition rounds at its boundary
        return clp.dslash_eo_pallas_diag_hop(
            self.gauge_eo_pp[p], self._u_bw[p], t, x, dims, p,
            hop_coeff=-(self.kappa ** 2), blk_pl=k2_blk,
            diag_twist=k2_twist, interpret=itp, block_z=bz,
            out_dtype=self.store_dtype, tb_sign=self._tb_sign, **epilogue)

    def _M_sign_pairs(self, x, sign, form=None):
        p = self.matpc
        form = form or self._op_form
        if form == "pallas":
            return self._M_sign_fused(x, sign)
        self._count_route(form, "post")
        self._count_route(form, "diag_hop", "combine")
        t = self._d_to(x, 1 - p, self.store_dtype)
        t = self._Ainv_q_sign_pairs(t, sign, self.store_dtype)
        dd = self._d_to(t, p, jnp.float32)
        out = (self._diag_sign_pairs(x, sign, jnp.float32)
               - (self.kappa ** 2) * dd)
        return out.astype(self.store_dtype)

    def M_pairs(self, x):
        return self._M_sign_pairs(x, +1)

    def Mdag_pairs(self, x):
        return self._g5_pairs(self._M_sign_pairs(self._g5_pairs(x), -1))

    def MdagM_pairs(self, x):
        return self.Mdag_pairs(self.M_pairs(x))

    @property
    def MdagM_cg_step_pairs(self):
        """The first half of a mixed-precision CG iteration on MdagM,
        what solvers/mixed.cg_reliable_loop applies, where the operator
        is served by its fused kernels (``MdagM_cg_step_pairs_mrhs``
        for one source, in any storage); None everywhere else (the
        ``xla`` form, so also a mesh or ``use_pallas`` off), and the
        solve program then takes solvers/mixed.cg_step of
        ``MdagM_pairs`` in the one loop, as for every other family."""
        return self._MdagM_cg_step_fused if self._op_form == "pallas" \
            else None

    def _MdagM_cg_step_fused(self, p, r, r2, k=None):
        """From the search direction ``p``, the sloppy residual ``r``
        and its ``|r|^2`` ``r2`` to ``(r - alpha MdagM p, its squared
        norm, alpha, pAp)``, in storage, out of the fused kernels'
        epilogue (a K2 call that stores narrower than f32 keeps its hop
        sum in f32 on chip).  ``q = g5 M(+s) p`` is stored once,
        rounded, with ``pAp = |q|^2`` summed from the values as stored;
        the second ``M``'s K2 call writes ``r - alpha g5 M(-s) q`` in
        ``r``'s place, rounded once from f32, and sums it.  ``MdagM p``
        never reaches HBM and no XLA pass over the vectors makes a
        cast, a gamma5, the dot, the update of ``r`` or ``|r|^2``.
        ``k``, the iteration, is the generic step's (an armed fault)."""
        from ..solvers.block import cg_alpha
        q, pAp = self._M_sign_fused(p, +1, g5=True, nrm=True)
        alpha = cg_alpha(r2, pAp)
        r, r2 = self._M_sign_fused(q, -1, g5=True, rc=r, alpha=alpha)
        return r, r2, alpha, pAp

    # -- multi-RHS forms ------------------------------------------------
    # The _PairSloppyBase MRHS defaults encode the WILSON composition
    # (x - kappa^2 DD) and are wrong for any operator with a nontrivial
    # diagonal; the Schur family gets its own batched forms here, with
    # the fused path riding the MRHS epilogue kernels (gauge AND block
    # tiles resident across the RHS stream).

    def _diag_sign_pairs_mrhs(self, x, sign, out_dtype):
        return jax.vmap(
            lambda v: self._diag_sign_pairs(v, sign, out_dtype))(x)

    def _Ainv_q_sign_pairs_mrhs(self, x, sign, out_dtype):
        return jax.vmap(
            lambda v: self._Ainv_q_sign_pairs(v, sign, out_dtype))(x)

    def _mrhs_form(self) -> str:
        """The form the batched operator is served in: the fused MRHS
        kernels only where the single-source operator is fused, and
        then what the chip read for a batch where it has
        (``_MRHS_FORM``)."""
        if self._op_form != "pallas":
            return "xla"
        return self._MRHS_FORM or "pallas"

    def _count_mrhs(self, form, stage, route="none", epilogue="none"):
        """clover_mrhs_route_total, counted where a batched ``M`` is
        traced (as wilson_mrhs_route_total), by the route each fused
        call takes from its shapes and by its epilogue: ``none`` on
        ``post``; on ``diag_hop`` ``combine`` (A x - kappa^2 D t),
        ``norm2`` (gamma5 and the sums of squares besides) or
        ``residual`` (r - alpha g5 of that, summed)."""
        from ..obs import metrics as omet
        omet.inc("clover_mrhs_route_total", form=form, stage=stage,
                 route=route, epilogue=epilogue)

    def _M_sign_fused_mrhs(self, x, sign, **epilogue):
        """``M(sign) x`` by the two fused MRHS kernels, f32 out.
        ``epilogue``: ``g5``, ``nrm``, ``rc`` / ``alpha`` of
        ops/clover_pallas.dslash_eo_pallas_diag_hop_mrhs, the second
        kernel; with ``nrm`` or ``rc`` the result is the pair (batch,
        its squared norms per source)."""
        from ..ops import clover_pallas as clp
        p = self.matpc
        k1_blk, k1_twist = self._fused_k1_params(sign)
        k2_blk, k2_twist = self._fused_k2_params(sign)
        dims = tuple(self.dims)
        itp = self._pallas_interpret
        bz = getattr(self, "_block_z", None)
        u_q, u_p = self.gauge_eo_pp[1 - p], self.gauge_eo_pp[p]
        form, route = clp.mrhs_form(u_q, x, None, k1_blk, self.store_dtype,
                                    bz)
        self._count_mrhs("pallas", "post", route[0], form)
        t = clp.dslash_eo_pallas_post_mrhs(
            u_q, self._u_bw[1 - p], x, dims,
            1 - p, blk_pl=k1_blk, twist=k1_twist, interpret=itp,
            block_z=bz, out_dtype=self.store_dtype,
            tb_sign=self._tb_sign)
        form, route = clp.mrhs_form(
            u_p, t, x, k2_blk, jnp.float32, bz, epilogue.get("nrm", False),
            epilogue.get("rc"))
        self._count_mrhs("pallas", "diag_hop", route[0], form)
        return clp.dslash_eo_pallas_diag_hop_mrhs(
            u_p, self._u_bw[p], t, x, dims, p,
            hop_coeff=-(self.kappa ** 2), blk_pl=k2_blk,
            diag_twist=k2_twist, interpret=itp, block_z=bz,
            out_dtype=jnp.float32, tb_sign=self._tb_sign, **epilogue)

    def _M_sign_pairs_mrhs(self, x, sign, form=None):
        p = self.matpc
        form = form or self._mrhs_form()
        if form == "pallas":
            return self._M_sign_fused_mrhs(x, sign).astype(self.store_dtype)
        self._count_mrhs(form, "post")
        self._count_mrhs(form, "diag_hop", epilogue="combine")
        t = self._d_to_mrhs(x, 1 - p, self.store_dtype)
        t = self._Ainv_q_sign_pairs_mrhs(t, sign, self.store_dtype)
        dd = self._d_to_mrhs(t, p, jnp.float32)
        out = (self._diag_sign_pairs_mrhs(x, sign, jnp.float32)
               - (self.kappa ** 2) * dd)
        return out.astype(self.store_dtype)

    def M_pairs_mrhs(self, x):
        return self._M_sign_pairs_mrhs(x, +1)

    def Mdag_pairs_mrhs(self, x):
        return self._g5_pairs_mrhs(
            self._M_sign_pairs_mrhs(self._g5_pairs_mrhs(x), -1))

    def MdagM_pairs_mrhs(self, x):
        return self.Mdag_pairs_mrhs(self.M_pairs_mrhs(x))

    def MdagM_cg_step_pairs_mrhs(self, p, r, rz, k=None):
        """The first half of a batched CG iteration on MdagM, what
        solvers/block.batched_cg_pairs_loop applies: from the search
        directions ``p``, the residuals ``r`` and their ``|r|^2``
        ``rz`` to ``(r - alpha MdagM p, its squared norms per source,
        alpha, pAp)``, taken from the fused kernels' epilogue where the
        batch is served by them in f32.  MdagM is g5 M(-s) g5 M(+s) and
        ``Mdag`` is ``M``'s adjoint for either twist sign, so ``p .
        MdagM p = |q|^2`` with ``q = g5 M(+s) p``, which the K2 kernel
        that stores ``q`` sums as it stores (the ``norm2`` form):
        ``alpha`` is known before the second ``M``, whose K2 kernel
        then writes ``r - alpha g5 M(-s) q`` in ``r``'s place and sums
        that (the ``residual`` form).  ``MdagM p`` and the un-signed
        twin of ``q`` never reach HBM, and no XLA pass over the batch
        makes a gamma5, ``pAp``, the update of ``r`` or ``|r|^2``.
        ``k``, the iteration, is the generic step's (an armed fault).
        Everywhere else (the ``xla`` form, sloppy storage):
        solvers/block.cg_step of ``MdagM_pairs_mrhs``, XLA's dot,
        update and sum."""
        from ..solvers import block
        f32 = jnp.dtype(jnp.float32)
        if (self._mrhs_form() != "pallas" or r.dtype != f32
                or jnp.dtype(self.store_dtype) != f32):
            return block.cg_step(self.MdagM_pairs_mrhs)(p, r, rz, k)
        q, pAp = self._M_sign_fused_mrhs(p, +1, g5=True, nrm=True)
        alpha = block.cg_alpha(rz, pAp)
        r, r2 = self._M_sign_fused_mrhs(q, -1, g5=True, rc=r, alpha=alpha)
        return r, r2, alpha, pAp

    def prepare_pairs_mrhs(self, b_even_b, b_odd_b):
        """Batched prepare: b_p + kappa D Ainv_q b_q with the MRHS hop
        (canonical complex parity batches in, f32 pair rhs out — the
        wilson MRHS boundary convention)."""
        from ..fields.geometry import EVEN
        p = self.matpc
        b_p, b_q = ((b_even_b, b_odd_b) if p == EVEN
                    else (b_odd_b, b_even_b))
        to_pp = jax.vmap(self._to_pairs)
        t = self._Ainv_q_sign_pairs_mrhs(to_pp(b_q), +1,
                                         self.store_dtype)
        t = self._d_to_mrhs(t, p, jnp.float32)
        return to_pp(b_p).astype(jnp.float32) + self.kappa * t

    def solution_from_pairs_mrhs(self, x_b, dtype=jnp.complex64):
        return jax.vmap(lambda x: self._from_pairs(x, dtype))(x_b)

    def reconstruct_pairs_mrhs(self, x_b, b_even_b, b_odd_b):
        """Batched reconstruct: x_q = Ainv_q (b_q + kappa D x_p)."""
        from ..fields.geometry import EVEN
        p = self.matpc
        b_q = b_odd_b if p == EVEN else b_even_b
        to_pp = jax.vmap(self._to_pairs)
        t = self._d_to_mrhs(x_b, 1 - p, jnp.float32)
        xq_b = self._Ainv_q_sign_pairs_mrhs(
            to_pp(b_q).astype(jnp.float32) + self.kappa * t, +1,
            jnp.float32)
        x_p = self.solution_from_pairs_mrhs(x_b, b_q.dtype)
        x_q = self.solution_from_pairs_mrhs(xq_b, b_q.dtype)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    # -- prepare / reconstruct in pair space ----------------------------
    def prepare_pairs(self, b_even, b_odd):
        from ..fields.geometry import EVEN
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        t = self._Ainv_q_sign_pairs(self._to_pairs(b_q), +1,
                                    self.store_dtype)
        t = self._d_to(t, p, jnp.float32)
        rhs = self._to_pairs(b_p).astype(jnp.float32) + self.kappa * t
        return rhs.astype(self.store_dtype)

    def reconstruct_pairs(self, x_pp, b_even, b_odd):
        from ..fields.geometry import EVEN
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        t = self._d_to(x_pp, 1 - p, jnp.float32)
        xq_pp = self._Ainv_q_sign_pairs(
            self._to_pairs(b_q).astype(jnp.float32) + self.kappa * t,
            +1, jnp.float32)
        x_p = self._from_pairs(x_pp, b_q.dtype)
        x_q = self._from_pairs(xq_pp, b_q.dtype)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)


class DiracWilsonPCPacked:
    """PC Wilson operator on the TPU-native packed half-lattice layout.

    ``prepare`` takes canonical (T,Z,Y,Xh,4,3) parity fields and returns a
    PACKED rhs; ``M`` acts packed->packed (the whole Krylov loop stays in
    the device-native order); ``reconstruct`` takes the packed solution and
    canonical sources and returns canonical parity fields.  This mirrors
    how QUDA keeps solver fields in native order and converts only at the
    interface boundary (lib/interface_quda.cpp loadGauge/invert flow).
    """

    def __init__(self, dpc: DiracWilsonPC):
        from ..ops import wilson_packed as wpk
        self.geom = dpc.geom
        self.kappa = dpc.kappa
        self.matpc = dpc.matpc
        self._dpc = dpc
        self.dims = dpc.geom.lattice_shape      # (T, Z, Y, X)
        self.gauge_eo_p = wpk.pack_gauge_eo(dpc.gauge_eo)

    def D_to(self, psi_p, target_parity):
        from ..ops import wilson_packed as wpk
        return wpk.dslash_eo_packed(self.gauge_eo_p, psi_p, self.dims,
                                    target_parity)

    def M(self, x_p):
        p = self.matpc
        tmp = self.D_to(x_p, 1 - p)
        return x_p - (self.kappa ** 2) * self.D_to(tmp, p)

    def Mdag(self, x_p):
        sign = jnp.asarray([1.0, 1.0, -1.0, -1.0], x_p.real.dtype)
        g5 = sign[:, None, None, None, None].astype(x_p.dtype)
        return g5 * self.M(g5 * x_p)

    def MdagM(self, x_p):
        return self.Mdag(self.M(x_p))

    def prepare(self, b_even, b_odd):
        from ..ops import wilson_packed as wpk
        return wpk.pack_spinor(self._dpc.prepare(b_even, b_odd))

    def reconstruct(self, x_p_packed, b_even, b_odd):
        from ..ops import wilson_packed as wpk
        T, Z, Y, X = self.dims
        x_p = wpk.unpack_spinor(x_p_packed, (T, Z, Y, X // 2))
        return self._dpc.reconstruct(x_p, b_even, b_odd)

    def flops_per_site_M(self) -> int:
        return self._dpc.flops_per_site_M()

    def sloppy(self, prec: str = "half") -> "DiracWilsonPCPackedSloppy":
        """bf16 companion on the PACKED pair layout (matSloppy analog;
        int8 'quarter' falls back to bf16 storage here)."""
        return DiracWilsonPCPackedSloppy(self)

    def pairs(self, store_dtype=jnp.bfloat16, use_pallas: bool = False,
              pallas_interpret: bool = False,
              mesh=None,
              sharded_policy: str | None = None,
              precision_form: str | None = None
              ) -> "DiracWilsonPCPackedSloppy":
        """Pair-storage companion at an arbitrary storage dtype.

        With f32 storage this is the PRECISE operator in a fully
        complex-free representation — what the pallas kernels consume,
        and the native-order analog of QUDA keeping solver fields in
        float2/float4 orders (no complex type on the device either).
        ``use_pallas`` swaps the stencil for the hand-tuned pallas eo
        kernel (the gather kernel with resident pre-shifted backward
        links).  ``mesh``: a jax.sharding.Mesh with t/z/y/x axes
        partitioning the lattice — the stencil then runs the sharded
        eo pallas policy under shard_map on the same kernel
        (multi-chip CG hot loop, lib/dslash_policy.hpp:522
        analog), with ``sharded_policy`` (or QUDA_TPU_SHARDED_POLICY)
        selecting the halo transport: xla_facefix, fused_halo, or auto
        (raced via utils.tune)."""
        return DiracWilsonPCPackedSloppy(self, store_dtype, use_pallas,
                                         pallas_interpret, mesh=mesh,
                                         sharded_policy=sharded_policy,
                                         precision_form=precision_form)

    def codec(self, precise_dtype, store_dtype=None):
        """StorageCodec matching this operator's sloppy representation
        (pass the built sloppy operator's store_dtype)."""
        from ..solvers.mixed import packed_pair_codec
        return packed_pair_codec(store_dtype or jnp.bfloat16,
                                 precise_dtype)


class DiracWilsonPCPackedSloppy(_ProgramOperand, _PackedHopMixin,
                                _PairSloppyBase):
    """bf16 pair-storage PC Wilson operator on the PACKED layout:
    spinors (4,3,2,T,Z,Y*Xh) bf16, gauge likewise — the sloppy stencil
    of the packed solve path (ops/wilson_packed.dslash_eo_packed_pairs).
    Hop/gauge machinery comes from _PackedHopMixin; the complex
    boundary stays in the PACKED complex order (the packed operator's
    interface), overriding the mixin's canonical converters."""

    def __init__(self, dpk: "DiracWilsonPCPacked", store_dtype=jnp.bfloat16,
                 use_pallas: bool = False, pallas_interpret: bool = False,
                 mesh=None, sharded_policy: str | None = None,
                 precision_form: str | None = None):
        self._setup_hop(dpk.geom, dpk.gauge_eo_p, store_dtype,
                        use_pallas, pallas_interpret,
                        tb_sign=getattr(dpk._dpc, "antiperiodic_t", True),
                        mesh=mesh, sharded_policy=sharded_policy,
                        precision_form=precision_form)
        self.kappa = float(dpk.kappa)
        self.matpc = dpk.matpc

    @classmethod
    def from_packed(cls, geom, gauge_eo_packed, kappa, matpc,
                    store_dtype=jnp.float32, use_pallas: bool = False,
                    pallas_interpret: bool = False, tb_sign: bool = True
                    ) -> "DiracWilsonPCPackedSloppy":
        """From the boundary-folded packed links alone
        (wilson_packed.pack_gauge_eo): what a resident Wilson term is
        built from, no canonical DiracWilsonPC or DiracWilsonPCPacked
        in between.  The kernel form is resolved from the environment
        as ``pairs()`` does without pins."""
        op = object.__new__(cls)
        op._setup_hop(geom, gauge_eo_packed, store_dtype, use_pallas,
                      pallas_interpret, tb_sign=tb_sign)
        op.kappa = float(kappa)
        op.matpc = matpc
        return op

    def _to_pairs(self, x):
        from ..ops import wilson_packed as wpk
        return wpk.to_packed_pairs(x, self.store_dtype)

    def _from_pairs(self, x, dtype):
        from ..ops import wilson_packed as wpk
        return wpk.from_packed_pairs(x, dtype)

    # -- canonical-boundary helpers (complex-free solve orchestration) --
    def prepare_pairs(self, b_even, b_odd):
        """Canonical complex parity sources -> pair-form PC rhs:
        b_p + kappa D b_q, the DiracWilsonPC.prepare composition on the
        pair representation (the one home for that formula off the
        complex path).  Uses the mixin's CANONICAL converter explicitly
        — this class's own _to_pairs takes packed-complex arrays."""
        from ..fields.geometry import EVEN
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        to_pp = lambda x: _PackedHopMixin._to_pairs(self, x)
        rhs = (to_pp(b_p).astype(jnp.float32)
               + self.kappa * self._d_to(to_pp(b_q), p, jnp.float32))
        return rhs

    def solution_from_pairs(self, x_pp, dtype=jnp.complex64):
        """Pair-form PC solution -> canonical complex parity field."""
        return _PackedHopMixin._from_pairs(self, x_pp, dtype)

    def reconstruct_pairs(self, x_pp, b_even, b_odd):
        """Pair-form PC solution + canonical complex sources -> canonical
        complex parity fields: x_q = b_q + kappa D x_p
        (DiracWilsonPC.reconstruct composed on the pair representation,
        so the opposite-parity hop runs the SAME complex-free stencil as
        the solve — the pallas-in-solver route's reconstruction)."""
        from ..fields.geometry import EVEN
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        to_pp = lambda x: _PackedHopMixin._to_pairs(self, x)
        t = self._d_to(x_pp, 1 - p, jnp.float32)
        xq_pp = to_pp(b_q).astype(jnp.float32) + self.kappa * t
        x_p = _PackedHopMixin._from_pairs(self, x_pp, b_q.dtype)
        x_q = _PackedHopMixin._from_pairs(self, xq_pp, b_q.dtype)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def verified_exit_pairs(self, b, x_pp):
        """The API's verified exit on the pair representation: the
        canonical full-lattice source ``b`` and the pair-form PC
        solution -> (canonical full-lattice solution, |b - M x| / |b|).
        x_q = b_q + kappa D x_p is the reconstruction; the residual is
        that of the RETURNED solution under the full M = 1 - kappa D,
        applied parity by parity with this operator's own hop in f32:
        no canonical (...,4,3) temporary beyond the two boundaries.
        With a leading source axis on both, the MRHS hop and one
        residual per source.  Meant to be traced (solvers/program.py)
        on the f32 operator."""
        from ..fields.geometry import EVEN
        from ..fields.spinor import even_odd_join, even_odd_split
        f32, p, kappa = jnp.float32, self.matpc, self.kappa
        batched = b.ndim == 7
        per_src = jax.vmap if batched else (lambda f: f)
        hop = self._d_to_mrhs if batched else self._d_to
        to_pp = per_src(
            lambda v: _PackedHopMixin._to_pairs(self, v).astype(f32))
        from_pp = per_src(
            lambda v: _PackedHopMixin._from_pairs(self, v, b.dtype))
        norm2 = per_src(lambda v: jnp.sum(v * v))
        halves = per_src(lambda v: even_odd_split(v, self.geom))(b)
        b_p, b_q = (to_pp(h)
                    for h in (halves if p == EVEN else halves[::-1]))
        x_p = x_pp.astype(f32)
        # D x_p serves the reconstruction and the q half of M x (the
        # compiler merges a second identical hop anyway)
        d_xp = hop(x_p, 1 - p, f32)
        x_q = b_q + kappa * d_xp
        r_p = b_p - (x_p - kappa * hop(x_q, p, f32))
        r_q = b_q - (x_q - kappa * d_xp)
        x_e, x_o = (from_pp(v)
                    for v in ((x_p, x_q) if p == EVEN else (x_q, x_p)))
        x = per_src(lambda e, o: even_odd_join(e, o, self.geom))(x_e, x_o)
        return x, jnp.sqrt((norm2(r_p) + norm2(r_q))
                           / (norm2(b_p) + norm2(b_q)))

    # -- the batched operator, its combine in the second hop's epilogue --
    # Where the batched hop is the plain pallas MRHS kernel
    # (``_plain_mrhs``: decided by the operator's own route, no knob)
    # the second hop writes [g5] (x - kappa^2 D D x) itself
    # (ops/wilson_pallas_packed: the combine epilogue, kappa an SMEM
    # operand), so the CG tail reads one array where the base
    # composition hands XLA the bare hop sum and x (PERF.md section 6,
    # PR 33).  Every other representation keeps _PairSloppyBase's
    # composition, operation for operation; prepare / reconstruct / the
    # verified exit apply bare hops either way.  The same epilogue sums
    # the squares of what it stores, per source: |g5 M x|^2 is the
    # batched CG's pAp (PR 37), and with alpha known from it the last
    # hop of an iteration writes r - alpha MdagM p and sums the new
    # |r|^2 (MdagM_cg_step_pairs_mrhs; PR 39).
    def _M_g5_norm2_pairs_mrhs(self, x, g5: bool):
        """``([g5] M x, its squared norms per source)`` on the plain
        kernel route: both from the second hop's epilogue."""
        p = self.matpc
        tmp = self._hop_mrhs(x, 1 - p, self.store_dtype)
        return self._hop_mrhs(tmp, p, self.store_dtype, xc=x,
                              coeff=-(self.kappa ** 2), g5=g5)

    def _M_g5_pairs_mrhs(self, x, g5: bool):
        """``M x``, or ``g5 M x``."""
        if not self._plain_mrhs():
            out = super().M_pairs_mrhs(x)
            return self._g5_pairs_mrhs(out) if g5 else out
        return self._M_g5_norm2_pairs_mrhs(x, g5)[0]

    def M_pairs_mrhs(self, x):
        return self._M_g5_pairs_mrhs(x, False)

    def Mdag_pairs_mrhs(self, x):
        return self._M_g5_pairs_mrhs(self._g5_pairs_mrhs(x), True)

    def MdagM_pairs_mrhs(self, x):
        # g5 M g5 M x: each M's second hop applies the g5 in front of it
        return self._M_g5_pairs_mrhs(self._M_g5_pairs_mrhs(x, True), True)

    def MdagM_cg_step_pairs_mrhs(self, p, r, rz, k=None):
        """The first half of a batched CG iteration on MdagM, what
        solvers/block.batched_cg_pairs_loop applies: from the search
        directions ``p``, the residuals ``r`` and their ``|r|^2``
        ``rz`` to ``(r - alpha MdagM p, its squared norms per source,
        alpha, pAp)``.  MdagM is g5 M g5 M, so ``p . MdagM p = |q|^2``
        with ``q = g5 M p``, which the hop that stores ``q`` sums as it
        stores (the combine epilogue): ``alpha`` is known before the
        second ``M``, whose last hop then writes ``r - alpha g5 M q``
        in ``r``'s place and sums that (the residual form), and
        ``MdagM p`` is never stored.  No pass over the batch for
        ``pAp``, the update of ``r`` or ``|r|^2``.  ``k``, the
        iteration, is the generic step's (an armed fault).  Off the
        plain kernel route: solvers/block.cg_step of
        ``MdagM_pairs_mrhs``, XLA's dot, update and sum."""
        from ..solvers import block
        if not self._plain_mrhs():
            return block.cg_step(self.MdagM_pairs_mrhs)(p, r, rz, k)
        q, pAp = self._M_g5_norm2_pairs_mrhs(p, True)
        alpha = block.cg_alpha(rz, pAp)
        tmp = self._hop_mrhs(q, 1 - self.matpc, self.store_dtype)
        r, r2 = self._hop_mrhs(tmp, self.matpc, self.store_dtype, xc=q,
                               coeff=-(self.kappa ** 2), g5=True, rc=r,
                               alpha=alpha)
        return r, r2, alpha, pAp

    # -- multi-RHS boundary helpers (the invert_multi_src_quda route) --
    def prepare_pairs_mrhs(self, b_even_b, b_odd_b):
        """Batched canonical complex parity sources (N, T,Z,Y,Xh,4,3) ->
        batched pair-form PC rhs (N,4,3,2,T,Z,Y*Xh): prepare_pairs with
        the batched hop, so the MRHS stencil serves source preparation
        too (gauge read once for all N)."""
        from ..fields.geometry import EVEN
        p = self.matpc
        b_p, b_q = ((b_even_b, b_odd_b) if p == EVEN
                    else (b_odd_b, b_even_b))
        to_pp = jax.vmap(lambda x: _PackedHopMixin._to_pairs(self, x))
        rhs = (to_pp(b_p).astype(jnp.float32)
               + self.kappa * self._d_to_mrhs(to_pp(b_q), p,
                                              jnp.float32))
        return rhs

    def solution_from_pairs_mrhs(self, x_b, dtype=jnp.complex64):
        return jax.vmap(
            lambda x: _PackedHopMixin._from_pairs(self, x, dtype))(x_b)

    def reconstruct_pairs_mrhs(self, x_b, b_even_b, b_odd_b):
        """Batched reconstruct_pairs: x_q = b_q + kappa D x_p with the
        MRHS hop.  Returns canonical complex (even, odd) batches."""
        from ..fields.geometry import EVEN
        p = self.matpc
        b_q = b_odd_b if p == EVEN else b_even_b
        to_pp = jax.vmap(lambda x: _PackedHopMixin._to_pairs(self, x))
        t = self._d_to_mrhs(x_b, 1 - p, jnp.float32)
        xq_b = to_pp(b_q).astype(jnp.float32) + self.kappa * t
        x_p = self.solution_from_pairs_mrhs(x_b, b_q.dtype)
        x_q = self.solution_from_pairs_mrhs(xq_b, b_q.dtype)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)


jax.tree_util.register_pytree_node_class(DiracWilsonPCPackedSloppy)


class DiracWilsonPCSloppy(_PairSloppyBase):
    """Low-precision PC Wilson operator on CANONICAL pair storage
    (T,Z,Y,X//2,4,3,2): bf16 ('half') or int8 block-float gauge
    ('quarter'); the whole sloppy CG loop stays in half storage."""

    _spin_axis = -3

    def __init__(self, dpc: DiracWilsonPC, prec: str = "half"):
        from ..ops import pair as pops
        self.geom = dpc.geom
        self.kappa = float(dpc.kappa)
        self.matpc = dpc.matpc
        self.prec = prec
        # links are already boundary-phase folded in the precise operator
        self.gauge_eo_st = tuple(
            pops.encode_gauge(dpc.gauge_eo[p], prec) for p in (0, 1))

    def _d_to(self, psi_pairs, target_parity, out_dtype):
        from ..ops import pair as pops
        return pops.dslash_eo_pairs(self.gauge_eo_st, psi_pairs, self.geom,
                                    target_parity, out_dtype=out_dtype)

    def _to_pairs(self, x):
        from ..ops import pair as pops
        return pops.to_pairs(x, self.store_dtype)

    def _from_pairs(self, x, dtype):
        from ..ops import pair as pops
        return pops.from_pairs(x, dtype)
