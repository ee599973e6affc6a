"""Staggered and improved-staggered (asqtad/HISQ) Dirac operators.

Reference behavior: lib/dirac_staggered.cpp, lib/dirac_improved_staggered.cpp.
M = 2m + D with anti-Hermitian D, MILC mass convention.  The even/odd
operator exploits that M^dag M = 4m^2 - D_{p q} D_{q p} is Hermitian
positive definite per parity — staggered CG solves it directly
(DiracStaggeredPC::MdagM in QUDA does exactly this).

prepare/reconstruct for the PC solve of M x = b:
    on parity p:   (4m^2 - D_pq D_qp) x_p = 2m b_p - D_pq b_q
    then           x_q = (b_q - D_qp x_p) / (2m)
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from ..fields.geometry import EVEN, LatticeGeometry
from ..ops import staggered as sops
from ..ops.boundary import apply_staggered_phases
from ..ops.wilson import split_gauge_eo
from .dirac import Dirac, DiracPC, MATPC_EVEN_EVEN
from .wilson import _ProgramOperand


class DiracStaggered(Dirac):
    """Full-lattice staggered operator M = 2m + D (nspin=1 fields)."""

    g5_hermitian = False  # staggered uses epsilon(x) = (-1)^(x+y+z+t) instead

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry, mass: float,
                 improved: bool = False, long_links: jnp.ndarray | None = None,
                 fold_phases: bool = True, antiperiodic_t: bool = True):
        self.geom = geom
        self.mass = mass
        self.improved = improved
        if fold_phases:
            gauge = apply_staggered_phases(gauge, geom, antiperiodic_t)
            if long_links is not None:
                long_links = apply_staggered_phases(long_links, geom,
                                                    antiperiodic_t, nhop=3)
        self.fat = gauge
        self.long = long_links if improved else None

    def D(self, psi):
        return sops.dslash_full(self.fat, psi, self.long)

    def M(self, psi):
        return 2.0 * self.mass * psi + self.D(psi)

    def Mdag(self, psi):
        # D anti-Hermitian: Mdag = 2m - D
        return 2.0 * self.mass * psi - self.D(psi)

    def flops_per_site_M(self) -> int:
        return (1146 if self.improved else 570) + 24

    # --- diag + per-direction hop decomposition (MG coarsening probes;
    # fat links only: the 3-hop Naik term is dropped from the MG
    # PRECONDITIONER stencil, the standard staggered-MG simplification —
    # the outer solve still uses the full operator) ---
    nspin = 1

    def diag(self, psi):
        return 2.0 * self.mass * psi

    def hop(self, psi, mu, sign):
        return sops.hop_term(self.fat, psi, mu, sign)


class DiracStaggeredPC(DiracPC):
    """Parity-restricted staggered normal operator 4m^2 - D_pq D_qp.

    This IS the solver operator (Hermitian positive definite); M() returns
    it directly so cg(dpc.M, ...) needs no normal-equation wrap.
    """

    hermitian = True
    g5_hermitian = False

    def __init__(self, gauge: jnp.ndarray, geom: LatticeGeometry, mass: float,
                 improved: bool = False, long_links: jnp.ndarray | None = None,
                 matpc: int = MATPC_EVEN_EVEN, fold_phases: bool = True,
                 antiperiodic_t: bool = True):
        self.geom = geom
        self.mass = mass
        self.matpc = matpc
        self.improved = improved
        if fold_phases:
            gauge = apply_staggered_phases(gauge, geom, antiperiodic_t)
            if long_links is not None:
                long_links = apply_staggered_phases(long_links, geom,
                                                    antiperiodic_t, nhop=3)
        self.fat_eo = split_gauge_eo(gauge, geom)
        self.long_eo = (split_gauge_eo(long_links, geom)
                        if improved and long_links is not None else None)

    def D_to(self, psi, target_parity):
        return sops.dslash_eo(self.fat_eo, psi, self.geom, target_parity,
                              self.long_eo)

    def M(self, x_p):
        p = self.matpc
        return (4.0 * self.mass ** 2) * x_p - self.D_to(self.D_to(x_p, 1 - p), p)

    def Mdag(self, x_p):
        return self.M(x_p)

    def MdagM(self, x_p):
        # the PC operator is already the normal operator; MdagM is provided
        # for interface parity but solvers should use M directly
        return self.M(self.M(x_p))

    def flops_per_site_M(self) -> int:
        # two half-lattice dslashes + shifted axpy (the DiracWilsonPC
        # counting convention; improved adds the 3-hop Naik term)
        return 2 * (1146 if self.improved else 570) + 24

    def prepare(self, b_even, b_odd):
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        return 2.0 * self.mass * b_p - self.D_to(b_q, p)

    def reconstruct(self, x_p, b_even, b_odd):
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        x_q = (b_q - self.D_to(x_p, 1 - p)) / (2.0 * self.mass)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def pairs(self, store_dtype=jnp.float32, use_pallas: bool = False,
              pallas_interpret: bool = False,
              form: str | None = None, mesh=None,
              sharded_policy: str | None = None
              ) -> "DiracStaggeredPCPairs":
        """Complex-free packed companion (f32 = the precise TPU solve
        path; bf16 = the sloppy operator); see DiracStaggeredPCPairs."""
        return DiracStaggeredPCPairs(self, store_dtype, use_pallas,
                                     pallas_interpret,
                                     form=form, mesh=mesh,
                                     sharded_policy=sharded_policy)


_STAG_FORM_NOTICED = False


def _notice_staggered_form(form: str, policy: str | None, source: str):
    """One-time provenance notice of what a mesh changed: a requested
    form it cannot serve, and the halo policy it pinned or raced (the
    round-6 wilson.py notice rule: no decision without a trace)."""
    global _STAG_FORM_NOTICED
    if _STAG_FORM_NOTICED:
        return
    _STAG_FORM_NOTICED = True
    from ..utils import logging as qlog
    pol = f", halo policy {policy}" if policy else ""
    qlog.printq(
        f"staggered dslash: pallas form {form}{pol} ({source}); pin the "
        "halo policy via QUDA_TPU_SHARDED_POLICY", qlog.SUMMARIZE)


STAGGERED_FORMS = ("two_pass", "v3")


def served_forms(improved: bool, use_pallas: bool, mesh_axes=(),
                 form: str | None = None) -> tuple:
    """(hop form, batched-hop form) a staggered pair operator serves:
    the ONE place either is decided, from what the operator is built
    with and nothing else (no environment read, no race).

    ``improved``: fat + Naik links (else fat only).  ``mesh_axes``: the
    lattice axes a mesh partitions (names among t, z, y, x; empty on
    one chip).  ``form``: a caller's request, ``two_pass`` (the gather
    kernel on resident pre-shifted backward links) or ``v3`` (the
    scatter kernel, no backward links), honoured wherever a kernel
    runs that can serve it.

    * one chip, kernels, fat + Naik -> (``v3``, ``scatter_two_pass``):
      the chip's readings.  One v5e, 24^4: in the CG loop of
      hisq24_single.strange v3 reads call_s 0.0883 s against two_pass
      0.0964 (PERF.md section 6, PR 32); 8 sources in f32, the v3 pass
      under the RHS-innermost wrap reads 0.636 s against the gather
      MRHS kernel's 0.656 and ``vmap`` of v3's 1.304 (PR 35).
    * one chip, kernels, fat only -> (``two_pass``,
      ``gather_two_pass``).  This hop set was never read on the chip
      and no benchmark cell builds it; until PR 45 such an operator
      raced two_pass against v3 at construction on a chip with tuning
      on, and served two_pass everywhere else.
    * a mesh -> ``two_pass``, or ``v3`` where a caller asks for it and
      the mesh partitions t / z only (the scatter exterior shards no
      y / x); the batched hop is ``vmap`` of the single-source hop.
      A mesh needs the kernels.
    * the XLA stencil -> (``two_pass``, ``vmap``): a label, there is
      no kernel to pick.
    """
    if form is not None and form not in STAGGERED_FORMS:
        raise ValueError(f"staggered form must be one of "
                         f"{STAGGERED_FORMS}, got {form!r}")
    if not use_pallas:
        if mesh_axes:
            raise ValueError(
                "mesh-sharded staggered pair operators need "
                "use_pallas=True (the XLA pair stencil shards via "
                "GSPMD instead)")
        return "two_pass", "vmap"
    if mesh_axes:
        t_z_only = not ({"y", "x"} & set(mesh_axes))
        return ("v3" if form == "v3" and t_z_only else "two_pass"), "vmap"
    return (form or ("v3" if improved else "two_pass"),
            "scatter_two_pass" if improved else "gather_two_pass")


class DiracStaggeredPCPairs(_ProgramOperand):
    """Complex-free packed pair-form of DiracStaggeredPC — the staggered
    solver operator for TPU runtimes without complex64 execution, and
    (with bf16 storage) the sloppy staggered operator of mixed solves.

    Mirrors models/wilson.DiracWilsonPCPackedSloppy: half-lattice links
    packed to (4,3,3,2,T,Z,Y*Xh) re/im planes at ``store_dtype``, spinors
    (3,2,T,Z,Y*Xh); compute f32.  ``use_pallas`` swaps the stencil for
    the hand-tuned eo kernels (ops/staggered_pallas); which kernel body
    serves the hop and the batched hop is ``served_forms``' decision,
    ``form`` a caller's request to it:

    * ``two_pass`` — separate fat/long gather launches with resident
                     pre-shifted backward links;
    * ``v3``       — the two-pass scatter form, no backward links (what
                     fat + Naik serves on one chip).

    Links are kept in full storage whatever QUDA_TPU_PRECISION_FORM
    asks the Wilson family for (a one-time notice says so).

    ``mesh`` runs the hop under shard_map (t/z mesh axes partition T/Z)
    through the sharded staggered eo policies
    (parallel/pallas_dslash.dslash_staggered_eo_pallas_sharded[_v3]),
    with the halo transport picked by ``sharded_policy`` /
    QUDA_TPU_SHARDED_POLICY — the same policy seam as Wilson ('auto'
    races and caches per (volume, mesh, form)).

    Reference behavior: QUDA solves staggered systems in float2-pair
    native orders on device too (include/color_spinor_field_order.h);
    this is that representation made explicit.
    """

    hermitian = True

    # the solve-program operand (solvers/program.py): resident links,
    # the gather forms' pre-shifted backward links and the mass are
    # leaves; what picks the traced hop is static
    _PROGRAM_ARRAYS = ("fat_eo_pp", "long_eo_pp", "_fat_bw", "_long_bw",
                       "mass")
    _PROGRAM_STATIC = ("geom", "dims", "matpc", "store_dtype",
                       "use_pallas", "_pallas_interpret", "_pallas_form",
                       "_mrhs_form")

    def __init__(self, dpc: DiracStaggeredPC, store_dtype=jnp.float32,
                 use_pallas: bool = False, pallas_interpret: bool = False,
                 form: str | None = None, mesh=None,
                 sharded_policy: str | None = None):
        from ..ops import staggered_packed as spk
        from ..ops.wilson_packed import to_packed_pairs
        pack = lambda gs: tuple(
            to_packed_pairs(spk.pack_links(g), store_dtype) for g in gs)
        self._setup(dpc.geom, dpc.mass, dpc.matpc, pack(dpc.fat_eo),
                    pack(dpc.long_eo) if dpc.long_eo is not None
                    else None, store_dtype, use_pallas, pallas_interpret,
                    form, mesh, sharded_policy)

    @classmethod
    def from_packed(cls, geom, fat_eo_pp, long_eo_pp, mass, matpc,
                    store_dtype=jnp.float32, use_pallas: bool = False,
                    pallas_interpret: bool = False,
                    form: str | None = None) -> "DiracStaggeredPCPairs":
        """From the phase- and boundary-folded (even, odd) pair links
        alone (ops/staggered_packed.ks_links_eo_pairs, at
        ``store_dtype``): what a resident KS term is built from, no
        canonical DiracStaggeredPC in between.  The kernel form is
        ``served_forms``', as for ``pairs()``."""
        op = object.__new__(cls)
        op._setup(geom, mass, matpc, fat_eo_pp, long_eo_pp, store_dtype,
                  use_pallas, pallas_interpret, form, None, None)
        return op

    def with_mass(self, mass: float):
        """The same resident arrays under another mass (a leaf of the
        pytree: a program's executable is shared)."""
        op = copy.copy(self)
        op.mass = float(mass)
        return op

    def _setup(self, geom, mass, matpc, fat_eo_pp, long_eo_pp,
               store_dtype, use_pallas, pallas_interpret, form, mesh,
               sharded_policy):
        from ..utils import config as qconf
        self.geom = geom
        self.mass = float(mass)
        self.matpc = matpc
        self.dims = tuple(geom.lattice_shape)
        self.store_dtype = store_dtype
        self.fat_eo_pp = tuple(fat_eo_pp)
        self.long_eo_pp = (tuple(long_eo_pp) if long_eo_pp is not None
                           else None)
        self.use_pallas = use_pallas
        if use_pallas:
            # pallas-construction fault seam (robust/faultinject.py) —
            # the staggered construction-failure fallback: the
            # escalation ladder catches this and re-solves on the XLA
            # stencil form (same seam as models/wilson._setup_hop)
            from ..robust import faultinject as finj
            finj.maybe_raise("pallas_build")
        self._pallas_interpret = pallas_interpret
        self._fat_bw = self._long_bw = None

        # single-chip escape: a 1-device mesh shards nothing
        if mesh is not None and getattr(mesh, "size", 2) == 1:
            mesh = None
        self._mesh = mesh
        self._mesh_yx = None
        mesh_axes = ()
        if mesh is not None:
            from ..parallel.pallas_dslash import AXIS_NAMES, _mesh_counts
            mesh_axes = tuple(a for a, n in zip(AXIS_NAMES,
                                                _mesh_counts(mesh))
                              if n > 1)
        self._pallas_form, self._mrhs_form = served_forms(
            self.long_eo_pp is not None, use_pallas, mesh_axes, form)
        if form is not None and form != self._pallas_form and use_pallas:
            _notice_staggered_form(
                self._pallas_form, None,
                f"the scatter exterior shards t/z only; a y/x mesh "
                f"serves {self._pallas_form} (requested {form})")
        form = self._pallas_form
        if mesh is not None:
            self._sharded_policy = (
                sharded_policy
                or str(qconf.get("QUDA_TPU_SHARDED_POLICY", fresh=True))
                or "auto")
            from ..parallel.pallas_dslash import (
                SHARDED_POLICIES, notice_legacy_single_policy)
            if self._sharded_policy in SHARDED_POLICIES:
                # bare single-value form: maps onto every partitioned
                # axis, with a one-time deprecation-style notice
                notice_legacy_single_policy(self._sharded_policy)

        # links stay in full storage: the precision storage forms of
        # QUDA_TPU_PRECISION_FORM are the Wilson family's
        pform = str(qconf.get("QUDA_TPU_PRECISION_FORM", fresh=True))
        if pform and pform != "full":
            from .wilson import _notice_precision_form
            _notice_precision_form(
                pform, "full", "the staggered family serves full "
                "storage only")

        # the gather form keeps resident pre-shifted backward links
        # (the scatter form reads the opposite-parity links as they are)
        if use_pallas and mesh is None and form == "two_pass":
            self._ensure_bw()

        # multi-chip: move the resident links (and the globally
        # pre-shifted backward links the gather form needs) onto the
        # mesh once here, then resolve the halo policy
        if mesh is not None:
            if form == "two_pass":
                self._ensure_bw()
            # y/x-partitioned meshes: re-order the trailing fused Y·Xh
            # axis into the block-contiguous layout ONCE, after the
            # backward pre-shift (which needs the natural global
            # order), so the ("y","x") PartitionSpec hands every shard
            # whole local rows at the LOCAL row width
            _, _, n_y, n_x = _mesh_counts(mesh)
            self._mesh_yx = (n_y, n_x)
            if n_x > 1:
                from ..parallel import mesh as qmesh
                _, _, Y, X = self.dims
                rl = lambda gs: (tuple(
                    qmesh.fuse_block_layout(g, n_y, n_x, Y, X // 2)
                    for g in gs) if gs is not None else None)
                self.fat_eo_pp = rl(self.fat_eo_pp)
                self.long_eo_pp = rl(self.long_eo_pp)
                self._fat_bw = rl(self._fat_bw)
                self._long_bw = rl(self._long_bw)
            from jax.sharding import NamedSharding, PartitionSpec as P
            gspec = NamedSharding(
                mesh,
                P(None, None, None, None, "t", "z", ("y", "x")))
            put = lambda gs: (tuple(jax.device_put(g, gspec)
                                    for g in gs)
                              if gs is not None else None)
            self.fat_eo_pp = put(self.fat_eo_pp)
            self.long_eo_pp = put(self.long_eo_pp)
            self._fat_bw = put(self._fat_bw)
            self._long_bw = put(self._long_bw)
            if self._sharded_policy == "auto":
                # race EAGERLY, at construction (the first hop usually
                # fires inside a solver trace, where timing concrete
                # candidates is impossible)
                self._resolve_sharded_policy(self.matpc, None)
            else:
                from ..parallel.pallas_dslash import (
                    _policy_label, resolve_axis_policies)
                pols = resolve_axis_policies(self._sharded_policy)
                self._sharded_policy = pols
                _notice_staggered_form(
                    form, _policy_label(pols, list(mesh_axes)), "pinned")

    def _ensure_bw(self):
        """Resident pre-shifted backward links of the gather forms
        (backward_links_eo on the GLOBAL arrays — under a mesh their t/z
        shifts then already carry the cross-shard links), computed once
        per KS-link load and shared by the two_pass and MRHS kernels."""
        if self._fat_bw is not None:
            return
        from ..ops import staggered_pallas as spl
        self._fat_bw = tuple(
            spl.backward_links_eo(self.fat_eo_pp[1 - p], self.dims,
                                  p, 1) for p in (0, 1))
        self._long_bw = (tuple(
            spl.backward_links_eo(self.long_eo_pp[1 - p], self.dims,
                                  p, 3) for p in (0, 1))
            if self.long_eo_pp is not None else None)

    # -- sharded dispatch (the QUDA_TPU_SHARDED_POLICY seam) ------------
    def _build_sharded_fn(self, target_parity, out_dtype, policy):
        """jitted shard_map of the sharded staggered eo policy for one
        (parity, out_dtype, halo policy) configuration; ``policy`` is
        anything resolve_axis_policies accepts."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.pallas_dslash import (
            dslash_staggered_eo_pallas_sharded,
            dslash_staggered_eo_pallas_sharded_v3)
        pspec = P(None, None, "t", "z", ("y", "x"))
        gspec = P(None, None, None, None, "t", "z", ("y", "x"))
        improved = self.long_eo_pp is not None
        odt = out_dtype or self.store_dtype

        if self._pallas_form == "two_pass":
            def local(fh, fb, lh, lb, psi):
                return dslash_staggered_eo_pallas_sharded(
                    fh, fb, psi, self.dims, target_parity, self._mesh,
                    long_here_pl=lh, long_bw_pl=lb,
                    interpret=self._pallas_interpret,
                    policy=policy).astype(odt)
        else:
            def local(fh, ft, lh, lt, psi):
                return dslash_staggered_eo_pallas_sharded_v3(
                    fh, ft, psi, self.dims, target_parity, self._mesh,
                    long_here_pl=lh, long_there_pl=lt,
                    interpret=self._pallas_interpret,
                    policy=policy).astype(odt)
        n_g = 4 if improved else 2
        if improved:
            fn = jax.shard_map(
                local, mesh=self._mesh,
                in_specs=(gspec,) * n_g + (pspec,), out_specs=pspec,
                check_vma=False)
        else:
            fn = jax.shard_map(
                lambda fh, fb, psi: local(fh, fb, None, None, psi),
                mesh=self._mesh, in_specs=(gspec, gspec, pspec),
                out_specs=pspec, check_vma=False)
        return jax.jit(fn)

    def _sharded_args(self, target_parity):
        p = target_parity
        second = (self._fat_bw[p] if self._pallas_form == "two_pass"
                  else self.fat_eo_pp[1 - p])
        if self.long_eo_pp is None:
            return (self.fat_eo_pp[p], second)
        fourth = (self._long_bw[p] if self._pallas_form == "two_pass"
                  else self.long_eo_pp[1 - p])
        return (self.fat_eo_pp[p], second, self.long_eo_pp[p], fourth)

    def _resolve_sharded_policy(self, target_parity, out_dtype):
        """'auto' races every PARTITIONED mesh axis independently on
        REAL shard-resident operands via utils.tune, greedily (each
        axis race pins its winner before the next races) and caches
        per (volume, mesh, form, axis) — the Wilson per-axis policy
        engine covering staggered through the same seam."""
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
        from jax.sharding import NamedSharding, PartitionSpec as P
        T, Z, _, _ = self.dims
        yxh = self.fat_eo_pp[0].shape[-1]
        psi0 = jax.device_put(
            jnp.zeros((3, 2, T, Z, yxh), self.store_dtype),
            NamedSharding(self._mesh,
                          P(None, None, "t", "z", ("y", "x"))))
        mesh_shape = tuple(int(self._mesh.shape[a])
                           for a in self._mesh.axis_names)
        aux = (f"{self._pallas_form}|mesh{mesh_shape}|"
               f"{jnp.dtype(self.store_dtype).name}")
        pols = {a: "xla_facefix" for a in AXIS_NAMES}
        warm, seeded = True, None
        for ax in live:
            axis_cands = [p for p in SHARDED_POLICIES
                          if p == "xla_facefix" or ax in FUSED_HALO_AXES]
            if len(axis_cands) < 2:
                continue    # x: only the facefix transport serves it
            cands = {p: self._build_sharded_fn(
                        target_parity, out_dtype, dict(pols, **{ax: p}))
                     for p in axis_cands}
            name = f"staggered_eo_sharded_policy_{ax}"
            warm = warm and (qtune.cached_param(
                name, self.dims, aux=aux) is not None)
            pols[ax] = qtune.tune(
                name, self.dims, cands,
                self._sharded_args(target_parity) + (psi0,), aux=aux)
            seeded = cands[pols[ax]]
        self._sharded_policy_winner = pols
        key = (target_parity,
               jnp.dtype(out_dtype or self.store_dtype).name)
        if seeded is None:
            seeded = self._build_sharded_fn(target_parity, out_dtype,
                                            dict(pols))
        self.__dict__.setdefault("_sharded_fns", {})[key] = seeded
        _notice_staggered_form(
            self._pallas_form, _policy_label(pols, live),
            "warm cache (chip-keyed tunecache)" if warm
            else "raced+cached (QUDA_TPU_SHARDED_POLICY=auto)")
        return pols

    def _sharded_d_to(self, target_parity, out_dtype):
        cache = self.__dict__.setdefault("_sharded_fns", {})
        key = (target_parity,
               jnp.dtype(out_dtype or self.store_dtype).name)
        if key not in cache:
            policy = self._resolve_sharded_policy(target_parity,
                                                  out_dtype)
            cache[key] = self._build_sharded_fn(target_parity,
                                                out_dtype, policy)
        return cache[key]

    def D_to_pairs(self, psi_pp, target_parity, out_dtype=None):
        out_dtype = out_dtype or self.store_dtype
        if self.use_pallas:
            from ..ops import staggered_pallas as spl
            p = target_parity
            if self._mesh is not None:
                fn = self._sharded_d_to(p, out_dtype)
                return fn(*self._sharded_args(p), psi_pp)
            if self._pallas_form == "v3":
                return spl.dslash_staggered_eo_pallas_v3(
                    self.fat_eo_pp[p], self.fat_eo_pp[1 - p], psi_pp,
                    self.dims, p,
                    long_here_pl=(self.long_eo_pp[p]
                                  if self.long_eo_pp is not None else None),
                    long_there_pl=(self.long_eo_pp[1 - p]
                                   if self.long_eo_pp is not None
                                   else None),
                    interpret=self._pallas_interpret, out_dtype=out_dtype)
            return spl.dslash_staggered_eo_pallas(
                self.fat_eo_pp[p], self._fat_bw[p], psi_pp, self.dims, p,
                long_here_pl=(self.long_eo_pp[p]
                              if self.long_eo_pp is not None else None),
                long_bw_pl=(self._long_bw[p]
                            if self._long_bw is not None else None),
                interpret=self._pallas_interpret, out_dtype=out_dtype)
        from ..ops import staggered_packed as spk
        return spk.dslash_staggered_eo_packed_pairs(
            self.fat_eo_pp, psi_pp, self.dims, target_parity,
            self.long_eo_pp, out_dtype=out_dtype)

    def _d_to_mrhs(self, psi_b, target_parity, out_dtype=None):
        """Batched eo hop: psi_b (N,3,2,T,Z,Y*Xh), by ``_mrhs_form``
        (see ``served_forms``).  The MRHS kernels fetch the
        fat/long tiles once per (t, z-block) and stream the N spinor
        tiles through them; ``vmap`` is the single-RHS stencil per
        source.  Counted per traced call in
        ``staggered_mrhs_route_total``."""
        from ..obs import metrics as omet
        out_dtype = out_dtype or self.store_dtype
        form, p = self._mrhs_form, target_parity
        omet.inc("staggered_mrhs_route_total",
                 form=form if form != "vmap" else "vmap_" + (
                     self._pallas_form if self.use_pallas else "xla"))
        if form == "vmap":
            return jax.vmap(
                lambda q: self.D_to_pairs(q, p, out_dtype))(psi_b)
        from ..ops import staggered_pallas as spl
        lng = self.long_eo_pp
        if form == "scatter_two_pass":
            return spl.dslash_staggered_eo_pallas_v3_mrhs(
                self.fat_eo_pp[p], self.fat_eo_pp[1 - p], psi_b,
                self.dims, p,
                long_here_pl=lng[p] if lng is not None else None,
                long_there_pl=lng[1 - p] if lng is not None else None,
                interpret=self._pallas_interpret, out_dtype=out_dtype)
        self._ensure_bw()
        return spl.dslash_staggered_eo_pallas_mrhs(
            self.fat_eo_pp[p], self._fat_bw[p], psi_b, self.dims, p,
            long_here_pl=lng[p] if lng is not None else None,
            long_bw_pl=(self._long_bw[p]
                        if self._long_bw is not None else None),
            interpret=self._pallas_interpret, out_dtype=out_dtype)

    def M_pairs(self, x_pp):
        """(4m^2 - D_pq D_qp) on pair arrays — Hermitian positive
        definite; cg(op.M_pairs, rhs_pairs) solves it directly."""
        p = self.matpc
        dd = self.D_to_pairs(self.D_to_pairs(x_pp, 1 - p), p,
                             out_dtype=jnp.float32)
        out = (4.0 * self.mass ** 2) * x_pp.astype(jnp.float32) - dd
        return out.astype(self.store_dtype)

    Mdag_pairs = M_pairs

    def MdagM_pairs(self, x_pp):
        return self.M_pairs(self.M_pairs(x_pp))

    # -- multi-RHS (leading batch axis) forms ---------------------------
    # One home for the batched Schur composition so the MRHS solve path
    # (solvers/block.py, invert_multi_src_quda) cannot diverge from the
    # single-RHS math — the models/wilson pattern on the second headline
    # family.  The PC operator is Hermitian positive definite per lane,
    # so the batched solvers run it directly (no normal-equation wrap).

    def M_pairs_mrhs(self, x_b):
        p = self.matpc
        tmp = self._d_to_mrhs(x_b, 1 - p, self.store_dtype)
        dd = self._d_to_mrhs(tmp, p, jnp.float32)
        out = (4.0 * self.mass ** 2) * x_b.astype(jnp.float32) - dd
        return out.astype(self.store_dtype)

    Mdag_pairs_mrhs = M_pairs_mrhs

    def MdagM_pairs_mrhs(self, x_b):
        return self.M_pairs_mrhs(self.M_pairs_mrhs(x_b))

    # -- complex in/out wrappers (interface boundary) -------------------
    def _yx_block_pairs(self, x, inverse: bool = False):
        """x-sharded meshes keep resident links AND solver spinors in
        the block-contiguous fused layout (parallel/mesh.
        fuse_block_layout) — a pure site relabeling the packed solver
        algebra never observes; convert at the canonical boundary
        only.  Identity off-mesh and when the x axis is unpartitioned."""
        yx = getattr(self, "_mesh_yx", None)
        if yx is None or yx[1] == 1:
            return x
        from ..parallel import mesh as qmesh
        _, _, Y, X = self.dims
        f = (qmesh.unfuse_block_layout if inverse
             else qmesh.fuse_block_layout)
        return f(x, yx[0], yx[1], Y, X // 2)

    def _to_pairs(self, x):
        from ..ops import staggered_packed as spk
        from ..ops.wilson_packed import to_packed_pairs
        return self._yx_block_pairs(
            to_packed_pairs(spk.pack_staggered(x), self.store_dtype))

    def _from_pairs(self, x_pp, dtype):
        from ..ops import staggered_packed as spk
        from ..ops.wilson_packed import from_packed_pairs
        T, Z, Y, X = self.dims
        return spk.unpack_staggered(
            from_packed_pairs(self._yx_block_pairs(x_pp, inverse=True),
                              dtype), (T, Z, Y, X // 2))

    def M(self, x):
        return self._from_pairs(self.M_pairs(self._to_pairs(x)), x.dtype)

    Mdag = M

    def MdagM(self, x):
        return self._from_pairs(self.MdagM_pairs(self._to_pairs(x)),
                                x.dtype)


    # -- pair-space Schur boundary (the whole solve stays complex-free) --
    def prepare_pairs(self, b_even, b_odd):
        """Canonical complex parity sources -> pair-form PC rhs:
        2m b_p - D_pq b_q, computed on pair arrays."""
        p = self.matpc
        b_p, b_q = (b_even, b_odd) if p == EVEN else (b_odd, b_even)
        bp = self._to_pairs(b_p).astype(jnp.float32)
        dq = self.D_to_pairs(self._to_pairs(b_q), p,
                             out_dtype=jnp.float32)
        return ((2.0 * self.mass) * bp - dq).astype(self.store_dtype)

    def reconstruct_pairs(self, x_pp, b_even, b_odd):
        """Pair-form PC solution -> canonical complex (x_even, x_odd):
        x_q = (b_q - D_qp x_p) / 2m, the D applied on pair arrays."""
        p = self.matpc
        b_q = b_odd if p == EVEN else b_even
        dq = self.D_to_pairs(x_pp, 1 - p, out_dtype=jnp.float32)
        x_q_pp = (self._to_pairs(b_q).astype(jnp.float32) - dq) / (
            2.0 * self.mass)
        x_p = self._from_pairs(x_pp, b_q.dtype)
        x_q = self._from_pairs(x_q_pp, b_q.dtype)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)

    def verified_exit_pairs(self, b, x_pp):
        """The API's verified exit on the pair representation: the
        canonical full-lattice source ``b`` (T,Z,Y,X,1,3) and the
        pair-form PC solution -> (canonical full-lattice solution,
        |b - (2m + D) x| / |b|).  x_q = (b_q - D x_p) / 2m is the
        reconstruction; the residual is that of the RETURNED solution
        under the full M = 2m + D, parity by parity with this
        operator's own hop in f32 (on the q sites it is what rounding
        leaves of the reconstruction): no canonical (...,1,3) temporary
        beyond the two boundaries.  With a leading source axis on both,
        the batched hop and one residual per source.  Meant to be
        traced (solvers/program.py) on the f32 operator."""
        from ..fields.spinor import even_odd_join, even_odd_split
        f32, p, m2 = jnp.float32, self.matpc, 2.0 * self.mass
        batched = b.ndim == 7
        per_src = jax.vmap if batched else (lambda f: f)
        hop = ((lambda v, par: self._d_to_mrhs(v, par, f32)) if batched
               else (lambda v, par: self.D_to_pairs(v, par, out_dtype=f32)))
        halves = per_src(lambda v: even_odd_split(v, self.geom))(b)
        b_p, b_q = (per_src(self._to_pairs)(h).astype(f32)
                    for h in (halves if p == EVEN else halves[::-1]))
        x_p = x_pp.astype(f32)
        d_xp = hop(x_p, 1 - p)
        x_q = (b_q - d_xp) / m2
        r_p = b_p - (m2 * x_p + hop(x_q, p))
        r_q = b_q - (m2 * x_q + d_xp)
        norm2 = per_src(lambda v: jnp.sum(v * v))
        x_e, x_o = (per_src(lambda w: self._from_pairs(w, b.dtype))(v)
                    for v in ((x_p, x_q) if p == EVEN else (x_q, x_p)))
        join = per_src(lambda e, o: even_odd_join(e, o, self.geom))
        return (join(x_e, x_o),
                jnp.sqrt((norm2(r_p) + norm2(r_q))
                         / (norm2(b_p) + norm2(b_q))))

    def verified_exit_shifts_pairs(self, b_pp, X_pp, shifts, claimed,
                                   shift_r2, bound):
        """The verified exit of a multi-shift solve (A + sigma_i) x_i =
        b on A = 4m^2 - D_pq D_qp: the pair-form PC right-hand side
        ``b_pp``, the N pair-form solutions ``X_pp`` (N, 3, 2, T, Z,
        Y*Xh) and ``shifts`` (N,) -> (canonical parity solutions (N, T,
        Z, Y, Xh, 1, 3), the N true residuals |b - (A + sigma_i) x_i| /
        |b|, the loop's analytic residuals sqrt(``shift_r2``) / |b|,
        N flags: ``claimed`` by the loop AND a true residual <=
        ``bound``; a NaN fails).  The N solutions are a batch for the
        batched hop (``M_pairs_mrhs``): ONE application for all
        shifts, links read once.  The residual is the PC system's own,
        not amplified by 1 / 2m as the full system's is.  Meant to be
        traced (solvers/program.py) on the f32 operator."""
        f32 = jnp.float32
        b, X = b_pp.astype(f32), X_pp.astype(f32)
        sig = shifts.astype(f32).reshape((-1,) + (1,) * b.ndim)
        r = b[None] - (self.M_pairs_mrhs(X).astype(f32) + sig * X)
        b2 = jnp.sum(b * b)
        true_res = jnp.sqrt(jnp.sum(r * r, axis=tuple(range(1, r.ndim)))
                            / b2)
        return (self.solution_from_pairs_mrhs(X), true_res,
                jnp.sqrt(shift_r2.astype(f32) / b2),
                jnp.logical_and(claimed, true_res <= bound))

    # -- multi-RHS boundary helpers (the invert_multi_src_quda route) ---
    def prepare_pairs_mrhs(self, b_even_b, b_odd_b):
        """Batched canonical complex parity sources (N, T,Z,Y,Xh,1,3) ->
        batched pair-form PC rhs (N,3,2,T,Z,Y*Xh): 2m b_p - D_pq b_q
        with the batched hop, so the MRHS stencil serves source
        preparation too (links read once for all N)."""
        p = self.matpc
        b_p, b_q = ((b_even_b, b_odd_b) if p == EVEN
                    else (b_odd_b, b_even_b))
        to_pp = jax.vmap(self._to_pairs)
        bp = to_pp(b_p).astype(jnp.float32)
        dq = self._d_to_mrhs(to_pp(b_q), p, jnp.float32)
        return ((2.0 * self.mass) * bp - dq).astype(self.store_dtype)

    def solution_from_pairs_mrhs(self, x_b, dtype=jnp.complex64):
        return jax.vmap(lambda x: self._from_pairs(x, dtype))(x_b)

    def reconstruct_pairs_mrhs(self, x_b, b_even_b, b_odd_b):
        """Batched reconstruct_pairs: x_q = (b_q - D_qp x_p) / 2m with
        the MRHS hop.  Returns canonical complex (even, odd) batches."""
        p = self.matpc
        b_q = b_odd_b if p == EVEN else b_even_b
        to_pp = jax.vmap(self._to_pairs)
        dq = self._d_to_mrhs(x_b, 1 - p, jnp.float32)
        xq_b = (to_pp(b_q).astype(jnp.float32) - dq) / (2.0 * self.mass)
        x_p = self.solution_from_pairs_mrhs(x_b, b_q.dtype)
        x_q = self.solution_from_pairs_mrhs(xq_b, b_q.dtype)
        return (x_p, x_q) if p == EVEN else (x_q, x_p)


jax.tree_util.register_pytree_node_class(DiracStaggeredPCPairs)
