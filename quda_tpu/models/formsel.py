"""Shared pallas-vs-xla form selection for the operator zoo.

The clover, twisted-mass/twisted-clover, and DWF/Möbius pair operators
all face the same binary choice the wilson/staggered families resolve
with their form knobs: run the family through its fused pallas kernel
(ops/clover_pallas, ops/dwf_pallas) or through the XLA stencil
composition.  This module is that decision made once — QUDA's
tune.cpp:862 rule (policies are timed, never assumed) applied through
utils.tune, with warm-cache provenance and the round-6 notice rule (no
knob or auto decision takes effect silently).

Knobs (utils/config.py): QUDA_TPU_CLOVER_FORM / QUDA_TPU_TWISTED_FORM /
QUDA_TPU_DWF_FORM ∈ {'', auto, pallas, xla}.  Resolution precedence:
explicit ``form=`` kwarg > env knob > auto ('' is auto, except in a
family whose forms have been measured on the chip: ``MEASURED``).  'auto' races the two
compositions at operator construction and caches the winner per
(volume, family, dtype[, Ls]); with tuning disabled it resolves
statically to pallas with a notice — the expected chip winner (the
staggered auto-static precedent, models/staggered.py) — and in
interpret mode statically to xla, because a race would time the
interpreter, not the hardware, and the fused kernels' interpret
compiles dwarf the staged composition they replace (fused stays
opt-in off-chip via form='pallas').
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

KNOBS = {
    "clover": "QUDA_TPU_CLOVER_FORM",
    "twisted": "QUDA_TPU_TWISTED_FORM",
    "dwf": "QUDA_TPU_DWF_FORM",
}

FORMS = ("", "auto", "pallas", "xla")

# Families whose two forms have been read on the chip: with the knob
# unset they serve the winner WITHOUT a race.  Clover, one v5e, 24^4
# (PERF.md, PR 28): fused 953 / 515 us an M (f32 / bf16) against staged
# 1,308 / 796, and the race itself lowered eight kernels through Mosaic
# in every process, 90-130 s of load_clover_quda.  'auto' still races.
MEASURED = {"clover": "pallas"}

# The form a BATCH is served in (a leading source axis:
# _SchurPairOpBase._M_sign_pairs_mrhs) where the family's single-source
# form is the fused one: no race and no knob of its own (an operator
# pinned or resolved to 'xla' serves its batch staged too).  Clover, one
# v5e, 24^4, eight sources, f32, in a loop (PERF.md section 6, PR 46):
# fused 9.15 ms a MdagM and 10.91 ms a batched-CG iteration against
# staged 14.13 and 15.78 (the bare fullz hops + XLA's block products).
MEASURED_MRHS = {"clover": "pallas"}

_NOTICED: set = set()


def _notice(family: str, form: str, source: str):
    key = (family, form, source)
    if key in _NOTICED:
        return
    _NOTICED.add(key)
    from ..utils import logging as qlog
    qlog.printq(
        f"{family} operator: form {form} ({source}); pin via "
        f"{KNOBS[family]}", qlog.SUMMARIZE)


def _reset_notices():
    """Test seam: let a suite observe a fresh one-time notice."""
    _NOTICED.clear()


def fused_capable(op) -> Optional[str]:
    """None when ``op`` (a _PackedHopMixin pair operator) can host the
    fused epilogue kernels; otherwise the reason it cannot.  The fused
    forms are built on the full-tile gather kernel: folded/r12f/int8
    precision storage, multi-chip meshes, and plain XLA stencils all
    keep the staged composition."""
    if not getattr(op, "use_pallas", False):
        return "use_pallas=False (XLA stencil path)"
    if getattr(op, "_mesh", None) is not None:
        return "multi-chip mesh (sharded hop keeps staged diagonal)"
    pf = getattr(op, "_precision_form", None)
    if pf not in (None, "", "full", "r12"):
        return f"precision form {pf} (fused epilogue reads full-tile layouts)"
    return None


def resolve_form(family: str, requested: Optional[str], op,
                 race: Optional[Callable[[], str]] = None,
                 aux: str = "") -> str:
    """Resolve the family form to 'pallas' or 'xla'.

    ``requested`` is the explicit kwarg (None = not given); the env
    knob is read fresh underneath it.  ``race`` builds+times both
    compositions and returns the winner; it is only invoked on-chip
    with tuning enabled.  ``aux`` disambiguates the tunecache entry
    (dtype, Ls, ...).
    """
    from ..utils import config as qconf
    knob = KNOBS[family]
    req = requested
    if req is None:
        req = str(qconf.get(knob, fresh=True))
    if req not in FORMS:
        raise ValueError(
            f"{knob}={req!r}: expected one of {FORMS}")
    if not req and family not in MEASURED:
        req = "auto"

    blocker = fused_capable(op)
    if blocker is not None:
        if req == "pallas":
            _notice(family, "xla", f"requested pallas but {blocker}")
        return "xla"
    if req == "xla":
        _notice(family, "xla", "pinned")
        return "xla"
    if req == "pallas":
        _notice(family, "pallas", "pinned")
        return "pallas"

    # auto, or unset with a measured winner
    from ..utils import tune as qtune
    if getattr(op, "_pallas_interpret", False):
        # interpret mode: a race would time the interpreter, and the
        # fused kernels' interpret compiles are an order of magnitude
        # slower than the staged form they'd replace — fused stays
        # opt-in (form='pallas') off-chip
        _notice(family, "xla",
                "auto default (interpret mode: fused form is opt-in)")
        return "xla"
    if not req:
        _notice(family, MEASURED[family],
                "default: the chip's measured winner, no race "
                f"({knob}=auto races)")
        return MEASURED[family]
    if not qtune.tuning_enabled():
        _notice(family, "pallas",
                "auto default (tuning disabled: no chip race)")
        return "pallas"
    volume = tuple(op.dims)
    warm = qtune.cached_param(f"{family}_form", volume, aux=aux)
    won = race() if race is not None else "pallas"
    _notice(family, won,
            "warm cache (chip-keyed tunecache)" if warm is not None
            else f"raced+cached ({knob}=auto)")
    return won


def resolve_ndeg(requested: Optional[str]) -> str:
    """Non-degenerate doublet resolution: validation and notices only —
    the doublet has no fused form (the -b tau_1 flavor mixing couples
    the two flavor lanes, which is not a per-plane epilogue term), so
    every outcome is the staged composition."""
    from ..utils import config as qconf
    knob = KNOBS["twisted"]
    req = requested
    if req is None:
        req = str(qconf.get(knob, fresh=True))
    if req not in FORMS:
        raise ValueError(f"{knob}={req!r}: expected one of {FORMS}")
    if req == "pallas":
        _notice("twisted", "xla",
                "requested pallas but the ndeg doublet has no fused form")
    return "xla"


def race_schur(family: str, op, aux: str = "") -> str:
    """Race the fused-pallas vs staged-XLA Schur composition of a
    _SchurPairOpBase operator on a concrete dummy spinor.  Both
    candidates run op._M_sign_pairs with the form pinned EXPLICITLY, so
    the race never reads the attribute it is about to decide."""
    import jax
    import jax.numpy as jnp
    T, Z, _, _ = op.dims
    yxh = op.gauge_eo_pp[0].shape[-1]
    psi0 = jnp.zeros((4, 3, 2, T, Z, yxh), op.store_dtype)
    if getattr(op, "program_signature", None) is not None:
        # a pytree operator goes in as an ARGUMENT: closed over, its
        # links and blocks are constants of each candidate (1 GB
        # executables at 24^4 that no cache holds: PERF.md, PR 28)
        cands = {form: jax.jit(
            lambda o, v, form=form: o._M_sign_pairs(v, +1, form=form))
            for form in ("pallas", "xla")}
        return race_forms(family, op, cands, (op, psi0), aux=aux)
    cands = {
        "pallas": jax.jit(
            lambda v: op._M_sign_pairs(v, +1, form="pallas")),
        "xla": jax.jit(lambda v: op._M_sign_pairs(v, +1, form="xla")),
    }
    return race_forms(family, op, cands, (psi0,), aux=aux)


def race_ls_hop(family: str, op, aux: str = "") -> str:
    """Race the two forms of a Möbius pair operator on an
    (Ls, 4, 3, 2, T, Z, YXh) dummy: the Ls-batched 4d hop kernel with
    the s-block kernel beside it vs the vmap-over-s stencil with the
    f32 einsum.  ``_op_form`` decides the hop and the s-blocks alike
    (DiracMobiusPCPairs._apply_blocks), so the race times what it
    decides: the whole ``M_pairs`` (two hops, three block products) on
    a copy of ``op`` with the form pinned, never the attribute it is
    about to set."""
    import copy
    import functools
    import jax
    import jax.numpy as jnp
    T, Z, _, _ = op.dims
    yxh = op.gauge_eo_pp[0].shape[-1]
    psi0 = jnp.zeros((op.ls, 4, 3, 2, T, Z, yxh), op.store_dtype)
    m_pairs = jax.jit(type(op).M_pairs)
    cands = {}
    for form in ("pallas", "xla"):
        pinned = copy.copy(op)
        pinned._op_form = form
        # a pytree operator goes in as an ARGUMENT, as in race_schur:
        # closed over, its links are constants of each candidate (225
        # and 532 MB executables at 24^4 x 12: my chip run, PR 44)
        cands[form] = (functools.partial(m_pairs, pinned)
                       if pinned.program_signature is not None
                       else jax.jit(pinned.M_pairs))
    return race_forms(family, op, cands, (psi0,), aux=aux)


def race_forms(family: str, op, candidates: Dict[str, Callable],
               args: tuple, aux: str = "") -> str:
    """Time the {'pallas': f, 'xla': g} candidates on concrete operands
    via utils.tune and cache the winner.  Candidates are ordered
    pallas-first so tune's degradation rules (tuning disabled -> first
    candidate; all candidates fail -> first candidate, uncached) land
    on the kernel path the race exists to promote."""
    from ..utils import tune as qtune
    return qtune.tune(f"{family}_form", tuple(op.dims), candidates,
                      args, aux=aux)
