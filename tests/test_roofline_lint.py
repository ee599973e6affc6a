"""Roofline-model lint: every kernel-form label the package can emit
must have a KERNEL_MODELS entry in obs/roofline.py, so a new kernel
cannot ship unattributable (the round-9 methodology rule made static).

Two emission surfaces are linted:

* `interfaces/quda_api._solve_form` — swept over dummy operators
  covering the full attribute lattice (wilson/staggered x kernel
  form/generation x reconstruct-12 x mesh x pallas-off), so every label
  the function can construct is checked, including the f-string
  composites a static harvest would miss.  This half executes package
  code, so it stays here rather than in the engine;
* literal form strings recorded by the API routes and benches — since
  round 17 harvested by the unified static-analysis engine
  (quda_tpu/analysis, rule ``roofline-model``; record()/attribute()/
  model() first args, ``form`` assignments, and ``form=...`` keyword
  literals, filtered to the roofline namespace) over the shared
  single-parse index.
"""

import itertools

import numpy as np

from quda_tpu import analysis
from quda_tpu.interfaces.quda_api import _solve_form
from quda_tpu.obs import roofline as orf


def _mk(name, **attrs):
    o = type(name, (), {})()
    for k, v in attrs.items():
        setattr(o, k, v)
    return o


def _wilson_ops():
    # the resident link row extent (3 vs 2) drives the _r12 suffix
    g18 = (np.zeros((4, 3, 3, 2, 2, 2, 4), np.float32),)
    g12 = (np.zeros((4, 2, 3, 2, 2, 2, 4), np.float32),)
    for g, mesh in itertools.product((g18, g12), (None, object())):
        yield _mk("DiracWilsonPCPackedPairs", use_pallas=True,
                  gauge_eo_pp=g, _mesh=mesh)
    # precision storage forms (round 16): every (_precision_form,
    # store_dtype) pair the operator can serve single-chip must label
    # to a modeled row (int8 has gauge_eo_pp=None — the label path
    # must not trip on the missing link array)
    import jax.numpy as jnp
    for pform, store in itertools.product(
            ("full", "r12", "r12f", "fold", "bzfull", "int8"),
            (jnp.float32, jnp.bfloat16)):
        g = None if pform == "int8" else (
            g12[0:1] if pform in ("r12", "r12f") else g18)
        yield _mk("DiracWilsonPCPackedPairs", use_pallas=True,
                  gauge_eo_pp=g, _mesh=None,
                  _precision_form=pform, store_dtype=store)
    yield _mk("DiracWilsonPCPackedPairs", use_pallas=False)


def _staggered_ops():
    from quda_tpu.models.staggered import STAGGERED_FORMS
    for form, improved, mesh in itertools.product(
            STAGGERED_FORMS, (False, True), (None, object())):
        yield _mk("DiracStaggeredPCPairs", use_pallas=True,
                  _pallas_form=form,
                  long_eo_pp=(object(),) if improved else None,
                  _mesh=mesh)
    yield _mk("DiracStaggeredPCPairs", use_pallas=False,
              long_eo_pp=None)


def _zoo_ops():
    """Operator-zoo sweep (round 18): every class-name family x fused/
    staged x link storage x (for DWF) Ls — including the Ls values that
    must fall back to the flops-only 'dwf_pallas' row."""
    g18 = (np.zeros((4, 3, 3, 2, 2, 2, 4), np.float32),)
    g12 = (np.zeros((4, 2, 3, 2, 2, 2, 4), np.float32),)
    schur = ("DiracCloverPCPairs", "DiracTwistedMassPCPairs",
             "DiracTwistedCloverPCPairs", "DiracNdegTwistedMassPCPairs")
    for cls, form, g in itertools.product(schur, ("pallas", "xla", None),
                                          (g18, g12)):
        yield _mk(cls, _op_form=form, gauge_eo_pp=g)
    for cls, form, ls in itertools.product(
            ("DiracMobiusPCPairs", "DiracDomainWall5DPCPairs"),
            ("pallas", "xla"), (4, 6, 8, 12, 16)):
        yield _mk(cls, _op_form=form, gauge_eo_pp=g18, ls=ls)


def test_solve_form_labels_have_models():
    missing = {}
    for op in itertools.chain(_wilson_ops(), _staggered_ops(),
                              _zoo_ops()):
        form = _solve_form(op)
        if form not in orf.KERNEL_MODELS:
            missing.setdefault(form, type(op).__name__)
    assert not missing, (
        f"_solve_form can emit labels without a KERNEL_MODELS entry: "
        f"{missing} — add the traffic model to obs/roofline.py (or "
        "None bytes for an honest flops-only row)")


def test_recorded_form_literals_have_models():
    bad = [f for f in analysis.run_package().by_rule("roofline-model")
           if not f.suppressed]
    assert not bad, (
        "form literals recorded without a KERNEL_MODELS entry:\n  "
        + "\n  ".join(f.render() for f in bad))


def test_mg_coarse_bench_literal_is_harvested_and_modeled():
    """The round-15 coarse-kernel bench row attributes through
    form='mg_coarse_pallas' (a keyword literal): the engine's harvest
    must see it and the model must exist, so editing either side alone
    fails."""
    from quda_tpu.analysis.rules_legacy import (_in_roofline_namespace,
                                                _roofline_literals)
    mod = analysis.package_index().get("bench_suite.py")
    assert mod is not None
    lits = {s for s, _ in _roofline_literals(mod)
            if _in_roofline_namespace(s)}
    assert "mg_coarse_pallas" in lits
    assert "mg_coarse_pallas" in orf.KERNEL_MODELS


def test_mrhs_models_amortize_with_nrhs():
    """nrhs-dependent traffic models must be callable, decreasing in N,
    and anchored to the single-RHS two-pass totals at N=1 (the Wilson
    MRHS kernel's full-Z route reads psi twice where the single-RHS
    kernel reads it five times: 1152 - 3 x 96; the improved staggered
    batch serves the scatter pass, three psi reads where the gather
    pass reads five: 1512 - 2 x 48; the fused clover batch's full-Z
    route holds one slice a step beside its block planes and reads psi
    three times, 1728 - 2 x 96, the twisted-mass batch without blocks
    two slices like the Wilson batch, 1152 - 3 x 96: PR 47)."""
    for form, n1 in (("staggered_mrhs", 1416.0),
                     ("staggered_fat_mrhs", 720.0),
                     ("wilson_mrhs", 864.0),
                     ("clover_pallas_mrhs", 1536.0),
                     ("twisted_mass_pallas_mrhs", 864.0),
                     ("twisted_clover_pallas_mrhs", 1536.0)):
        bps = orf.KERNEL_MODELS[form]["bytes_per_site"]
        assert callable(bps)
        assert bps(1) == n1
        assert bps(8) < bps(4) < bps(1)


def test_zoo_fused_models_meet_round18_traffic_targets():
    """Acceptance pins for the operator-zoo fused forms: one VMEM pass
    means the fused diagonal adds only the resident block bytes over
    the v2 hop (nothing for the static twist), and the Ls-batched DWF
    hop amortizes the 576 B/site links to 576/Ls per plane (and reads
    each plane twice, 288 B with its write, on the MRHS kernel's full-Z
    route)."""
    hop = orf.KERNEL_MODELS["wilson_v2"]["bytes_per_site"]
    assert orf.KERNEL_MODELS["clover_pallas"]["bytes_per_site"] == hop + 576
    assert (orf.KERNEL_MODELS["twisted_mass_pallas"]["bytes_per_site"]
            == hop)
    assert (orf.KERNEL_MODELS["twisted_clover_pallas"]["bytes_per_site"]
            == orf.KERNEL_MODELS["clover_pallas"]["bytes_per_site"])
    for ls, name in ((4, "dwf_ls4_pallas"), (8, "dwf_ls8_pallas")):
        per_plane = orf.KERNEL_MODELS[name]["bytes_per_site"] / ls
        assert per_plane == 288.0 + 576.0 / ls
    # unregistered Ls and every staged composition stay flops-only or
    # fully generic — no traffic claim without a matching kernel
    for name in ("dwf_pallas", "dwf_xla", "clover_xla", "twisted_xla",
                 "twisted_clover_xla", "dwf_ls8_pallas_mrhs"):
        assert orf.KERNEL_MODELS[name]["bytes_per_site"] is None
