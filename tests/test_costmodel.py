"""Cost-model cross-check tests (obs/costmodel.py): the drift LINT over
every registered pallas traffic model, the deliberately-wrong fixtures
(a factor-2 slip in either direction must fail), the
Compiled.cost_analysis capture, and the record_execution ->
note_compile -> cost_drift.tsv session report."""

import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.obs import costmodel as ocost
from quda_tpu.obs import metrics as omet
from quda_tpu.obs import trace as otr
from quda_tpu.obs.roofline import KERNEL_MODELS
from quda_tpu.utils import config as qconf


@pytest.fixture(autouse=True)
def _isolation():
    ocost.reset()
    omet.stop(flush_files=False)
    otr.stop(flush_files=False)
    qconf.reset_cache()
    yield
    ocost.reset()
    omet.stop(flush_files=False)
    otr.stop(flush_files=False)
    qconf.reset_cache()


def test_xla_cost_reports_flops_and_bytes():
    cost = ocost.xla_cost(lambda x: jnp.einsum("ij,j->i", x, x[0]),
                          jnp.ones((32, 32), jnp.float32))
    assert cost["flops"] and cost["flops"] > 0
    assert cost["bytes"] and cost["bytes"] > 0


# operator-zoo forms are linted by the dedicated round-18 tests below —
# split per family so no single non-slow test pays more than one
# reference-stencil compile (the family refs cache per process, so the
# file total is one compile per family either way)
_ZOO_PREFIXES = ("clover", "twisted_mass", "twisted_clover", "dwf")


def _lint_rows(forms):
    assert forms
    rows = ocost.lint(forms)
    assert len(rows) == len(forms)
    for r in rows:
        assert r["checked"] and r["ok"], r
        # the flop models sit a few percent under XLA's HLO count
        assert 0.9 <= r["flops_ratio"] <= 1.3, r
        assert (ocost.BYTES_REREAD_MIN <= r["bytes_ratio"]
                <= ocost.BYTES_REREAD_MAX), r
    return rows


def test_drift_lint_passes_for_every_registered_pallas_form():
    """ISSUE acceptance: the cost-model drift lint passes for every
    registered pallas form — and covers ALL of them (a form with a
    traffic model but no footprint spec fails, so a new kernel cannot
    ship unchecked).  The operator-zoo rows run in the per-family
    tests below; together the sweeps cover the full registry."""
    zoo = [f for f in ocost.checkable_forms()
           if f.startswith(_ZOO_PREFIXES)]
    forms = [f for f in ocost.checkable_forms() if f not in zoo]
    _lint_rows(forms)
    assert set(forms) | set(zoo) == set(ocost.checkable_forms())


@pytest.mark.slow
def test_zoo_clover_drift_rows_pass():
    """Clover + twisted-clover rows (the twisted-clover footprints alias
    the clover specs, so this is one reference compile).  The zoo drift
    tests are slow-tier: each family's reference-stencil compile costs
    12-19s, and tier-1 runs the whole suite under a hard wall-clock
    budget — the non-zoo sweep above stays non-slow and the registry
    -completeness assert there keeps new forms from shipping unlinted."""
    _lint_rows([f for f in ocost.checkable_forms()
                if f.startswith(("clover", "twisted_clover"))])


@pytest.mark.slow
def test_zoo_twisted_mass_drift_rows_pass():
    _lint_rows([f for f in ocost.checkable_forms()
                if f.startswith("twisted_mass")])


@pytest.mark.slow
def test_zoo_dwf_ls4_drift_row_passes():
    _lint_rows(["dwf_ls4_pallas"])


@pytest.mark.slow
def test_zoo_dwf_ls8_drift_row_passes():
    _lint_rows(["dwf_ls8_pallas"])


def test_checkable_forms_are_the_pallas_models():
    forms = set(ocost.checkable_forms())
    assert "wilson_v2" in forms and "staggered_fat_naik_v3" in forms
    # honest flops-only rows are exempt by design
    assert "wilson_xla" not in forms and "generic" not in forms


def test_mg_coarse_form_is_checkable():
    """The fused coarse-stencil kernel's row (round 15) is covered by
    the drift lint like every other pallas traffic model."""
    assert "mg_coarse_pallas" in ocost.checkable_forms()
    row = ocost.drift_row("mg_coarse_pallas")
    assert row["checked"] and row["ok"], row


def test_mg_coarse_wrong_flops_model_fails(monkeypatch):
    """A KERNEL_MODELS edit that disagrees with XLA's flop count for
    the coarse reference contraction must fail tier-1."""
    wrong = dict(KERNEL_MODELS["mg_coarse_pallas"],
                 flops_per_site=3 * 4608)
    monkeypatch.setitem(KERNEL_MODELS, "mg_coarse_pallas", wrong)
    ocost.reset()
    row = ocost.drift_row("mg_coarse_pallas")
    assert not row["ok"] and any("flops drift" in r
                                 for r in row["reasons"])
    with pytest.raises(AssertionError, match="flops drift"):
        ocost.lint(["mg_coarse_pallas"])


def test_mg_coarse_inflated_bytes_model_fails(monkeypatch):
    """Claiming 4x the operand-footprint floor (or less than one read
    of the links) fails the bytes cross-check."""
    for bad in (4 * 9856, 2000):
        wrong = dict(KERNEL_MODELS["mg_coarse_pallas"],
                     bytes_per_site=bad)
        monkeypatch.setitem(KERNEL_MODELS, "mg_coarse_pallas", wrong)
        ocost.reset()
        row = ocost.drift_row("mg_coarse_pallas")
        assert not row["ok"] and any("bytes drift" in r
                                     for r in row["reasons"]), (bad, row)


def test_deliberately_inflated_bytes_model_fails(monkeypatch):
    """A factor-2 bytes inflation (the classic copied-table slip) must
    fail the lint."""
    wrong = dict(KERNEL_MODELS["wilson_v2"], bytes_per_site=2 * 1152)
    monkeypatch.setitem(KERNEL_MODELS, "wilson_v2", wrong)
    ocost.reset()          # drop the cached passing verdict
    row = ocost.drift_row("wilson_v2")
    assert not row["ok"]
    assert any("bytes drift" in r for r in row["reasons"])
    with pytest.raises(AssertionError, match="bytes drift"):
        ocost.lint(["wilson_v2"])


def test_below_footprint_bytes_model_fails(monkeypatch):
    """A model claiming LESS traffic than the operand footprint (data
    cannot be moved less than once) must fail."""
    wrong = dict(KERNEL_MODELS["wilson_v2"], bytes_per_site=600)
    monkeypatch.setitem(KERNEL_MODELS, "wilson_v2", wrong)
    ocost.reset()
    row = ocost.drift_row("wilson_v2")
    assert not row["ok"] and any("bytes drift" in r
                                 for r in row["reasons"])


def test_wrong_flops_model_fails(monkeypatch):
    wrong = dict(KERNEL_MODELS["staggered_fat"], flops_per_site=2500)
    monkeypatch.setitem(KERNEL_MODELS, "staggered_fat", wrong)
    ocost.reset()
    row = ocost.drift_row("staggered_fat")
    assert not row["ok"] and any("flops drift" in r
                                 for r in row["reasons"])


def test_zoo_forms_are_checkable():
    """Round 18: every operator-zoo traffic row is covered by the drift
    lint — including the r12 and MRHS variants and the twisted-clover
    rows that alias the clover footprint spec."""
    forms = set(ocost.checkable_forms())
    for f in ("clover_pallas", "clover_pallas_r12", "clover_pallas_mrhs",
              "twisted_mass_pallas", "twisted_mass_pallas_r12",
              "twisted_mass_pallas_mrhs", "twisted_clover_pallas",
              "twisted_clover_pallas_r12", "twisted_clover_pallas_mrhs",
              "dwf_ls4_pallas", "dwf_ls8_pallas"):
        assert f in forms, f
    # flops-only rows stay exempt by design
    for f in ("clover_xla", "twisted_xla", "twisted_clover_xla",
              "dwf_xla", "dwf_pallas", "dwf_ls8_pallas_mrhs"):
        assert f not in forms, f


@pytest.mark.slow
def test_zoo_wrong_flops_model_fails(monkeypatch):
    """A factor-3 flop slip in any zoo row must fail: the reference
    stencils (clover blocks on the hop, the twisted inverse rotation,
    the vmap-over-s 4d hop) pin each family's arithmetic.  (Factor 3,
    not 2: FLOPS_RTOL=0.5 tolerates the XLA count sitting either side
    of the model, so a doubled model still lands on the band edge.)"""
    for form in ("clover_pallas", "twisted_mass_pallas",
                 "twisted_clover_pallas", "dwf_ls4_pallas"):
        orig = KERNEL_MODELS[form]
        wrong = dict(orig, flops_per_site=3 * orig["flops_per_site"])
        monkeypatch.setitem(KERNEL_MODELS, form, wrong)
        ocost.reset()
        row = ocost.drift_row(form)
        assert not row["ok"] and any("flops drift" in r
                                     for r in row["reasons"]), (form, row)
        with pytest.raises(AssertionError, match="flops drift"):
            ocost.lint([form])
        monkeypatch.setitem(KERNEL_MODELS, form, orig)


@pytest.mark.slow
def test_zoo_wrong_bytes_model_fails(monkeypatch):
    """Bytes honesty for the zoo rows: claiming twice the modeled
    traffic (or less than one read of the operand footprint) fails."""
    for form, floor in (("clover_pallas", 1344), ("twisted_mass_pallas",
                                                  768),
                        ("twisted_clover_pallas", 1344),
                        ("dwf_ls8_pallas", 2112)):
        for bad in (2 * KERNEL_MODELS[form]["bytes_per_site"],
                    floor - 100):
            wrong = dict(KERNEL_MODELS[form], bytes_per_site=bad)
            monkeypatch.setitem(KERNEL_MODELS, form, wrong)
            ocost.reset()
            row = ocost.drift_row(form)
            assert not row["ok"] and any(
                "bytes drift" in r for r in row["reasons"]), (form, bad)


def test_agreeing_model_fixture_and_drift_event(tmp_path):
    """An agreeing model passes and mirrors a cost_drift trace event."""
    otr.start(str(tmp_path))
    ocost.reset()
    row = ocost.drift_row("wilson_v2")
    assert row["ok"]
    paths = otr.stop()
    import json
    lines = [json.loads(ln) for ln in open(paths["jsonl"])]
    evs = [ln for ln in lines if ln.get("name") == "cost_drift"]
    assert evs and evs[0]["form"] == "wilson_v2" and evs[0]["ok"]


def test_record_execution_notes_compiles_once(tmp_path):
    """The Compiled-capture hook: metrics.record_execution notes each
    DISTINCT key's first execution for the session drift report."""
    omet.start(str(tmp_path))
    omet.record_execution("invert_quda", "wilson_v2", (4, 4, 4, 4),
                          "single", "cg", 1.25)
    omet.record_execution("invert_quda", "wilson_v2", (4, 4, 4, 4),
                          "single", "cg", 0.01)    # warm: not re-noted
    omet.record_execution("invert_quda", "gcr_mg", (4, 4, 4, 4),
                          "single", "gcr-mg", 3.0)
    noted = ocost.noted_compiles()
    assert [n["form"] for n in noted] == ["wilson_v2", "gcr_mg"]
    assert noted[0]["seconds"] == 1.25


def test_save_report_joins_models_and_verdicts(tmp_path):
    ocost.note_compile("invert_quda", "wilson_v2", (4, 4, 4, 4),
                       "single", "cg", 2.0)
    ocost.note_compile("invert_quda", "gcr_mg", (4, 4, 4, 4),
                       "single", "gcr-mg", 5.0)
    ocost.drift_row("wilson_v2")       # probe so the verdict is cached
    out = ocost.save_report(path=str(tmp_path))
    body = open(out).read()
    lines = body.strip().splitlines()
    assert lines[0].startswith("api\tform\tsolver")
    w = next(ln for ln in lines if "\twilson_v2\t" in ln)
    assert "\tTrue\tTrue\t" in w          # checked + ok
    assert "1152" in w                    # analytic bytes joined
    g = next(ln for ln in lines if "\tgcr_mg\t" in ln)
    assert g                              # unmodeled forms still listed


def test_save_report_none_without_compiles(tmp_path):
    assert ocost.save_report(path=str(tmp_path)) is None
