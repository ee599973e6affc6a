"""Precision storage forms (PERF.md round 16): model-level dispatch of
the bf16 full-tile fold / bz=Z admission, the fused in-kernel recon-12
forms (Wilson r12f + staggered Naik r12), and the int8 block-float
links — interpreter bit-match against the resident-full-links reference
through the SAME operator surface the solvers drive (``_d_to`` /
``D_to_pairs``), both parities, MRHS, and the sharded downgrade path.

Bitwise claims are exact by construction and asserted exactly:

* ``fold`` is a storage-layout permutation of the same f32/bf16
  elements — identical arithmetic, identical result bits;
* ``bzfull`` changes only the pallas grid blocking — same kernel body;
* ``r12f`` runs the identical reconstruction arithmetic as resident
  r12 storage (shared ``_recon12_wrap``) — r12 and r12f must agree
  BITWISE with each other, and to f32 roundoff with full links;
* ``int8`` is bounded-error vs full (block-float quantisation), and
  the pallas in-kernel decompression must bit-match the XLA
  decompress-at-setup route built from the same (q, scale) pair.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.gauge import GaugeField
from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.models.staggered import DiracStaggeredPC
from quda_tpu.models.wilson import DiracWilsonPC
from quda_tpu.utils import config as qconf

GEOM = LatticeGeometry((4, 4, 4, 4))


@pytest.fixture(autouse=True)
def _fresh_config():
    qconf.reset_cache()
    yield
    qconf.reset_cache()


def _wilson_dpk():
    gauge = GaugeField.random(jax.random.PRNGKey(21), GEOM).data.astype(
        jnp.complex64)
    return DiracWilsonPC(gauge, GEOM, kappa=0.11).packed()


def _staggered_dpc():
    fat = GaugeField.random(jax.random.PRNGKey(22), GEOM).data.astype(
        jnp.complex64)
    lng = GaugeField.random(jax.random.PRNGKey(23), GEOM).data.astype(
        jnp.complex64)
    return DiracStaggeredPC(fat, GEOM, mass=0.05, improved=True,
                            long_links=lng)


def _psi(shape, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _wilson_out(dpk, form, parity, store=jnp.float32, psi=None):
    sl = dpk.pairs(store, use_pallas=True, pallas_interpret=True,
                   precision_form=form)
    T, Z, Y, X = GEOM.lattice_shape
    p = psi if psi is not None else _psi((4, 3, 2, T, Z, Y * X // 2))
    return np.asarray(sl._d_to(p.astype(store), parity, jnp.float32)), sl


@pytest.mark.parametrize(
    "parity", [0, pytest.param(1, marks=pytest.mark.slow)])
@pytest.mark.parametrize(
    "form", [pytest.param("r12", marks=pytest.mark.slow),
             "r12f", "fold",
             pytest.param("bzfull", marks=pytest.mark.slow)])
def test_wilson_precision_forms_match_full(form, parity):
    dpk = _wilson_dpk()
    ref, _ = _wilson_out(dpk, "full", parity)
    out, sl = _wilson_out(dpk, form, parity)
    assert sl._precision_form == form
    if form in ("fold", "bzfull"):
        # layout/blocking changes only: identical arithmetic -> bits
        assert np.array_equal(out, ref)
    else:
        err = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
        assert err < 3e-5, (form, err)


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_wilson_r12f_bitmatches_resident_r12(parity):
    """r12f shares r12's stored rows and reconstruction arithmetic —
    only the backward-hop data movement differs (scatter reads of the
    unshifted opposite-parity links vs the resident pre-shifted copy).
    Same inputs, same arithmetic: the results must agree bitwise."""
    dpk = _wilson_dpk()
    a, _ = _wilson_out(dpk, "r12", parity)
    b, _ = _wilson_out(dpk, "r12f", parity)
    assert np.array_equal(a, b)


def test_wilson_bf16_fold_bitmatches_bf16_full():
    """The re/im-into-sublane fold at bf16 storage is the round-16
    full-tile form: same bf16 elements, permuted rows — the hop must
    reproduce the unfolded bf16 kernel bit for bit."""
    dpk = _wilson_dpk()
    ref, _ = _wilson_out(dpk, "full", 0, store=jnp.bfloat16)
    out, sl = _wilson_out(dpk, "fold", 0, store=jnp.bfloat16)
    assert sl._precision_form == "fold"
    assert np.array_equal(out, ref)


def test_wilson_int8_links_bounded_error_and_xla_bitmatch():
    """int8 block-float links: bounded quantisation error vs full
    links, and the in-kernel decompression bit-matches the XLA route
    decompressed at setup from the same (q, scale) arrays."""
    dpk = _wilson_dpk()
    ref, _ = _wilson_out(dpk, "full", 0)
    out, sl = _wilson_out(dpk, "int8", 0)
    assert sl._precision_form == "int8"
    assert sl.gauge_eo_pp is None and sl._gauge_q[0].dtype == jnp.int8
    err = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
    assert err < 2e-2, err
    T, Z, Y, X = GEOM.lattice_shape
    psi = _psi((4, 3, 2, T, Z, Y * X // 2))
    xla = dpk.pairs(jnp.float32, use_pallas=False,
                    precision_form="int8")
    assert xla._precision_form == "int8"
    x_out = np.asarray(xla._d_to(psi, 0, jnp.float32))
    p_out = np.asarray(sl._d_to(psi, 0, jnp.float32))
    assert np.max(np.abs(x_out - p_out)) < 1e-5


@pytest.mark.parametrize(
    "n", [pytest.param(1, marks=pytest.mark.slow), 3])
@pytest.mark.parametrize(
    "form", [pytest.param("r12f", marks=pytest.mark.slow), "fold",
             pytest.param("bzfull", marks=pytest.mark.slow),
             pytest.param("int8", marks=pytest.mark.slow)])
def test_wilson_precision_mrhs_matches_single(form, n):
    """The batched hop of every precision form equals the single-RHS
    hop per column (N=1 and N=3 — the MRHS kernels where they exist,
    the vmap fallback where they don't)."""
    dpk = _wilson_dpk()
    sl = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   precision_form=form)
    T, Z, Y, X = GEOM.lattice_shape
    pb = jnp.stack([_psi((4, 3, 2, T, Z, Y * X // 2), seed=5 + i)
                    for i in range(n)])
    ob = np.asarray(sl._d_to_mrhs(pb, 0, jnp.float32))
    for i in range(n):
        oi = np.asarray(sl._d_to(pb[i], 0, jnp.float32))
        assert np.array_equal(ob[i], oi), (form, n, i)


def test_staggered_wilson_only_forms_downgrade(monkeypatch):
    """The precision storage forms are the Wilson family's: a staggered
    operator built while QUDA_TPU_PRECISION_FORM asks for any of them
    keeps full R=3 links in the plain layout and says so ONCE per
    requested form; 'full' and the unset knob say nothing."""
    from quda_tpu.models import wilson as mw
    from quda_tpu.utils import logging as qlog
    dpc = _staggered_dpc()
    monkeypatch.setattr(mw, "_PRECISION_NOTICED", set())
    said = []
    monkeypatch.setattr(qlog, "printq",
                        lambda msg, *a, **kw: said.append(msg))
    shapes = {tuple(g.shape) for g in dpc.pairs(jnp.float32).long_eo_pp}
    for pform in ("", "full", "r12", "fold", "r12f", "bzfull", "int8",
                  "auto"):
        monkeypatch.setenv("QUDA_TPU_PRECISION_FORM", pform)
        qconf.reset_cache()
        for _ in range(2):
            op = dpc.pairs(jnp.float32, use_pallas=True,
                           pallas_interpret=True)
            assert {tuple(g.shape) for g in op.long_eo_pp} == shapes
            assert not hasattr(op, "_precision_form")
        noticed = (pform, "full") in mw._PRECISION_NOTICED
        assert noticed == (pform not in ("", "full")), pform
    assert len(said) == 6, said


def test_env_knob_resolution(monkeypatch):
    """QUDA_TPU_PRECISION_FORM drives construction when no explicit
    kwarg pins the form; the explicit kwarg wins over the env."""
    dpk = _wilson_dpk()
    monkeypatch.setenv("QUDA_TPU_PRECISION_FORM", "r12f")
    qconf.reset_cache()
    sl = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True)
    assert sl._precision_form == "r12f"
    sl2 = dpk.pairs(jnp.float32, use_pallas=True,
                    pallas_interpret=True, precision_form="fold")
    assert sl2._precision_form == "fold"


def test_legacy_reconstruct_env_still_resolves(monkeypatch):
    """QUDA_TPU_RECONSTRUCT=12 with no precision form remains the r12
    route (the pre-round-16 contract must not break)."""
    dpk = _wilson_dpk()
    monkeypatch.setenv("QUDA_TPU_RECONSTRUCT", "12")
    monkeypatch.delenv("QUDA_TPU_PRECISION_FORM", raising=False)
    qconf.reset_cache()
    sl = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True)
    assert sl._precision_form == "r12"
    assert sl.gauge_eo_pp[0].shape[1] == 2


def test_xla_path_serves_int8_full_only(monkeypatch):
    """The XLA stencil has no in-kernel decompression: pallas-only
    forms downgrade to full (with a notice); int8 decompresses at
    setup and keeps its label."""
    dpk = _wilson_dpk()
    for pform, served in (("fold", "full"), ("bzfull", "full"),
                          ("r12f", "full"), ("int8", "int8")):
        sl = dpk.pairs(jnp.float32, use_pallas=False,
                       precision_form=pform)
        assert sl._precision_form == served, pform


def test_bzfull_audits_single_buffer_admission(monkeypatch):
    """The bz=Z full-block admission must leave an audit trail: a block
    admitted single-buffered (double-buffering would bust the scoped
    16 MB window) is flagged in obs.memory's VMEM audit with the
    PADDED tile byte count."""
    from quda_tpu.obs import memory as omem
    from quda_tpu.ops import wilson_pallas_packed as wpp
    omem.reset()
    # budget small enough that double-buffering Z=8 f32 blocks fails
    # but one copy fits inside the scoped window
    monkeypatch.setenv("QUDA_TPU_PALLAS_VMEM_MB", "1.0")
    qconf.reset_cache()
    bz = wpp._pick_bz(8, 1024, jnp.float32, planes=288, min_bz=8,
                      allow_bzfull=True)
    assert bz == 8
    rows = {r["knob"]: r for r in omem.audit_vmem_budgets()}
    row = rows["QUDA_TPU_PALLAS_VMEM_MB"]
    assert row["last_bz"] == 8
    assert row["last_single_buffered"] is True
    assert row["last_block_bytes"] > 0


def test_pick_bz_dtype_sublane_padding():
    """_pick_bz charges PADDED tile bytes per dtype: sublane tiles are
    8 rows f32, 16 bf16, 32 int8 — a z-block of 2 rows costs a full
    tile's rows, and the bf16/int8 tiles must not be charged at the
    f32 pad."""
    from quda_tpu.obs import memory as omem
    from quda_tpu.ops import wilson_pallas_packed as wpp
    omem.reset()
    wpp._pick_bz(8, 128, jnp.float32, planes=1)
    f32_bytes = omem.audit_vmem_budgets()[0]["last_block_bytes"]
    wpp._pick_bz(8, 128, jnp.bfloat16, planes=1)
    bf16_bytes = omem.audit_vmem_budgets()[0]["last_block_bytes"]
    # same logical elements; bf16 halves the element size but pads to
    # 16 sublane rows — the PADDED charge is what VMEM really holds
    assert f32_bytes == 8 * 128 * 4
    assert bf16_bytes == 16 * 128 * 2


@pytest.mark.slow
def test_sharded_mesh_downgrades_precision_forms():
    """Mesh-sharded kernels speak full/r12 only: r12f and int8
    downgrade to r12, fold/bzfull to full — and the downgraded sharded
    operator still matches the unsharded reference (the round-8
    sharded-r12 path, exterior face fixes included)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from quda_tpu.parallel.mesh import make_lattice_mesh
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual mesh")
    geom = LatticeGeometry((4, 4, 8, 16))
    gauge = GaugeField.random(jax.random.PRNGKey(31), geom).data.astype(
        jnp.complex64)
    dpk = DiracWilsonPC(gauge, geom, kappa=0.12).packed()
    T, Z, Y, X = geom.lattice_shape
    psi = _psi((4, 3, 2, T, Z, Y * X // 2), seed=9)
    ref_op = dpk.pairs(jnp.float32, use_pallas=True,
                       pallas_interpret=True, precision_form="r12")
    ref = np.asarray(ref_op._d_to(psi, 0, jnp.float32))

    mesh = make_lattice_mesh(grid=(4, 2, 1, 1), n_src=1)
    sh = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   mesh=mesh, sharded_policy="xla_facefix",
                   precision_form="r12f")
    assert sh._precision_form == "r12"       # mesh downgrade
    assert sh.gauge_eo_pp[0].shape[1] == 2   # compressed storage kept
    x_s = jax.device_put(
        psi, NamedSharding(mesh, P(None, None, None, "t", "z", None)))
    out = np.asarray(jax.jit(lambda q: sh._d_to(q, 0, jnp.float32))(x_s))
    err = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
    assert err < 3e-5, err

    for pform, served in (("fold", "full"), ("int8", "r12")):
        op = dpk.pairs(jnp.float32, use_pallas=True,
                       pallas_interpret=True, mesh=mesh,
                       sharded_policy="xla_facefix",
                       precision_form=pform)
        assert op._precision_form == served, pform
