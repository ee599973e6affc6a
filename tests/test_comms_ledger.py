"""ICI comms-ledger tests (obs/comms.py): zero-overhead off path, the
ppermute seam recording real traced slab bytes, the analytic halo-model
arithmetic, per-solve attribution, and the acceptance drill — a sharded
Wilson CG solve on a 2-device virtual mesh whose ledger rows equal the
analytic halo model for the active policy."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.obs import comms as ocomms
from quda_tpu.obs import metrics as omet
from quda_tpu.obs import roofline as orf
from quda_tpu.obs import trace as otr
from quda_tpu.parallel.mesh import make_lattice_mesh
from quda_tpu.utils import config as qconf

@pytest.fixture(autouse=True)
def _comms_isolation():
    # full reset (not stop): exchange entries are process-lifetime by
    # design — tests need clean-slate isolation
    ocomms.reset()
    otr.stop(flush_files=False)
    omet.stop(flush_files=False)
    orf.reset()
    qconf.reset_cache()
    yield
    ocomms.reset()
    otr.stop(flush_files=False)
    omet.stop(flush_files=False)
    orf.reset()
    qconf.reset_cache()


def _boom(*a, **kw):
    raise AssertionError("comms-ledger code ran with the ledger off")


def _two_device_mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 virtual devices")
    return make_lattice_mesh(grid=(1, 2, 1, 1), n_src=1,
                             devices=jax.devices()[:2])


def _sharded_shift_fn(mesh, shape):
    """A compiled shard_map shift exercising the _permute_slice seam
    (the one lax.ppermute home) without any pallas compile."""
    from jax.sharding import PartitionSpec as P

    from quda_tpu.parallel.halo import make_sharded_shift
    shift = make_sharded_shift(mesh)
    spec = P("t", "z", "y", "x")
    return jax.jit(jax.shard_map(
        lambda a: shift(a, 2, +1), mesh=mesh, in_specs=(spec,),
        out_specs=spec, check_vma=False))


def test_off_is_noop(monkeypatch):
    """Off means off: scope() hands back the module singleton, the
    recording entry points return after one global load, and the ledger
    internals are never entered (raising stub)."""
    assert not ocomms.enabled()
    monkeypatch.delenv("QUDA_TPU_TRACE", raising=False)
    monkeypatch.delenv("QUDA_TPU_METRICS", raising=False)
    qconf.reset_cache()
    assert ocomms.maybe_start() is None     # rides the existing knobs
    assert ocomms.scope("x") is ocomms._NOOP_SCOPE
    assert ocomms.scope("y", policy="p") is ocomms._NOOP_SCOPE
    monkeypatch.setattr(ocomms._Ledger, "record", _boom)
    ocomms.record_exchange(nbytes=4, axis="z")
    ocomms.record_replication(np.zeros(8, np.float32), axis="src",
                              n_devices=4)
    assert ocomms.ledger() == [] and ocomms.solve_rows() == []
    assert ocomms.attribute_solve("f", 10, 2.0, 1.0) is None


def test_compiled_exchange_never_touches_ledger_when_off(monkeypatch):
    """The raising-stub pin for the seams themselves: with the ledger
    off a COMPILED shard_map exchange (ppermute through
    halo._permute_slice) traces and runs without entering the ledger."""
    monkeypatch.setattr(ocomms._Ledger, "record", _boom)
    mesh = _two_device_mesh()
    arr = jnp.arange(4 * 4 * 4 * 4, dtype=jnp.float32).reshape(4, 4, 4, 4)
    out = _sharded_shift_fn(mesh, arr.shape)(arr)
    np.testing.assert_allclose(np.asarray(out),
                               np.roll(np.asarray(arr), -1, axis=1))


def test_ppermute_seam_records_traced_slab_bytes():
    """The _permute_slice seam records the face slab's bytes from the
    TRACED shapes: a (T,Z,Y,X)=(4,4,4,4) f32 shift over a 2-way z ring
    sends one (4,1,4,4) face = 256 B per device."""
    ocomms.start()
    mesh = _two_device_mesh()
    arr = jnp.ones((4, 4, 4, 4), jnp.float32)
    _sharded_shift_fn(mesh, arr.shape)(arr)
    rows = ocomms.ledger()
    assert len(rows) == 1
    r = rows[0]
    assert r["bytes"] == 4 * 1 * 4 * 4 * 4
    assert r["axis"] == "z" and r["policy"] == "ppermute"
    assert r["site"] == "unscoped" and r["dtype"] == "float32"


def test_scope_labels_and_dedupe():
    ocomms.start()
    with ocomms.scope("wilson_eo_sharded_v2:p0", policy="xla_facefix",
                      mesh_axes=(1, 2)):
        for _ in range(3):     # identical re-traces dedupe into count
            ocomms.record_exchange(nbytes=128, axis="z",
                                   direction="down")
        ocomms.record_exchange(nbytes=128, axis="z", direction="up")
    rows = ocomms.ledger()
    assert len(rows) == 2
    assert all(r["site"] == "wilson_eo_sharded_v2:p0"
               and r["policy"] == "xla_facefix"
               and r["mesh"] == "1x2" for r in rows)
    down = next(r for r in rows if r["direction"] == "down")
    assert down["traces"] == 3 and down["bytes"] == 128


def test_halo_model_arithmetic():
    """wilson_eo_halo_model from first principles: (T,Z,Y,X)=(16,8,4,4)
    on a (1,2) mesh — one partitioned axis (z), two 4x3x2xT_locxYXh f32
    slabs per device per invocation."""
    m = ocomms.wilson_eo_halo_model((16, 8, 4, 4), (1, 2))
    yxh = 4 * 4 // 2
    assert m["axes"] == {"z": 2 * 4 * 3 * 2 * 16 * yxh * 4}
    assert m["per_device"] == m["axes"]["z"]
    assert m["total"] == 2 * m["per_device"]
    # both axes partitioned
    m2 = ocomms.wilson_eo_halo_model((16, 8, 4, 4), (2, 2))
    assert set(m2["axes"]) == {"t", "z"}
    assert m2["total"] == 4 * m2["per_device"]


def test_per_invocation_and_attribute_solve():
    """Per-invocation bytes = max per-site group (parity symmetry);
    attribution = per-invocation x applies x dslash_per_apply x
    devices; replication rows are excluded from the invocation model."""
    ocomms.start()
    for p in (0, 1):
        with ocomms.scope(f"wilson_eo_sharded_v2:p{p}",
                          policy="xla_facefix", mesh_axes=(2,)):
            ocomms.record_exchange(nbytes=1000, axis="z",
                                   direction="down")
            ocomms.record_exchange(nbytes=1000, axis="z",
                                   direction="up")
    ocomms.record_replication(np.zeros(250, np.float32), axis="src",
                              n_devices=2)   # 1000 B replicated, excluded
    assert ocomms.per_invocation_bytes() == 2000
    row = ocomms.attribute_solve("wilson_sharded_v2", applies=10,
                                 dslash_per_apply=2.0, seconds=0.5,
                                 label="unit")
    assert row["ici_bytes"] == 2000 * 10 * 2 * 2
    assert row["devices"] == 2
    assert row["gbps"] == round(row["ici_bytes"] / 0.5 / 1e9, 3)
    assert row["form"] == "ici:wilson_sharded_v2"
    assert ocomms.solve_rows() == [row]


def test_policy_race_rows_do_not_double_count():
    """A QUDA_TPU_SHARDED_POLICY=auto race traces BOTH policies under
    one site; the candidates move the same slabs, so per-invocation
    bytes must be ONE policy group's total, not the sum."""
    ocomms.start()
    for pol in ("xla_facefix", "fused_halo"):
        with ocomms.scope("wilson_eo_sharded_v2:p0", policy=pol,
                          mesh_axes=(1, 2)):
            ocomms.record_exchange(nbytes=1000, axis="z",
                                   direction="down")
            ocomms.record_exchange(nbytes=1000, axis="z",
                                   direction="up")
    assert ocomms.per_invocation_bytes() == 2000


def test_site_prefix_confines_attribution_to_one_family():
    """A staggered stencil traced earlier in the session must not set
    the per-invocation bytes of a Wilson solve's attribution."""
    ocomms.start()
    with ocomms.scope("staggered_eo_sharded_v2:p0",
                      policy="xla_facefix", mesh_axes=(1, 2)):
        ocomms.record_exchange(nbytes=9000, axis="z", direction="down")
    with ocomms.scope("wilson_eo_sharded_v2:p0", policy="xla_facefix",
                      mesh_axes=(1, 2)):
        ocomms.record_exchange(nbytes=1000, axis="z", direction="down")
    assert ocomms.per_invocation_bytes(site_prefix="wilson") == 1000
    row = ocomms.attribute_solve("wilson_sharded_v2", 1, 1.0, 1.0,
                                 site_prefix="wilson")
    assert row["bytes_per_invocation_per_device"] == 1000


def test_scope_mesh_wins_over_seam_single_ring():
    """_permute_slice only sees its own ring; the scope's full
    (n_t, n_z) must win so the device count is the mesh product."""
    ocomms.start()
    with ocomms.scope("wilson_eo_sharded_v2:p0", policy="xla_facefix",
                      mesh_axes=(2, 2)):
        # the seam passes its single ring, as _permute_slice does
        ocomms.record_exchange(nbytes=500, axis="z", direction="down",
                               mesh_axes=(2,))
    rows = ocomms.ledger()
    assert rows[0]["mesh"] == "2x2"
    row = ocomms.attribute_solve("wilson_sharded_v2", 1, 1.0, 1.0)
    assert row["devices"] == 4


def test_mixed_dtype_stencils_do_not_double_count():
    """A mixed-precision solve traces an f32 and a bf16 stencil under
    one site+policy; each invocation runs ONE of them — max, not sum."""
    ocomms.start()
    with ocomms.scope("wilson_eo_sharded_v2:p0", policy="xla_facefix",
                      mesh_axes=(1, 2)):
        ocomms.record_exchange(nbytes=1000, axis="z", direction="down",
                               dtype="float32")
        ocomms.record_exchange(nbytes=500, axis="z", direction="down",
                               dtype="bfloat16")
    assert ocomms.per_invocation_bytes() == 1000


def test_attribution_never_splits_bytes_across_policies(tmp_path):
    """Race-tied policies: the total is counted ONCE under the combined
    label, never split between a policy the solve may not have run."""
    omet.start(str(tmp_path))
    ocomms.start()
    for pol in ("xla_facefix", "fused_halo"):
        with ocomms.scope("wilson_eo_sharded_v2:p0", policy=pol,
                          mesh_axes=(1, 2)):
            ocomms.record_exchange(nbytes=1000, axis="z",
                                   direction="down")
    row = ocomms.attribute_solve("wilson_sharded_v2", applies=10,
                                 dslash_per_apply=1.0, seconds=1.0)
    assert row["ici_bytes"] == 1000 * 10 * 2
    assert row["policy"] == "fused_halo+xla_facefix"
    snap = omet.snapshot()
    counts = {labels: v for (name, labels), v in
              snap["counters"].items() if name == "ici_bytes_total"}
    assert list(counts.values()) == [float(row["ici_bytes"])]


def test_await_phase_blocks_arrays_and_objects():
    """The MG phase sync must find device arrays BOTH as bare
    array/pytree products (a jax Array has an empty __dict__) and
    inside plain objects (Transfer/CoarseOperator)."""
    from quda_tpu.mg.mg import MG

    class FakeArray:
        def __init__(self):
            self.blocked = 0

        def block_until_ready(self):
            self.blocked += 1
            return self

    bare = FakeArray()
    MG._await_phase(bare)
    assert bare.blocked == 1

    class Product:
        def __init__(self):
            self.v = FakeArray()
            self.y = {"a": FakeArray()}

    prod = Product()
    MG._await_phase(prod)
    assert prod.v.blocked == 1 and prod.y["a"].blocked == 1

    real = jnp.ones((3,))
    assert MG._await_phase(real) is real     # finds the array directly


def test_entries_survive_stop_like_the_jit_cache():
    """Exchange entries are process-lifetime: a second init/end session
    reuses compiled executables that never re-trace, so stop() must
    keep the entries (reset() is the test-only full wipe)."""
    ocomms.start()
    with ocomms.scope("wilson_eo_sharded_v2:p0", policy="xla_facefix",
                      mesh_axes=(1, 2)):
        ocomms.record_exchange(nbytes=777, axis="z", direction="down")
    ocomms.stop()                     # end_quda
    assert not ocomms.enabled()
    ocomms.start()                    # next session, warm jit cache
    assert ocomms.per_invocation_bytes() == 777
    row = ocomms.attribute_solve("wilson_sharded_v2", 1, 1.0, 1.0)
    assert row is not None and row["ici_bytes"] == 777 * 2
    ocomms.reset()
    assert ocomms.ledger() == []


def test_pct_nominal_is_per_device_rate():
    """Devices send concurrently: the saturation percentage compares
    the PER-DEVICE rate against the per-chip nominal link — a 4-device
    mesh at per-device rate r must report r/nominal, not 4r/nominal."""
    ocomms.start()
    with ocomms.scope("wilson_eo_sharded_v2:p0", policy="xla_facefix",
                      mesh_axes=(2, 2)):
        ocomms.record_exchange(nbytes=10 ** 9, axis="z",
                               direction="down")
    row = ocomms.attribute_solve("wilson_sharded_v2", applies=1,
                                 dslash_per_apply=1.0, seconds=1.0)
    assert row["devices"] == 4
    assert row["gbps"] == pytest.approx(4.0)          # mesh aggregate
    assert row["gbps_per_device"] == pytest.approx(1.0)
    assert row["pct_nominal_ici"] == pytest.approx(
        100.0 / ocomms.ICI_NOMINAL_GBPS, rel=1e-6)


def test_retrace_at_new_shape_replaces_not_sums():
    """The entries are process-lifetime (jit-cache model): the same
    stencil site re-traced at a LARGER lattice must replace its slot
    (latest wins), not sum shapes a single invocation never moved —
    while genuinely distinct slots (other axes) still sum."""
    ocomms.start()
    with ocomms.scope("wilson_eo_sharded_v2:p0", policy="xla_facefix",
                      mesh_axes=(2, 2)):
        ocomms.record_exchange(nbytes=1000, axis="z", direction="down")
        ocomms.record_exchange(nbytes=2000, axis="t", direction="down")
        # the worker now serves a larger lattice: same site/slot,
        # bigger slab
        ocomms.record_exchange(nbytes=4000, axis="z", direction="down")
    assert ocomms.per_invocation_bytes() == 4000 + 2000


def test_mg_phase_records_even_when_phase_raises(tmp_path):
    """A raising phase (the pallas-compile failure robust/escalate
    retries) must still land in the breakdown and the counter — the
    trace span records its duration unconditionally, and the three
    surfaces must not disagree on the error paths."""
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.mg.mg import MG

    omet.start(str(tmp_path))
    mg = MG.__new__(MG)
    mg.setup_breakdown = []
    mg.geom = LatticeGeometry((4, 4, 4, 4))
    with pytest.raises(RuntimeError, match="boom"):
        with mg._phase(0, "coarse_probe"):
            raise RuntimeError("boom")
    assert [(r["level"], r["phase"]) for r in mg.setup_breakdown] == \
        [(0, "coarse_probe")]
    snap = omet.snapshot()
    assert any(n == "mg_setup_phase_seconds_total"
               for (n, _) in snap["counters"])


def test_replication_row_bytes():
    ocomms.start()
    g = np.zeros((4, 3, 3), np.complex64)      # 288 B
    ocomms.record_replication(g, axis="src", n_devices=4, what="gauge")
    rows = ocomms.ledger()
    assert len(rows) == 1
    assert rows[0]["bytes"] == g.nbytes * 3
    assert rows[0]["direction"] == "replicate"
    assert rows[0]["site"] == "split_grid:gauge"


def test_roofline_tsv_carries_ici_rows(tmp_path):
    """attribute_solve rows ride roofline.tsv next to the HBM rows."""
    ocomms.start()
    with ocomms.scope("s:p0", policy="xla_facefix", mesh_axes=(2,)):
        ocomms.record_exchange(nbytes=512, axis="z")
    ocomms.attribute_solve("wilson_sharded_v2", 4, 2.0, 0.25,
                           label="tsv_check")
    orf.record("wilson_v2", 128, 10, 0.01, label="hbm_row")
    out = orf.save(path=str(tmp_path))
    body = open(out).read()
    assert "hbm_row" in body
    assert "ici:wilson_sharded_v2" in body
    assert "tsv_check|xla_facefix|axes=z|devices=2" in body
    # an ICI-only session still writes the tsv
    orf.reset()
    out2 = orf.save(fname="roofline2.tsv", path=str(tmp_path))
    assert out2 and "ici:wilson_sharded_v2" in open(out2).read()


def _sharded_wilson_solve(policy: str):
    """The acceptance drill body: 2-device virtual-mesh sharded Wilson
    CG through the pairs operator, returning (iters, dims, mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from quda_tpu.fields.gauge import GaugeField
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.fields.spinor import ColorSpinorField, even_odd_split
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.ops import wilson_packed as wpk
    from quda_tpu.solvers.cg import cg
    mesh = _two_device_mesh()
    geom = LatticeGeometry((4, 4, 4, 8))    # ctor (x,y,z,t)
    T, Z, Y, X = geom.lattice_shape
    gauge = GaugeField.random(jax.random.PRNGKey(5), geom).data.astype(
        jnp.complex64)
    psi = ColorSpinorField.gaussian(jax.random.PRNGKey(6), geom
                                    ).data.astype(jnp.complex64)
    pe, _ = even_odd_split(psi, geom)
    dpk = DiracWilsonPC(gauge, geom, kappa=0.1).packed()
    op = dpk.pairs(jnp.float32, use_pallas=True, pallas_interpret=True,
                   mesh=mesh, sharded_policy=policy)
    b = wpk.to_packed_pairs(wpk.pack_spinor(pe), jnp.float32)
    b_s = jax.device_put(b, NamedSharding(
        mesh, P(None, None, None, "t", "z", None)))
    res = jax.jit(lambda v: cg(op.MdagM_pairs, v, tol=1e-5,
                               maxiter=20))(b_s)
    return int(res.iters), (T, Z, Y, X), mesh


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["xla_facefix", "fused_halo"])
def test_acceptance_sharded_solve_ledger_matches_model(policy,
                                                      monkeypatch):
    """ISSUE acceptance: with QUDA_TPU_TRACE=1 + QUDA_TPU_METRICS=1 a
    sharded Wilson CG solve's ledger rows total exactly the analytic
    halo model per device per dslash invocation, for the active
    policy (the ledger rides the existing knobs — maybe_start)."""
    monkeypatch.setenv("QUDA_TPU_TRACE", "1")
    monkeypatch.setenv("QUDA_TPU_METRICS", "1")
    qconf.reset_cache()
    assert ocomms.maybe_start() is not None
    iters, dims, mesh = _sharded_wilson_solve(policy)
    assert iters > 2
    model = ocomms.wilson_eo_halo_model(dims, (1, 2))
    rows = ocomms.ledger()
    assert rows, "sharded solve recorded no ledger rows"
    per_parity = {}
    for r in rows:
        assert r["policy"] == policy
        assert r["axis"] == "z"
        per_parity[r["site"]] = per_parity.get(r["site"], 0) + r["bytes"]
    assert set(per_parity) == {"wilson_eo_sharded_v2:p0",
                               "wilson_eo_sharded_v2:p1"}
    for site, total in per_parity.items():
        assert total == model["per_device"], (site, total, model)
    assert ocomms.per_invocation_bytes() == model["per_device"]
    # per-solve attribution: applies = iters CG iterations x MdagM (2 M)
    # x 2 dslash per PC M
    row = ocomms.attribute_solve("wilson_sharded_v2", iters * 2, 2.0,
                                 1.0, label="acceptance")
    assert row["ici_bytes"] == model["per_device"] * iters * 4 * 2
