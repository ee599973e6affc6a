"""Utility subsystem tests: logging, timers, tune cache, I/O, checksums,
monitor, RNG."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.fields.geometry import LatticeGeometry
from quda_tpu.fields.gauge import GaugeField
from quda_tpu.utils import logging as qlog
from quda_tpu.utils import tune
from quda_tpu.utils.checksum import gauge_checksum
from quda_tpu.utils.io import (load_checkpoint, load_field, load_gauge_ildg,
                               load_vectors, save_checkpoint, save_field,
                               save_gauge_ildg, save_vectors)
from quda_tpu.utils.monitor import Monitor
from quda_tpu.utils.rng import LatticeRNG
from quda_tpu.utils.timer import TimeProfile, get_profile, push_profile

GEOM = LatticeGeometry((4, 4, 4, 4))


def test_logging_ladder(capsys):
    qlog.set_verbosity("silent")
    qlog.printq("hidden")
    with qlog.push_verbosity("verbose"):
        qlog.printq("shown", qlog.VERBOSE)
    qlog.set_verbosity("summarize")
    err = capsys.readouterr().err
    assert "hidden" not in err and "shown" in err


def test_logging_prefix(capsys):
    with qlog.push_prefix("SOLVER: "):
        qlog.printq("inside")
    qlog.printq("outside")
    err = capsys.readouterr().err
    assert "SOLVER: inside" in err
    assert "quda_tpu: outside" in err


def test_errorq_raises():
    with pytest.raises(qlog.QudaError):
        qlog.errorq("boom")


def test_timer_profile():
    prof = TimeProfile("test")
    with prof("compute"):
        time.sleep(0.01)
    assert prof.seconds["compute"] >= 0.01
    assert prof.count["compute"] == 1
    with push_profile("nested") as p:
        time.sleep(0.005)
    assert get_profile("nested").seconds["total"] >= 0.005
    assert "compute" in prof.summary()


def test_tune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    x = jnp.ones((64, 64))
    slow = jax.jit(lambda a: (a @ a) @ (a @ a) @ (a @ a))
    fast = jax.jit(lambda a: a + 1.0)
    calls = {"n": 0}
    winner = tune.tune("dummy", (64, 64), {"slow": slow, "fast": fast},
                       (x,))
    assert winner == "fast"
    # cached on disk: reload into a fresh cache dict
    tune._cache.clear()
    tune.load_cache()
    key = tune.tune_key("dummy", (64, 64), "")
    assert tune._cache[key]["param"] == "fast"
    # profile recording
    tune.record_launch("dummy", (64, 64), "", 0.01, flops=1e9)
    tune.save_profile()
    assert (tmp_path / "profile_0.tsv").exists()


def test_field_io_roundtrip(tmp_path):
    g = GaugeField.random(jax.random.PRNGKey(1), GEOM).data
    p = str(tmp_path / "gauge")
    save_field(p, g, {"kind": "gauge"})
    back, meta = load_field(p)
    assert np.array_equal(np.asarray(back), np.asarray(g))
    assert meta["kind"] == "gauge"


def test_field_io_detects_corruption(tmp_path):
    g = GaugeField.random(jax.random.PRNGKey(2), GEOM).data
    p = str(tmp_path / "bad")
    save_field(p, g)
    import json as _json
    import numpy as _np
    with _np.load(p + ".npz") as z:
        data = z["data"]
        meta = _json.loads(str(z["meta"]))
    data = data.copy()
    data.flat[0] += 1.0
    _np.savez_compressed(p + ".npz", data=data, meta=_json.dumps(meta))
    with pytest.raises(IOError):
        load_field(p)


def test_ildg_roundtrip(tmp_path):
    g = GaugeField.random(jax.random.PRNGKey(3), GEOM).data
    p = str(tmp_path / "cfg.ildg")
    save_gauge_ildg(p, g, GEOM)
    back = load_gauge_ildg(p, GEOM)
    assert np.allclose(np.asarray(back), np.asarray(g))
    # byte-identical checksums
    assert gauge_checksum(back) == gauge_checksum(g)


def test_vector_io_precision_drop(tmp_path):
    vecs = (jax.random.normal(jax.random.PRNGKey(4), (3, 8, 8))
            + 1j * jax.random.normal(jax.random.PRNGKey(5), (3, 8, 8)))
    p = str(tmp_path / "vecs")
    save_vectors(p, vecs, evals=jnp.arange(3.0), save_dtype=np.complex64)
    back, evals = load_vectors(p, dtype=np.complex128)
    assert back.dtype == jnp.complex128
    assert np.allclose(np.asarray(back), np.asarray(vecs), atol=1e-6)
    assert np.allclose(np.asarray(evals), [0, 1, 2])


def test_checkpoint_roundtrip(tmp_path):
    state = {"gauge": GaugeField.random(jax.random.PRNGKey(6), GEOM).data,
             "step": jnp.asarray(42)}
    p = str(tmp_path / "ckpt")
    save_checkpoint(p, state)
    back = load_checkpoint(p)
    assert int(back["step"]) == 42
    assert np.allclose(np.asarray(back["gauge"]),
                       np.asarray(state["gauge"]))


def test_monitor_samples():
    with Monitor(period_s=0.005) as mon:
        time.sleep(0.05)
    assert len(mon.samples) >= 3
    assert all(s["host_rss"] > 0 for s in mon.samples)


def test_rng_deterministic_and_checkpointable():
    r1 = LatticeRNG(7, GEOM)
    a = r1.gaussian((4, 3))
    state = r1.state()
    b = r1.gaussian((4, 3))
    r2 = LatticeRNG.from_state(state, GEOM)
    b2 = r2.gaussian((4, 3))
    assert np.array_equal(np.asarray(b), np.asarray(b2))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    # full determinism from the seed
    r3 = LatticeRNG(7, GEOM)
    assert np.array_equal(np.asarray(r3.gaussian((4, 3))), np.asarray(a))


def test_per_test_limit_ends_a_test_blocked_in_c(tmp_path):
    """tests/conftest.py's per-test limit, driven for real: a pytest
    run whose only test blocks in a C wait (no Python signal handler
    would ever run) must die by itself, non-zero, with the traceback of
    the blocked test on stderr despite pytest's capture."""
    import subprocess
    import sys
    conftest = os.path.join(os.path.dirname(__file__), "conftest.py")
    (tmp_path / "conftest.py").write_text(
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('tier1', {conftest!r})\n"
        "tier1 = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(tier1)\n"
        "tier1.LIMIT_S = 5\n"
        "pytest_runtest_call = tier1.pytest_runtest_call\n")
    (tmp_path / "test_hang.py").write_text(
        "import threading\n\n\n"
        "def test_hang():\n"
        "    threading.Event().wait()\n")
    run = subprocess.run(      # TimeoutExpired: the limit did not fire
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "--rootdir", str(tmp_path), str(tmp_path)],
        capture_output=True, text=True, timeout=30, cwd=str(tmp_path))
    assert run.returncode != 0, run.stdout
    assert "Timeout (0:00:05)!" in run.stderr, run.stderr
    assert "in test_hang" in run.stderr, run.stderr
