"""Central env-flag registry (utils/config.py) — the QUDA_* config
system analog (SURVEY §5.6): typed parsing, typo detection, and the
knobs' effect on API behavior."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.utils import config as qconf


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for v in list(os.environ):
        if v.startswith("QUDA_TPU_") or v in qconf.SUBSUMED:
            monkeypatch.delenv(v, raising=False)
    qconf.reset_cache()
    yield
    qconf.reset_cache()


def test_defaults_and_types():
    assert qconf.get("QUDA_TPU_ENABLE_TUNING") is True
    assert qconf.get("QUDA_TPU_MAX_MULTI_RHS") == 32
    assert qconf.get("QUDA_TPU_MONITOR_PERIOD") == 1.0
    assert qconf.get("QUDA_TPU_VERBOSITY") == "summarize"


def test_env_override_and_parse(monkeypatch):
    monkeypatch.setenv("QUDA_TPU_ENABLE_TUNING", "0")
    monkeypatch.setenv("QUDA_TPU_MAX_MULTI_RHS", "8")
    monkeypatch.setenv("QUDA_TPU_VERBOSITY", "debug")
    qconf.reset_cache()
    assert qconf.get("QUDA_TPU_ENABLE_TUNING") is False
    assert qconf.get("QUDA_TPU_MAX_MULTI_RHS") == 8
    assert qconf.get("QUDA_TPU_VERBOSITY") == "debug"


def test_bad_values_raise(monkeypatch):
    monkeypatch.setenv("QUDA_TPU_ENABLE_TUNING", "maybe")
    qconf.reset_cache()
    with pytest.raises(ValueError):
        qconf.get("QUDA_TPU_ENABLE_TUNING")
    monkeypatch.setenv("QUDA_TPU_VERBOSITY", "shouty")
    with pytest.raises(ValueError):
        qconf.get("QUDA_TPU_VERBOSITY", fresh=True)


def test_unregistered_knob_raises():
    with pytest.raises(KeyError):
        qconf.get("QUDA_TPU_NO_SUCH_KNOB")


def test_check_environment_flags_typos_and_legacy(monkeypatch):
    monkeypatch.setenv("QUDA_TPU_ENABLE_TUNNING", "1")       # typo
    monkeypatch.setenv("QUDA_ENABLE_DEVICE_MEMORY_POOL", "1")  # CUDA-era
    seen = []
    bad = qconf.check_environment(warn=seen.append)
    assert "QUDA_TPU_ENABLE_TUNNING" in bad
    assert "QUDA_ENABLE_DEVICE_MEMORY_POOL" in bad
    assert any("ENABLE_TUNNING" in m for m in seen)
    assert any("XLA/PJRT allocator" in m for m in seen)


def test_describe_lists_every_knob():
    text = qconf.describe()
    for name in qconf.knobs():
        assert name in text
    assert "QUDA_ENABLE_NVSHMEM" in text  # subsumed section


def test_max_multi_rhs_caps_block_solvers(monkeypatch):
    # advisory warn-and-proceed (the reference's QUDA_MAX_MULTI_RHS is a
    # compile-time instantiation bound, not a runtime batch rejection)
    from quda_tpu.solvers.block import batched_cg
    monkeypatch.setenv("QUDA_TPU_MAX_MULTI_RHS", "2")
    qconf.reset_cache()
    B = jnp.ones((3, 8), jnp.complex128)
    with pytest.warns(UserWarning, match="MAX_MULTI_RHS"):
        res = batched_cg(lambda x: x, B)
    assert res.x.shape == B.shape          # the batch still ran


def test_sloppy_precision_override(monkeypatch):
    from quda_tpu.interfaces.params import InvertParam
    from quda_tpu.interfaces.quda_api import _resolve_sloppy
    p = InvertParam(dslash_type="wilson", kappa=0.12)
    monkeypatch.setenv("QUDA_TPU_SLOPPY_PRECISION", "single")
    qconf.reset_cache()
    assert _resolve_sloppy(p) == "single"
    monkeypatch.delenv("QUDA_TPU_SLOPPY_PRECISION")
    qconf.reset_cache()
    # back to the platform default (cuda_prec on CPU backends)
    assert _resolve_sloppy(p) == p.cuda_prec


def test_packed_and_pallas_switches(monkeypatch):
    from quda_tpu.interfaces.quda_api import (_packed_enabled,
                                              _pallas_enabled)
    assert _packed_enabled(True) and not _packed_enabled(False)
    assert _pallas_enabled(True) and not _pallas_enabled(False)
    monkeypatch.setenv("QUDA_TPU_PACKED", "0")
    monkeypatch.setenv("QUDA_TPU_PALLAS", "1")
    assert not _packed_enabled(True)
    assert _pallas_enabled(False)


@pytest.mark.parametrize("name", ["QUDA_TPU_PALLAS_VERSION",
                                  "QUDA_TPU_STAGGERED_FORM",
                                  "QUDA_TPU_FUSED_TAIL"])
def test_a_retired_knob_is_flagged_once(name, monkeypatch):
    """QUDA_TPU_PALLAS_VERSION went with the v1 and v3 Wilson kernels
    (PR 30), QUDA_TPU_STAGGERED_FORM with the fused staggered kernels
    and QUDA_TPU_FUSED_TAIL with the pallas CG tail (PR 45): each is
    unregistered (``knobs()`` does not list it, ``get`` raises), and a
    user who still sets it is told so by ``check_environment``: once,
    as retired, not as a typo."""
    assert name not in qconf.knobs()
    assert name in qconf.SUBSUMED
    with pytest.raises(KeyError, match="unregistered"):
        qconf.get(name)
    monkeypatch.setenv(name, "1")
    msgs = []
    assert qconf.check_environment(msgs.append) == [name]
    (msg,) = msgs
    assert "no effect" in msg and "unrecognised" not in msg
    assert name in qconf.describe()


def test_force_monitor_logs(monkeypatch, capsys):
    from quda_tpu.gauge.action import _force_monitor
    monkeypatch.setenv("QUDA_TPU_ENABLE_FORCE_MONITOR", "1")
    qconf.reset_cache()
    f = jnp.ones((4, 2, 2, 2, 2, 3, 3), jnp.complex64)
    _force_monitor(f, "test kick")
    err = capsys.readouterr().err  # printq emits on stderr (rank-gated)
    assert "force test kick" in err and "rms" in err


def test_profile_dump(tmp_path, monkeypatch):
    from quda_tpu.utils import timer
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    monkeypatch.setenv("QUDA_TPU_PROFILE_OUTPUT_BASE", "prof_test")
    qconf.reset_cache()
    with timer.push_profile("cfgtest", "compute"):
        np.zeros(4).sum()
    timer.save_profiles()
    text = (tmp_path / "prof_test.tsv").read_text()
    assert "cfgtest" in text and "compute" in text


def test_do_not_profile(monkeypatch):
    from quda_tpu.utils import timer
    monkeypatch.setenv("QUDA_TPU_DO_NOT_PROFILE", "1")
    qconf.reset_cache()
    before = dict(timer.get_profile("skipme").seconds)
    with timer.push_profile("skipme", "compute") as prof:
        assert prof is None
    assert dict(timer.get_profile("skipme").seconds) == before


def test_monitor_default_lifecycle(tmp_path, monkeypatch):
    from quda_tpu.utils import monitor as qmon
    monkeypatch.setenv("QUDA_TPU_ENABLE_MONITOR", "1")
    monkeypatch.setenv("QUDA_TPU_MONITOR_PERIOD", "0.01")
    monkeypatch.setenv("QUDA_TPU_RESOURCE_PATH", str(tmp_path))
    qconf.reset_cache()
    m = qmon.start_default()
    assert m is not None
    import time as _t
    _t.sleep(0.05)
    qmon.stop_default()
    text = (tmp_path / "monitor.tsv").read_text()
    assert "device_bytes" in text and len(text.splitlines()) > 1
