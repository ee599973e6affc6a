"""Entry point ``invert_quda`` on the Wilson-clover operator with the
clover term resident: ``open`` = ``init_quda``, ``load_gauge_quda``,
``load_clover_quda`` (QUDA's ``invert_test --dslash-type clover``
calls ``loadCloverQuda`` once per configuration), ``call`` =
``invert_quda``.  Interface as ``entry/invert_quda.py``; the counters
add the seconds of ``load_clover_quda`` (its own profile's total)."""

from quda_tpu.interfaces import quda_api as api
from quda_tpu.utils import timer
from . import invert_quda as single

LOAD_PROFILE = "load_clover_quda"

call = single.call
close = single.close


def open(config, traffic, gauge):
    state = single.open(config, traffic, gauge)
    api.load_clover_quda(single.invert_param(state))
    return state


def counters():
    out = single.counters()
    out["phase.clover_load"] = float(
        timer.get_profile(LOAD_PROFILE).seconds.get("total", 0.0))
    return out
