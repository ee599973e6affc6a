"""Entry point ``invert_quda``: one source per call.

An entry module is how the harness drives one of the program's entry
points.  It exposes ``open`` (set-up against a resident gauge), ``call``
(one API call on a batch of canonical sources, returning the solutions
and what the API reported for each source), ``counters`` (running totals
of the program's own spans and counters, snapshotted around the window)
and ``close``.  Everything here belongs to the system under test; the
yardstick (data, reference, comparison) is elsewhere.
"""

from quda_tpu.interfaces import quda_api as api
from quda_tpu.interfaces.params import GaugeParam, InvertParam
from quda_tpu.utils import timer

PROFILE = "invert_quda"
PHASES = ("setup", "compute", "epilogue")


def open(config, traffic, gauge):
    """init + resident gauge; ``gauge`` is (4,T,Z,Y,X,3,3) complex64."""
    api.init_quda()
    api.load_gauge_quda(gauge, GaugeParam(
        X=tuple(reversed(gauge.shape[1:5])), **config["gauge_param"]))
    return {"config": config, "kappa": float(traffic["kappa"])}


def invert_param(state):
    """A fresh InvertParam per call (the API writes results into it)."""
    return InvertParam(kappa=state["kappa"],
                       **state["config"]["invert_param"])


def call(state, sources):
    """sources (1,T,Z,Y,X,4,3) -> solutions (1,...) and per-source info."""
    p = invert_param(state)
    x = api.invert_quda(sources[0], p)
    return x[None], {"iters": [int(p.iter_count)],
                     "true_res": [float(p.true_res)],
                     "converged": [bool(p.converged)]}


def phase_counters(profile):
    """Seconds the API's own utils/timer profile has charged to each
    phase so far (obs.trace.phase writes them)."""
    prof = timer.get_profile(profile)
    return {f"phase.{c}": float(prof.seconds.get(c, 0.0)) for c in PHASES}


def counters():
    return phase_counters(PROFILE)


def close(state):
    api.end_quda()
