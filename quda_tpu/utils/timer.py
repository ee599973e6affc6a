"""TimeProfile: named, categorised, nestable timers with a global stack.

Reference behavior: include/timer.h / lib/timer.cpp — TimeProfile with
~30 QudaProfileType categories, pushProfile RAII, device timers via event
pairs, and the endQuda summary print.  Device timing here wraps
block_until_ready around the timed region (XLA's async dispatch plays the
role of CUDA streams).
"""

from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, List, Optional

# QudaProfileType analog
CATEGORIES = (
    "init", "download", "upload", "compute", "comms", "epilogue", "free",
    "io", "chrono", "eigen", "tune", "setup", "preamble", "total",
)


class TimeProfile:
    def __init__(self, name: str):
        self.name = name
        self.seconds: Dict[str, float] = defaultdict(float)
        self.count: Dict[str, int] = defaultdict(int)
        # per-category stack of open start times: nested same-category
        # spans each keep their own interval (a plain dict dropped the
        # outer interval on re-entrant start, losing its time entirely)
        self._open: Dict[str, List[float]] = {}

    def start(self, category: str = "total"):
        self._open.setdefault(category, []).append(time.perf_counter())

    def stop(self, category: str = "total", sync=None):
        if sync is not None:
            sync.block_until_ready()
        stack = self._open.get(category)
        if not stack:
            return          # unmatched stop stays a no-op
        t0 = stack.pop()
        self.seconds[category] += time.perf_counter() - t0
        self.count[category] += 1

    @contextmanager
    def __call__(self, category: str = "total"):
        self.start(category)
        try:
            yield
        finally:
            self.stop(category)

    def summary(self) -> str:
        lines = [f"TimeProfile [{self.name}]"]
        for cat in sorted(self.seconds, key=lambda c: -self.seconds[c]):
            lines.append(f"  {cat:>10}: {self.seconds[cat]:10.4f} s"
                         f"  ({self.count[cat]} calls)")
        return "\n".join(lines)


_profiles: Dict[str, TimeProfile] = {}
_stack: List[TimeProfile] = []


def get_profile(name: str) -> TimeProfile:
    if name not in _profiles:
        _profiles[name] = TimeProfile(name)
    return _profiles[name]


def _profiling_enabled() -> bool:
    from . import config as qconf
    return not qconf.get("QUDA_TPU_DO_NOT_PROFILE", fresh=True)


@contextmanager
def push_profile(name: str, category: str = "total"):
    """pushProfile RAII analog (timer.h:243); a no-op under
    QUDA_TPU_DO_NOT_PROFILE (reference: QUDA_DO_NOT_PROFILE)."""
    if not _profiling_enabled():
        yield None
        return
    prof = get_profile(name)
    _stack.append(prof)
    prof.start(category)
    try:
        yield prof
    finally:
        prof.stop(category)
        _stack.pop()


def current_profile() -> Optional[TimeProfile]:
    return _stack[-1] if _stack else None


def print_summary():
    from .logging import printq
    for prof in _profiles.values():
        printq(prof.summary())
    from ..obs import build as obuild
    built = obuild.summary()
    if built:
        printq(built)
    save_profiles()


def save_profiles():
    """Dump per-profile summaries as <QUDA_TPU_PROFILE_OUTPUT_BASE>.tsv
    under the resource path (reference: QUDA_PROFILE_OUTPUT_BASE tsv
    dumps in lib/tune.cpp)."""
    from . import config as qconf
    path = qconf.get("QUDA_TPU_RESOURCE_PATH", fresh=True)
    if not path or not _profiles:
        return
    base = qconf.get("QUDA_TPU_PROFILE_OUTPUT_BASE", fresh=True)
    import os
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, f"{base}.tsv"), "w") as fh:
        fh.write("profile\tcategory\tseconds\tcount\n")
        for prof in _profiles.values():
            for cat, t in sorted(prof.seconds.items()):
                fh.write(f"{prof.name}\t{cat}\t{t:.6f}\t"
                         f"{prof.count.get(cat, 0)}\n")


# global flop/byte counters (Tunable::flops_global analog, lib/tune.cpp)
_counters = {"flops": 0.0, "bytes": 0.0}


def add_flops(n: float):
    _counters["flops"] += n


def add_bytes(n: float):
    _counters["bytes"] += n


def flops_global() -> float:
    return _counters["flops"]


def bytes_global() -> float:
    return _counters["bytes"]
