"""Autotuner with a persistent, chip-keyed on-disk cache.

Reference behavior: lib/tune.cpp (1167 LoC) + include/tune_quda.h — every
kernel brute-force times its launch configurations once, caches the winner
in $QUDA_RESOURCE_PATH/tunecache.tsv keyed by {volume, name, aux}, and
doubles as the profiling system (profile_N.tsv).  The reference cache also
carries the hardware it was measured on and refuses to serve entries from
a different device — a winner timed on one chip is NOISE on another.

TPU analog: XLA already schedules fused kernels, so what remains tunable is
the CHOICE among whole implementations (pure-XLA stencil vs Pallas kernel,
Pallas block shapes, halo policies, staggered kernel forms).  `tune` times
jitted candidates (median of inner reps after warmup), persists winners to
$QUDA_TPU_RESOURCE_PATH/tunecache.json, and records per-key call counts and
timings for `save_profile`.

Cache key schema (v2): ``platform|volume|name|aux`` where ``platform`` is
:func:`platform_key` — backend, device kind, and visible device count — so
a winner raced on CPU interpret is never silently reused on TPU (or vice
versa), and a multi-host mesh does not serve a single-chip race.  Entries
written by the pre-platform schema carry no ``platform`` field and are
dropped at load with a one-time "stale schema, re-racing" notice (the
QUDA_TUNE_VERSION_CHECK analog for the key layout itself).

Warm start: :func:`warm_start` (called by ``init_quda``) re-loads the
persistent cache under the current resource path and mirrors the load —
entry counts, stale drops, platform — into the obs trace stream, so a
fresh worker's first solve hits the raced winners of previous processes
(policy races included: QUDA_TPU_SHARDED_POLICY's auto-races go
through `tune` and therefore through this store) without a
compile/race storm, and the warm-start behavior is auditable in the
chrome artifact next to the solves it accelerated.
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

_cache: Dict[str, dict] = {}
_profile: Dict[str, dict] = {}
_loaded_path = None
_platform_key: Optional[str] = None
_stale_noticed = False
# (key, candidate) of every entrant that raised in a race this process
# (the in-process record chip_smoke.py reads: zero on a healthy chip)
_failed: list = []


def _resource_path():
    from . import config as qconf
    return qconf.get("QUDA_TPU_RESOURCE_PATH", fresh=True)


def platform_key() -> str:
    """Stable id of the hardware this process races on: backend platform,
    device kind, and visible device count (the mesh-capacity component),
    e.g. ``tpu:TPU-v5-lite:n8`` or ``cpu:cpu:n1``.  Computed lazily (the
    first call may initialise the jax backend) and cached per process;
    '|' and whitespace are folded so the key splits cleanly."""
    global _platform_key
    if _platform_key is None:
        import jax
        devs = jax.devices()   # no device visible -> raises, never a
        #                        made-up 'unknown' key to race under
        kind = str(getattr(devs[0], "device_kind", "")
                   or devs[0].platform)
        kind = re.sub(r"[\s|]+", "-", kind).strip("-")
        _platform_key = f"{devs[0].platform}:{kind}:n{len(devs)}"
    return _platform_key


def tune_key(name: str, volume, aux: str = "") -> str:
    """TuneKey {volume, name, aux} analog (include/tune_key.h:56) with
    the v2 platform/chip/mesh component prepended — see module docstring."""
    return f"{platform_key()}|{volume}|{name}|{aux}"


def cached_param(name: str, volume, aux: str = "") -> Optional[str]:
    """The cached winner for this (platform, volume, name, aux), or None
    when the race has not run on this hardware yet.  Lets call sites
    report warm-cache-vs-raced provenance without a second race."""
    e = _cache.get(tune_key(name, volume, aux))
    return e.get("param") if isinstance(e, dict) else None


def _notice_stale(n: int, path: str):
    """One-time notice for pre-platform-schema entries: they are not
    attributable to a chip, so they are invalidated (re-raced on first
    use) rather than migrated into a key they were never measured under."""
    global _stale_noticed
    _obs_event("tune_cache_invalidated", count=n, path=path,
               reason="stale schema: entry has no platform key")
    if _stale_noticed:
        return
    _stale_noticed = True
    try:
        from . import logging as qlog
        qlog.warningq(
            f"tunecache {path}: dropped {n} entr"
            f"{'y' if n == 1 else 'ies'} recorded under the pre-platform "
            "key schema (not attributable to this chip); stale schema, "
            "re-racing on first use")
    except Exception:
        pass


def load_cache() -> Optional[dict]:
    """Load tunecache.json under the current resource path into the
    process cache.  Entries without a ``platform`` field (the pre-v2
    un-keyed schema) are dropped with a one-time notice — a winner that
    cannot name the hardware it was timed on must not be served.
    Returns {'path', 'entries', 'stale'} stats (None when no resource
    path is configured)."""
    global _loaded_path
    path = _resource_path()
    if not path:
        return None
    f = os.path.join(path, "tunecache.json")
    loaded = stale = 0
    if os.path.exists(f):
        try:
            with open(f) as fh:
                raw = json.load(fh)
        except (json.JSONDecodeError, OSError):
            raw = {}
        for k, v in raw.items():
            if isinstance(v, dict) and v.get("platform"):
                _cache[k] = v
                loaded += 1
            else:
                stale += 1
        if stale:
            _notice_stale(stale, f)
    _loaded_path = f
    return {"path": f, "entries": loaded, "stale": stale}


def save_cache():
    path = _resource_path()
    if not path:
        return
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "tunecache.json"), "w") as fh:
        json.dump(_cache, fh, indent=1, sort_keys=True)


def warm_start() -> int:
    """init_quda hook: (re)load the persistent cache so this process's
    first solve serves already-raced (platform, volume, form) winners
    with zero re-races, and mirror the load as a ``tune_cache_loaded``
    trace event (counts + platform) so warm-start behavior is auditable
    in the chrome artifact.  Returns the number of entries usable on
    THIS hardware."""
    stats = load_cache() or {"path": "", "entries": 0, "stale": 0}
    here = platform_key()
    usable = sum(1 for k in _cache if k.startswith(here + "|"))
    _obs_event("tune_cache_loaded", path=stats["path"],
               entries=len(_cache), usable_here=usable,
               stale_dropped=stats["stale"], platform=here)
    _obs_gauge("tune_cache_entries", len(_cache), scope="total")
    _obs_gauge("tune_cache_entries", usable, scope="usable_here")
    _obs_gauge("tune_cache_entries", stats["stale"],
               scope="stale_dropped")
    return usable


def cache_snapshot(platform_only: bool = True) -> Dict[str, dict]:
    """Host-side copy of the in-process tunecache — with
    ``platform_only`` restricted to the entries servable on THIS
    hardware (the ones a solve on this chip could have consulted).
    The postmortem bundle writer (obs/postmortem.py) embeds this so a
    replayed solve can be compared against the winners the original
    solve was served."""
    here = platform_key() + "|"
    return {k: dict(v) for k, v in _cache.items()
            if not platform_only or k.startswith(here)}


def tuning_enabled() -> bool:
    from . import config as qconf
    return qconf.get("QUDA_TPU_ENABLE_TUNING", fresh=True)


def _obs_event(name: str, **fields):
    """Mirror tuner decisions into the trace stream (no-op when tracing
    is off) so every cached choice is auditable next to the spans it
    affects — the policy-engine-as-profiler contract."""
    try:
        from ..obs import trace as otr
        otr.event(name, cat="tune", **fields)
    except Exception:
        pass


def _obs_metric(name: str, value: float = 1.0, **labels):
    """Mirror tuner cache behavior into the metrics registry (no-op when
    QUDA_TPU_METRICS is off) — the warm-cache hit/miss/race accounting a
    serving fleet reads before scaling (ROADMAP item 2's compile/race
    storm is diagnosed HERE)."""
    try:
        from ..obs import metrics as omet
        omet.inc(name, value, **labels)
    except Exception:
        pass


def _obs_gauge(name: str, value: float, **labels):
    try:
        from ..obs import metrics as omet
        omet.set_gauge(name, value, **labels)
    except Exception:
        pass


def tune(name: str, volume, candidates: Dict[str, Callable], args: tuple,
         aux: str = "", reps: int = 3, inner: int = 5) -> str:
    """Return the winning candidate key; time once per chip, cache forever.

    candidates: {param_string: jitted callable}; each is called as f(*args)
    and must return a jax array (block_until_ready used for timing).
    Candidate timings, failures, the winner and cache hits are emitted
    as trace events (obs/trace.py) and the candidate timings accumulate
    into the profiler half (record_launch -> profile_N.tsv).
    """
    key = tune_key(name, volume, aux)
    if key in _cache and _cache[key]["param"] in candidates:
        _obs_event("tune_cached", key=key,
                   param=_cache[key]["param"],
                   seconds=_cache[key].get("time"))
        _obs_metric("tune_cache_hits_total", kernel=name)
        return _cache[key]["param"]
    _obs_metric("tune_cache_misses_total", kernel=name)
    if not tuning_enabled():
        return next(iter(candidates))
    _obs_metric("tune_races_total", kernel=name)
    best, best_t = None, float("inf")
    for param, fn in candidates.items():
        try:
            out = fn(*args)
            out.block_until_ready()  # compile + warmup
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(inner):
                    out = fn(*args)
                out.block_until_ready()
                times.append((time.perf_counter() - t0) / inner)
            t = min(times)
        except Exception as e:
            _obs_event("tune_candidate_failed", key=key, param=param,
                       error=str(e)[:120])
            # the trace event is cut to 120 chars and exists only in a
            # trace session: a candidate the compiler refuses (the
            # pallas entrant, typically) must not drop out of the race
            # unseen — full message, once per (kernel, candidate)
            from . import logging as qlog
            qlog.warn_once(
                f"tune_candidate_failed:{name}:{param}",
                f"tune: candidate {param!r} of {key} failed and left "
                f"the race: {type(e).__name__}: {e}")
            _failed.append((key, param))
            continue
        record_launch(name, volume, f"{aux}|{param}", t)
        _obs_event("tune_candidate", key=key, param=param, seconds=t)
        if t < best_t:
            best, best_t = param, t
    if best is None:
        # every candidate raised (a race mid-chip-window can lose all
        # its entrants to a transient): degrade to the STATIC DEFAULT —
        # the first registered candidate, by the same convention
        # tuning-disabled uses — with a one-time notice, and do NOT
        # cache: the degraded choice was never timed, so the next
        # process re-races (tune.cpp skips failing launches the same
        # way; an all-fail race aborting the solve would turn a tuning
        # hiccup into an outage)
        default = next(iter(candidates))
        _obs_event("tune_race_all_failed", key=key, fallback=default,
                   n_candidates=len(candidates))
        _obs_metric("tune_race_failures_total", kernel=name)
        from . import logging as qlog
        qlog.warn_once(
            f"tune_all_failed:{name}",
            f"tune: every candidate failed for {key}; degrading to "
            f"the static default {default!r} (not cached — re-raced "
            "next time)")
        return default
    _cache[key] = {"param": best, "time": best_t,
                   "platform": platform_key()}
    _obs_event("tune_winner", key=key, param=best, seconds=best_t)
    save_cache()
    return best


def record_launch(name: str, volume, aux: str, seconds: float,
                  flops: float = 0.0, bytes_: float = 0.0):
    """Accumulate per-kernel stats (the profiler half of lib/tune.cpp)."""
    key = tune_key(name, volume, aux)
    p = _profile.setdefault(key, {"calls": 0, "seconds": 0.0, "flops": 0.0,
                                  "bytes": 0.0})
    p["calls"] += 1
    p["seconds"] += seconds
    p["flops"] += flops
    p["bytes"] += bytes_


def save_profile(fname: str = "profile_0.tsv") -> Optional[str]:
    """Write profile_N.tsv like lib/tune.cpp:528-610; returns the path
    (None without a resource path) so end_quda can index it into
    artifacts_manifest.json."""
    path = _resource_path()
    if not path:
        return None
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, fname), "w") as fh:
        fh.write("key\tcalls\tseconds\tGFLOPS\tGB/s\n")
        for key, p in sorted(_profile.items()):
            s = max(p["seconds"], 1e-12)
            fh.write(f"{key}\t{p['calls']}\t{p['seconds']:.6f}\t"
                     f"{p['flops'] / s / 1e9:.2f}\t"
                     f"{p['bytes'] / s / 1e9:.2f}\n")
    return os.path.join(path, fname)


load_cache()
