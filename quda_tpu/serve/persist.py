"""Cross-process warm start: compilation cache + executable-key index.

Reference behavior: QUDA's tunecache.tsv under QUDA_RESOURCE_PATH means
a fresh process never re-times launch configurations it has already
raced; the analog gap on the XLA side is the COMPILE — a fresh worker
re-lowering and re-compiling every solve executable is the "compile
storm" ROADMAP item 2 names.  Two halves close it:

* the **persistent XLA compilation cache**:
  ``utils.compile_cache.enable_compile_cache`` (also called by
  ``init_quda``) keeps it where ``JAX_COMPILATION_CACHE_DIR`` says, else
  at the fixed ``<checkout>/.jax_cache`` (knob
  ``QUDA_TPU_SERVE_COMPILE_CACHE=0`` turns it off) so executables built
  by one process deserialise in the next instead of recompiling;
* the **executable-key index**: obs/metrics counts a ``compiles_total``
  the first time a (api, form, shape, dtype, solver) key executes *in
  this process* — honest for a cold process, wrong for a warm one whose
  executables the cache serves.  ``save_warm_keys`` writes the session's
  executed keys to ``executable_keys.json`` (next to ``tunecache.json``,
  platform-scoped the same way: a CPU key must not pre-warm a TPU
  worker), and ``warm_start`` seeds them back into the registry — so
  worker process B records ``compiles_total == 0`` for already-keyed
  executables while ``executions_total`` advances: the acceptance
  instrument that proves the storm is gone.

``SolveService.start`` calls :func:`warm_start`; ``stop`` calls
:func:`save_warm_keys`.  Both are safe (and useful) outside the
service too.
"""

from __future__ import annotations

import json
import os
from typing import Optional

WARM_KEYS_FILE = "executable_keys.json"

# keys that executed BEFORE the persistent cache was wired this process
# (warm_start snapshots them): their executables were never serialized,
# so they must not be persisted as warm — and None means warm_start has
# not run, in which case nothing is provably cached and save is a no-op
_precache_keys: "set | None" = None


def _resource_path() -> str:
    from ..utils import config as qconf
    return str(qconf.get("QUDA_TPU_RESOURCE_PATH", fresh=True))


def warm_keys_path() -> Optional[str]:
    root = _resource_path()
    return os.path.join(root, WARM_KEYS_FILE) if root else None


def _index_scope() -> str:
    """The scope the key index is stored under: hardware platform
    (tunecache discipline — another chip's executables are noise) PLUS
    the jax version, because an upgrade invalidates every persistent-
    cache entry (the XLA cache key includes the compiler fingerprint):
    keys recorded under jax X would seed compiles_total == 0 under
    jax Y while worker B genuinely recompiles everything — the false
    negative the instrument exists to expose."""
    import jax

    from ..utils.tune import platform_key
    return f"{platform_key()}|jax{jax.__version__}"


def load_warm_keys() -> set:
    """Executable keys recorded by previous processes on this platform
    + jax version (see :func:`_index_scope`)."""
    path = warm_keys_path()
    if not path or not os.path.exists(path):
        return set()
    try:
        with open(path) as fh:
            raw = json.load(fh)
    except (json.JSONDecodeError, OSError):
        return set()
    keys = raw.get(_index_scope(), [])
    return {str(k) for k in keys} if isinstance(keys, list) else set()


def save_warm_keys() -> int:
    """Merge this session's executed keys into the on-disk index under
    the current platform; returns THIS session's contribution (the
    count written, 0 when there is nothing or nowhere to write —
    matching the serve_warm_keys{scope=saved} gauge, which an operator
    compares against {scope=loaded} to spot a session that recompiled
    everything).  Skipped
    entirely when the compilation cache is disabled: a key promises
    "this executable is persisted", and a cache-less session persisted
    nothing — saving its keys would poison the next worker's
    compile accounting (the warm_start seeding guard's dual)."""
    from ..obs import metrics as omet
    from ..utils.compile_cache import compile_cache_dir
    path = warm_keys_path()
    if (not path or _precache_keys is None
            or compile_cache_dir() is None):
        return 0
    # only keys whose compile happened WITH the cache wired (or that
    # were themselves loaded from the index) are provably persisted;
    # a key compiled before warm_start ran was never serialized
    seen = omet.executable_keys() - _precache_keys
    if not seen:
        return 0
    try:
        with open(path) as fh:
            raw = json.load(fh)
        if not isinstance(raw, dict):
            raw = {}
    except (json.JSONDecodeError, OSError, FileNotFoundError):
        raw = {}
    here = _index_scope()
    merged = sorted(set(raw.get(here, [])) | seen)
    raw[here] = merged
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(raw, fh, indent=1, sort_keys=True)
    omet.set_gauge("serve_warm_keys", len(seen), scope="saved")
    return len(seen)


def warm_start() -> dict:
    """Worker-startup hook: enable the compilation cache and seed the
    compile-accounting registry with the platform's persisted
    executable keys.  Mirrored as a ``serve_warm_start`` trace event
    and the ``serve_warm_keys{scope=loaded}`` gauge so the warm-start
    behavior is auditable next to the solves it accelerated (the
    tune.warm_start discipline)."""
    from ..obs import metrics as omet
    from ..obs import trace as otr
    global _precache_keys
    from ..utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # the key index is only honest WITH the compilation cache: keys
    # claim "this executable is already built and persisted" — seeding
    # them while the cache is disabled/unconfigurable would record
    # compiles_total == 0 for executables this process genuinely
    # recompiles, green-lighting the exact storm the instrument exists
    # to expose
    keys = load_warm_keys() if cache_dir else set()
    # keys already executed before the cache was wired were never
    # serialized — snapshot them so save_warm_keys won't persist them
    # (the loaded ones ARE in the cache, so they stay saveable)
    _precache_keys = omet.executable_keys() - keys
    seeded = omet.seed_executable_keys(keys)
    omet.set_gauge("serve_warm_keys", len(keys), scope="loaded")
    otr.event("serve_warm_start", cat="serve",
              cache_dir=cache_dir or "",
              keys_loaded=len(keys), keys_seeded=seeded)
    return {"cache_dir": cache_dir, "keys_loaded": len(keys),
            "keys_seeded": seeded}
