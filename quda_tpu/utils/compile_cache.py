"""The ONE home of the persistent XLA compilation cache placement.

A cold process compiles every solve executable; the persistent cache
lets the next process (a restarted solve-service worker, a second run
of ``chip_smoke.py``) deserialise them instead.  The directory is part
of the cache key, so it must never move:

* ``JAX_COMPILATION_CACHE_DIR`` set -> the operator placed the cache
  from outside; JAX reads that variable itself and this module sets
  NOTHING in code;
* unset -> ``<checkout>/.jax_cache``, derived from this package's own
  location (never the working directory, a temp name, a pid or a time).

``QUDA_TPU_SERVE_COMPILE_CACHE=0`` turns the wiring off.  Called by
``init_quda``, by ``serve.persist.warm_start`` and by ``chip_smoke.py``
before the first compile; idempotent.
"""

from __future__ import annotations

import os
from typing import Optional

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> Optional[str]:
    """The directory the persistent compilation cache uses (None when
    QUDA_TPU_SERVE_COMPILE_CACHE=0 disabled it)."""
    from . import config as qconf
    if str(qconf.get("QUDA_TPU_SERVE_COMPILE_CACHE", fresh=True)) == "0":
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR


def enable_compile_cache() -> Optional[str]:
    """Wire the persistent compilation cache; returns its directory
    (None when disabled).  With JAX_COMPILATION_CACHE_DIR set this is a
    pure report — jax already read the variable."""
    d = compile_cache_dir()
    if d is not None and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        if jax.config.jax_compilation_cache_dir != d:
            jax.config.update("jax_compilation_cache_dir", d)
    return d
