"""Where JAX's persistent compilation cache lives.

Called once, before the first dispatch, by every process that
dispatches to the device (``daemon.main``, ``benchmark.run``,
``chip_smoke.main``, ``__graft_entry__``) — never by the test suite.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that variable itself and
this helper sets nothing.  Unset: the cache goes to ``.jax_cache/`` at
the root of the checkout, a path computed from this package's location
only.  The directory is part of the cache key, so it must not move
between processes: no temporary name, pid or time enters it.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Returns the directory the cache will use."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
