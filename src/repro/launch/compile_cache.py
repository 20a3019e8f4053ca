"""Where entry points keep JAX's persistent compilation cache.

Called once by each program entry point (``chip_smoke.py``,
``python -m repro.launch.analysis``, ``benchmarks/run.py``,
``benchmarks/bench_sweep.py``), before anything compiles.  The library
(``repro.sweep``) never sets a cache on import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set here.
* unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path (the
  cache is keyed on nothing that moves between runs of one checkout), never
  built from a temporary directory, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (src/repro/launch/ → three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
