"""Process-level JAX set-up shared by the entry points and lane children.

* :func:`setup_compile_cache` — every entry point (``launch.insitu``,
  ``launch.train``, ``launch.catalog_serve``, ``benchmarks.run``,
  ``chip_smoke.py``) calls it first, so one checkout reuses its compiled
  programs from run to run.
* :func:`pin_to_cpu` — spawned lane and writer processes call it before
  anything touches a JAX backend. A chip belongs to one process at a
  time: the engine or trainer that spawned the child holds it, and a
  child reaching for it would fail or hang (DESIGN.md §13).
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (``<checkout>/src/repro/runtime.py``)
CHECKOUT = Path(__file__).resolve().parents[2]


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and no other path is set. Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache``: the directory is part of what makes
    an entry found again, so it never derives from a temp name, a pid or
    the time. Every compile is cached, however short.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def pin_to_cpu() -> None:
    """Keep this process on JAX's CPU backend, whatever the host holds."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
