"""Persistent XLA compilation cache for the repo's entry points.

Each entry point's ``main()`` calls ``enable()`` first thing (never at
import, so importing a module changes no JAX state).  The cache directory
is part of the cache key, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set -> JAX already reads it; nothing else
  is configured.
* unset -> ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

On an accelerator every compile is kept, however short: the serving
warmup is many small kernels, each under JAX's default one-second floor,
and together they are most of a cold start.  On the CPU nothing is
configured: compiles there are cheap, and XLA:CPU executables loaded back
by another process can fault on a host-feature mismatch (fleet replicas
died of it).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def enable() -> str | None:
    """Turn the persistent compilation cache on; returns its directory
    (on the CPU: ``JAX_COMPILATION_CACHE_DIR`` or None, left as is)."""
    import jax

    path = os.environ.get(ENV)
    if jax.default_backend() == "cpu":
        return path
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
