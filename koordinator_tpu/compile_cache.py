"""JAX's persistent compilation cache, placed from outside or at one
fixed in-checkout path.

The first solve of every shape compiles (seconds to minutes at the
50,000 x 10,240 shape), and a restarted scheduler — or the next
``chip_smoke.py`` / bench process — pays it again unless the compiled
programs persist.  The cache directory is part of each entry's key, so
it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  sets no path and only reports the one in force;
- unset: ``<checkout>/.jax_cache`` (git-ignored), never a temp name,
  pid or timestamp.

:func:`enable_compile_cache` must run before the process's first jit —
JAX decides once, at its first compile, whether the cache is in use.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
#: process-wide like the JAX listener registry it mirrors (listeners
#: cannot be unregistered through the public API, so there is one)
_events: dict[str, int] | None = None


def enable_compile_cache() -> str:
    """Point JAX at the persistent cache; returns the directory in force."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def cache_events() -> dict[str, int]:
    """Live ``{"hits", "misses"}`` counts of persistent-cache reads that
    found a program and compiles that wrote one, since the first call."""
    global _events
    if _events is None:
        import jax.monitoring

        counts = {"hits": 0, "misses": 0}

        def on_event(event: str, **_kw) -> None:
            if event == _HIT:
                counts["hits"] += 1
            elif event == _MISS:
                counts["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        _events = counts
    return _events
