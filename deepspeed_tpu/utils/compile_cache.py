"""Where JAX's persistent compilation cache lives.

The directory is part of the cache key's lookup, so it must not move between
processes or runs: it is either where ``JAX_COMPILATION_CACHE_DIR`` says (JAX
reads that variable itself — nothing is set here) or the fixed, git-ignored
``<checkout>/.jax_cache`` next to the package. Never a tempdir, a pid or a
clock.
"""

from __future__ import annotations

import os
from typing import Dict

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counters: Dict[str, int] = {}


def place_compile_cache() -> str:
    """Called by every entry point before its first compile. Returns the
    directory in use."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_counters() -> Dict[str, int]:
    """Live ``{"hits", "misses"}`` of this process's persistent-cache
    lookups (counted from the first call on): a key that moves between runs
    shows as misses where hits were expected."""
    if not _counters:
        import jax.monitoring

        _counters.update(hits=0, misses=0)

        def _on_event(event: str, **_) -> None:
            if event in _EVENTS:
                _counters[_EVENTS[event]] += 1

        jax.monitoring.register_event_listener(_on_event)
    return _counters
