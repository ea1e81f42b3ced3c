"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``, ``python -m repro.experiments``) call
:func:`enable`; importing ``repro`` does not, so tests that assert a real
compile are never served from disk.
"""
from __future__ import annotations

import contextlib
import os
import pathlib
from typing import Dict, Iterator

ENV = "JAX_COMPILATION_CACHE_DIR"
EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache goes to ``.jax_cache/`` at
    the repository root: a fixed path, since the path is part of what a
    later run must find again.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


@contextlib.contextmanager
def counting() -> Iterator[Dict[str, int]]:
    """Count the persistent cache's hits and misses (a miss is an entry
    written) while entered, from JAX's monitoring events; yields the dict
    it fills, ``{"cache_hits": n, "cache_misses": m}``."""
    import jax.monitoring

    counts = dict.fromkeys(EVENTS.values(), 0)

    def listen(event: str, **kwargs) -> None:
        if event in EVENTS:
            counts[EVENTS[event]] += 1

    jax.monitoring.register_event_listener(listen)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_listener(listen)
