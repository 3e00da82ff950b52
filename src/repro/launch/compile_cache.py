"""JAX's persistent compilation cache, set up in one place.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks.run``) calls :func:`enable_compile_cache`
before its first compile.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set → JAX already reads it; nothing else is
  set, so the cache lives where the environment says;
* otherwise → ``<checkout>/.jax_cache``, a fixed path (the path is part of
  what a later run must find again, so never a temporary directory, a pid
  or a time).

:class:`CompileClock` sums the compile time JAX reports, so a run can print
its cold and warm compile seconds.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"

# JAX times each backend compile under this event, persistent-cache reads
# included (jax._src.dispatch.BACKEND_COMPILE_EVENT)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


class CompileClock:
    """Backend compile seconds, compile count and persistent-cache hits
    recorded from construction on (a process-lifetime listener)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.seconds += seconds
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1


__all__ = ["enable_compile_cache", "CompileClock", "DEFAULT_DIR"]
