"""Where JAX keeps compiled programs between processes.

:func:`enable_compile_cache` is for entry points (``chip_smoke.py``,
``benchmarks/run.py``); nothing calls it at import.  The cache key includes
the directory, so the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself), else ``.jax_cache`` at
the repository root.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CompileLog:
    """Counts the backend compiles, and their seconds, made while open.

    A compile served by the persistent cache counts too: JAX reports both
    under one event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs

    def __enter__(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
