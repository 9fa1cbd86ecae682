"""What every entry and the harness share: the compile cache, the compile
counter, host spans in the profiler's trace, and the peaks table."""
from __future__ import annotations

import json
import os
from pathlib import Path

HERE = Path(__file__).resolve().parent
CACHE_DIR = HERE / ".jax_cache"


def use_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory inside this checkout (the path is part of
    the cache's key, so it never moves). Every program is cached, however
    short its compile, so a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Counts backend compiles (persistent-cache loads included)."""

    def __init__(self):
        import jax

        self.n = 0
        self._jax = jax

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        self._jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def span(name: str):
    """A host span in the profiler's trace (free when no trace is taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error, never a default."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]
