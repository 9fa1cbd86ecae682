"""Per-phase host spans and wall timers for the round engines.

``PhaseTimer.phase(name)`` and ``NULL_TIMER.phase(name)`` both open a
``jax.profiler.TraceAnnotation`` named ``host.<name>`` around the phase, so
every engine phase lands in the profiler's trace on the clock its device
planes share, whether telemetry is on or off. When no trace is being taken
a span costs one TraceMe check. ``PhaseTimer`` (held with
``telemetry=True``) also accumulates count/seconds per phase; ``NULL_TIMER``
(held otherwise) keeps no totals.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


class PhaseTimer:
    """Accumulates wall seconds per named phase.

    ``sync`` tells engines to ``jax.block_until_ready`` inside device
    phases so async dispatch cannot leak timed work across phases — only
    honest when a timer is actually attached.
    """

    sync = True

    def __init__(self):
        self.totals: Dict[str, list] = {}  # name -> [count, seconds]

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(f"host.{name}"):
                yield
        finally:
            ent = self.totals.setdefault(name, [0, 0.0])
            ent[0] += 1
            ent[1] += time.perf_counter() - t0

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {
            name: {"count": c, "total_s": s, "mean_s": s / max(c, 1)}
            for name, (c, s) in sorted(self.totals.items())
        }


class _NullTimer:
    sync = False
    totals: Dict[str, list] = {}

    def phase(self, name: str):
        return TraceAnnotation(f"host.{name}")

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {}


NULL_TIMER = _NullTimer()


def host_metadata(timestamp: Optional[str] = None) -> Dict[str, object]:
    """Environment stamp for benchmark artifacts (BENCH_rounds.json):
    the context that makes cross-machine perf numbers comparable. The
    timestamp is passed in by the runner (benchmarks/run.py) so library
    code stays clock-free."""
    import os
    import platform
    import sys

    import jax
    import numpy as np

    return {
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": sys.version.split()[0],
        "jax_version": jax.__version__,
        "jax_backend": jax.default_backend(),
        "numpy_version": np.__version__,
        "timestamp": timestamp,
    }
