"""Observability for the IPLS reproduction: structured per-round metrics,
protocol event traces, per-phase wall timing, and host spans in the JAX
profiler's trace.

Every engine phase opens a ``jax.profiler.TraceAnnotation`` named
``host.<phase>`` (``run_window``, ``fate_draw``, ``control``, ``batches``,
``stage``, ``device_window``, ``wait``, ``account``; see docs/TELEMETRY.md),
telemetry on or off; take a trace with ``jax.profiler.trace(dir,
create_perfetto_trace=True)``. The round programs name their device
sub-layers with ``jax.named_scope`` (``weight_build``, ``contrib_gather``,
``wire_codec``, ``ring_roll``, ``replica_merge``). When no trace is taken a
span costs one TraceMe check. Telemetry disabled otherwise costs nothing:
engines hold ``NULL_TIMER`` and skip every tap, and the jitted programs are
unchanged (no extra outputs in the jaxpr).
"""
from repro.telemetry.recorder import MetricsRecorder
from repro.telemetry.schema import (
    CHANNELS,
    FINISH_KEYS,
    ROW_KEYS,
    SCHEMA_VERSION,
    TELEMETRY_SCHEMA,
)
from repro.telemetry.timing import NULL_TIMER, PhaseTimer, host_metadata
from repro.telemetry.trace import TraceWriter

__all__ = [
    "MetricsRecorder",
    "TraceWriter",
    "PhaseTimer",
    "NULL_TIMER",
    "host_metadata",
    "SCHEMA_VERSION",
    "CHANNELS",
    "FINISH_KEYS",
    "ROW_KEYS",
    "TELEMETRY_SCHEMA",
]
