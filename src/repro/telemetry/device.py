"""Device-side metric math shared by all engines.

The fused engines inline ``metric_pair`` into their window program as an
auxiliary output, while the scalar engine calls the standalone jitted
``host_normsq`` on bitwise-identical planes. The math is the same, but the
float32 reduction runs in two different XLA programs, and no compiler
promises them the same summation order (on the CPU they have been seen one
ULP apart). So the equivalence contract holds these two metrics to 2 ULP,
not to the bit (tests/test_telemetry.py); every other column of the
metric stream is byte-identical across engines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def normsq(x):
    """Sum of squares, f32 in / f32 scalar out. Pure — safe to inline into
    any jitted engine body."""
    return jnp.sum(x * x)


def metric_pair(delta_plane, value_plane):
    """The per-round (delta_normsq, value_normsq) auxiliary output of the
    fused engines, as one (2,) f32 vector."""
    return jnp.stack([normsq(delta_plane), normsq(value_plane)])


_normsq_j = jax.jit(normsq)


def host_normsq(x: np.ndarray) -> float:
    """Scalar-engine entry point: the same jitted reduction, value pulled
    back to a python float (exact f32 round-trip)."""
    return float(np.asarray(_normsq_j(jnp.asarray(x, jnp.float32))))
