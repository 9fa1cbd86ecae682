"""Pure-jnp oracle for the fused IPLS aggregation kernel.

Semantics (shared with the kernel and the scalar engine):
``w - eps * masked_SUM(deltas)`` — the 1/r normalization lives in the eps
recursion, never in the reduction, so the update is bitwise comparable
across engines (a mean inside undone by ``eps*r`` outside is not f32-
invertible). Empty masks leave w unchanged (eps * 0 == 0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ipls_aggregate_ref(
    w: jax.Array,        # (N,) current partition value
    deltas: jax.Array,   # (R, N) one delta per (potential) contributor
    mask: jax.Array,     # (R,) 1.0 where the contribution arrived
    eps: jax.Array,      # () staleness weight
) -> jax.Array:
    """w - eps * masked_sum(deltas); empty mask leaves w unchanged."""
    mask = mask.astype(jnp.float32)
    agg = jnp.einsum(
        "r,rn->n", mask, deltas.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    return (w.astype(jnp.float32) - eps.astype(jnp.float32) * agg).astype(w.dtype)


def ipls_aggregate_batched_ref(
    w: jax.Array,        # (K, N) partition values
    deltas: jax.Array,   # (K, R, N) deltas per partition per contributor slot
    mask: jax.Array,     # (K, R) 1.0 where the contribution arrived
    eps: jax.Array,      # (K,) staleness weight per partition
) -> jax.Array:
    """Per-partition ``w - eps * masked_sum(deltas)``; all-zero mask rows
    (zero-contributor rounds, possible under lossy networks) leave their
    partition unchanged. R is whatever the round's contributor table needs —
    the kernel pads it to R_TILE chunks, the oracle takes it as-is."""
    mask = mask.astype(jnp.float32)
    agg = jnp.einsum(
        "kr,krn->kn", mask, deltas.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    return (w.astype(jnp.float32) - eps.astype(jnp.float32)[:, None] * agg).astype(w.dtype)


def ipls_aggregate_batched_q_ref(
    w: jax.Array,         # (K, N) partition values
    own: jax.Array,       # (K, N) the holder's own (never-quantized) delta
    q: jax.Array,         # (K, R, N) int8 wire codes of remote deltas
    scales: jax.Array,    # (K, R, ceil(N/QBLOCK)) f32 per-block pow2 scales
    mask: jax.Array,      # (K, R) 1.0 where the remote contribution arrived
    own_mask: jax.Array,  # (K,) 1.0 where the holder's own delta participates
    eps: jax.Array,       # (K,) staleness weight per partition
    qblock: int = 1024,
) -> jax.Array:
    """Quantized-input oracle: dequantize (q * scale — exact, scales are
    powers of two or 0) then the same masked-sum update, the raw own-delta
    summed first."""
    K, R, N = q.shape
    nb = scales.shape[2]
    pad = nb * qblock - N
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad))).reshape(K, R, nb, qblock)
    deq = qb.astype(jnp.float32) * scales[..., None]
    deq = deq.reshape(K, R, nb * qblock)[..., :N]
    mask = mask.astype(jnp.float32)
    own_mask = own_mask.astype(jnp.float32)
    agg = own_mask[:, None] * own.astype(jnp.float32) + jnp.einsum(
        "kr,krn->kn", mask, deq, precision=jax.lax.Precision.HIGHEST
    )
    return (w.astype(jnp.float32) - eps.astype(jnp.float32)[:, None] * agg).astype(w.dtype)
