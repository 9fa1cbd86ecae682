"""jit'd wrapper for flash-decode (GQA repeat handled here)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_ref


def decode(q, k, v, pos, use_kernel: bool = True, interpret: bool | None = None):
    """q: (B,H,D); k,v: (B,KV,S,D)."""
    H, KV = q.shape[1], k.shape[1]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if use_kernel:
        return decode_attention(q, k, v, pos, interpret=interpret)
    return decode_ref(q, k, v, pos)
