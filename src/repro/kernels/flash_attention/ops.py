"""jit'd wrapper: GQA-aware flash attention entry point."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import mha_ref


def attention(q, k, v, causal: bool = True, use_kernel: bool = True, interpret: bool | None = None):
    """q: (B,H,S,D); k,v: (B,KV,S,D) with H % KV == 0 (repeated here)."""
    H, KV = q.shape[1], k.shape[1]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if use_kernel:
        return flash_attention(q, k, v, causal=causal, interpret=interpret)
    return mha_ref(q, k, v, causal=causal)
