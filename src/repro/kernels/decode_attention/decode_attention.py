"""Flash-decode — Pallas TPU kernel (FlashDecoding, arXiv:2311.01282 idea
adapted to TPU: the KV cache is split into sequence blocks; partial softmax
statistics accumulate in VMEM scratch across the sequential grid).

This kernel is the single-chip building block of the CONTEXT-PARALLEL decode
path: across chips the cache is sharded over "model"/("data","model") and the
(num, denom) pairs combine with one tiny all-reduce; within a chip this
kernel streams the local S/BS blocks through VMEM.

Grid: (B, H, nS). Valid-length masking comes from the ``pos`` scalar (SMEM).
Block: (BS=256, D) keys/values — 128 KiB per operand at D=128, f32 acc in
scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ipls_aggregate.ipls_aggregate import default_interpret

BS = 256
NEG_INF = float(np.finfo(np.float32).min)


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, ns):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[0]
    block_start = si * BS

    @pl.when(block_start <= pos)
    def _run():
        q = q_ref[0, 0].astype(jnp.float32)          # (1, D) kept 2D
        k = k_ref[0, 0].astype(jnp.float32)          # (BS, D)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                      # (1, BS)
        idx = block_start + jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)
        logits = jnp.where(idx <= pos, logits, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, pos, interpret: bool | None = None):
    """q: (B,H,D); k,v: (B,H,S,D), S % 256 == 0; pos: () int32."""
    if interpret is None:
        interpret = default_interpret()
    B, H, D = q.shape
    S = k.shape[2]
    assert S % BS == 0, (S,)
    ns = S // BS
    scale = 1.0 / np.sqrt(D)
    q4 = q.reshape(B, H, 1, D)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, ns=ns),
        grid=(B, H, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, BS, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, BS, D), lambda b, h, s: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, q4, k, v)
    return out.reshape(B, H, D)
