"""FlashAttention forward — Pallas TPU kernel (arXiv:2205.14135, adapted to
the TPU memory hierarchy: HBM -> VMEM tiles sized for the MXU, sequential
grid accumulation instead of warp-level parallelism).

Grid: (B*H, nQ, nK) — TPU executes the grid sequentially per core, so the
running-softmax state (m, l, acc) lives in VMEM scratch that persists across
the innermost K dimension. Causal blocks above the diagonal are skipped with
pl.when (no MXU work issued).

Block sizes: BQ=BK=128 (MXU-aligned); head_dim passes through whole (<=256).
VMEM working set: q(128xD) + k,v(128xD) + acc(128xD) f32 + logits(128x128)
~= 0.5 MiB at D=128 — far under the 16 MiB budget, leaving room for the
compiler's double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ipls_aggregate.ipls_aggregate import default_interpret

BQ = 128
BK = 128
NEG_INF = float(np.finfo(np.float32).min)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, causal, scale, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    should_run = True
    if causal:
        should_run = ki * BK <= qi * BQ + BQ - 1  # any overlap with lower tri

    @pl.when(should_run)
    def _run():
        q = q_ref[0].astype(jnp.float32)            # (BQ, D)
        k = k_ref[0].astype(jnp.float32)            # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # (BQ, BK)
        if causal:
            rows = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
            cols = ki * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
            logits = jnp.where(rows >= cols, logits, NEG_INF)
        m_prev = m_scr[...]                           # (BQ, 1)
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)                   # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)               # (BQ, 1)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, causal: bool = True, interpret: bool | None = None):
    """q,k,v: (B, H, S, D) with S % 128 == 0. Returns (B, H, S, D)."""
    if interpret is None:
        interpret = default_interpret()
    B, H, S, D = q.shape
    assert S % BQ == 0 and S % BK == 0, (S,)
    scale = 1.0 / np.sqrt(D)
    nq, nk = S // BQ, S // BK
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)

    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=scale, nk=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
