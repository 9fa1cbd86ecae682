"""Chunked RWKV6/GLA linear recurrence — Pallas TPU kernel.

The XLA reference path (models/ssm.py) materializes the (Q,Q,K) pairwise
decay tensor per chunk in HBM; this kernel keeps it entirely in VMEM and
carries the (K,V) state in scratch across the sequential chunk grid — one
HBM read of r/k/v/logw and one write of the output per token, which is the
bandwidth lower bound for this operator.

Grid: (B*H, nChunks) sequential. Per-step VMEM: 4 x (Q,K) operands + (Q,Q)
pair buffer per lane-group + (K,V) f32 state ≈ 1.5 MiB at Q=64, K=V=64.

Adaptation note (DESIGN.md §2): the CUDA RWKV kernels parallelize over
(B,H) thread blocks with warp-level time recursion; on TPU the MXU wants
matmul form, so we use the chunked GLA formulation (intra-chunk pairwise +
inter-chunk state carry) — same math, MXU-shaped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ipls_aggregate.ipls_aggregate import default_interpret

Q = 64  # chunk length


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sfin_ref, s_scr, *, nc):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)    # (Q,K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)    # (1,K) broadcast row
    S = s_scr[...]                       # (K,V)

    L = jnp.cumsum(lw, axis=0)          # inclusive
    Lx = L - lw                          # exclusive

    # intra-chunk: pairwise per-channel decay (j < i), contracted over K
    diff = Lx[:, None, :] - L[None, :, :]              # (Q,Q,K)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    strict = (ii > jj)[..., None]
    w_pair = jnp.where(strict, jnp.exp(diff), 0.0)     # (Q,Q,K)
    att = jnp.einsum("ik,ijk,jk->ij", r, w_pair, k)    # (Q,Q)
    y = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # bonus diagonal
    bon = jnp.sum(r * u * k, axis=1, keepdims=True)    # (Q,1)
    y = y + bon * v
    # carried state
    y = y + jax.lax.dot_general(r * jnp.exp(Lx), S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)

    # state update
    last = L[-1:, :]                                    # (1,K)
    S_new = S * jnp.exp(last).T + jax.lax.dot_general(
        (k * jnp.exp(last - L)), v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s_scr[...] = S_new

    @pl.when(ci == nc - 1)
    def _emit():
        sfin_ref[0] = S_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv6_scan(r, k, v, logw, u, interpret: bool | None = None):
    """r,k,v,logw: (B,T,H,K) with T % 64 == 0; u: (H,K).
    Returns (out (B,T,H,K), final_state (B,H,K,V))."""
    if interpret is None:
        interpret = default_interpret()
    B, T, H, K = r.shape
    V = v.shape[-1]
    assert T % Q == 0, (T,)
    nc = T // Q

    def fold(a):  # (B,T,H,Kv) -> (B*H, T, Kv)
        return jnp.moveaxis(a, 2, 1).reshape(B * H, T, a.shape[-1])

    rf, kf, vf, lwf = fold(r), fold(k), fold(v), fold(logw)
    uf = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)

    out, sfin = pl.pallas_call(
        functools.partial(_kernel, nc=nc),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, K, V), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, V), r.dtype),
            jax.ShapeDtypeStruct((B * H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, lwf, uf)
    out = jnp.moveaxis(out.reshape(B, H, T, V), 1, 2)
    return out, sfin.reshape(B, H, K, V)
