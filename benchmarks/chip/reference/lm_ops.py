"""Plain float32 pieces of a decoder-only language model, for the model
references of the mesh cells: matrix products (optionally with float8
inputs, the precision control), RMSNorm, rotary embeddings, and the
next-token loss of one row through a final norm and an untied head."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_CHUNK = 1024  # positions per block of the output head's logits


def q8(t):
    """Forward in float8 e4m3 with one scale per tensor, gradient straight
    through."""
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
    q = (t / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return t + jax.lax.stop_gradient(q - t)


def mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = q8(a), q8(b)
    return jnp.einsum(spec, a, b)


def rms(x, scale, eps):
    """RMSNorm with the weight stored as (weight - 1), as the program stores
    it."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope_tables(seq_len: int, dim: int, theta: float):
    """cos and sin of each position's angles, computed in float64."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(seq_len, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def rope(x, cos, sin):
    """x (S, H, dim): the two halves of each head rotated by position."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def head_loss(fnorm, table, x, tokens, eps: float, fp8: bool = False):
    """Mean next-token cross-entropy of one row: the final norm, the head
    over positions 0..S-2, the targets tokens 1..S-1; the head's logits in
    blocks of ``HEAD_CHUNK`` positions, each recomputed in the backward."""
    h = rms(x[:-1], fnorm.astype(F32), eps)
    tgt = tokens[1:]
    n = h.shape[0]
    c = min(HEAD_CHUNK, n)
    blocks = -(-n // c)
    pad = blocks * c - n
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(blocks, c, -1)
    tb = jnp.pad(tgt, (0, pad)).reshape(blocks, c)
    valid = (jnp.arange(blocks * c) < n).reshape(blocks, c)
    table = table.astype(F32)

    @jax.checkpoint
    def block(hc, tc, vc):
        logits = mm("cd,vd->cv", hc, table, fp8)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vc, lse - picked, 0.0))

    def body(acc, xs):
        return acc + block(*xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32), (hb, tb, valid))
    return total / n
