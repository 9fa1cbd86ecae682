"""Plain reference of InternLM2 (arXiv:2403.17297): pre-norm decoder
layers of grouped-query causal attention with rotary embeddings and a
SwiGLU MLP, a final RMSNorm and an untied output head, in float32. The
configuration's ``reference_model`` names this module; ``mesh_step.py``
drives it.

Departures from the published model, as the program runs it: the norms'
eps is the program's 1e-6 (published 1e-5); the norm weight is stored as
(weight - 1)."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference.lm_ops import F32, head_loss, mm, rms, rope, rope_tables

EMBED, FINAL_NORM, HEAD = "embed/table", "final_norm/scale", "lm_head/table"
LAYER = {
    "attn_norm": "g0/b0/norm/scale", "wq": "g0/b0/attn/wq", "wk": "g0/b0/attn/wk",
    "wv": "g0/b0/attn/wv", "wo": "g0/b0/attn/wo", "mlp_norm": "g0/b1/norm/scale",
    "wg": "g0/b1/mlp/wg", "wu": "g0/b1/mlp/wu", "wd": "g0/b1/mlp/wd",
}


def leaf_shapes(model: dict) -> Dict[str, tuple]:
    """{leaf name: (shape, std)} in the program's parameter layout: the
    layers stacked on a leading axis (group ``g0``), each layer an attention
    block (``b0``) and an MLP block (``b1``) with their pre-norms. A std of
    0 is a norm weight, stored as (weight - 1)."""
    L, d, f = model["num_hidden_layers"], model["hidden_size"], model["intermediate_size"]
    h, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    V = model["vocab_size"]
    return {
        EMBED: ((V, d), 0.02),
        FINAL_NORM: ((d,), 0.0),
        "g0/b0/attn/wk": ((L, d, kv, hd), d ** -0.5),
        "g0/b0/attn/wo": ((L, h, hd, d), (h * hd) ** -0.5),
        "g0/b0/attn/wq": ((L, d, h, hd), d ** -0.5),
        "g0/b0/attn/wv": ((L, d, kv, hd), d ** -0.5),
        "g0/b0/norm/scale": ((L, d), 0.0),
        "g0/b1/mlp/wd": ((L, f, d), f ** -0.5),
        "g0/b1/mlp/wg": ((L, d, f), d ** -0.5),
        "g0/b1/mlp/wu": ((L, d, f), d ** -0.5),
        "g0/b1/norm/scale": ((L, d), 0.0),
        HEAD: ((V, d), 0.02),
    }


def check(arch, model: dict) -> None:
    """The program's ``ArchConfig`` has the configuration's rope theta and
    an untied head, which its leaves do not show."""
    attn = arch.groups[0].blocks[0].attn
    if attn.rope_theta != model["rope_theta"] or arch.tie_embeddings:
        raise ValueError(f"{arch.name}: rope or head differ from the configuration")


def groups(model: dict):
    """[(group prefix, layers in it, {weight: leaf})], in order."""
    return [("g0", model["num_hidden_layers"], LAYER)]


def layer_fwd(group: int, w: dict, x, model: dict, fp8: bool = False):
    """One layer on one row ``x`` (S, d): pre-norm grouped-query causal
    attention and pre-norm SwiGLU, each added to the residual."""
    w = {k: v.astype(F32) for k, v in w.items()}
    eps = model["rms_norm_eps"]
    S = x.shape[0]
    hd = model["head_dim"]
    rep = model["num_attention_heads"] // model["num_key_value_heads"]
    cos, sin = rope_tables(S, hd, model["rope_theta"])
    h = rms(x, w["attn_norm"], eps)
    q = rope(mm("sd,dhk->shk", h, w["wq"], fp8), cos, sin)
    k = rope(mm("sd,dhk->shk", h, w["wk"], fp8), cos, sin)
    v = mm("sd,dhk->shk", h, w["wv"], fp8)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = mm("shk,thk->hst", q, k, fp8) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = mm("hst,thk->shk", p, v, fp8)
    x = x + mm("shk,hkd->sd", o, w["wo"], fp8)
    h = rms(x, w["mlp_norm"], eps)
    a = jax.nn.silu(mm("sd,df->sf", h, w["wg"], fp8)) * mm("sd,df->sf", h, w["wu"], fp8)
    return x + mm("sf,fd->sd", a, w["wd"], fp8)


def loss(fnorm, table, x, tokens, model: dict, fp8: bool = False):
    """Mean next-token cross-entropy of one row."""
    return head_loss(fnorm, table, x, tokens, model["rms_norm_eps"], fp8)
