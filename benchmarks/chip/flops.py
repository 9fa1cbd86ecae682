"""Model FLOPs of one round of a fleet, from the cell's shapes: what the
algorithm requires, counting matrix products (2 per multiply-add) and no
recomputation."""
from __future__ import annotations


def mlp_forward_flops(layers) -> int:
    """One sample through the MLP ``layers`` = [784, 500, 100, 10]."""
    return sum(2 * fi * fo for fi, fo in zip(layers[:-1], layers[1:]))


def mlp_train_flops(layers) -> int:
    """One sample's forward and backward pass (backward = 2x forward)."""
    return 3 * mlp_forward_flops(layers)


def fleet_round_flops(config: dict, traffic: dict) -> float:
    """Every agent's local SGD (``local_iters`` steps on a batch) plus the
    evaluation of ``eval_agents`` models on the test set every
    ``eval_cadence`` rounds, as a mean per round."""
    fleet, data = config["fleet"], config["data"]
    A, layers = fleet["num_agents"], fleet["layers"]
    shard = data["num_train"] // A
    batch = min(traffic["batch_size"], shard)
    sgd = A * traffic["local_iters"] * batch * mlp_train_flops(layers)
    evaluated = traffic["eval_agents"] or A
    evals = evaluated * data["num_test"] * mlp_forward_flops(layers)
    return sgd + evals / traffic["eval_cadence"]


# ------------------------------------------------------ language models ---
# Forward FLOPs of one sequence through a decoder-only LM, from its
# ``ArchConfig`` (``repro.models.transformer``): its groups of blocks, each
# repeated. Embedding lookups are gathers and count nothing; norms, rotary
# embeddings, softmax and activations are elementwise and count nothing.


def causal_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs a causal attention over ``seq_len`` positions
    scores: position i sees min(i + 1, window) keys."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    w = window
    return w * (w + 1) // 2 + (seq_len - w) * w


def attn_forward_flops(s, seq_len: int) -> int:
    """GQA attention (``AttnSpec``): q, k, v and output projections per
    token, and QK^T and PV per causal pair."""
    d, h, kv, hd = s.d_model, s.n_heads, s.kv_heads, s.head_dim
    proj = 2 * d * (h * hd + 2 * kv * hd) + 2 * h * hd * d
    return seq_len * proj + causal_pairs(seq_len, s.window) * 2 * h * (hd + hd)


def mla_forward_flops(s, seq_len: int) -> int:
    """Multi-head latent attention (``MLASpec``, training form): the query,
    the latent down-projection and shared rope key, the up-projections of
    keys and values from the latent, the output projection; scores over
    nope + rope dimensions and values of ``v_head`` per causal pair."""
    d, h = s.d_model, s.n_heads
    qk = s.qk_nope + s.qk_rope
    proj = (
        2 * d * h * qk + 2 * d * s.kv_lora + 2 * d * s.qk_rope
        + 2 * s.kv_lora * h * (s.qk_nope + s.v_head) + 2 * h * s.v_head * d
    )
    return seq_len * proj + causal_pairs(seq_len) * 2 * h * (qk + s.v_head)


def ffn_forward_flops(s, seq_len: int) -> int:
    """A (gated) MLP (``MLPSpec``): two or three d x d_ff products."""
    mats = 3 if s.gated else 2
    return seq_len * mats * 2 * s.d_model * s.d_ff


def moe_forward_flops(s, seq_len: int) -> int:
    """A routed MoE (``MoESpec``): the router over all experts, ``top_k``
    gated experts of width ``d_expert`` per token, and the shared experts
    (one gated MLP of total width ``d_shared``). This is the work the model
    requires, whatever share of the experts a chip holds: ``mfu`` divides
    it by every chip's peak. Capacity padding and dropped tokens are not
    work the model requires."""
    d = s.d_model
    per_token = 2 * d * s.num_experts + s.top_k * 3 * 2 * d * s.d_expert
    if s.num_shared > 0:
        per_token += 3 * 2 * d * s.d_shared
    return seq_len * per_token


BLOCK_FLOPS = {
    "attn": lambda b, n: attn_forward_flops(b.attn, n),
    "mla": lambda b, n: mla_forward_flops(b.mla, n),
    "mlp": lambda b, n: ffn_forward_flops(b.mlp, n),
    "moe": lambda b, n: moe_forward_flops(b.moe, n),
}


def lm_forward_flops(arch, seq_len: int) -> int:
    """One sequence of ``seq_len`` tokens through every block, and the
    output head over the ``seq_len - 1`` positions that have a next token
    (the loss's logits)."""
    total = 0
    for g in arch.groups:
        for b in tuple(g.blocks) + tuple(g.shared):
            if b.kind not in BLOCK_FLOPS:
                raise ValueError(f"no FLOP count for block kind {b.kind!r}")
            total += g.repeat * BLOCK_FLOPS[b.kind](b, seq_len)
    return total + (seq_len - 1) * 2 * arch.d_model * arch.vocab


def lm_train_flops(arch, seq_len: int) -> int:
    """Forward and backward (2x forward) of one sequence."""
    return 3 * lm_forward_flops(arch, seq_len)
