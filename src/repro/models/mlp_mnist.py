"""The paper's evaluation model: a 4-layer MLP, 785x500x100x10.

(785 = 784 pixels + bias, i.e. standard 784-in layers with biases.) Pure jax;
parameters flatten deterministically (sorted dict order) for the IPLS
partition plane.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LAYERS = [(784, 500), (500, 100), (100, 10)]


def init_params(seed: int = 0) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    params: Dict[str, np.ndarray] = {}
    for i, (fan_in, fan_out) in enumerate(LAYERS):
        bound = float(np.sqrt(6.0 / (fan_in + fan_out)))
        params[f"w{i}"] = rng.uniform(-bound, bound, size=(fan_in, fan_out)).astype(np.float32)
        params[f"b{i}"] = np.zeros((fan_out,), np.float32)
    return params


def apply(params, x: jax.Array) -> jax.Array:
    h = x
    n = len(LAYERS)
    for i in range(n):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def loss_and_acc(params, x: jax.Array, y: jax.Array) -> Tuple[jax.Array, jax.Array]:
    logits = apply(params, x)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
    acc = (jnp.argmax(logits, axis=1) == y).mean()
    return nll, acc


from functools import partial


@partial(jax.jit, static_argnums=(4,))
def sgd_steps(params, x, y, lr: float, num_iters: int):
    """Run ``num_iters`` SGD iterations on one (already-batched) shard chunk.
    The paper's local optimisation phase: plain SGD on local data."""

    def body(p, _):
        grads = jax.grad(lambda q: loss_and_acc(q, x, y)[0])(p)
        p = jax.tree.map(lambda w, g: w - lr * g, p, grads)
        return p, None

    params, _ = jax.lax.scan(body, params, None, length=num_iters)
    return params


@partial(jax.jit, static_argnums=(4, 5))
def sgd_steps_flat(w_flat, x, y, lr: float, num_iters: int, layout):
    """`sgd_steps` on the FLAT weight vector: unflatten once, carry the
    parameter tree through the `num_iters` steps, flatten once in `layout`
    order. The flat<->tree re-layouts are paid once a call, not once a step,
    and the result is bit-identical to the tree scan. The vectorized round
    engine vmaps this over all agents; `layout` is the (hashable) flatten
    layout from `core.partition.flatten_params`."""
    from repro.core.partition import unflatten_params

    params = sgd_steps(unflatten_params(w_flat, layout), x, y, lr, num_iters)
    return _flatten_in_layout_order(params, layout)


def _flatten_in_layout_order(params, layout) -> jax.Array:
    """Traced inverse of `unflatten_params`: the leaves named by `layout`,
    raveled and concatenated in its order."""

    def leaf(name):
        node = params
        for part in name.split("/"):
            node = node[part]
        return node.reshape(-1)

    return jnp.concatenate([leaf(name) for name, _ in layout])


@jax.jit
def evaluate(params, x, y) -> jax.Array:
    return loss_and_acc(params, x, y)[1]
