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
