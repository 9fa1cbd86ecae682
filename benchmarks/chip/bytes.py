"""HBM bytes that the aggregation of a round requires, from the cell's
shapes: for each (partition, holder) instance, the holder's own delta read
once (float32, it never crossed the wire), each delivered remote delta read
once at the wire's width with its scales, the base value read once and the
result written once. Padding, masked-out slots and gathered copies are not
work the aggregation requires, so they are not counted."""
from __future__ import annotations

from benchmarks.chip.reference import ipls


def partition_sizes(n: int, k: int):
    base, rem = divmod(n, k)
    return [base + (1 if i < rem else 0) for i in range(k)]


def delivered_updates(config: dict, traffic: dict, seed: int, rounds) -> list:
    """Per partition, the UpdateModel deltas delivered to a holder over
    ``rounds`` (a message delayed past its round is counted in the round it
    was sent)."""
    fleet, net = config["fleet"], traffic["network"]
    A, K = fleet["num_agents"], fleet["num_partitions"]
    _, holders = ipls.assign_partitions(A, K, fleet["pi"], fleet["rho"])
    out = [0] * K
    lossless = net["loss_prob"] <= 0
    for k in range(K):
        remote = [a for a in range(A) if a not in holders[k]]
        if lossless:
            out[k] = len(remote) * len(rounds)
            continue
        for r in rounds:
            for a in remote:
                ok, _ = ipls.message_fate(net, seed, ipls.CH_UPDATE, r, a, k, 0)
                out[k] += ok
    return out


def fleet_agg_bytes(config: dict, traffic: dict, seed: int, rounds) -> float:
    """Total over ``rounds`` of the bytes the aggregation requires."""
    fleet = config["fleet"]
    n = sum(a * b + b for a, b in zip(fleet["layers"][:-1], fleet["layers"][1:]))
    sizes = partition_sizes(n, fleet["num_partitions"])
    _, holders = ipls.assign_partitions(
        fleet["num_agents"], fleet["num_partitions"], fleet["pi"], fleet["rho"]
    )
    remote = delivered_updates(config, traffic, seed, rounds)
    wire = fleet["wire_dtype"]
    total = 0.0
    for k, s in enumerate(sizes):
        per_holder = 3 * 4 * s  # own delta, base, result
        total += len(holders[k]) * per_holder * len(rounds) + remote[k] * ipls.wire_bytes(s, wire)
    return total
