"""The numbers that decide ``correct`` for a fleet cell: what the program
produced after its first window against the plain reference after the same
rounds from the same seed.

  change_gap   worst leaf (w0, b0, ...) of the gap between the norms of the
               parameters' change over the window, program vs reference,
               over all agents' models, as a share of the reference's norm
               of that leaf or of the median leaf, whichever is larger.
               Leaves whose reference change is under a thousandth of the
               median leaf's are left out (none are at these sizes).
  loss_gap     gap of the mean test-set cross-entropy of all agents' models,
               as a share of the reference's.
  traffic_gap  largest absolute gap of bytes sent, messages sent and
               messages dropped since the fleet formed: exact, limit 0.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import ipls


def leaf_change_norms(models: np.ndarray, w0: np.ndarray, layers) -> dict:
    d = models - w0[None, :]
    return {
        name: float(np.linalg.norm(d[:, sl].astype(np.float64)))
        for name, sl in ipls.leaf_slices(layers).items()
    }


def mean_test_nll(models: np.ndarray, layers, x_te, y_te) -> float:
    _, nll = ipls.eval_rows(ipls.make_eval(layers), models, jnp.asarray(x_te), jnp.asarray(y_te))
    return float(np.mean(nll))


def fleet_numbers(prog: dict, ref: dict, x_te, y_te) -> dict:
    layers = ref["layers"]
    n_ref = leaf_change_norms(ref["models"], ref["w0"], layers)
    n_prog = leaf_change_norms(prog["models"], ref["w0"], layers)
    median = float(np.median(list(n_ref.values())))
    change_gap = max(
        abs(n_prog[k] - n_ref[k]) / max(n_ref[k], median)
        for k in n_ref
        if n_ref[k] >= 1e-3 * median
    )
    l_ref = mean_test_nll(ref["models"], layers, x_te, y_te)
    l_prog = mean_test_nll(prog["models"], layers, x_te, y_te)
    traffic_gap = max(
        abs(int(prog[k]) - int(ref[k]))
        for k in ("bytes_total", "messages_sent", "messages_dropped")
    )
    return {
        "change_gap": change_gap,
        "loss_gap": abs(l_prog - l_ref) / l_ref,
        "traffic_gap": float(traffic_gap),
    }
