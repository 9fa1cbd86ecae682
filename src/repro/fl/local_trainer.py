"""Per-agent local optimisation (the paper's M.fit(d_i, SGD) line).

Wraps the MNIST MLP trainer in the flatten/unflatten plumbing that the IPLS
partition plane works over: the trainer takes and returns FLAT weight vectors.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import numpy as np

from repro.core.partition import flatten_params, unflatten_params
from repro.models import mlp_mnist


@dataclasses.dataclass
class LocalTrainer:
    agent_id: int
    x: np.ndarray
    y: np.ndarray
    lr: float = 0.1
    local_iters: int = 10
    batch_size: int = 128
    seed: int = 0

    def __post_init__(self):
        self._layout = None
        self._rng = np.random.default_rng(self.seed + 1000 * (self.agent_id + 1))

    def layout(self):
        if self._layout is None:
            _, self._layout = flatten_params(mlp_mnist.init_params(0))
        return self._layout

    def draw_indices(self) -> np.ndarray:
        """Advance this agent's private RNG stream by one round's batch
        selection and return the chosen rows of its shard. The single source
        of truth for the per-round data order — the vectorized engine draws
        through this same method (and gathers the rows on the device), which
        is what keeps the two engines' SGD inputs identical."""
        bs = min(self.batch_size, len(self.x))
        return self._rng.choice(len(self.x), size=bs, replace=False)

    def draw_batch(self) -> Tuple[np.ndarray, np.ndarray]:
        """One round's batch: the rows `draw_indices` selects."""
        sel = self.draw_indices()
        return self.x[sel], self.y[sel]

    def train_delta(self, w_flat: np.ndarray) -> np.ndarray:
        """Run local SGD from w_flat; return delta = w_before - w_after
        (the paper's convention: holders apply w <- w - eps*delta)."""
        params = unflatten_params(w_flat.astype(np.float32), self.layout())
        xb, yb = self.draw_batch()
        new_params = mlp_mnist.sgd_steps(
            jax.tree.map(np.asarray, params),
            xb,
            yb,
            self.lr,
            self.local_iters,
        )
        new_flat, _ = flatten_params(jax.tree.map(np.asarray, new_params))
        return w_flat - new_flat

    def evaluate(self, w_flat: np.ndarray, x: np.ndarray, y: np.ndarray) -> float:
        params = unflatten_params(w_flat.astype(np.float32), self.layout())
        return float(mlp_mnist.evaluate(jax.tree.map(np.asarray, params), x, y))
