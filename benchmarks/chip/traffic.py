"""The one generator behind every traffic mix: it reads a mix's data file
(``traffic/<name>.json``) and a configuration's file (``configs/<name>.json``)
and makes the inputs of a run from them and the seed.

The data set is fixed by the configuration (``data_seed``), as a real data
set is: the same images in every run, so the programs that hold the test set
compile once per checkout. The run's seed draws everything else: the split of
the samples over the agents, the initial weights, each agent's batches and
every message's fate (the last three inside the program under test, from
``SimConfig.seed``).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
SIDE = 28


def load(kind: str, name: str) -> dict:
    """``configs``, ``traffic`` or ``limits`` file of that name."""
    with open(HERE / kind / f"{name}.json") as f:
        return json.load(f)


def _prototypes(rng: np.random.Generator, num_classes: int) -> np.ndarray:
    """One smooth image per class: four Gaussian strokes, scaled to max 1."""
    protos = np.zeros((num_classes, SIDE, SIDE), np.float32)
    yy, xx = np.mgrid[0:SIDE, 0:SIDE].astype(np.float32)
    for c in range(num_classes):
        img = np.zeros((SIDE, SIDE), np.float32)
        for _ in range(4):
            cx, cy = rng.uniform(4, SIDE - 4, size=2)
            sx, sy = rng.uniform(2.0, 5.0, size=2)
            amp = rng.uniform(0.6, 1.0)
            img += amp * np.exp(-(((xx - cx) / sx) ** 2 + ((yy - cy) / sy) ** 2))
        protos[c] = img / max(img.max(), 1e-6)
    return protos


def mnist_like(num_train: int, num_test: int, num_classes: int, noise: float, seed: int):
    """MNIST-shaped 10-class images: a class prototype, shifted by up to two
    pixels each way (cyclically), scaled by U(0.7, 1.3), plus Gaussian pixel
    noise, clipped to [0, 1]. Returns (x_train, y_train, x_test, y_test),
    images flattened to 784 float32."""
    rng = np.random.default_rng(seed)
    protos = _prototypes(rng, num_classes)
    base = np.arange(SIDE)

    def make(n: int):
        y = rng.integers(0, num_classes, size=n)
        shifts = rng.integers(-2, 3, size=(n, 2))
        rows = (base[None, :] - shifts[:, :1]) % SIDE
        cols = (base[None, :] - shifts[:, 1:]) % SIDE
        x = protos[y[:, None, None], rows[:, :, None], cols[:, None, :]]
        x *= rng.uniform(0.7, 1.3, size=(n, 1, 1)).astype(np.float32)
        x += noise * rng.standard_normal((n, SIDE, SIDE)).astype(np.float32)
        np.clip(x, 0.0, 1.0, out=x)
        return x.reshape(n, SIDE * SIDE).astype(np.float32), y.astype(np.int32)

    x_tr, y_tr = make(num_train)
    x_te, y_te = make(num_test)
    return x_tr, y_tr, x_te, y_te


def iid_shards(x: np.ndarray, y: np.ndarray, num_agents: int, seed: int
               ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """The paper's uniform split: a seeded permutation cut into near-equal
    contiguous shards, one per agent."""
    perm = np.random.default_rng(seed).permutation(len(x))
    return [(x[s], y[s]) for s in np.array_split(perm, num_agents)]


def fleet_inputs(config: dict, seed: int):
    """(shards, x_test, y_test) of a fleet configuration under ``seed``."""
    d = config["data"]
    x_tr, y_tr, x_te, y_te = mnist_like(
        d["num_train"], d["num_test"], d["num_classes"], d["noise"], d["data_seed"]
    )
    return iid_shards(x_tr, y_tr, config["fleet"]["num_agents"], seed), x_te, y_te


# ------------------------------------------------------ token training ---
def markov_tokens(num_sequences: int, seq_len: int, vocab: int, noise: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Token sequences in which each next token is a fixed successor of the
    one before (a seeded table over the vocabulary), or, with probability
    ``noise``, a uniform draw: a language with signal to learn. (The same
    process as ``repro.data.synthetic.synth_tokens``.)"""
    successor = rng.integers(0, vocab, size=vocab)
    toks = np.empty((num_sequences, seq_len), np.int32)
    cur = rng.integers(0, vocab, size=num_sequences)
    for t in range(seq_len):
        toks[:, t] = cur
        jump = rng.random(num_sequences) < noise
        cur = np.where(jump, rng.integers(0, vocab, size=num_sequences), successor[cur])
    return toks


def round_batch(traffic: dict, num_agents: int, vocab: int, seed: int, rnd: int) -> dict:
    """The global batch of round ``rnd``: ``sequences_per_agent`` rows per
    agent, agent-major (the rows of agent a are a*spa .. (a+1)*spa - 1), and
    a participation mask over the rows. Each agent sits the round out with
    probability ``absent_prob``, independently; a round in which none is
    present is drawn again. Every round has the same sizes, whatever the
    seed."""
    spa = traffic["sequences_per_agent"]
    rng = np.random.default_rng([seed, rnd])
    present = np.zeros(num_agents, bool)
    while not present.any():
        present = rng.random(num_agents) >= traffic["absent_prob"]
    toks = markov_tokens(num_agents * spa, traffic["seq_len"], vocab, traffic["token_noise"], rng)
    return {
        "tokens": toks,
        "participation": np.repeat(present, spa).astype(np.float32),
    }
