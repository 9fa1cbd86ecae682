"""Shared benchmark utilities: dataset, eval subsampling, CSV output."""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import List

from repro.data import synth_mnist

# evaluation uses a 2000-sample test subset and samples <=5 agents per round
# (full-set, all-agent eval would dominate single-core runtime without
# changing any relative conclusion)
EVAL_N = 2000


def use_compile_cache() -> str:
    """Place JAX's persistent compile cache; call from entry points only.
    JAX_COMPILATION_CACHE_DIR, when set, is left to JAX and is the only
    cache directory; otherwise the cache is a fixed ``.jax_cache`` at the
    root of this checkout (the path is part of the cache key, so it must
    not move between runs)."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def load_data(num_train=60000, num_test=EVAL_N, seed=0):
    return synth_mnist(num_train=num_train, num_test=num_test, seed=seed)


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


def emit(rows: List[str]) -> None:
    print("name,us_per_call,derived")
    for r in rows:
        print(r)


def save_json(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
