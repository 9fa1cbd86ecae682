"""A fleet small enough for the CPU, run through the harness's own code."""
import copy

import pytest

from benchmarks.chip import traffic as gen


def small_spec(config: str, traffic: str, cell: str, **traffic_kw) -> dict:
    """The cell's files, with the fleet and data cut to 4 agents and a few
    thousand samples (every width of the MLP kept)."""
    c = copy.deepcopy(gen.load("configs", config))
    t = copy.deepcopy(gen.load("traffic", traffic))
    c["fleet"].update(num_agents=4, num_partitions=4)
    c["data"].update(num_train=2000, num_test=500)
    t.update(batch_size=64, scan_rounds=4, eval_cadence=4, eval_agents=2)
    t.update(traffic_kw)
    return {
        "cell": {"name": cell, "config": config, "traffic": traffic, "chips": 1},
        "config": c, "traffic": t, "limits": gen.load("limits", cell),
        "end_to_end": [{"name": "rounds_per_s", "unit": "rounds/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }


@pytest.fixture
def perfect_spec():
    return small_spec("mnist-mlp.a100-f32", "perfect", "mnist-mlp.a100-f32.perfect")


@pytest.fixture
def lossy_spec():
    return small_spec("mnist-mlp.a32-int8", "lossy", "mnist-mlp.a32-int8.lossy")
