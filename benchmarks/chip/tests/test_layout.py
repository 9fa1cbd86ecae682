"""BENCHMARK.json and the files the harness finds by name, without a chip."""
import importlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.chip import traffic as gen

ROOT = Path(__file__).resolve().parents[3]
HERE = ROOT / "benchmarks" / "chip"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    assert NAME.fullmatch(cell["name"]) and NAME.fullmatch(cell["traffic"])
    assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200
    configs = {c["name"]: c for c in BENCH["configs"]}
    config = gen.load("configs", cell["config"])
    assert configs[cell["config"]]["file"] == f"benchmarks/chip/configs/{cell['config']}.json"
    assert set(configs[cell["config"]]["reduced"]) == set(config["reduced"])
    gen.load("traffic", cell["traffic"])
    limits = gen.load("limits", cell["name"])
    assert limits and all(v >= 0 for v in limits.values())
    entry = importlib.import_module(f"benchmarks.chip.entries.{config['entry']}")
    assert callable(entry.build) and callable(entry.round_flops)
    reported = [
        m for m in BENCH["end_to_end"] + BENCH["per_layer"]
        if cell["name"] in m.get("workloads", [cell["name"]])
    ]
    assert {"setup_s"} < {m["name"] for m in reported if m in BENCH["end_to_end"]}
    assert any(m in BENCH["per_layer"] for m in reported)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"]
)
def test_metric_has_a_reader(metric):
    assert NAME.fullmatch(metric["name"]) and UNIT.fullmatch(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    mod = importlib.import_module(f"benchmarks.chip.metrics.{metric['name']}")
    assert callable(mod.read)
    if metric in BENCH["per_layer"]:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert metric["layer"] and "\n" not in metric["layer"]
    else:
        assert 0.01 <= metric["bound"] <= 0.25


def test_exits_nonzero_without_a_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cell = BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", cell, "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert '"correct"' not in last


# ------------------------------------------ the mesh entry's layout check ---
@pytest.mark.parametrize("changed", [None, "g1/b1/moe/wg"])
def test_layout_check_takes_an_mla_moe_program(monkeypatch, changed):
    import types

    from benchmarks.chip.entries import mesh_step
    from benchmarks.chip.reference.mesh_step import flatten
    from repro.configs.registry import build_model, get_config

    model = build_model(get_config("deepseek-v2-lite-16b", reduced=True))
    leaves = {k: (tuple(v.shape), 0.02) for k, v in flatten(model.param_shapes()).items()}
    assert model.cfg.groups[0].blocks[0].attn is None  # MLA blocks have no AttnSpec
    if changed:
        shape = leaves[changed][0]
        leaves[changed] = ((shape[0], shape[1] // 4) + shape[2:], 0.02)  # 2 of 8 experts
    # a model reference for this test only, with no check of its own
    mref = types.ModuleType("benchmarks.chip.reference.mla_moe_leaves")
    mref.leaf_shapes = lambda model: leaves
    monkeypatch.setitem(sys.modules, mref.__name__, mref)
    config = {"arch": "deepseek-v2-lite-16b", "reference_model": "mla_moe_leaves", "model": {}}
    if changed:
        with pytest.raises(ValueError, match="parameters"):
            mesh_step._check_layout(model, config)
    else:
        mesh_step._check_layout(model, config)


@pytest.mark.parametrize("changed", [None, "rope_theta", "tied_head"])
def test_internlm2_check(changed):
    import dataclasses

    from benchmarks.chip.entries import mesh_step
    from benchmarks.chip.reference import internlm2
    from repro.configs import internlm2_1_8b
    from repro.configs.registry import build_model

    arch = internlm2_1_8b.reduced()
    config = gen.load("configs", "internlm2-1.8b.data4")
    config["model"].update(
        num_hidden_layers=2, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=128, vocab_size=256, rope_theta=10000.0,
    )
    if changed == "rope_theta":
        config["model"]["rope_theta"] = 1e6
    if changed == "tied_head":
        arch = dataclasses.replace(arch, tie_embeddings=True)
    if changed is None:
        internlm2.check(arch, config["model"])
        mesh_step._check_layout(build_model(arch), config)
        return
    with pytest.raises(ValueError, match="rope or head"):
        internlm2.check(arch, config["model"])
    if changed == "rope_theta":  # a tied head has no lm_head leaf: the leaves differ first
        with pytest.raises(ValueError, match="rope or head"):
            mesh_step._check_layout(build_model(arch), config)
