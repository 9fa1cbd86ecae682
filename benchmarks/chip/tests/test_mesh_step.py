"""The mesh cell driven through the harness's own ``run`` on four virtual CPU
devices at a small size, each group of cases in a process of its own
(_mesh_cpu.py), against limits set for that size: a sound run is correct;
the program with each fault planted is not; the control (the reference with
float8 matrix products, in the program's place) fails at least one
limit."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]

GROUPS = {
    "sound": ["sound"],
    "control": ["control_fp8"],
    "state_and_batch": ["state_unchanged", "half_batch"],
    "exchange_and_absent": ["no_exchange", "absent_counted"],
    "eps_and_lr": ["eps_off", "lr_halved"],
    "layer": ["layer_dropped"],
}


def _run(cases):
    proc = subprocess.run(
        [sys.executable, str(HERE / "_mesh_cpu.py"), *cases],
        cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    return {d["case"]: d for d in lines}


@pytest.mark.parametrize("group", GROUPS)
def test_mesh_cell_cases(group):
    got = _run(GROUPS[group])
    assert set(got) == set(GROUPS[group])
    for case, d in got.items():
        over = [k for k, c in d["compared"].items() if c["value"] > c["limit"]]
        if case == "sound":
            assert d["correct"] and not over, d["compared"]
            assert set(d["metrics"]) == {"rounds_per_s", "setup_s"}
            assert d["counts"]["rounds"] > 0 and d["counts"]["compiles"] == 0
        else:
            assert not d["correct"] and over, (case, d["compared"])
