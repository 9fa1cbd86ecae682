"""The benchmark-wide metrics take a round's model FLOPs from the cell's
entry: any configuration goes through them, and the fleet cells' ``mfu`` is
what it was, to the bit."""
import gzip
import json
import sys
import types
from pathlib import Path

import pytest

from benchmarks.chip import common, flops, trace
from benchmarks.chip import run as harness
from benchmarks.chip import traffic as gen
from benchmarks.chip.metrics import mfu

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
BENCH_WIDE = [
    {"name": "rounds_per_s", "unit": "rounds/s"}, {"name": "setup_s", "unit": "s"},
    {"name": "device_idle_share", "unit": "%"}, {"name": "mfu", "unit": "%"},
]


def _recorded():
    with gzip.open(TESTDATA / "fleet_trace.json.gz", "rt") as f:
        return json.load(f)


def test_any_entry_goes_through_the_benchmark_wide_metrics(monkeypatch):
    stub = types.ModuleType("benchmarks.chip.entries._stub")
    stub.round_flops = lambda config, traffic: 1e12 * traffic["rows"]
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    ctx = types.SimpleNamespace(
        config={"entry": "_stub"}, traffic={"rows": 3}, chips=4, setup_s=12.5,
        counts={"rounds": 10, "elapsed_s": 5.0},
        trace_summary={"busy_s": 4.0, "window_s": 5.0},
        peaks={"bf16_flops_per_s": 197e12},
    )
    got = harness.read_metrics(BENCH_WIDE, ctx)
    assert got["rounds_per_s"]["value"] == 2.0
    assert got["setup_s"]["value"] == 12.5
    assert got["device_idle_share"]["value"] == pytest.approx(20.0)
    assert got["mfu"]["value"] == pytest.approx(100 * 3e13 / 5.0 / (4 * 197e12))


@pytest.mark.parametrize("config,mix", [
    ("mnist-mlp.a100-f32", "perfect"), ("mnist-mlp.a32-int8", "lossy"),
])
def test_fleet_mfu_unchanged_to_the_bit(config, mix):
    tr = _recorded()
    s = trace.summary(tr)
    peaks = common.peaks("TPU v5 lite")
    ctx = types.SimpleNamespace(
        config=gen.load("configs", config), traffic=gen.load("traffic", mix),
        counts={"rounds": tr["rounds"]}, chips=1, trace_summary=s, peaks=peaks,
    )
    # the expression mfu.py evaluated before it asked the entry
    done = flops.fleet_round_flops(ctx.config, ctx.traffic) * ctx.counts["rounds"]
    before = 100.0 * done / s["window_s"] / (ctx.chips * peaks["bf16_flops_per_s"])
    assert mfu.read(ctx) == before
