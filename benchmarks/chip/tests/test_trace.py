"""The reduction from a trace to per-layer numbers, on a hand-made trace and
on a small trace recorded on a TPU v5e."""
import gzip
import json
import types
from pathlib import Path

import pytest

from benchmarks.chip import trace
from benchmarks.chip.metrics import _fleet_layers, device_idle_share

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
MS = 1e6  # ns


def _hand_made():
    dev = [
        [0 * MS, 2 * MS, "fusion.1", "jit(scan_window)/while/body/jit(sgd_steps_flat)/dot"],
        [1 * MS, 1.5 * MS, "gather.2", "jit(scan_window)/while/body/gather"],  # overlaps sgd
        [4 * MS, 1 * MS, "custom-call.3", "jit(scan_window)/jit(ipls_aggregate_batched)/pallas_call"],
        [6 * MS, 1 * MS, "fusion.4", "jit(scan_window)/while/body/jit(evaluate)/dot"],
        [9 * MS, 2 * MS, "copy.5", "jit(scan_window)/while/body/copy"],  # runs past the window
    ]
    host = [
        [0, 10 * MS, "bench.measure"],
        [0.5 * MS, 8.5 * MS, "bench.window"],
        [5 * MS, 1 * MS, "host.control_round"],
        [7 * MS, 2 * MS, "host.draw_batches"],
    ]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_busy_union_and_idle_share():
    tr = _hand_made()
    t0, t1 = trace.window(tr)
    evs = tr["devices"]["/device:TPU:0"]
    assert trace.union([(0, 2), (1, 3), (4, 5)]) == [(0, 3), (4, 5)]
    assert trace.busy_ns(evs, t0, t1) == 5.5 * MS  # [0,2.5] + [4,5] + [6,7] + [9,10]
    s = trace.summary(tr)
    assert s["window_s"] == pytest.approx(0.010)
    assert s["busy_s"] == pytest.approx(0.0055)
    ctx = types.SimpleNamespace(trace_summary=s)
    assert device_idle_share.read(ctx) == pytest.approx(45.0)


def test_layers_partition_busy_time():
    tr = _hand_made()
    ctx = types.SimpleNamespace(trace=tr, counts={"rounds": 2})
    parts = _fleet_layers.ns_per_round(ctx)
    assert parts == {
        "agg_kernel": 0.5 * MS, "sgd": 1.0 * MS, "eval": 0.5 * MS, "gather_merge": 0.75 * MS,
    }
    t0, t1 = trace.window(tr)
    busy = trace.busy_ns(tr["devices"]["/device:TPU:0"], t0, t1)
    assert sum(parts.values()) * 2 == pytest.approx(busy)


def test_idle_gaps_go_to_host_spans():
    tr = _hand_made()
    t0, t1 = trace.window(tr)
    idle = trace.idle_by_host_span(tr["devices"]["/device:TPU:0"], tr["host"], t0, t1)
    # idle: [2.5,4] window only, [5,6] control, [7,9] batches
    assert idle == pytest.approx(
        {"bench.window": 0.0015, "host.control_round": 0.001, "host.draw_batches": 0.002}
    )
    bd = trace.summary(tr)["breakdown"]
    assert set(bd) == {"device_ops", "idle_gaps"}
    for key in bd:
        assert len(bd[key]) <= 10
        assert all(isinstance(n, str) and isinstance(v, float) for n, v in bd[key])
    assert bd["device_ops"][0] == [
        "fusion.1 jit(scan_window)/while/body/jit(sgd_steps_flat)/dot", pytest.approx(0.002)
    ]


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(TESTDATA / "fleet_trace.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_trace_partitions_busy_time(recorded):
    ctx = types.SimpleNamespace(trace=recorded, counts={"rounds": recorded["rounds"]})
    parts = _fleet_layers.ns_per_round(ctx)
    assert all(v > 0 for v in parts.values()), parts
    t0, t1 = trace.window(recorded)
    busy = sum(trace.busy_ns(d, t0, t1) for d in recorded["devices"].values())
    assert sum(parts.values()) * recorded["rounds"] == pytest.approx(busy, rel=1e-9)
    s = trace.summary(recorded)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["breakdown"]["device_ops"] and s["breakdown"]["idle_gaps"]


def test_exchange_in_flight_and_exposed():
    from benchmarks.chip.metrics import exchange_exposed_ms_per_round, exchange_ms_per_round

    dev0 = [
        [0 * MS, 2 * MS, "fusion.1", ""],
        [1 * MS, 0.5 * MS, "all-gather-start.2", ""],  # in flight 1..4 ms
        [3 * MS, 1 * MS, "all-gather-done.2", ""],
        [5 * MS, 1 * MS, "all-reduce.3", ""],  # 5..6 ms, half under fusion.4
        [5.5 * MS, 1 * MS, "fusion.4", ""],
    ]
    dev1 = [[0, 1 * MS, "all-reduce.3", ""]]
    tr = {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
          "host": [[0, 10 * MS, "bench.measure"]]}
    ctx = types.SimpleNamespace(trace=tr, counts={"rounds": 1})
    # chip 0: 3 + 1 ms in flight, 2 + 0.5 exposed; chip 1: 1 and 1
    assert exchange_ms_per_round.read(ctx) == pytest.approx(2.5)
    assert exchange_exposed_ms_per_round.read(ctx) == pytest.approx(1.75)
    fleet = {"devices": {"/device:TPU:0": dev0[:1]}, "host": tr["host"]}
    assert exchange_ms_per_round.read(types.SimpleNamespace(trace=fleet, counts={"rounds": 1})) is None
