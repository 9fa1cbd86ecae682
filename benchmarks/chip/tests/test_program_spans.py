"""The readers of the engine's device scopes and host phase spans, on a
hand-made trace and on small traces recorded on a TPU v5e."""
import gzip
import importlib
import json
import types
from pathlib import Path

import pytest

from benchmarks.chip import trace
from benchmarks.chip.metrics import _fleet_layers, _fleet_sublayers

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
MS = 1e6  # ns
BODY = "jit(scan_window)/while/body/closed_call"
SCOPE_METRICS = {s: f"{s}_ms_per_round" for s in _fleet_sublayers.SCOPES}
HOST_METRICS = {
    "host_control_ms_per_round": ("host.fate_draw", "host.control"),
    "host_batches_ms_per_round": ("host.batches",),
    "host_stage_ms_per_round": ("host.stage",),
}


def _hand_made():
    dev = [
        [0 * MS, 2 * MS, "fusion.1", f"{BODY}/vmap(jit(sgd_steps_flat))/dot"],
        # overlaps SGD: only its last 0.5 ms is weight build
        [1 * MS, 1.5 * MS, "fusion.2", f"{BODY}/weight_build/gather"],
        [3 * MS, 2 * MS, "fusion.3", f"{BODY}/contrib_gather/gather"],
        [5 * MS, 0.5 * MS, "pad.4", f"{BODY}/jit(ipls_aggregate_batched_q)/pad"],
        [5.5 * MS, 1 * MS, "ipls_aggregate_batched_q.5",
         f"{BODY}/jit(ipls_aggregate_batched_q)/pallas_call"],
        [6.5 * MS, 0.25 * MS, "fusion.6", f"{BODY}/wire_codec/abs"],
        [6.75 * MS, 0.25 * MS, "fusion.7", f"{BODY}/ring_roll/concatenate"],
        [7 * MS, 0.5 * MS, "fusion.8", f"{BODY}/replica_merge/select_n"],
        # no path: made by the compiler, it takes the scope of the op before
        [7.5 * MS, 0.5 * MS, "dynamic-slice.12", ""],
        [8 * MS, 0.5 * MS, "fusion.9", f"{BODY}/jit(evaluate)/dot"],
        # no path after an op in no scope: unscoped
        [8.5 * MS, 0.5 * MS, "dynamic-update-slice.10", ""],
        [9 * MS, 1.5 * MS, "copy.11", f"{BODY}/replica_merge/copy"],  # past the window
    ]
    host = [
        [0, 10 * MS, "bench.measure"],
        [-1 * MS, 10.5 * MS, "bench.window"],
        [-1 * MS, 10.4 * MS, "host.run_window"],
        [-1 * MS, 1.5 * MS, "host.fate_draw"],  # half before the window
        [0.5 * MS, 1 * MS, "host.control"],
        [0.6 * MS, 0.8 * MS, "host.control_round"],  # the harness's own wrapper
        [1.5 * MS, 0.5 * MS, "host.batches"],
        [2 * MS, 1.25 * MS, "host.stage"],
        [3.25 * MS, 0.25 * MS, "host.device_window"],
        [3.5 * MS, 5.75 * MS, "host.wait"],
        [9.25 * MS, 0.1 * MS, "host.account"],
    ]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def _ctx(tr, rounds):
    return types.SimpleNamespace(trace=tr, counts={"rounds": rounds})


def _read(name, ctx):
    return importlib.import_module(f"benchmarks.chip.metrics.{name}").read(ctx)


def test_scopes_partition_gather_merge():
    ctx = _ctx(_hand_made(), 2)
    parts = _fleet_sublayers.ns_per_round(ctx)
    assert parts == pytest.approx({
        "agg_kernel": 0.5 * MS, "sgd": 1.0 * MS, "eval": 0.25 * MS,
        # the kernel wrapper's pad counts as feeding the kernel
        "contrib_gather": 1.25 * MS, "weight_build": 0.25 * MS,
        "wire_codec": 0.125 * MS, "ring_roll": 0.125 * MS,
        "replica_merge": 1.0 * MS, "unscoped": 0.25 * MS,
    })
    layers = _fleet_layers.ns_per_round(ctx)
    rest = sum(parts[s] for s in _fleet_sublayers.SCOPES) + parts["unscoped"]
    assert rest == pytest.approx(layers["gather_merge"], rel=1e-12)
    for scope, metric in SCOPE_METRICS.items():
        assert _read(metric, ctx) == pytest.approx(parts[scope] * 1e-6)


def test_host_phases_read_their_spans():
    ctx = _ctx(_hand_made(), 2)
    # fate draw clipped to [0, 0.5] + control 1.0, over 2 rounds
    assert _read("host_control_ms_per_round", ctx) == pytest.approx(0.75)
    assert _read("host_batches_ms_per_round", ctx) == pytest.approx(0.25)
    assert _read("host_stage_ms_per_round", ctx) == pytest.approx(0.625)


def test_every_idle_gap_has_a_phase():
    tr = _hand_made()
    t0, t1 = trace.window(tr)
    idle = trace.idle_by_host_span(tr["devices"]["/device:TPU:0"], tr["host"], t0, t1)
    # the device waits only while the host stages the window's inputs
    assert idle == pytest.approx({"host.stage": 0.0005})


def test_a_program_without_scopes_reads_nothing():
    tr = _hand_made()
    tr["devices"]["/device:TPU:0"] = [
        ev[:3] + [ev[3].replace("weight_build/", "").replace("contrib_gather/", "")
                  .replace("wire_codec/", "").replace("ring_roll/", "")
                  .replace("replica_merge/", "")]
        for ev in tr["devices"]["/device:TPU:0"]
    ]
    tr["host"] = [h for h in tr["host"] if h[2].startswith("bench.")]
    ctx = _ctx(tr, 2)
    assert _fleet_layers.ns_per_round(ctx)["gather_merge"] > 0
    for metric in list(SCOPE_METRICS.values()) + list(HOST_METRICS):
        assert _read(metric, ctx) is None, metric


def _recorded(name):
    with gzip.open(TESTDATA / name, "rt") as f:
        return json.load(f)


@pytest.mark.parametrize(
    "name,scopes",
    [
        ("program_spans_perfect.json.gz", ("contrib_gather", "weight_build", "replica_merge")),
        ("program_spans_lossy.json.gz", _fleet_sublayers.SCOPES),
    ],
    ids=["perfect", "lossy"],
)
def test_recorded_trace_partitions_gather_merge(name, scopes):
    recorded = _recorded(name)
    ctx = _ctx(recorded, recorded["rounds"])
    parts = _fleet_sublayers.ns_per_round(ctx)
    layers = _fleet_layers.ns_per_round(ctx)
    rest = sum(parts[s] for s in _fleet_sublayers.SCOPES) + parts["unscoped"]
    assert rest == pytest.approx(layers["gather_merge"], rel=1e-9)
    for k in ("agg_kernel", "sgd", "eval"):
        assert parts[k] == layers[k]
    for scope in scopes:
        assert _read(SCOPE_METRICS[scope], ctx) > 0, scope
    for metric, spans in HOST_METRICS.items():
        if any(h[2] in spans for h in recorded["host"]):
            assert _read(metric, ctx) > 0, metric
    # each idle gap of the window is put down to a phase of the engine
    t0, t1 = trace.window(recorded)
    evs = next(iter(recorded["devices"].values()))
    idle = trace.idle_by_host_span(evs, recorded["host"], t0, t1)
    unnamed = sum(v for k, v in idle.items() if k in ("bench.window", "host.run_window"))
    assert unnamed < 0.1 * sum(idle.values())


def test_the_parents_recorded_trace_reads_nothing():
    with gzip.open(TESTDATA / "fleet_trace.json.gz", "rt") as f:
        recorded = json.load(f)
    ctx = _ctx(recorded, recorded["rounds"])
    for metric in list(SCOPE_METRICS.values()) + list(HOST_METRICS):
        assert _read(metric, ctx) is None, metric
