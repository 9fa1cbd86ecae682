"""Telemetry acceptance: one metric stream, three engines, zero overhead off.

The contract (docs/TELEMETRY.md): with telemetry enabled, the scalar pubsub
oracle and the vectorized engine (one-round and multi-round windows) emit
the same JSONL metric streams under identical configs — PERFECT and LOSSY
conditions, replication 1..3, f32 and int8 wire. Every column is
byte-identical except the two float32 norm reductions, which run in
different XLA programs and agree within 2 ULP. With telemetry disabled, the
engines compute bitwise-identical results to the enabled run (the metric
aux outputs observe, never perturb).
"""
import dataclasses
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.data import iid_split, synth_mnist
from repro.fl import SimConfig, make_simulation
from repro.p2p.network import LOSSY, PERFECT, NetworkConditions
from repro.telemetry import NULL_TIMER, MetricsRecorder, PhaseTimer
from repro.telemetry.report import load_stream, main as report_main, summarize
from repro.telemetry.schema import CHANNELS, ROW_KEYS, SCHEMA_VERSION


@pytest.fixture(scope="module")
def data():
    return synth_mnist(num_train=900, num_test=200, seed=0)


def _run(data, engine, scan=0, telemetry=True, **kw):
    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(
        num_agents=6, num_partitions=5, pi=2, rounds=3, local_iters=2,
        batch_size=32, eval_agents=2, engine=engine, scan_rounds=scan,
        telemetry=telemetry, **kw,
    )
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
    sim = make_simulation(cfg, shards, x_te, y_te)
    sim.run()
    return sim


# float32 sums of squares reduced in different XLA programs (the scalar
# engine's standalone reduction vs the one inlined in a fused body): no
# compiler orders them alike, so they are held to a few ULP, not to the bit
NORM_KEYS = ("delta_normsq", "value_normsq")
NORM_MAX_ULP = 2


def _assert_streams_agree(a, b):
    """Two streams' rows: every column byte-identical except NORM_KEYS,
    which agree as float32 within NORM_MAX_ULP."""
    assert len(a) == len(b)
    for line_a, line_b in zip(a, b):
        ra, rb = json.loads(line_a), json.loads(line_b)
        assert list(ra) == list(rb)
        for key in ra:
            if key in NORM_KEYS:
                np.testing.assert_array_max_ulp(
                    np.float32(ra[key]), np.float32(rb[key]), maxulp=NORM_MAX_ULP
                )
            else:
                assert json.dumps(ra[key]) == json.dumps(rb[key]), key


# ---- the acceptance bar: the same stream from every engine ------------------
@pytest.mark.parametrize(
    "kw",
    [
        # rho=3 exercises the ordered replica merges; PERFECT and LOSSY
        # take entirely different vectorized paths (phase tables vs events)
        dict(conditions=PERFECT, rho=3),
        dict(conditions=LOSSY, rho=3),
        # the int8 wire quantizes deltas AND the accounting (4x fewer bytes)
        dict(conditions=LOSSY, rho=2, wire_dtype="int8"),
    ],
    ids=["perfect-rho3", "lossy-rho3", "lossy-int8"],
)
def test_metric_streams_identical_across_engines_norms_within_2ulp(data, kw):
    sims = [
        _run(data, "scalar", **kw),
        _run(data, "vectorized", **kw),
        _run(data, "vectorized", scan=2, **kw),
    ]
    streams = [s.recorder.jsonl_lines()[1:] for s in sims]
    _assert_streams_agree(streams[0], streams[1])
    _assert_streams_agree(streams[0], streams[2])
    assert len(streams[0]) == 3  # one row per round


def test_rows_follow_the_schema(data):
    sim = _run(data, "scalar", conditions=LOSSY, rho=2)
    for row in sim.recorder.rows:
        assert tuple(row) == ROW_KEYS
    lines = sim.recorder.jsonl_lines(meta={"engine": "scalar"})
    head = json.loads(lines[0])
    assert head["schema_version"] == SCHEMA_VERSION
    assert head["meta"] == {"engine": "scalar"}
    # lossy traffic actually landed in the channel columns
    total_msgs = sum(
        r[f"msgs_{ch}"] for r in sim.recorder.rows for ch in CHANNELS
    )
    assert total_msgs > 0
    assert sim.recorder.rows[-1]["msgs_total"] == sim.net.pubsub.messages_sent


# ---- disabled telemetry is invisible ---------------------------------------
@pytest.mark.parametrize("engine,scan", [("vectorized", 0), ("vectorized", 2)])
def test_disabled_telemetry_changes_nothing(data, engine, scan):
    kw = dict(conditions=LOSSY, rho=2, seed=3)
    on = _run(data, engine, scan=scan, telemetry=True, **kw)
    off = _run(data, engine, scan=scan, telemetry=False, **kw)
    assert off.recorder is None
    np.testing.assert_array_equal(on.agent_weights(), off.agent_weights())
    for a, b in zip(on.history, off.history):
        assert a == b
    assert on._bytes_total == off._bytes_total


def test_scalar_engine_disabled_telemetry_changes_nothing(data):
    kw = dict(conditions=LOSSY, rho=2, seed=3)
    on = _run(data, "scalar", telemetry=True, **kw)
    off = _run(data, "scalar", telemetry=False, **kw)
    assert off.recorder is None
    for a in range(6):
        np.testing.assert_array_equal(
            on.agents[a].load_model(), off.agents[a].load_model()
        )
    for ra, rb in zip(on.history, off.history):
        assert ra == rb


# ---- hypothesis: stream equality is seed/condition independent --------------
try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=4, deadline=None)
    @given(
        seed=st.integers(0, 50),
        loss=st.sampled_from([0.0, 0.15, 0.4]),
    )
    def test_stream_equality_property(seed, loss):
        # module-scoped fixtures don't mix with @given; tiny fixed-shape
        # config so every example reuses the same compiled programs
        x_tr, y_tr, x_te, y_te = synth_mnist(num_train=400, num_test=80, seed=1)
        cond = NetworkConditions(loss_prob=loss, delay_prob=0.2, max_delay_rounds=2)
        cfg = SimConfig(
            num_agents=4, num_partitions=3, pi=2, rho=2, rounds=2,
            local_iters=1, batch_size=32, eval_agents=1, seed=seed,
            conditions=cond, telemetry=True,
        )
        shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
        sims = []
        for engine in ("scalar", "vectorized"):
            sim = make_simulation(
                dataclasses.replace(cfg, engine=engine), shards, x_te, y_te
            )
            sim.run()
            sims.append(sim)
        _assert_streams_agree(*(s.recorder.jsonl_lines()[1:] for s in sims))
except ImportError:  # pragma: no cover - hypothesis is in the base image
    pass


# ---- protocol traces --------------------------------------------------------
def test_trace_events_are_chrome_trace_shaped(data, tmp_path):
    sim = _run(data, "scalar", conditions=LOSSY, rho=2, trace=True)
    trace = sim.recorder.trace
    assert trace is not None
    doc = trace.to_dict()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    # metadata + protocol instants; host phases live in the profiler's trace
    assert {e["ph"] for e in events} == {"M", "i"}
    for ev in events:
        assert {"name", "ph", "pid"} <= set(ev)
        assert ev["pid"] == 1
        if ev["ph"] == "i":
            assert ev["ts"] >= 0
    # round-trips through json on disk
    out = tmp_path / "run.trace.json"
    trace.write(str(out))
    assert json.loads(out.read_text())["traceEvents"]


def _host_spans(log_dir) -> list:
    """[(start_ns, end_ns, name)] of the ``host.`` spans in a profiler
    trace, in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("host."):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return sorted(out)


def test_phase_timer_accumulates_and_traces(tmp_path):
    pt = PhaseTimer()
    with jax.profiler.trace(str(tmp_path)):
        with pt.phase("fate_draw"):
            pass
        with pt.phase("fate_draw"):
            pass
        with NULL_TIMER.phase("control"):
            pass
    s = pt.summary()
    assert s["fate_draw"]["count"] == 2
    assert s["fate_draw"]["total_s"] >= 0
    assert "control" not in s and NULL_TIMER.summary() == {}
    names = [n for _, _, n in _host_spans(tmp_path)]
    assert names == ["host.fate_draw", "host.fate_draw", "host.control"]


# every host step of one scanned window, in the order the engine takes them
WINDOW_PHASES = {
    "perfect": ("batches", "stage", "device_window", "wait", "account"),
    "lossy": (
        "fate_draw", "control", "batches", "stage", "device_window", "wait", "account",
    ),
}


def _window_sim(data, conditions, wire_dtype="f32", seed=0):
    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(
        num_agents=6, num_partitions=5, pi=2, rho=2, rounds=100, local_iters=2,
        batch_size=32, eval_agents=2, engine="vectorized", scan_rounds=2,
        conditions=conditions, wire_dtype=wire_dtype, seed=seed,
    )
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
    return make_simulation(cfg, shards, x_te, y_te)


@pytest.mark.parametrize(
    "path,conditions,wire",
    [("perfect", PERFECT, "f32"), ("lossy", LOSSY, "int8")],
    ids=["perfect", "lossy-int8"],
)
def test_window_phases_are_profiler_spans(data, tmp_path, path, conditions, wire):
    sim = _window_sim(data, conditions, wire)
    assert sim.recorder is None  # telemetry off: the spans need no timer
    sim.run_window(0, 2)  # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        sim.run_window(2, 2)
    spans = _host_spans(tmp_path)
    outer = [s for s in spans if s[2] == "host.run_window"]
    assert len(outer) == 1
    t0, t1, _ = outer[0]
    inner = [s for s in spans if s[2] != "host.run_window"]
    assert [n for _, _, n in inner] == [f"host.{p}" for p in WINDOW_PHASES[path]]
    for a, b, _ in inner:
        assert t0 <= a <= b <= t1
    # the phases follow one another and do not overlap
    for (_, b, _), (a, _, _) in zip(inner, inner[1:]):
        assert b <= a


@pytest.mark.parametrize(
    "conditions,wire", [(PERFECT, "f32"), (LOSSY, "int8")], ids=["perfect", "lossy-int8"]
)
def test_profiler_changes_nothing(data, tmp_path, conditions, wire):
    on, off = (_window_sim(data, conditions, wire, seed=5) for _ in range(2))
    with jax.profiler.trace(str(tmp_path)):
        hist_on = on.run_window(0, 2) + on.run_window(2, 2)
    hist_off = off.run_window(0, 2) + off.run_window(2, 2)
    assert hist_on == hist_off
    np.testing.assert_array_equal(on.agent_weights(), off.agent_weights())


# ---- report CLI -------------------------------------------------------------
def test_report_cli_digest(data, tmp_path, capsys):
    sim = _run(data, "vectorized", conditions=LOSSY, rho=2)
    path = tmp_path / "metrics.jsonl"
    sim.recorder.write_jsonl(str(path), meta={"engine": "vectorized"})
    head, rows = load_stream(str(path))
    assert head["schema_version"] == SCHEMA_VERSION
    assert len(rows) == 3
    digest = summarize(rows)
    assert digest["rounds"] == 3
    assert digest["msgs_total"] == rows[-1]["msgs_total"]
    assert report_main([str(path)]) == 0
    assert "rounds 0..2" in capsys.readouterr().out
    assert report_main([str(path), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload[str(path)]["rounds"] == 3


def test_report_cli_rejects_foreign_schema(tmp_path, capsys):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"schema_version":99,"meta":{}}\n')
    assert report_main([str(bad)]) == 1


# ---- recorder unit behavior -------------------------------------------------
def test_recorder_channel_mapping_matches_fates():
    rec = MetricsRecorder(ticks_per_round=4, max_delay_ticks=2)
    # REPLY at tick phase 1 is a fetch reply; at phase 3 an update reply
    from repro.core.api import REPLY_TOPIC, UPDATE_TOPIC

    rec.on_send(REPLY_TOPIC, 1, sender=0, nbytes=100)
    rec.on_send(REPLY_TOPIC, 3, sender=0, nbytes=100)
    rec.on_send(UPDATE_TOPIC, 2, sender=1, nbytes=50)
    rec.finish_round(
        round=0, active=2, contrib=[1], eps=[1.0], delta_normsq=0.0,
        value_normsq=0.0, accs=[0.5], bytes_total=250, msgs_total=3,
        drops_total=0,
    )
    row = rec.rows[0]
    assert row["msgs_fetch_reply"] == 1
    assert row["msgs_update_reply"] == 1
    assert row["msgs_update"] == 1
    assert row["bytes_update"] == 50
