"""Multi-round fused scan engine correctness.

``SimConfig(scan_rounds=W)`` folds W rounds into a single ``lax.scan`` device
call; ``scan_rounds=0`` runs one-round windows of the same program. The fused
path must be *W-invariant*: any window size (including partial tail
windows) produces the same per-round history and final weights as
one-round windows — which in turn match the scalar pubsub oracle to float
tolerance with exact traffic counters. Eval can additionally be thinned to a
cadence without perturbing the training trajectory.
"""

import hashlib

import numpy as np
import pytest

from repro.data import iid_split, synth_mnist
from repro.fl import SimConfig, make_simulation
from repro.p2p.network import LOSSY, NetworkConditions

# windows of different sizes are the same arithmetic in a different dispatch
# grouping: only scheduling noise separates them (~3e-8 observed)
ATOL_SCAN = 3e-7
# vectorized vs scalar re-associates batched reductions: PR-2 tolerance
ATOL_ORACLE = 1e-4


@pytest.fixture(scope="module")
def data():
    return synth_mnist(num_train=1500, num_test=300, seed=0)


def _run(data, engine, scan, rounds=8, cadence=1, **kw):
    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(
        rounds=rounds, local_iters=3, engine=engine, scan_rounds=scan,
        eval_cadence=cadence, **kw,
    )
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
    sim = make_simulation(cfg, shards, x_te, y_te)
    hist = sim.run()
    return sim, hist


def _assert_same_vectorized(sim_a, hist_a, sim_b, hist_b):
    """Two vectorized runs (different window sizes) must agree to float noise
    on weights/accs and exactly on every traffic counter."""
    np.testing.assert_allclose(
        sim_a.agent_weights(), sim_b.agent_weights(), atol=ATOL_SCAN
    )
    assert len(hist_a) == len(hist_b)
    for ma, mb in zip(hist_a, hist_b):
        assert ma["round"] == mb["round"] and ma["active"] == mb["active"]
        assert ma["bytes_total"] == mb["bytes_total"]
        np.testing.assert_allclose(ma["acc_mean"], mb["acc_mean"], atol=ATOL_SCAN)
    assert sim_a.messages_sent == sim_b.messages_sent
    assert sim_a.messages_dropped == sim_b.messages_dropped


NETS = [
    pytest.param({}, id="perfect"),
    pytest.param(dict(conditions=LOSSY, seed=1), id="lossy"),
]


@pytest.mark.parametrize("net", NETS)
def test_scan_w_invariance(data, net):
    """scan_rounds=1 == 2 == 4: identical weights, metrics, and counters
    round-by-round (W only regroups dispatches; scan_rounds=0 is W=1 by
    construction)."""
    kw = dict(num_agents=5, num_partitions=8, pi=2, rho=2, **net)
    sim_u, hist_u = _run(data, "vectorized", 1, **kw)
    for W in (2, 4):
        sim_w, hist_w = _run(data, "vectorized", W, **kw)
        _assert_same_vectorized(sim_u, hist_u, sim_w, hist_w)


@pytest.mark.parametrize("net", NETS)
def test_scan8_matches_scalar_oracle(data, net):
    """Acceptance bar: scan_rounds=8 vs the scalar pubsub oracle — weights
    within PR-2 tolerance, bytes/messages/drops exactly equal per round,
    and the whole 8-round run is a single device dispatch."""
    kw = dict(num_agents=5, num_partitions=8, pi=2, rho=2, **net)
    sim_s, hist_s = _run(data, "scalar", 0, **kw)
    sim_w, hist_w = _run(data, "vectorized", 8, **kw)
    for ms, mw in zip(hist_s, hist_w):
        assert ms["round"] == mw["round"] and ms["active"] == mw["active"]
        assert ms["bytes_total"] == mw["bytes_total"]
        np.testing.assert_allclose(ms["acc_mean"], mw["acc_mean"], atol=5e-3)
    assert sim_s.net.pubsub.messages_sent == sim_w.messages_sent
    assert sim_s.net.pubsub.messages_dropped == sim_w.messages_dropped
    w_s = np.stack([sim_s.agents[a].load_model() for a in range(kw["num_agents"])])
    np.testing.assert_allclose(w_s, sim_w.agent_weights(), atol=ATOL_ORACLE)
    assert sim_w.device_dispatches == 1


@pytest.mark.parametrize("net", NETS)
def test_scan_partial_tail_window(data, net):
    """rounds not divisible by scan_rounds: the tail window is shorter and
    must still agree with one-round windows."""
    kw = dict(num_agents=4, num_partitions=6, pi=2, rho=2, rounds=7, **net)
    sim_u, hist_u = _run(data, "vectorized", 0, **kw)
    sim_w, hist_w = _run(data, "vectorized", 4, **kw)  # windows of 4 + 3
    _assert_same_vectorized(sim_u, hist_u, sim_w, hist_w)
    assert sim_w.device_dispatches == 2


def test_scan_deep_delay_ring(data):
    """Delays spanning multiple rounds exercise the bounded-depth dense
    queues (depth Lu+1) inside the window control plane."""
    cond = NetworkConditions(loss_prob=0.2, delay_prob=0.5, max_delay_rounds=6)
    kw = dict(num_agents=4, num_partitions=6, pi=2, rho=2, conditions=cond, seed=9)
    sim_s, _ = _run(data, "scalar", 0, **kw)
    sim_u, hist_u = _run(data, "vectorized", 0, **kw)
    sim_w, hist_w = _run(data, "vectorized", 3, **kw)
    _assert_same_vectorized(sim_u, hist_u, sim_w, hist_w)
    w_s = np.stack([sim_s.agents[a].load_model() for a in range(4)])
    np.testing.assert_allclose(w_s, sim_w.agent_weights(), atol=ATOL_ORACLE)
    assert sim_s.net.pubsub.messages_dropped == sim_w.messages_dropped


@pytest.mark.parametrize("net", NETS)
def test_eval_cadence_thins_eval_without_perturbing_training(data, net):
    """eval_cadence=3 evaluates every 3rd round + the final round; skipped
    rounds reuse the last computed accuracy. The weight trajectory and all
    traffic counters are untouched."""
    kw = dict(num_agents=5, num_partitions=8, pi=2, rho=2, **net)
    sim_u, hist_u = _run(data, "vectorized", 0, **kw)
    sim_c, hist_c = _run(data, "vectorized", 4, cadence=3, **kw)
    np.testing.assert_allclose(
        sim_u.agent_weights(), sim_c.agent_weights(), atol=ATOL_SCAN
    )
    assert len(hist_u) == len(hist_c)
    for mu, mc in zip(hist_u, hist_c):
        assert mu["bytes_total"] == mc["bytes_total"]
        r = mu["round"]
        if (r + 1) % 3 == 0 or r == 7:
            np.testing.assert_allclose(mu["acc_mean"], mc["acc_mean"], atol=ATOL_SCAN)
        assert np.isfinite(mc["acc_mean"])  # skipped rounds carry last eval


@pytest.mark.parametrize("net", NETS)
def test_eval_cadence_ignored_without_scan(data, net):
    """eval_cadence thins only scanned windows: scan_rounds=0 evaluates
    every round, so its history equals the eval_cadence=1 run."""
    kw = dict(num_agents=5, num_partitions=8, pi=2, rho=2, **net)
    sim_1, hist_1 = _run(data, "vectorized", 0, **kw)
    sim_3, hist_3 = _run(data, "vectorized", 0, cadence=3, **kw)
    assert hist_3 == hist_1
    np.testing.assert_array_equal(sim_3.agent_weights(), sim_1.agent_weights())


def test_scan_rounds_rejected_for_negative():
    cfg = SimConfig(num_agents=4, rounds=2, engine="vectorized", scan_rounds=-1)
    x = np.zeros((40, 784), np.float32)
    y = np.zeros((40,), np.int64)
    shards = iid_split(x, y, 4, seed=0)
    with pytest.raises(ValueError):
        make_simulation(cfg, shards, x[:8], y[:8])


def test_scalar_engine_ignores_scan_rounds(data):
    """scan_rounds is a vectorized-engine knob; the scalar oracle ignores it
    so configs can be shared across engines."""
    sim_a, hist_a = _run(data, "scalar", 0, rounds=3, num_agents=4)
    sim_b, hist_b = _run(data, "scalar", 4, rounds=3, num_agents=4)
    w_a = np.stack([sim_a.agents[a].load_model() for a in range(4)])
    w_b = np.stack([sim_b.agents[a].load_model() for a in range(4)])
    np.testing.assert_array_equal(w_a, w_b)
    assert [m["bytes_total"] for m in hist_a] == [m["bytes_total"] for m in hist_b]


# Output digests of the fused engine, pinned on the CPU before the window's
# batches moved from host-staged images to a device-resident training set
# gathered by staged row indices, and kept when the per-round programs gave
# way to one-round windows (scan 0). Neither change moves what SGD sees:
# weights, accuracies and traffic counters stay equal to the bit, in windows
# of 4 rounds and of one.
def _join_shard(agent_id):
    # a joiner's own 100-row shard: one the training table lacks, and a
    # second batch bucket (100 rows < batch size 128)
    x, y, _, _ = synth_mnist(num_train=100, num_test=10, seed=agent_id)
    return x, y


PINNED_CASES = {
    "perfect-f32": dict(num_agents=5, num_partitions=8, pi=2, rho=2),
    "lossy-int8": dict(
        num_agents=5, num_partitions=8, pi=2, rho=2, conditions=LOSSY, seed=1,
        wire_dtype="int8",
    ),
    # 1500 rows over 13 agents: shards of 115 and 116, two batch buckets
    "two-bucket": dict(num_agents=13, num_partitions=8, pi=2, rho=2),
    # the offline/online/leave re-snapshots keep the training table; the
    # join brings a shard it lacks
    "lossy-churn": dict(
        num_agents=5, num_partitions=6, pi=2, rho=2, conditions=LOSSY, seed=0,
        memory=True, join_shard=_join_shard,
        churn={1: [(2, "offline")], 3: [(4, "leave"), (2, "online")], 4: [(5, "join")]},
    ),
}
PINNED = {
    "perfect-f32": "f7df1cbfd3a6bf4296d09cf6a2403995cc8b0720d39b115b02148cfba197b55c",
    "lossy-int8": "4d280669be0c33edc96d38681272c296881a331a74e0c1ae128234d7f1dd75f8",
    "two-bucket": "81851b4edef0d71b40d87c8d2beab703f654857609423bc0c887381006c81669",
    "lossy-churn": "c026ebf1f916e968c8d2b1d5885e767cab93693cc713d3306a7892146c40459f",
}


def _output_digest(sim, hist) -> str:
    h = hashlib.sha256(np.ascontiguousarray(sim.agent_weights(), np.float32).tobytes())
    for m in hist:
        h.update(repr(tuple(m[k] for k in (
            "round", "active", "bytes_total", "acc_mean", "acc_std", "acc_max"
        ))).encode())
    h.update(repr((sim.messages_sent, sim.messages_dropped)).encode())
    return h.hexdigest()


@pytest.mark.parametrize("scan", [0, 4])
@pytest.mark.parametrize("case", list(PINNED_CASES))
def test_fused_outputs_pinned_to_the_bit(data, case, scan):
    sim, hist = _run(data, "vectorized", scan, **PINNED_CASES[case])
    assert _output_digest(sim, hist) == PINNED[case]


@pytest.mark.parametrize("net", NETS)
def test_staged_bytes_counts_indices_not_images(data, net):
    """A window stages each agent's batch as int32 row indices, plus the
    routing (PERFECT) or control (LOSSY) tables; the images stay on the
    device. At this fleet that is below one agent-round of images a round."""
    W, A, bs = 4, 5, 128
    sim, _ = _run(
        data, "vectorized", W, rounds=W, num_agents=A, num_partitions=8, pi=2,
        rho=2, **net,
    )
    index_bytes = W * A * bs * 4
    assert index_bytes <= sim.staged_bytes < W * bs * 784 * 4
    if not net:
        # PERFECT: indices + the phase-cycled routing tables + the eval gates
        per_round = sum(
            t.nbytes for t in (
                sim._t_inst[0], sim._contrib_idx[0], sim._contrib_mask[0],
                sim._t_inst[0][sim._eval_idx],
            )
        ) + 1
        assert sim.staged_bytes == index_bytes + W * per_round
