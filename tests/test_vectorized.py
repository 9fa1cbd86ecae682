"""Two-engine equivalence + partition-batched aggregation kernel.

The vectorized engine must reproduce the scalar engine's per-round dataflow
exactly — under PERFECT conditions and under LOSSY ones, where both engines
read per-message fates from the same keyed counter-based stream (same
routing, same loss/delay decisions, same eps recursion, same reply caching);
any residual difference is float noise from batched vs per-agent device ops.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import iid_split, synth_mnist
from repro.fl import IPLSSimulation, SimConfig, make_simulation
from repro.p2p.network import LOSSY, PERFECT, NetworkConditions

RNG = np.random.default_rng(3)


@pytest.fixture(scope="module")
def data():
    return synth_mnist(num_train=1500, num_test=300, seed=0)


def _run_both(data, **kw):
    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(rounds=4, local_iters=3, **kw)
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
    sim_s = IPLSSimulation(cfg, shards, x_te, y_te)
    hist_s = sim_s.run()
    sim_v = make_simulation(dataclasses.replace(cfg, engine="vectorized"), shards, x_te, y_te)
    hist_v = sim_v.run()
    return sim_s, hist_s, sim_v, hist_v


def _assert_equivalent(sim_s, hist_s, sim_v, hist_v, num_agents, atol_w=1e-4):
    for ms, mv in zip(hist_s, hist_v):
        assert ms["round"] == mv["round"] and ms["active"] == mv["active"]
        assert ms["bytes_total"] == mv["bytes_total"]
        np.testing.assert_allclose(ms["acc_mean"], mv["acc_mean"], atol=5e-3)
    # pubsub-mirroring counters stay live on both engine paths
    assert sim_s.net.pubsub.messages_sent == sim_v.messages_sent
    assert sim_s.net.pubsub.messages_dropped == sim_v.messages_dropped
    w_s = np.stack([sim_s.agents[a].load_model() for a in range(num_agents)])
    np.testing.assert_allclose(w_s, sim_v.agent_weights(), atol=atol_w)


@pytest.mark.parametrize(
    "kw",
    [
        dict(num_agents=5, num_partitions=8, pi=2, rho=2),
        dict(num_agents=4, num_partitions=6, pi=2, rho=1),
        # more agents than partition slots: some agents own nothing
        dict(num_agents=10, num_partitions=6, pi=2, rho=2, eval_agents=3),
        dict(num_agents=6, num_partitions=5, pi=2, rho=3),
        # 1500 rows over 13 agents: shards of 115 and 116, two batch buckets
        dict(num_agents=13, num_partitions=8, pi=2, rho=2),
    ],
)
def test_engines_equivalent_under_perfect(data, kw):
    sim_s, hist_s, sim_v, hist_v = _run_both(data, **kw)
    _assert_equivalent(sim_s, hist_s, sim_v, hist_v, kw["num_agents"])


# acceptance bar for the lossy-network vectorization: batched and scalar
# engines agree round-by-round across seeds — weights to float tolerance,
# messages_dropped / bytes_total exactly
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_engines_equivalent_under_lossy(data, seed):
    sim_s, hist_s, sim_v, hist_v = _run_both(
        data, num_agents=5, num_partitions=8, pi=2, rho=2, conditions=LOSSY, seed=seed
    )
    _assert_equivalent(sim_s, hist_s, sim_v, hist_v, 5)
    assert sim_s.net.pubsub.messages_sent == sim_v.messages_sent
    assert sim_s.net.pubsub.messages_dropped == sim_v.messages_dropped
    assert sim_v.messages_dropped > 0  # losses actually happened


@pytest.mark.parametrize(
    "kw",
    [
        # rho=1: every loss is unrecoverable for the round; delayed updates
        # pile onto the single holder next round
        dict(num_agents=4, num_partitions=6, pi=2, rho=1, seed=5),
        # rho=3 exercises the replica-consensus masks + version filtering
        dict(num_agents=6, num_partitions=5, pi=2, rho=3, seed=6),
        # loss-only and delay-only corners of NetworkConditions
        dict(num_agents=4, num_partitions=6, pi=2, rho=2, seed=7,
             conditions=NetworkConditions(loss_prob=0.4)),
        dict(num_agents=4, num_partitions=6, pi=2, rho=2, seed=8,
             conditions=NetworkConditions(delay_prob=0.5, max_delay_rounds=2)),
        # delays longer than one round: deeper delta ring buffer
        dict(num_agents=4, num_partitions=6, pi=2, rho=2, seed=9,
             conditions=NetworkConditions(loss_prob=0.2, delay_prob=0.5, max_delay_rounds=6)),
    ],
)
def test_engines_equivalent_lossy_corners(data, kw):
    kw.setdefault("conditions", LOSSY)
    sim_s, hist_s, sim_v, hist_v = _run_both(data, **kw)
    _assert_equivalent(sim_s, hist_s, sim_v, hist_v, kw["num_agents"])
    assert sim_s.net.pubsub.messages_dropped == sim_v.messages_dropped


def test_lossy_kernel_path_matches_scalar(data):
    """The partition-batched Pallas kernel path (interpret mode off-TPU)
    aggregates the ring-buffered delta windows identically."""
    from repro.fl.vectorized import VectorizedIPLSSimulation

    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(
        num_agents=4, num_partitions=6, pi=2, rho=2, rounds=3,
        local_iters=2, conditions=LOSSY, seed=0,
    )
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
    sim_s = IPLSSimulation(cfg, shards, x_te, y_te)
    sim_s.run()
    sim_v = VectorizedIPLSSimulation(cfg, shards, x_te, y_te, use_kernel=True)
    sim_v.run()
    w_s = np.stack([sim_s.agents[a].load_model() for a in range(cfg.num_agents)])
    np.testing.assert_allclose(w_s, sim_v.agent_weights(), atol=1e-4)
    assert sim_s.net.pubsub.total_bytes() == sim_v._bytes_total


@pytest.mark.parametrize(
    "conditions, wire_dtype",
    [(PERFECT, "f32"), (LOSSY, "f32"), (LOSSY, "int8")],
    ids=["perfect-f32", "lossy-f32", "lossy-int8"],
)
def test_kernel_path_matches_scalar_uneven_partitions(data, conditions, wire_dtype):
    """Partitions of unequal size (K does not divide N): the kernel path's
    contributor layout zero-pads the narrower partitions to S, interpret
    mode off-TPU."""
    from repro.fl.vectorized import VectorizedIPLSSimulation

    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(
        num_agents=4, num_partitions=8, pi=2, rho=2, rounds=3, local_iters=2,
        conditions=conditions, wire_dtype=wire_dtype, scan_rounds=3, seed=0,
    )
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
    sim_s = IPLSSimulation(cfg, shards, x_te, y_te)
    sim_s.run()
    sim_v = VectorizedIPLSSimulation(cfg, shards, x_te, y_te, use_kernel=True)
    assert len(set(sim_v._sizes.tolist())) > 1
    sim_v.run()
    w_s = np.stack([sim_s.agents[a].load_model() for a in range(cfg.num_agents)])
    np.testing.assert_allclose(w_s, sim_v.agent_weights(), atol=1e-4)
    assert sim_s.net.pubsub.total_bytes() == sim_v._bytes_total
    assert sim_s.net.pubsub.messages_sent == sim_v.messages_sent
    assert sim_s.net.pubsub.messages_dropped == sim_v.messages_dropped


@pytest.mark.parametrize(
    "N, K, S_extra",
    [(1000, 5, 0), (1003, 4, 0), (1000, 5, 24), (1003, 4, 5)],
    ids=["even", "uneven", "even-widened", "uneven-widened"],
)
def test_partition_slices_match_element_gather(N, K, S_extra):
    """`contributor_rows` (the kernel path's contributor layout) and
    `partition_plane` (the int8 delta plane) give what the element-indexed
    (row, column) gathers gave: every in-partition lane bitwise, every pad
    lane +0.0 (the old gathers' mask multiply left -0.0 wherever column 0
    was negative). `S_extra` widens S past the largest partition, as the
    int8 wire rounds it up to whole quantization blocks."""
    from repro.core.partition import PartitionSpec
    from repro.fl.vectorized import contributor_rows, partition_plane

    spec = PartitionSpec.even(N, K)
    sizes, offsets = np.asarray(spec.sizes), np.asarray(spec.offsets())
    S = int(sizes.max()) + S_extra
    rows, R = 7, 5
    inst_k = np.repeat(np.arange(K), 2).astype(np.int32)   # rho = 2, k-major
    inst_of_k = [np.nonzero(inst_k == k)[0] for k in range(K)]
    idx = RNG.integers(0, rows, (len(inst_k), R)).astype(np.int32)
    D = jnp.asarray(RNG.standard_normal((rows, N)), jnp.float32)

    lane = np.arange(S)
    valid = lane[None, :] < sizes[:, None]                    # (K, S)
    col = np.where(valid, offsets[:, None] + lane[None, :], 0)

    def bits(x, keep):
        return np.where(keep, np.asarray(x).view(np.uint32), np.uint32(0))

    # contributor layout (K_inst, R, S), the kernel path's G
    G_old = D[idx[:, :, None], col[inst_k][:, None, :]] * valid[inst_k][:, None, :]
    G_new = contributor_rows(
        D, jnp.asarray(idx), inst_of_k=inst_of_k, offsets=offsets, sizes=sizes, S=S
    )
    keep = np.broadcast_to(valid[inst_k][:, None, :], G_old.shape)
    np.testing.assert_array_equal(np.asarray(G_new).view(np.uint32), bits(G_old, keep))
    # int8 delta plane (rows, K, S)
    Dplane_old = D[:, col] * jnp.asarray(valid, jnp.float32)[None]
    P = partition_plane(D, offsets=offsets, sizes=sizes, S=S)
    keep = np.broadcast_to(valid[None], Dplane_old.shape)
    np.testing.assert_array_equal(np.asarray(P).view(np.uint32), bits(Dplane_old, keep))


def test_vectorized_rejects_out_of_scope_configs(data):
    x_tr, y_tr, x_te, y_te = data
    shards = iid_split(x_tr, y_tr, 4, seed=0)
    # lossy conditions are IN scope since the mask-stream path
    lossy = SimConfig(num_agents=4, rounds=2, conditions=LOSSY, engine="vectorized")
    sim = make_simulation(lossy, shards, x_te, y_te)
    assert sim._lossy
    # churn is IN scope since the event-boundary re-snapshot path
    churny = SimConfig(num_agents=4, rounds=2, churn={1: [(3, "offline")]}, engine="vectorized")
    sim = make_simulation(churny, shards, x_te, y_te)
    assert sim._lossy and sim._replay == [1]
    with pytest.raises(ValueError):
        make_simulation(dataclasses.replace(lossy, engine="nope"), shards, x_te, y_te)


# ---- partition-batched Pallas kernel ----------------------------------------
@pytest.mark.parametrize("N", [256, 70001])  # 70001: padded tail in every tile
@pytest.mark.parametrize("R", [1, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_kernel_matches_per_partition_ref(N, R, dtype):
    from repro.kernels.ipls_aggregate.ops import aggregate_batched
    from repro.kernels.ipls_aggregate.ref import ipls_aggregate_ref

    K = 6
    w = jnp.asarray(RNG.standard_normal((K, N)), dtype)
    d = jnp.asarray(RNG.standard_normal((K, R, N)), dtype)
    m = jnp.asarray(RNG.integers(0, 2, (K, R)), jnp.float32)
    m = m.at[1].set(0.0)  # an r=0 partition must pass through untouched
    eps = jnp.asarray(RNG.uniform(0.1, 1.0, K), jnp.float32)
    got = aggregate_batched(w, d, m, eps)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    for k in range(K):
        ref_k = ipls_aggregate_ref(w[k], d[k], m[k], eps[k])
        np.testing.assert_allclose(
            np.asarray(got[k], np.float32), np.asarray(ref_k, np.float32), atol=tol, rtol=tol
        )
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(w[1]))


def test_batched_kernel_matches_batched_ref_unequal_sizes():
    """Zero-padded tails (partitions of unequal true size sharing one padded
    width) stay exactly zero through the kernel."""
    from repro.kernels.ipls_aggregate.ops import aggregate_batched
    from repro.kernels.ipls_aggregate.ref import ipls_aggregate_batched_ref

    K, R, N = 4, 3, 5000
    sizes = [5000, 3777, 1, 4096]
    w = np.zeros((K, N), np.float32)
    d = np.zeros((K, R, N), np.float32)
    for k, s in enumerate(sizes):
        w[k, :s] = RNG.standard_normal(s)
        d[k, :, :s] = RNG.standard_normal((R, s))
    m = jnp.ones((K, R), jnp.float32)
    eps = jnp.asarray(RNG.uniform(0.1, 1.0, K), jnp.float32)
    got = np.asarray(aggregate_batched(jnp.asarray(w), jnp.asarray(d), m, eps))
    ref = np.asarray(ipls_aggregate_batched_ref(jnp.asarray(w), jnp.asarray(d), m, eps))
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
    for k, s in enumerate(sizes):
        assert np.all(got[k, s:] == 0.0)


# ---- churn: event-boundary re-snapshot --------------------------------------
CHURN_ALL_ACTIONS = {
    1: [(2, "offline")],
    3: [(4, "leave"), (2, "online")],
    4: [(5, "join")],
    6: [(1, "crash")],
}


@pytest.mark.parametrize("scan", [0, 3])
@pytest.mark.parametrize("wire_dtype", ["f32", "int8"])
def test_churn_matches_scalar_all_actions(data, scan, wire_dtype):
    """All five membership actions on the vectorized engine (round-at-a-time
    and lax.scan-windowed): event rounds replay on the embedded scalar
    oracle, fused spans re-snapshot at each boundary, and the result matches
    the scalar engine exactly — weights, traffic counters, and the telemetry
    stream byte-for-byte."""
    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(
        num_agents=5, num_partitions=6, pi=2, rho=2, rounds=8,
        local_iters=2, conditions=LOSSY, seed=0, churn=CHURN_ALL_ACTIONS,
        telemetry=True, memory=True, wire_dtype=wire_dtype,
    )
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
    sim_s = make_simulation(cfg, shards, x_te, y_te)
    hist_s = sim_s.run()
    sim_v = make_simulation(
        dataclasses.replace(cfg, engine="vectorized", scan_rounds=scan),
        shards, x_te, y_te,
    )
    hist_v = sim_v.run()
    for ms, mv in zip(hist_s, hist_v):
        assert ms["round"] == mv["round"] and ms["active"] == mv["active"]
        assert ms["bytes_total"] == mv["bytes_total"]
    ps = sim_s.net.pubsub
    assert ps.messages_sent == sim_v.messages_sent
    assert ps.messages_dropped == sim_v.messages_dropped
    ids = [a for a, ag in sim_s.agents.items() if ag.live]
    assert ids == sim_v.agent_ids()
    w_s = np.stack([sim_s.agents[a].load_model() for a in ids])
    np.testing.assert_allclose(w_s, sim_v.agent_weights(), atol=3e-8)
    assert sim_s.recorder.jsonl_lines()[1:] == sim_v.recorder.jsonl_lines()[1:]
    if scan:
        # windows split only at the 4 event rounds: far fewer dispatches
        # than one (or more) per round
        assert sim_v.device_dispatches < cfg.rounds


def test_churn_rho1_crash_reassignment_matches(data):
    """rho=1 crash orphans partitions; the re-snapshot must pick up the
    table's reassignment and the zero/cache-seeded holder states."""
    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(
        num_agents=4, num_partitions=8, pi=2, rho=1, rounds=6,
        local_iters=2, conditions=LOSSY, seed=2, churn={2: [(1, "crash")]},
    )
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=2)
    sim_s = IPLSSimulation(cfg, shards, x_te, y_te)
    hist_s = sim_s.run()
    sim_v = make_simulation(
        dataclasses.replace(cfg, engine="vectorized"), shards, x_te, y_te
    )
    hist_v = sim_v.run()
    for ms, mv in zip(hist_s, hist_v):
        assert ms["round"] == mv["round"] and ms["active"] == mv["active"]
        assert ms["bytes_total"] == mv["bytes_total"]
    assert sim_s.net.pubsub.messages_sent == sim_v.messages_sent
    assert sim_s.net.pubsub.messages_dropped == sim_v.messages_dropped
    ids = [a for a, ag in sim_s.agents.items() if ag.live]
    w_s = np.stack([sim_s.agents[a].load_model() for a in ids])
    np.testing.assert_allclose(w_s, sim_v.agent_weights(), atol=3e-8)


def test_draw_indices_selects_draw_batch_rows(data):
    """`draw_indices` advances the trainer's stream exactly as `draw_batch`
    does, and indexing the shard with it gives `draw_batch`'s arrays."""
    from repro.fl.local_trainer import LocalTrainer

    x, y = data[0][:300], data[1][:300]
    a = LocalTrainer(3, x, y, seed=7)
    b = LocalTrainer(3, x, y, seed=7)
    for _ in range(5):
        sel = a.draw_indices()
        xb, yb = b.draw_batch()
        np.testing.assert_array_equal(x[sel], xb)
        np.testing.assert_array_equal(y[sel], yb)
    assert a._rng.bit_generator.state == b._rng.bit_generator.state


@pytest.mark.parametrize("scan", [0, 3])
@pytest.mark.parametrize(
    "kw",
    [
        dict(num_agents=13, num_partitions=8, pi=2, rho=2),
        dict(num_agents=5, num_partitions=6, pi=2, rho=2, conditions=LOSSY,
             churn=CHURN_ALL_ACTIONS, memory=True),
    ],
    ids=["perfect-two-bucket", "lossy-churn"],
)
def test_fused_engine_advances_trainer_streams_like_scalar(data, scan, kw):
    """The fused engine draws batch rows through the trainers' own streams:
    after a run every trainer's RNG state equals the scalar engine's (offline
    agents' streams frozen alike)."""
    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(rounds=7, local_iters=1, **kw)
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=0)
    sim_s = IPLSSimulation(cfg, shards, x_te, y_te)
    sim_s.run()
    sim_v = make_simulation(
        dataclasses.replace(cfg, engine="vectorized", scan_rounds=scan),
        shards, x_te, y_te,
    )
    sim_v.run()
    trainers_v = sim_v._seed.trainers
    assert sorted(trainers_v) == sorted(sim_s.trainers)
    for a, tr in sim_s.trainers.items():
        assert trainers_v[a]._rng.bit_generator.state == tr._rng.bit_generator.state, a
