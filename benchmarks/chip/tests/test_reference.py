"""The plain reference against the program on the CPU: the generator, the
wire codec and whole rounds of the protocol, at a small size."""
import numpy as np
import pytest

from benchmarks.chip import traffic as gen
from benchmarks.chip.reference import compare, ipls


def test_generator_matches_the_programs():
    from repro.data import iid_split, synth_mnist

    ours = gen.mnist_like(300, 50, 10, 0.25, seed=7)
    theirs = synth_mnist(num_train=300, num_test=50, seed=7)
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(a, b)
    x, y = ours[0], ours[1]
    for (xa, ya), (xb, yb) in zip(gen.iid_shards(x, y, 4, 3), iid_split(x, y, 4, seed=3)):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def test_codec_and_fates_match_the_programs():
    from repro.core.wire import _np_quantize
    from repro.p2p.network import LOSSY

    x = np.random.default_rng(0).standard_normal(5000).astype(np.float32) * 1e-3
    err = np.random.default_rng(1).standard_normal(5000).astype(np.float32) * 1e-5
    q, s, e = ipls.quantize(x, err)
    q2, s2, e2 = _np_quantize(x, err)
    np.testing.assert_array_equal(q, q2[:5000])
    np.testing.assert_array_equal(s, s2)
    np.testing.assert_array_equal(e, e2)
    net = {"loss_prob": 0.15, "delay_prob": 0.25, "max_delay_ticks": 2}
    for key in [(2, 5, 3, 1, 0), (4, 0, 9, 2, 7), (3, 11, 1, 0, 2)]:
        ok, d = LOSSY.sample_stream(2**31 + 9, *key)
        assert ipls.message_fate(net, 2**31 + 9, *key) == (bool(ok), int(d))


def test_join_rule_matches_the_programs():
    from repro.core.partition import PartitionTable

    table = PartitionTable(10, 2, 2)
    table.bootstrap(0)
    for a in range(1, 30):
        table.join(a)
    _, holders = ipls.assign_partitions(30, 10, 2, 2)
    assert holders == [table.holders_of(k) for k in range(10)]


@pytest.mark.parametrize("spec_name", ["perfect_spec", "lossy_spec"])
def test_reference_follows_the_program(spec_name, request):
    """Program (vectorized engine, the CPU's XLA path) and reference agree to
    float rounding and count the same traffic exactly."""
    from benchmarks.chip.entries import fused_engine

    spec = request.getfixturevalue(spec_name)
    run = fused_engine.build(spec["config"], spec["traffic"], seed=2**31 + 17)
    run.free()
    got = run.readings()
    assert got["traffic_gap"] == 0
    assert got["change_gap"] < 1e-5 and got["loss_gap"] < 1e-5
    assert set(spec["limits"]) <= set(got)


def test_leaf_rule_and_norms():
    layers = [(784, 500), (500, 100), (100, 10)]
    w0 = ipls.init_weights(layers, 3)
    assert w0.shape == (443_610,)
    models = np.stack([w0 + 1.0, w0 + 2.0])
    n = compare.leaf_change_norms(models, w0, layers)
    assert n["b2"] == pytest.approx(np.sqrt(10 * (1 + 4)))
