"""flops.py and bytes.py against counts made by hand."""
from benchmarks.chip import bytes as agg_bytes
from benchmarks.chip import flops
from benchmarks.chip import traffic as gen

LAYERS = [784, 500, 100, 10]


def test_mlp_flops_per_sample():
    # 2 * (784*500 + 500*100 + 100*10) forward, three times that to train
    assert flops.mlp_forward_flops(LAYERS) == 886_000
    assert flops.mlp_train_flops(LAYERS) == 2_658_000


def test_perfect_round_flops():
    config = gen.load("configs", "mnist-mlp.a100-f32")
    traffic = gen.load("traffic", "perfect")
    sgd = 100 * 10 * 128 * 2_658_000  # 340.2 GFLOP
    evals = 10 * 10_000 * 886_000 / 8  # 10 agents on the test set every 8 rounds
    assert flops.fleet_round_flops(config, traffic) == sgd + evals


def test_perfect_kernel_bytes():
    # every agent's delta slice for each partition reaches one holder (98
    # remote + 2 own per partition), each holder reads its base and writes its
    # result: (100 + 2*2) slices of 4 bytes per parameter, 443,610 parameters
    config = gen.load("configs", "mnist-mlp.a100-f32")
    traffic = gen.load("traffic", "perfect")
    got = agg_bytes.fleet_agg_bytes(config, traffic, seed=5, rounds=range(8, 16))
    assert got == 8 * (100 + 4) * 4 * 443_610


def test_lossy_int8_kernel_bytes_count_delivered_codes():
    config = gen.load("configs", "mnist-mlp.a32-int8")
    traffic = gen.load("traffic", "lossy")
    rounds = range(8, 10)
    delivered = agg_bytes.delivered_updates(config, traffic, 9, rounds)
    sizes = agg_bytes.partition_sizes(443_610, 10)
    # 30 remote senders per partition and round, 15% lost
    assert 0.8 * 30 * 2 < sum(delivered) / 10 < 0.9 * 30 * 2
    want = sum(
        2 * 3 * 4 * s * 2 + d * (s + 4 * -(-s // 1024)) for s, d in zip(sizes, delivered)
    )
    assert agg_bytes.fleet_agg_bytes(config, traffic, 9, rounds) == want
