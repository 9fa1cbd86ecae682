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


# ----------------------------------------------------- language models ---
def _arch(name):
    from repro.configs.registry import get_config

    return get_config(name, reduced=True)


def test_dense_gqa_lm_flops_by_hand():
    # internlm2 reduced: 2 layers, d 64, 4 heads / 2 kv of 16, SwiGLU 128, vocab 256
    S = 8
    proj = 2 * 64 * (4 * 16 + 2 * 2 * 16) + 2 * 4 * 16 * 64  # q, k, v, o per token
    attn = S * proj + (S * (S + 1) // 2) * 2 * 4 * (16 + 16)  # 36 causal pairs
    mlp = S * 3 * 2 * 64 * 128
    head = (S - 1) * 2 * 64 * 256
    fwd = 2 * (attn + mlp) + head
    assert fwd == 1_427_456
    assert flops.lm_forward_flops(_arch("internlm2-1.8b"), S) == fwd
    assert flops.lm_train_flops(_arch("internlm2-1.8b"), S) == 3 * fwd


def test_mla_and_moe_lm_flops_by_hand():
    # deepseek-v2-lite reduced: MLA (d 64, 4 heads, kv_lora 32, nope 16, rope 8,
    # v 16); one dense layer (SwiGLU 128), then 2 MoE layers of 8 experts of
    # 32, top-2, plus 2 shared experts of total width 64; vocab 256
    S = 8
    mla_tok = (2 * 64 * 4 * 24 + 2 * 64 * 32 + 2 * 64 * 8
               + 2 * 32 * 4 * (16 + 16) + 2 * 4 * 16 * 64)
    mla = S * mla_tok + 36 * 2 * 4 * (24 + 16)
    dense = S * 3 * 2 * 64 * 128
    moe = S * (2 * 64 * 8 + 2 * 3 * 2 * 64 * 32 + 3 * 2 * 64 * 64)
    head = (S - 1) * 2 * 64 * 256
    fwd = (mla + dense) + 2 * (mla + moe) + head
    assert fwd == 2_270_976
    assert flops.lm_forward_flops(_arch("deepseek-v2-lite-16b"), S) == fwd


def test_moe_flops_count_the_work_the_model_requires(monkeypatch):
    # the count is the MoESpec's top_k experts a token, not what the program's
    # parameters hold: with the layer's expert weights cut to 2 of the 8, the
    # reduced deepseek-v2-lite still counts as in test_mla_and_moe_lm_flops_by_hand
    import dataclasses

    from repro.models import layers

    real = layers.init_moe

    def hold_two(s):
        defs = real(s)
        for k in ("wg", "wu", "wd"):
            defs[k] = dataclasses.replace(defs[k], shape=(2,) + defs[k].shape[1:])
        return defs

    monkeypatch.setattr(layers, "init_moe", hold_two)
    assert layers.init_moe(_arch("deepseek-v2-lite-16b").groups[1].blocks[1].moe)["wg"].shape[0] == 2
    assert flops.lm_forward_flops(_arch("deepseek-v2-lite-16b"), 8) == 2_270_976


def test_windowed_attention_counts_the_window():
    import dataclasses

    from repro.configs.base import attn_block
    from repro.models.transformer import ArchConfig, GroupSpec

    b = attn_block(64, 4, 2, 16, window=3)
    arch = ArchConfig(name="w", vocab=16, d_model=64, groups=(GroupSpec(blocks=(b,), repeat=1),))
    # positions see 1, 2, 3, 3, 3, 3, 3, 3 keys
    assert flops.causal_pairs(8, 3) == 21
    proj = 2 * 64 * (4 * 16 + 2 * 2 * 16) + 2 * 4 * 16 * 64
    assert flops.lm_forward_flops(arch, 8) == 8 * proj + 21 * 256 + 7 * 2 * 64 * 16
    full = dataclasses.replace(arch, groups=(GroupSpec(blocks=(attn_block(64, 4, 2, 16),)),))
    assert flops.lm_forward_flops(full, 8) - flops.lm_forward_flops(arch, 8) == (36 - 21) * 256


def test_mesh_round_flops_counts_every_row():
    from benchmarks.chip.entries import mesh_step

    config = gen.load("configs", "internlm2-1.8b.data4")
    traffic = gen.load("traffic", "train4k")
    arch = mesh_step.arch_config(config)
    one = flops.lm_train_flops(arch, 4096)
    assert 46e12 < one < 47e12  # ~6 x 1.89e9 params x 4,096 + attention
    assert mesh_step.round_flops(config, traffic) == 4 * one
