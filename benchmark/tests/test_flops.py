"""The FLOPs and bytes functions against counts made by hand for the
configurations."""
import json
import os

import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_cerebras_gpt_full_depth_counts():
    cfg = config("cerebras-gpt-1.3b")
    # per block: qkv 3 d^2, out d^2, two MLP matrices of d x 4d
    block = 12 * 2048 * 2048                       # 50 331 648
    head = 50257 * 2048                            # 102 926 336
    assert flops.lm_matmul_params(cfg) == 24 * block + head == 1_310_885_888
    # everything: + token and position embeddings, biases, norms
    per_block_rest = (3 + 1 + 4 + 1) * 2048 + 4 * 2048
    total = (24 * (block + per_block_rest) + head + 50257   # head + bias
             + 50257 * 2048 + 2048 * 2048                   # embeddings
             + 2 * 2048)                                    # final norm
    assert flops.lm_params(cfg) == total == 1_418_699_857
    # one decode step at 16 slots of 300 tokens, float32 weights and KV
    kv = 16 * 300 * 2 * 24 * 2048 * 4
    assert flops.lm_decode_bytes(cfg, [300] * 16) == 1_310_885_888 * 4 + kv
    assert 2 * 24 * 2048 * 4 == 393_216            # KV bytes a token


def test_cerebras_gpt_l8_train_flops():
    cfg = config("cerebras-gpt-1.3b-l8")
    matmul = 8 * 12 * 2048 * 2048 + 50257 * 2048   # 505 579 520
    assert flops.lm_matmul_params(cfg) == matmul == 505_579_520
    assert flops.lm_params(cfg) == 612_967_505
    per_token = 6 * matmul + 12 * 8 * 2048 * 2048  # unmasked attention
    assert flops.lm_train_flops_per_token(cfg, 2048) == per_token
    # 8192 tokens a step
    assert abs(per_token * 8192 / 1e12 - 28.15) < 0.01


def test_roofline_picks_the_larger_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(197e12, 819e9 / 2, peaks) == (1.0, "flops")
    assert flops.roofline_seconds(197e12 / 4, 819e9, peaks) == (1.0, "bytes")
    # 16 bytes a float32 parameter, plus batch and outputs
    assert flops.train_step_bytes(10, 3, 5) == 168


def test_a_step_over_four_chips_counts_each_chips_share():
    """The four-chip rehearsal on the v5e read 137.7 % before the reader
    divided by the chips: four chips' operations over one chip's peak."""
    import manifest

    reader = manifest.load_module("metrics", "train_step_roofline")
    cfg = config("cerebras-gpt-1.3b-l8")
    run = {"facts": {"train_flops_per_item":
                     flops.lm_train_flops_per_token(cfg, 2048),
                     "items_per_step": 4 * 2048,
                     "n_params": flops.lm_params(cfg),
                     "batch_bytes": 65536, "output_bytes": 823410688},
           "trace": {"modules": {"jit_step": (2, 0.648)}},
           "peaks": manifest.load_peaks("TPU v5 lite"), "chips": 1}
    one = reader.read(run)
    assert abs(one - 44.1) < 0.1                   # the cell's own reading
    run["facts"] = dict(run["facts"], items_per_step=16 * 2048,
                        batch_bytes=4 * 65536, output_bytes=4 * 823410688)
    run["chips"] = 4
    assert abs(reader.read(run) - one) < 0.2       # same share of a chip
