"""`lib/sconv_bytes.py` against counts made here from the keys of the
`lfm2-8b-a1b` configuration file (18 conv layers, 6 attention layers, 2
dense FFNs, 22 x 32 experts): weights, tails a slot, K/V a token, a decode
step's bytes, active FLOPs a token."""

import json
import os

from conftest import BENCH
from lib import sconv_bytes

CONFIG = json.load(open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")))
TPU = CONFIG["tpu"]
H, FD, F, X, K, V = 2048, 7168, 1792, 32, 4, 65536


def test_the_files_keys_are_the_counts_keys():
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"],
            CONFIG["moe_intermediate_size"], CONFIG["num_experts"],
            CONFIG["num_experts_per_tok"], CONFIG["vocab_size"]) == (
        H, FD, F, X, K, V)
    kinds = CONFIG["layer_types"]
    assert (len(kinds), kinds.count("conv"), kinds.count("full_attention"),
            CONFIG["num_dense_layers"]) == (24, 18, 6, 2)
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        2, 6, 10, 14, 18, 21]


def test_one_layer_of_each_kind():
    conv = (H * 3 * H + 3 * H * 4          # B|C|x int8 + f32 scales
            + H * H + H * 4                # out_proj
            + 3 * H * 2 + H * 2)           # taps and the layer norm, bf16
    assert sconv_bytes.conv_weight_bytes(CONFIG, TPU) == conv
    attn = (2 * H * H + 2 * H * 512 + (2 * H + 2 * 512) * 4
            + (H + 2 * 64) * 2)
    assert sconv_bytes.attention_weight_bytes(CONFIG, TPU) == attn
    dense = 3 * H * FD + (2 * FD + H) * 4 + H * 2
    assert sconv_bytes.dense_ffn_bytes(CONFIG, TPU) == dense
    expert = 3 * H * F + (2 * F + H) * 4
    assert sconv_bytes.expert_weight_bytes(CONFIG, TPU) == expert
    assert sconv_bytes.moe_fixed_bytes(CONFIG, TPU) == (
        H * X * 2 + H * 2 + X * 4)
    # the issue's table: 7.75 GB of experts, 0.09 + 0.30 + 0.06 + 0.27
    assert 7.74e9 < 22 * X * 3 * H * F < 7.76e9
    assert 0.29e9 < 18 * (H * 3 * H + H * H) < 0.31e9
    assert 0.062e9 < 6 * (2 * H * H + 2 * H * 512) < 0.064e9


def test_the_whole_models_weights_are_half_the_chip():
    total = sconv_bytes.weight_bytes(CONFIG, TPU)
    assert 8.47e9 < total < 8.52e9                   # the issue's 8.48 GB
    params = (22 * X * 3 * H * F + 2 * 3 * H * FD + 18 * 4 * H * H
              + 6 * (2 * H * H + 2 * H * 512) + V * H)
    assert 8.33e9 < params < 8.35e9                  # 8.34 B parameters
    active = (22 * K * 3 * H * F + 2 * 3 * H * FD + 18 * 4 * H * H
              + 6 * (2 * H * H + 2 * H * 512) + V * H)
    assert 1.55e9 < active < 1.57e9                  # 1.56 B active


def test_tails_and_kv_of_a_slot():
    assert sconv_bytes.state_bytes_per_slot(CONFIG, TPU) == (
        18 * 2 * H * 2) == 147_456
    # six attention layers: K and V x 8 heads x (64 int8 + one f32 scale)
    assert sconv_bytes.kv_bytes_per_token(CONFIG, TPU) == (
        6 * 2 * 8 * 68) == 6_528
    bf16 = dict(TPU, kv_quantization=None)
    assert sconv_bytes.kv_bytes_per_token(CONFIG, bf16) == 6 * 2 * 512 * 2
    assert 0.53e9 < 128 * 640 * 6_528 < 0.54e9


def test_a_decode_step_streams_every_expert_and_moves_each_tail_both_ways():
    full = sconv_bytes.step_bytes(CONFIG, TPU, 128 * 300, 128)
    hit = X * (1 - (1 - 1 / X) ** (128 * K))         # 512 pairs: every one
    assert X - hit < 1e-5
    by_hand = (sconv_bytes.weight_bytes(CONFIG, TPU, hit)
               + 2 * 128 * 147_456 + 128 * 300 * 6_528 + 128 * H * 2)
    assert abs(full - by_hand) < 1e3
    assert abs(sconv_bytes.weight_bytes(CONFIG, TPU, hit)
               - sconv_bytes.weight_bytes(CONFIG, TPU)) < 1e4
    assert 8.7e9 < full < 8.9e9                      # ~8.8 GB: 10.7 ms
    # the experts are 91% of what a step streams
    assert 0.90 < 22 * X * sconv_bytes.expert_weight_bytes(
        CONFIG, TPU) / sconv_bytes.weight_bytes(CONFIG, TPU) < 0.92
    # fewer slots hit fewer experts: 8 slots, 32 pairs
    small = dict(TPU, max_batch_size=8)
    assert sconv_bytes.step_bytes(CONFIG, small, 0, 0) < 0.75 * full
    # an idle engine still steps every lane's tail
    idle = sconv_bytes.step_bytes(CONFIG, TPU, 0, 0)
    assert idle > sconv_bytes.weight_bytes(CONFIG, TPU, hit) \
        + 2 * 128 * 147_456 - 1e3


def test_active_flops_of_a_token_and_of_a_prompt():
    conv = 2 * H * 3 * H + 2 * H * H + 2 * 3 * H + 2 * H
    attn = 2 * H * H + 2 * 2 * H * 512 + 2 * H * H
    dense = 6 * H * FD
    moe = 2 * H * X + K * 6 * H * F
    by_hand = 18 * conv + 6 * attn + 2 * dense + 22 * moe
    assert sconv_bytes.active_flops_per_token(CONFIG) == by_hand
    assert 2.8e9 < by_hand < 2.9e9          # 2 x the 1.43 B active matrices
    # all 32 experts would be 8x the 4 a token uses: never counted
    assert 22 * X * 6 * H * F > 5 * by_hand
    s = 179
    assert sconv_bytes.prefill_flops(CONFIG, s) == (
        s * by_hand + 6 * 4 * H * s * (s + 1) / 2 + 2 * H * V)
