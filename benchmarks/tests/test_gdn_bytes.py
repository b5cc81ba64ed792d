"""`lib/gdn_bytes.py` against hand counts for the `qwen3-next-80b-a3b`
configuration file (one period: 3 Gated DeltaNet layers, 1 gated-attention
layer, 512 experts a layer): weights, state a slot, K/V a token, a decode
step's bytes, active FLOPs a token."""

import json
import os

from conftest import BENCH
from lib import gdn_bytes

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "qwen3-next-80b-a3b.json")))
TPU = CONFIG["tpu"]


def test_the_pattern_comes_from_the_interval():
    assert gdn_bytes.layer_kinds(CONFIG) == ["linear_attention"] * 3 + [
        "full_attention"]
    full = dict(CONFIG, num_hidden_layers=48)
    kinds = gdn_bytes.layer_kinds(full)
    assert kinds.count("full_attention") == 12 and kinds[3] == kinds[47]


def test_one_layer_of_each_kind():
    linear = (2048 * 12288 + 12288 * 4       # q|k|v|z int8 + f32 scales
              + 4096 * 2048 + 2048 * 4       # out_proj
              + 2048 * 64 * 2                # b|a, bf16
              + 4 * 8192 * 2                 # conv taps, bf16, no bias
              + (2048 + 128) * 2             # the layer norm, the head norm
              + 2 * 32 * 4)                  # A_log, dt_bias
    assert gdn_bytes.linear_weight_bytes(CONFIG, TPU) == linear
    assert 33.6e6 < 2048 * 12288 + 4096 * 2048 + 2048 * 64 < 33.8e6
    attn = (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
            + (8192 + 2 * 512 + 2048) * 4 + (2048 + 2 * 256) * 2)
    assert gdn_bytes.attention_weight_bytes(CONFIG, TPU) == attn
    assert 27.2e6 < 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 < 27.3e6
    expert = 3 * 2048 * 512 + (2 * 512 + 2048) * 4
    assert gdn_bytes.expert_weight_bytes(CONFIG, TPU) == expert
    assert 3 * 2048 * 512 == 3_145_728               # the issue's 3.146 M
    fixed = (3 * 2048 * 512 + (2 * 512 + 2048) * 4   # the shared expert
             + 2048 * 512 * 2 + 2 * 2048 * 2)        # router, gate, norm
    assert gdn_bytes.ffn_fixed_bytes(CONFIG, TPU) == fixed
    # a layer's 512 experts are 1.611 GB
    assert 1.610e9 < 512 * 3 * 2048 * 512 < 1.612e9


def test_state_and_kv_of_a_slot():
    per_slot = gdn_bytes.state_bytes_per_slot(CONFIG, TPU)
    assert per_slot["ssm"] == 3 * 32 * 128 * 128 * 4 == 6_291_456
    assert per_slot["conv"] == 3 * 3 * 8192 * 2 == 147_456
    assert 0.82e9 < 128 * sum(per_slot.values()) < 0.83e9
    # ONE attention layer: K and V x 2 heads x (256 int8 + one f32 scale)
    assert gdn_bytes.kv_bytes_per_token(CONFIG, TPU) == 2 * 2 * 260 == 1040
    bf16 = dict(TPU, kv_quantization=None)
    assert gdn_bytes.kv_bytes_per_token(CONFIG, bf16) == 2 * 512 * 2


def test_a_decode_step_moves_the_experts_hit_and_the_state_once_each_way():
    full = gdn_bytes.decode_step_bytes(CONFIG, TPU, 128 * 300, 128)
    hit = 512 * (1 - (1 - 1 / 512) ** 1280)          # 1,280 pairs: 91.8%
    assert 469 < hit < 471
    weights = (3 * gdn_bytes.linear_weight_bytes(CONFIG, TPU)
               + gdn_bytes.attention_weight_bytes(CONFIG, TPU)
               + 4 * (hit * gdn_bytes.expert_weight_bytes(CONFIG, TPU)
                      + gdn_bytes.ffn_fixed_bytes(CONFIG, TPU))
               + 2048 * 2 + 2048 * 151936 + 151936 * 4)   # norm, int8 head
    state = 2 * 128 * (6_291_456 + 147_456)
    by_hand = weights + state + 128 * 300 * 1040 + 128 * 2048 * 2
    assert abs(full - by_hand) < 1e3
    assert 5.9e9 < 4 * hit * 3 * 2048 * 512 < 5.95e9     # the issue's 5.93 GB
    assert 1.64e9 < state < 1.66e9                       # the issue's 1.65 GB
    assert 8.0e9 < full < 8.2e9                          # ~8.1 GB: 9.9 ms
    # an idle engine still steps every lane's state
    idle = gdn_bytes.decode_step_bytes(CONFIG, TPU, 0, 0)
    assert idle > weights + state - 1e3


def test_active_flops_of_a_token_and_of_a_prompt():
    linear = (2 * 2048 * 12288 + 2 * 2048 * 64 + 2 * 4096 * 2048
              + 2 * 4 * 8192 + 7 * 32 * 128 * 128)
    attn = 2 * 2048 * 8192 + 2 * 2048 * 512 * 2 + 2 * 4096 * 2048
    ffn = (2 * 2048 * 512 + 10 * 6 * 2048 * 512 + 6 * 2048 * 512
           + 2 * 2048)
    by_hand = 3 * linear + attn + 4 * ffn
    assert gdn_bytes.active_flops_per_token(CONFIG) == by_hand
    assert 0.54e9 < by_hand < 0.56e9
    # all 512 experts would be 47x the 11 a token uses: never counted
    assert 4 * 512 * 6 * 2048 * 512 > 20 * by_hand
    s = 179
    prompt = gdn_bytes.prefill_flops(CONFIG, s)
    assert prompt == (s * by_hand + 4 * 4096 * s * (s + 1) / 2
                      + 2 * 2048 * 151936)
