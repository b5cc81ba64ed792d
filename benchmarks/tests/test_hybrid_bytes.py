"""`lib/hybrid_bytes.py` against hand counts for the `granite-4.0-h-small`
configuration file (one period: 9 mamba layers, 1 attention layer): weights,
state a slot, K/V a token, a decode step's bytes, active FLOPs a token."""

import json
import os

from conftest import BENCH
from lib import hybrid_bytes

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "granite-4.0-h-small.json")))
TPU = CONFIG["tpu"]


def test_one_layer_of_each_kind():
    mamba = (4096 * 16768 + 16768 * 4        # in_proj int8 + f32 scales
             + 8192 * 4096 + 4096 * 4        # out_proj
             + (4 + 1) * 8448 * 2            # conv taps and bias, bf16
             + (4096 + 8192) * 2             # the two norms
             + 3 * 128 * 4)                  # dt_bias, A_log, D
    assert hybrid_bytes.mamba_weight_bytes(CONFIG, TPU) == mamba
    assert 102.3e6 < mamba < 102.5e6         # the issue's 102.3 M
    attn = (2 * 4096 * 4096 + 2 * 4096 * 1024
            + (2 * 4096 + 2 * 1024) * 4 + 4096 * 2)
    assert hybrid_bytes.attention_weight_bytes(CONFIG, TPU) == attn
    expert = 3 * 4096 * 768 + (2 * 768 + 4096) * 4
    assert hybrid_bytes.expert_weight_bytes(CONFIG, TPU) == expert
    fixed = (3 * 4096 * 1536 + (2 * 1536 + 4096) * 4   # the shared expert
             + 4096 * 72 * 2 + 4096 * 2)               # router, norm
    assert hybrid_bytes.ffn_fixed_bytes(CONFIG, TPU) == fixed
    # 72 experts are 679.5 M of a layer's parameters
    assert 679e6 < 72 * 3 * 4096 * 768 < 680e6


def test_state_and_kv_of_a_slot():
    per_slot = hybrid_bytes.state_bytes_per_slot(CONFIG, TPU)
    assert per_slot["ssm"] == 9 * 128 * 64 * 128 * 4 == 37_748_736
    assert per_slot["conv"] == 9 * 3 * 8448 * 2 == 456_192
    # 128 slots: 4.83 GB of state
    assert 4.83e9 < 128 * per_slot["ssm"] < 4.84e9
    # ONE attention layer: K and V x 8 heads x (128 int8 + one f32 scale)
    assert hybrid_bytes.kv_bytes_per_token(CONFIG, TPU) == 2 * 8 * 132
    bf16 = dict(TPU, kv_quantization=None)
    assert hybrid_bytes.kv_bytes_per_token(CONFIG, bf16) == 2 * 1024 * 2


def test_a_decode_step_moves_the_weights_once_and_the_state_twice():
    full = hybrid_bytes.decode_step_bytes(CONFIG, TPU, 128 * 300, 128)
    weights = (9 * hybrid_bytes.mamba_weight_bytes(CONFIG, TPU)
               + hybrid_bytes.attention_weight_bytes(CONFIG, TPU)
               + 10 * (72 * hybrid_bytes.expert_weight_bytes(CONFIG, TPU)
                       + hybrid_bytes.ffn_fixed_bytes(CONFIG, TPU))
               + 4096 * 2 + 4096 * 100352 * 2)       # final norm, tied head
    state = 2 * 128 * (37_748_736 + 456_192)
    by_hand = weights + state + 128 * 300 * 2112 + 128 * 4096 * 2
    # 1,280 pairs over 72 experts miss one with probability 1.7e-8
    assert abs(full - by_hand) < 1e3
    assert 8.7e9 < weights < 8.9e9 and 9.7e9 < state < 9.8e9
    assert 18.5e9 < full < 18.7e9                    # 22.7 ms at 819 GB/s
    # an idle engine still steps every lane's state
    idle = hybrid_bytes.decode_step_bytes(CONFIG, TPU, 0, 0)
    assert idle > weights + state - 1e3


def test_active_flops_of_a_token_and_of_a_prompt():
    mamba = (2 * 4096 * 16768 + 2 * 8192 * 4096 + 2 * 4 * 8448
             + 5 * 128 * 64 * 128)
    attn = 2 * 4096 * 4096 * 2 + 2 * 4096 * 1024 * 2
    ffn = 2 * 4096 * 72 + 10 * 6 * 4096 * 768 + 6 * 4096 * 1536
    by_hand = 9 * mamba + attn + 10 * ffn
    assert hybrid_bytes.active_flops_per_token(CONFIG) == by_hand
    assert 4.2e9 < by_hand < 4.4e9                   # the issue's ~4.2 GFLOP
    # all 72 experts would be 4.7x that: the counts never include them
    assert 10 * 72 * 6 * 4096 * 768 > 3 * by_hand
    s = 179
    prompt = hybrid_bytes.prefill_flops(CONFIG, s)
    assert prompt == (s * by_hand + 4 * 4096 * s * (s + 1) / 2
                      + 2 * 4096 * 100352)
