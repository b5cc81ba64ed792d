"""`lib/nh_bytes.py` against hand counts at the published configuration: the
held experts at TWO matrices, the state read and written, the share's pairs,
and what must stay out of the counts (the absent experts, the chunked form's
products, the padding of a stored width)."""

import json
import os

import pytest

from conftest import BENCH
from lib import nh_bytes
from lib.moe_bytes import experts_hit

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "nemotron-3-nano-30b-a3b.json")))
TPU = CONFIG["tpu"]


def test_the_dims_are_the_published_ones_and_the_share_is_a_quarter():
    d = nh_bytes._dims(CONFIG)
    assert (d["mamba"], d["attn"], d["moe"]) == (23, 6, 23)
    assert (d["inner"], d["conv"], d["proj"]) == (4096, 6144, 10304)
    assert (d["held"], d["routed_over"], d["k"]) == (32, 128, 6)
    assert (d["q"], d["kv"], d["f"], d["fs"]) == (4096, 256, 1856, 3712)
    assert nh_bytes.held_pairs(CONFIG, 64) == 64 * 6 / 4 == 96
    # a file without the share's keys holds all it routes over
    whole = {k: v for k, v in CONFIG.items()
             if k not in ("experts_routed_over", "experts_held")}
    whole["n_routed_experts"] = 128
    assert nh_bytes.held_pairs(whole, 64) == 64 * 6


def test_weight_bytes_by_hand_int8_with_a_scale_a_column():
    # one mamba block: in_proj 2688 x 10304, out_proj 4096 x 2688, conv
    # (4 taps + bias) x 6144 and the two norms in bf16, three f32 a head
    mamba = ((2688 * 10304 + 4 * 10304) + (4096 * 2688 + 4 * 2688)
             + 5 * 6144 * 2 + (2688 + 4096) * 2 + 3 * 64 * 4)
    assert nh_bytes.mamba_weight_bytes(CONFIG, TPU) == mamba
    attn = (2 * (2688 * 4096 + 4 * 4096) - 4 * 4096 + 4 * 2688
            + 2 * (2688 * 256 + 4 * 256) + 2688 * 2)
    assert nh_bytes.attention_weight_bytes(CONFIG, TPU) == attn
    # an expert is TWO matrices of the PUBLISHED width, never the stored one
    expert = (2688 * 1856 + 4 * 1856) + (1856 * 2688 + 4 * 2688)
    assert nh_bytes.expert_weight_bytes(CONFIG, TPU) == expert
    fixed = ((2688 * 3712 + 4 * 3712) + (3712 * 2688 + 4 * 2688)
             + 2688 * 128 * 2 + 128 * 4 + 2688 * 2)
    assert nh_bytes.ffn_fixed_bytes(CONFIG, TPU) == fixed
    assert nh_bytes.head_bytes(CONFIG, TPU) == 2688 * 131072 + 4 * 131072


def test_the_state_counts_twice_and_the_kv_once():
    per_slot = nh_bytes.state_bytes_per_slot(CONFIG, TPU)
    assert per_slot == {"ssm": 23 * 64 * 64 * 128 * 4,
                        "conv": 23 * 3 * 6144 * 2}
    assert nh_bytes.state_layer_bytes(CONFIG, TPU) == (
        2 * 64 * 64 * 64 * 128 * 4)
    # 6 attention blocks x (K and V) x 2 heads x (128 int8 + one f32 scale)
    assert nh_bytes.kv_bytes_per_token(CONFIG, TPU) == 6 * 2 * 2 * 132
    idle = nh_bytes.decode_step_bytes(CONFIG, TPU, 0, 0)
    live = nh_bytes.decode_step_bytes(CONFIG, TPU, 64 * 300, 64)
    assert live - idle == 64 * 300 * 6 * 2 * 2 * 132 + 64 * 2688 * 2
    state = 2 * 64 * (per_slot["ssm"] + per_slot["conv"])
    weights = idle - state
    # the issue's arithmetic: ~9.2 GB of weights (7.3 of them experts when
    # all 32 are hit) and 3.1 GB of state each way
    assert 6.1e9 < state < 6.4e9
    hit = experts_hit(96, 32)
    assert 30.0 < hit < 31.0
    experts = 23 * hit * nh_bytes.expert_weight_bytes(CONFIG, TPU)
    assert abs(nh_bytes.experts_step_bytes(CONFIG, TPU, 64) * 23
               - experts) < 1
    assert 6.8e9 < experts < 7.2e9 and 8.6e9 < weights < 9.2e9


def test_the_active_flops_count_held_pairs_alone_at_two_matrices():
    per_token = nh_bytes.active_flops_per_token(CONFIG)
    state_elems = 64 * 64 * 128
    mamba = (2 * 2688 * 10304 + 2 * 4096 * 2688 + 2 * 4 * 6144
             + 5 * state_elems)
    attn = 2 * 2688 * 4096 * 2 + 2 * 2 * 2688 * 256
    ffn = (2 * 2688 * 128 + 1.5 * 2 * 2 * 2688 * 1856
           + 2 * 2 * 2688 * 3712)
    assert per_token == pytest.approx(23 * mamba + 6 * attn + 23 * ffn)
    assert nh_bytes.expert_flops_per_token(CONFIG) == pytest.approx(
        1.5 * 4 * 2688 * 1856)
    # a prompt: its tokens, causal attention in 6 blocks, one head row
    s = 100
    assert nh_bytes.prefill_flops(CONFIG, s) == pytest.approx(
        s * per_token + 6 * 4 * 4096 * s * (s + 1) / 2 + 2 * 2688 * 131072)
    # all 128 experts would be four times the expert term
    whole = dict(CONFIG, n_routed_experts=128)
    whole.pop("experts_routed_over")
    assert nh_bytes.expert_flops_per_token(whole) == pytest.approx(
        4 * nh_bytes.expert_flops_per_token(CONFIG))


def test_bf16_serving_doubles_the_matrices_and_keeps_the_state():
    bf16 = dict(TPU, quantization=None, kv_quantization=None)
    assert nh_bytes.expert_weight_bytes(CONFIG, bf16) == 2 * 2 * 2688 * 1856
    assert nh_bytes.kv_bytes_per_token(CONFIG, bf16) == 6 * 2 * 256 * 2
    assert nh_bytes.state_bytes_per_slot(CONFIG, bf16) == (
        nh_bytes.state_bytes_per_slot(CONFIG, TPU))
