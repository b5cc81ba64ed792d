"""`lib/mla_bytes.py` against hand counts at the cut configuration's widths:
the weights the chip holds, the latent row, a decode step's bytes and a
prefill's active FLOPs in the expanded form."""

import json
import os

from conftest import BENCH
from lib import mla_bytes
from lib.moe_bytes import experts_hit

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "kanana-2-30b-a3b.json")))
TPU = CONFIG["tpu"]
H, HEADS, R, ROPE, NOPE, V = 2048, 32, 512, 64, 128, 128


def int8(k, n):
    return k * n + 4 * n


def test_one_layers_attention_is_four_matrices_and_two_norms():
    want = (int8(H, HEADS * (NOPE + ROPE)) + int8(H, R + ROPE)
            + int8(R, HEADS * (NOPE + V)) + int8(HEADS * V, H)
            + (H + R) * 2)
    assert mla_bytes.attention_weight_bytes(CONFIG, TPU) == want
    assert 26.3e6 < want < 26.6e6           # the issue's 26.35 M + scales


def test_the_expert_layers_and_the_dense_one():
    assert mla_bytes.expert_weight_bytes(CONFIG, TPU) == (
        2 * int8(H, 768) + int8(768, H))
    assert mla_bytes.dense_ffn_bytes(CONFIG, TPU) == (
        2 * int8(H, 6144) + int8(6144, H) + 2 * H)
    assert mla_bytes.moe_fixed_bytes(CONFIG, TPU) == (
        H * 128 * 2 + 128 * 4 + H * 2 + 2 * int8(H, 1536) + int8(1536, H))


def test_the_whole_model_is_what_the_chip_was_seen_to_hold():
    total = mla_bytes.weight_bytes(CONFIG, TPU)
    # jax.eval_shape of the program's init: 5,415,397,888 B with the
    # bfloat16 absorbed factors (67,108,864 B), which this count leaves out
    assert abs(total - (5_415_397_888 - 67_108_864)) < 2e6
    seven = 7 * 128 * mla_bytes.expert_weight_bytes(CONFIG, TPU)
    assert 0.79 < seven / total < 0.82


def test_a_cached_position_is_one_row_of_576_values_in_640_lanes():
    assert mla_bytes.latent_row_bytes(CONFIG, TPU) == 576 * 2
    assert mla_bytes.cache_bytes_per_token(CONFIG, TPU) == 8 * 640 * 2
    assert mla_bytes.cache_bytes_per_token(CONFIG, TPU) * 64 * 11776 == \
        8 * 64 * 11776 * 640 * 2
    expanded = 8 * 32 * (192 + 128) * 2
    assert expanded / (8 * 576 * 2) > 17


def test_a_decode_steps_bytes_are_weights_hit_experts_and_live_rows():
    live, slots = 64 * 8300.0, 64.0
    hit = experts_hit(slots * 6, 128)
    assert 120 < hit < 128
    want = (8 * mla_bytes.attention_weight_bytes(CONFIG, TPU)
            + mla_bytes.dense_ffn_bytes(CONFIG, TPU)
            + 7 * (hit * mla_bytes.expert_weight_bytes(CONFIG, TPU)
                   + mla_bytes.moe_fixed_bytes(CONFIG, TPU))
            + H * 2 + int8(H, 128256)
            + live * 8 * 576 * 2 + slots * H * 2)
    got = mla_bytes.decode_step_bytes(CONFIG, TPU, live, slots)
    assert abs(got - want) < 1
    latent = mla_bytes.latent_step_bytes(CONFIG, TPU, live)
    assert latent == live * 8 * 1152
    assert 0.45 < latent / got < 0.55        # half of a step is latents
    # an empty engine still streams what every step multiplies by
    assert mla_bytes.decode_step_bytes(CONFIG, TPU, 0, 0) > 0.5e9


def test_the_kernels_call_is_bound_by_its_bytes():
    live = 64 * 8300.0
    nbytes = mla_bytes.kernel_bytes(CONFIG, TPU, live, 64)
    flops = mla_bytes.kernel_flops(CONFIG, live)
    assert nbytes == live * 1152 + 64 * 32 * (576 + 512) * 2
    assert flops == 2 * live * 32 * (576 + 512)
    assert 55 < flops / nbytes < 61          # under the v5e's ridge of ~240
    assert nbytes / 819e9 > flops / 197e12


def test_a_prefills_active_flops_in_the_expanded_form():
    per_token = mla_bytes.active_flops_per_token(CONFIG)
    attn = 2 * (H * 32 * 192 + H * 576 + R * 32 * 256 + 32 * 128 * H)
    moe = 2 * H * 128 + 6 * 6 * H * 768 + 6 * H * 1536
    assert per_token == 8 * attn + 6 * H * 6144 + 7 * moe
    s = 8192
    pairs = s * (s + 1) // 2
    assert mla_bytes.attention_flops(CONFIG, s) == 2.0 * 8 * 32 * 320 * pairs
    total = mla_bytes.prefill_flops(CONFIG, s)
    assert total == s * per_token + mla_bytes.attention_flops(CONFIG, s) \
        + 2 * H * 128256
    # attention is ~40% of a long prompt's work (the issue's reckoning)
    assert 0.35 < mla_bytes.attention_flops(CONFIG, s) / total < 0.5
    # one call of the attention kernel: one layer's causal pairs
    assert mla_bytes.flash_call_flops(CONFIG, s) == 2.0 * 32 * 320 * pairs
