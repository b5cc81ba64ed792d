"""`lib/swa_bytes.py` against hand counts at the cut configuration's widths:
the weights the chip holds, a cached position, both leaves of the cache and
the one capacity they replace, a decode step's bytes and a prefill's active
FLOPs under each layer's own mask."""

import json
import os

from conftest import BENCH
from lib import swa_bytes
from lib.moe_bytes import experts_hit

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "smallthinker-21b-a3b.json")))
TPU = CONFIG["tpu"]
H, HEADS, KV, D, F, X, V = 2560, 28, 4, 128, 768, 64, 151936
ROW = 2 * KV * (D + 4)            # int8 K and V with a float32 scale a head


def int8(k, n):
    return k * n + 4 * n


def test_one_layers_attention_is_four_matrices_and_a_norm():
    want = (int8(H, HEADS * D) + 2 * int8(H, KV * D) + int8(HEADS * D, H)
            + H * 2)
    assert swa_bytes.attention_weight_bytes(CONFIG, TPU) == want
    assert 20.9e6 < want < 21.1e6              # the issue's 21.0 M


def test_an_expert_and_what_every_token_reads_beside_it():
    assert swa_bytes.expert_weight_bytes(CONFIG, TPU) == (
        2 * int8(H, F) + int8(F, H))
    assert swa_bytes.moe_fixed_bytes(CONFIG, TPU) == H * X * 2 + H * 2


def test_the_whole_model_is_what_the_programs_init_makes():
    # jax.eval_shape of the program's init (int8 matrices, f32 column
    # scales, bf16 embedding, norms and routers): 5,965,969,920 B
    assert swa_bytes.weight_bytes(CONFIG, TPU) == 5_965_969_920
    experts = 12 * X * swa_bytes.expert_weight_bytes(CONFIG, TPU)
    assert 0.75 < experts / 5_965_969_920 < 0.78


def test_a_cached_position_and_both_leaves():
    assert swa_bytes.kv_row_bytes(CONFIG, TPU) == ROW == 1056
    assert swa_bytes.kv_row_bytes(
        CONFIG, dict(TPU, kv_quantization=None)) == 2 * KV * D * 2
    got = swa_bytes.cache_bytes(CONFIG, TPU)
    assert got["full"] == 3 * 64 * 11776 * ROW
    assert got["ring"] == 9 * 64 * 4096 * ROW
    assert got["total"] == got["full"] + got["ring"]
    assert got["uniform"] == 12 * 64 * 11776 * ROW
    assert 4.87e9 < got["total"] < 4.89e9      # the issue's 4.88 GB
    assert 9.54e9 < got["uniform"] < 9.56e9    # ... against 9.55 GB
    # with the weights, one capacity would not fit the chip's 16.9 GB
    assert got["uniform"] + 5_965_969_920 > 15.5e9


def test_a_decode_steps_bytes_are_weights_hit_experts_and_live_rows():
    full_rows, ring_rows, slots = 64 * 7400.0, 64 * 4096.0, 64.0
    hit = experts_hit(slots * 6, X)
    assert 63.8 < hit < 64
    full, ring = swa_bytes.cache_step_bytes(CONFIG, TPU, full_rows,
                                            ring_rows)
    assert full == full_rows * 3 * ROW and ring == ring_rows * 9 * ROW
    want = (12 * (swa_bytes.attention_weight_bytes(CONFIG, TPU)
                  + hit * swa_bytes.expert_weight_bytes(CONFIG, TPU)
                  + swa_bytes.moe_fixed_bytes(CONFIG, TPU))
            + H * 2 + int8(H, V) + full + ring + slots * H * 2)
    got = swa_bytes.decode_step_bytes(CONFIG, TPU, full_rows, ring_rows,
                                      slots)
    assert abs(got - want) < 1
    # the issue's reckoning: 42% of a step's bytes are cache rows, 62% of
    # those the rings
    assert 0.41 < (full + ring) / got < 0.45
    assert 0.61 < ring / (full + ring) < 0.64
    # an empty engine still streams what every step multiplies by
    assert swa_bytes.decode_step_bytes(CONFIG, TPU, 0, 0, 0) > 0.6e9


def test_the_kernels_calls_of_a_step_are_bound_by_their_bytes():
    full_rows, ring_rows = 64 * 7400.0, 64 * 4096.0
    nbytes = swa_bytes.kernel_step_bytes(CONFIG, TPU, full_rows, ring_rows,
                                         64)
    assert nbytes == (full_rows * 3 + ring_rows * 9) * ROW \
        + 12 * 2 * 64 * HEADS * D * 2
    flops = 2.0 * 2 * HEADS * D * (full_rows * 3 + ring_rows * 9)
    assert flops / nbytes < 30                 # far under the ridge of ~240


def test_the_pairs_a_mask_leaves():
    assert swa_bytes.causal_pairs(5) == 15
    assert swa_bytes.window_pairs(5, 8) == 15              # under the window
    assert swa_bytes.window_pairs(8, 8) == 36              # at it
    # past it: query t sees min(t + 1, 8) keys
    assert swa_bytes.window_pairs(20, 8) == sum(min(t + 1, 8)
                                                for t in range(20))
    s = 8192
    assert swa_bytes.window_pairs(s, 4096) == 4096 * 4097 // 2 \
        + 4096 * 4096
    assert swa_bytes.window_pairs(s, 4096) < 0.76 * swa_bytes.causal_pairs(s)


def test_a_prefills_active_flops_under_each_layers_mask():
    per_token = swa_bytes.active_flops_per_token(CONFIG)
    attn = 2 * (H * HEADS * D + 2 * H * KV * D + HEADS * D * H)
    moe = 2 * H * X + 6 * 6 * H * F
    assert per_token == 12 * (attn + moe)
    s = 8192
    full = 2.0 * HEADS * 2 * D * swa_bytes.causal_pairs(s)
    window = 2.0 * HEADS * 2 * D * swa_bytes.window_pairs(s, 4096)
    assert swa_bytes.layer_attention_flops(CONFIG, s, False) == full
    assert swa_bytes.layer_attention_flops(CONFIG, s, True) == window
    assert swa_bytes.attention_flops(CONFIG, s) == 3 * full + 9 * window
    total = swa_bytes.prefill_flops(CONFIG, s)
    assert total == s * per_token + 3 * full + 9 * window + 2 * H * V
    # attention is about three tenths of a long prompt's work
    assert 0.25 < swa_bytes.attention_flops(CONFIG, s) / total < 0.35
