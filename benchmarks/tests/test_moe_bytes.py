"""`lib/moe_bytes.py` against hand counts for the `mixtral-8x7b` configuration
file: weights a chip, KV a token, routed FLOPs a token, a prompt's prefill."""

import json
import os

import pytest

from conftest import BENCH
from lib import moe_bytes

CONFIG = json.load(open(os.path.join(BENCH, "configs", "mixtral-8x7b.json")))
TPU = CONFIG["tpu"]


def test_weights_of_the_whole_model_and_of_a_chip():
    by_hand = (
        32 * 8 * 3 * 4096 * 14336            # expert matrices, int8
        + 32 * 8 * (2 * 14336 + 4096) * 4    # their f32 column scales
        + 32 * (2 * 4096 * 4096 + 2 * 4096 * 1024)   # wq wo, wk wv
        + 32 * (2 * 4096 + 2 * 1024) * 4     # their scales
        + 32 * 4096 * 8 * 2                  # routers, bf16
        + 32 * 2 * 4096 * 2 + 4096 * 2       # norms, bf16
        + 4096 * 32000 + 32000 * 4)          # LM head and its scales
    assert by_hand == 46_608_028_672
    assert moe_bytes.weight_bytes(CONFIG, TPU) == by_hand
    # 11.7 GB a chip; 96.8% of it the experts (the issue's "97%")
    assert 11.6e9 < by_hand / 4 < 11.7e9
    experts = 32 * 8 * moe_bytes.expert_weight_bytes(CONFIG, TPU)
    assert 0.96 < experts / by_hand < 0.97


def test_kv_bytes_of_one_token():
    # 32 layers x (K and V) x 8 heads x (128 int8 + one f32 scale)
    assert moe_bytes.kv_bytes_per_token(CONFIG, TPU) == 67_584
    bf16 = dict(TPU, kv_quantization=None)
    assert moe_bytes.kv_bytes_per_token(CONFIG, bf16) == 32 * 2 * 1024 * 2
    # 64 slots x 2048 tokens: 8.9 GB, 2.2 GB a chip
    assert 8.8e9 < 64 * 2048 * 67_584 < 8.9e9


def test_routed_flops_of_one_token():
    by_hand = 32 * 2 * (4096 * 4096 * 2      # wq, wo
                        + 4096 * 1024 * 2    # wk, wv
                        + 4096 * 8           # router
                        + 2 * 3 * 4096 * 14336)  # 2 experts x 3 matrices
    assert moe_bytes.routed_matmul_flops_per_token(CONFIG) == by_hand
    assert 25.2e9 < by_hand < 25.3e9
    # all 8 experts would be 3.7x that: the counts never include them
    dense = by_hand + 32 * 2 * 6 * 3 * 4096 * 14336
    assert 3.6 < dense / by_hand < 3.8


def test_prefill_flops_of_a_prompt():
    s = 592                                   # 573 + 19 template tokens
    attention = 32 * 4 * 4096 * s * (s + 1) // 2
    head = 2 * 4096 * 32000
    want = s * moe_bytes.routed_matmul_flops_per_token(CONFIG) + attention \
        + head
    assert moe_bytes.prefill_flops(CONFIG, s) == want
    assert 14.9e12 < want < 15.2e12            # the issue's "~15 TFLOP"
    assert attention / want < 0.01


@pytest.mark.parametrize("pairs,experts,low,high", [
    (128, 8, 7.999, 8.0), (2, 8, 1.8, 1.9), (0, 8, 0.0, 0.0)])
def test_experts_a_step_hits(pairs, experts, low, high):
    assert low <= moe_bytes.experts_hit(pairs, experts) <= high


def test_decode_step_bytes_a_chip():
    # empty cache: a quarter of the sharded weights, the replicated router
    # and norms whole, nothing else
    empty = moe_bytes.decode_step_bytes(CONFIG, TPU, 0, 0)
    replicated = 32 * (4096 * 8 * 2 + 2 * 4096 * 2) + 4096 * 2
    sharded = 46_608_028_672 - replicated
    assert empty == pytest.approx(sharded / 4 + replicated, rel=1e-6)
    # 64 slots at 1000 tokens each add their KV / 4 and 64 embedding rows
    full = moe_bytes.decode_step_bytes(CONFIG, TPU, 64_000, 64)
    assert full - empty == pytest.approx(
        64_000 * 67_584 / 4 + 64 * 4096 * 2, rel=1e-9)
    # the floor of a step at 819 GB/s: 14.2 ms empty, 15.6 ms at 1000 a slot
    assert 14.0e-3 < empty / 819e9 < 14.4e-3
    assert 15.4e-3 < full / 819e9 < 15.7e-3
