"""`lib/bd_bytes.py` against hand counts at the published widths of the sdar
cell (12 layers, 128 experts top 8 of width 768, GQA 32/4 of 128, int8
weights and K/V, 128 slots, blocks of 4)."""

import json
import os

from conftest import BENCH
from lib import bd_bytes as bd

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "sdar-30b-a3b-chat.json")))
TPU = CONFIG["tpu"]
BLOCK = 4


def test_one_layers_weights_by_hand():
    # wq 2048 x 4096, wk / wv 2048 x 512, wo 4096 x 2048 in int8, an f32
    # scale a column; bf16 norms: 2048 + 2 x 128
    mixer = (2048 * 4096 + 4 * 4096 + 2 * (2048 * 512 + 4 * 512)
             + 4096 * 2048 + 4 * 2048 + 2 * (2048 + 256))
    assert bd.mixer_weight_bytes(CONFIG, TPU) == mixer
    expert = 2 * (2048 * 768 + 4 * 768) + 768 * 2048 + 4 * 2048
    assert bd.expert_weight_bytes(CONFIG, TPU) == expert
    assert bd.ffn_fixed_bytes(CONFIG, TPU) == 2048 * 128 * 2 + 2048 * 2
    assert bd.head_bytes(CONFIG, TPU) == 2048 * 151936 + 4 * 151936
    # K and V of 4 heads x 128 in int8 with an f32 scale a head
    assert bd.kv_row_bytes(CONFIG, TPU) == 2 * 4 * (128 + 4) == 1056
    assert bd.cache_bytes_per_token(CONFIG, TPU) == 12 * 1056 == 12672


def test_a_forward_reads_every_expert_and_the_live_cache():
    lengths = [200] * 100
    got = bd.forward_bytes(CONFIG, TPU, lengths, BLOCK)
    # 100 live slots: 3,200 pairs over 128 experts, every one hit under
    # uniform routing (an upper count of what this traffic hits)
    hit = 128 * (1 - (1 - 1 / 128) ** 3200)
    assert 127.99 < hit <= 128
    want = (12 * (bd.mixer_weight_bytes(CONFIG, TPU)
                  + hit * bd.expert_weight_bytes(CONFIG, TPU)
                  + bd.ffn_fixed_bytes(CONFIG, TPU))
            + 2048 * 2 + bd.head_bytes(CONFIG, TPU)
            + 100 * 200 * 12672 + 2 * 400 * 12672 + 400 * 2048 * 2)
    assert abs(got - want) < 1
    assert 7.9e9 < got < 8.4e9          # the issue's ~7.8 GB + live K/V
    # idle slots are not counted: a lone live slot's 32 pairs hit 28.4
    # experts of a layer, and no other slot's rows move
    one = bd.forward_bytes(CONFIG, TPU, [200], BLOCK)
    few = 128 * (1 - (1 - 1 / 128) ** 32)
    assert abs(one - (12 * (bd.mixer_weight_bytes(CONFIG, TPU)
                            + few * bd.expert_weight_bytes(CONFIG, TPU)
                            + bd.ffn_fixed_bytes(CONFIG, TPU))
                      + 2048 * 2 + bd.head_bytes(CONFIG, TPU)
                      + 200 * 12672 + 2 * 4 * 12672 + 4 * 2048 * 2)) < 1
    assert bd.forward_bytes(CONFIG, TPU, [], BLOCK) == (
        12 * (bd.mixer_weight_bytes(CONFIG, TPU)
              + bd.ffn_fixed_bytes(CONFIG, TPU))
        + 2048 * 2 + bd.head_bytes(CONFIG, TPU))
    # a commit forward reads no head: the mean forward of 2 + 1 reads 2/3
    mean = bd.forward_bytes(CONFIG, TPU, lengths, BLOCK, 2 / 3)
    assert abs((got - mean) - (2048 * 2 + bd.head_bytes(CONFIG, TPU)) / 3) < 1


def test_a_forwards_flops_by_hand():
    per_token = 12 * (2 * 2048 * 4096 + 4 * 2048 * 512 + 2 * 4096 * 2048
                      + 2 * 2048 * 128 + 8 * 6 * 2048 * 768)
    assert bd.active_flops_per_token(CONFIG) == per_token
    assert bd.head_flops_per_token(CONFIG) == 2 * 2048 * 151936
    lengths = [200] * 100
    got = bd.forward_flops(CONFIG, TPU, lengths, BLOCK)
    pairs = 100 * 4 * 204               # the 28 idle slots are not counted
    want = 400 * (per_token + 2 * 2048 * 151936) + 12 * 4 * 4096 * pairs
    assert got == want
    full = bd.forward_flops(CONFIG, TPU, [200] * 128, BLOCK)
    assert 0.95e12 < full < 1.1e12      # the issue's ~1.0 TFLOP at 128 live
    assert bd.forward_flops(CONFIG, TPU, [], BLOCK) == 0


def test_pairs_under_the_block_mask():
    # 8 positions, blocks of 4: 4 x 4 + 4 x 8; a partial block sees itself
    assert bd.block_pairs(8, 4) == 48
    assert bd.block_pairs(6, 4) == 4 * 4 + 2 * 6
    assert bd.block_pairs(0, 4) == 0
    causal = 8 * 9 // 2
    assert bd.block_pairs(8, 4) > causal


def test_an_admissions_flops():
    # 51 tokens: 48 in whole blocks, 3 open the block; 2 steps + a commit
    per_token = bd.active_flops_per_token(CONFIG)
    attn = 12 * 4 * 4096
    want = (48 * per_token + attn * bd.block_pairs(48, 4)
            + 3 * (4 * per_token + attn * 4 * 52)
            + 2 * 4 * bd.head_flops_per_token(CONFIG))
    assert bd.prefill_flops(CONFIG, 51, 4, 2) == want
    # the left-over tokens ride the opening block: 48 to 51 tokens cost the
    # same forwards, the 52nd completes another whole block
    assert bd.prefill_flops(CONFIG, 48, 4, 2) == bd.prefill_flops(
        CONFIG, 51, 4, 2) < bd.prefill_flops(CONFIG, 52, 4, 2)
