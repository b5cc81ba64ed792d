"""`lib/dsa_bytes.py` against hand counts for the `keye-vl-2.0-30b-a3b`
configuration file (four layers of GQA 32/4 under a lightning indexer of 16
heads x 64 picking 2,048 positions, 128 experts top 8 a layer): weights, the
cache a token, a decode step's bytes in the gather form, active FLOPs."""

import json
import os

from conftest import BENCH
from lib import dsa_bytes

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "keye-vl-2.0-30b-a3b.json")))
TPU = CONFIG["tpu"]


def test_one_layers_weights():
    mixer = (2048 * 4096 + 4096 * 4          # wq int8 + f32 column scales
             + 2 * (2048 * 512 + 512 * 4)    # wk, wv
             + 4096 * 2048 + 2048 * 4        # wo
             + 2048 * 1024 + 1024 * 4        # index queries: 16 heads x 64
             + 2048 * 64 + 64 * 4            # the one index key
             + 2048 * 16 * 2                 # head weights, bf16
             + (2048 + 2 * 128) * 2)         # layer norm, q and k norms
    assert dsa_bytes.mixer_weight_bytes(CONFIG, TPU) == mixer
    # the issue's 18.9 M attention + 2.3 M indexer parameters a layer
    assert 18.8e6 < 2 * 2048 * 4096 + 2 * 2048 * 512 < 18.9e6
    assert 2.2e6 < 2048 * (1024 + 64 + 16) < 2.3e6
    expert = 3 * 2048 * 768 + (2 * 768 + 2048) * 4
    assert dsa_bytes.expert_weight_bytes(CONFIG, TPU) == expert
    # 4 layers x 128 experts are 2.42 GB
    assert 2.41e9 < 4 * 128 * 3 * 2048 * 768 < 2.42e9
    assert dsa_bytes.ffn_fixed_bytes(CONFIG, TPU) == 2048 * 128 * 2 + 4096
    assert dsa_bytes.head_bytes(CONFIG, TPU) == 2048 * 151936 + 151936 * 4


def test_the_cache_of_a_token():
    assert dsa_bytes.index_bytes_per_token(CONFIG, TPU) == 4 * 64 * 2 == 512
    # K and V x 4 heads x (128 int8 + one f32 scale) a layer
    assert dsa_bytes.kv_row_bytes(CONFIG, TPU) == 2 * 4 * 132 == 1056
    assert dsa_bytes.cache_bytes_per_token(CONFIG, TPU) == 4736
    assert 4.96e9 < 64 * 16384 * 4736 < 4.97e9
    bf16 = dict(TPU, kv_quantization=None)
    assert dsa_bytes.kv_row_bytes(CONFIG, bf16) == 2 * 512 * 2


def test_a_decode_step_reads_every_index_key_and_the_selected_rows_alone():
    lengths = [8400] * 64
    full = dsa_bytes.decode_step_bytes(CONFIG, TPU, lengths)
    hit = 128 * (1 - (127 / 128) ** 512)
    assert 125 < hit < 126
    weights = (4 * (dsa_bytes.mixer_weight_bytes(CONFIG, TPU)
                    + hit * dsa_bytes.expert_weight_bytes(CONFIG, TPU)
                    + dsa_bytes.ffn_fixed_bytes(CONFIG, TPU))
               + 2048 * 2 + dsa_bytes.head_bytes(CONFIG, TPU))
    index = 64 * 8400 * 512
    rows = 64 * 2048 * 4 * 1056
    assert abs(full - (weights + index + rows + 64 * 4096)) < 1
    # the issue's figures: 2.8 GB of weights, 0.28 of index keys, 0.55 of
    # selected rows: 3.6 GB
    assert 2.7e9 < weights < 2.9e9 and 0.27e9 < index < 0.28e9
    assert 0.55e9 < rows < 0.56e9 and 3.5e9 < full < 3.7e9
    # a slot under topk reads all it has; an idle engine reads weights
    short = dsa_bytes.decode_step_bytes(CONFIG, TPU, [100])
    assert abs(short - (weights + 100 * 512 + 100 * 4 * 1056 + 4096)) < 1
    assert dsa_bytes.decode_step_bytes(CONFIG, TPU, []) == weights
    # what a masked form reads instead of the selected rows
    assert 2.2e9 < 64 * 8400 * 4 * 1056 < 2.3e9


def test_active_flops_select_and_never_count_every_causal_pair():
    per_token = 4 * (2 * 2048 * 4096 * 2 + 2 * 2 * 2048 * 512
                     + 2 * 2048 * (1024 + 64 + 16) + 2 * 2048 * 128
                     + 8 * 3 * 2 * 2048 * 768)
    assert dsa_bytes.active_flops_per_token(CONFIG) == per_token
    assert dsa_bytes.causal_pairs(4) == 10
    assert dsa_bytes.selected_pairs(4, 2) == 1 + 2 + 2 + 2
    assert dsa_bytes.selected_pairs(3, 8) == 6
    s = 8192
    got = dsa_bytes.prefill_flops(CONFIG, s)
    index = 4 * 2 * 16 * 65 * s * (s + 1) // 2
    attention = 4 * 4 * 4096 * (2048 * 2049 // 2 + (s - 2048) * 2048)
    assert got == s * per_token + index + attention + 2 * 2048 * 151936
    # the issue's figures for 8,192 tokens: projections and experts 3.9
    # TFLOP (ours has the indexer's projections in it), the indexer 0.27,
    # and attention over the SELECTION 0.96 where every causal pair is 2.2
    assert 3.8e12 < s * per_token < 4.0e12
    assert 0.27e12 < index < 0.29e12
    assert 0.9e12 < attention < 1.0e12
    assert 2.1e12 < 4 * 4 * 4096 * s * (s + 1) // 2 < 2.3e12


def test_the_kernels_own_work_is_the_blocks_under_the_diagonal():
    # 128-query blocks against 512-key blocks: query block qi visits
    # (qi * 128 + 127) // 512 + 1 key blocks
    assert dsa_bytes.flash_flops(CONFIG, 512) == 4 * 4096 * 4 * 128 * 512
    blocks = sum((qi * 128 + 127) // 512 + 1 for qi in range(64))
    assert blocks == 4 * (1 + 2 + 3 + 4 + 5 + 6 + 7 + 8 + 9 + 10 + 11 + 12
                          + 13 + 14 + 15 + 16)
    assert dsa_bytes.flash_flops(CONFIG, 8192, 2) == (
        2 * 4 * 4096 * blocks * 128 * 512)
    # a little over the causal half of the square
    assert 0.5 < dsa_bytes.flash_flops(CONFIG, 8192) / (
        4 * 4096 * 8192 * 8192) < 0.54
