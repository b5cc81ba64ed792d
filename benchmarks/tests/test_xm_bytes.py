"""`lib/xm_bytes.py` against hand counts at the published widths of
benchmarks/configs/k-exaone-236b-a23b.json: the share's weights (the
recount the file's `deployment` states), the cache, a decode step's bytes
and a prefill's active operations."""

import json
import os

import pytest

from conftest import BENCH
from lib import xm_bytes

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "k-exaone-236b-a23b.json")))
TPU = CONFIG["tpu"]
E, Q, KV, F, FD, V = 6144, 8192, 1024, 2048, 18432, 19200


def matrix(k, n):
    return k * n + 4 * n        # int8 + a float32 scale a column


ATTN = (matrix(E, Q) + 2 * matrix(E, KV) + matrix(Q, E) + (E + 256) * 2)
EXPERT = 2 * matrix(E, F) + matrix(F, E)
FIXED = EXPERT + E * 128 * 2 + 4 * 128 + E * 2     # shared, router, bias, norm


def test_the_dims_are_the_files():
    d = xm_bytes._dims(CONFIG)
    assert (d["h"], d["q"], d["kv"], d["f"], d["fd"], d["vocab"]) == (
        E, Q, KV, F, FD, V)
    assert (d["layers"], d["window"], d["full"], d["dense"], d["sparse"],
            d["mtp"]) == (12, 9, 3, 1, 11, 1)
    assert (d["held"], d["routed_over"], d["k"], d["span"]) == (16, 128, 8,
                                                                128)


def test_the_pieces_are_the_hand_counts():
    assert xm_bytes.attention_weight_bytes(CONFIG, TPU) == ATTN
    assert xm_bytes.gated_bytes(CONFIG, TPU, F) == EXPERT
    assert xm_bytes.sparse_fixed_bytes(CONFIG, TPU) == FIXED
    assert xm_bytes.head_bytes(CONFIG, TPU) == matrix(E, V)
    assert xm_bytes.module_fixed_bytes(CONFIG, TPU) == (
        matrix(2 * E, E) + ATTN + FIXED + 3 * E * 2)
    assert xm_bytes.kv_row_bytes(CONFIG, TPU) == 2 * 8 * 132 == 2112
    assert xm_bytes.held_pairs(CONFIG, 128) == 128.0    # 128 x 8 x 16 / 128


def test_the_weights_are_the_deployments_recount():
    """9.971 GB by jax.eval_shape of the program's init (the file's
    `deployment`): the shapes' count lands within 0.1% of it."""
    total = xm_bytes.weight_bytes(CONFIG, TPU)
    assert total == (
        12 * ATTN + (2 * matrix(E, FD) + matrix(FD, E) + E * 2)
        + 11 * (FIXED + 16 * EXPERT) + xm_bytes.module_fixed_bytes(
            CONFIG, TPU) + 16 * EXPERT + V * E * 2 + matrix(E, V) + E * 2)
    assert abs(total / 9.971e9 - 1) < 1e-3
    assert "9.971 GB" in CONFIG["deployment"]


def test_the_cache_is_four_full_leaves_and_nine_rings_of_256():
    c = xm_bytes.cache_bytes(CONFIG, TPU)
    assert xm_bytes.ring_rows(CONFIG, TPU) == 256
    assert xm_bytes.ring_rows(CONFIG, {**TPU, "speculative": None}) == 128
    assert c["full"] == 4 * 64 * 5376 * 2112
    assert c["ring"] == 9 * 64 * 256 * 2112
    assert abs(c["full"] / 2.907e9 - 1) < 1e-3
    assert abs(c["ring"] / 0.311e9 - 1) < 2e-3
    assert abs((c["total"] + xm_bytes.weight_bytes(CONFIG, TPU))
               / 16.91e9 - 0.780) < 2e-3


def test_a_decode_step_reads_the_share_once_and_the_live_rows():
    full_rows, ring_rows = 64 * 3000.0, 64 * 128.0
    full, ring = xm_bytes.cache_step_bytes(CONFIG, TPU, full_rows, ring_rows)
    assert full == full_rows * 4 * 2112 and ring == ring_rows * 9 * 2112
    step = xm_bytes.decode_step_bytes(CONFIG, TPU, full_rows, ring_rows,
                                      64.0)
    # 64 slots x 2 positions: 128 held pairs hit all but a sliver of the 16
    hit = 16 * (1 - (15 / 16) ** 128)
    assert 15.99 < hit < 16
    weights = (12 * ATTN + 2 * matrix(E, FD) + matrix(FD, E) + E * 2
               + 11 * (FIXED + hit * EXPERT)
               + xm_bytes.module_fixed_bytes(CONFIG, TPU) + hit * EXPERT
               + E * 2 + matrix(E, V))
    assert step == pytest.approx(weights + full + ring + 64 * 3 * E * 2)
    # everything the chip holds but the embedding's unread rows, about once
    assert 0.97 < weights / (xm_bytes.weight_bytes(CONFIG, TPU)
                             - V * E * 2) <= 1.0
    # at the roof: ~14 ms a step at 819 GB/s
    assert 0.013 < step / 819e9 < 0.015
    # no live slot: no expert is hit, no row is read
    idle = xm_bytes.decode_step_bytes(CONFIG, TPU, 0.0, 0.0, 0.0)
    assert idle == pytest.approx(weights - 12 * hit * EXPERT)


def test_a_prefills_active_operations_count_held_pairs_alone():
    attn = 2 * (E * Q + 2 * E * KV + Q * E)
    sparse = 2 * E * 128 + 6 * E * F + 1.0 * 6 * E * F   # 1 held pair a token
    per_token = (12 * attn + 6 * E * FD + 11 * sparse
                 + (4 * E * E + attn + sparse))
    assert xm_bytes.active_flops_per_token(CONFIG) == pytest.approx(
        per_token)
    assert xm_bytes.window_pairs(100, 128) == 100 * 101 // 2
    assert xm_bytes.window_pairs(1000, 128) == 128 * 129 // 2 + 872 * 128
    pairs = 4 * xm_bytes.causal_pairs(1000) + 9 * xm_bytes.window_pairs(
        1000, 128)
    assert xm_bytes.attention_flops(CONFIG, 1000) == 2.0 * 64 * 256 * pairs
    assert xm_bytes.prefill_flops(CONFIG, 1000) == pytest.approx(
        1000 * per_token + 2.0 * 64 * 256 * pairs + 2 * 2 * E * V)
    # the routed experts a token computes are an eighth of the top 8
    assert xm_bytes.held_pairs(CONFIG, 1) == 1.0
