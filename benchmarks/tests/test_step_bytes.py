"""Bytes per decode step against hand counts for both models."""

import json
import os

from lib import step_bytes
from lib.peaks import UnknownDeviceError, peaks_for

import pytest

from conftest import BENCH


def cfg(name):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


def test_mistral_7b_int8():
    c = cfg("mistral-7b")
    # per layer: wq 4096², wk/wv 4096×1024, wo 4096², wg/wu/wd 4096×14336
    mats = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 4096 * 14336 * 3
    scales = 4 * (4096 + 1024 + 1024 + 4096 + 14336 + 14336 + 4096)
    norms = 2 * 4096 * 2
    expect = 32 * (mats + scales + norms) + 4096 * 2 + (
        4096 * 32768 + 32768 * 4)
    assert step_bytes.weight_bytes(c, c["tpu"]) == expect
    assert 7.1e9 < expect < 7.3e9
    # int8 KV: 32 layers × (K and V) × 8 heads × (128 B + one f32 scale)
    assert step_bytes.kv_bytes_per_token(c, c["tpu"]) == 32 * 2 * 8 * 132


def test_qwen2_7b_int8():
    c = cfg("qwen2-7b")
    mats = 3584 * 3584 * 2 + 3584 * 512 * 2 + 3584 * 18944 * 3
    scales = 4 * (3584 + 512 + 512 + 3584 + 18944 + 18944 + 3584)
    norms = 2 * 3584 * 2
    bias = (3584 + 512 + 512) * 2
    expect = 28 * (mats + scales + norms + bias) + 3584 * 2 + (
        3584 * 152064 + 152064 * 4)
    assert step_bytes.weight_bytes(c, c["tpu"]) == expect
    assert step_bytes.kv_bytes_per_token(c, c["tpu"]) == 28 * 2 * 4 * 132


def test_bf16_tp4_is_a_quarter_per_chip():
    c = cfg("mistral-7b-bf16-tp4")
    w = step_bytes.weight_bytes(c, c["tpu"])
    assert 14.2e9 < w < 14.3e9  # 14.5 GB of parameters less the embedding table
    assert step_bytes.kv_bytes_per_token(c, c["tpu"]) == 131072  # 128 KiB
    per_chip = step_bytes.decode_step_bytes(c, c["tpu"], 1000.0, 0.0)
    assert per_chip == w / 4 + 1000 * 131072 / 4


def test_peaks_table():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDeviceError):
        peaks_for("TPU v99")
