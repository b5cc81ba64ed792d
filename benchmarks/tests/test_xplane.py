"""The trace reduction: on a small trace recorded on a TPU v5e (six runs of
a four-matmul jitted step with a 20 ms host sleep between them; recorded by
PR 23's first chip call, 26 KB) and on a synthetic two-chip trace."""

import os
from types import SimpleNamespace as NS

import pytest

from lib import xplane

from conftest import TESTS

SMALL = os.path.join(TESTS, "data", "small_tpu.xplane.pb")


def test_recorded_tpu_trace():
    from jax.profiler import ProfileData

    out = xplane.reduce_profile(ProfileData.from_file(SMALL), "jit_step")
    assert out["devices"] == 1
    assert out["decode"]["runs"] == 6 and out["decode"]["names"] == ["jit_step"]
    # six runs of ~13 us each; busy is the union of the ops inside them
    assert 60e-6 < out["busy_s"] < 90e-6
    assert out["busy_s"] <= out["decode"]["seconds"] * 1.001
    assert 0.10 < out["window_s"] < 0.12
    assert out["ops"][0][0] == "convolution_tanh_fusion.2 bf16[256,1024]"
    assert out["collective_s"] == 0.0
    # the five gaps between the six runs are the script's time.sleep(0.02)
    gaps = out["idle_gaps"][:5]
    assert all(0.020 < s < 0.024 for _, s in gaps), gaps
    assert all(name.endswith("$time sleep") for name, _ in gaps), gaps


def test_parse_op():
    text = ("%fusion.259 = bf16[128,14336]{1,0:T(8,128)(2,1)} fusion(bf16[1]"
            "{0} %p), kind=kOutput, calls=%fused_computation.2")
    assert xplane.parse_op(text) == ("fusion.259", "bf16[128,14336]",
                                     "fusion")
    loop = ("%while.42 = (s32[]{:T(128)}, bf16[128,1,4096]{2,0,1}) "
            "while((s32[]{:T(128)}, bf16[128,1,4096]{2,0,1}) %tuple.141), "
            "condition=%c, body=%b")
    assert xplane.parse_op(loop) == ("while.42", "s32[]", "while")


def ev(name, start_s, dur_s):
    return NS(name=name, start_ns=start_s * 1e9, duration_ns=dur_s * 1e9,
              stats=[])


def device_plane(i, ops, modules):
    return NS(name=f"/device:TPU:{i}", lines=[
        NS(name="XLA Ops", events=ops),
        NS(name="XLA Modules", events=modules)])


def test_synthetic_two_chips():
    def chip_ops(shift):
        return [
            ev("%while.1 = (s32[]) while((s32[]) %t), body=%b", 1.0 + shift,
               1.0),                                   # container: 1.0–2.0
            ev("%fusion.1 = bf16[8,8]{1,0} fusion(%a)", 1.0 + shift, 0.6),
            ev("%all-reduce.3 = bf16[8,8]{1,0} all-reduce(%x)", 1.6 + shift,
               0.4),
            ev("%fusion.2 = f32[4]{0} fusion(%a)", 3.0 + shift, 0.5),
        ]
    mods = [ev("jit_decode_block(123)", 1.0, 1.0),
            ev("jit_prefill(9)", 3.0, 0.5)]
    host = NS(name="/host:CPU", lines=[
        NS(name="", events=[ev("$time sleep", 0.9, 3.0)]),   # capture thread
        NS(name="python3", events=[
            ev("$scheduler.py:1 _admit_new", 2.05, 0.9),
            ev("$array.py:2 _value", 2.1, 0.8),
            ev("$unrelated.py:3 f", 0.0, 0.5)])])
    data = NS(planes=[device_plane(0, chip_ops(0.0), mods),
                      device_plane(1, chip_ops(0.0), list(mods)), host])
    out = xplane.reduce_profile(data, "decode_block")
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(1.5)       # per chip, not summed
    assert out["window_s"] == pytest.approx(2.5)
    ops = dict(out["ops"])
    assert "while.1 s32[]" not in ops                # containers left out
    assert ops["fusion.1 bf16[8,8]"] == pytest.approx(0.6)
    assert out["collective_s"] == pytest.approx(0.4)
    assert out["decode"]["seconds"] == pytest.approx(1.0)
    assert out["decode"]["runs"] == 1
    # one gap, 2.0–3.0: the innermost covering span, not the capture's sleep
    assert out["idle_gaps"][0][0] == "python3:$array.py:2 _value"
    assert out["idle_gaps"][0][1] == pytest.approx(1.0)


def test_no_device_plane_reduces_to_nothing():
    out = xplane.reduce_profile(NS(planes=[NS(name="/host:CPU", lines=[])]))
    assert out["busy_s"] == 0.0 and out["decode"] is None
