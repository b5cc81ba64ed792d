"""`readers/nh.py`: the decode step, the prefill programs, the recurrence
kernel and the expert product against their roofs, the held pairs' share,
and the readers that say nothing for another family, for a program without
the counters (the parent) and without a trace."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from conftest import BENCH
from lib import nh_bytes
from readers import nh

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "nemotron-3-nano-30b-a3b.json")))
GRANITE = json.load(open(os.path.join(BENCH, "configs",
                                      "granite-4.0-h-small.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic",
                                      "batch64-closed.json")))
RECORDS = [{"stamps": [(5.0, 40), (60.0, 400)], "t_done": 61.0,
            "prompt_tokens": 100, "tokens": 300}] * 64 + [
    {"stamps": [(20.0, 40), (30.0, 100)], "t_done": 30.0,
     "prompt_tokens": 81, "tokens": 200}] * 10
HBM, MXU = 819e9, 197e12


def moe(pairs, held):
    return {"pairs": pairs, "expert_pairs": [held // 32] * 32,
            "held_pairs": held, "absent_pairs": pairs - held}


def ctx_of(config=CONFIG, *, route="routed", start=None, end=None,
           trace=True, runs=None, ops=None):
    phase = NS(records=RECORDS, w0=10.0, w1=50.0, trace_path=None,
               stats_start={"engine": {"moe": start} if start else {}},
               stats_end={"engine": {
                   **({"moe": end} if end else {}),
                   "startup": {"moe": {"route": {"decode": route}}}
                   if route else {}}})
    ctx = NS(cell=NS(config=config, tpu=config["tpu"], traffic=TRAFFIC),
             device={"kind": "TPU v5 lite", "count": 1}, phase=phase,
             trace={"window_s": 3.0,
                    "programs": {"jit_prefill(123)": (0.3, 6),
                                 "jit_decode_block(7)": (2.6, 7)},
                    "decode": {"seconds": 2.6, "runs": 7}}
             if trace else None)
    ctx.__dict__["_gdn_runs"] = {"decode_block": runs}
    ctx.__dict__["_ssm_ops"] = ops or {}
    return ctx


RUNS = {"runs": 5.0, "seconds": 5 * 16 * 0.025, "cut": 2.0}


def test_the_decode_share_is_the_steps_bytes_over_a_whole_runs_step():
    got = nh.decode_hbm_share(ctx_of(runs=RUNS))
    live_slots, live_tokens = 64.0, None
    from lib import window
    live_slots, live_tokens = window.mean_live(RECORDS, 10.0, 50.0)
    want = 100 * nh_bytes.decode_step_bytes(
        CONFIG, CONFIG["tpu"], live_tokens, live_slots) / 0.025 / HBM
    assert got == pytest.approx(want) and 60 < got < 100
    assert nh.decode_hbm_share(ctx_of(runs=None)) is None
    assert nh.decode_hbm_share(ctx_of(runs=RUNS, trace=False)) is None
    assert nh.decode_hbm_share(ctx_of(GRANITE, runs=RUNS)) is None


def test_the_prefill_share_counts_the_windows_prompts_at_held_pairs():
    got = nh.prefill_mxu_share(ctx_of())
    flops = 10 * nh_bytes.prefill_flops(CONFIG, 81 + 19)   # first token in
    want = 100 * flops / 40.0 / (0.3 / 3.0) / MXU
    assert got == pytest.approx(want) and 0 < got < 100
    assert nh.prefill_mxu_share(ctx_of(trace=False)) is None
    assert nh.prefill_mxu_share(ctx_of(GRANITE)) is None


def test_the_held_share_is_held_over_all_pairs_of_the_window():
    ctx = ctx_of(start=moe(1000, 200), end=moe(9000, 2232))
    assert nh.held_pair_share(ctx) == pytest.approx(100 * 2032 / 8000)
    # a program that holds all it routes over has no such counter
    plain = ctx_of(end={"pairs": 9000, "expert_pairs": [1] * 128})
    assert nh.held_pair_share(plain) is None
    assert nh.held_pair_share(ctx_of()) is None
    assert nh.held_pair_share(
        ctx_of(start=moe(5, 1), end=moe(5, 1))) is None


def test_the_recurrence_kernels_share_is_a_layers_state_both_ways():
    ops = {"ssm_step": {"events": 23 * 80.0, "seconds": 23 * 80 * 0.45e-3}}
    got = nh.step_roofline(ctx_of(ops=ops), op="ssm_step")
    assert got == pytest.approx(100 * 2 * 134217728 / 0.45e-3 / HBM)
    assert 70 < got < 75
    assert nh.step_roofline(ctx_of(ops={"ssm_step": None}),
                            op="ssm_step") is None
    assert nh.step_roofline(ctx_of(GRANITE, ops=ops), op="ssm_step") is None
    assert nh.step_roofline(ctx_of(ops=ops, trace=False),
                            op="ssm_step") is None


def test_the_expert_products_share_is_hit_experts_over_the_kernels_time():
    # 40 s of samples: 2,000 expert hits a second (76 steps x 23 blocks x
    # ~1.1 ... of the 32 held), the kernel running a sixth of the capture
    ops = {"moe_gmm": {"events": 3800.0, "seconds": 0.5}}
    ctx = ctx_of(route="routed", ops=ops)
    ctx.phase.samples = [(10.0, {"engine": {"moe": {"expert_hits": 1000}}}),
                         (50.0, {"engine": {"moe": {"expert_hits": 81000}}})]
    expert = nh_bytes.expert_weight_bytes(CONFIG, CONFIG["tpu"])
    got = nh.expert_roofline(ctx)
    assert got == pytest.approx(100 * 2000 * expert / (0.5 / 3.0) / HBM)
    assert 10 < got < 100
    # no counter (a program that holds all it routes over; the parent), a
    # mixture at decode, no kernel events, another family, no trace
    plain = ctx_of(route="routed", ops=ops)
    plain.phase.samples = [(10.0, {"engine": {"moe": {}}}),
                           (50.0, {"engine": {"moe": {}}})]
    assert nh.expert_roofline(plain) is None
    for other in (ctx_of(route="dense-mixture", ops=ops),
                  ctx_of(route=None, ops=ops), ctx_of(route="routed"),
                  ctx_of(GRANITE, route="routed", ops=ops),
                  ctx_of(route="routed", ops=ops, trace=False)):
        other.phase.samples = ctx.phase.samples
        assert nh.expert_roofline(other) is None
