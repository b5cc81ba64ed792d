"""`readers/dsa.py`: the kernel's events and the prefill dispatches counted
from a capture; the counters' ratio; the readers that say nothing for another
family, for a program without the counter, and without a trace."""

import json
import os
from types import SimpleNamespace as NS

from conftest import BENCH
from lib import dsa_bytes
from readers import dsa

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "keye-vl-2.0-30b-a3b.json")))


def capture():
    ms = 1_000_000
    ops = [("%dsa_flash = bf16[1,4,8,8192,128]{4,3,2,1,0} custom-call(...)",
            0, 20 * ms),
           ("%fusion.12 = f32[256,8192]{1,0} fusion(...)", 20 * ms, 5 * ms),
           ("%dsa_flash.1 = bf16[1,4,8,8192,128]{4,3,2,1,0} custom-call()",
            30 * ms, 22 * ms),
           ("%moe_gmm.3 = f32[65536,768]{1,0} custom-call(...)", 60 * ms, ms)]
    # a raw trace packs a span's keywords into its name; `ProfileData`
    # hands them out as the event's stats
    spans = [("sym.engine.prefill#n=1,cached=False,bucket=8192#", 0, 90 * ms,
              ()),
             ("sym.engine.prefill", 95 * ms, 60 * ms,
              (("n", 1), ("cached", False), ("bucket", 6144))),
             ("sym.engine.prefill", 160 * ms, ms, (("n", 1),)),  # before PR 40
             ("sym.sched.sync#entry=prefill#", 0, ms, ())]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            NS(name=n, start_ns=s, duration_ns=d) for n, s, d in ops])]),
        NS(name="/host:CPU", lines=[NS(name="engine", events=[
            NS(name=n, start_ns=s, duration_ns=d, stats=st)
            for n, s, d, st in spans])])])


def test_the_kernels_events_and_the_dispatches_are_counted_from_the_capture():
    out = dsa.count_kernel(capture(), "dsa_flash")
    assert out["events"] == 2 and abs(out["seconds"] - 0.042) < 1e-12
    assert out["prefills"] == [[8192, 1], [6144, 1]]
    assert dsa.count_kernel(capture(), "ssm_step") == {
        "events": 0, "seconds": 0.0, "prefills": [[8192, 1], [6144, 1]]}


def ctx_with(counted, **kw):
    cell = NS(config=CONFIG, tpu=CONFIG["tpu"])
    ctx = NS(cell=cell, trace={"window_s": 3.0}, device={
        "kind": "TPU v5 lite", "count": 1}, phase=NS(trace_path=None), **kw)
    ctx.__dict__["_dsa_ops"] = {"dsa_flash": counted}
    return ctx


def test_the_roofline_prices_each_event_at_the_dispatches_mean_work():
    counted = dsa.count_kernel(capture(), "dsa_flash")
    got = dsa.flash_roofline(ctx_with(counted), "dsa_flash")
    mean = (dsa_bytes.flash_flops(CONFIG, 8192)
            + dsa_bytes.flash_flops(CONFIG, 6144)) / 2
    assert abs(got - 100 * 2 * mean / 0.042 / 197e12) < 1e-9
    assert 0 < got < 100
    # no event of the kernel (the parent), no span with a bucket: nothing
    assert dsa.flash_roofline(ctx_with(dict(counted, events=0)),
                              "dsa_flash") is None
    assert dsa.flash_roofline(ctx_with(dict(counted, prefills=[])),
                              "dsa_flash") is None
    assert dsa.flash_roofline(ctx_with(None), "dsa_flash") is None


def test_the_select_ratio_is_the_counters_growth():
    def phase(start, end):
        return NS(stats_start={"engine": start}, stats_end={"engine": end})

    grown = phase({"dsa": {"candidates": 1000, "selected": 900}},
                  {"dsa": {"candidates": 5000, "selected": 2100}})
    assert dsa.select_ratio(NS(phase=grown)) == 30.0
    assert dsa.select_ratio(NS(phase=phase({}, {}))) is None      # a parent
    still = phase({"dsa": {"candidates": 7, "selected": 7}},
                  {"dsa": {"candidates": 7, "selected": 7}})
    assert dsa.select_ratio(NS(phase=still)) is None


def test_the_index_cache_is_a_share_of_the_chips_memory():
    sparse = {"index_cache_bytes": 64 * 16384 * 512}
    end = {"engine": {"startup": {
        "attention": {"sparse": sparse},
        "device": {"hbm": [{"bytes_limit": 16909336064,
                            "bytes_in_use": 8e9}]}}}}
    got = dsa.index_hbm_share(NS(phase=NS(stats_end=end)))
    assert abs(got - 100 * 536870912 / 16909336064) < 1e-9
    assert dsa.index_hbm_share(NS(phase=NS(stats_end={"engine": {
        "startup": {"attention": {}, "device": {"hbm": []}}}}))) is None


def test_live_lengths_hold_the_prompt_the_template_and_what_was_sent():
    records = [
        {"stamps": [(10.0, 4), (11.0, 4)], "t_done": 12.0,
         "prompt_tokens": 5000, "tokens": 8},
        {"stamps": [(10.9, 2)], "t_done": 11.2, "prompt_tokens": 7000,
         "tokens": 2},
        {"stamps": [], "t_done": None, "prompt_tokens": 9000, "tokens": 0}]
    cell = NS(config=CONFIG, tpu=CONFIG["tpu"])
    ctx = NS(cell=cell, phase=NS(records=records, w0=10.0, w1=12.0))
    # samples at 10.25, 10.75, 11.25, 11.75
    assert dsa.live_lengths(ctx) == [[5023], [5023], [5027], [5027]]
    ctx.phase.w0, ctx.phase.w1 = 10.5, 11.5      # 10.75 and 11.25 -> one
    assert dsa.live_lengths(ctx, step_s=1.0) == [[5027, 7021]]


def test_readers_say_nothing_for_another_family_or_without_a_trace():
    other = NS(config={"model_type": "qwen3_next",
                       "decode_program": "decode_block",
                       "prefill_program": "prefill"}, tpu={})
    ctx = NS(cell=other, trace={"window_s": 1.0, "programs": {}}, phase=None)
    assert dsa.decode_hbm_share(ctx) is None
    assert dsa.prefill_mxu_share(ctx) is None
    assert dsa.flash_roofline(ctx, "dsa_flash") is None
    mine = NS(config=CONFIG, tpu=CONFIG["tpu"])
    assert dsa.decode_hbm_share(NS(cell=mine, trace=None, phase=None)) is None
    assert dsa.prefill_mxu_share(NS(cell=mine, trace=None,
                                    phase=None)) is None
