"""`readers/swa.py`: the counters' growth over a window, the decode step,
the decode kernel and the prefill kernel against their roofs, and the
readers that say nothing for another family, for a program without the
counters (the parent) and without a trace."""

import json
import os
from types import SimpleNamespace as NS

from conftest import BENCH
from lib import swa_bytes
from readers import swa

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "smallthinker-21b-a3b.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic",
                                      "draft-closed.json")))
RECORDS = [{"stamps": [(5.0, 400), (60.0, 400)], "t_done": 61.0,
            "prompt_tokens": 7000, "tokens": 800}] * 60


def counters(steps, full, ring, wraps, prefill):
    return {"decode_steps": steps, "full_rows": full, "ring_rows": ring,
            "ring_wraps": wraps, "prefill_tokens": prefill}


def phase(start, end, **kw):
    kw = {"records": RECORDS, "w0": 10.0, "w1": 50.0, "trace_path": None,
          **kw}
    return NS(stats_start={"engine": {"swa": start} if start else {}},
              stats_end={"engine": {"swa": end} if end else {}}, **kw)


def cell():
    return NS(config=CONFIG, tpu=CONFIG["tpu"], traffic=TRAFFIC)


def traced(counted_runs, counted_ops, start, end, **kw):
    ctx = NS(cell=cell(), device={"kind": "TPU v5 lite", "count": 1},
             trace={"window_s": 3.0, "programs": {
                 "jit_prefill(123)": (1.2, 4),
                 "jit_decode_block(7)": (1.7, 7)}},
             phase=phase(start, end, **kw))
    ctx.__dict__["_gdn_runs"] = {"decode_block": counted_runs}
    ctx.__dict__["_dsa_ops"] = {"swa_decode": counted_ops}
    return ctx


FULL, RING = 60 * 7400, 60 * 4096
START = counters(1600, 1600 * FULL, 1600 * RING, 10, 10**6)
END = counters(3200, 3200 * FULL, 3200 * RING, 30, 2 * 10**6)


def test_the_cache_share_is_a_count_against_a_count_and_splits_by_leaf():
    ctx = NS(cell=cell(), phase=phase(START, END), trace=None)
    tpu = CONFIG["tpu"]
    full, ring = swa_bytes.cache_step_bytes(CONFIG, tpu, FULL, RING)
    step = swa_bytes.decode_step_bytes(CONFIG, tpu, FULL, RING, 60.0)
    assert abs(swa.cache_hbm_share(ctx) - 100 * (full + ring) / step) < 1e-9
    assert abs(swa.cache_hbm_share(ctx, part="ring")
               - 100 * ring / step) < 1e-9
    assert abs(swa.cache_hbm_share(ctx, part="full")
               + swa.cache_hbm_share(ctx, part="ring")
               - swa.cache_hbm_share(ctx)) < 1e-9
    assert 38 < swa.cache_hbm_share(ctx) < 46
    # the window's last sample is what is read, not the stats after the drain
    drained = counters(9999, 1, 1, 1, 1)
    ctx = NS(cell=cell(), trace=None, phase=phase(START, drained, samples=[
        (49.0, {"engine": {"swa": END}}),
        (51.0, {"engine": {"swa": drained}})]))
    assert abs(swa.cache_hbm_share(ctx) - 100 * (full + ring) / step) < 1e-9


def test_the_decode_step_and_the_kernel_against_the_hbm():
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 2},
                 {"events": 768, "seconds": 0.4, "prefills": []}, START, END)
    tpu = CONFIG["tpu"]
    step_s = 1.0 / 4 / 16
    nbytes = swa_bytes.decode_step_bytes(CONFIG, tpu, FULL, RING, 60.0)
    assert abs(swa.decode_hbm_share(ctx)
               - 100 * nbytes / step_s / 819e9) < 1e-6
    assert 0 < swa.decode_hbm_share(ctx) < 100
    per_call = swa_bytes.kernel_step_bytes(CONFIG, tpu, FULL, RING, 64) / 12
    want = 100 * 768 * per_call / 0.4 / 819e9
    assert abs(swa.decode_attn_roofline(ctx, "swa_decode") - want) < 1e-6
    assert 0 < want < 100
    # no whole run, or no event of the kernel, in the capture: nothing
    none = traced({"runs": 0, "seconds": 0.0, "cut": 2}, None, START, END)
    assert swa.decode_hbm_share(none) is None
    assert swa.decode_attn_roofline(none, "swa_decode") is None


def test_the_admissions_share_of_the_mxu():
    records = [{"stamps": [(10.0 + i, 4)], "t_done": 60.0,
                "prompt_tokens": 7000, "tokens": 8} for i in range(20)]
    records.append({"stamps": [(5.0, 4)], "t_done": 9.0,
                    "prompt_tokens": 7000, "tokens": 8})  # before the window
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 0}, None, START, END,
                 records=records)
    flops = 20 * swa_bytes.prefill_flops(CONFIG, 7019)
    want = 100 * flops / 40.0 / (1.2 / 3.0) / 197e12
    assert abs(swa.prefill_mxu_share(ctx) - want) < 1e-9
    assert 0 < want < 100


def test_the_prefill_kernel_against_the_mxu_by_a_lower_count():
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 0}, None, START, END)
    # 36 events (3 dispatches x 12 layers) in 0.2 s; the spans of a probe's
    # bucket, the cell's first bucket and its last
    ctx.__dict__["_dsa_ops"]["flash_wide"] = {
        "events": 36, "seconds": 0.2,
        "prefills": [[1024, 1], [5888, 1], [8320, 1]]}
    # each bucket at the least prompt it takes: 1 token (no bucket below),
    # the traffic's shortest prompt + template, one over the bucket below
    least = (1, 5120 + 19, 7424 + 1)
    mean = sum(swa_bytes.attention_flops(CONFIG, n) for n in least) / 3 / 12
    want = 100 * 36 * mean / 0.2 / 197e12
    assert abs(swa.prefill_roofline(ctx, "flash_wide") - want) < 1e-9
    assert 0 < want < 100
    # priced at the bucket itself it would read higher: the count is lower
    assert want < 100 * 36 * sum(
        swa_bytes.attention_flops(CONFIG, b)
        for b in (1024, 5888, 8320)) / 3 / 12 / 0.2 / 197e12
    ctx.__dict__["_dsa_ops"]["flash_wide"] = {
        "events": 0, "seconds": 0.0, "prefills": []}
    assert swa.prefill_roofline(ctx, "flash_wide") is None


def test_a_parent_or_another_family_reads_as_nothing():
    readers = (swa.decode_hbm_share, swa.cache_hbm_share,
               swa.prefill_mxu_share,
               lambda c: swa.prefill_roofline(c, "flash_wide"),
               lambda c: swa.decode_attn_roofline(c, "swa_decode"))
    # the parent: this configuration, a program without the counters
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 0},
                 {"events": 0, "seconds": 0.0, "prefills": []}, None, None)
    for reader in readers:
        assert reader(ctx) is None
    ctx.trace = None
    for reader in readers:
        assert reader(ctx) is None
    # another family, whatever its program counts
    other = NS(config={"model_type": "deepseek_v3", "kv_lora_rank": 512,
                       "decode_program": "decode_block",
                       "prefill_program": "prefill"}, tpu=CONFIG["tpu"],
               traffic=TRAFFIC)
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 0},
                 {"events": 9, "seconds": 0.1, "prefills": [[1024, 1]]},
                 START, END)
    ctx.__dict__["_dsa_ops"]["flash_wide"] = {
        "events": 9, "seconds": 0.1, "prefills": [[1024, 1]]}
    ctx.cell = other
    for reader in readers:
        assert reader(ctx) is None
