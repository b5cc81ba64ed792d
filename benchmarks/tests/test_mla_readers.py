"""`readers/mla.py`: the counters' growth over a window, the decode step and
the kernel against their roofs, and the readers that say nothing for another
family, for a program without the counters (the parent) and without a
trace."""

import json
import os
from types import SimpleNamespace as NS

from conftest import BENCH
from lib import mla_bytes
from readers import mla

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "kanana-2-30b-a3b.json")))
RECORDS = [{"stamps": [(5.0, 400), (60.0, 400)], "t_done": 61.0,
            "prompt_tokens": 7000, "tokens": 800}] * 60


def counters(steps, live, prefill):
    return {"decode_steps": steps, "live_positions": live,
            "prefill_tokens": prefill}


def phase(start, end, **kw):
    kw = {"records": RECORDS, "w0": 10.0, "w1": 50.0, "trace_path": None,
          **kw}
    return NS(stats_start={"engine": {"mla": start} if start else {}},
              stats_end={"engine": {"mla": end} if end else {}}, **kw)


TRAFFIC = json.load(open(os.path.join(BENCH, "traffic",
                                      "report-closed.json")))


def cell():
    return NS(config=CONFIG, tpu=CONFIG["tpu"], traffic=TRAFFIC)


def traced(counted_runs, counted_ops, start, end, **kw):
    ctx = NS(cell=cell(), device={"kind": "TPU v5 lite", "count": 1},
             trace={"window_s": 3.0, "programs": {
                 "jit_prefill(123)": (1.2, 4),
                 "jit_decode_block(7)": (1.7, 7)}},
             phase=phase(start, end, **kw))
    ctx.__dict__["_gdn_runs"] = {"decode_block": counted_runs}
    ctx.__dict__["_dsa_ops"] = {"mla_decode": counted_ops}
    return ctx


START, END = counters(1600, 1600 * 400_000, 10**6), counters(
    3200, 1600 * 400_000 + 1600 * 500_000, 2 * 10**6)


def test_the_latent_share_is_a_count_against_a_count():
    ctx = NS(cell=cell(), phase=phase(START, END), trace=None)
    live = 500_000.0
    want = 100 * mla_bytes.latent_step_bytes(CONFIG, CONFIG["tpu"], live) \
        / mla_bytes.decode_step_bytes(CONFIG, CONFIG["tpu"], live, 60.0)
    assert abs(mla.latent_hbm_share(ctx) - want) < 1e-9
    assert 45 < want < 55
    # the window's last sample is what is read, not the stats after the drain
    drained = counters(9999, 1, 1)
    ctx = NS(cell=cell(), trace=None, phase=phase(START, drained, samples=[
        (49.0, {"engine": {"mla": END}}),
        (51.0, {"engine": {"mla": drained}})]))
    assert abs(mla.latent_hbm_share(ctx) - want) < 1e-9


def test_the_decode_step_and_the_kernel_against_their_roofs():
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 2},
                 {"events": 600, "seconds": 0.6, "prefills": []}, START, END)
    step_s = 1.0 / 4 / 16
    nbytes = mla_bytes.decode_step_bytes(CONFIG, CONFIG["tpu"], 500_000.0,
                                         60.0)
    assert abs(mla.decode_hbm_share(ctx)
               - 100 * nbytes / step_s / 819e9) < 1e-6
    assert 0 < mla.decode_hbm_share(ctx) < 100
    least = mla_bytes.kernel_bytes(CONFIG, CONFIG["tpu"], 500_000.0,
                                   64) / 819e9
    assert least > mla_bytes.kernel_flops(CONFIG, 500_000.0) / 197e12
    assert abs(mla.decode_roofline(ctx, "mla_decode")
               - 100 * 600 * least / 0.6) < 1e-6
    assert 0 < mla.decode_roofline(ctx, "mla_decode") < 100
    # no whole run, or no event of the kernel, in the capture: nothing
    none = traced({"runs": 0, "seconds": 0.0, "cut": 2}, None, START, END)
    assert mla.decode_hbm_share(none) is None
    assert mla.decode_roofline(none, "mla_decode") is None


def test_the_admissions_share_of_the_mxu():
    records = [{"stamps": [(10.0 + i, 4)], "t_done": 60.0,
                "prompt_tokens": 8000, "tokens": 8} for i in range(20)]
    records.append({"stamps": [(5.0, 4)], "t_done": 9.0,
                    "prompt_tokens": 8000, "tokens": 8})  # before the window
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 0}, None, START, END,
                 records=records)
    flops = 20 * mla_bytes.prefill_flops(CONFIG, 8019)
    want = 100 * flops / 40.0 / (1.2 / 3.0) / 197e12
    assert abs(mla.prefill_mxu_share(ctx) - want) < 1e-9
    assert 0 < want < 100


def test_the_prefill_kernel_against_the_mxu_by_a_lower_count():
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 0}, None, START, END)
    # 24 events (3 dispatches x 8 layers) in 0.24 s; the spans of a probe's
    # bucket, the cell's first bucket and its last
    ctx.__dict__["_dsa_ops"]["flash_wide"] = {
        "events": 24, "seconds": 0.24,
        "prefills": [[1024, 1], [6912, 1], [9344, 1]]}
    # each bucket at the least prompt it takes: 1 token (no bucket below),
    # the traffic's shortest prompt + template, one over the bucket below
    least = (1, 6144 + 19, 8448 + 1)
    mean = sum(mla_bytes.flash_call_flops(CONFIG, n) for n in least) / 3
    want = 100 * 24 * mean / 0.24 / 197e12
    assert abs(mla.prefill_roofline(ctx, "flash_wide") - want) < 1e-9
    assert 0 < want < 100
    # priced at the bucket itself it would read higher: the count is lower
    assert want < 100 * 24 * sum(
        mla_bytes.flash_call_flops(CONFIG, b)
        for b in (1024, 6912, 9344)) / 3 / 0.24 / 197e12
    ctx.__dict__["_dsa_ops"]["flash_wide"] = {
        "events": 0, "seconds": 0.0, "prefills": []}
    assert mla.prefill_roofline(ctx, "flash_wide") is None


def test_a_parent_or_another_family_reads_as_nothing():
    readers = (mla.decode_hbm_share, mla.latent_hbm_share,
               mla.prefill_mxu_share,
               lambda c: mla.prefill_roofline(c, "flash_wide"),
               lambda c: mla.decode_roofline(c, "mla_decode"))
    # the parent: this configuration, a program without the counters
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 0},
                 {"events": 0, "seconds": 0.0, "prefills": []}, None, None)
    for reader in readers:
        assert reader(ctx) is None
    ctx.trace = None
    for reader in readers:
        assert reader(ctx) is None
    # another family, whatever its program counts
    other = NS(config={"model_type": "KeyeVL2",
                       "decode_program": "decode_block",
                       "prefill_program": "prefill"}, tpu=CONFIG["tpu"])
    ctx = traced({"runs": 4, "seconds": 1.0, "cut": 0},
                 {"events": 9, "seconds": 0.1, "prefills": []}, START, END)
    ctx.cell = other
    for reader in readers:
        assert reader(ctx) is None
