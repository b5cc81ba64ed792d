"""`readers/bd.py`: the counters' ratios over a window, the mean forward
against both roofs, and the readers that say nothing for another family, for
a program without the counters (the parent) and without a trace."""

import json
import os
from types import SimpleNamespace as NS

from conftest import BENCH
from lib import bd_bytes
from readers import bd

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "sdar-30b-a3b-chat.json")))
STARTUP = {"diffusion": {"block": 4, "steps": 2, "forwards_per_dispatch": 12}}


def counters(forwards, commit, live, committed, dropped, opening):
    return {"block": 4, "steps": 2, "rule": "low_confidence_static",
            "threshold": None, "forwards": forwards,
            "commit_forwards": commit, "admit_forwards": 0,
            "live_slot_forwards": live, "positions_unmasked": 0,
            "tokens_committed": committed, "tokens_dropped": dropped,
            "opening_block_tokens": opening}


def phase(start, end, **kw):
    return NS(stats_start={"engine": {"diffusion": start} if start else {}},
              stats_end={"engine": {"diffusion": end, "startup": STARTUP}
                         if end else {}}, **kw)


def cell():
    return NS(config=CONFIG, tpu=CONFIG["tpu"])


def test_the_counters_ratios_are_their_growth_over_the_window():
    start = counters(120, 40, 120 * 100, 16000, 1000,
                     {"1": 10, "2": 10, "3": 10, "4": 10})
    # 10 dispatches at 100 live slots: 16,000 tokens made for them, 15,200
    # kept; 28 idle slots' 4,480 dropped; 8 admissions of 2 tokens
    end = counters(240, 80, 240 * 100, 16000 + 15200 + 16,
                   1000 + 800 + 4480, {"1": 10, "2": 18, "3": 10, "4": 10})
    ctx = NS(cell=cell(), phase=phase(start, end))
    assert abs(bd.tokens_per_forward(ctx) - 15200 / 12000) < 1e-12
    assert bd.commit_share(ctx) == 100 * 40 / 120
    # the window's last sample is what is read, not the stats after the drain
    drained = counters(480, 160, 300 * 100, 40000, 90000, {"2": 30})
    ctx = NS(cell=cell(), phase=phase(start, drained, w1=50.0, samples=[
        (49.0, {"engine": {"diffusion": end}}),
        (51.0, {"engine": {"diffusion": drained}})]))
    assert bd.commit_share(ctx) == 100 * 40 / 120
    assert abs(bd.tokens_per_forward(ctx) - 15200 / 12000) < 1e-12
    assert abs(bd.dropped_share(ctx)
               - 100 * 5280 / (15216 + 5280)) < 1e-9
    # nothing dropped: block / (steps + 1)
    full = counters(240, 80, 240 * 100, 16000 + 16000, 1000,
                    {"1": 10, "2": 10, "3": 10, "4": 10})
    assert abs(bd.tokens_per_forward(NS(cell=cell(),
                                        phase=phase(start, full)))
               - 4 / 3) < 1e-12


def test_a_parent_or_another_family_reads_as_nothing():
    ctx = NS(cell=cell(), phase=phase(None, None), trace=None)
    for reader in (bd.tokens_per_forward, bd.commit_share, bd.dropped_share,
                   bd.forward_ms, bd.decode_hbm_share, bd.forward_mxu_share,
                   bd.prefill_mxu_share):
        assert reader(ctx) is None
    other = NS(config={"model_type": "KeyeVL2",
                       "decode_program": "decode_block",
                       "prefill_program": "prefill"}, tpu={})
    end = counters(240, 80, 24000, 100, 10, {"1": 1})
    ctx = NS(cell=other, phase=phase(end, end),
             trace={"window_s": 1.0, "programs": {}})
    for reader in (bd.tokens_per_forward, bd.forward_ms,
                   bd.decode_hbm_share, bd.prefill_mxu_share):
        assert reader(ctx) is None


def traced(counted, records):
    end = counters(240, 80, 24000, 100, 10, {"1": 1})
    ctx = NS(cell=cell(), device={"kind": "TPU v5 lite", "count": 1},
             trace={"window_s": 3.0, "programs": {
                 "jit_bd_prefill(123)": (0.6, 20),
                 "jit_bd_decode_block(7)": (2.0, 14)}},
             phase=phase(end, end, records=records, w0=10.0, w1=50.0,
                         trace_path=None))
    ctx.__dict__["_gdn_runs"] = {"bd_decode_block": counted}
    return ctx


def test_the_mean_forward_against_both_roofs():
    records = [{"stamps": [(5.0, 40), (60.0, 40)], "t_done": 61.0,
                "prompt_tokens": 81, "tokens": 80}] * 100
    ctx = traced({"runs": 10, "seconds": 1.5, "cut": 1}, records)
    forward_s = 1.5 / 10 / 12
    assert abs(bd.forward_ms(ctx) - 1e3 * forward_s) < 1e-12
    lengths = [100 + 40] * 100      # prompt + template + half the reply
    nbytes = bd_bytes.forward_bytes(CONFIG, CONFIG["tpu"], lengths, 4, 2 / 3)
    flops = bd_bytes.forward_flops(CONFIG, CONFIG["tpu"], lengths, 4, 2 / 3)
    assert abs(bd.decode_hbm_share(ctx)
               - 100 * nbytes / forward_s / 819e9) < 1e-6
    assert abs(bd.forward_mxu_share(ctx)
               - 100 * flops / forward_s / 197e12) < 1e-6
    assert 0 < bd.decode_hbm_share(ctx) < 100
    assert 0 < bd.forward_mxu_share(ctx) < 100
    # no whole run in the capture: nothing
    assert bd.forward_ms(traced({"runs": 0, "seconds": 0.0, "cut": 2},
                                records)) is None
    assert bd.forward_ms(traced(None, records)) is None


def test_the_admissions_share_of_the_mxu():
    records = [{"stamps": [(10.0 + i, 4)], "t_done": 60.0,
                "prompt_tokens": 100, "tokens": 8} for i in range(20)]
    records.append({"stamps": [(5.0, 4)], "t_done": 9.0,
                    "prompt_tokens": 100, "tokens": 8})   # before the window
    ctx = traced({"runs": 10, "seconds": 1.5, "cut": 0}, records)
    flops = 20 * bd_bytes.prefill_flops(CONFIG, 119, 4, 2)
    want = 100 * flops / 40.0 / (0.6 / 3.0) / 197e12
    assert abs(bd.prefill_mxu_share(ctx) - want) < 1e-9
