"""`readers/sconv.py` on a synthetic capture: the decode step is timed from
WHOLE runs (`readers/gdn.py`'s count), the bytes come from
`lib/sconv_bytes.py`, and a reader with nothing to read says nothing."""

import json
import os
from types import SimpleNamespace as NS

from conftest import BENCH
from lib import sconv_bytes
from readers import sconv

CONFIG = json.load(open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")))


def context(trace=None, counted=None, records=()):
    cell = NS(config=CONFIG, tpu=CONFIG["tpu"])
    phase = NS(records=list(records), w0=0.0, w1=10.0, trace_path=None)
    ctx = NS(cell=cell, trace=trace, phase=phase,
             device={"kind": "TPU v5 lite", "count": 1})
    if counted is not None:      # what `readers.gdn._counted` caches a run
        ctx._gdn_runs = {"decode_block": counted}
    return ctx


def stream(t0, t1, prompt, n):
    """One client record: first token at t0, done at t1, n tokens."""
    step = (t1 - t0) / n
    return {"stamps": [(t0 + i * step, 1) for i in range(n)], "t_done": t1,
            "prompt_tokens": prompt, "tokens": n}


def test_decode_share_is_step_bytes_over_whole_runs_over_the_hbm_peak():
    # 10 whole runs of 16 steps in 2.4 s: 15 ms a step
    counted = {"runs": 10.0, "seconds": 2.4, "cut": 2.0}
    ctx = context(trace={"window_s": 3.0, "programs": {}}, counted=counted)
    got = sconv.decode_hbm_share(ctx)
    nbytes = sconv_bytes.step_bytes(CONFIG, CONFIG["tpu"], 0, 0)
    want = 100 * nbytes / 0.015 / 819e9
    assert abs(got - want) < 1e-9 and 65 < got < 75
    # no whole run in the capture: nothing
    none = context(trace={"window_s": 3.0, "programs": {}},
                   counted={"runs": 0.0, "seconds": 0.0, "cut": 2.0})
    assert sconv.decode_hbm_share(none) is None


def test_prefill_share_counts_the_active_flops_of_the_windows_prompts():
    records = [stream(1.0, 9.0, 100, 200), stream(2.0, 9.5, 60, 150),
               stream(11.0, 12.0, 500, 10)]             # outside the window
    trace = {"window_s": 3.0,
             "programs": {"jit_prefill(1)": (0.06, 4),
                          "jit_decode_block(2)": (2.5, 10)}}
    got = sconv.prefill_mxu_share(context(trace=trace, records=records))
    flops = (sconv_bytes.prefill_flops(CONFIG, 119)
             + sconv_bytes.prefill_flops(CONFIG, 79))
    want = 100 * flops / 10.0 / (0.06 / 3.0) / 197e12
    assert abs(got - want) < 1e-9 and 0 < got < 100
    no_prefill = dict(trace, programs={"jit_decode_block(2)": (2.5, 10)})
    assert sconv.prefill_mxu_share(context(trace=no_prefill,
                                           records=records)) is None


def test_readers_say_nothing_for_another_family_or_without_a_trace():
    other = NS(config={"model_type": "qwen3_next",
                       "decode_program": "decode_block",
                       "prefill_program": "prefill"}, tpu={})
    ctx = NS(cell=other, trace={"window_s": 1.0, "programs": {}}, phase=None)
    assert sconv.decode_hbm_share(ctx) is None
    assert sconv.prefill_mxu_share(ctx) is None
    assert sconv.decode_hbm_share(context()) is None
    assert sconv.prefill_mxu_share(context()) is None
