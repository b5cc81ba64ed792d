"""`readers/xm.py`: the counters' growth over a window, the decode step
against the HBM, the module's share of a decode block from a made-up
capture, and the readers that say nothing for another family, for a program
without the counters (the parent) and without a trace."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from conftest import BENCH
from lib import xm_bytes
from readers import xm

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "k-exaone-236b-a23b.json")))
TRAFFIC = json.load(open(os.path.join(BENCH, "traffic",
                                      "reason-closed.json")))
RECORDS = [{"stamps": [(12.0, 400), (60.0, 400)], "t_done": 61.0,
            "prompt_tokens": 600, "tokens": 2000}] * 10


def engine(steps, full, ring, drafted, accepted, pairs, held):
    return {"swa": {"decode_steps": steps, "full_rows": full,
                    "ring_rows": ring, "ring_wraps": 0,
                    "prefill_tokens": 10 * steps},
            "mtp": {"drafted": drafted, "accepted": accepted,
                    "emitted": drafted + accepted, "steps": 64 * steps,
                    "prefill_tokens": 10 * steps},
            "moe": {"pairs": pairs, "held_pairs": held,
                    "absent_pairs": pairs - held, "expert_hits": 1,
                    "expert_pairs": [1] * 16, "route": {"decode": "routed"}}}


FULL, RING = 64 * 3000, 64 * 128
START = engine(1600, 1600 * FULL, 1600 * RING, 100000, 300, 8000, 1000)
END = engine(3200, 3200 * FULL, 3200 * RING, 200000, 700, 16000, 2000)


def ctx_of(start, end, config=CONFIG, trace=None, **kw):
    kw = {"records": RECORDS, "w0": 10.0, "w1": 50.0, "trace_path": None,
          **kw}
    return NS(cell=NS(config=config, tpu=config["tpu"], traffic=TRAFFIC),
              device={"kind": "TPU v5 lite", "count": 1}, trace=trace,
              phase=NS(stats_start={"engine": start},
                       stats_end={"engine": end}, **kw))


def test_the_counters_growth_gives_the_three_counted_shares():
    ctx = ctx_of(START, END)
    assert xm.accept_share(ctx) == pytest.approx(100 * 400 / 100000)
    assert xm.held_pair_share(ctx) == pytest.approx(12.5)
    tpu = CONFIG["tpu"]
    rows = sum(xm_bytes.cache_step_bytes(CONFIG, tpu, FULL, RING))
    step = xm_bytes.decode_step_bytes(CONFIG, tpu, FULL, RING, 64.0)
    assert xm.cache_hbm_share(ctx) == pytest.approx(100 * rows / step)
    assert 10 < xm.cache_hbm_share(ctx) < 20
    # the window's last sample is what is read, not the stats after the drain
    drained = engine(9999, 1, 1, 1, 1, 1, 1)
    ctx = ctx_of(START, drained, samples=[(49.0, {"engine": END}),
                                          (51.0, {"engine": drained})])
    assert xm.accept_share(ctx) == pytest.approx(0.4)
    assert xm.cache_hbm_share(ctx) == pytest.approx(100 * rows / step)


def test_the_decode_step_against_the_hbm_and_the_prefill_against_the_mxu():
    trace = {"window_s": 3.0, "programs": {
        "jit_prefill(123)": (0.3, 4), "jit_mtp_decode_block(7)": (2.5, 7)}}
    ctx = ctx_of(START, END, trace=trace)
    ctx.__dict__["_gdn_runs"] = {"decode_block": {
        "runs": 5, "seconds": 2.0, "cut": 2}}
    step_s = 2.0 / 5 / 16
    nbytes = xm_bytes.decode_step_bytes(CONFIG, CONFIG["tpu"], FULL, RING,
                                        64.0)
    assert xm.decode_hbm_share(ctx) == pytest.approx(
        100 * nbytes / step_s / 819e9)
    assert 50 < xm.decode_hbm_share(ctx) < 75
    flops = 10 * xm_bytes.prefill_flops(CONFIG, 619)
    assert xm.prefill_mxu_share(ctx) == pytest.approx(
        100 * flops / 40.0 / (0.3 / 3.0) / 197e12)
    assert 0 < xm.prefill_mxu_share(ctx) < 100


def test_the_modules_share_is_its_scopes_ops_inside_the_decode_blocks():
    """A made-up raw `XSpace`: an op's `op_name` is the `tf_op` stat of its
    event METADATA (by value or by a reference into the stat names)."""
    stat_names = {1: NS(name="tf_op"), 2: NS(name="flops"),
                  3: NS(name="jit(mtp_decode_block)/mtp_module/moe_gmm")}

    def md(name, op_name=None, ref=0):
        stats = [NS(metadata_id=2, str_value="", ref_value=0)]
        if op_name or ref:
            stats.append(NS(metadata_id=1, str_value=op_name or "",
                            ref_value=ref))
        return NS(name=name, stats=stats)

    metadata = {
        10: md("jit_mtp_decode_block(7)"), 11: md("jit_prefill(3)"),
        20: md("%fusion.1", "jit(mtp_decode_block)/while/body/mtp_module/"
                            "dot_general:"),
        21: md("%fusion.2", "jit(mtp_decode_block)/while/body/layers/dot:"),
        22: md("%moe_gmm.3", ref=3),
        23: md("%fusion.4", "jit(prefill)/mtp_module/dot_general:")}

    def ev(m, start_ns, dur_ns):
        return NS(metadata_id=m, offset_ps=start_ns * 1000,
                  duration_ps=dur_ns * 1000)

    space = NS(planes=[
        NS(name="/host:CPU", lines=[], stat_metadata={}, event_metadata={}),
        NS(name="/device:TPU:0", stat_metadata=stat_names,
           event_metadata=metadata, lines=[
               NS(name="XLA Modules", timestamp_ns=5, events=[
                   ev(10, 0, 1000), ev(11, 2000, 1000), ev(10, 4000, 1000)]),
               NS(name="XLA Ops", timestamp_ns=5, events=[
                   ev(20, 100, 50), ev(21, 200, 300), ev(22, 600, 100),
                   # the same scope OUTSIDE a decode block (the prefill's)
                   ev(23, 2100, 500)])])])
    counted = xm.scope_seconds(space, "decode_block", "mtp_module")
    assert counted == {"scope_s": pytest.approx(150e-9),
                       "program_s": pytest.approx(2000e-9)}
    ctx = ctx_of(START, END, trace={"window_s": 3.0, "programs": {}})
    ctx.__dict__["_xm_scopes"] = {"mtp_module": counted}
    assert xm.draft_share(ctx) == pytest.approx(7.5)
    # a capture without the scope (the parent's program) reads as nothing
    ctx.__dict__["_xm_scopes"] = {"mtp_module": {"scope_s": 0.0,
                                                 "program_s": 2e-6}}
    assert xm.draft_share(ctx) is None
    # the recorded TPU trace of the tests has no such program: zeros
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    real = xplane_pb2.XSpace()
    with open(os.path.join(BENCH, "tests", "data",
                           "small_tpu.xplane.pb"), "rb") as fh:
        real.ParseFromString(fh.read())
    assert xm.scope_seconds(real, "decode_block", "mtp_module") == {
        "scope_s": 0.0, "program_s": 0.0}
    found = xm.scope_seconds(real, "jit_step", "dot_general")
    assert 0 < found["scope_s"] <= found["program_s"]


@pytest.mark.parametrize("reader", [
    xm.accept_share, xm.held_pair_share, xm.cache_hbm_share,
    xm.decode_hbm_share, xm.prefill_mxu_share, xm.draft_share])
def test_a_reader_with_nothing_to_read_says_nothing(reader):
    other = json.load(open(os.path.join(BENCH, "configs",
                                        "smallthinker-21b-a3b.json")))
    trace = {"window_s": 3.0, "programs": {"jit_prefill(1)": (0.3, 4)}}
    # another family's configuration, whatever the counters say
    assert reader(ctx_of(START, END, config=other, trace=trace)) is None
    # the parent's program: no `mtp` block, no `swa` growth, no scope
    bare = ctx_of({}, {}, trace=trace)
    bare.__dict__["_gdn_runs"] = {"decode_block": None}
    bare.__dict__["_xm_scopes"] = {"mtp_module": None}
    assert reader(bare) is None
    # no trace: the trace readers say nothing, the counted ones still read
    untraced = ctx_of(START, END)
    if reader in (xm.decode_hbm_share, xm.prefill_mxu_share,
                  xm.draft_share):
        assert reader(untraced) is None
    else:
        assert reader(untraced) is not None
