"""`readers/gdn.py whole_runs`: the decode step is timed from WHOLE runs of
the decode program — the runs a capture cuts at its edges, which
`lib/xplane.py` counts as runs, are left out."""

from types import SimpleNamespace as NS

from readers import gdn


def plane(name, events):
    return NS(name=name, lines=[NS(name="XLA Modules", events=[
        NS(name=n, start_ns=s, duration_ns=d) for n, s, d in events])])


def test_runs_cut_by_the_captures_edges_are_left_out():
    ms = 1_000_000
    events = [("jit_decode_block(7)", 0, 90 * ms),          # cut at the start
              ("jit_prefill(3)", 90 * ms, 30 * ms),
              ("jit_decode_block(7)", 120 * ms, 240 * ms),
              ("jit_decode_block(7)", 360 * ms, 244 * ms),
              ("jit_insert_all(5)", 604 * ms, 2 * ms),
              ("jit_decode_block(7)", 606 * ms, 236 * ms),
              ("jit_decode_block(7)", 842 * ms, 158 * ms)]  # cut at the end
    data = NS(planes=[plane("/device:TPU:0", events),
                      plane("/host:CPU", [("jit_decode_block(7)", 0, ms)])])
    out = gdn.whole_runs(data, "decode_block")
    assert out["runs"] == 3 and out["cut"] == 2
    assert abs(out["seconds"] - 0.720) < 1e-9
    # xplane's count would be 5 runs over 0.968 s: a step read 19% short


def test_a_short_run_inside_the_capture_is_cut_too_and_nothing_reads_none():
    ms = 1_000_000
    events = [("jit_prefill(3)", 0, 10 * ms),
              ("jit_decode_block(7)", 10 * ms, 100 * ms),   # under 80%
              ("jit_decode_block(7)", 110 * ms, 240 * ms),
              ("jit_decode_block(7)", 350 * ms, 240 * ms),
              ("jit_prefill(3)", 590 * ms, 10 * ms)]
    out = gdn.whole_runs(NS(planes=[plane("/device:TPU:0", events)]),
                         "decode_block")
    assert (out["runs"], out["cut"]) == (2.0, 1.0)
    assert abs(out["seconds"] - 0.48) < 1e-9
    empty = gdn.whole_runs(NS(planes=[plane("/device:TPU:0", events)]),
                           "verify")
    assert empty == {"runs": 0.0, "seconds": 0.0, "cut": 0.0}


def test_readers_say_nothing_for_another_family_or_without_a_trace():
    cell = NS(config={"model_type": "granitemoehybrid",
                      "decode_program": "decode_block"}, tpu={})
    ctx = NS(cell=cell, trace={"window_s": 1.0, "programs": {}}, phase=None)
    assert gdn.decode_hbm_share(ctx) is None
    assert gdn.prefill_mxu_share(ctx) is None
    mine = NS(config={"model_type": "qwen3_next",
                      "decode_program": "decode_block",
                      "prefill_program": "prefill"}, tpu={})
    assert gdn.decode_hbm_share(NS(cell=mine, trace=None, phase=None)) is None
    assert gdn.prefill_mxu_share(NS(cell=mine, trace=None,
                                    phase=None)) is None
