"""The program-span reduction (`lib/spans.py`) on a synthetic trace with
hand-placed busy intervals and `sym.*` spans, its readers on what it
returns, and every new metric walked by `run.py` on the tiny CPU cell
(each returns a value or None, never raises)."""

import json
import os
import shutil
from types import SimpleNamespace as NS

import pytest

from lib import spans
from readers import spans as readers

from conftest import CHECKOUT, TESTS, cell_entries
from test_run_cpu import rehearsal_line, run_cell

MANIFEST = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
NEW = [m for m in MANIFEST["per_layer"] if m["name"].startswith(
    ("idle_in.", "admit_busy_share", "sched_sync_share",
     "sched_process_share", "compile_share", "lowerings_in_window",
     "stage_"))]


def ev(name, start_s, dur_s):
    return NS(name=name, start_ns=start_s * 1e9, duration_ns=dur_s * 1e9,
              stats=[])


def op(start_s, dur_s):
    return ev("%fusion.1 = bf16[8,8]{1,0} fusion(%a)", start_s, dur_s)


def synthetic():
    """Device busy 0–2, 3–4, 6–7, 9–10: idle 2–3, 4–6, 7–9 = 5 s of a 10 s
    window. The engine thread: sync 0–2.5, process 2.5–3, admit 3–5 (with
    its prefill child), dispatch 5–5.5, nothing 5.5–7, admit 7–8, wait
    8–9.5. A second device plane and a sleeping capture thread must not
    count."""
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[op(0, 1), op(1, 1), op(3, 1), op(6, 1),
                                   op(9, 1)]),
        NS(name="XLA Modules", events=[ev("jit_decode_block(1)", 0, 2)])])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[op(0, 10)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("sym.capture", 0, 10),
                                  ev("$time sleep", 0, 10)]),
        NS(name="python", events=[
            ev("sym.sched.sync", 0, 2.5), ev("sym.sched.process", 2.5, 0.5),
            ev("sym.sched.admit", 3, 2), ev("sym.engine.prefill#n=2#", 3.1,
                                            1.8),
            ev("sym.sched.dispatch", 5, 0.5), ev("sym.sched.admit", 7, 1),
            ev("sym.sched.wait", 8, 1.5),
            ev("$scheduler.py:1 _admit_new", 3, 2)]),
        NS(name="python", events=[ev("sym.emit.emit_flush", 2.6, 0.2)])])
    return NS(planes=[dev0, dev1, host])


def test_idle_split_is_exact_and_sums_to_the_idle_time():
    out = spans.reduce_spans(synthetic())
    assert out["window_s"] == pytest.approx(9.5)
    assert out["idle_s"] == pytest.approx(5.0)
    assert out["idle_in"] == pytest.approx({
        "sync": 0.5,        # 2–2.5
        "process": 0.5,     # 2.5–3
        "admit": 2.0,       # 4–5 and 7–8
        "other": 1.5,       # dispatch 5–5.5, wait 8–9
        "none": 0.5})       # 5.5–6
    assert sum(out["idle_in"].values()) == pytest.approx(out["idle_s"])
    # admit wall 3 s; the device ran during 3–4 of it
    assert out["admit_s"] == pytest.approx(3.0)
    assert out["admit_busy_s"] == pytest.approx(1.0)
    assert out["sched_lines"] == 1 and out["sched_overlap_s"] == (
        pytest.approx(0.0))
    assert out["sched_cover_s"] == pytest.approx(8.0)
    assert out["phase_s"]["sym.engine.prefill"] == pytest.approx(1.8)
    assert out["phase_s"]["sym.capture"] == pytest.approx(10.0)
    assert out["events"] == 9


def test_readers_turn_it_into_shares():
    ctx = NS(_spans=spans.reduce_spans(synthetic()))
    shares = {p: readers.idle_in(ctx, p)
              for p in ("admit", "sync", "process", "other", "none")}
    assert shares == pytest.approx({"admit": 40.0, "sync": 10.0,
                                    "process": 10.0, "other": 30.0,
                                    "none": 10.0})
    assert sum(shares.values()) == pytest.approx(100.0)
    assert readers.admit_busy_share(ctx) == pytest.approx(100.0 / 3)


def test_only_the_part_the_loop_phases_cover_is_read():
    """The profiler drops the phase in progress when the capture starts:
    with no phase before 3.0 the window is 3–9.5, and the idle of 2–3 is
    left out, not read as a hole."""
    data = synthetic()
    line = data.planes[2].lines[1]
    line.events = [e for e in line.events
                   if e.name not in ("sym.sched.sync", "sym.sched.process")]
    out = spans.reduce_spans(data)
    assert out["window_s"] == pytest.approx(6.5)
    assert out["idle_s"] == pytest.approx(4.0)
    assert out["idle_in"] == pytest.approx({
        "sync": 0.0, "process": 0.0, "admit": 2.0, "other": 1.5,
        "none": 0.5})


def test_admit_is_clipped_to_the_device_window():
    data = synthetic()
    data.planes[2].lines[1].events.append(ev("sym.sched.admit", 9.5, 4.0))
    out = spans.reduce_spans(data)
    assert out["admit_s"] == pytest.approx(3.5)      # 9.5–10 of the last
    assert out["admit_busy_s"] == pytest.approx(1.5)


def test_a_trace_without_program_spans_reduces_to_nothing():
    data = synthetic()
    for line in data.planes[2].lines:
        line.events = [e for e in line.events
                       if not e.name.startswith("sym.")]
    assert spans.reduce_spans(data) is None
    ctx = NS(_spans=None)
    assert readers.idle_in(ctx, "admit") is None
    assert readers.admit_busy_share(ctx) is None


def test_spans_without_a_device_plane_give_no_share():
    data = synthetic()
    data.planes = data.planes[2:]
    out = spans.reduce_spans(data)
    assert out["idle_s"] is None and out["phase_s"]["sym.sched.sync"] == (
        pytest.approx(2.5))
    ctx = NS(_spans=out)
    assert readers.idle_in(ctx, "none") is None
    assert readers.admit_busy_share(ctx) is None


def test_the_recorded_trace_of_an_older_program_reads_as_nothing():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(
        os.path.join(TESTS, "data", "small_tpu.xplane.pb"))
    assert spans.reduce_spans(data) is None


def test_counter_delta_and_missing_counters():
    ctx = NS(phase=NS(samples=[
        (1.0, {"engine": {"compile": {"lowerings": 40}}}),
        (2.0, {"engine": {"compile": {"lowerings": 43}}}),
        (3.0, {"engine": {"compile": {"lowerings": 47}}})]))
    path = "engine.compile.lowerings"
    assert readers.counter_delta(ctx, path) == 7
    ctx.phase.samples[0] = (1.0, {"engine": {}})     # the parent commit
    assert readers.counter_delta(ctx, path) is None


def test_overlap_of_interval_lists():
    a = [(0.0, 2.0), (3.0, 4.0), (6.0, 9.0)]
    b = [(1.0, 3.5), (8.0, 10.0)]
    assert spans.overlap(a, b) == pytest.approx(1.0 + 0.5 + 1.0)
    assert spans.overlap(a, []) == 0.0
    assert spans.clip(a, 1.0, 7.0) == [(1.0, 2.0), (3.0, 4.0), (6.0, 7.0)]


def test_new_metrics_run_on_the_tiny_cpu_cell(tmp_path):
    """The manifest's new entries, appended to a copy of the tiny
    manifest: a traced run walks every new reader. On the CPU there is no
    device plane, so the span shares read as nothing; the counters of the
    program read as values."""
    data = tmp_path / "data"
    shutil.copytree(os.path.join(TESTS, "data"), data)
    m = json.load(open(data / "BENCHMARK.tiny.json"))
    for entry in NEW:
        entry = dict(entry)
        if "workloads" in entry:
            entry["workloads"] = ["tiny.tiny-open"]
        m["per_layer"].append(entry)
    json.dump(m, open(data / "BENCHMARK.tiny.json", "w"))
    out = run_cell(str(data / "BENCHMARK.tiny.json"), "tiny.tiny-open",
                   trace=1)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "not tpu" in out.stderr and "Traceback" not in out.stderr
    line = rehearsal_line(out.stderr)
    assert "correct=True" in line and "failed=0" in line
    for name in ("sched_sync_share", "sched_process_share", "compile_share",
                 "lowerings_in_window", "stage_pipe_in_mean_s",
                 "stage_prefill_mean_s", "stage_emit_mean_s",
                 "stage_relay_mean_s"):
        assert f"'{name}'" in line, line
    assert "idle_in" not in line and "admit_busy_share" not in line
    found = [ln for ln in out.stderr.splitlines()
             if "program spans in the capture" in ln]
    assert found and '"sym.sched.sync"' in found[-1], out.stderr[-2000:]


def test_the_manifest_names_the_new_metrics():
    names = {m["name"] for m in NEW}
    assert {"idle_in.admit", "idle_in.sync", "idle_in.process",
            "idle_in.other", "idle_in.none", "admit_busy_share",
            "sched_sync_share", "sched_process_share", "compile_share",
            "lowerings_in_window", "stage_pipe_in_mean_s",
            "stage_prefill_mean_s", "stage_emit_mean_s",
            "stage_relay_mean_s"} == names
    reached = {m["name"] for m in cell_entries(MANIFEST,
                                               "mistral-7b.chat-open")}
    assert names <= reached
    for m in NEW:
        # each moves a metric that every cell it reaches reports: the
        # scheduler's and the stages' entries the judged tail of the gaps
        assert m["moves"] == "gap_tail_s"
