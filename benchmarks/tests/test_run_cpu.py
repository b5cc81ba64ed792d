"""`run.py` end to end at `tiny` on the CPU: every phase runs, every reader
is walked, and then it REFUSES — non-zero exit, nothing on stdout — because
the engine host's platform is not tpu. The second test is the data-driven
requirement: a new traffic file and a new per-layer metric file dropped into
a copy of the data directory run without `run.py` being touched. The third
is what a `model_config` PR does: a new configuration file, its cell and one
per-layer entry of its own are ADDED — no entry and no file that is there is
edited — and the cell inherits every reading that has no `workloads` key."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, CHECKOUT, TESTS

RUN = os.path.join(BENCH, "run.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def run_cell(manifest, workload, trace=0):
    return subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed",
         "3000000001", "--seconds", "3", "--trace", str(trace),
         "--manifest", manifest], cwd=CHECKOUT, env=ENV,
        capture_output=True, text=True, timeout=600)


def rehearsal_line(stderr):
    lines = [ln for ln in stderr.splitlines() if "rehearsal:" in ln]
    assert lines, stderr[-3000:]
    return lines[-1]


def test_closed_cell_on_the_cpu_refuses_to_print_device_metrics():
    out = run_cell(os.path.join(TESTS, "data", "BENCHMARK.tiny.json"),
                   "tiny.tiny-closed")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not tpu" in out.stderr
    line = rehearsal_line(out.stderr)
    assert "correct=True" in line and "failed=0" in line
    for name in ("out_tok_s", "gap_tail_s", "tpot_p50_ms", "setup_s",
                 "sched_occupancy", "wire_gap_p99_s", "kv_fill",
                 "decode_step_ms", "wire_ttft_p50_s",
                 "wire_ttft_p95_s", "sched_queue_mean_s"):
        assert f"'{name}'" in line, line
    # a closed loop's TTFT is recorded (the queue wait its client count
    # imposes) and judged nowhere; the open cell's own readings stay there
    for name in ("wire_ttft_mean_s", "slo_share", "gen_late_p99_ms"):
        assert f"'{name}'" not in line, line


def test_a_real_cell_is_not_built_on_the_cpu():
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "mistral-7b.chat-open",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=CHECKOUT,
        env=ENV, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_new_cell_and_metric_are_new_files_only(tmp_path):
    data = tmp_path / "data"
    shutil.copytree(os.path.join(TESTS, "data"), data)
    t = json.load(open(data / "traffic" / "tiny-open.json"))
    t.update(rate_per_s=6.0, why="a mix this harness has never heard of")
    json.dump(t, open(data / "traffic" / "tiny-rush.json", "w"))
    os.makedirs(data / "layer_metrics")
    json.dump({"name": "wire_gap_p50_s", "layer": "client / wire",
               "unit": "s", "better": "lower", "source": "host_clock",
               "moves": "gap_tail_s", "reader": "client.gap_percentile_s",
               "params": {"p": 50}},
              open(data / "layer_metrics" / "wire_gap_p50_s.json", "w"))
    m = json.load(open(data / "BENCHMARK.tiny.json"))
    m["workloads"].append({"name": "tiny.tiny-rush", "config": "tiny",
                           "traffic": "tiny-rush", "chips": 1, "why": "new"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "tiny.tiny-open" in metric.get("workloads", []):
            metric["workloads"].append("tiny.tiny-rush")
    m["per_layer"].append({"name": "wire_gap_p50_s", "unit": "s",
                           "better": "lower", "source": "host_clock",
                           "layer": "client / wire", "moves": "gap_tail_s",
                           "workloads": ["tiny.tiny-rush"]})
    json.dump(m, open(data / "BENCHMARK.tiny.json", "w"))
    out = run_cell(str(data / "BENCHMARK.tiny.json"), "tiny.tiny-rush",
                   trace=1)
    assert out.returncode != 0 and out.stdout.strip() == ""
    line = rehearsal_line(out.stderr)
    assert "correct=True" in line and "attempted=18" in line, line
    for name in ("wire_gap_p50_s", "wire_ttft_p50_s", "wire_ttft_mean_s",
                 "wire_ttft_p95_s",
                 "gen_late_p99_ms", "slo_share"):
        assert f"'{name}'" in line, line


def test_a_new_configuration_inherits_the_common_readings(tmp_path):
    data = tmp_path / "data"
    shutil.copytree(os.path.join(TESTS, "data"), data)
    shutil.copyfile(data / "configs" / "tiny.json",
                    data / "configs" / "tiny-drawn.json")
    os.makedirs(data / "layer_metrics")
    json.dump({"name": "drawn_gap_p50_s", "layer": "client / wire",
               "unit": "s", "better": "lower", "source": "host_clock",
               "moves": "gap_tail_s", "reader": "client.gap_percentile_s",
               "params": {"p": 50}},
              open(data / "layer_metrics" / "drawn_gap_p50_s.json", "w"))
    # the manifest that is there: the tests' cells under every entry of the
    # real manifest that has no `workloads` key
    before = json.load(open(data / "BENCHMARK.tiny.json"))
    real = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    have = {e["name"] for e in before["per_layer"]}
    before["per_layer"] += [e for e in real["per_layer"]
                            if "workloads" not in e
                            and e["name"] not in have]
    m = json.loads(json.dumps(before))
    m["configs"].append({"name": "tiny-drawn", "source": "test preset",
                         "file": "configs/tiny-drawn.json", "reduced": [],
                         "why": "a configuration this harness never saw"})
    m["workloads"].append({"name": "tiny-drawn.tiny-closed",
                           "config": "tiny-drawn", "traffic": "tiny-closed",
                           "chips": 1, "why": "new"})
    m["per_layer"].append({"name": "drawn_gap_p50_s", "unit": "s",
                           "better": "lower", "source": "host_clock",
                           "layer": "client / wire", "moves": "gap_tail_s",
                           "workloads": ["tiny-drawn.tiny-closed"]})
    # entries were added; none that was there was touched
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert m[group][:len(before[group])] == before[group]
    json.dump(m, open(data / "BENCHMARK.tiny.json", "w"))
    out = run_cell(str(data / "BENCHMARK.tiny.json"),
                   "tiny-drawn.tiny-closed", trace=1)
    assert out.returncode != 0 and out.stdout.strip() == ""
    line = rehearsal_line(out.stderr)
    assert "correct=True" in line and "failed=0" in line, line
    inherited = [e["name"] for e in before["end_to_end"] + before["per_layer"]
                 if "workloads" not in e]
    assert {"gap_tail_s", "tpot_p50_ms", "setup_s", "wire_gap_p99_s",
            "decode_step_ms", "sched_occupancy", "kv_fill",
            "wire_out_tok_s", "wire_ttft_p50_s", "admit_share",
            "tail_interval_p99_s", "stall_count"} <= set(inherited)
    silent_on_a_cpu = {"hbm_used", "device_idle", "top_op_share",
                       "admit_busy_share"}
    for name in inherited:
        if name in silent_on_a_cpu or name.startswith("idle_in."):
            continue        # device-trace and memory readers: no plane here
        assert f"'{name}'" in line, (name, line)
    assert "'drawn_gap_p50_s'" in line and "'out_tok_s'" not in line, line
