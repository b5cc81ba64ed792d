"""`run.py` end to end at `tiny` on the CPU: every phase runs, every reader
is walked, and then it REFUSES — non-zero exit, nothing on stdout — because
the engine host's platform is not tpu. The second test is the data-driven
requirement: a new traffic file and a new per-layer metric file dropped into
a copy of the data directory run without `run.py` being touched."""

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
    for name in ("out_tok_s", "gap_p99_s", "setup_s", "sched_occupancy",
                 "kv_fill", "decode_step_ms.closed"):
        assert f"'{name}'" in line, line
    assert "ttft" not in line  # judged and read in the open cell only


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
               "moves": "gap_p99_s", "reader": "client.gap_percentile_s",
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
                           "layer": "client / wire", "moves": "gap_p99_s",
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
