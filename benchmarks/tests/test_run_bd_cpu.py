"""The sdar cell's shape rehearsed through `run.py` on the CPU: `tiny-bd`
(blocks of four under the block mask, two denoise forwards and a commit a
block, GQA with q/k norms, 8 experts top 2), int8 weights and int8 KV, a
closed loop, every metric file of the real cell. Every phase runs — opening
blocks of every size are denoised by the admission program, streams are cut at
their budgets inside a block, and the wire's, the provider's and the engine's
token counts agree — every reader is walked, and then it REFUSES: non-zero
exit, nothing on stdout, because the engine host's platform is not tpu."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, CHECKOUT, TESTS, rehome

RUN = os.path.join(BENCH, "run.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
REAL_CELL = "sdar-30b-a3b-chat.batch-closed"
CELL = "tiny-bd.tiny-closed"


def test_bd_cell_on_the_cpu_refuses_but_walks_its_readers(tmp_path):
    data = tmp_path / "data"
    shutil.copytree(os.path.join(TESTS, "data"), data)
    real = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    m = json.load(open(data / "BENCHMARK.tiny.json"))
    m["configs"].append({"name": "tiny-bd", "source": "test preset",
                         "file": "configs/tiny-bd.json", "reduced": [],
                         "why": "CPU rehearsal of the sdar_moe model"})
    m["workloads"].append({"name": CELL, "config": "tiny-bd",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "rehearsal"})
    reached = rehome(m, real, REAL_CELL, CELL)
    # the cell's own metrics reach it, by name
    own = {"bd_tokens_per_forward", "bd_dropped_share", "bd_forward_ms",
           "bd_decode_hbm_share", "bd_forward_mxu_share",
           "bd_prefill_mxu_share", "moe_expert_imbalance"}
    assert own <= set(reached), own - set(reached)
    json.dump(m, open(data / "BENCHMARK.tiny.json", "w"))
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000047",
         "--seconds", "3", "--trace", "1", "--manifest",
         str(data / "BENCHMARK.tiny.json")], cwd=CHECKOUT, env=ENV,
        capture_output=True, text=True, timeout=900)
    assert out.returncode != 0 and out.stdout.strip() == "", out.stdout
    assert "not tpu" in out.stderr, out.stderr[-3000:]
    lines = [ln for ln in out.stderr.splitlines() if "rehearsal:" in ln]
    assert lines, out.stderr[-3000:]
    line = lines[-1]
    assert "correct=True" in line and "failed=0" in line, line
    # every reader that needs no device trace found something to read
    for name in ("gap_tail_s", "tpot_p50_ms", "setup_s",
                 "bd_tokens_per_forward", "bd_dropped_share",
                 "moe_expert_imbalance", "wire_out_tok_s",
                 "decode_step_ms", "sched_occupancy", "kv_fill",
                 "wire_ttft_p50_s", "wire_gap_p99_s", "admit_share"):
        assert f"'{name}'" in line, line
    # ... and the trace readers found no device plane (nor the CPU a
    # memory limit), and said nothing
    for name in ("bd_forward_ms", "bd_decode_hbm_share",
                 "bd_forward_mxu_share", "bd_prefill_mxu_share",
                 "hbm_used"):
        assert f"'{name}'" not in line, line
