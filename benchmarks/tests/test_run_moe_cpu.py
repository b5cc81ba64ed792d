"""The new cell's shape rehearsed through `run.py` on the CPU: `tiny-moe`,
int8 weights and int8 KV, `mesh {model: 4}` on four virtual devices, a closed
loop, every MoE metric file of the real cell. Every phase runs, every reader
is walked, and then it REFUSES — non-zero exit, nothing on stdout — because
the engine host's platform is not tpu."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, CHECKOUT, TESTS, rehome

RUN = os.path.join(BENCH, "run.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                     + " --xla_force_host_platform_device_count=4").strip()}
CELL = "tiny-moe-tp4.tiny-closed"


def test_moe_cell_on_four_virtual_devices_refuses_but_walks_its_readers(
        tmp_path):
    data = tmp_path / "data"
    shutil.copytree(os.path.join(TESTS, "data"), data)
    real = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    m = json.load(open(data / "BENCHMARK.tiny.json"))
    m["configs"].append({"name": "tiny-moe-tp4", "source": "test preset",
                         "file": "configs/tiny-moe-tp4.json", "reduced": [],
                         "why": "CPU rehearsal of the sharded expert model"})
    m["workloads"].append({"name": CELL, "config": "tiny-moe-tp4",
                           "traffic": "tiny-closed", "chips": 4,
                           "why": "rehearsal"})
    reached = rehome(m, real, "mixtral-8x7b.rag-closed", CELL)
    # the cell's own metrics reach it, by name
    own = {"moe_decode_hbm_share", "moe_prefill_mxu_share",
           "moe_expert_imbalance", "collective_share"}
    assert own <= set(reached), own - set(reached)
    json.dump(m, open(data / "BENCHMARK.tiny.json", "w"))
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000001",
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
                 "moe_expert_imbalance",
                 "wire_out_tok_s", "decode_step_ms",
                 "sched_occupancy", "kv_fill", "wire_gap_p99_s",
                 "admit_share"):
        assert f"'{name}'" in line, line
    # ... and the trace readers found no device plane, and said nothing
    for name in ("moe_decode_hbm_share", "moe_prefill_mxu_share",
                 "collective_share"):
        assert f"'{name}'" not in line, line
