"""The nemotron cell's shape rehearsed through `run.py` on the CPU: `tiny-nh`
(blocks of one sub-layer each — "MEM*EMEM*EME" — two groups of mamba heads,
no rotary, ungated relu2 experts of which the chip holds four of eight), int8
weights and an int8 cache, a closed loop, every metric file of the real cell.
Every phase runs, every reader is walked, and then it REFUSES: non-zero exit,
nothing on stdout, because the engine host's platform is not tpu."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, CHECKOUT, TESTS, rehome

RUN = os.path.join(BENCH, "run.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
REAL_CELL = "nemotron-3-nano-30b-a3b.batch64-closed"
CELL = "tiny-nh.tiny-closed"


def test_nh_cell_on_the_cpu_refuses_but_walks_its_readers(tmp_path):
    data = tmp_path / "data"
    shutil.copytree(os.path.join(TESTS, "data"), data)
    real = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    m = json.load(open(data / "BENCHMARK.tiny.json"))
    m["configs"].append({"name": "tiny-nh", "source": "test preset",
                         "file": "configs/tiny-nh.json", "reduced": [],
                         "why": "CPU rehearsal of the nemotron_h model"})
    m["workloads"].append({"name": CELL, "config": "tiny-nh",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "rehearsal"})
    reached = rehome(m, real, REAL_CELL, CELL)
    # the cell's own metrics reach it, by name
    own = {"nh_decode_hbm_share", "nh_prefill_mxu_share",
           "nh_held_pair_share", "nh_ssm_step_roofline",
           "nh_expert_roofline", "moe_expert_imbalance.nh",
           "state_hbm_share.nh", "state_prefill_tok_s.nh",
           "state_installs_per_s.nh"}
    assert own <= set(reached), own - set(reached)
    # ... and the common readings by inheritance, with no entry of its own
    assert {"decode_step_ms", "device_idle", "hbm_used", "kv_fill",
            "sched_occupancy"} <= set(reached)
    json.dump(m, open(data / "BENCHMARK.tiny.json", "w"))
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000061",
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
                 "nh_held_pair_share", "moe_expert_imbalance.nh",
                 "state_prefill_tok_s.nh", "state_installs_per_s.nh",
                 "wire_out_tok_s", "decode_step_ms", "sched_occupancy",
                 "kv_fill", "wire_ttft_p50_s", "wire_gap_p99_s",
                 "admit_share"):
        assert f"'{name}'" in line, line
    # ... and the trace readers found no device plane (nor the CPU a
    # memory limit), and said nothing
    for name in ("nh_decode_hbm_share", "nh_prefill_mxu_share",
                 "nh_ssm_step_roofline", "nh_expert_roofline",
                 "state_hbm_share.nh", "hbm_used"):
        assert f"'{name}'" not in line, line
