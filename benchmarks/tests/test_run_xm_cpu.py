"""The K-EXAONE cell's shape rehearsed through `run.py` on the CPU:
`tiny-xm-bytes` (window and full layers, a held share of the experts, the
multi-token-prediction module DRAFTING inside the decode block — every step
a two-position verify, a lane's tokens coming back packed and counted),
int8 weights and an int8 cache, a closed loop, every metric file of the
real cell. Every phase runs, every reader is walked, the wire's tokens are
the host's to the token, and then it REFUSES: non-zero exit, nothing on
stdout, because the engine host's platform is not tpu."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, CHECKOUT, TESTS, rehome

RUN = os.path.join(BENCH, "run.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
REAL_CELL = "k-exaone-236b-a23b.reason-closed"
CELL = "tiny-xm.tiny-closed"


def test_xm_cell_on_the_cpu_refuses_but_walks_its_readers(tmp_path):
    data = tmp_path / "data"
    shutil.copytree(os.path.join(TESTS, "data"), data)
    real = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    m = json.load(open(data / "BENCHMARK.tiny.json"))
    m["configs"].append({"name": "tiny-xm", "source": "test preset",
                         "file": "configs/tiny-xm.json", "reduced": [],
                         "why": "CPU rehearsal of the K-EXAONE model"})
    m["workloads"].append({"name": CELL, "config": "tiny-xm",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "rehearsal"})
    reached = rehome(m, real, REAL_CELL, CELL)
    # the cell's own metrics reach it, by name
    own = {"xm_decode_hbm_share", "xm_prefill_mxu_share", "mtp_accept_share",
           "mtp_draft_share", "xm_held_pair_share", "xm_cache_hbm_share",
           "moe_expert_imbalance.xm"}
    assert own <= set(reached), own - set(reached)
    json.dump(m, open(data / "BENCHMARK.tiny.json", "w"))
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000065",
         "--seconds", "3", "--trace", "1", "--manifest",
         str(data / "BENCHMARK.tiny.json")], cwd=CHECKOUT, env=ENV,
        capture_output=True, text=True, timeout=900)
    assert out.returncode != 0 and out.stdout.strip() == "", out.stdout
    assert "not tpu" in out.stderr, out.stderr[-3000:]
    lines = [ln for ln in out.stderr.splitlines() if "rehearsal:" in ln]
    assert lines, out.stderr[-3000:]
    line = lines[-1]
    # (correct: among the rest, the wire's tokens are the host's, with 1 or
    # 2 a step, and two greedy probes through the drafting agree)
    assert "correct=True" in line and "failed=0" in line, line
    # every reader that needs no device trace found something to read
    for name in ("gap_tail_s", "tpot_p50_ms", "setup_s", "mtp_accept_share",
                 "xm_cache_hbm_share", "xm_held_pair_share",
                 "moe_expert_imbalance.xm", "wire_out_tok_s",
                 "decode_step_ms", "sched_occupancy", "kv_fill",
                 "wire_ttft_p50_s", "wire_gap_p99_s", "admit_share"):
        assert f"'{name}'" in line, line
    # ... and the trace readers found no device plane (nor the CPU a
    # memory limit), and said nothing
    for name in ("xm_decode_hbm_share", "xm_prefill_mxu_share",
                 "mtp_draft_share", "hbm_used"):
        assert f"'{name}'" not in line, line
