"""Metrics of the Gated DeltaNet / gated-attention path (qwen3_next family):
the device trace against the counts of `lib/gdn_bytes.py`. A reader that
finds nothing to read (no trace, a configuration of another family, a capture
without a whole run of the decode program) returns None and the metric is
left out of the line. The program's `startup.ssm` block and `engine.ssm`
counters are read by `readers/hybrid.py`'s functions (`state_hbm_share`,
`prefill_tok_s`, `installs_per_s`), which ask nothing of the family.

The decode step's time comes from WHOLE runs of the decode program only,
counted from the capture itself (`python -m readers.gdn <capture> <program>`,
a process of its own pinned to the CPU, as `readers/ssm.py` counts its
kernel's events): a 3 s capture cuts the run in progress at either end,
`lib/xplane.py` counts both pieces as runs, and a step timed from them reads
up to a fifth short (PERF.md §7, next `benchmark` issue, 4). A run is cut
when it is the first or the last module event of its device's line, or lasts
under `WHOLE_SHARE` of the median run.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

from lib import gdn_bytes, window
from lib.peaks import peaks_for
from lib.xplane import DEVICE_PLANE, MODULES_LINE, find_xplane

WHOLE_SHARE = 0.8


def _is_gdn(ctx) -> bool:
    return ctx.cell.config.get("model_type") == "qwen3_next"


def whole_runs(data, program: str) -> dict:
    """{"runs", "seconds", "cut"} of the module events whose name holds
    `program`, per chip, the runs a capture's edge cut left out (and
    counted under "cut"). `data` is a jax.profiler.ProfileData or anything
    shaped like one."""
    kept: list[float] = []
    cut, planes = 0, 0
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            events = sorted(line.events, key=lambda ev: ev.start_ns)
            mine = [(i, ev.duration_ns * 1e-9)
                    for i, ev in enumerate(events) if program in ev.name]
            if not mine:
                continue
            planes += 1
            inner = [(i, s) for i, s in mine
                     if 0 < i < len(events) - 1]
            floor = (WHOLE_SHARE * statistics.median(s for _, s in inner)
                     if inner else 0.0)
            whole = [s for _, s in inner if s >= floor]
            cut += len(mine) - len(whole)
            kept += whole
    chips = max(1, planes)
    return {"runs": len(kept) / chips, "seconds": sum(kept) / chips,
            "cut": cut / chips}


def _counted(ctx, program: str) -> dict | None:
    """`whole_runs` on the run's capture, once per run. A count that fails
    is logged and reads as nothing."""
    from lib.harness import BENCH_DIR, log

    cache = ctx.__dict__.setdefault("_gdn_runs", {})
    if program not in cache:
        cache[program] = None
        if ctx.trace is not None and ctx.phase.trace_path:
            env = {**os.environ, "JAX_PLATFORMS": "cpu",
                   "TPU_LOG_DIR": "disabled"}
            env.pop("BENCH_RUN", None)
            try:
                out = subprocess.run(
                    [sys.executable, "-m", "readers.gdn",
                     find_xplane(ctx.phase.trace_path), program],
                    cwd=BENCH_DIR, env=env, capture_output=True, text=True,
                    timeout=300)
                if out.returncode == 0:
                    line = out.stdout.strip().splitlines()[-1]
                    log(f"whole runs of {program!r} in the capture: {line}")
                    cache[program] = json.loads(line)
                else:
                    log(f"run count failed: {out.stderr[-2000:]}")
            except (OSError, subprocess.TimeoutExpired, ValueError,
                    IndexError) as exc:
                log(f"run count failed: {exc!r}")
    return cache[program]


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must move (`gdn_bytes.decode_step_bytes`:
    weights, the experts the step hits, the state of every slot read once
    and written once, live K/V) ÷ the device time of one step — the mean
    WHOLE run of the decode program ÷ `decode_block` — ÷ the chip's
    published HBM bandwidth: a share of the whole decode step."""
    name = ctx.cell.config.get("decode_program")
    if not _is_gdn(ctx) or not ctx.trace or not name:
        return None
    counted = _counted(ctx, name)
    if not counted or not counted["runs"] or counted["seconds"] <= 0:
        return None
    ph = ctx.phase
    slots, tokens = window.mean_live(ph.records, ph.w0, ph.w1)
    nbytes = gdn_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu,
                                         tokens, slots)
    step_s = (counted["seconds"] / counted["runs"]
              / ctx.cell.tpu["decode_block"])
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def prefill_mxu_share(ctx) -> float | None:
    """Active FLOPs prefilled per second ÷ device seconds of the prefill
    programs per second ÷ the chip's published bf16 peak — the rates as
    `readers/hybrid.py prefill_mxu_share` takes them: the numerator over
    the window (prompts whose first token arrived in it, with the
    template's tokens, through `gdn_bytes.prefill_flops`), the denominator
    over the capture inside it (programs whose name holds the
    configuration's `prefill_program`). Padding to a bucket is time spent
    and no work counted."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not _is_gdn(ctx) or not t or not name or not t.get("window_s"):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        gdn_bytes.prefill_flops(ctx.cell.config,
                                r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak


def main(argv: list[str]) -> int:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(argv[1]))
    print(json.dumps(whole_runs(data, argv[2])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
