"""The recurrent state's decode kernel against its roofline: the device
trace's events of the op of a given name against the state bytes of
`lib/hybrid_bytes.py`. A reader that finds nothing to read (no trace, a
configuration without `layer_types`, a capture that holds no op of that name
— the parent of the PR that brought the kernel) returns None and the metric
is left out of the line.

The events are COUNTED from the capture itself (`python -m readers.ssm
<capture> <op>`, a process of its own pinned to the CPU, as
`readers/spans.py` runs `lib/spans.py`), not taken as the reduced trace's
`decode.runs` x `decode_block` x layers: a 3 s capture cuts the decode run in
progress at either end, `lib/xplane.py` counts both pieces as runs (6 "runs"
over 5.0 runs' worth of device seconds: PERF.md, PR 34), and bytes counted
from runs would read up to a half over what moved. One event of the op IS one
layer's pass over every slot's state.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from lib import hybrid_bytes
from lib.peaks import peaks_for
from lib.xplane import DEVICE_PLANE, OPS_LINE, find_xplane, parse_op


def count_op(data, op: str) -> dict:
    """{"events", "seconds"} of the device ops named `op` (`op`, `op.1`,
    ...), per chip: summed over the device planes that ran any, divided by
    their number. `data` is a jax.profiler.ProfileData or anything shaped
    like one."""
    events, seconds, planes = 0, 0.0, 0
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        found = 0
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if parse_op(ev.name)[0].split(".")[0] == op:
                    found += 1
                    seconds += ev.duration_ns * 1e-9
        events += found
        planes += bool(found)
    chips = max(1, planes)
    return {"events": events / chips, "seconds": seconds / chips}


def _counted(ctx, op: str) -> dict | None:
    """`count_op` on the run's capture, once per run and op. A count that
    fails is logged and reads as nothing."""
    from lib.harness import BENCH_DIR, log

    cache = ctx.__dict__.setdefault("_ssm_ops", {})
    if op not in cache:
        cache[op] = None
        if ctx.trace is not None and ctx.phase.trace_path:
            env = {**os.environ, "JAX_PLATFORMS": "cpu",
                   "TPU_LOG_DIR": "disabled"}
            env.pop("BENCH_RUN", None)
            try:
                out = subprocess.run(
                    [sys.executable, "-m", "readers.ssm",
                     find_xplane(ctx.phase.trace_path), op],
                    cwd=BENCH_DIR, env=env, capture_output=True, text=True,
                    timeout=300)
                if out.returncode == 0:
                    line = out.stdout.strip().splitlines()[-1]
                    log(f"events of {op!r} in the capture: {line}")
                    cache[op] = json.loads(line)
                else:
                    log(f"op count failed: {out.stderr[-2000:]}")
            except (OSError, subprocess.TimeoutExpired, ValueError,
                    IndexError) as exc:
                log(f"op count failed: {exc!r}")
    return cache[op]


def step_roofline(ctx, op: str) -> float | None:
    """Bytes of recurrent state one pass of a layer must move — the state
    of EVERY slot in that layer, read once and written once
    (`hybrid_bytes.state_bytes_per_slot` ÷ the mamba layers; the decay,
    dt x, B and C the kernel also reads are not counted, so it cannot read
    over 100%) ÷ the mean device seconds of the capture's events of the op
    named `op` (the kernel: one event a layer a step) ÷ the chip's
    published HBM bandwidth. Bound by bytes: the kernel's operations (7 a
    state element) are under 2% of the chip's float32 rate at that time."""
    kinds = ctx.cell.config.get("layer_types")
    if not kinds or "mamba" not in kinds:
        return None
    counted = _counted(ctx, op)
    if not counted or not counted["events"] or counted["seconds"] <= 0:
        return None
    per_slot = hybrid_bytes.state_bytes_per_slot(ctx.cell.config,
                                                 ctx.cell.tpu)["ssm"]
    layer_bytes = (2 * per_slot // list(kinds).count("mamba")
                   * int(ctx.cell.tpu["max_batch_size"]))
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return (100.0 * layer_bytes * counted["events"] / counted["seconds"]
            / peak)


def main(argv: list[str]) -> int:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(argv[1]))
    print(json.dumps(count_op(data, argv[2])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
