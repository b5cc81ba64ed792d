"""Metrics read from the program's own spans in the device trace
(`lib/spans.py`'s object) and from the counters that go with them. Without a
trace, or from a program that writes no `sym.*` span, they return None."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from lib.harness import BENCH_DIR, log
from lib.xplane import find_xplane
from readers.stats import _dig


def _reduced(ctx) -> dict | None:
    """`lib/spans.py` on the run's capture, once per run, in a process of
    its own pinned to the CPU (it reads a file; it must never reach for the
    chip). A reduction that fails is logged and reads as nothing."""
    if not hasattr(ctx, "_spans"):
        ctx._spans = None
        if ctx.trace is not None and ctx.phase.trace_path:
            env = {**os.environ, "JAX_PLATFORMS": "cpu",
                   "TPU_LOG_DIR": "disabled"}
            env.pop("BENCH_RUN", None)
            try:
                pb = find_xplane(ctx.phase.trace_path)
                log(f"capture file: {os.path.getsize(pb)} bytes")
                out = subprocess.run(
                    [sys.executable, "-m", "lib.spans", pb],
                    cwd=BENCH_DIR, env=env,
                    capture_output=True, text=True, timeout=300)
                if out.returncode == 0:
                    line = out.stdout.strip().splitlines()[-1]
                    log(f"program spans in the capture: {line}")
                    ctx._spans = json.loads(line)
                else:
                    log(f"span reduction failed: {out.stderr[-2000:]}")
            except (OSError, subprocess.TimeoutExpired, ValueError,
                    IndexError) as exc:
                log(f"span reduction failed: {exc!r}")
    return ctx._spans


def idle_in(ctx, phase: str) -> float | None:
    """Share of the first device plane's idle time inside the capture that
    falls inside one group of the scheduler's loop phases."""
    r = _reduced(ctx)
    if not r or not r["idle_in"] or not r["idle_s"]:
        return None
    return 100.0 * r["idle_in"][phase] / r["idle_s"]


def admit_busy_share(ctx) -> float | None:
    """Share of the admission phase's wall during which the device was
    busy."""
    r = _reduced(ctx)
    if not r or not r["admit_s"]:
        return None
    return 100.0 * r["admit_busy_s"] / r["admit_s"]


def counter_delta(ctx, path: str) -> float | None:
    """Growth of a cumulative counter between the window's first and last
    stats sample."""
    a = _dig(ctx.phase.samples[0][1], path)
    b = _dig(ctx.phase.samples[-1][1], path)
    return None if a is None or b is None else b - a
