"""Metrics of the learned-sparse-attention path (KeyeVL2 family): the
program's `stats.engine.dsa` counters and `startup.attention.sparse` block,
and the device trace against the counts of `lib/dsa_bytes.py`. A reader that
finds nothing to read (no trace, a configuration without `sa_config`, a
program without the counter or the kernel — the parent of the PR that
brought them) returns None and the metric is left out of the line.

The decode step's time comes from WHOLE runs of the decode program
(`readers/gdn.py whole_runs`' way of counting); the kernel's events and the
prefill dispatches that ran them are counted from the capture itself
(`python -m readers.dsa <capture> <op>`, a process of its own pinned to the
CPU, as `readers/ssm.py` counts its kernel's events).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from lib import dsa_bytes
from lib.peaks import peaks_for
from lib.xplane import DEVICE_PLANE, OPS_LINE, find_xplane, parse_op

from readers.stats import _dig

PREFILL_SPAN = "sym.engine.prefill"


def _is_dsa(ctx) -> bool:
    return "sa_config" in ctx.cell.config


def select_ratio(ctx) -> float | None:
    """Growth of `selected` ÷ growth of `candidates` over the window: the
    share of the positions a query could attend to that it did."""
    a = _dig(ctx.phase.stats_start, "engine.dsa") or {}
    b = _dig(ctx.phase.stats_end, "engine.dsa")
    if not b:
        return None
    grew = b["candidates"] - a.get("candidates", 0)
    if grew <= 0:
        return None
    return 100.0 * (b["selected"] - a.get("selected", 0)) / grew


def index_hbm_share(ctx) -> float | None:
    """The index cache's bytes (`startup.attention.sparse`) ÷ the fullest
    chip's memory limit."""
    sparse = _dig(ctx.phase.stats_end, "engine.startup.attention.sparse")
    hbm = _dig(ctx.phase.stats_end, "engine.startup.device.hbm") or []
    limit = max((h.get("bytes_limit") or 0 for h in hbm), default=0)
    if not sparse or not limit:
        return None
    return 100.0 * sparse["index_cache_bytes"] / limit


def live_lengths(ctx, step_s: float = 0.5) -> list[list[int]]:
    """Per sample of the window, the positions each live stream holds: its
    prompt with the template's tokens plus what it has been sent so far."""
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    n = max(1, int((ph.w1 - ph.w0) / step_s))
    out = []
    for i in range(n):
        t = ph.w0 + (i + 0.5) * (ph.w1 - ph.w0) / n
        lengths = []
        for r in ph.records:
            if (not r["stamps"] or r.get("t_done") is None
                    or not r["stamps"][0][0] <= t < r["t_done"]):
                continue
            chars = sum(c for _, c in r["stamps"]) or 1
            seen = sum(c for ts, c in r["stamps"] if ts <= t)
            lengths.append(int(r["prompt_tokens"] + template
                               + (r.get("tokens") or 0) * seen / chars))
        out.append(lengths)
    return out


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must move in the GATHER form
    (`dsa_bytes.decode_step_bytes`: weights, the experts the step hits,
    every live position's index keys, `min(length, topk)` K/V rows a slot
    and layer; the mean over the window's samples) ÷ the device time of
    one step — the mean WHOLE run of the decode program ÷ `decode_block` —
    ÷ the chip's published HBM bandwidth. It counts the gather form's
    bytes whatever runs: a masked decode reads more and so reads lower."""
    from readers.gdn import _counted

    name = ctx.cell.config.get("decode_program")
    if not _is_dsa(ctx) or not ctx.trace or not name:
        return None
    counted = _counted(ctx, name)
    if not counted or not counted["runs"] or counted["seconds"] <= 0:
        return None
    samples = live_lengths(ctx)
    nbytes = sum(dsa_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu,
                                             s) for s in samples
                 ) / len(samples)
    step_s = (counted["seconds"] / counted["runs"]
              / ctx.cell.tpu["decode_block"])
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def prefill_mxu_share(ctx) -> float | None:
    """ACTIVE FLOPs prefilled per second (`dsa_bytes.prefill_flops` of the
    prompts whose first token arrived in the window, with the template's
    tokens) ÷ device seconds of the prefill programs per second (over the
    capture inside it) ÷ the chip's published bf16 peak, as
    `readers/gdn.py prefill_mxu_share` is built. Padding to a bucket, the
    masked pairs a kernel computes and the threshold's passes are time
    spent and no work counted."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not _is_dsa(ctx) or not t or not name or not t.get("window_s"):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        dsa_bytes.prefill_flops(ctx.cell.config,
                                r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak


def _span_attrs(ev) -> dict | None:
    """The keywords of a `sym.engine.prefill` event: its stats (how
    `ProfileData` hands out a TraceAnnotation's keywords), or the
    `name#k=v,...#` form a raw trace names it by; None for another event."""
    name, _, packed = ev.name.partition("#")
    if name != PREFILL_SPAN:
        return None
    if packed:
        return dict(kv.split("=", 1) for kv in packed.strip("#").split(","))
    return {str(k): v for k, v in getattr(ev, "stats", None) or ()}


def count_kernel(data, op: str) -> dict:
    """{"events", "seconds", "prefills"}: the device ops named `op` (`op`,
    `op.1`, ...) of the first device plane that ran any, and the
    (bucket, rows) of every `sym.engine.prefill` span in the capture.
    `data` is a jax.profiler.ProfileData or anything shaped like one."""
    events, seconds = 0, 0.0
    prefills: list[list[int]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            if events:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if parse_op(ev.name)[0].split(".")[0] == op:
                        events += 1
                        seconds += ev.duration_ns * 1e-9
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    attrs = _span_attrs(ev)
                    if attrs and "bucket" in attrs:
                        prefills.append([int(attrs["bucket"]),
                                         int(attrs.get("n", 1))])
    return {"events": events, "seconds": seconds, "prefills": prefills}


def _counted(ctx, op: str) -> dict | None:
    """`count_kernel` on the run's capture, once per run and op. A count
    that fails is logged and reads as nothing."""
    from lib.harness import BENCH_DIR, log

    cache = ctx.__dict__.setdefault("_dsa_ops", {})
    if op not in cache:
        cache[op] = None
        if ctx.trace is not None and ctx.phase.trace_path:
            env = {**os.environ, "JAX_PLATFORMS": "cpu",
                   "TPU_LOG_DIR": "disabled"}
            env.pop("BENCH_RUN", None)
            try:
                out = subprocess.run(
                    [sys.executable, "-m", "readers.dsa",
                     find_xplane(ctx.phase.trace_path), op],
                    cwd=BENCH_DIR, env=env, capture_output=True, text=True,
                    timeout=300)
                if out.returncode == 0:
                    line = out.stdout.strip().splitlines()[-1]
                    log(f"events of {op!r} in the capture: {line}")
                    cache[op] = json.loads(line)
                else:
                    log(f"kernel count failed: {out.stderr[-2000:]}")
            except (OSError, subprocess.TimeoutExpired, ValueError,
                    IndexError) as exc:
                log(f"kernel count failed: {exc!r}")
    return cache[op]


def flash_roofline(ctx, op: str) -> float | None:
    """The prefill attention kernel against the MXU: the FLOPs its calls
    computed (`dsa_bytes.flash_flops` of every prefill dispatch whose span
    is in the capture — QK^T and PV over the KV blocks at or under the
    diagonal, one call a layer, whatever was selected) ÷ the device seconds
    of the capture's events of the op named `op` ÷ the chip's published
    bf16 peak. Bound by FLOPs: the K/V and mask bytes a call reads move in
    under a tenth of that time. An event is priced at the MEAN work of the
    capture's dispatches, so a dispatch the capture's edge cut moves the
    share only by how far its bucket lies from that mean."""
    if not _is_dsa(ctx):
        return None
    counted = _counted(ctx, op)
    if (not counted or not counted["events"] or counted["seconds"] <= 0
            or not counted["prefills"]):
        return None
    # one event is one layer's call of one dispatch: the events that are
    # there, each at the mean work of the dispatches whose span is there
    flops = counted["events"] * sum(
        dsa_bytes.flash_flops(ctx.cell.config, b, n)
        for b, n in counted["prefills"]) / len(counted["prefills"])
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    return 100.0 * flops / counted["seconds"] / peak


def main(argv: list[str]) -> int:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(argv[1]))
    print(json.dumps(count_kernel(data, argv[2])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
