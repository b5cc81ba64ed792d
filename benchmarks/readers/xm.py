"""Metrics of the exaone_moe path (K-EXAONE: window + full layers, a held
share of the experts, the multi-token-prediction module drafting on the
device): the program's `stats.engine.mtp` counters (drafts scored and
accepted, tokens yielded, (slot, step) pairs), `stats.engine.swa` (the rows a
step read) and `stats.engine.moe` (the pairs that fell on held experts), and
the device trace against the counts of `lib/xm_bytes.py`. A reader that finds
nothing to read (no trace, a configuration that is no `exaone_moe`, a program
without the counters or the module's scope — the parent of the PR that
brought them) returns None and the metric is left out of the line.

The decode step's time comes from WHOLE runs of the decode program
(`readers/gdn.py whole_runs`, through `_counted` there); the module's ops are
counted from the capture's raw protobuf by this file run as a process of its
own pinned to the CPU (`python -m readers.xm <capture> <program> <scope>`).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from lib import xm_bytes
from lib.peaks import peaks_for
from lib.xplane import DEVICE_PLANE, MODULES_LINE, OPS_LINE, find_xplane

from readers.stats import _dig


def _is_xm(ctx) -> bool:
    return ctx.cell.config.get("model_type") == "exaone_moe"


def _grew(ctx, block: str) -> dict | None:
    """Growth of every `stats.engine.<block>` counter over the window: from
    the stats read at its start to the last sample taken inside it (the
    stats read after the drain also hold the drain, where the slots
    empty). Lists (the per-expert pairs) are left out."""
    ph = ctx.phase
    a = _dig(ph.stats_start, f"engine.{block}") or {}
    inside = [s for t, s in getattr(ph, "samples", ()) if t <= ph.w1]
    b = _dig(inside[-1] if inside else ph.stats_end, f"engine.{block}")
    if not _is_xm(ctx) or not b:
        return None
    return {k: v - a.get(k, 0) for k, v in b.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def accept_share(ctx) -> float | None:
    """Drafts accepted ÷ drafts scored over the window
    (`stats.engine.mtp`). With seeded random weights it reads about zero:
    the module's argmax is rarely in the trunk's keep set."""
    g = _grew(ctx, "mtp")
    if not g or g.get("drafted", 0) <= 0:
        return None
    return 100.0 * g["accepted"] / g["drafted"]


def _live(ctx) -> tuple[float, float, float] | None:
    """(full rows, ring rows, live slots) of the mean decode step in the
    window: the rows from `stats.engine.swa` (a slot's length with the
    step's last position, and that capped at the window), the slots from
    `stats.engine.mtp`'s (slot, step) pairs."""
    swa, mtp = _grew(ctx, "swa"), _grew(ctx, "mtp")
    if not swa or not mtp or swa.get("decode_steps", 0) <= 0:
        return None
    steps = swa["decode_steps"]
    return (swa["full_rows"] / steps, swa["ring_rows"] / steps,
            mtp["steps"] / steps)


def _step_s(ctx) -> float | None:
    """Device seconds of one decode step: the mean WHOLE run of the decode
    program ÷ `decode_block`."""
    from readers.gdn import _counted

    name = ctx.cell.config.get("decode_program")
    if not _is_xm(ctx) or not ctx.trace or not name:
        return None
    counted = _counted(ctx, name)
    if not counted or not counted["runs"] or counted["seconds"] <= 0:
        return None
    return (counted["seconds"] / counted["runs"]
            / ctx.cell.tpu["decode_block"])


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must move (`xm_bytes.decode_step_bytes`: the
    held trunk weights and the module's once, the head's slice once, each
    live full row and ring row once a layer of its kind) ÷ the device time
    of one step ÷ the chip's published HBM bandwidth."""
    step_s, live = _step_s(ctx), _live(ctx)
    if step_s is None or live is None:
        return None
    nbytes = xm_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu, *live)
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def cache_hbm_share(ctx) -> float | None:
    """The cache rows' share of the bytes a decode step must move: a count
    against a count, from the program's counters and shapes alone."""
    live = _live(ctx)
    if live is None:
        return None
    cfg, tpu = ctx.cell.config, ctx.cell.tpu
    rows = sum(xm_bytes.cache_step_bytes(cfg, tpu, live[0], live[1]))
    return 100.0 * rows / xm_bytes.decode_step_bytes(cfg, tpu, *live)


def held_pair_share(ctx) -> float | None:
    """(token, expert) pairs that fell on a HELD expert ÷ all the pairs the
    router made over the window (`stats.engine.moe`): held ÷ routed over
    under uniform routing, 12.5% here."""
    g = _grew(ctx, "moe")
    if not g or "held_pairs" not in g or g.get("pairs", 0) <= 0:
        return None
    return 100.0 * g["held_pairs"] / g["pairs"]


def prefill_mxu_share(ctx) -> float | None:
    """ACTIVE FLOPs prefilled per second (`xm_bytes.prefill_flops` of the
    prompts whose first token arrived in the window, with the template's
    tokens) ÷ device seconds of the prefill programs per second (over the
    capture inside it) ÷ the chip's published bf16 peak, as `readers/swa.py
    prefill_mxu_share` is built."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not _is_xm(ctx) or not t or not name or not t.get("window_s"):
        return None
    if not _dig(ctx.phase.stats_end, "engine.mtp"):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        xm_bytes.prefill_flops(ctx.cell.config,
                               r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak


def scope_seconds(space, program: str, scope: str) -> dict:
    """{"scope_s", "program_s"} of the first device plane that ran
    `program`: the device seconds of the ops whose `op_name` holds `scope`
    that lie INSIDE a run of the program, and of those runs. A
    `jax.named_scope` is part of an op's `op_name`, which the trace keeps
    as the `tf_op` stat of the op's EVENT METADATA — `ProfileData` hands
    out an event's own stats alone, so `space` is the capture's raw
    `XSpace` (or anything shaped like one: planes with `stat_metadata`,
    `event_metadata` and lines of events with `metadata_id`, `offset_ps`,
    `duration_ps`)."""
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}

        def spans(line):
            base = line.timestamp_ns * 1000
            return [(base + ev.offset_ps, base + ev.offset_ps
                     + ev.duration_ps, ev.metadata_id)
                    for ev in line.events]

        runs = sorted(
            (s, e) for s, e, m in spans(lines[MODULES_LINE])
            if program in plane.event_metadata[m].name
        ) if MODULES_LINE in lines else []
        if not runs or OPS_LINE not in lines:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        scoped = {
            m for m, md in plane.event_metadata.items()
            if any(names.get(st.metadata_id) == "tf_op" and scope in (
                st.str_value or names.get(st.ref_value, ""))
                for st in md.stats)}
        scope_ps, at = 0, 0
        for start, end, m in sorted(spans(lines[OPS_LINE])):
            while at < len(runs) and runs[at][1] <= start:
                at += 1
            if at == len(runs):
                break
            if start >= runs[at][0] and m in scoped:
                scope_ps += end - start
        return {"scope_s": scope_ps * 1e-12,
                "program_s": sum(e - s for s, e in runs) * 1e-12}
    return {"scope_s": 0.0, "program_s": 0.0}


def _scoped(ctx, program: str, scope: str) -> dict | None:
    """`scope_seconds` on the run's capture, once per run. A count that
    fails is logged and reads as nothing."""
    from lib.harness import BENCH_DIR, log

    cache = ctx.__dict__.setdefault("_xm_scopes", {})
    if scope not in cache:
        cache[scope] = None
        if ctx.trace is not None and ctx.phase.trace_path:
            env = {**os.environ, "JAX_PLATFORMS": "cpu",
                   "TPU_LOG_DIR": "disabled"}
            env.pop("BENCH_RUN", None)
            try:
                out = subprocess.run(
                    [sys.executable, "-m", "readers.xm",
                     find_xplane(ctx.phase.trace_path), program, scope],
                    cwd=BENCH_DIR, env=env, capture_output=True, text=True,
                    timeout=300)
                if out.returncode == 0:
                    line = out.stdout.strip().splitlines()[-1]
                    log(f"ops under {scope!r} in the capture: {line}")
                    cache[scope] = json.loads(line)
                else:
                    log(f"scope count failed: {out.stderr[-2000:]}")
            except (OSError, subprocess.TimeoutExpired, ValueError,
                    IndexError) as exc:
                log(f"scope count failed: {exc!r}")
    return cache[scope]


def draft_share(ctx) -> float | None:
    """The module's share of a decode block's device time: the device
    seconds of the ops under the module's scope (`mtp_scope`) inside the
    capture's runs of the decode program ÷ those runs' device seconds."""
    cfg = ctx.cell.config
    program, scope = cfg.get("decode_program"), cfg.get("mtp_scope")
    if not _is_xm(ctx) or not ctx.trace or not program or not scope:
        return None
    counted = _scoped(ctx, program, scope)
    if not counted or counted["program_s"] <= 0 or counted["scope_s"] <= 0:
        return None
    return 100.0 * counted["scope_s"] / counted["program_s"]


def main(argv: list[str]) -> int:
    # the capture's raw protobuf: the one place an op's `op_name` is kept
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(find_xplane(argv[1]), "rb") as fh:
        space.ParseFromString(fh.read())
    print(json.dumps(scope_seconds(space, argv[2], argv[3])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
