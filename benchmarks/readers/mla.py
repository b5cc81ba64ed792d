"""Metrics of the latent-attention path (HF `deepseek_v3`): the program's
`stats.engine.mla` counters (decode forwards, the live rows they read,
tokens prefilled expanded), and the device trace against the counts of
`lib/mla_bytes.py`. A reader that finds nothing to read (no trace, a
configuration without `kv_lora_rank`, a program without the counters or the
kernel — the parent of the PR that brought them) returns None and the
metric is left out of the line.

The decode step's time comes from WHOLE runs of the decode program
(`readers/gdn.py whole_runs`' way of counting, through `_counted` there);
the kernel's events are counted from the capture itself by
`readers/dsa.py`'s counter (a process of its own pinned to the CPU).
"""

from __future__ import annotations

from lib import mla_bytes
from lib.peaks import peaks_for

from readers.stats import _dig


def _is_mla(ctx) -> bool:
    return "kv_lora_rank" in ctx.cell.config


def _grew(ctx) -> dict | None:
    """Growth of every `stats.engine.mla` counter over the window: from the
    stats read at its start to the last sample taken inside it (the stats
    read after the drain also hold the drain, where the slots empty)."""
    ph = ctx.phase
    a = _dig(ph.stats_start, "engine.mla") or {}
    inside = [s for t, s in getattr(ph, "samples", ()) if t <= ph.w1]
    b = _dig(inside[-1] if inside else ph.stats_end, "engine.mla")
    if not _is_mla(ctx) or not b:
        return None
    return {k: v - a.get(k, 0) for k, v in b.items()}


def _live(ctx) -> tuple[float, float] | None:
    """(live rows, live slots) of the mean decode step in the window: the
    rows from the program's counters (what the kernel was told to read),
    the slots from the client records."""
    from lib import window

    g = _grew(ctx)
    if not g or g.get("decode_steps", 0) <= 0:
        return None
    ph = ctx.phase
    slots, _ = window.mean_live(ph.records, ph.w0, ph.w1)
    return g["live_positions"] / g["decode_steps"], slots


def _step_s(ctx) -> float | None:
    """Device seconds of one decode step: the mean WHOLE run of the decode
    program ÷ `decode_block`."""
    from readers.gdn import _counted

    name = ctx.cell.config.get("decode_program")
    if not _is_mla(ctx) or not ctx.trace or not name:
        return None
    counted = _counted(ctx, name)
    if not counted or not counted["runs"] or counted["seconds"] <= 0:
        return None
    return (counted["seconds"] / counted["runs"]
            / ctx.cell.tpu["decode_block"])


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must move (`mla_bytes.decode_step_bytes`:
    every weight outside the routed experts once, the experts the live
    slots' pairs hit, each live latent row once a layer) ÷ the device time
    of one step ÷ the chip's published HBM bandwidth."""
    step_s, live = _step_s(ctx), _live(ctx)
    if step_s is None or live is None:
        return None
    nbytes = mla_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu,
                                         *live)
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def latent_hbm_share(ctx) -> float | None:
    """The latent rows' share of the bytes a decode step must move: a count
    against a count, from the program's counters and shapes alone."""
    live = _live(ctx)
    if live is None:
        return None
    cfg, tpu = ctx.cell.config, ctx.cell.tpu
    return (100.0 * mla_bytes.latent_step_bytes(cfg, tpu, live[0])
            / mla_bytes.decode_step_bytes(cfg, tpu, *live))


def prefill_mxu_share(ctx) -> float | None:
    """ACTIVE FLOPs prefilled per second in the expanded form
    (`mla_bytes.prefill_flops` of the prompts whose first token arrived in
    the window, with the template's tokens) ÷ device seconds of the prefill
    programs per second (over the capture inside it) ÷ the chip's published
    bf16 peak, as `readers/dsa.py prefill_mxu_share` is built. Padding to a
    bucket or of a width, and a kernel's whole diagonal blocks, are time
    spent and no work counted."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not _is_mla(ctx) or not t or not name or not t.get("window_s"):
        return None
    if not _dig(ctx.phase.stats_end, "engine.mla"):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        mla_bytes.prefill_flops(ctx.cell.config,
                                r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak


def prefill_roofline(ctx, op: str) -> float | None:
    """The prefill attention kernel against the MXU: the capture's events of
    the op named `op` (one a layer and dispatch), each priced at the mean
    over the capture's prefill dispatches of `mla_bytes.flash_call_flops`
    at the LEAST prompt its bucket takes in this cell — one token over the
    next smaller bucket, or the traffic's shortest prompt with the template
    where that is longer: a lower count, so the share cannot pass 100% — ÷
    the events' device seconds ÷ the chip's published bf16 peak. Bound by
    FLOPs: a head's K and V are read once a call."""
    from readers.dsa import _counted

    if not _is_mla(ctx) or not ctx.trace:
        return None
    counted = _counted(ctx, op)
    if (not counted or not counted["events"] or counted["seconds"] <= 0
            or not counted["prefills"]):
        return None
    buckets = sorted(ctx.cell.tpu["prefill_buckets"])
    shortest = (int(ctx.cell.traffic["prompt_tokens"]["min"])
                + int(ctx.cell.config.get("template_tokens", 0)))

    def least(bucket: int) -> int:
        lo = max([b for b in buckets if b < bucket], default=0) + 1
        return max(lo, shortest) if shortest <= bucket else lo

    flops = counted["events"] * sum(
        rows * mla_bytes.flash_call_flops(ctx.cell.config, least(bucket))
        for bucket, rows in counted["prefills"]) / len(counted["prefills"])
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    return 100.0 * flops / counted["seconds"] / peak


def decode_roofline(ctx, op: str) -> float | None:
    """The decode kernel against its roofline: what its calls must do —
    `mla_bytes.kernel_bytes` and `kernel_flops` of one call at the mean live
    rows of the window's decode steps, times the capture's events of the op
    named `op` (one a layer and step) — each ÷ the chip's published peak,
    the LARGER of the two times (the roofline's) ÷ the events' device
    seconds. One shared row serves 32 heads, so a call is 60 FLOPs a byte:
    under the chip's ridge (~240), bound by the bytes."""
    from readers.dsa import _counted

    live = _live(ctx)
    if live is None or not ctx.trace:
        return None
    counted = _counted(ctx, op)
    if not counted or not counted["events"] or counted["seconds"] <= 0:
        return None
    cfg, tpu = ctx.cell.config, ctx.cell.tpu
    peaks = peaks_for(ctx.device["kind"])
    least_s = max(
        mla_bytes.kernel_bytes(cfg, tpu, live[0], tpu["max_batch_size"])
        / peaks["hbm_bytes_per_s"],
        mla_bytes.kernel_flops(cfg, live[0]) / peaks["bf16_flops"])
    return 100.0 * counted["events"] * least_s / counted["seconds"]
