"""Metrics of the window + full attention path (PowerInfer `smallthinker`):
the program's `stats.engine.swa` counters (decode forwards, the rows a full
layer and a window layer read in them, ring wraps, tokens prefilled), and
the device trace against the counts of `lib/swa_bytes.py`. A reader that
finds nothing to read (no trace, a configuration without
`sliding_window_layout`, a program without the counters or the named kernel
calls — the parent of the PR that brought them) returns None and the metric
is left out of the line.

The decode step's time comes from WHOLE runs of the decode program
(`readers/gdn.py whole_runs`' way of counting, through `_counted` there);
the kernels' events are counted from the capture itself by
`readers/dsa.py`'s counter (a process of its own pinned to the CPU).
"""

from __future__ import annotations

from lib import swa_bytes
from lib.peaks import peaks_for

from readers.stats import _dig


def _is_swa(ctx) -> bool:
    return "sliding_window_layout" in ctx.cell.config


def _grew(ctx) -> dict | None:
    """Growth of every `stats.engine.swa` counter over the window: from the
    stats read at its start to the last sample taken inside it (the stats
    read after the drain also hold the drain, where the slots empty)."""
    ph = ctx.phase
    a = _dig(ph.stats_start, "engine.swa") or {}
    inside = [s for t, s in getattr(ph, "samples", ()) if t <= ph.w1]
    b = _dig(inside[-1] if inside else ph.stats_end, "engine.swa")
    if not _is_swa(ctx) or not b:
        return None
    return {k: v - a.get(k, 0) for k, v in b.items()}


def _live(ctx) -> tuple[float, float, float] | None:
    """(full rows, ring rows, live slots) of the mean decode step in the
    window: the rows from the program's counters (what each kind's kernel
    call was told to read), the slots from the client records."""
    from lib import window

    g = _grew(ctx)
    if not g or g.get("decode_steps", 0) <= 0:
        return None
    ph = ctx.phase
    slots, _ = window.mean_live(ph.records, ph.w0, ph.w1)
    steps = g["decode_steps"]
    return g["full_rows"] / steps, g["ring_rows"] / steps, slots


def _step_s(ctx) -> float | None:
    """Device seconds of one decode step: the mean WHOLE run of the decode
    program ÷ `decode_block`."""
    from readers.gdn import _counted

    name = ctx.cell.config.get("decode_program")
    if not _is_swa(ctx) or not ctx.trace or not name:
        return None
    counted = _counted(ctx, name)
    if not counted or not counted["runs"] or counted["seconds"] <= 0:
        return None
    return (counted["seconds"] / counted["runs"]
            / ctx.cell.tpu["decode_block"])


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must move (`swa_bytes.decode_step_bytes`:
    every weight outside the experts once, the experts the live slots'
    pairs hit, each live full row and each ring row once a layer of its
    kind) ÷ the device time of one step ÷ the chip's published HBM
    bandwidth."""
    step_s, live = _step_s(ctx), _live(ctx)
    if step_s is None or live is None:
        return None
    nbytes = swa_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu,
                                         *live)
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def cache_hbm_share(ctx, part: str = "all") -> float | None:
    """The cache rows' share of the bytes a decode step must move — `part`
    "all", or the "ring" rows' or the "full" rows' alone: a count against a
    count, from the program's counters and shapes alone."""
    live = _live(ctx)
    if live is None:
        return None
    cfg, tpu = ctx.cell.config, ctx.cell.tpu
    full, ring = swa_bytes.cache_step_bytes(cfg, tpu, live[0], live[1])
    rows = {"all": full + ring, "ring": ring, "full": full}[part]
    return 100.0 * rows / swa_bytes.decode_step_bytes(cfg, tpu, *live)


def prefill_mxu_share(ctx) -> float | None:
    """ACTIVE FLOPs prefilled per second (`swa_bytes.prefill_flops` of the
    prompts whose first token arrived in the window, with the template's
    tokens: window-bounded pairs on the window layers, causal on the full
    ones, k experts a token) ÷ device seconds of the prefill programs per
    second (over the capture inside it) ÷ the chip's published bf16 peak, as
    `readers/mla.py prefill_mxu_share` is built. Padding to a bucket and a
    kernel's whole diagonal and edge tiles are time spent and no work
    counted."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not _is_swa(ctx) or not t or not name or not t.get("window_s"):
        return None
    if not _dig(ctx.phase.stats_end, "engine.swa"):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        swa_bytes.prefill_flops(ctx.cell.config,
                                r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak


def decode_attn_roofline(ctx, op: str) -> float | None:
    """The decode-attention kernel against the HBM: what its calls must
    move — `swa_bytes.kernel_step_bytes` of one step at the mean rows of
    the window's decode steps, a layer's share of it for each of the
    capture's events of the op named `op` (one a layer and step, over a
    full leaf or a ring) — ÷ the events' device seconds ÷ the chip's
    published HBM bandwidth. A row of int8 K and V serves 7 query heads a
    KV head: ~14 FLOPs a byte, far under the chip's ridge — bound by the
    bytes."""
    from readers.dsa import _counted

    live = _live(ctx)
    if live is None or not ctx.trace:
        return None
    counted = _counted(ctx, op)
    if not counted or not counted["events"] or counted["seconds"] <= 0:
        return None
    cfg, tpu = ctx.cell.config, ctx.cell.tpu
    per_call = (swa_bytes.kernel_step_bytes(cfg, tpu, live[0], live[1],
                                            tpu["max_batch_size"])
                / cfg["num_hidden_layers"])
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * counted["events"] * per_call / counted["seconds"] / peak


def prefill_roofline(ctx, op: str) -> float | None:
    """The prefill attention kernel against the MXU: the capture's events of
    the op named `op` (one a layer and dispatch: a layer's share of a
    dispatch's `swa_bytes.attention_flops` each), a dispatch priced at the
    LEAST prompt its bucket takes in this cell — one token over the next
    smaller bucket, or the traffic's shortest prompt with the template
    where that is longer: a lower count, so the share cannot pass 100% —
    over 28 query heads and only the pairs a layer's mask leaves, ÷ the
    events' device seconds ÷ the chip's published bf16 peak. Bound by
    FLOPs: a KV head's K and V are read once for its seven query heads."""
    from readers.dsa import _counted

    if not _is_swa(ctx) or not ctx.trace:
        return None
    counted = _counted(ctx, op)
    if (not counted or not counted["events"] or counted["seconds"] <= 0
            or not counted["prefills"]):
        return None
    cfg = ctx.cell.config
    buckets = sorted(ctx.cell.tpu["prefill_buckets"])
    shortest = (int(ctx.cell.traffic["prompt_tokens"]["min"])
                + int(cfg.get("template_tokens", 0)))

    def least(bucket: int) -> int:
        lo = max([b for b in buckets if b < bucket], default=0) + 1
        return max(lo, shortest) if shortest <= bucket else lo

    per_call = sum(
        rows * swa_bytes.attention_flops(cfg, least(bucket))
        for bucket, rows in counted["prefills"]) / len(
        counted["prefills"]) / cfg["num_hidden_layers"]
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    return 100.0 * counted["events"] * per_call / counted["seconds"] / peak
