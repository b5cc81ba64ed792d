"""Metrics of generation by diffusion over blocks (SDAR family): the
program's `stats.engine.diffusion` counters and `startup.diffusion` block,
and the device trace against the counts of `lib/bd_bytes.py`. A reader that
finds nothing to read (no trace, a configuration of another family, a program
without the counters — the parent of the PR that brought them) returns None
and the metric is left out of the line.

A forward's time comes from WHOLE runs of the decode program
(`readers/gdn.py whole_runs`' way of counting) ÷ the forwards a dispatch is
made of (`startup.diffusion.forwards_per_dispatch`: denoise and commit
forwards alike, so it is the MEAN forward, and the bytes and FLOPs it is held
against carry the head's share of a block's forwards).
"""

from __future__ import annotations

from lib import bd_bytes
from lib.peaks import peaks_for

from readers.stats import _dig


def _is_bd(ctx) -> bool:
    return ctx.cell.config.get("model_type") == "sdar_moe"


def _startup(ctx) -> dict | None:
    return _dig(ctx.phase.stats_end, "engine.startup.diffusion")


def _grew(ctx) -> dict | None:
    """Growth of every `stats.engine.diffusion` counter over the window
    (`opening` the tokens of the opening blocks: sum of n x histogram[n]):
    from the stats read at its start to the last sample taken inside it —
    the stats read after the drain also hold the drain, where slots empty
    and every block of theirs is dropped."""
    ph = ctx.phase
    a = _dig(ph.stats_start, "engine.diffusion") or {}
    inside = [s for t, s in getattr(ph, "samples", ()) if t <= ph.w1]
    b = _dig(inside[-1] if inside else ph.stats_end, "engine.diffusion")
    if not _is_bd(ctx) or not b:
        return None
    out = {k: v - a.get(k, 0) for k, v in b.items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)}
    hist_a = a.get("opening_block_tokens") or {}
    out["opening"] = sum(int(n) * (c - hist_a.get(n, 0))
                         for n, c in b["opening_block_tokens"].items())
    return out


def tokens_per_forward(ctx) -> float | None:
    """Tokens of decode dispatches that reached a stream ÷ (forwards x live
    slots) over the window: block / (steps + 1) less what was dropped past a
    budget or a stop token (4 / 3 at a block of 4 and 2 steps)."""
    g = _grew(ctx)
    if not g or g["live_slot_forwards"] <= 0:
        return None
    return (g["tokens_committed"] - g["opening"]) / g["live_slot_forwards"]


def commit_share(ctx) -> float | None:
    """Commit forwards ÷ all forwards of the decode dispatches."""
    g = _grew(ctx)
    if not g or g["forwards"] <= 0:
        return None
    return 100.0 * g["commit_forwards"] / g["forwards"]


def dropped_share(ctx) -> float | None:
    """Tokens the forwards yielded that reached no stream (past a budget or a
    stop token, idle and stale slots' blocks) ÷ all they yielded."""
    g = _grew(ctx)
    made = (g["tokens_committed"] + g["tokens_dropped"]) if g else 0
    if made <= 0:
        return None
    return 100.0 * g["tokens_dropped"] / made


def _forward_s(ctx) -> float | None:
    """Device seconds of the mean forward of a decode dispatch."""
    from readers.gdn import _counted

    name = ctx.cell.config.get("decode_program")
    startup = _startup(ctx)
    if not _is_bd(ctx) or not ctx.trace or not name or not startup:
        return None
    counted = _counted(ctx, name)
    if not counted or not counted["runs"] or counted["seconds"] <= 0:
        return None
    return (counted["seconds"] / counted["runs"]
            / startup["forwards_per_dispatch"])


def forward_ms(ctx) -> float | None:
    s = _forward_s(ctx)
    return None if s is None else 1e3 * s


def _against(ctx, count, peak_key: str) -> float | None:
    """`count(model, serving, lengths, block, head_share)` of the mean
    forward, over the window's samples of the live streams' lengths, ÷ the
    mean forward's device time ÷ the chip's published peak."""
    from readers.dsa import live_lengths

    s = _forward_s(ctx)
    if s is None:
        return None
    startup = _startup(ctx)
    share = startup["steps"] / (startup["steps"] + 1)
    samples = live_lengths(ctx)
    mean = sum(count(ctx.cell.config, ctx.cell.tpu, lengths,
                     startup["block"], share)
               for lengths in samples) / len(samples)
    return 100.0 * mean / s / peaks_for(ctx.device["kind"])[peak_key]


def decode_hbm_share(ctx) -> float | None:
    """Bytes one forward must move (`bd_bytes.forward_bytes`: weights, every
    live position's K/V, the live blocks' own rows, and the experts UNIFORM
    routing would hit — an upper count of what this traffic hits, so the
    share reads at or above the true one: that module's docstring) ÷ the
    mean forward's device time ÷ the chip's published HBM bandwidth."""
    return _against(ctx, bd_bytes.forward_bytes, "hbm_bytes_per_s")


def forward_mxu_share(ctx) -> float | None:
    """FLOPs one forward must compute for its LIVE slots
    (`bd_bytes.forward_flops`) ÷ the mean forward's device time ÷ the chip's
    published bf16 peak."""
    return _against(ctx, bd_bytes.forward_flops, "bf16_flops")


def prefill_mxu_share(ctx) -> float | None:
    """ACTIVE FLOPs admitted per second (`bd_bytes.prefill_flops` of the
    prompts whose first tokens arrived in the window, with the template's
    tokens: whole blocks under the block mask and the opening block's
    forwards) ÷ device seconds of the admission programs per second (over
    the capture inside it) ÷ the chip's published bf16 peak, as
    `readers/dsa.py prefill_mxu_share` is built."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    startup = _startup(ctx)
    if (not _is_bd(ctx) or not t or not name or not startup
            or not t.get("window_s")):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        bd_bytes.prefill_flops(ctx.cell.config,
                               r["prompt_tokens"] + template,
                               startup["block"], startup["steps"])
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak
