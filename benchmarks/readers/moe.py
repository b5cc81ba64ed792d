"""Metrics of the sparse-expert path: the reduced device trace against the
counts of `lib/moe_bytes.py`, and the program's `stats.engine.moe` counters.
A reader that finds nothing to read (no trace, no such program in it, a
program without the counter — the parent of the PR that brought it) returns
None and the metric is left out of the line."""

from __future__ import annotations

from lib import moe_bytes, window
from lib.peaks import peaks_for

from readers.stats import _dig


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must read per chip (all experts' slices when the
    step's pairs hit them all, attention, scales, LM head, live KV) ÷ the
    traced device time of one step of the decode program ÷ the chip's
    published HBM bandwidth."""
    t = ctx.trace
    if not t or not t.get("decode") or not t["decode"]["runs"]:
        return None
    if "num_local_experts" not in ctx.cell.config:
        return None
    ph = ctx.phase
    slots, tokens = window.mean_live(ph.records, ph.w0, ph.w1)
    nbytes = moe_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu,
                                         tokens, slots)
    step_s = (t["decode"]["seconds"] / t["decode"]["runs"]
              / ctx.cell.tpu["decode_block"])
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def prefill_mxu_share(ctx) -> float | None:
    """Routed FLOPs prefilled per second ÷ device seconds of the prefill
    programs per second ÷ the chips' published bf16 peak.

    The numerator is a rate over the window (prompts whose first token
    arrived in it: their tokens with the template's, through
    `moe_bytes.prefill_flops`), the denominator a rate over the capture
    inside it (the programs whose name holds the configuration's
    `prefill_program`, device seconds per chip ÷ the capture's length): a
    closed loop in steady state prefills at one rate. Padding to a bucket is
    time the programs spend and no work counted, so it lowers the share."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not t or not name or not t.get("window_s"):
        return None
    if "num_local_experts" not in ctx.cell.config:
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        moe_bytes.prefill_flops(ctx.cell.config,
                                r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak


def expert_imbalance(ctx) -> float | None:
    """Busiest expert's (token, expert) pairs ÷ the mean over experts, of
    the pairs `stats.engine.moe.expert_pairs` grew by over the window."""
    a = _dig(ctx.phase.stats_start, "engine.moe.expert_pairs")
    b = _dig(ctx.phase.stats_end, "engine.moe.expert_pairs")
    if not a or not b or len(a) != len(b):
        return None
    grew = [y - x for x, y in zip(a, b)]
    if sum(grew) <= 0:
        return None
    return max(grew) / (sum(grew) / len(grew))
