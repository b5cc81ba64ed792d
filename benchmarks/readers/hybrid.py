"""Metrics of the hybrid (recurrent-state + attention, routed + shared expert)
path: the reduced device trace against the counts of `lib/hybrid_bytes.py`,
and the program's `stats.engine.startup.ssm` block and `stats.engine.ssm`
counters. A reader that finds nothing to read (no trace, a configuration
without `layer_types`, a program without the block — the parent of the PR
that brought it) returns None and the metric is left out of the line."""

from __future__ import annotations

from lib import hybrid_bytes, window
from lib.peaks import peaks_for

from readers.stats import _dig, counter_share


def _is_hybrid(ctx) -> bool:
    return "layer_types" in ctx.cell.config


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must move (`hybrid_bytes.decode_step_bytes`:
    weights, the experts the step hits, the state of every slot read AND
    written, live K/V) ÷ the traced device time of one step of the decode
    program ÷ the chip's published HBM bandwidth."""
    t = ctx.trace
    if not _is_hybrid(ctx) or not t or not t.get("decode") \
            or not t["decode"]["runs"]:
        return None
    ph = ctx.phase
    slots, tokens = window.mean_live(ph.records, ph.w0, ph.w1)
    nbytes = hybrid_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu,
                                            tokens, slots)
    step_s = (t["decode"]["seconds"] / t["decode"]["runs"]
              / ctx.cell.tpu["decode_block"])
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def prefill_mxu_share(ctx) -> float | None:
    """Active FLOPs prefilled per second ÷ device seconds of the prefill
    programs per second ÷ the chip's published bf16 peak — the rates as
    `readers/moe.py prefill_mxu_share` takes them: the numerator over the
    window (prompts whose first token arrived in it, with the template's
    tokens, through `hybrid_bytes.prefill_flops`), the denominator over the
    capture inside it (programs whose name holds the configuration's
    `prefill_program`). Padding to a bucket is time spent and no work
    counted."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not _is_hybrid(ctx) or not t or not name or not t.get("window_s"):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        hybrid_bytes.prefill_flops(ctx.cell.config,
                                   r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak


def state_hbm_share(ctx) -> float | None:
    """The recurrent state and conv tails of all slots, as the program
    reports them (`startup.ssm.state_bytes`), ÷ the chip's `bytes_limit`."""
    state = _dig(ctx.phase.stats_end, "engine.startup.ssm.state_bytes")
    hbm = _dig(ctx.phase.stats_end, "engine.startup.device.hbm") or []
    limits = [h["bytes_limit"] for h in hbm if h.get("bytes_limit")]
    if not state or not limits:
        return None
    return 100.0 * state / min(limits)


def _counter_rate(ctx, key: str) -> float | None:
    """Growth of an `engine.ssm` counter over the sampled window, a second
    (`stats.counter_share` is that growth as a percentage of the window)."""
    share = counter_share(ctx, f"engine.ssm.{key}")
    return None if share is None else share / 100.0


def prefill_tok_s(ctx) -> float | None:
    """Valid prompt tokens the mamba layers scanned, a second."""
    return _counter_rate(ctx, "prefill_tokens")


def installs_per_s(ctx) -> float | None:
    """Lanes whose recurrent state an insert overwrote, a second."""
    return _counter_rate(ctx, "state_installs")
