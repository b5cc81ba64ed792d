"""Metrics of the gated-short-convolution path (lfm2_moe family): the device
trace against the counts of `lib/sconv_bytes.py`. A reader that finds nothing
to read (no trace, a configuration of another family, a capture without a
whole run of the decode program) returns None and the metric is left out of
the line. The program's `startup.ssm` block and `engine.ssm` counters are
read by `readers/hybrid.py`'s functions (`state_hbm_share`, `prefill_tok_s`,
`installs_per_s`), which ask nothing of the family.

The decode step's time comes from WHOLE runs of the decode program only, by
`readers/gdn.py`'s rule and code (`_counted`: the capture's cut runs are left
out), not from `lib/xplane.py`'s count of every piece.
"""

from __future__ import annotations

from lib import sconv_bytes, window
from lib.peaks import peaks_for

from readers.gdn import _counted


def _is_sconv(ctx) -> bool:
    return ctx.cell.config.get("model_type") == "lfm2_moe"


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must move (`sconv_bytes.step_bytes`: every
    layer's weights with the experts the step hits, the head, the tails of
    every slot read once and written once, the LIVE K/V whatever runs the
    attention) ÷ the device time of one step — the mean WHOLE run of the
    decode program ÷ `decode_block` — ÷ the chip's published HBM bandwidth:
    a share of the whole decode step."""
    name = ctx.cell.config.get("decode_program")
    if not _is_sconv(ctx) or not ctx.trace or not name:
        return None
    counted = _counted(ctx, name)
    if not counted or not counted["runs"] or counted["seconds"] <= 0:
        return None
    ph = ctx.phase
    slots, tokens = window.mean_live(ph.records, ph.w0, ph.w1)
    nbytes = sconv_bytes.step_bytes(ctx.cell.config, ctx.cell.tpu, tokens,
                                    slots)
    step_s = (counted["seconds"] / counted["runs"]
              / ctx.cell.tpu["decode_block"])
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak


def prefill_mxu_share(ctx) -> float | None:
    """Active FLOPs prefilled per second ÷ device seconds of the prefill
    programs per second ÷ the chip's published bf16 peak — the rates as
    `readers/gdn.py prefill_mxu_share` takes them: the numerator over the
    window (prompts whose first token arrived in it, with the template's
    tokens, through `sconv_bytes.prefill_flops`), the denominator over the
    capture inside it (programs whose name holds the configuration's
    `prefill_program`). Padding to a bucket is time spent and no work
    counted; the dense mixture's extra FLOPs are never counted."""
    t = ctx.trace
    name = ctx.cell.config.get("prefill_program")
    if not _is_sconv(ctx) or not t or not name or not t.get("window_s"):
        return None
    device_s = sum(v[0] for n, v in t["programs"].items() if name in n)
    if device_s <= 0:
        return None
    ph = ctx.phase
    template = int(ctx.cell.config.get("template_tokens", 0))
    flops = sum(
        sconv_bytes.prefill_flops(ctx.cell.config,
                                  r["prompt_tokens"] + template)
        for r in ph.records
        if r["stamps"] and ph.w0 <= r["stamps"][0][0] < ph.w1)
    if not flops:
        return None
    chips = max(1, int(ctx.device["count"] or 1))
    peak = peaks_for(ctx.device["kind"])["bf16_flops"]
    busy_share = device_s / t["window_s"]
    return 100.0 * flops / (ph.w1 - ph.w0) / chips / busy_share / peak
