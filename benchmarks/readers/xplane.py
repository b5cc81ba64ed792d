"""Metrics read from the reduced device trace (`lib/xplane.py`'s object).
Without a trace (`--trace 0`, or a capture that failed) they return None."""

from __future__ import annotations

from lib import step_bytes, window
from lib.peaks import peaks_for


def device_idle(ctx) -> float | None:
    t = ctx.trace
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def top_op_share(ctx) -> float | None:
    t = ctx.trace
    if not t or not t["ops"] or not t["busy_s"]:
        return None
    return 100.0 * t["ops"][0][1] / t["busy_s"]


def collective_share(ctx) -> float | None:
    t = ctx.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]


def decode_hbm_share(ctx) -> float | None:
    """Bytes one decode step must read (weights + live KV + scales, from
    shapes, per chip) ÷ the traced device time of one decode step ÷ the
    chip's published HBM bandwidth. The step time is the decode program's
    device seconds ÷ its runs ÷ decode_block, so host gaps between programs
    are not in it."""
    t = ctx.trace
    if not t or not t.get("decode") or not t["decode"]["runs"]:
        return None
    ph = ctx.phase
    slots, tokens = window.mean_live(ph.records, ph.w0, ph.w1)
    nbytes = step_bytes.decode_step_bytes(ctx.cell.config, ctx.cell.tpu,
                                          tokens, slots)
    step_s = (t["decode"]["seconds"] / t["decode"]["runs"]
              / ctx.cell.tpu["decode_block"])
    peak = peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / step_s / peak
