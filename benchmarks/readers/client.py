"""Metrics read from the client records (host clock, client side)."""

from __future__ import annotations

from lib import window


def out_tok_s(ctx) -> float | None:
    """Tokens that reached clients inside the window ÷ window."""
    p = ctx.phase
    return window.window_tokens(p.records, p.w0, p.w1) / (p.w1 - p.w0)


def gap_percentile_s(ctx, p: float = 99.0) -> float | None:
    ph = ctx.phase
    return window.percentile(window.window_gaps(ph.records, ph.w0, ph.w1), p)


def gap_band_mean_s(ctx, lo: float = 80.0, hi: float = 98.0) -> float | None:
    """Mean of the window's gaps from their `lo`th percentile up to their
    `hi`th (`lib/window.py band_mean`): the judged tail, `gap_tail_s`."""
    ph = ctx.phase
    return window.band_mean(window.window_gaps(ph.records, ph.w0, ph.w1),
                            lo, hi)


def ttft_percentile_s(ctx, p: float = 50.0) -> float | None:
    """From DUE time, over requests due in the window. A missing request
    sorts as infinitely late, so it raises the percentile instead of
    leaving the sample."""
    ph = ctx.phase
    ttfts, missing = window.window_ttfts(ph.records, ph.w0, ph.w1)
    value = window.percentile(ttfts + [float("inf")] * missing, p)
    return None if value in (None, float("inf")) else value


def ttft_mean_s(ctx) -> float | None:
    """Mean TTFT from due time over EVERY request due in the window. One
    with no first token is charged its whole wait (until it ended, or until
    the run's last stamp), so a failure can only raise the mean; `failed`
    counts it as well."""
    ph = ctx.phase
    due = window.due_in_window(ph.records, ph.w0, ph.w1)
    if not due:
        return None
    horizon = max(r["t_done"] or ph.w1 for r in ph.records)
    waits = [((r["t_done"] or horizon) if window.failed(r)
              else r["stamps"][0][0]) - r["due"] for r in due]
    return sum(waits) / len(waits)


def tpot_p50_ms(ctx) -> float | None:
    ph = ctx.phase
    v = window.percentile(window.tpots(ph.records, ph.w0, ph.w1), 50)
    return None if v is None else 1e3 * v


def gen_late_p99_ms(ctx) -> float | None:
    """How late the generator sent: actual send − due time."""
    ph = ctx.phase
    late = [r["t_send"] - r["due"]
            for r in window.due_in_window(ph.records, ph.w0, ph.w1)]
    v = window.percentile(late, 99)
    return None if v is None else 1e3 * v


def slo_share(ctx) -> float | None:
    """Share of requests due in the window that met both limits of the
    traffic file (TTFT from due time, and every inter-chunk gap)."""
    ph = ctx.phase
    limits = ctx.cell.traffic.get("limits") or {}
    due = window.due_in_window(ph.records, ph.w0, ph.w1)
    if not due or not limits:
        return None
    met = 0
    for r in due:
        if window.failed(r):
            continue
        ts = [t for t, _ in r["stamps"]]
        worst_gap = max((b - a for a, b in zip(ts, ts[1:])), default=0.0)
        if (ts[0] - r["due"] <= limits["ttft_s"]
                and worst_gap <= limits["gap_s"]):
            met += 1
    return 100.0 * met / len(due)


def kv_fill(ctx) -> float | None:
    """Tokens resident in live slots ÷ (slots × context), mean over the
    window; from what each stream had been sent at each sample time."""
    ph = ctx.phase
    _, tokens = window.mean_live(ph.records, ph.w0, ph.w1)
    tpu = ctx.cell.tpu
    return 100.0 * tokens / (tpu["max_batch_size"] * tpu["max_seq_len"])
