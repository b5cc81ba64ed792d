"""Metrics read from the program's own counters and histograms: the provider
`stats` op (`provider.stats()` with the engine host's block under `engine`).
They are lifetime totals, so a window's share is the difference between the
read at the window's start and the read after it. The histograms export
count and mean (no buckets), so a window gives a MEAN, never a median."""

from __future__ import annotations

from lib import window


def _dig(d: dict | None, path: str):
    for key in path.split("."):
        if not isinstance(d, dict):
            return None
        d = d.get(key)
    return d


def setup_s(ctx) -> float | None:
    """Process start → window start."""
    return ctx.setup_s


def hist_window_mean(ctx, path: str, scale: float = 1.0) -> float | None:
    v = window.hist_delta_mean(_dig(ctx.phase.stats_start, path),
                               _dig(ctx.phase.stats_end, path))
    return None if v is None else scale * v


def provider_hop_mean_s(ctx) -> float | None:
    """Provider TTFT − engine TTFT over the window's requests: what the
    pipe, the relay and the provider's event loop add to a first token."""
    prov = hist_window_mean(ctx, "ttft_s")
    eng = hist_window_mean(ctx, "engine.engine_ttft_s")
    return None if prov is None or eng is None else prov - eng


def counter_share(ctx, path: str) -> float | None:
    """A cumulative-seconds counter's growth over the sampled window, as a
    share of that window."""
    s0, s1 = ctx.phase.samples[0], ctx.phase.samples[-1]
    a, b = _dig(s0[1], path), _dig(s1[1], path)
    if a is None or b is None or s1[0] <= s0[0]:
        return None
    return 100.0 * (b - a) / (s1[0] - s0[0])


def occupancy(ctx) -> float | None:
    """Live slots ÷ slots, mean of the stats op's samples in the window."""
    vals = [_dig(s, "engine.occupancy") for _, s in ctx.phase.samples]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return 100.0 * sum(vals) / len(vals) / ctx.cell.tpu["max_batch_size"]


def decode_step_ms(ctx) -> float | None:
    """The scheduler's own figure: block-interval p50 ÷ decode_block, from
    its lifetime reservoir as read after the window (the warm phase is the
    same traffic, so the lifetime median is the steady one)."""
    return _dig(ctx.phase.stats_end, "engine.decode_step_ms")


def hbm_used(ctx) -> float | None:
    """bytes_in_use ÷ bytes_limit on the fullest chip, as the engine host
    read them once every program had compiled and the cache was allocated."""
    hbm = _dig(ctx.phase.stats_end, "engine.startup.device.hbm") or []
    shares = [h["bytes_in_use"] / h["bytes_limit"] for h in hbm
              if h.get("bytes_limit")]
    return 100.0 * max(shares) if shares else None
