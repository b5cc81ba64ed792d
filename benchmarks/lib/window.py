"""Percentiles and window arithmetic over client records.

A record is what one request left at the client (`lib/client_worker.py`):

    {"due": t, "t_send": t, "stamps": [[t, chars], ...], "t_done": t | None,
     "tokens": n | None, "finish": str | None, "error": str | None,
     "prompt_tokens": n, "max_new": n, "warm": bool}

All times are CLOCK_MONOTONIC seconds, one clock across the processes of a
machine. The window is [w0, w1). Every end-to-end number is taken over all the
work and all the time of the window: every gap that ends in it is ranked, and
the judged tail (`band_mean`) is a fixed band of those ranks, the same in
every run — no run, request or interval is left out by what it measured.
"""

from __future__ import annotations

import math


def _rank(n: int, p: float) -> int:
    """Index of the nearest-rank `p`th percentile among `n` sorted samples."""
    return min(n - 1, max(0, math.ceil(p / 100.0 * n) - 1))


def percentile(values: list[float], p: float) -> float | None:
    """Nearest-rank percentile, p in 0..100; None for no samples."""
    if not values:
        return None
    return sorted(values)[_rank(len(values), p)]


def band_mean(values: list[float], lo: float, hi: float) -> float | None:
    """Mean of the samples from the `lo`th nearest-rank percentile up to the
    `hi`th, both included; None for no samples. With lo 80 and hi 98 that is
    the worst fifth without its top fiftieth: where ONE event holds 1.0-1.7%
    of the samples (a block interval of a closed loop: every live stream's
    gap at once), the plain 99th percentile IS that event, and a mean over
    the band under the 98th is owned by no single one."""
    if not values:
        return None
    n = len(values)
    band = sorted(values)[_rank(n, lo):_rank(n, hi) + 1]
    return sum(band) / len(band) if band else None


def window_tokens(records: list[dict], w0: float, w1: float) -> float:
    """Tokens that reached clients inside the window, by arrival stamp.

    The wire carries text chunks, not token counts; a request's exact token
    count arrives with its end frame. Each chunk is given its request's
    tokens in proportion to its characters (the byte tokenizer decodes one
    token to at most one character, and a chunk is one decode block), so the
    error is confined to the two chunks of a stream that straddle the
    window's edges."""
    total = 0.0
    for r in records:
        if not r.get("tokens") or not r["stamps"]:
            continue
        chars = sum(c for _, c in r["stamps"])
        if chars <= 0:
            continue
        inside = sum(c for t, c in r["stamps"] if w0 <= t < w1)
        total += r["tokens"] * inside / chars
    return total


def window_gaps(records: list[dict], w0: float, w1: float) -> list[float]:
    """Time between successive chunks of one stream, for every gap that ENDS
    inside the window (a stall is charged to the moment it was felt)."""
    gaps = []
    for r in records:
        ts = [t for t, _ in r["stamps"]]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if w0 <= b < w1)
    return gaps


def due_in_window(records: list[dict], w0: float, w1: float) -> list[dict]:
    return [r for r in records if w0 <= r["due"] < w1]


def failed(record: dict) -> bool:
    """A request that errored, was shed, or never showed a first token by the
    time the run drained counts as missing, and as failed."""
    return bool(record.get("error")) or not record["stamps"] or (
        record.get("t_done") is None)


def window_ttfts(records: list[dict], w0: float, w1: float
                 ) -> tuple[list[float], int]:
    """(TTFTs from DUE time of the requests due in the window, count of
    those that are missing). Timing from the due time charges a generator
    or a server stall to every request it delayed."""
    ttfts, missing = [], 0
    for r in due_in_window(records, w0, w1):
        if failed(r):
            missing += 1
        else:
            ttfts.append(r["stamps"][0][0] - r["due"])
    return ttfts, missing


def tpots(records: list[dict], w0: float, w1: float) -> list[float]:
    """Per request that ended in the window: (last − first chunk) over
    (tokens − 1), seconds per output token on the wire."""
    out = []
    for r in records:
        if failed(r) or not w0 <= r["t_done"] < w1:
            continue
        if (r.get("tokens") or 0) > 1 and len(r["stamps"]) > 1:
            out.append((r["stamps"][-1][0] - r["stamps"][0][0])
                       / (r["tokens"] - 1))
    return out


def live_at(records: list[dict], t: float) -> tuple[int, float]:
    """(streams live, tokens resident in their slots) at time `t`: a stream
    is live from its first chunk to its end; it holds its prompt plus what
    it has been sent so far."""
    streams, tokens = 0, 0.0
    for r in records:
        if not r["stamps"] or r.get("t_done") is None:
            continue
        if r["stamps"][0][0] <= t < r["t_done"]:
            streams += 1
            chars = sum(c for _, c in r["stamps"]) or 1
            seen = sum(c for ts, c in r["stamps"] if ts <= t)
            tokens += r["prompt_tokens"] + (r.get("tokens") or 0) * seen / chars
    return streams, tokens


def mean_live(records: list[dict], w0: float, w1: float,
              step_s: float = 0.5) -> tuple[float, float]:
    """Mean (live streams, resident tokens) sampled every `step_s`."""
    n = max(1, int((w1 - w0) / step_s))
    overlapping = [r for r in records if r["stamps"]
                   and r.get("t_done") is not None
                   and r["stamps"][0][0] < w1 and r["t_done"] > w0]
    samples = [live_at(overlapping, w0 + (i + 0.5) * (w1 - w0) / n)
               for i in range(n)]
    return (sum(s for s, _ in samples) / n, sum(k for _, k in samples) / n)


def hist_delta_mean(start: dict | None, end: dict | None) -> float | None:
    """Mean of the observations a lifetime histogram took between two reads
    (`{"count", "mean"}` each): the program exports no buckets, so a window's
    median cannot be recovered, its mean can."""
    if not end or not end.get("count"):
        return None
    c0 = (start or {}).get("count") or 0
    m0 = (start or {}).get("mean") or 0.0
    n = end["count"] - c0
    if n <= 0:
        return None
    return (end["count"] * end["mean"] - c0 * m0) / n
