"""Metrics read from the scheduler's read records: `stats.engine.reads`
(one record for every entry that left the in-flight queue: `fields` names
the columns, `recent` holds the last 64) and `stats.engine.stalls`. The
harness samples `stats` every second, so the union of the samples' `recent`
lists by `seq` is every read of the window. All stamps are CLOCK_MONOTONIC,
the clock of the client stamps and of `w0` / `w1`.

An INTERVAL is decode-block read to decode-block read (`t` to `t`), ending
inside the window, never across an idle boundary (the later block's
`caused_by` is the earlier one's `seq`) and never across a record the
samples missed. The TAIL is the longest 5% of the window's intervals, at
least 3: what the clients' gap tail (`gap_tail_s`, and `wire_gap_p99_s`
above it) is made of on the engine's side.

A program without `reads` (before PR 37) reads as None everywhere.
`tools/read_tail.py` prints a run's tail, record by record, from a
`run.py --dump` file, and joins the records to a capture.
"""

from __future__ import annotations

import math

from lib import window
from readers import client
from readers.stats import _dig

BLOCKS = ("decode_block", "verify")
PARTS = ("block", "prefill", "other")
# An interval runs from read stamp to read stamp and its `device_s` from
# ready stamp to ready stamp: exact parts may pass their interval by the
# stamps' own distance (microseconds). Past this they were cut.
CLIP_S = 1e-3


def _all_stats(ctx) -> list[dict]:
    ph = ctx.phase
    return [ph.stats_start, *(s for _, s in ph.samples), ph.stats_end]


def records(ctx) -> list[dict] | None:
    """Every read the samples hold, by `seq`; None without `reads`."""
    if not hasattr(ctx, "_reads"):
        by_seq: dict[int, dict] = {}
        seen = False
        for stats in _all_stats(ctx):
            reads = _dig(stats, "engine.reads")
            if not reads:
                continue
            seen = True
            for row in reads["recent"]:
                rec = dict(zip(reads["fields"], row))
                by_seq[rec["seq"]] = rec
        ctx._reads = ([by_seq[k] for k in sorted(by_seq)] if seen else None)
    return ctx._reads


def intervals(recs: list[dict], w0: float, w1: float) -> list[dict]:
    """`{"s", "block", "admissions"}` per interval ending in [w0, w1]: its
    seconds, the record of the block that closed it, and the records of
    the admissions read inside it."""
    out = []
    prev = None      # the last block record, while no seq is missing since
    inside: list[dict] = []
    last_seq = None
    for rec in recs:
        if last_seq is not None and rec["seq"] != last_seq + 1:
            prev, inside = None, []
        last_seq = rec["seq"]
        if rec["kind"] not in BLOCKS:
            inside.append(rec)
            continue
        if (prev is not None and rec["caused_by"] == prev["seq"]
                and w0 <= rec["t"] <= w1):
            out.append({"s": rec["t"] - prev["t"], "block": rec,
                        "admissions": inside})
        prev, inside = rec, []
    return out


def tail(ivs: list[dict]) -> list[dict]:
    n = max(3, math.ceil(0.05 * len(ivs)))
    return sorted(ivs, key=lambda iv: iv["s"], reverse=True)[:n]


def _window_intervals(ctx) -> list[dict] | None:
    recs = records(ctx)
    if recs is None:
        return None
    return intervals(recs, ctx.phase.w0, ctx.phase.w1)


def interval_p99_s(ctx) -> float | None:
    """99th percentile of the window's intervals: the engine's side of the
    client gap p99."""
    ivs = _window_intervals(ctx)
    if not ivs:
        return None
    return window.percentile([iv["s"] for iv in ivs], 99)


def wire_excess_ms(ctx) -> float | None:
    """Client gap p99 − interval p99: what the emit worker, the pipe, the
    relay, the wire and the client add at the tail."""
    gap = client.gap_percentile_s(ctx, 99)
    p99 = interval_p99_s(ctx)
    return None if gap is None or p99 is None else 1e3 * (gap - p99)


def admitted_s(rec: dict) -> float:
    """Device seconds of the admission work a record stands for: a chunked
    prompt's final chunk stands for the `chunks` dispatched unread ahead of
    it too, and its `device_s` is one chunk's."""
    n = 1 + rec.get("chunks", 0) if rec["kind"] == "chunk" else 1
    return n * rec["device_s"]


def split(ivs: list[dict]) -> dict[str, float]:
    """The seconds of `ivs` split into the closing block's device seconds,
    the device seconds of the admissions read inside (a chunked prompt's
    final chunk with the `chunks` that ran unread ahead of it, each at its
    `device_s`), and the rest: late reads, host work, and a stall — the
    program prices a read whose wait ran past its entry at what the entry
    should have taken, so the excess is in no `device_s`. An inexact
    `device_s` is a bound or a charged estimate and can overshoot its
    interval: it is cut to it, so the three sum to the intervals' seconds,
    and `clipped` counts the intervals that were cut."""
    out = {"block": 0.0, "prefill": 0.0, "other": 0.0, "clipped": 0}
    for iv in ivs:
        block_s = iv["block"]["device_s"]
        prefill_s = sum(map(admitted_s, iv["admissions"]))
        block = min(block_s, iv["s"])
        prefill = min(prefill_s, iv["s"] - block)
        out["block"] += block
        out["prefill"] += prefill
        out["other"] += iv["s"] - block - prefill
        out["clipped"] += block_s + prefill_s > iv["s"] + CLIP_S
    return out


def tail_in(ctx, part: str) -> float | None:
    """Share of the tail intervals' seconds in `part` (block / prefill /
    other); the three sum to 100."""
    ivs = _window_intervals(ctx)
    if not ivs:
        return None
    parts = split(tail(ivs))
    total = sum(parts[p] for p in PARTS)
    return 100.0 * parts[part] / total if total > 0 else None


def tail_clipped(ctx) -> float | None:
    """Tail intervals whose block and admissions claimed more seconds than
    the interval has (an inexact bound overshot and `split` cut it): with
    `read_exact_share`, how far `tail_in.*` can be trusted."""
    ivs = _window_intervals(ctx)
    if not ivs:
        return None
    return split(tail(ivs))["clipped"]


def tail_admissions(ctx) -> float | None:
    """Mean admission entries read inside a tail interval."""
    ivs = _window_intervals(ctx)
    if not ivs:
        return None
    worst = tail(ivs)
    return sum(len(iv["admissions"]) for iv in worst) / len(worst)


def read_exact_share(ctx) -> float | None:
    """Share of the window's reads whose `device_s` is exact (the thread
    waited for the entry and for the one before it)."""
    recs = records(ctx)
    if recs is None:
        return None
    inside = [r for r in recs if ctx.phase.w0 <= r["t"] <= ctx.phase.w1]
    if not inside:
        return None
    return 100.0 * sum(bool(r["exact"]) for r in inside) / len(inside)


def prefill_tok_per_device_s(ctx) -> float | None:
    """Valid prompt tokens of the admissions read in the window ÷ their
    device seconds: the rate at which the chip prefills while it prefills
    (not per second of window: that is `admit_device_share`'s other
    factor)."""
    recs = records(ctx)
    if recs is None:
        return None
    inside = [r for r in recs if r["kind"] not in BLOCKS
              and ctx.phase.w0 <= r["t"] <= ctx.phase.w1]
    seconds = sum(map(admitted_s, inside))
    if seconds <= 0:
        return None
    return sum(r["tokens"] for r in inside) / seconds


def stall_count(ctx) -> float | None:
    """Growth of `stalls.count` from the window's first sample to its last
    (`stats_end` is read after the drain, so it is not the window's)."""
    ph = ctx.phase
    last = ph.samples[-1][1] if ph.samples else ph.stats_end
    a = _dig(ph.stats_start, "engine.stalls.count")
    b = _dig(last, "engine.stalls.count")
    return None if a is None or b is None else b - a


def stall_longest_s(ctx) -> float | None:
    """The longest stall (its `excess_s`) that ended in the window; 0 when
    there was none."""
    found = False
    longest = 0.0
    for stats in _all_stats(ctx):
        stalls = _dig(stats, "engine.stalls")
        if stalls is None:
            continue
        found = True
        for st in stalls["recent"]:
            if ctx.phase.w0 <= st["t"] <= ctx.phase.w1:
                longest = max(longest, st["excess_s"])
    return longest if found else None
