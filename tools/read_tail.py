#!/usr/bin/env python3
"""A run's read records, looked at: `python tools/read_tail.py <dump.json>
[capture]`.

`<dump.json>` is what `benchmarks/run.py --dump <dir>` writes (the window,
the client records and the `stats` samples, so every read record of the
window: `benchmarks/readers/tail.py`). Prints one JSON line: the tail
metrics, the clients' gap p99 beside what explains it — the plain interval
p99 with `flush_ahead`'s growth over the window (blocks whose events left
ahead of an admission, and the seconds of those admissions' waits) or, for
a dump of a tree before PR 38, the p99 of the rebuilt "block read -> first
admission read" intervals — the tail's intervals record by record, how
many of them `split` had to cut, admission entries read per interval (the histogram
`tools/per_block` took at dispatch until PR 37; a chunked prompt counts
once here, at its final chunk), prefill tokens per device second by
program shape, and every stall record with the harness's own poll gap
across it. With a capture of the same run (a `--trace 1` run leaves its
path in the result line), each record is also held against the capture's
program runs of the same `seq`: `join`.

Touches no device: a dump and a capture are files.
"""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import window  # noqa: E402
from lib.xplane import DEVICE_PLANE, MODULES_LINE, find_xplane  # noqa: E402
from readers import client, tail  # noqa: E402
from readers.stats import _dig, counter_share  # noqa: E402

SYNC = "sym.sched.sync"


def _sync_attrs(ev) -> dict | None:
    """`entry` and `seq` of a `sym.sched.sync` event: its stats (how
    `ProfileData` hands out a TraceAnnotation's keywords), or the
    `name#k=v,...#` form a raw trace names it by. None on a program
    whose sync carries none (before PR 37)."""
    name, _, packed = ev.name.partition("#")
    if name != SYNC:
        return None
    attrs = (dict(kv.split("=", 1) for kv in packed.strip("#").split(","))
             if packed else {str(k): v for k, v in ev.stats})
    return attrs if "seq" in attrs and "entry" in attrs else None


def capture_entries(data) -> list[dict]:
    """What a capture holds of each read, joined by `seq`: the
    `sym.sched.sync` events of the host plane carry `entry` and `seq`
    (`_sync_attrs`), and reads are in device order, so the first device
    plane's program runs (`XLA Modules`) that ended before a sync did, and
    after the sync before it, are that entry's. `data` is a
    `jax.profiler.ProfileData` or anything shaped like one. The first sync
    of a capture is left out: programs from before the capture began may
    be missing from its share."""
    syncs, runs = [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            if runs is None:
                for line in plane.lines:
                    if line.name == MODULES_LINE:
                        runs = sorted(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                            for ev in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    attrs = _sync_attrs(ev)
                    if attrs is not None:
                        syncs.append({
                            "seq": int(attrs["seq"]),
                            "entry": str(attrs["entry"]),
                            "end": (ev.start_ns + ev.duration_ns) * 1e-9})
    syncs.sort(key=lambda s: s["end"])
    out, i = [], 0
    runs = runs or []
    for n, sync in enumerate(syncs):
        mine = []
        # the host learns of the end a moment after the device reached it
        while i < len(runs) and runs[i][1] <= sync["end"]:
            mine.append(runs[i])
            i += 1
        if n and mine:
            out.append({**sync, "programs": [name for _s, _e, name in mine],
                        "program_s": sum(e - s for s, e, _n in mine),
                        "span_s": mine[-1][1] - mine[0][0]})
    return out


def join(recs: list[dict], entries: list[dict]) -> list[dict]:
    """Each record with the capture's account of the same `seq`."""
    by_seq = {r["seq"]: r for r in recs}
    return [{**by_seq[e["seq"]], **e} for e in entries
            if e["seq"] in by_seq and by_seq[e["seq"]]["kind"] == e["entry"]]


def prefill_rates(recs: list[dict], w0: float, w1: float) -> dict[str, dict]:
    """Per program shape (`rows x bucket`), the window's admission reads:
    how many, their valid prompt tokens, their device seconds, and the
    tokens a device second."""
    out: dict[str, dict] = {}
    for r in recs:
        if r["kind"] in tail.BLOCKS or not w0 <= r["t"] <= w1:
            continue
        row = out.setdefault(f"{r['kind']} {r['rows']}x{r['bucket']}",
                             {"reads": 0, "tokens": 0, "device_s": 0.0})
        row["reads"] += 1
        row["tokens"] += r["tokens"]
        row["device_s"] += tail.admitted_s(r)
    for row in out.values():
        row["tok_per_device_s"] = (row["tokens"] / row["device_s"]
                                   if row["device_s"] > 0 else None)
    return out


def leave_p99_s(recs: list[dict], ivs: list[dict]) -> float | None:
    """99th percentile of the intervals between the moments two successive
    blocks' events LEFT the engine thread on a tree before PR 38, whose
    loop flushed a block's events only after the first admission read
    behind it: that admission's read stamp, or the block's own with none
    behind it. There this, and not the plain interval, met the clients'
    gap p99 (PERF.md §6, PR 37)."""
    by_seq = {r["seq"]: r for r in recs}

    def left(block: dict) -> float:
        nxt = by_seq.get(block["seq"] + 1)
        return (nxt["t"] if nxt is not None
                and nxt["kind"] not in tail.BLOCKS else block["t"])

    spans = [left(iv["block"]) - left(by_seq[iv["block"]["caused_by"]])
             for iv in ivs]
    return window.percentile(spans, 99) if spans else None


def flush_ahead(ctx) -> dict | None:
    """Growth of `stats.engine.flush_ahead` over the window's samples
    (PR 38: blocks whose events left with an admission unread behind
    them, and the seconds of those admissions' waits; `lead_share` is the
    `flush_lead_share` metric); None for a dump of an older tree."""
    (_t0, first), (_t1, last) = ctx.phase.samples[0], ctx.phase.samples[-1]
    a, b = (_dig(s, "engine.flush_ahead") for s in (first, last))
    if a is None or b is None:
        return None
    blocks = b["blocks"] - a["blocks"]
    lead_s = b["lead_s"] - a["lead_s"]
    share = counter_share(ctx, "engine.flush_ahead.lead_s")
    return {"blocks": blocks, "lead_s": round(lead_s, 6),
            "lead_mean_s": round(lead_s / blocks, 6) if blocks else None,
            "lead_share": None if share is None else round(share, 3)}


def report(dump: dict, capture: str | None = None) -> dict:
    stats = [s for _t, s in dump["samples"]]
    ctx = SimpleNamespace(phase=SimpleNamespace(
        w0=dump["w0"], w1=dump["w1"], records=dump["records"],
        samples=dump["samples"], stats_start=stats[0], stats_end=stats[-1]))
    out = {"interval_p99_s": tail.interval_p99_s(ctx),
           "wire_excess_ms": tail.wire_excess_ms(ctx),
           "tail_in": {p: tail.tail_in(ctx, p) for p in tail.PARTS},
           "tail_clipped": tail.tail_clipped(ctx),
           "tail_admissions": tail.tail_admissions(ctx),
           "read_exact_share": tail.read_exact_share(ctx),
           "prefill_tok_per_device_s": tail.prefill_tok_per_device_s(ctx),
           "stall_count": tail.stall_count(ctx),
           "stall_longest_s": tail.stall_longest_s(ctx),
           "stalls": _dig(stats[-1], "engine.stalls")}
    # A second observer in another process: the harness polls `stats`
    # (provider -> host's reader thread; neither the engine thread nor the
    # device) on a fixed period, so a poll that came back late across a
    # stall says the freeze was wider than the engine thread.
    polls = [t for t, _s in dump["samples"]]
    for stall in (out["stalls"] or {}).get("recent", []):
        t0 = stall["t"] - stall["wall_s"]
        stall["poll_gap_s"] = max(
            (b - a for a, b in zip(polls, polls[1:])
             if a < stall["t"] and b > t0), default=None)
    recs = tail.records(ctx)
    if not recs:
        return out
    ivs = tail.intervals(recs, dump["w0"], dump["w1"])
    out["intervals"] = len(ivs)
    # What the clients' gap p99 is held against: since PR 38 a block's
    # events leave at its read, so the plain interval, beside the waits
    # they were spared; before, the rebuilt "block read -> first
    # admission read" intervals.
    out["client_gap_p99_s"] = client.gap_percentile_s(ctx, 99)
    ahead = flush_ahead(ctx)
    if ahead is not None:
        out["flush_ahead"] = ahead
    else:
        out["leave_p99_s"] = leave_p99_s(recs, ivs)
    hist: dict[int, int] = {}
    for iv in ivs:
        n = len(iv["admissions"])
        hist[n] = hist.get(n, 0) + 1
    out["admissions_per_interval"] = {str(k): hist[k] for k in sorted(hist)}
    out["prefill_rates"] = prefill_rates(recs, dump["w0"], dump["w1"])
    out["tail"] = [
        {"s": round(iv["s"], 4), "seq": iv["block"]["seq"],
         "block_s": iv["block"]["device_s"],
         "block_exact": iv["block"]["exact"],
         "admissions": [[a["kind"], a["rows"], a["bucket"],
                         tail.admitted_s(a), a["exact"]]
                        for a in iv["admissions"]]}
        for iv in tail.tail(ivs)]
    if capture:
        from jax.profiler import ProfileData

        out["join"] = join(recs, capture_entries(
            ProfileData.from_file(find_xplane(capture))))
    return out


def main(argv: list[str]) -> int:
    if len(argv) not in (2, 3):
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[1]) as fh:
        dump = json.load(fh)
    print(json.dumps(report(dump, argv[2] if len(argv) > 2 else None)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
