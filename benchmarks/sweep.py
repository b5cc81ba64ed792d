"""Find an open-loop cell's knee: several arrival rates in one process, after
one set-up. Not called by any run; its table is quoted in PERF.md and in the
traffic file.

    python benchmarks/sweep.py --workload <cell> --rates 6,9,12,15,18 \
        [--seconds 20] [--seed 1] [--out chiprun_out/sweep.json]

For each rate it runs the cell's warm phase and a window of `--seconds`, then
drains. A rate is SUSTAINED when completions kept up with arrivals (every
request due in the window completed, none failed) and the backlog at the
window's end (requests in flight at the provider) is no higher than at its
middle plus what one second of arrivals adds. The knee is the highest
sustained rate of those swept. The cell then runs at 0.8 × knee, written
into the traffic file as a number.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from lib import harness, window  # noqa: E402
from lib.harness import BenchFailure, log  # noqa: E402


def row(rate: float, phase: harness.Phase) -> dict:
    due = window.due_in_window(phase.records, phase.w0, phase.w1)
    ttfts, missing = window.window_ttfts(phase.records, phase.w0, phase.w1)
    mid = phase.samples[len(phase.samples) // 2][1]
    end = phase.samples[-1][1]

    def backlog(s: dict) -> int:
        return int(s.get("in_flight") or 0)

    sustained = (missing == 0 and not any(window.failed(r) for r in due)
                 and backlog(end) <= backlog(mid) + rate)
    return {
        "rate_per_s": rate, "offered": len(due),
        "completed_in_window": sum(
            1 for r in due if not window.failed(r) and r["t_done"] < phase.w1),
        "failed": sum(window.failed(r) for r in due),
        "in_flight_mid": backlog(mid), "in_flight_end": backlog(end),
        "queue_depth_mid": (mid.get("engine") or {}).get("queue_depth"),
        "queue_depth_end": (end.get("engine") or {}).get("queue_depth"),
        "occupancy_end": (end.get("engine") or {}).get("occupancy"),
        "ttft_p50_s": window.percentile(ttfts, 50),
        "ttft_p95_s": window.percentile(ttfts, 95),
        "gap_p99_s": window.percentile(
            window.window_gaps(phase.records, phase.w0, phase.w1), 99),
        "out_tok_s": window.window_tokens(
            phase.records, phase.w0, phase.w1) / (phase.w1 - phase.w0),
        "sustained": sustained,
    }


async def sweep(args) -> dict:
    cell = harness.load_cell(args.workload, args.manifest)
    if cell.traffic["loop"] != "open":
        raise BenchFailure("a sweep needs an open-loop traffic file")
    rates = [float(r) for r in args.rates.split(",")]
    serving = harness.Serving(cell, T_PROCESS_START)
    rows = []
    try:
        async with serving:
            await serving.registered()
            await serving.greedy_probe()
            for rate in rates:
                fleet = harness.Fleet(harness.fleet_size(cell))
                try:
                    await fleet.spawn()
                    phase = await serving.run_phase(
                        fleet, args.seed, args.seconds, False, rate=rate)
                finally:
                    await fleet.kill()
                rows.append(row(rate, phase))
                log(f"sweep: {json.dumps(rows[-1])}")
            startup = (phase.stats_end.get("engine") or {}).get("startup")
    except BaseException:
        print(serving.log_tail(), file=sys.stderr)
        raise
    finally:
        serving.cleanup()
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    return {"workload": cell.name, "seconds": args.seconds,
            "seed": args.seed, "device": (startup or {}).get("device"),
            "knee_per_s": max(sustained) if sustained else None,
            "rows": rows}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    try:
        table = asyncio.run(sweep(args))
    except BenchFailure as exc:
        print(f"benchmarks/sweep.py: FAIL: {exc}", file=sys.stderr)
        return 1
    text = json.dumps(table, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
