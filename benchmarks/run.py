"""Run one cell of the benchmark.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It starts the in-process SymmetryServer and the
provider as its own OS process (whose engine host is the only process that
touches JAX), connects the clients, runs the warm traffic, measures for
`--seconds`, prints ONE JSON line last on stdout (`correct`, `attempted`,
`failed`, `metrics`, `device`, `breakdown` when traced, and last `compared`:
every number `correct` rests on beside its limit, which are also the last
lines on stderr), tears everything down and leaves no process behind. The
line before it is the `setup_s` split.

It exits non-zero and prints no result when the engine host found no TPU
(or fewer chips than the cell asks for), when a phase failed, or when the
program under test is not beside it.

A cell is data: its `BENCHMARK.json` entry names a configuration file
(`configs/`), a traffic file (`traffic/`) and, per metric, a reader file
(`end_to_end/`, `layer_metrics/`). Nothing in this file knows a cell, a
configuration, a traffic mix or a metric by name.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, CHECKOUT)

from lib import harness, window  # noqa: E402
from lib.harness import BenchFailure, log  # noqa: E402


METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


@dataclass
class RunContext:
    """What the readers read."""

    cell: harness.Cell
    phase: harness.Phase
    setup_s: float
    device: dict
    trace: dict | None = None


def metric_entries(cell: harness.Cell, group: str) -> list[dict]:
    """The manifest's metrics of `group` that this cell reports: all that
    carry no `workloads` key, and those that list the cell."""
    return [m for m in cell.manifest[group]
            if "workloads" not in m or cell.name in m["workloads"]]


def read_metric(cell: harness.Cell, group: str, entry: dict,
                ctx: RunContext) -> float | None:
    """Find `<group>/<name>.json`, call the reader it names."""
    rel = os.path.join(METRIC_DIRS[group], entry["name"] + ".json")
    path = os.path.join(cell.root, rel)
    if not os.path.exists(path):
        path = os.path.join(BENCH_DIR, rel)
    spec = harness.load_json(path)
    module, func = spec["reader"].rsplit(".", 1)
    fn = getattr(importlib.import_module(f"readers.{module}"), func)
    value = fn(ctx, **(spec.get("params") or {}))
    return None if value is None else float(value)


def collect_metrics(cell: harness.Cell, group: str,
                    ctx: RunContext) -> dict:
    out = {}
    for entry in metric_entries(cell, group):
        value = read_metric(cell, group, entry, ctx)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def device_block(startup: dict, trace: dict | None) -> dict:
    """The device as the ENGINE HOST's JAX reported it. `memory_peak_bytes`
    is the fullest chip's bytes in use once the weights, the cache and every
    program were resident — the program reads `memory_stats()` only then
    (PERF.md, Open questions)."""
    dev = startup.get("device") or {}
    hbm = dev.get("hbm") or []
    out = {"platform": dev.get("platform"), "kind": dev.get("device_kind"),
           "count": dev.get("device_count"),
           "memory_peak_bytes": max((h["bytes_in_use"] for h in hbm),
                                    default=0)}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def compare(phase: harness.Phase, probe_texts: list[str],
            probe_tokens: int) -> list[tuple[str, float, float, str]]:
    """Every number a run compares, as (name, value, limit, what it means
    when the value is over the limit). Conservation and self-consistency:
    what a run can show about the outputs being right without a second
    forward pass (PERF.md §7 says what that leaves out)."""
    done = [r for r in phase.records if not r.get("error")]
    short, empty, first_short = 0, 0, None
    for r in done:
        exact = r["tokens"] == r["max_new"]
        # a sampled EOS ends a stream early, and says so — as the FIRST
        # token it leaves a stream of 0 tokens, which a seed in some tens
        # draws once; many of them are a program that stopped answering
        stopped = (r["finish"] == "stop"
                   and 0 <= (r["tokens"] or 0) <= r["max_new"])
        if stopped and not r["tokens"]:
            empty += 1
        if not (exact or stopped):
            short += 1
            first_short = first_short or r
    out = [("short_streams", short, 0,
            first_short and f"a stream asked for {first_short['max_new']} "
            f"tokens and delivered {first_short['tokens']} (finish "
            f"{first_short['finish']})"),
           ("empty_stop_streams", empty, max(0, len(done) - 1) // 100,
            f"{empty} of {len(done)} completed streams ended `stop` with 0 "
            f"tokens (limit: under 1%)"),
           ("open_streams",
            sum(r.get("t_done") is None for r in phase.records), 0,
            "a stream was left open")]
    wire = sum(r["tokens"] or 0 for r in done) + probe_tokens
    engine = phase.stats_end.get("engine") or {}
    if len(done) == len(phase.records):
        for name, who, counted in (
                ("wire_host_token_gap", "host", engine.get("tokens")),
                ("wire_provider_token_gap", "provider",
                 phase.stats_end.get("tokens_out"))):
            out.append((name, abs(wire - (counted or 0)), 0,
                        f"the wire carried {wire} tokens, the {who} "
                        f"counted {counted}"))
    sup = engine.get("supervisor") or {}
    out.append(("host_respawns", (sup.get("restarts") or 0)
                + (sup.get("respawn_failures") or 0), 0,
                f"the supervisor respawned the host: {sup}"))
    out.append(("greedy_probe_mismatch",
                int(len(set(probe_texts)) != 1 or not probe_texts[0]), 0,
                f"two identical greedy requests differ: {probe_texts!r}"))
    out.append(("in_flight_after_drain",
                phase.stats_end.get("in_flight") or 0, 0,
                f"{phase.stats_end.get('in_flight')} requests still in "
                f"flight after the drain"))
    return out


def over_limit(compared: list[tuple[str, float, float, str]]) -> list[str]:
    """The reasons a run is not correct; empty means correct."""
    return [text for _, value, limit, text in compared if value > limit]


def check_correct(phase: harness.Phase, probe_texts: list[str],
                  probe_tokens: int) -> list[str]:
    return over_limit(compare(phase, probe_texts, probe_tokens))


def refusal(device: dict, cell: harness.Cell) -> str | None:
    """Why no result may be printed for the device the host reported."""
    if device["platform"] != "tpu":
        return (f"the engine host's platform is {device['platform']!r}, "
                f"not tpu: no result is printed for it")
    if device["count"] != cell.chips:
        return (f"the cell asks for {cell.chips} chips, the engine host "
                f"has {device['count']}")
    return None


async def serve(cell: harness.Cell, serving: harness.Serving, args):
    """Everything that needs the system up: returns (phase, probe texts,
    probe tokens); the system is down again when this returns."""
    fleet = harness.Fleet(harness.fleet_size(cell))
    async with serving:
        try:
            await fleet.spawn()
            await serving.registered()
            t_probe = time.monotonic()
            texts, tokens = await serving.greedy_probe()
            serving.timings["probe_s"] = time.monotonic() - t_probe
            phase = await serving.run_phase(
                fleet, args.seed, float(args.seconds), bool(args.trace))
        finally:
            await fleet.kill()
    return phase, texts, tokens


async def run(args) -> tuple[dict, dict]:
    cell = harness.load_cell(args.workload, args.manifest)
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and not cell.config.get("cpu_rehearsal")):
        # The engine host obeys a CPU pinned by name, so the verdict is
        # known before anything is built — and a 7B model is not built on a
        # CPU to reach it.
        raise BenchFailure(
            "JAX_PLATFORMS=cpu pins the engine host to the CPU: a device "
            "metric is never taken there")
    serving = harness.Serving(cell, T_PROCESS_START)
    phase = None
    try:
        phase, probe_texts, probe_tokens = await serve(cell, serving, args)
        startup = (phase.stats_end.get("engine") or {}).get("startup") or {}
        trace = None
        if args.trace:
            if not phase.trace_path:
                raise BenchFailure(f"the device trace was not captured: "
                                   f"{phase.trace_error}")
            trace = harness.reduce_trace(
                phase.trace_path, cell.config.get("decode_program", ""))
        device = device_block(startup, trace)
        refused = refusal(device, cell)
        if trace is not None and trace["busy_s"] <= 0 and refused is None:
            raise BenchFailure("the trace shows no device operation")
        setup_s = phase.w0 - T_PROCESS_START
        ctx = RunContext(cell, phase, setup_s, device, trace)
        compared = compare(phase, probe_texts, probe_tokens) + [
            ("provider_exit_code", 255 if serving.provider_rc is None
             else abs(serving.provider_rc), 0,
             f"the provider exited with code {serving.provider_rc} on "
             f"drain"),
            ("orphan_processes", len(serving.orphans), 0,
             f"children outlived the provider: {serving.orphans}")]
        why = over_limit(compared)
        for reason in why:
            log(f"NOT CORRECT: {reason}")
        due = window.due_in_window(phase.records, phase.w0, phase.w1)
        n_failed = sum(window.failed(r) for r in due)
        if refused is not None:
            # A rehearsal off the chip still walks every reader, and says
            # which found something — never a value under a device name.
            names = {g: sorted(collect_metrics(cell, g, ctx))
                     for g in ("end_to_end", "per_layer")}
            log(f"rehearsal: correct={not why} attempted={len(due)} "
                f"failed={n_failed} readers with a value: {names}")
            raise BenchFailure(refused)
        result = {
            "correct": not why, "attempted": len(due), "failed": n_failed,
            "metrics": collect_metrics(
                cell, "per_layer" if args.trace else "end_to_end", ctx),
            "device": device,
        }
        if trace is not None:
            result["breakdown"] = {
                "device_ops": [[n.replace(" ", "_"), s]
                               for n, s in trace["ops"][:10]],
                "idle_gaps": trace["idle_gaps"][:5]}
        # last in the line: every number compared, beside its limit
        result["compared"] = {name: [value, limit]
                              for name, value, limit, _ in compared}
        split = {"setup_s": setup_s, **serving.timings, **phase.timings,
                 "build_s": startup.get("build_s"),
                 "warmup_s": startup.get("warmup_s"),
                 "workload": cell.name, "seed": args.seed,
                 "window_s": phase.w1 - phase.w0,
                 "requests": len(phase.records),
                 "offered": len(phase.offered)}
        return split, result
    except BaseException:
        tail = serving.log_tail()
        if tail:
            print(f"--- provider + engine host log tail ---\n{tail}",
                  file=sys.stderr)
        raise
    finally:
        if args.dump and phase is not None:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(
                    args.dump, f"{args.workload}.{args.seed}.json"),
                    "w") as fh:
                json.dump({"workload": args.workload, "seed": args.seed,
                           "setup_s": phase.w0 - T_PROCESS_START,
                           "w0": phase.w0, "w1": phase.w1,
                           "records": phase.records,
                           "samples": phase.samples}, fh)
        serving.cleanup()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="another BENCHMARK.json (the tests' tiny cells)")
    ap.add_argument("--dump", default=None,
                    help="write the run's client records and stats samples "
                         "into this directory (for looking at a run: "
                         "tools/read_tail.py, benchmarks/admit.py)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(CHECKOUT, "symmetry_tpu")):
        print("benchmarks/run.py: the program under test (symmetry_tpu/) is "
              "not in this checkout", file=sys.stderr)
        return 1
    try:
        split, result = asyncio.run(run(args))
    except BenchFailure as exc:
        print(f"benchmarks/run.py: FAIL: {exc}", file=sys.stderr)
        return 1
    for name, (value, limit) in result["compared"].items():
        print(f"compared: {name} {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({"setup_split": split}))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
