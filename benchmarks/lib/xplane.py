"""Reduce a `jax.profiler` trace (`*.xplane.pb`) to the numbers the benchmark
reports. Run as a script it prints one JSON object; `readers/xplane.py` reads
that object.

    python benchmarks/lib/xplane.py <trace dir or .xplane.pb> [decode_program]

What it computes, per the `on-chip-measurement` guide:

- `busy_s`: per device plane, the union of the intervals in which an XLA op
  ran, averaged over the device planes; `window_s`: the traced window, from
  the earliest to the latest event of any plane that reported one.
- `ops`: device seconds by op (summed over chips, divided by the chip count,
  so it is a per-chip time), labelled `name shape` from the HLO line the trace
  names the event by. `while` / `conditional` / `call` are left out: their
  bodies' ops are on the same line, and counting both would count twice.
- `programs`: device seconds and run count by XLA module (jitted program).
- `collective_s`: per-chip seconds inside all-reduce / all-gather /
  reduce-scatter / collective-permute / all-to-all ops.
- `idle_gaps`: the longest gaps of the busy union on the first device plane,
  each attributed to the host span that covers most of it: the innermost
  (shortest) host event that overlaps at least half of the gap. Host events
  that last longer than half the traced window are passed over — the thread
  that runs the capture sleeps through the whole window and would otherwise
  "explain" every gap (PR 22's ledger shows exactly that: five gaps, all
  `time.sleep`).

This module imports only `jax.profiler.ProfileData`, which reads the file and
touches no device. It is run in a process of its own after the engine host
has exited, with `JAX_PLATFORMS=cpu`.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
MAX_HOST_EVENTS = 400_000   # longest-first cut of the host events kept
MIN_HOST_EVENT_S = 50e-6


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def union(intervals: list[tuple[float, float]]
          ) -> tuple[float, list[tuple[float, float]]]:
    """(covered length, merged intervals) of [start, end) intervals."""
    merged: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


CONTAINERS = {"while", "conditional", "call"}


def parse_op(text: str) -> tuple[str, str, str]:
    """(name, first result shape, opcode) of an "XLA Ops" event. The TPU
    trace names such an event by its whole HLO line —
    `%fusion.259 = bf16[128,14336]{1,0:T(8,128)(2,1)} fusion(...), kind=...`
    — and a tuple-shaped result opens with `(`. The benchmark labels an op
    `name shape`: `fusion.259 bf16[128,14336]`."""
    m = re.match(r"%?(\S+) = (.*)$", text, re.S)
    if not m:
        return text[:60], "", ""
    name, rest = m.group(1), m.group(2)
    shape = re.search(r"[a-z]+[0-9]*\[[^\]]*\]", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = re.sub(r"^\S+", "", rest, count=1)
    op = re.match(r"\s*([A-Za-z][\w\-]*)\(", rest)
    return name, shape.group(0) if shape else "", op.group(1) if op else ""


def attribute_gap(gap: tuple[float, float], host_events: list[tuple],
                  window_s: float) -> str:
    """Name of the innermost host span that overlaps ≥ half of `gap`."""
    g0, g1 = gap
    need = 0.5 * (g1 - g0)
    best = None
    for s, e, name in host_events:
        if e - s > 0.5 * window_s:
            continue
        if min(e, g1) - max(s, g0) >= need:
            if best is None or (e - s) < best[0]:
                best = (e - s, name)
    return best[1] if best else "unattributed"


def reduce_profile(data, decode_program: str = "") -> dict:
    """`data` is a jax.profiler.ProfileData (or anything shaped like one:
    `.planes` → `.name`, `.lines` → `.name`, `.events` → `.name`,
    `.start_ns`, `.duration_ns`, `.stats`)."""
    device_busy: list[float] = []
    ops: dict[str, float] = {}
    programs: dict[str, list[float]] = {}
    collective = 0.0
    t_min, t_max = None, None
    first_gaps: list[tuple[float, float]] = []
    host_events: list[tuple[float, float, str]] = []
    n_dev = 0

    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if is_device:
            n_dev += 1
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                intervals = []
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    intervals.append((s, e))
                    name, shape, opcode = parse_op(ev.name)
                    if opcode in CONTAINERS:
                        continue  # its body's ops are on this line too
                    label = f"{name} {shape}".strip()
                    ops[label] = ops.get(label, 0.0) + (e - s)
                    if COLLECTIVE.search(opcode) or COLLECTIVE.search(name):
                        collective += e - s
                if intervals:
                    busy, merged = union(intervals)
                    device_busy.append(busy)
                    lo, hi = merged[0][0], merged[-1][1]
                    t_min = lo if t_min is None else min(t_min, lo)
                    t_max = hi if t_max is None else max(t_max, hi)
                    if not first_gaps:
                        first_gaps = [(a[1], b[0])
                                      for a, b in zip(merged, merged[1:])]
            elif is_device and line.name == MODULES_LINE:
                for ev in line.events:
                    name = re.sub(r"\(\d+\)$", "", ev.name)
                    rec = programs.setdefault(name, [0.0, 0])
                    rec[0] += ev.duration_ns * 1e-9
                    rec[1] += 1
            elif not is_device and plane.name.startswith("/host"):
                for ev in line.events:
                    d = ev.duration_ns * 1e-9
                    if d >= MIN_HOST_EVENT_S:
                        s = ev.start_ns * 1e-9
                        host_events.append(
                            (s, s + d,
                             f"{line.name or 'thread'}:{ev.name}"))

    if not device_busy:
        return {"devices": n_dev, "busy_s": 0.0, "window_s": 0.0,
                "ops": [], "programs": {}, "collective_s": 0.0,
                "idle_gaps": [], "decode": None}

    chips = len(device_busy)
    window_s = t_max - t_min
    if len(host_events) > MAX_HOST_EVENTS:
        host_events.sort(key=lambda x: x[0] - x[1])
        del host_events[MAX_HOST_EVENTS:]
    longest = sorted(first_gaps, key=lambda g: g[0] - g[1])[:10]
    gaps = [[attribute_gap(g, host_events, window_s), g[1] - g[0]]
            for g in longest]
    decode = None
    if decode_program:
        hits = [(n, v) for n, v in programs.items() if decode_program in n]
        if hits:
            decode = {"seconds": sum(v[0] for _, v in hits) / chips,
                      "runs": sum(v[1] for _, v in hits) / chips,
                      "names": sorted(n for n, _ in hits)}
    return {
        "devices": chips,
        "busy_s": sum(device_busy) / chips,
        "window_s": window_s,
        "ops": sorted(([n, s / chips] for n, s in ops.items()),
                      key=lambda x: -x[1])[:40],
        "programs": {n: [v[0] / chips, v[1] / chips]
                     for n, v in sorted(programs.items(),
                                        key=lambda kv: -kv[1][0])[:20]},
        "collective_s": collective / chips,
        "idle_gaps": gaps,
        "decode": decode,
    }


def main(argv: list[str]) -> int:
    from jax.profiler import ProfileData

    path = find_xplane(argv[1])
    data = ProfileData.from_file(path)
    out = reduce_profile(data, argv[2] if len(argv) > 2 else "")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
