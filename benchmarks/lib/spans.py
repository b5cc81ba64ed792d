"""Reduce the PROGRAM'S OWN spans in a `jax.profiler` trace: the `sym.*`
events the served program writes into the capture's host plane
(`symmetry_tpu/utils/trace.py Tracer.phase`, on the clock of the device
rows), set against the device's busy intervals. Run as a module it prints
one JSON object; `readers/spans.py` reads that object.

    cd benchmarks && python -m lib.spans <trace dir or .xplane.pb>

What it computes:

- `window_s`: the part of the capture in which both the device and the
  scheduler's loop can be seen: from the first `sym.sched.<phase>` event's
  start to the last one's end, inside the first device plane's first-to-
  last XLA op. The profiler keeps only events that began AND ended while
  it ran, so the loop phase in progress when the capture starts and the
  one in progress when it stops are not in the file — with phases of
  0.4–0.7 s that is up to a quarter of a 3 s capture, and it is left out
  rather than read as a hole.
- `idle_s`: on the FIRST device plane, the time inside that window in
  which no op ran (the complement of `lib/xplane.py`'s busy union — the
  quantity `device_idle` reports as a share of its own, wider window).
- `idle_in`: that idle time split by what the engine thread was doing (the
  scheduler's loop phases partition its thread's time): seconds of idle
  inside `admit`, `sync`, `process`, `other` (every other phase: dispatch,
  chunks, flush, wait) and `none` (under no phase span — a hole in the
  partition). The five sum to `idle_s`.
- `admit_s` / `admit_busy_s`: wall inside `sym.sched.admit` in the window
  and the part of it during which the device was busy — admission waiting
  for the chip rather than holding it up.
- For a reader who looks at a run: `phase_s`, wall per `sym.*` span name
  inside the capture; `events`, how many `sym.*` events there were;
  `sched_lines`, how many thread lines carry `sym.sched.` events (one: the
  engine thread's); `sched_cover_s` and `sched_overlap_s`, the union of
  the loop phases and what their lengths sum to beyond it (zero when they
  tile the line without overlap).

A trace with no `sym.sched.` event (a program from before these spans)
reduces to `None`. Like `lib/xplane.py` this imports only
`jax.profiler.ProfileData` and runs in a process of its own pinned to the
CPU.
"""

from __future__ import annotations

import json
import sys

from .xplane import DEVICE_PLANE, OPS_LINE, find_xplane, union

PREFIX = "sym."
PHASE_PREFIX = "sym.sched."
NAMED = ("admit", "sync", "process")

Intervals = list[tuple[float, float]]


def overlap(a: Intervals, b: Intervals) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(intervals: Intervals, lo: float, hi: float) -> Intervals:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce_spans(data) -> dict | None:
    """`data` is a jax.profiler.ProfileData, or anything shaped like one
    (see `lib/xplane.py reduce_profile`)."""
    busy: Intervals | None = None
    spans: dict[str, Intervals] = {}
    sched_lines = 0
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            if busy is None:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        _, busy = union([
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                on_line = False
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.setdefault(ev.name.split("#", 1)[0], []).append(
                            (s, s + ev.duration_ns * 1e-9))
                        on_line |= ev.name.startswith(PHASE_PREFIX)
                sched_lines += on_line
    phases = {name[len(PHASE_PREFIX):]: union(ivs)[1]
              for name, ivs in spans.items() if name.startswith(PHASE_PREFIX)}
    if not phases:
        return None
    every = union([iv for ivs in phases.values() for iv in ivs])
    out = {"events": sum(len(v) for v in spans.values()),
           "sched_lines": sched_lines, "sched_cover_s": every[0],
           "sched_overlap_s": sum(
               e - s for name, ivs in spans.items()
               if name.startswith(PHASE_PREFIX) for s, e in ivs) - every[0],
           "phase_s": {name: sum(e - s for s, e in ivs)
                       for name, ivs in sorted(spans.items())},
           "window_s": None, "idle_s": None, "idle_in": None,
           "admit_s": None, "admit_busy_s": None}
    if not busy:
        return out
    lo = max(busy[0][0], every[1][0][0])
    hi = min(busy[-1][1], every[1][-1][1])
    busy = clip(busy, lo, hi)
    if not busy:
        return out
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    idle_s = sum(e - s for s, e in idle)
    covered = overlap(idle, every[1])
    idle_in = {name: overlap(idle, phases.get(name, [])) for name in NAMED}
    idle_in["other"] = covered - sum(idle_in.values())
    idle_in["none"] = idle_s - covered
    admit = clip(phases.get("admit", []), lo, hi)
    out.update(window_s=hi - lo, idle_s=idle_s, idle_in=idle_in,
               admit_s=sum(e - s for s, e in admit),
               admit_busy_s=overlap(busy, admit))
    return out


def main(argv: list[str]) -> int:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(argv[1]))
    print(json.dumps(reduce_spans(data)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
