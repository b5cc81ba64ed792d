"""The start-up family: `setup_s` split along the program's own start-up
timeline (PR 56). Each process stamps its start-up on CLOCK_MONOTONIC — the
clock `run.py`'s `T_PROCESS_START` and `w0` are read from — and freezes the
stamps into `stats.startup.timeline` (the provider) and
`stats.engine.startup.timeline` (the engine host): rows `[name, t0, t1,
parent]`. The eight parts are the differences of NINE consecutive stamps on
that one clock, so they sum to the run's `setup_s`:

    T0           the harness's own start: `w0 - setup_s`
    spawn        → the provider process's start   (`provider.process` t0)
    provider_boot→ the engine host process's start (`host.process` t0)
    host_boot    → the first touch of the backend  (`build.devices` t0)
    devices      → the weights' build begins       (`build.params` t0)
    build        → warm-up begins                  (`warmup` t0)
    warmup       → warm-up ends                    (`warmup` t1)
    register     → the server acknowledged the join (`registered`)
    traffic      → `w0`, the window's start

A part whose stamp the program did not record reads None (a program from
before the timeline, a provider that never registered), never 0.
`warmup_total` reads one total of the warm-up record, `startup.warmup`:
`compile_s`, `run_s`, `cache_misses`.
"""

from __future__ import annotations

from readers.stats import _dig

PARTS = ("spawn", "provider_boot", "host_boot", "devices", "build",
         "warmup", "register", "traffic")


def _stamps(stats: dict | None, path: str) -> dict:
    """The timeline at `path` as {name: (t0, t1)}; {} where there is none."""
    try:
        return {row[0]: (float(row[1]), float(row[2]))
                for row in _dig(stats, path)}
    except (TypeError, ValueError, IndexError):
        return {}


def boundaries(ctx) -> list[float | None]:
    """The nine stamps, oldest first; None where one is missing."""
    provider = _stamps(ctx.phase.stats_end, "startup.timeline")
    host = _stamps(ctx.phase.stats_end, "engine.startup.timeline")

    def at(rows: dict, name: str, end: int = 0) -> float | None:
        return rows[name][end] if name in rows else None

    return [ctx.phase.w0 - ctx.setup_s,
            at(provider, "provider.process"),
            at(host, "host.process"),
            at(host, "build.devices"),
            at(host, "build.params"),
            at(host, "warmup"),
            at(host, "warmup", 1),
            at(provider, "registered"),
            ctx.phase.w0]


def part_s(ctx, part: str) -> float | None:
    """Seconds between the two stamps that bound `part`."""
    i = PARTS.index(part)
    t0, t1 = boundaries(ctx)[i:i + 2]
    return None if t0 is None or t1 is None else t1 - t0


def warmup_total(ctx, field: str) -> float | None:
    """One total of the warm-up record (`stats.engine.startup.warmup`)."""
    value = _dig(ctx.phase.stats_end, f"engine.startup.warmup.{field}")
    return None if value is None else float(value)
