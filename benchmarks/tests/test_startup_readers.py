"""`readers/startup.py`: the eight parts are differences of nine consecutive
stamps on one clock, so over any context they sum to `setup_s`; a part whose
stamp the program did not record reads None, never 0."""

from types import SimpleNamespace as NS

import pytest

from readers import startup

T0 = 5000.125   # the harness's own start, on CLOCK_MONOTONIC
PROVIDER = [["provider.process", 5000.875, 5001.5, None],
            ["provider.backend", 5001.5, 5047.25, None],
            ["backend.spawn", 5001.5625, 5001.625, "provider.backend"],
            ["backend.ready", 5001.625, 5047.125, "provider.backend"],
            ["backend.clock", 5047.125, 5047.25, "provider.backend"],
            ["provider.listen", 5047.25, 5047.3125, None],
            ["provider.dht", 5047.3125, 5047.375, None],
            ["provider.server", 5047.375, 5047.5, None],
            ["registered", 5047.5, 5047.5, None]]
HOST = [["host.process", 5001.625, 5006.0, None],
        ["host.config", 5006.0, 5006.0625, None],
        ["build.devices", 5006.0625, 5009.5, None],
        ["build.params", 5009.5, 5019.75, None],
        ["build.state", 5019.75, 5020.25, None],
        ["warmup", 5020.25, 5046.75, None],
        ["host.scheduler", 5046.75, 5047.0, None],
        ["ready", 5047.0, 5047.0, None]]
WARMUP = {"programs": 41, "wall_s": 26.5, "compile_s": 9.25,
          "retrieval_s": 7.5, "run_s": 12.0, "cache_hits": 40,
          "cache_misses": 0, "slowest": []}
WANT = {"spawn": 0.75, "provider_boot": 0.75, "host_boot": 4.4375,
        "devices": 3.4375, "build": 10.75, "warmup": 26.5,
        "register": 0.75, "traffic": 12.0}


def context(provider=PROVIDER, host=HOST, warmup=WARMUP, w0=5059.5):
    stats = {"startup": {"timeline": provider, "origin": "kernel"},
             "engine": {"startup": {"timeline": host, "origin": "kernel",
                                    "warmup": warmup}}}
    return NS(setup_s=w0 - T0, phase=NS(w0=w0, stats_end=stats))


def test_the_eight_parts_tile_setup_s_exactly():
    ctx = context()
    parts = {p: startup.part_s(ctx, p) for p in startup.PARTS}
    assert parts == WANT          # binary fractions: no rounding to allow
    assert sum(parts.values()) == ctx.setup_s == 59.375


def test_they_tile_it_whatever_the_stamps_are():
    # odd decimals and a clock far from zero: the sum telescopes
    shift = 108201.892300463
    move = lambda rows: [[n, a * 1.000001 + shift, b * 1.000001 + shift, p]
                         for n, a, b, p in rows]
    w0 = 5059.5 * 1.000001 + shift
    stats = {"startup": {"timeline": move(PROVIDER)},
             "engine": {"startup": {"timeline": move(HOST)}}}
    ctx = NS(setup_s=w0 - (T0 * 1.000001 + shift),
             phase=NS(w0=w0, stats_end=stats))
    total = sum(startup.part_s(ctx, p) for p in startup.PARTS)
    assert total == pytest.approx(ctx.setup_s, abs=1e-6)


@pytest.mark.parametrize("gone,silent", [
    ("provider.process", {"spawn", "provider_boot"}),
    ("host.process", {"provider_boot", "host_boot"}),
    ("build.devices", {"host_boot", "devices"}),
    ("build.params", {"devices", "build"}),
    ("warmup", {"build", "warmup", "register"}),
    ("registered", {"register", "traffic"})])
def test_a_missing_stamp_reads_none_never_zero(gone, silent):
    ctx = context(provider=[r for r in PROVIDER if r[0] != gone],
                  host=[r for r in HOST if r[0] != gone])
    for part in startup.PARTS:
        value = startup.part_s(ctx, part)
        if part in silent:
            assert value is None
        else:
            assert value == WANT[part]


@pytest.mark.parametrize("stats", [
    {}, {"engine": None}, {"engine": {"startup": {}}},
    {"startup": {"timeline": None}, "engine": {"startup": {
        "timeline": "not a list", "warmup": None}}}])
def test_a_program_without_a_timeline_yields_nothing_and_raises_nothing(
        stats):
    # the parent commit of PR 56, or a host that is down: every metric of
    # the family is left out of the line
    ctx = NS(setup_s=60.0, phase=NS(w0=5060.0, stats_end=stats))
    assert [startup.part_s(ctx, p) for p in startup.PARTS] == [None] * 8
    assert startup.warmup_total(ctx, "compile_s") is None
    assert startup.warmup_total(ctx, "cache_misses") is None


def test_the_warm_ups_totals_and_a_count_of_zero_is_a_value():
    ctx = context()
    assert startup.warmup_total(ctx, "compile_s") == 9.25
    assert startup.warmup_total(ctx, "run_s") == 12.0
    assert startup.warmup_total(ctx, "cache_misses") == 0.0
    assert startup.warmup_total(ctx, "no_such_total") is None


def test_every_entry_of_the_family_has_its_file_and_names_a_part():
    import json
    import os

    import run

    manifest = json.load(open(os.path.join(run.CHECKOUT, "BENCHMARK.json")))
    mine = [m for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    assert len(mine) == 11 and all("workloads" not in m for m in mine)
    parts = []
    for entry in mine:
        spec = json.load(open(os.path.join(
            run.BENCH_DIR, "layer_metrics", entry["name"] + ".json")))
        assert spec["reader"].startswith("startup.")
        parts.append(spec["params"].get("part"))
    assert tuple(p for p in parts if p) == startup.PARTS
