"""`readers/tail.py`: the window's intervals from the scheduler's read
records, their tail, its split, the prefill rate and the stall metrics — on
synthetic `stats` samples; `tools/read_tail.py`: the join with a capture on
a synthetic trace, and the listing of a dump; and the eleven metric files
walked by `run.py` at `tiny` on the CPU."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest
from conftest import BENCH, CHECKOUT, TESTS

from readers import tail
from tools import read_tail

FIELDS = ["seq", "t", "kind", "rows", "bucket", "tokens", "caused_by",
          "wait_s", "late", "device_s", "exact", "behind", "host_s",
          "cpu_s", "lowerings", "gc_s", "chunks"]
NAMES = ["tail_interval_p99_s", "tail_wire_excess_ms", "tail_in.block",
         "tail_in.prefill", "tail_in.other", "tail_admissions",
         "read_exact_share", "stall_count", "stall_longest_s",
         "tail_clipped", "prefill_tok_per_device_s"]


def rec(seq, t, kind="decode_block", device_s=0.3, exact=True,
        caused_by=None, rows=128, bucket=0):
    return [seq, t, kind, rows, bucket, 100, caused_by, device_s, False,
            device_s, exact, 1, 0.002, 0.002, 0, 0.0, 0]


def steady(n_blocks, t0=100.0, block_s=0.3, prefills=(0.1,), slow=None):
    """`n_blocks` decode blocks, each followed by `prefills` admissions;
    `slow` = {block index: extra seconds nobody's device_s explains}."""
    rows, t, seq, last_block = [], t0, 0, None
    for i in range(n_blocks):
        t += block_s + (slow or {}).get(i, 0.0)
        rows.append(rec(seq, t, device_s=block_s, caused_by=last_block))
        last_block = seq
        seq += 1
        for p in prefills:
            t += p
            rows.append(rec(seq, t, "prefill", p, caused_by=last_block,
                            rows=4, bucket=128))
            seq += 1
    return rows


def ctx_of(rows, w0, w1, *, per_sample=40, stalls=None, gaps=None):
    """A run whose stats samples each hold the LAST `per_sample` records
    read by then (so consecutive samples overlap), one sample a second."""
    def stats_at(t):
        seen = [r for r in rows if r[1] <= t]
        engine = {"reads": {"n": len(seen), "fields": FIELDS,
                            "recent": seen[-per_sample:]}}
        if stalls is not None:
            past = [s for s in stalls if s["t"] <= t]
            engine["stalls"] = {"count": len(past), "recent": past[-8:]}
        return {"engine": engine}

    ticks = [w0 + i for i in range(int(w1 - w0) + 1)]
    samples = [(t, stats_at(t)) for t in ticks]
    records = [{"stamps": [[a, 16], [b, 16]]} for a, b in (gaps or [])]
    return SimpleNamespace(phase=SimpleNamespace(
        w0=w0, w1=w1, samples=samples, stats_start=samples[0][1],
        stats_end=stats_at(w1 + 5.0), records=records))


def test_overlapping_samples_union_by_seq():
    rows = steady(60)
    ctx = ctx_of(rows, 102.0, 122.0)
    recs = tail.records(ctx)
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(set(seqs))
    # every record read from the first sample's horizon on, exactly once
    first = ctx.phase.samples[0][1]["engine"]["reads"]["recent"][0][0]
    assert seqs == list(range(first, len(rows)))
    assert recs[0]["kind"] in ("decode_block", "prefill")


def test_intervals_are_clipped_to_the_window_and_to_the_chain():
    rows = steady(40)          # a block every 0.4 s from t = 100.3
    recs = [dict(zip(FIELDS, r)) for r in rows]
    ivs = tail.intervals(recs, 104.0, 108.0)
    assert ivs and all(104.0 <= iv["block"]["t"] <= 108.0 for iv in ivs)
    assert len(ivs) == 10
    assert all(iv["s"] == pytest.approx(0.4) for iv in ivs)
    assert all(len(iv["admissions"]) == 1 for iv in ivs)
    # an idle boundary (caused_by None) starts a new chain: no interval
    # ends at that block
    cut = [dict(r) for r in recs]
    blocks = [r for r in cut if r["kind"] == "decode_block"]
    blocks[20]["caused_by"] = None
    assert len(tail.intervals(cut, 100.0, 200.0)) == len(blocks) - 2
    # a record the samples missed breaks the chain as well
    holed = [r for r in recs if r["seq"] != 31]
    assert len(tail.intervals(holed, 100.0, 200.0)) == len(blocks) - 2


@pytest.mark.parametrize("n,want", [(20, 3), (60, 3), (100, 5), (161, 9)])
def test_the_tail_is_the_longest_five_percent_at_least_three(n, want):
    ivs = [{"s": float(i)} for i in range(n)]
    worst = tail.tail(ivs)
    assert len(worst) == want
    assert [iv["s"] for iv in worst] == [float(n - 1 - i)
                                         for i in range(want)]


def test_p99_and_wire_excess():
    rows = steady(101, slow={50: 0.5})
    gaps = [(110.0, 110.4)] * 989 + [(120.0, 120.93)] * 11
    ctx = ctx_of(rows, 100.0, 142.0, gaps=gaps)
    # 100 intervals: 99 of 0.4 s, one of 0.9 s -> the 99th percentile by
    # nearest rank is the 99th value
    assert tail.interval_p99_s(ctx) == pytest.approx(0.4)
    rows = steady(101, slow={50: 0.5, 70: 0.5})
    ctx = ctx_of(rows, 100.0, 143.0, gaps=gaps)
    assert tail.interval_p99_s(ctx) == pytest.approx(0.9)
    assert tail.wire_excess_ms(ctx) == pytest.approx(30.0, abs=1e-6)


@pytest.mark.parametrize("prefills,slow,want", [
    ((0.1,), None, (75.0, 25.0, 0.0)),
    ((0.1, 0.2), None, (50.0, 50.0, 0.0)),
    ((), None, (100.0, 0.0, 0.0)),
    ((0.1,), {10: 0.4, 20: 0.4, 30: 0.4}, (37.5, 12.5, 50.0)),
])
def test_the_split_sums_to_100(prefills, slow, want):
    rows = steady(40, prefills=prefills, slow=slow)
    ctx = ctx_of(rows, 100.0, 140.0, per_sample=60)
    parts = [tail.tail_in(ctx, p) for p in ("block", "prefill", "other")]
    assert sum(parts) == pytest.approx(100.0)
    assert parts == pytest.approx(list(want), abs=1e-6)
    assert tail.tail_admissions(ctx) == pytest.approx(len(prefills))


def test_a_bound_that_overshoots_is_cut_to_its_interval():
    recs = [dict(zip(FIELDS, r)) for r in steady(10)]
    for r in recs:
        if r["kind"] == "prefill":
            r["device_s"], r["exact"] = 5.0, False     # a charged estimate
    parts = tail.split(tail.intervals(recs, 0.0, 1e9))
    assert all(v >= 0 for v in parts.values())
    assert sum(parts[p] for p in tail.PARTS) == pytest.approx(9 * 0.4)
    assert parts["other"] == pytest.approx(0.0)
    assert parts["clipped"] == 9                # ... and every cut counted


def test_the_clipped_count_is_of_the_tail_and_ignores_the_stamps_jitter():
    rows = steady(60, slow={20: 0.2})           # interval 20 is the longest
    rows[1][FIELDS.index("device_s")] += 2e-4   # ready stamps, read stamps
    ctx = ctx_of(rows, 100.0, 125.0)
    assert tail.tail_clipped(ctx) == 0
    # the admission inside it was priced by an estimate that overshoots:
    # 0.3 of block + 0.9 claimed in 0.6 s
    rows[39][FIELDS.index("device_s")] = 0.9
    rows[39][FIELDS.index("exact")] = False
    ctx = ctx_of(rows, 100.0, 125.0)
    assert tail.tail_clipped(ctx) == 1
    assert sum(tail.tail_in(ctx, p) for p in tail.PARTS) == (
        pytest.approx(100.0))


def test_a_stalled_read_lands_in_other():
    """The program prices a read whose wait ran past its entry at what the
    entry should have taken (inexact), so a stall is in no `device_s`."""
    rows = steady(40, slow={10: 2.3})           # block 10 read 2.3 s late
    blk = [r for r in rows if r[FIELDS.index("kind")] == "decode_block"][10]
    blk[FIELDS.index("exact")] = False          # device_s stays 0.3
    ctx = ctx_of(rows, 100.0, 140.0, per_sample=60)
    # the tail: 2.7 s + two of 0.4 s = 3.5 s; block 0.9, prefill 0.3
    assert tail.tail_in(ctx, "other") == pytest.approx(100 * 2.3 / 3.5)
    assert tail.tail_in(ctx, "block") == pytest.approx(100 * 0.9 / 3.5)
    assert tail.tail_clipped(ctx) == 0


def test_prefill_tokens_per_device_second():
    rows = steady(40, prefills=(0.1, 0.2))      # 100 tokens each
    ctx = ctx_of(rows, 100.0, 140.0, per_sample=60)
    assert tail.prefill_tok_per_device_s(ctx) == pytest.approx(200 / 0.3)
    # a chunked prompt's record stands for the chunks ahead of it
    for r in rows:
        if r[FIELDS.index("kind")] == "prefill":
            r[FIELDS.index("kind")] = "chunk"
            r[FIELDS.index("chunks")] = 1
    ctx = ctx_of(rows, 100.0, 140.0, per_sample=60)
    assert tail.prefill_tok_per_device_s(ctx) == pytest.approx(200 / 0.6)
    blocks_only = ctx_of(steady(40, prefills=()), 100.0, 140.0,
                         per_sample=60)
    assert tail.prefill_tok_per_device_s(blocks_only) is None


def test_a_final_chunk_stands_for_the_chunks_ahead_of_it():
    """A chunked prompt's record holds ONE chunk's seconds and how many ran
    unread ahead of it: all of them are prefill, not `other`."""
    recs = [dict(zip(FIELDS, r)) for r in steady(3, prefills=(0.05,))]
    # each interval: 0.3 s of block, then three chunks of 0.05 s of which
    # only the last is read
    for i, r in enumerate(recs):
        r["t"] += 0.1 * (i // 2 + (r["kind"] == "prefill"))
        if r["kind"] == "prefill":
            r["kind"], r["chunks"] = "chunk", 2
    parts = tail.split(tail.intervals(recs, 0.0, 1e9))
    assert parts == pytest.approx(
        {"block": 0.6, "prefill": 0.3, "other": 0.0, "clipped": 0},
        abs=1e-9)


def test_read_exact_share_counts_the_windows_reads():
    rows = steady(40)
    for r in rows[::4]:
        r[FIELDS.index("exact")] = False
    ctx = ctx_of(rows, 100.0, 140.0, per_sample=60)
    assert tail.read_exact_share(ctx) == pytest.approx(75.0)


def test_stall_metrics():
    rows = steady(40)
    stalls = [{"t": 95.0, "excess_s": 9.0, "phase": "sync"},
              {"t": 104.2, "excess_s": 1.9, "phase": "sync"},
              {"t": 110.7, "excess_s": 4.7, "phase": "dispatch"}]
    ctx = ctx_of(rows, 100.0, 116.0, stalls=stalls)
    assert tail.stall_count(ctx) == 2           # the one before is not its
    assert tail.stall_longest_s(ctx) == 4.7
    quiet = ctx_of(rows, 100.0, 116.0, stalls=[])
    assert tail.stall_count(quiet) == 0 and tail.stall_longest_s(quiet) == 0.0


@pytest.mark.parametrize("fn,kwargs", [
    (tail.interval_p99_s, {}), (tail.wire_excess_ms, {}),
    (tail.tail_in, {"part": "block"}), (tail.tail_in, {"part": "other"}),
    (tail.tail_admissions, {}), (tail.read_exact_share, {}),
    (tail.tail_clipped, {}), (tail.prefill_tok_per_device_s, {}),
    (tail.stall_count, {}), (tail.stall_longest_s, {})])
def test_a_program_without_reads_reads_as_none(fn, kwargs):
    stats = {"engine": {"tokens": 5, "admit": {"device_s": 1.0}}}
    ctx = SimpleNamespace(phase=SimpleNamespace(
        w0=0.0, w1=40.0, samples=[(0.0, stats), (40.0, stats)],
        stats_start=stats, stats_end=stats,
        records=[{"stamps": [[1.0, 16], [1.5, 16]]}]))
    assert fn(ctx, **kwargs) is None


class Ev:
    def __init__(self, name, start_s, dur_s, **stats):
        self.name = name
        self.start_ns = int(start_s * 1e9)
        self.duration_ns = int(dur_s * 1e9)
        self.stats = list(stats.items())


def plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=n.replace("_", " "), events=evs)
        for n, evs in lines.items()])


def test_capture_entries_join_programs_to_reads_by_seq():
    def sync(seq, entry, end):
        if seq % 2:        # the raw form of the name, and the stats form
            return Ev(f"sym.sched.sync#entry={entry},seq={seq},rows=4,"
                      f"bucket=128#", end - 0.2, 0.2)
        return Ev("sym.sched.sync", end - 0.2, 0.2, entry=entry, seq=seq,
                  rows=4, bucket=128)

    data = SimpleNamespace(planes=[
        plane("/device:TPU:0", XLA_Modules=[
            Ev("jit_decode_block(1)", 0.70, 0.30),      # seq 7's, cut
            Ev("jit_decode_block(1)", 1.00, 0.30),      # seq 8
            Ev("jit_prefill(7)", 1.30, 0.09),           # seq 9 ...
            Ev("jit_insert(3)", 1.39, 0.01),
            Ev("jit_decode_block(1)", 1.40, 0.31),      # seq 10
            Ev("jit_decode_block(1)", 1.71, 0.30)]),    # read after the end
        plane("/device:TPU:1", XLA_Modules=[Ev("jit_decode_block(1)",
                                               1.0, 0.3)]),
        plane("/host:CPU", engine=[
            sync(7, "decode_block", 1.0005), sync(8, "decode_block", 1.3004),
            sync(9, "prefill", 1.4003), sync(10, "decode_block", 1.7105),
            Ev("sym.sched.sync", 0.5, 0.1),             # an older program's
            Ev("sym.sched.process", 1.3004, 0.001)])])
    entries = read_tail.capture_entries(data)
    assert [e["seq"] for e in entries] == [8, 9, 10]    # the first is cut
    by = {e["seq"]: e for e in entries}
    assert by[8]["programs"] == ["jit_decode_block(1)"]
    assert by[8]["program_s"] == pytest.approx(0.30)
    assert by[9]["programs"] == ["jit_prefill(7)", "jit_insert(3)"]
    assert by[9]["program_s"] == pytest.approx(0.10)
    assert by[9]["span_s"] == pytest.approx(0.10)
    assert by[10]["program_s"] == pytest.approx(0.31)
    recs = [dict(zip(FIELDS, rec(8, 50.0))),
            dict(zip(FIELDS, rec(9, 50.1, "prefill", 0.1))),
            dict(zip(FIELDS, rec(10, 50.4, "prefill")))]  # kind disagrees
    joined = read_tail.join(recs, entries)
    assert [j["seq"] for j in joined] == [8, 9]
    assert joined[1]["device_s"] == 0.1 and joined[1]["program_s"] == (
        pytest.approx(0.10))


def test_the_cli_reads_a_dump(tmp_path):
    rows = steady(60, slow={30: 0.6})
    ctx = ctx_of(rows, 100.0, 124.0, stalls=[
        {"t": 112.5, "excess_s": 0.6, "wall_s": 0.9, "phase": "sync",
         "seq": 60}],
        gaps=[(110.0, 110.4)] * 50)
    dump = tmp_path / "cell.1.json"
    dump.write_text(json.dumps({
        "w0": ctx.phase.w0, "w1": ctx.phase.w1,
        "records": ctx.phase.records, "samples": ctx.phase.samples}))
    out = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "tools", "read_tail.py"),
         str(dump)], cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["stall_count"] == 1 and got["stall_longest_s"] == 0.6
    # the harness's own poll across the stall: on time here (one a second)
    assert got["stalls"]["recent"][0]["poll_gap_s"] == pytest.approx(1.0)
    assert got["tail"][0]["s"] == pytest.approx(1.0)
    assert sum(got["tail_in"].values()) == pytest.approx(100.0)
    assert got["admissions_per_interval"] == {"1": got["intervals"]}
    assert got["tail_clipped"] == 0
    (shape, rate), = got["prefill_rates"].items()
    assert shape == "prefill 4x128" and rate["reads"] >= got["intervals"]
    assert rate["tok_per_device_s"] == pytest.approx(1000.0) == (
        pytest.approx(got["prefill_tok_per_device_s"]))


def test_the_manifest_lists_the_eleven_for_every_cell():
    manifest = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"] in NAMES}
    assert sorted(mine) == sorted(NAMES)
    for name, entry in mine.items():
        assert "workloads" not in entry and entry["moves"] == "gap_tail_s"
        spec = json.load(open(os.path.join(
            BENCH, "layer_metrics", name + ".json")))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        module, func = spec["reader"].rsplit(".", 1)
        assert module == "tail" and callable(getattr(tail, func))


def test_the_eleven_are_walked_on_the_cpu(tmp_path):
    """`run.py` at `tiny`, the real metric files: every one of the eleven
    finds something to read in a closed cell, and the run still REFUSES."""
    data = tmp_path / "data"
    shutil.copytree(os.path.join(TESTS, "data"), data)
    real = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    m = json.load(open(data / "BENCHMARK.tiny.json"))
    m["per_layer"] += [e for e in real["per_layer"] if e["name"] in NAMES]
    json.dump(m, open(data / "BENCHMARK.tiny.json", "w"))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny.tiny-closed", "--seed", "3000000037", "--seconds", "3",
         "--trace", "0", "--manifest", str(data / "BENCHMARK.tiny.json")],
        cwd=CHECKOUT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode != 0 and out.stdout.strip() == ""
    lines = [ln for ln in out.stderr.splitlines() if "rehearsal:" in ln]
    assert lines, out.stderr[-3000:]
    assert "correct=True" in lines[-1] and "failed=0" in lines[-1]
    for name in NAMES:
        assert f"'{name}'" in lines[-1], lines[-1]


def closed_loop_records(n_streams, intervals, t0=100.0):
    """Every live stream receives its chunk at the same read: `n_streams`
    records whose stamps are the running sum of `intervals`."""
    stamps, t = [], t0
    for dt in intervals:
        t += dt
        stamps.append([t, 16])
    return [{"stamps": list(stamps)} for _ in range(n_streams)], t0, t + 1.0


@pytest.mark.parametrize("late,tail_share", [
    # ONE block of 99 read late by 0.1 s, then by 2.3 s (a stall): it holds
    # 1.01% of the gaps, so the 99th percentile IS that block, and the band
    # up to the 98th holds none of its gaps
    ({47: 0.1}, 0.0),
    ({47: 2.3}, 0.0),
    # two late blocks are 2.02% of the gaps: three of them reach the band
    ({31: 0.1, 71: 0.1}, 3 / 2282),
    # THREE are 3% of the gaps: one of them lies in the band, and the
    # judged tail says so (131 of its 2,282 gaps)
    ({15: 0.1, 31: 0.1, 71: 0.1}, 131 / 2282),
])
def test_one_interval_owns_the_p99_and_not_the_band(late, tail_share):
    """`gap_tail_s` (`client.gap_band_mean_s` 80..98) on a synthetic closed
    loop of 128 streams: 99 intervals of which every eighth holds an
    admission (0.5 s, the plateau) and the rest are plain blocks (0.4 s)."""
    from readers import client

    def ctx(extra):
        intervals = [(0.5 if i % 8 == 7 else 0.4) + extra.get(i, 0.0)
                     for i in range(100)]   # the first stamp opens no gap
        records, w0, w1 = closed_loop_records(128, intervals)
        return SimpleNamespace(phase=SimpleNamespace(
            records=records, w0=w0, w1=w1))

    calm, odd = ctx({}), ctx(late)
    by = max(late.values())
    # the band holds 999 gaps of a plain block and 1,283 of the plateau
    plain = (999 * 0.4 + 1283 * 0.5) / 2282
    assert client.gap_percentile_s(calm, 99) == pytest.approx(0.5)
    assert client.gap_band_mean_s(calm, 80, 98) == pytest.approx(plain)
    assert client.gap_percentile_s(odd, 99) == pytest.approx(0.5 + by)
    assert client.gap_band_mean_s(odd, 80, 98) == pytest.approx(
        plain + tail_share * by)
