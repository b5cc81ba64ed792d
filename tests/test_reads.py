"""The read record and the stall record (PR 37): `stats()["reads"]`,
`stats()["stalls"]`, the `sched.sync` span's attrs, and the provider's
`engine_stall` flight dump.

  - every entry that leaves the in-flight queue leaves ONE record, in FIFO
    order, with its kind, rows, tokens and what caused it; `exact` only when
    the thread waited for the entry and for the one before it; `recent` is
    capped at 64 while `n` counts on;
  - the records are written from the numbers `admit.device_s` is booked
    from, not a second measurement;
  - a plain run has no stall; a read that sleeps and a dispatch call that
    sleeps each leave one, with the phase, the entry, the excess, the
    lowering that overlapped it, and `memory: None` on the CPU; the read
    that stalled is priced at what it should have taken, inexact; a
    collector that raises leaves a shorter record and serving goes on;
  - the threshold is one median block interval, floored at 0.25 s;
  - the backend's heartbeat tells the provider when `stalls.count` grows,
    and the provider dumps once with reason `engine_stall`.

A fake device whose tokens have a controllable `is_ready` and delay; no
chip, no model.
"""

import asyncio
import json
import threading
import time

import numpy as np
import pytest

from symmetry_tpu.engine.engine import SamplingParams
from symmetry_tpu.engine.scheduler import (READ_FIELDS, STALL_FLOOR_S,
                                           GenRequest, Scheduler)
from symmetry_tpu.engine.tokenizer import ByteTokenizer
from symmetry_tpu.utils import devprof


class Lazy:
    """Tokens still on the device: the first `np.asarray` waits `wall`
    seconds (and logs the read); `is_ready` says whether it would."""

    def __init__(self, arr, log, name, wall=0.0, ready=False):
        self.arr = np.asarray(arr, dtype=np.int32)
        self.shape = self.arr.shape
        self.log, self.name, self.wall = log, name, wall
        self.read = ready

    def is_ready(self):
        return self.read

    def __array__(self, dtype=None, copy=None):
        if not self.read and self.wall:
            time.sleep(self.wall)
        self.read = True
        self.log.append(("read", self.name))
        return self.arr


class Job:
    def __init__(self, slot):
        self.slot = slot
        self.chunks = 0


class Device:
    """The scheduler-facing engine with the DISPATCH forms: a dispatch
    returns at once with lazy tokens. `walls` maps an entry's name (P0,
    B3, C1 ...) to its read wall, `slow_dispatch` to the wall of the
    dispatch CALL; everything else takes `wall`."""

    def __init__(self, slots=4, block=4, wall=0.002, batch_cap=4,
                 chunked_from=64):
        self.max_slots, self.decode_block = slots, block
        self.slot_capacity = 4096
        self.tokenizer = ByteTokenizer()
        self.prefill_buckets = (16, 32, 128)
        self.prefill_chunk = 32
        self.wall, self.batch_cap = wall, batch_cap
        self.chunked_from = chunked_from
        self.walls: dict[str, float] = {}
        self.slow_dispatch: dict[str, float] = {}
        self.on_dispatch = None
        self.log: list[tuple[str, str]] = []
        self.n = {"P": 0, "B": 0, "C": 0}

    def _name(self, kind):
        name = f"{kind}{self.n[kind]}"
        self.n[kind] += 1
        self.log.append(("dispatch", name))
        if name in self.slow_dispatch:
            if self.on_dispatch is not None:
                self.on_dispatch()
            time.sleep(self.slow_dispatch[name])
        return name

    def bucket_for(self, n):
        return next(b for b in self.prefill_buckets if n <= b)

    def prefill_batches_for(self, bucket):
        return (self.batch_cap,)

    def wants_chunked(self, n):
        return n >= self.chunked_from

    def start_chunked_prefill(self, slot, ids, sampling, hit=None):
        return Job(slot)

    def advance_chunked_prefill_dispatch(self, job):
        name = self._name("C")
        job.chunks += 1
        if job.chunks < 2:
            return None
        return Lazy([ord("A")], self.log, name,
                    self.walls.get(name, self.wall))

    advance_chunked_prefill = advance_chunked_prefill_dispatch

    def prefill_and_insert_many_dispatch(self, group):
        name = self._name("P")
        return Lazy([ord("A")] * len(group), self.log, name,
                    self.walls.get(name, self.wall))

    def prefill_and_insert_many(self, group):
        raise AssertionError("the dispatch form is there: never called")

    def decode_steps_dispatch(self):
        name = self._name("B")
        return Lazy(np.full((self.decode_block, self.max_slots), ord("b")),
                    self.log, name, self.walls.get(name, self.wall))

    def release_slot(self, slot):
        pass

    def slot_length(self, slot):
        return 0


class SyncDevice(Device):
    """Only the synchronous forms: host values when the call returns."""

    prefill_and_insert_many_dispatch = None
    advance_chunked_prefill_dispatch = None

    def advance_chunked_prefill(self, job):
        self._name("C")
        time.sleep(self.wall)
        job.chunks += 1
        return ord("A") if job.chunks >= 2 else None

    def prefill_and_insert(self, slot, ids, sampling):
        self._name("P")
        time.sleep(self.wall)
        return ord("A")

    def prefill_and_insert_many(self, group):
        self._name("P")
        time.sleep(self.wall)
        return [ord("A")] * len(group)

    def decode_steps_dispatch(self):
        self._name("B")
        return np.full((self.decode_block, self.max_slots), ord("b"),
                       dtype=np.int32)


def submit(sched, prompt: bytes, max_new=9):
    sched.submit(GenRequest(
        prompt_ids=list(prompt), sampling=SamplingParams(),
        max_new_tokens=max_new, emit=lambda ev: None,
        id=prompt[:6].decode()))


def serve(sched, prompts, max_new=9, gap=0.004):
    """Start the loop, serve `prompts`, stop."""
    done = {p[:6].decode(): threading.Event() for p in prompts}

    def sink(batch):
        for req, ev in batch:
            if ev.done:
                done[req.id].set()

    sched._emit_batch = sink
    sched.start()
    try:
        for p in prompts:
            submit(sched, p, max_new=max_new)
            time.sleep(gap)
        for rid, ev in done.items():
            assert ev.wait(30), f"{rid} hung"
    finally:
        sched.stop(timeout=10)
    assert not sched._thread.is_alive()


def records(sched):
    reads = sched.stats()["reads"]
    assert tuple(reads["fields"]) == READ_FIELDS
    return [dict(zip(reads["fields"], row)) for row in reads["recent"]]


PROMPTS = [b"r%d" % i + b"x" * (6 * (i % 3)) for i in range(7)]


class TestReadRecords:
    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize("device", [Device, SyncDevice])
    def test_one_record_per_read_in_fifo_order(self, device, depth):
        eng = device()
        sched = Scheduler(eng, pipeline_depth=depth)
        serve(sched, PROMPTS, max_new=14)
        recs = records(sched)
        st = sched.stats()
        assert st["reads"]["n"] == len(recs) < 64
        assert [r["seq"] for r in recs] == list(range(len(recs)))
        assert all(b["t"] >= a["t"] for a, b in zip(recs, recs[1:]))
        kinds = [r["kind"] for r in recs]
        assert kinds.count("decode_block") == st["block_syncs"]
        assert (kinds.count("prefill") + kinds.count("chunk")
                == st["admit"]["reads"])
        if device is Device:
            # device order: what was dispatched first is read first
            # (a job's non-final chunk C<even> leaves nothing to read)
            letters = {"prefill": "P", "decode_block": "B", "chunk": "C"}
            read_order = [n for k, n in eng.log if k == "read"]
            assert [letters[k] for k in kinds] == [n[0] for n in read_order]
        for r in recs:
            assert r["wait_s"] >= 0 and r["device_s"] >= 0
            assert r["host_s"] >= 0 and r["cpu_s"] >= 0 and r["gc_s"] >= 0
            assert r["lowerings"] == 0

    def test_rows_tokens_and_what_caused_each(self):
        eng = Device(slots=4, block=4, batch_cap=2)
        sched = Scheduler(eng)
        for p in (b"aaaa", b"bbbbbb"):          # one prefill of two rows
            submit(sched, p)
        sched._admit_new()
        sched._read_admissions()
        snapshot = dict(sched._slots)
        b0 = eng.decode_steps_dispatch()
        submit(sched, b"c" * 20)                # bucket 32, behind B0
        sched._admit_new()
        b1 = eng.decode_steps_dispatch()
        sched._process_pending(
            ("decode_block", b0, snapshot, time.monotonic(), None))
        sched._read_admissions()
        sched._process_pending(
            ("decode_block", b1, dict(sched._slots), time.monotonic(), None))
        p0, blk0, p1, blk1 = records(sched)
        assert (p0["kind"], p0["rows"], p0["bucket"], p0["tokens"]) == (
            "prefill", 2, 16, 10)
        assert p0["caused_by"] is None          # dispatched to an idle device
        assert (blk0["kind"], blk0["rows"], blk0["bucket"],
                blk0["tokens"]) == ("decode_block", 2, 0, 2 * 4)
        assert blk0["caused_by"] is None        # no block before it
        # rows is the program's batch: one prompt padded to the cap of 2
        assert (p1["rows"], p1["bucket"], p1["tokens"]) == (2, 32, 20)
        assert p1["caused_by"] == blk0["seq"]   # queued behind that block
        assert blk1["caused_by"] == blk0["seq"]
        assert blk1["tokens"] == 3 * 4
        assert [r["behind"] for r in (p0, blk0, p1, blk1)] == [0, 1, 0, 0]

    def test_a_final_chunk_counts_the_chunks_ahead_of_it(self):
        eng = Device(slots=2, chunked_from=20)
        sched = Scheduler(eng)
        submit(sched, b"s" * 8)                 # one dispatch
        submit(sched, b"l" * 40)                # two chunks: C0, then C1
        sched._admit_new()
        sched._advance_prefills()
        sched._advance_prefills()
        sched._read_admissions()
        short, final = records(sched)
        assert (short["kind"], short["chunks"]) == ("prefill", 0)
        # C0 left nothing to read: it ran inside the final chunk's interval
        assert (final["kind"], final["chunks"], final["tokens"]) == (
            "chunk", 1, 40)

    @pytest.mark.parametrize("prev_waited", [True, False])
    @pytest.mark.parametrize("this_waited", [True, False])
    def test_exact_only_when_the_thread_waited_for_both(self, prev_waited,
                                                        this_waited):
        eng = Device(slots=4, wall=0.01, batch_cap=1)
        sched = Scheduler(eng)
        submit(sched, b"m0")
        sched._admit_new()
        sched._read_admissions()
        snapshot = dict(sched._slots)
        block = eng.decode_steps_dispatch()
        submit(sched, b"m1")
        sched._admit_new()
        block.read = not prev_waited            # ready before the thread
        sched._pending[0].toks.read = not this_waited
        sched._process_pending(
            ("decode_block", block, snapshot, time.monotonic(), None))
        sched._read_admissions()
        first, blk, adm = records(sched)
        assert not first["exact"]               # the device was idle before
        assert not blk["exact"]                 # ... and so was its start
        assert blk["late"] == (not prev_waited)
        assert adm["late"] == (not this_waited)
        assert adm["exact"] == (prev_waited and this_waited)
        shape = ("prefill", 1, 16, 16)
        assert (shape in sched._shape_s) == adm["exact"]

    def test_recent_is_capped_and_n_counts_on(self):
        eng = Device(slots=1, block=1, wall=0.0)
        sched = Scheduler(eng)
        serve(sched, [b"long"], max_new=90)
        reads = sched.stats()["reads"]
        assert reads["n"] >= 90 and len(reads["recent"]) == 64
        seqs = [row[0] for row in reads["recent"]]
        assert seqs == list(range(reads["n"] - 64, reads["n"]))

    @pytest.mark.parametrize("device", [Device, SyncDevice])
    def test_device_s_is_what_admit_device_s_grew_by(self, device):
        eng = device(chunked_from=10_000)       # every prompt in one read
        sched = Scheduler(eng)
        serve(sched, PROMPTS, max_new=12)
        st = sched.stats()
        adms = [r for r in records(sched) if r["kind"] != "decode_block"]
        assert len(adms) == st["admit"]["reads"]
        assert sum(r["device_s"] for r in adms) == pytest.approx(
            st["admit"]["device_s"], abs=1e-5 * len(adms))
        assert sum(r["tokens"] for r in adms) == sum(len(p) for p in PROMPTS)
        assert sum(r["late"] for r in adms) == st["admit"]["ready_at_read"]

    def test_an_idle_boundary_breaks_the_chain(self):
        eng = Device(slots=2)
        sched = Scheduler(eng)
        sched._emit_batch = lambda batch: None
        sched.start()
        try:
            for burst in range(2):
                submit(sched, b"burst%d" % burst, max_new=10)
                deadline = time.monotonic() + 10
                while (sched.stats()["tokens"] < 10 * (burst + 1)
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                time.sleep(0.05)                # the loop goes idle
        finally:
            sched.stop(timeout=10)
        blocks = [r for r in records(sched) if r["kind"] == "decode_block"]
        heads = [b for b in blocks if b["caused_by"] is None]
        assert len(heads) == 2                  # one chain per burst
        for a, b in zip(blocks, blocks[1:]):
            assert b["caused_by"] in (None, a["seq"])
        # the idle wait is nobody's host span
        second = heads[1]
        first_of_burst = [r for r in records(sched)
                          if r["seq"] < second["seq"]
                          and r["t"] > blocks[blocks.index(second) - 1]["t"]]
        assert all(r["host_s"] < 0.04 for r in first_of_burst)


class TestStalls:
    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize("device", [Device, SyncDevice])
    def test_a_plain_run_has_none(self, device, depth):
        sched = Scheduler(device(), pipeline_depth=depth)
        serve(sched, PROMPTS, max_new=14)
        stalls = sched.stats()["stalls"]
        assert stalls == {"count": 0, "seconds": 0.0, "longest_s": 0.0,
                          "by_phase": {}, "threshold_s": STALL_FLOOR_S,
                          "recent": []}

    @pytest.mark.parametrize("median,want", [
        (None, STALL_FLOOR_S), (0.1, STALL_FLOOR_S), (0.6, 0.6)])
    def test_the_threshold_is_a_block_interval_with_a_floor(self, median,
                                                            want):
        sched = Scheduler(Device())
        sched._block_interval_s = median
        assert sched.stats()["stalls"]["threshold_s"] == want

    def test_the_threshold_follows_the_block_median(self):
        eng = Device(slots=1, block=2, wall=0.02)
        sched = Scheduler(eng)
        serve(sched, [b"steady"], max_new=40)
        blocks = [r for r in records(sched) if r["kind"] == "decode_block"]
        ivs = sorted(b["t"] - a["t"] for a, b in zip(blocks, blocks[1:]))
        assert sched._block_interval_s == pytest.approx(
            ivs[len(ivs) // 2], rel=0.25)
        assert 0.015 < sched._block_interval_s < 0.08
        exact = sorted(r["device_s"] for r in blocks if r["exact"])
        assert exact and sched._block_device_s == pytest.approx(
            exact[len(exact) // 2], rel=0.25)

    @pytest.mark.parametrize("entry", ["B6", "P2"])
    def test_a_read_that_sleeps_is_one_stall(self, entry):
        eng = Device(slots=4, batch_cap=1, wall=0.004)
        eng.walls[entry] = 0.45
        sched = Scheduler(eng)
        # the shape has run before: the wait has something to run past
        sched._shape_s[("prefill", 1, 16, 16)] = 0.004
        serve(sched, [b"s0", b"s1", b"s2", b"s3"], max_new=40, gap=0.03)
        stalls = sched.stats()["stalls"]
        assert stalls["count"] == 1 and stalls["by_phase"] == {"sync": 1}
        (stall,) = stalls["recent"]
        kind = "decode_block" if entry[0] == "B" else "prefill"
        assert (stall["phase"], stall["kind"]) == ("sync", kind)
        (rec,) = [r for r in records(sched) if r["seq"] == stall["seq"]]
        assert rec["kind"] == kind and rec["wait_s"] > 0.4
        # a wait that ran past the entry is no device time: the record is
        # priced at what the entry should take, inexact, and neither the
        # block median nor the shape's seconds learn the stall
        assert not rec["exact"] and rec["device_s"] < 0.05
        assert (sched._block_device_s or 0.0) < 0.05
        assert sched._shape_s[("prefill", 1, 16, 16)] < 0.05
        assert sched.stats()["admit"]["device_s"] < 0.3
        assert rec["wait_s"] - 0.05 < stall["excess_s"] <= rec["wait_s"]
        assert stall["excess_s"] == stalls["longest_s"] == stalls["seconds"]
        assert stall["wall_s"] >= rec["wait_s"]
        assert stall["cpu_s"] < 0.2             # blocked, not running
        assert stall["behind"] == rec["behind"]
        assert len(stall["in_flight"]) == stall["behind"]
        assert stall["memory"] is None          # the CPU reports none
        assert stall["compiles"] == [] and stall["gc_s"] >= 0
        assert stall["threshold_s"] == STALL_FLOOR_S
        for key in ("voluntary_switches", "involuntary_switches",
                    "major_faults", "process_cpu_s"):
            assert stall[key] >= 0

    @pytest.mark.parametrize("entry,phase,kind", [
        ("B5", "dispatch", "decode_block"), ("P1", "admit", "prefill"),
        ("C1", "chunks", "chunk")])
    def test_a_dispatch_call_that_sleeps_is_one_stall(self, entry, phase,
                                                      kind):
        eng = Device(slots=4, batch_cap=1, wall=0.004)
        eng.slow_dispatch[entry] = 0.4
        sched = Scheduler(eng)
        prompts = [b"d0", b"d1" + b"x" * 70, b"d2"]
        serve(sched, prompts, max_new=40, gap=0.03)
        stalls = sched.stats()["stalls"]
        assert stalls["count"] == 1 and stalls["by_phase"] == {phase: 1}
        (stall,) = stalls["recent"]
        assert (stall["phase"], stall["kind"]) == (phase, kind)
        assert 0.4 <= stall["excess_s"] <= stall["wall_s"] < 2.0
        assert stall["excess_s"] == pytest.approx(stall["wall_s"], abs=5e-3)
        assert stall["memory"] is None
        # the seq is the record the dispatched entry got when it was read
        (rec,) = [r for r in records(sched) if r["seq"] == stall["seq"]]
        assert rec["kind"] == kind
        # the slow call is host time of the next read, not device time
        assert rec["host_s"] > 0.35 or stall["seq"] > 0

    def test_a_synchronous_call_is_not_judged(self):
        eng = SyncDevice(slots=2, wall=0.3)     # its call IS the device work
        sched = Scheduler(eng)
        serve(sched, [b"q0", b"q1"], max_new=8, gap=0.0)
        assert sched.stats()["stalls"]["count"] == 0

    @pytest.mark.parametrize("entry,phase", [("B6", "sync"),
                                             ("P1", "admit")])
    def test_a_collector_that_raises_fails_nothing(self, entry, phase,
                                                   monkeypatch):
        import symmetry_tpu.engine.scheduler as scheduler

        def refuse():
            raise RuntimeError("memory_stats refused")

        monkeypatch.setattr(scheduler, "memory_report", refuse)
        eng = Device(slots=4, batch_cap=1, wall=0.004)
        (eng.walls if phase == "sync" else eng.slow_dispatch)[entry] = 0.4
        sched = Scheduler(eng)
        serve(sched, [b"g0", b"g1", b"g2"], max_new=40, gap=0.03)  # all end
        stalls = sched.stats()["stalls"]
        assert stalls["count"] == 1 and stalls["by_phase"] == {phase: 1}
        (stall,) = stalls["recent"]
        assert stall["phase"] == phase and "memory" not in stall
        assert stall["excess_s"] >= 0.35

    def test_the_lowering_that_overlapped_is_named(self):
        import jax

        watch = devprof.CompileWatch()
        watch.register()
        try:
            eng = Device(slots=2, batch_cap=1, wall=0.004)

            def relower():
                def fresh_step(x):
                    return x * 5 + 2

                jax.jit(fresh_step)(np.ones((3,), np.float32))

            eng.on_dispatch = relower
            eng.slow_dispatch["B3"] = 0.3
            sched = Scheduler(eng, compile_watch=watch)
            serve(sched, [b"w0"], max_new=30)
        finally:
            watch.unregister()
        (stall,) = sched.stats()["stalls"]["recent"]
        assert stall["phase"] == "dispatch"
        named = [(kind, name) for kind, name, _s in stall["compiles"]]
        assert ("lowerings", "jit(fresh_step)") in named
        # ... and a read after the slow call counts it in its host span
        assert sum(r["lowerings"] for r in records(sched)) >= 1


class TestSpans:
    def test_the_ring_span_carries_the_whole_record(self):
        sched = Scheduler(Device())
        serve(sched, PROMPTS[:3], max_new=10)
        spans = [s for s in sched.tracer.export()
                 if s["name"] == "sched.sync"]
        recs = records(sched)
        assert len(spans) == len(recs)
        for span, rec in zip(spans, recs):
            assert span["entry"] == rec["kind"]
            for key in READ_FIELDS:
                assert span[key] == rec[key], key

    def test_a_phase_yields_its_span_and_annotates_its_attrs(self,
                                                             monkeypatch):
        from symmetry_tpu.utils import trace

        made = []

        class Annotation:
            def __init__(self, name, **kwargs):
                made.append((name, kwargs))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(trace, "_annotation", Annotation)
        sched = Scheduler(Device())
        with sched._phase("sync", entry="prefill", seq=7, rows=4,
                          bucket=128) as span:
            span["device_s"] = 0.5
        assert made == [("sym.sched.sync", {"entry": "prefill", "seq": 7,
                                            "rows": 4, "bucket": 128})]
        (ring,) = [s for s in sched.tracer.export()
                   if s["name"] == "sched.sync"]
        assert ring["seq"] == 7 and ring["device_s"] == 0.5


class TestCompileWatchSpans:
    def test_overlapping_names_what_ran_inside(self):
        watch = devprof.CompileWatch()
        t = time.monotonic()
        watch._on_duration(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.05,
            fun_name="make")
        assert watch.lowerings == 1
        assert watch.overlapping(t - 1.0, t + 1.0) == [
            ["lowerings", "make", 0.05]]
        assert watch.overlapping(t + 0.5, t + 1.0) == []
        assert watch.overlapping(t - 2.0, t - 1.0) == []

    def test_the_collector_clock_runs(self):
        import gc

        devprof.gc_watch()
        devprof.gc_watch()                      # registered once
        assert gc.callbacks.count(devprof._on_gc) == 1
        before = devprof.gc_seconds()
        gc.collect()
        assert devprof.gc_seconds() > before


class TestProviderHook:
    def test_the_heartbeat_tells_the_provider_when_the_count_grows(self):
        from symmetry_tpu.provider.backends.tpu_native import (
            TpuNativeBackend)
        from symmetry_tpu.provider.config import ConfigManager

        be = TpuNativeBackend(ConfigManager(config={
            "name": "t", "public": False, "serverKey": "00" * 32,
            "modelName": "tiny:test", "apiProvider": "tpu_native",
            "tpu": {"model_preset": "tiny", "max_batch_size": 2,
                    "max_seq_len": 64, "prefill_buckets": [16]}}))
        told = []
        be.on_engine_stall = told.append
        be._note_stalls(None)
        be._note_stalls({"count": 0, "recent": []})
        assert told == []
        be._note_stalls({"count": 1, "recent": [{"phase": "sync"}]})
        be._note_stalls({"count": 1, "recent": [{"phase": "sync"}]})
        assert len(told) == 1 and told[0]["count"] == 1
        be._note_stalls({"count": 0})           # a respawned host
        be._note_stalls({"count": 1})
        assert len(told) == 2

    def test_the_provider_dumps_once_with_reason_engine_stall(self,
                                                              tmp_path):
        pytest.importorskip("cryptography")
        import json

        from symmetry_tpu.identity import Identity
        from symmetry_tpu.provider.config import ConfigManager
        from symmetry_tpu.provider.provider import SymmetryProvider
        from symmetry_tpu.transport.memory import MemoryTransport

        stalls = {"count": 1, "longest_s": 1.9, "recent": [
            {"phase": "sync", "kind": "decode_block", "seq": 41,
             "excess_s": 1.9}]}

        class StallingBackend:
            """A fake host behind a backend: its stall count grew."""

            on_engine_stall = None

            async def engine_stats(self):
                return {"stalls": stalls}

        async def main():
            cfg = ConfigManager(config={
                "name": "stall-prov", "public": False,
                "serverKey": "00" * 32, "modelName": "echo:x",
                "apiProvider": "echo", "dataCollectionEnabled": False,
                "flightRecorder": {"dir": str(tmp_path / "flight")}})
            provider = SymmetryProvider(
                cfg, transport=MemoryTransport(),
                identity=Identity.from_name("stall-prov"))
            provider.backend = backend = StallingBackend()
            backend.on_engine_stall = provider._on_engine_stall
            backend.on_engine_stall(stalls)
            backend.on_engine_stall({**stalls, "count": 2})  # rate-limited
            for _ in range(200):
                if list((tmp_path / "flight").glob("*.json")):
                    break
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.05)

        asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(main(), 60))
        (dump,) = (tmp_path / "flight").glob("*.json")
        assert dump.name.endswith("_engine_stall.json")
        payload = json.loads(dump.read_text())
        assert payload["reason"] == "engine_stall"
        assert payload["stats"]["engine"]["stalls"]["recent"][0][
            "seq"] == 41


class TestSymtop:
    def _engine(self, stalls=None):
        eng = Device(slots=1, block=2, wall=0.01)
        sched = Scheduler(eng)
        serve(sched, [b"top"], max_new=30)
        st = sched.stats()
        if stalls is not None:
            st["stalls"] = stalls
        return {"reads": st["reads"], "stalls": st["stalls"],
                "flush_ahead": st["flush_ahead"]}

    def test_columns_from_the_engine_block(self):
        import tools.symtop as symtop

        engine = self._engine({"count": 2, "longest_s": 4.71})
        rows = symtop.build_rows("prov", {}, None, now=0.0, engine=engine)
        assert rows[0]["stalls"] == "2/4.7"
        assert 0.005 < rows[0]["tail"] < 0.2
        table = symtop.render_table(rows)
        head, first = table.splitlines()[:2]
        assert head.split()[-3:] == ["AHEAD", "STALLS", "TAIL"]
        assert first.split()[-2] == "2/4.7"

    def test_flush_ahead_beside_the_stalls(self):
        """AHEAD = blocks whose events left ahead of an admission / the
        seconds of those admissions' waits (stats `flush_ahead`)."""
        import tools.symtop as symtop

        engine = self._engine()
        assert engine["flush_ahead"] == {"blocks": 0, "lead_s": 0.0}
        engine["flush_ahead"] = {"blocks": 212, "lead_s": 9.4312}
        rows = symtop.build_rows("prov", {}, None, now=0.0, engine=engine)
        assert rows[0]["ahead"] == "212/9.4"
        first = symtop.render_table(rows).splitlines()[1]
        assert first.split()[-3] == "212/9.4"

    @pytest.mark.parametrize("engine", [None, {}, {"tokens": 3}])
    def test_a_scrape_or_an_older_host_shows_nothing(self, engine):
        import tools.symtop as symtop

        rows = symtop.build_rows("prov", {}, None, now=0.0, engine=engine)
        assert rows[0]["stalls"] is None and rows[0]["tail"] is None
        assert rows[0]["ahead"] is None
        assert symtop.render_table(rows).splitlines()[1].split()[-3:] == [
            "-", "-", "-"]


class TestReadTailListing:
    """`tools/read_tail.py` on a dump: what the clients' gap p99 is held
    against. A tree before PR 38 flushed a block's events after the first
    admission read behind it, so the listing rebuilds those intervals; a
    dump that carries `flush_ahead` prints the plain interval beside the
    counter's growth."""

    BLOCK_S = 0.3

    def _dump(self, tmp_path, flush_ahead):
        """60 blocks of 0.3 s with two prefills of 0.05 s behind each;
        every tenth block's second prefill takes 0.2 s and the first one
        behind the block after it 0.25 s. The clients' chunks arrive when
        the block's events left: at the block's read (`flush_ahead`),
        else at the first admission's read behind it."""
        rows, left, t, seq, last = [], [], 100.0, 0, None
        lead_s = 0.0
        for i in range(60):
            t += self.BLOCK_S
            rows.append([seq, t, "decode_block", 128, 0, 2048, last,
                         self.BLOCK_S, False, self.BLOCK_S, True, 2,
                         0.002, 0.002, 0, 0.0, 0])
            last, seq, t_block = seq, seq + 1, t
            first = 0.25 if i % 10 == 6 else 0.05
            for n, p in enumerate((first, 0.2 if i % 10 == 5 else 0.05)):
                t += p
                rows.append([seq, t, "prefill", 4, 128, 400, last, p,
                             False, p, True, 1, 0.002, 0.002, 0, 0.0, 0])
                seq += 1
                if n == 0:
                    lead_s += p
                    left.append((t_block if flush_ahead else t, i + 1,
                                 lead_s))
        w0, w1 = 101.0, 121.0

        def stats_at(now):
            seen = [r for r in rows if r[1] <= now]
            engine = {"reads": {"n": len(seen), "fields": list(READ_FIELDS),
                                "recent": seen[-40:]}}
            if flush_ahead:
                gone = [x for x in left if x[0] <= now]
                engine["flush_ahead"] = {
                    "blocks": gone[-1][1] if gone else 0,
                    "lead_s": gone[-1][2] if gone else 0.0}
            return {"engine": engine}

        samples = [(w0 + i, stats_at(w0 + i)) for i in range(21)]
        stamps = [[m, 16] for m, _n, _s in left]
        path = tmp_path / "cell.1.json"
        path.write_text(json.dumps({
            "w0": w0, "w1": w1, "samples": samples,
            "records": [{"stamps": stamps}] * 4}))
        return path

    @pytest.mark.parametrize("flush_ahead", [False, True],
                             ids=["older-tree", "flush-ahead"])
    def test_the_client_p99_beside_what_explains_it(self, tmp_path,
                                                    flush_ahead):
        import os
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "read_tail.py"),
             str(self._dump(tmp_path, flush_ahead))],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        got = json.loads(out.stdout.strip().splitlines()[-1])
        # an interval is a block + the two prefills before it: 0.4, once
        # in ten 0.55 (the long second one), then 0.6 (the long first one)
        assert got["interval_p99_s"] == pytest.approx(0.6, abs=1e-6)
        if flush_ahead:
            assert "leave_p99_s" not in got
            assert got["client_gap_p99_s"] == pytest.approx(0.6, abs=1e-6)
            assert got["wire_excess_ms"] == pytest.approx(0.0, abs=1e-3)
            ahead = got["flush_ahead"]
            assert 43 <= ahead["blocks"] <= 49     # 20 s of 0.4-0.6 s
            assert ahead["lead_mean_s"] == pytest.approx(0.07, abs=0.01)
            assert ahead["lead_share"] == pytest.approx(
                100.0 * ahead["lead_s"] / 20.0, abs=1e-2)
        else:
            # block read -> first admission read: the 0.55 interval + the
            # difference of the two first waits (0.25 - 0.05)
            assert "flush_ahead" not in got
            assert got["leave_p99_s"] == pytest.approx(0.75, abs=1e-6)
            assert got["client_gap_p99_s"] == pytest.approx(0.75, abs=1e-6)
            assert got["wire_excess_ms"] == pytest.approx(150.0, abs=1e-3)
