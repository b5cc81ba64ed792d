"""The engine thread's loop phases, their annotations, and the lowering
counters (PR 25).

  - the partition: on a fake engine with real dispatch and sync walls, the
    `sched.*` ring records of the running loop never overlap and their
    cumulative seconds (`stats()["loop_s"]`) account for the thread's wall
    (`dispatch_thread_s` + the idle wait) to within 5%;
  - the annotations: no capture → no `TraceAnnotation` is ever constructed
    and a phase costs microseconds; a CPU capture of a tiny engine that is
    serving puts `sym.capture`, `sym.sched.sync` and `sym.sched.admit` on
    the trace's host plane; the flag is cleared when a capture raises;
  - `CompileWatch`: a new jitted function is one lowering, a repeated call
    is none, `recent` is bounded;
  - `stats` keeps every key the benchmark's correctness check reads.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

from symmetry_tpu.engine.engine import SamplingParams
from symmetry_tpu.engine.scheduler import LOOP_PHASES, GenRequest, Scheduler
from symmetry_tpu.engine.tokenizer import ByteTokenizer
from symmetry_tpu.utils import devprof, trace
from symmetry_tpu.utils.trace import Tracer

WALL = 0.003


class LazyBlock:
    """A block still on the device: np.asarray waits for it."""

    def __init__(self, arr):
        self.arr = arr
        self.shape = arr.shape

    def __array__(self, dtype=None, copy=None):
        time.sleep(WALL)
        return self.arr


class LazyFirsts(LazyBlock):
    """An admission's first tokens, still on the device: the read waits
    once, and `is_ready` says whether it would."""

    def __init__(self, n):
        super().__init__(np.full((n,), ord("A"), dtype=np.int32))
        self.waited = False

    def is_ready(self):
        return self.waited

    def __array__(self, dtype=None, copy=None):
        if not self.waited:
            time.sleep(WALL)
            self.waited = True
        return self.arr


class FakeJob:
    def __init__(self, slot):
        self.slot = slot
        self.chunks = 0


class FakeEngine:
    """The scheduler-facing engine contract with fixed walls: batched and
    chunked admission, a decode block whose sync blocks."""

    def __init__(self, slots=4, block=4):
        self.max_slots = slots
        self.decode_block = block
        self.slot_capacity = 4096
        self.tokenizer = ByteTokenizer()
        self.prefill_buckets = (32, 128)

    def bucket_for(self, n):
        return 32 if n <= 32 else 128

    def prefill_batches_for(self, bucket):
        return (4,)

    def wants_chunked(self, n):
        return n >= 64

    def start_chunked_prefill(self, slot, ids, sampling, hit=None):
        return FakeJob(slot)

    def advance_chunked_prefill(self, job):
        time.sleep(WALL)
        job.chunks += 1
        return ord("A") if job.chunks >= 2 else None

    def prefill_and_insert(self, slot, ids, sampling):
        time.sleep(WALL)
        return ord("A")

    def prefill_and_insert_many(self, group):
        time.sleep(WALL)
        return [ord("A")] * len(group)

    def decode_steps_dispatch(self):
        time.sleep(WALL / 10)
        return LazyBlock(np.full((self.decode_block, self.max_slots),
                                 ord("b"), dtype=np.int32))

    def release_slot(self, slot):
        pass

    def slot_length(self, slot):
        return 0


class DispatchFormEngine(FakeEngine):
    """The same device with the DISPATCH forms of admission: a dispatch
    returns at once with lazy first tokens; the wait moves to where the
    scheduler reads them."""

    def prefill_and_insert_many_dispatch(self, group):
        return LazyFirsts(len(group))

    def advance_chunked_prefill_dispatch(self, job):
        job.chunks += 1
        return LazyFirsts(1) if job.chunks >= 2 else None


ENGINES = {"sync_forms": FakeEngine, "dispatch_forms": DispatchFormEngine}


def drive(sched, n=10, max_new=24):
    """Start the loop, serve `n` requests (every third one chunked), idle
    a moment so the wait phase is entered, stop. Returns finished ids."""
    done, lock = [], threading.Lock()

    def emit_batch(batch):
        with lock:
            done.extend(req.id for req, ev in batch if ev.done)

    sched._emit_batch = emit_batch
    sched.start()
    for i in range(n):
        prompt = b"p" * (80 if i % 3 == 0 else 8)
        sched.submit(GenRequest(
            prompt_ids=list(prompt), sampling=SamplingParams(),
            max_new_tokens=max_new, emit=lambda ev: None, id=f"r{i}"))
        time.sleep(0.004)
    deadline = time.monotonic() + 20
    while len(done) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)
    sched.stop(timeout=10)
    assert not sched._thread.is_alive()
    return done


class TestPartition:
    @pytest.mark.parametrize("forms", list(ENGINES))
    @pytest.mark.parametrize("depth", [1, 2])
    def test_phases_tile_the_engine_thread(self, depth, forms):
        sched = Scheduler(ENGINES[forms](), pipeline_depth=depth)
        done = drive(sched)
        assert sorted(done) == sorted(f"r{i}" for i in range(10))
        stats = sched.stats()
        loop_s = stats["loop_s"]
        assert tuple(loop_s) == LOOP_PHASES
        assert stats["loop_iters"] > 10
        # every phase that this traffic exercises took time
        for name in ("sync", "process", "dispatch", "admit", "chunks",
                     "wait"):
            assert loop_s[name] > 0, (name, loop_s)
        # the ring's loop-phase records, in start order, never overlap
        spans = sorted((s["start"], s["start"] + s["duration_s"], s["name"])
                       for s in sched.tracer.export()
                       if s["name"].startswith("sched."))
        assert {n for _, _, n in spans} >= {
            "sched." + p for p in LOOP_PHASES if p != "flush" or depth == 1}
        for (s0, e0, n0), (s1, e1, n1) in zip(spans, spans[1:]):
            assert s1 >= e0 - 1e-6, (n0, e0, n1, s1)
        # and their seconds account for the thread's wall
        wall = stats["dispatch_thread_s"] + loop_s["wait"]
        assert sum(loop_s.values()) == pytest.approx(wall, rel=0.05)
        assert sum(loop_s.values()) <= wall * 1.001

    def test_admission_waits_where_it_reads(self):
        """With the dispatch forms the `admit` phase holds dispatch cost
        alone: the wait for an admission's first tokens is `sync`, and
        stats()["admit"] says how long it was and how often the thread
        was there first."""
        walls = {}
        for forms, engine in ENGINES.items():
            sched = Scheduler(engine(), pipeline_depth=2)
            drive(sched, n=9)
            st = sched.stats()
            walls[forms] = st
            adm = st["admit"]
            assert set(adm) == {"device_s", "wait_s", "reads",
                                "ready_at_read"}
            # every admission is read exactly once: one-dispatch prompts
            # and the final chunk of each chunked one
            assert adm["reads"] == st["admit_dispatches"] + 3
            assert adm["device_s"] > 0
        sync, disp = walls["sync_forms"], walls["dispatch_forms"]
        assert sync["admit"]["ready_at_read"] == sync["admit"]["reads"]
        assert sync["admit"]["wait_s"] < WALL
        assert disp["admit"]["ready_at_read"] == 0
        assert disp["admit"]["wait_s"] >= 0.9 * WALL * disp["admit"]["reads"]
        # admit_s stays "wall inside the dispatch calls"
        assert disp["admit_s"] < 0.5 * sync["admit_s"]
        assert disp["loop_s"]["admit"] < sync["loop_s"]["admit"]

    def test_child_spans_keep_their_ring_names(self):
        sched = Scheduler(FakeEngine(), pipeline_depth=2)
        drive(sched, n=4)
        names = {s["name"] for s in sched.tracer.export()}
        assert {"prefill_dispatch", "chunk_dispatch", "emit_flush"} <= names
        phase_s = sched.tracer.phase_s
        assert phase_s["engine.prefill"] > 0 and phase_s["engine.chunk"] > 0
        # a child lies inside its loop phase, which therefore took longer
        assert phase_s["sched.admit"] >= phase_s["engine.prefill"]
        assert phase_s["sched.chunks"] >= phase_s["engine.chunk"]

    def test_loop_s_does_not_depend_on_tracing(self):
        sched = Scheduler(FakeEngine(), pipeline_depth=2)
        sched.tracer.enabled = False
        drive(sched, n=3)
        assert not sched.tracer.export()
        assert sched.stats()["loop_s"]["sync"] > 0

    def test_stats_keep_what_the_benchmark_checks(self):
        """`check_correct` compares the wire's tokens with the host's
        `tokens`; the new blocks sit beside the old keys."""
        sched = Scheduler(FakeEngine(), pipeline_depth=2)
        drive(sched, n=5, max_new=24)
        stats = sched.stats()
        assert stats["tokens"] == 5 * 24 and stats["requests"] == 5
        for key in ("admit_s", "admit_dispatches", "chunk_s", "sync_s",
                    "dispatch_thread_s", "occupancy", "engine_ttft_s",
                    "reads", "stalls", "queue_depth"):
            assert key in stats, key
        # a block interval is read to read: t of one decode-block record
        # to t of the next (what `block_interval_s` held as a histogram)
        recs = [dict(zip(stats["reads"]["fields"], r))
                for r in stats["reads"]["recent"]]
        blocks = [r for r in recs if r["kind"] == "decode_block"]
        assert len(blocks) > 2 and stats["reads"]["n"] >= len(recs)
        assert all(b["t"] > a["t"] for a, b in zip(blocks, blocks[1:]))


class TestAnnotations:
    def test_no_capture_constructs_no_annotation(self, monkeypatch):
        import jax.profiler

        made = []

        class Sentinel:
            def __init__(self, name, **kwargs):
                made.append((name, kwargs))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Sentinel)
        assert trace._annotation is None
        tracer = Tracer()
        with tracer.phase("sched.sync"):
            pass
        sched = Scheduler(FakeEngine(), pipeline_depth=2)
        drive(sched, n=2)
        assert made == []
        # ... and under a capture each phase enters exactly one
        trace.set_capture_active(True)
        try:
            with tracer.phase("engine.prefill", ring="prefill_dispatch",
                              n=3, cached=False):
                pass
        finally:
            trace.set_capture_active(False)
        assert made == [("sym.engine.prefill", {"n": 3, "cached": False})]
        assert trace._annotation is None
        assert [s["name"] for s in tracer.export()] == [
            "sched.sync", "prefill_dispatch"]

    def test_phase_overhead_guard(self, loop_ratio):
        """With the rings off and no capture a phase is two clock reads
        and a dict add under the tracer's lock: it appends to no ring,
        makes no histogram and constructs no annotation, and costs a small
        multiple of that bare work done inline (2.5x here; a loop
        iteration enters about ten phases and lasts hundreds of
        milliseconds on the chip)."""
        tracer = Tracer()
        tracer.enabled = False

        def site():
            with tracer.phase("sched.sync"):
                pass

        lock, seconds = threading.Lock(), {}

        def bare():
            t0 = time.monotonic()
            dt = time.monotonic() - t0
            with lock:
                seconds["sched.sync"] = seconds.get("sched.sync", 0.0) + dt

        ratio = loop_ratio(site, 20_000, bare)
        assert ratio < 15, f"a disabled phase costs {ratio:.1f}x its bare work"
        assert tracer.phase_s["sched.sync"] > 0
        assert not tracer.export() and not tracer._hists
        assert trace._annotation is None  # nothing to construct one with

    def test_flag_cleared_when_a_capture_raises(self, monkeypatch, tmp_path):
        import jax.profiler

        seen = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda path, **options: None)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: seen.append(trace._annotation))

        def boom(_s):
            seen.append(trace._annotation)
            raise RuntimeError("interrupted")

        monkeypatch.setattr(devprof.time, "sleep", boom)
        with pytest.raises(RuntimeError, match="interrupted"):
            devprof.capture_device_profile(str(tmp_path), 0.1)
        # active during the sleep, cleared before stop_trace and after
        assert seen[0] is jax.profiler.TraceAnnotation and seen[1] is None
        assert trace._annotation is None
        assert devprof._capture_busy is False

    def test_cpu_capture_of_a_serving_engine_holds_the_spans(self, tmp_path):
        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        from symmetry_tpu.engine.engine import InferenceEngine
        from symmetry_tpu.models import init_params, preset

        cfg = preset("tiny")
        engine = InferenceEngine(
            cfg, init_params(cfg, jax.random.key(0), jnp.float32),
            ByteTokenizer(), max_slots=2, max_seq_len=64,
            prefill_buckets=(16,), cache_dtype=jnp.float32, decode_block=2)
        engine.warmup()
        sched = Scheduler(engine, pipeline_depth=2)
        sched.start()
        stop = threading.Event()

        def traffic():
            i = 0
            while not stop.is_set():
                sched.submit(GenRequest(
                    prompt_ids=list(b"hello"), sampling=SamplingParams(),
                    max_new_tokens=12, emit=lambda ev: None, id=f"c{i}"))
                i += 1
                time.sleep(0.02)

        feeder = threading.Thread(target=traffic)
        feeder.start()
        try:
            path = devprof.capture_device_profile(str(tmp_path), 0.4)
        finally:
            stop.set()
            feeder.join(timeout=10)
            sched.stop(timeout=30)
        assert not feeder.is_alive() and not sched._thread.is_alive()
        assert trace._annotation is None
        (pb,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        lines, syncs = {}, []
        for plane in ProfileData.from_file(pb).planes:
            if plane.name.startswith("/host"):
                for i, line in enumerate(plane.lines):
                    names = {ev.name.split("#", 1)[0] for ev in line.events
                             if ev.name.startswith("sym.")}
                    syncs += [dict(ev.stats) for ev in line.events
                              if ev.name.startswith("sym.sched.sync")]
                    if names:
                        lines[i] = names
        everything = set().union(*lines.values())
        # the sync events carry what was known before the wait: the entry's
        # kind and the seq its read record has (PR 37)
        attrs = syncs
        assert attrs and all(set(a) == {"entry", "seq", "rows", "bucket"}
                             for a in attrs)
        assert {a["entry"] for a in attrs} == {"decode_block", "prefill"}
        seqs = sorted(int(a["seq"]) for a in attrs)
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
        assert seqs[-1] < sched.stats()["reads"]["n"]
        assert {"sym.capture", "sym.sched.sync", "sym.sched.admit",
                "sym.engine.prefill", "sym.emit.emit_flush"} <= everything
        # the loop phases share one line (the engine thread's); the
        # capture's own span is on another
        sched_lines = [i for i, names in lines.items()
                       if any(n.startswith("sym.sched.") for n in names)]
        assert len(sched_lines) == 1
        assert "sym.capture" not in lines[sched_lines[0]]


class TestCompileWatch:
    def test_counts_lowerings_and_bounds_recent(self):
        import jax

        watch = devprof.CompileWatch()
        watch.register()
        try:
            def fresh(x):
                return x * 3 + 1

            f = jax.jit(fresh)
            before = watch.stats()
            f(np.ones((3,), np.float32)).block_until_ready()
            first = watch.stats()
            assert first["lowerings"] == before["lowerings"] + 1
            assert first["backend_compiles"] == before["backend_compiles"] + 1
            assert first["traces"] >= before["traces"] + 1
            assert first["host_s"] > before["host_s"]
            assert first["host_s"] == pytest.approx(
                first["trace_s"] + first["lower_s"] + first["backend_s"])
            assert any(kind == "lowerings" and "fresh" in name
                       for _t, kind, name, _s in first["recent"])
            f(np.ones((3,), np.float32)).block_until_ready()
            again = watch.stats()
            assert again["lowerings"] == first["lowerings"]
            assert again["traces"] == first["traces"]
            assert first["at_ready"] is None
            watch.mark_ready()
            for n in range(4, 20):          # a new shape lowers again
                f(np.ones((n,), np.float32)).block_until_ready()
            last = watch.stats()
            assert last["lowerings"] == first["lowerings"] + 16
            assert last["at_ready"]["lowerings"] == first["lowerings"]
            assert len(last["recent"]) == devprof.CompileWatch.RECENT == 32
        finally:
            watch.unregister()
        f(np.ones((40,), np.float32)).block_until_ready()
        assert watch.stats()["lowerings"] == last["lowerings"]

    def test_host_stats_carry_the_compile_block(self, capsys):
        """The host's stats reply gains `compile` and the scheduler's
        `loop_s`; `tokens` and the other counters are where they were."""
        import io
        import json
        import sys

        from symmetry_tpu.engine.host import EngineHost

        host = EngineHost(config=None)
        sched = Scheduler(FakeEngine(), pipeline_depth=2)
        host._scheduler = sched
        host.start = lambda: None
        stdin, sys.stdin = sys.stdin, io.StringIO('{"op":"stats"}\n')
        try:
            assert host.serve_forever() == 0
        finally:
            sys.stdin = stdin
        reply = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert reply["op"] == "stats" and reply["tokens"] == 0
        assert set(reply["compile"]) == {
            "traces", "trace_s", "lowerings", "lower_s", "backend_compiles",
            "backend_s", "cache_hits", "cache_misses", "retrieval_s",
            "host_s", "at_ready", "recent"}
        assert tuple(reply["loop_s"]) == LOOP_PHASES
        assert reply["loop_iters"] == 0
