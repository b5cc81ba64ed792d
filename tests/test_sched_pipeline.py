"""Overlapped scheduler pipeline (tpu.pipeline_depth): edge semantics.

The pipelined dispatch loop keeps `pipeline_depth` decode blocks (at
least two) and the admission dispatches between them in flight on the
device, and moves detokenize/event-build/delivery onto a bounded-queue
emit worker. These tests pin the seams the overlap
opens:

  - token identity: a real tiny CPU engine must produce byte-identical
    streams (greedy AND seeded sampled) at depth 1 (the pre-pipeline
    double buffer) and depth 2, with zero steady-state recompiles
    between traffic waves (compile_cache_sizes pinned).
  - the dispatch->sync window: a cancel landing while a block is in
    flight discards the block remainder; a slot freed at block N is
    never double-sampled by the already-in-flight block N+1 (the stale
    snapshot check); an inbox deadline expiring under a busy pipeline
    sheds as "expired" without touching active streams.
  - the emit worker: engine-loop death with events still queued fails
    every stream open (no hung client); the bounded queue is the
    backpressure contract — a slow sink stalls the dispatch thread
    instead of letting it run unboundedly ahead.

  - admission in flight (PR 31): an admission dispatch joins the same
    in-flight queue as the decode blocks and its first tokens are read in
    device order, with the next block already queued behind them; what
    the first token decides (EOS, budget, cancel, a device error) happens
    at the read; the per-block budget charges estimated device seconds.

White-box cases drive scheduler internals on a fake engine (no JAX, no
engine thread) exactly like test_scheduler_emit.py; the threaded cases
start the real loop against a fake device.
"""

import threading
import time

import numpy as np
import pytest

from symmetry_tpu.engine.engine import SamplingParams
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler
from symmetry_tpu.engine.tokenizer import ByteTokenizer


class FakeEngine:
    """The scheduler-facing engine contract, minus the device."""

    def __init__(self, slots=4, block=4, capacity=4096, buckets=(16, 32)):
        self.max_slots = slots
        self.decode_block = block
        self.slot_capacity = capacity
        self.tokenizer = ByteTokenizer()
        self.prefill_buckets = buckets
        self.dispatches = 0
        self.released: list[int] = []

    def bucket_for(self, n):
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket")

    def prefill_batches_for(self, bucket):
        return (4,)

    def prefill_and_insert(self, slot, ids, sampling):
        return ord("A")

    def prefill_and_insert_many(self, group):
        return [ord("A")] * len(group)

    def decode_steps_dispatch(self):
        self.dispatches += 1
        return np.full((self.decode_block, self.max_slots), ord("b"),
                       dtype=np.int32)

    def release_slot(self, slot):
        self.released.append(slot)

    def slot_length(self, slot):
        return 0


def submit(sched, prompt: bytes, max_new=100, cancelled=None,
           deadline_at=None, emit=None):
    sched.submit(GenRequest(
        prompt_ids=list(prompt), sampling=SamplingParams(),
        max_new_tokens=max_new, emit=emit or (lambda ev: None),
        cancelled=cancelled or (lambda: False), id=prompt.decode(),
        deadline_at=deadline_at))


def events_of(batches, req_id):
    return [ev for batch in batches for req, ev in batch
            if req.id == req_id]


class TestDispatchSyncWindow:
    """Races in the window a pipelined block spends in flight."""

    def test_cancel_between_dispatch_and_sync_discards_block(self):
        """The cancel lands AFTER the block's dispatch snapshot was
        taken and BEFORE its sync: the whole block is discarded, the
        stream finishes "cancelled", the slot frees."""
        eng = FakeEngine(slots=1)
        batches: list = []
        sched = Scheduler(eng, emit_batch=batches.append)
        cancelled: list = []
        submit(sched, b"r0", cancelled=lambda: bool(cancelled))
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        toks = eng.decode_steps_dispatch()
        snapshot = dict(sched._slots)  # the dispatch point
        cancelled.append(True)         # ...block now in flight
        tokens_before = sched.metrics["tokens"]
        sched._process_pending(
            ("decode_block", toks, snapshot, time.monotonic(), None))
        sched._flush_events()
        (ev,) = events_of(batches[-1:], "r0")
        assert ev.done and ev.finish_reason == "cancelled"
        assert ev.text == "" and ev.token_id is None
        assert sched.metrics["tokens"] == tokens_before
        assert not sched._slots and 0 in eng.released

    def test_freed_slot_never_double_sampled_by_in_flight_block(self):
        """Depth 2's hard invariant: r0 hits EOS in block N while block
        N+1 (dispatched before N synced, same snapshot) is already in
        flight; r1 then takes the freed slot. Block N+1's lane tokens
        for that slot belong to NOBODY — they must be discarded, never
        appended to r0 (done) or leaked into r1 (not in the snapshot)."""
        eng = FakeEngine(slots=1, block=4)
        batches: list = []
        sched = Scheduler(eng, emit_batch=batches.append)
        submit(sched, b"r0")
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        snapshot = dict(sched._slots)
        toks_n = eng.decode_steps_dispatch()
        toks_n[1, 0] = ByteTokenizer.EOS  # r0 stops mid-block N
        toks_n1 = eng.decode_steps_dispatch()  # N+1, in flight behind N
        sched._process_pending(
            ("decode_block", toks_n, snapshot, time.monotonic(), None))
        sched._flush_events()
        (ev,) = events_of(batches[-1:], "r0")
        assert ev.done and ev.finish_reason == "stop" and ev.text == "b"
        # The freed slot is re-admitted before block N+1 syncs.
        submit(sched, b"r1")
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        assert 0 in sched._slots and sched._slots[0].req.id == "r1"
        tokens_before = sched.metrics["tokens"]
        n_batches = len(batches)
        sched._process_pending(
            ("decode_block", toks_n1, snapshot, time.monotonic(), None))
        sched._flush_events()
        # Stale lane discarded wholesale: no event for anyone, no tokens
        # booked, r1's stream untouched by a block dispatched before it
        # existed.
        assert len(batches) == n_batches
        assert sched.metrics["tokens"] == tokens_before
        assert not events_of(batches[n_batches:], "r1")
        assert sched._slots[0].req.id == "r1"

    def test_deadline_expires_while_pipeline_busy_sheds_expired(self):
        """A queued request whose deadline passes while blocks are in
        flight is shed at its admission pass with finish "expired" —
        active streams never see it occupy a slot."""
        eng = FakeEngine(slots=2)
        batches: list = []
        sched = Scheduler(eng, emit_batch=batches.append)
        submit(sched, b"r0")
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        submit(sched, b"late", deadline_at=time.monotonic() - 0.01)
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        (ev,) = events_of(batches, "late")
        assert ev.done and ev.finish_reason == "expired"
        assert ev.error and "deadline" in ev.error
        # Only r0 ever held the slot.
        assert len(sched._slots) == 1
        assert sched._slots[0].req.id == "r0"


class TestEmitWorkerFaults:
    def test_loop_death_with_queued_events_fails_streams_open(self):
        """The engine loop dies mid-traffic with the emit queue
        non-empty (slow sink): every open stream must still receive a
        terminal error event — the worker drains before shutdown, no
        client hangs."""

        class DyingEngine(FakeEngine):
            def decode_steps_dispatch(self):
                if self.dispatches >= 3:
                    raise RuntimeError("device lost")
                return super().decode_steps_dispatch()

        eng = DyingEngine(slots=2, block=4)
        done = {"r0": threading.Event(), "r1": threading.Event()}
        finals: dict[str, object] = {}

        def sink(batch):
            time.sleep(0.05)  # keep the emit queue non-empty at death
            for req, ev in batch:
                if ev.done:
                    finals[req.id] = ev
                    done[req.id].set()

        sched = Scheduler(eng, pipeline_depth=2, emit_queue_blocks=2,
                          emit_batch=sink)
        submit(sched, b"r0", max_new=1000)
        submit(sched, b"r1", max_new=1000)
        sched.start()
        for rid, ev in done.items():
            assert ev.wait(30), f"{rid} hung after engine death"
        for rid, ev in finals.items():
            assert ev.finish_reason == "error", (rid, ev)
            assert "device lost" in (ev.error or ""), (rid, ev)
        sched._thread.join(10)
        assert not sched._thread.is_alive()
        sched._emit_thread.join(10)
        assert not sched._emit_thread.is_alive()

    def test_bounded_queue_backpressures_dispatch_thread(self):
        """emit_queue_blocks=1 + a slow sink: the dispatch thread must
        STALL on the full queue rather than run unboundedly ahead —
        dispatched-but-undelivered blocks stay within the pipeline
        depth + the queue bound + the in-progress batch, and the stream
        arrives complete and in order anyway."""
        eng = FakeEngine(slots=1, block=4)
        lead: list[int] = []
        sink_calls = [0]
        batches: list = []

        def sink(batch):
            time.sleep(0.02)
            lead.append(eng.dispatches - sink_calls[0])
            sink_calls[0] += 1
            batches.append(list(batch))

        sched = Scheduler(eng, pipeline_depth=2, emit_queue_blocks=1,
                          emit_batch=sink)
        done = threading.Event()
        submit(sched, b"r0", max_new=121,
               emit=lambda ev: done.set() if ev.done else None)
        sched.start()
        # The done event reaches the sink too (emit_batch delivery);
        # poll the collected batches for it.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if any(ev.done for ev in events_of(batches, "r0")):
                break
            time.sleep(0.01)
        sched.stop()
        evs = events_of(batches, "r0")
        assert evs and evs[-1].done and evs[-1].finish_reason == "length"
        # Completeness + order under backpressure: 1 activation token +
        # 120 block tokens, in production order.
        assert "".join(ev.text for ev in evs) == "A" + "b" * 120
        gens = [ev.tokens_generated for ev in evs]
        assert gens == sorted(gens) and gens[-1] == 121
        # The backpressure bound: in-flight on device (<= depth) +
        # queued (<= emit_queue_blocks) + the batch being delivered +
        # the engine thread's current block buffer.
        assert max(lead) <= 2 + 1 + 2, f"dispatch ran ahead: {max(lead)}"


class LazyToks:
    """Something still on the device: `np.asarray` waits for it (and
    logs the read); `is_ready` says whether it would."""

    def __init__(self, arr, log, name, wall=0.0, error=None, on_wait=None):
        self.arr = np.asarray(arr, dtype=np.int32)
        self.shape = self.arr.shape
        self.log, self.name, self.wall, self.error = log, name, wall, error
        self.on_wait = on_wait
        self.read = False

    def is_ready(self):
        return self.read

    def __array__(self, dtype=None, copy=None):
        if not self.read:
            self.log.append(("wait", self.name))
            if self.on_wait is not None:
                self.on_wait(self.name)
            if self.wall:
                time.sleep(self.wall)
        self.read = True
        self.log.append(("read", self.name))
        if self.error is not None:
            raise self.error
        return self.arr


class AsyncFakeEngine(FakeEngine):
    """A fake device with the DISPATCH forms of admission: a dispatch
    returns at once and logs itself; reading its tokens waits."""

    def __init__(self, *, first=ord("A"), prefill_wall=0.0, batch_cap=4,
                 block_wall=0.002, **kw):
        super().__init__(**kw)
        self.log: list[tuple] = []
        self.on_wait = None     # called with an entry's name as its wait begins
        self.first = first
        self.prefill_wall = prefill_wall
        self.block_wall = block_wall
        self.batch_cap = batch_cap
        self.prefill_error = None
        self.n_prefills = 0
        self.prefill_order: list[bytes] = []

    def prefill_batches_for(self, bucket):
        return (self.batch_cap,)

    def prefill_and_insert_many_dispatch(self, group):
        name = f"P{self.n_prefills}"
        self.n_prefills += 1
        self.prefill_order.extend(bytes(ids) for _s, ids, _p in group)
        self.log.append(("dispatch", name))
        return LazyToks([self.first] * len(group), self.log, name,
                        self.prefill_wall, self.prefill_error, self.on_wait)

    def decode_steps_dispatch(self):
        name = f"B{self.dispatches}"
        self.log.append(("dispatch", name))
        return LazyToks(super().decode_steps_dispatch(), self.log, name,
                        self.block_wall, on_wait=self.on_wait)


class SyncOnlyEngine(FakeEngine):
    """Only the synchronous admission forms (the multi-host lead's
    surface): results are host values when the call returns."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.log: list[tuple[str, str]] = []

    def prefill_and_insert(self, slot, ids, sampling):
        self.log.append(("dispatch", "P"))
        return super().prefill_and_insert(slot, ids, sampling)

    def prefill_and_insert_many(self, group):
        self.log.append(("dispatch", "P"))
        return super().prefill_and_insert_many(group)


def run_to_completion(sched, prompts, max_new=9):
    """Start the loop, serve `prompts`, stop; returns {id: [events]}."""
    got = {p.decode(): [] for p in prompts}
    done = {p.decode(): threading.Event() for p in prompts}

    def sink(batch):
        for req, ev in batch:
            got[req.id].append(ev)
            if ev.done:
                done[req.id].set()

    sched._emit_batch = sink
    sched.start()
    try:
        for p in prompts:
            submit(sched, p, max_new=max_new)
            time.sleep(0.01)
        for rid, ev in done.items():
            assert ev.wait(30), f"{rid} hung"
    finally:
        sched.stop(timeout=10)
    assert not sched._thread.is_alive()
    return got


class TestAdmissionInFlight:
    """Admission dispatches join the in-flight queue (PR 31)."""

    @pytest.mark.parametrize("depth", [1, 2])
    def test_block_is_queued_before_the_admission_is_read(self, depth):
        """Invariant (a): the decode block after an admission is
        dispatched BEFORE that admission's tokens are read; and entries
        are read in dispatch order (device order)."""
        eng = AsyncFakeEngine(slots=4, prefill_wall=0.005)
        sched = Scheduler(eng, pipeline_depth=depth)
        got = run_to_completion(
            sched, [b"r0", b"r1", b"r2", b"r3", b"r4", b"r5"], max_new=30)
        for rid, evs in got.items():
            assert evs[-1].finish_reason == "length", rid
            assert "".join(ev.text for ev in evs) == "A" + "b" * 29
        log = eng.log
        dispatched = [n for kind, n in log if kind == "dispatch"]
        reads = [n for kind, n in log if kind == "read"]
        assert reads == dispatched[:len(reads)], "read out of device order"
        assert eng.n_prefills >= 2
        for i, (kind, name) in enumerate(log):
            if kind == "read" and name.startswith("P"):
                at = log.index(("dispatch", name))
                assert any(k == "dispatch" and n.startswith("B")
                           for k, n in log[at:i]), (
                    f"{name} was read with no decode block queued "
                    f"behind it: {log[at:i + 1]}")
        st = sched.stats()
        assert st["admit"]["reads"] == eng.n_prefills
        assert st["admit"]["ready_at_read"] < st["admit"]["reads"]
        assert st["admit"]["wait_s"] > 0 and st["admit"]["device_s"] > 0
        assert st["admit_dispatches"] == eng.n_prefills

    def test_lane_is_live_from_dispatch_and_joins_the_next_block(self):
        """The slot is registered at dispatch, so the snapshot of the
        block dispatched behind the prefill names the request; its tokens
        of that block follow its first token."""
        eng = AsyncFakeEngine(slots=2)
        batches: list = []
        sched = Scheduler(eng, emit_batch=batches.append)
        submit(sched, b"r0")
        sched._admit_new()
        assert 0 in sched._slots and not sched._free.count(0)
        assert sched._slots[0].first_token_at is None
        (adm,) = sched._pending
        assert [(s, r.id) for s, r, _a in adm.members] == [(0, "r0")]
        snapshot = dict(sched._slots)
        toks = eng.decode_steps_dispatch()     # queued behind the prefill
        sched._read_admissions()
        assert not sched._pending
        assert sched._slots[0].first_token_at is not None
        sched._process_pending(
            ("decode_block", toks, snapshot, time.monotonic(), None))
        sched._flush_events()
        text = "".join(ev.text for ev in events_of(batches, "r0"))
        assert text == "A" + "b" * 4

    def test_sync_only_engine_takes_the_same_path(self):
        """An engine with only the synchronous forms goes through the
        same queue: its results are already on the host, the read is a
        no-op, and its device seconds are its dispatch wall."""
        eng = SyncOnlyEngine(slots=2)
        sched = Scheduler(eng)
        got = run_to_completion(sched, [b"s0", b"s1", b"s2"], max_new=13)
        for rid, evs in got.items():
            assert "".join(ev.text for ev in evs) == "A" + "b" * 12, rid
        st = sched.stats()
        assert st["admit"]["reads"] == st["admit_dispatches"] >= 2
        assert st["admit"]["ready_at_read"] == st["admit"]["reads"]
        assert st["admit"]["device_s"] == pytest.approx(st["admit_s"],
                                                        abs=1e-5)

    @pytest.mark.parametrize("case", ["eos", "max_new_1", "cancel",
                                      "device_error"])
    def test_what_the_first_token_decides_happens_at_the_read(self, case):
        """First token EOS / budget of one / cancel between dispatch and
        read / device error at the read: the right terminal event, the
        slot back in _free, the lane parked, and no token of the block
        already in flight delivered."""
        eng = AsyncFakeEngine(
            slots=1, first=ByteTokenizer.EOS if case == "eos" else ord("A"))
        if case == "device_error":
            eng.prefill_error = RuntimeError("HBM parity")
        batches: list = []
        sched = Scheduler(eng, emit_batch=batches.append)
        cancelled: list = []
        submit(sched, b"r0", max_new=1 if case == "max_new_1" else 100,
               cancelled=lambda: bool(cancelled))
        sched._admit_new()
        sched._flush_events()
        assert not events_of(batches, "r0")    # nothing decided yet
        snapshot = dict(sched._slots)
        assert list(snapshot) == [0]
        toks = eng.decode_steps_dispatch()     # in flight behind it
        if case == "cancel":
            cancelled.append(True)
        sched._read_admissions()
        (ev,) = events_of(batches, "r0")
        want = {"eos": ("stop", ""), "max_new_1": ("length", "A"),
                "cancel": ("cancelled", ""),
                "device_error": ("error", "")}[case]
        assert ev.done and (ev.finish_reason, ev.text) == want
        if case == "device_error":
            assert "HBM parity" in ev.error
        assert not sched._slots and sched._free == [0]
        assert eng.released == [0]
        tokens_before = sched.metrics["tokens"]
        n_batches = len(batches)
        sched._process_pending(
            ("decode_block", toks, snapshot, time.monotonic(), None))
        sched._flush_events()
        assert len(batches) == n_batches
        assert sched.metrics["tokens"] == tokens_before
        # the lane is reusable at once
        submit(sched, b"r1")
        sched._admit_new()
        assert sched._slots[0].req.id == "r1"


class SpecFakeEngine(AsyncFakeEngine):
    """A fake device that also verifies drafts: every lane accepts its
    whole draft, so the drafter (fed a stream of one repeated token)
    keeps proposing and the loop keeps draining its queue."""

    K_DRAFT = 2

    def __init__(self, **kw):
        from symmetry_tpu.engine.spec import SpecConfig

        super().__init__(**kw)
        self.spec = SpecConfig(k_draft=self.K_DRAFT)
        self.verifies = 0

    def verify_step_dispatch(self, draft, n_draft):
        name = f"V{self.verifies}"
        self.verifies += 1
        self.log.append(("dispatch", name))
        toks = np.full((1 + self.K_DRAFT, self.max_slots), ord("b"),
                       dtype=np.int32)
        return (LazyToks(toks, self.log, name, self.block_wall,
                         on_wait=self.on_wait),
                1 + np.asarray(n_draft))


# (pipeline_depth, emit offload): depth 1 never offloads; at depth 2 the
# un-started scheduler delivers inline and the started one through the
# worker.
EMIT_PATHS = [pytest.param(1, False, id="depth1-inline"),
              pytest.param(2, False, id="depth2-inline"),
              pytest.param(2, True, id="depth2-offload")]


def signature(events):
    return [(ev.text, ev.tokens_generated, ev.tokens_emitted, ev.done,
             ev.finish_reason, ev.ttft_s is not None,
             ev.costs is not None) for ev in events]


class TestWhatIsReadLeaves:
    """The loop's one rule for the flush (PR 38): the events of an entry
    — decode block, verify dispatch, admission — are handed on when that
    entry has been read, before the thread waits on the next one. Until
    then a block's chunk sat out the first admission's device seconds
    behind it."""

    def _sched(self, eng, depth, offload, **kw):
        """A scheduler driven white-box whose sink writes into the fake
        device's log; `offload` runs the emit worker without the loop."""
        got: dict[str, list] = {}

        def sink(batch):
            eng.log.append(("sink", tuple(
                (req.id, ev.text, ev.done) for req, ev in batch)))
            for req, ev in batch:
                got.setdefault(req.id, []).append(ev)

        sched = Scheduler(eng, pipeline_depth=depth, emit_batch=sink, **kw)
        if offload:
            sched._emit_offload = True
            sched._emit_thread = threading.Thread(
                target=sched._emit_worker_run, daemon=True)
            sched._emit_thread.start()
        return sched, got

    @staticmethod
    def _push(sched, eng):
        sched._push_block(("decode_block", eng.decode_steps_dispatch(),
                           dict(sched._slots), time.monotonic(), None))

    @staticmethod
    def _sunk(log, event):
        """Index of the sink call that carried `event`."""
        return next(i for i, (kind, what) in enumerate(log)
                    if kind == "sink" and event in what)

    def _block_with_an_admission_behind(self, depth, offload):
        """[B0, P1, B1] in flight with r0 live: the queue the loop reads
        from in steady state. Reads B0, then P1."""
        eng = AsyncFakeEngine(slots=3, prefill_wall=0.05, block_wall=0.02)
        sched, got = self._sched(eng, depth, offload)
        submit(sched, b"r0")
        sched._admit_new()
        sched._read_admissions()
        assert sched.stats()["flush_ahead"] == {"blocks": 0, "lead_s": 0.0}
        self._push(sched, eng)            # B0
        submit(sched, b"r1")
        sched._admit_new()                # P1, behind B0
        self._push(sched, eng)            # B1, behind P1: invariant (a)
        sched._read_through_block()
        sched._read_admissions()
        return eng, sched, got

    @pytest.mark.parametrize("depth,offload", EMIT_PATHS)
    def test_a_blocks_events_leave_before_the_admission_behind_it_is_read(
            self, depth, offload):
        eng, sched, got = self._block_with_an_admission_behind(
            depth, offload)
        sched._stop_emit_worker()
        log = eng.log
        chunk = self._sunk(log, ("r0", "bbbb", False))
        assert log.index(("read", "B0")) < chunk
        if offload:
            # handed to the worker before the wait: delivered while the
            # admission's 50 ms run
            assert chunk < log.index(("read", "P1"))
        else:
            assert chunk < log.index(("wait", "P1"))
        # ... and the admission's first token at its own read, with B1
        # still unread behind it
        first = self._sunk(log, ("r1", "A", False))
        assert log.index(("read", "P1")) < first
        assert ("wait", "B1") not in log
        assert [ev.text for ev in got["r0"]] == ["A", "bbbb"]
        assert [ev.text for ev in got["r1"]] == ["A"]

    @pytest.mark.parametrize("depth,offload", EMIT_PATHS)
    def test_flush_ahead_counts_a_block_with_an_admission_behind_it(
            self, depth, offload):
        eng, sched, _got = self._block_with_an_admission_behind(
            depth, offload)
        st = sched.stats()
        recs = [dict(zip(st["reads"]["fields"], r))
                for r in st["reads"]["recent"]]
        assert [r["kind"] for r in recs] == [
            "prefill", "decode_block", "prefill"]
        assert st["flush_ahead"]["blocks"] == 1
        assert st["flush_ahead"]["lead_s"] == pytest.approx(
            recs[2]["wait_s"], abs=2e-6)
        assert recs[2]["wait_s"] >= 0.05
        # B1 has no admission behind it: read, flushed, not counted; and
        # an admission read behind no flush-ahead adds no lead
        sched._read_through_block()
        submit(sched, b"r2")
        sched._admit_new()
        sched._read_admissions()
        sched._stop_emit_worker()
        after = sched.stats()
        assert after["reads"]["n"] == 5
        assert after["flush_ahead"] == st["flush_ahead"]
        assert after["admit"]["wait_s"] > st["admit"]["wait_s"]

    def _serve(self, depth, offload, order):
        """One scripted run of admissions between blocks — finishes at a
        first token, mid-block, by EOS and by budget — read in the loop's
        order (`now`) or in the parent's order of calls (`parent`: the
        block's events buffered until the first admission behind it has
        been read, then a flush after every further admission)."""
        eng = AsyncFakeEngine(slots=4, block=4)
        sched, got = self._sched(eng, depth, offload)
        pending = sched._pending

        def read():
            if order == "now":
                sched._read_through_block()
                sched._read_admissions()
                return
            flush = sched._flush_events
            sched._flush_events = lambda: False
            try:
                sched._read_through_block()
                while pending and not isinstance(pending[0], tuple):
                    sched._process_pending(pending.popleft())
                    flush()
            finally:
                sched._flush_events = flush
            flush()

        arrivals = [
            [(b"r0", 6), (b"r1", 14)],     # one group of two
            [(b"r2", 1)],                  # finishes at its first token
            [(b"r3", 9), (b"r4", 3)],
            [],
            [(b"r5", 5)],
        ]
        for step in range(8):
            for rid, max_new in (arrivals[step] if step < len(arrivals)
                                 else []):
                submit(sched, rid, max_new=max_new)
            sched._admit_new()
            sched._flush_events()
            if sched._slots:
                self._push(sched, eng)
            if sched._blocks_in_flight >= 2 or not sched._slots:
                read()
        while pending:
            read()
        sched._stop_emit_worker()
        assert not sched._slots
        return {rid: signature(evs) for rid, evs in got.items()}

    @pytest.mark.parametrize("depth,offload", EMIT_PATHS)
    def test_every_stream_is_what_the_parents_order_of_calls_sent(
            self, depth, offload):
        now = self._serve(depth, offload, "now")
        assert now == self._serve(depth, offload, "parent")
        want = {"r0": 6, "r1": 14, "r2": 1, "r3": 9, "r4": 3, "r5": 5}
        assert set(now) == set(want)
        for rid, n in want.items():
            evs = now[rid]
            assert "".join(e[0] for e in evs) == "A" + "b" * (n - 1), rid
            assert [e[3] for e in evs] == [False] * (len(evs) - 1) + [True]
            assert evs[-1][1:5] == (n, n, True, "length"), rid
            assert evs[-1][6], f"{rid}: no costs block on the terminal"
            gens = [e[1] for e in evs]
            assert gens == sorted(set(gens)), rid

    @pytest.mark.parametrize("engine_cls", [AsyncFakeEngine, SpecFakeEngine],
                             ids=["plain", "speculative"])
    @pytest.mark.parametrize("depth", [1, 2])
    def test_nothing_is_buffered_when_a_wait_begins(self, depth, engine_cls):
        """The real loop: at the start of every wait on an in-flight
        entry — the block read, the admissions behind it, and each entry
        of the speculative drain — the engine thread holds no event of
        the entries before it."""
        eng = engine_cls(slots=4, prefill_wall=0.005)
        sched = Scheduler(eng, pipeline_depth=depth)
        held: list[tuple] = []

        def on_wait(name):
            n = len(sched._pending_events) + len(sched._block_jobs)
            if n:
                held.append((name, n))

        eng.on_wait = on_wait
        got = run_to_completion(
            sched, [b"r0", b"r1", b"r2", b"r3", b"r4", b"r5"], max_new=30)
        assert not held, f"events held across a wait: {held[:5]}"
        for rid, evs in got.items():
            assert evs[-1].finish_reason == "length", rid
            assert "".join(ev.text for ev in evs) == "A" + "b" * 29
        log = [(kind, name) for kind, name in eng.log if kind != "wait"]
        st = sched.stats()
        if engine_cls is SpecFakeEngine:
            assert st["speculative"]["verify_blocks"] == eng.verifies > 0
            # the drain ran over more than one entry at least once: two
            # reads in a row before a verify dispatch
            assert any(
                log[i][0] == log[i + 1][0] == "read"
                and log[i + 2] == ("dispatch", log[i + 2][1])
                and log[i + 2][1].startswith("V")
                for i in range(len(log) - 2)), log[:40]
        else:
            # steady state reads B(k) with P(k) behind it
            assert st["flush_ahead"]["blocks"] >= 1
            assert 0 < st["flush_ahead"]["lead_s"] <= st["admit"]["wait_s"]


class TestEstimatedSecondsBudget:
    """The per-block admission bound charges each dispatch an estimate
    of its DEVICE seconds (the dispatch itself returns at once)."""

    def _busy(self, measured_s, n=4, batch_cap=1):
        """A scheduler with one live stream and `n` queued one-request
        prefills of a shape last measured at `measured_s`."""
        eng = AsyncFakeEngine(slots=8, batch_cap=batch_cap)
        sched = Scheduler(eng, admit_seconds_per_block=0.1)
        submit(sched, b"occ")
        sched._admit_new()
        sched._read_admissions()
        assert len(sched._slots) == 1
        if measured_s is not None:
            shape = ("prefill", batch_cap, 16, 16 * batch_cap)
            sched._shape_s[shape] = measured_s
        prompts = [b"q%d" % i for i in range(n)]
        for p in prompts:
            submit(sched, p)
        sched._spent_this_block = 0.0
        return sched, eng, prompts

    def test_a_slow_shape_lands_once_per_block(self):
        sched, eng, prompts = self._busy(0.4)
        sched._admit_new()
        assert eng.prefill_order[1:] == prompts[:1]
        assert sched._spent_this_block == pytest.approx(0.4)
        # the next block takes the next one, in arrival order
        sched._spent_this_block = 0.0
        sched._admit_new()
        assert eng.prefill_order[1:] == prompts[:2]

    def test_a_fast_shape_lands_several_times(self):
        sched, eng, prompts = self._busy(0.04)
        sched._admit_new()
        # 0.04 + 0.04 < 0.1 <= 0.12: three dispatches, then the bound
        assert eng.prefill_order[1:] == prompts[:3]
        assert sched._spent_this_block == pytest.approx(0.12)

    def test_an_unmeasured_shape_is_charged_by_its_tokens(self):
        """Before a shape has run: padded tokens x the slowest per-token
        rate any shape last showed. 16 tokens at 0.01 s a token = 0.16 s:
        one a block."""
        sched, eng, prompts = self._busy(None)
        sched._shape_s.clear()
        sched._shape_s.update({
            ("prefill", 4, 64, 256): 0.512,     # 0.002 s a token
            ("prefill", 1, 32, 32): 0.32})      # 0.01: the slowest
        sched._admit_new()
        assert eng.prefill_order[1:] == prompts[:1]
        assert sched._spent_this_block == pytest.approx(0.16)

    def test_the_read_measures_the_shape(self):
        """The interval between the ready stamps of an entry and the one
        before it — both waited for — becomes the shape's next charge;
        an admission dispatched to an idle device only bounds it."""
        eng = AsyncFakeEngine(slots=4, batch_cap=1, prefill_wall=0.03)
        sched = Scheduler(eng, admit_seconds_per_block=0.1)
        submit(sched, b"m0")
        sched._admit_new()
        sched._read_admissions()        # device idle before: not exact
        assert not sched._shape_s
        first = sched.stats()["admit"]["device_s"]
        assert 0.03 <= first < 0.2
        snapshot = dict(sched._slots)
        block = eng.decode_steps_dispatch()
        submit(sched, b"m1")
        sched._admit_new()              # queued behind the block
        sched._process_pending(
            ("decode_block", block, snapshot, time.monotonic(), None))
        sched._read_admissions()
        measured = sched._shape_s[("prefill", 1, 16, 16)]
        assert 0.03 <= measured < 0.1
        assert sched.stats()["admit"]["device_s"] == pytest.approx(
            first + measured, abs=1e-5)
        reads = sched.stats()["reads"]
        recs = [dict(zip(reads["fields"], r)) for r in reads["recent"]]
        assert [r["kind"] for r in recs] == [
            "prefill", "decode_block", "prefill"]
        assert [r["exact"] for r in recs] == [False, False, True]
        assert recs[2]["device_s"] == pytest.approx(measured, abs=1e-5)
        assert recs[2]["caused_by"] == recs[1]["seq"] == 1
        assert sched.stats()["admit"]["ready_at_read"] == 0

    def test_deferred_units_keep_arrival_order(self):
        """A group spanning two buckets whose first unit exhausts the
        budget defers the second — to _deferred, ahead of later
        arrivals."""
        eng = AsyncFakeEngine(slots=8, batch_cap=4)
        sched = Scheduler(eng, admit_seconds_per_block=0.1)
        submit(sched, b"occ")
        sched._admit_new()
        sched._read_admissions()
        sched._shape_s[("prefill", 4, 16, 64)] = 0.4
        sched._shape_s[("prefill", 4, 32, 128)] = 0.4
        short1, short2 = b"r1", b"r3"                       # bucket 16
        long1, long2, late = (b"x2" + b"x" * 18, b"x4" + b"x" * 18,
                              b"x5" + b"x" * 18)            # bucket 32
        for p in (short1, long1, short2, long2, late):
            submit(sched, p)
        sched._spent_this_block = 0.0
        sched._admit_new()
        assert [bytes(r.prompt_ids) for r in sched._deferred] == [
            long1, long2]
        assert eng.prefill_order[1:] == [short1, short2]
        sched._spent_this_block = 0.0
        sched._admit_new()
        order = eng.prefill_order
        assert order.index(long1) < order.index(long2) < order.index(late)
        assert not sched._deferred


class TestDepthTokenIdentity:
    """Real tiny CPU engine: the A/B invariant the tentpole pins."""

    @pytest.fixture(scope="class")
    def setup(self):
        import jax
        import jax.numpy as jnp

        from symmetry_tpu.models import init_params, preset

        cfg = preset("tiny")
        params = init_params(cfg, jax.random.key(0), jnp.float32)
        return cfg, params

    def _run_depth(self, cfg, params, depth):
        import jax.numpy as jnp

        from symmetry_tpu.engine.engine import InferenceEngine

        engine = InferenceEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=96,
            prefill_buckets=(16, 48), cache_dtype=jnp.float32,
            decode_block=4)
        sched = Scheduler(engine, debug_invariants=True,
                          pipeline_depth=depth)
        reqs = [
            (list(b"pipeline greedy one"), SamplingParams(), 16),
            (list(b"greedy two"), SamplingParams(), 16),
            (list(b"seeded sampled"),
             SamplingParams(temperature=0.8, top_k=8, seed=7), 16),
        ]
        sched.start()
        sigs = []
        try:
            for wave in range(2):
                results = {i: [] for i in range(len(reqs))}
                done = {i: threading.Event() for i in range(len(reqs))}
                for i, (ids, sampling, max_new) in enumerate(reqs):
                    def emit(ev, i=i):
                        results[i].append(ev)
                        if ev.done:
                            done[i].set()
                    sched.submit(GenRequest(
                        prompt_ids=list(ids), sampling=sampling,
                        max_new_tokens=max_new, emit=emit,
                        id=f"w{wave}r{i}"))
                for i, ev in done.items():
                    assert ev.wait(120), f"depth {depth} r{i} hung"
                sigs.append({
                    i: ("".join(ev.text for ev in evs),
                        [ev.token_id for ev in evs
                         if ev.token_id is not None],
                        evs[-1].tokens_generated,
                        evs[-1].finish_reason)
                    for i, evs in results.items()})
                if wave == 0:
                    sizes_w1 = engine.compile_cache_sizes()
        finally:
            sched.stop()
        # Zero steady-state recompiles: wave 2 re-ran the same traffic
        # shapes and must not have grown any jit cache.
        assert engine.compile_cache_sizes() == sizes_w1
        stats = sched.stats()
        assert stats["pipeline_depth"] == depth
        return sigs, stats

    def test_identity_and_split_depth_1_vs_2(self, setup):
        cfg, params = setup
        sigs1, stats1 = self._run_depth(cfg, params, 1)
        sigs2, stats2 = self._run_depth(cfg, params, 2)
        assert sigs1 == sigs2
        # The emit split: depth 1 keeps the inline pre-pipeline path
        # (zero offloaded wall), depth 2's worker carried real work.
        assert stats1["offloaded_s"] == 0
        assert stats2["offloaded_s"] > 0
        for stats in (stats1, stats2):
            assert stats["dispatch_thread_s"] > 0
            assert stats["dispatch_thread_block_s"]["p50"] is not None
            assert "pipeline_live_depth" in stats
            assert "emit_queue_depth" in stats


class SyncFormsOnly:
    """The real engine behind only its synchronous admission forms (what
    the multi-host lead offers): the scheduler's getattr for a dispatch
    form finds nothing."""

    HIDDEN = ("prefill_and_insert_many_dispatch",
              "prefill_and_insert_cached_dispatch",
              "advance_chunked_prefill_dispatch")

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        if name in self.HIDDEN:
            raise AttributeError(name)
        return getattr(self._engine, name)


class TestAdmissionFormIdentity:
    """Real tiny CPU engine, every admission path — full prefill, chunked
    prefill, the prefix-cached path and the seeded chunked one: greedy
    and seeded streams are token-identical whether admission dispatches
    (and is read in device order) or waits in place."""

    WAVE_1 = [
        (b"pipeline greedy one", SamplingParams()),          # chunked
        (b"greedy two", SamplingParams()),                   # one dispatch
        (b"seeded sampled",
         SamplingParams(temperature=0.8, top_k=8, seed=7)),
        (b"pipeline greedy one, with a longer tail",
         SamplingParams(temperature=0.7, top_k=4, seed=11)),  # chunked
    ]
    # The same prompts again now hit the radix cache (short suffix: the
    # cached path), and a new tail on a cached prefix takes the seeded
    # chunked path.
    WAVE_2 = WAVE_1 + [
        (b"pipeline greedy another tail of twenty-five",
         SamplingParams(temperature=0.9, top_k=8, seed=3)),
    ]

    @pytest.fixture(scope="class")
    def setup(self):
        import jax
        import jax.numpy as jnp

        from symmetry_tpu.models import init_params, preset

        cfg = preset("tiny")
        return cfg, init_params(cfg, jax.random.key(0), jnp.float32)

    def _serve(self, cfg, params, *, sync_only, depth):
        import jax.numpy as jnp

        from symmetry_tpu.engine.engine import InferenceEngine

        engine = InferenceEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=96,
            prefill_buckets=(16, 48), cache_dtype=jnp.float32,
            decode_block=4, prefill_chunk=16, prefix_cache_bytes=1 << 20,
            prefix_block_tokens=16)
        sigs, stats = [], []
        for wave in (self.WAVE_1, self.WAVE_2):
            # A scheduler per wave, everything queued before it starts:
            # the grouping is then the same in every run.
            sched = Scheduler(
                SyncFormsOnly(engine) if sync_only else engine,
                debug_invariants=True, pipeline_depth=depth)
            results = {i: [] for i in range(len(wave))}
            done = {i: threading.Event() for i in range(len(wave))}
            for i, (prompt, sampling) in enumerate(wave):
                def emit(ev, i=i):
                    results[i].append(ev)
                    if ev.done:
                        done[i].set()
                sched.submit(GenRequest(
                    prompt_ids=list(prompt), sampling=sampling,
                    max_new_tokens=12, emit=emit, id=f"r{i}"))
            sched.start()
            try:
                for i, ev in done.items():
                    assert ev.wait(120), f"r{i} hung"
            finally:
                sched.stop()
            sigs.append({
                i: ("".join(ev.text for ev in evs),
                    [ev.token_id for ev in evs if ev.token_id is not None],
                    evs[-1].tokens_generated, evs[-1].finish_reason,
                    evs[0].tokens_reused)
                for i, evs in results.items()})
            stats.append(sched.stats())
        return sigs, stats

    def test_dispatch_and_sync_forms_agree(self, setup):
        cfg, params = setup
        ref, ref_stats = self._serve(cfg, params, sync_only=True, depth=2)
        for depth in (1, 2):
            got, stats = self._serve(cfg, params, sync_only=False,
                                     depth=depth)
            assert got == ref, f"depth {depth}"
            for st, rst in zip(stats, ref_stats):
                assert st["tokens"] == rst["tokens"]
                assert st["admit"]["reads"] == rst["admit"]["reads"] > 0
                assert st["chunk_dispatches"] == rst["chunk_dispatches"]
        # every path ran: chunks in wave 1; in wave 2 cache hits through
        # both the short-suffix and the seeded chunked path
        assert ref_stats[0]["chunk_dispatches"] >= 4
        assert ref_stats[1]["prefix_cache"]["hits"] >= 3
        assert 0 < ref_stats[1]["chunk_dispatches"] < (
            ref_stats[0]["chunk_dispatches"])
        reused = [sig[4] for sig in ref[1].values()]
        assert reused[0] == 16 and reused[3] == 32 and reused[4] == 16
