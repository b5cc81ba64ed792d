"""Block-granular emit path + admission FIFO, on a fake engine.

White-box scheduler tests that need no JAX device work: a FakeEngine
implements the engine contract the Scheduler drives (prefill/insert,
block dispatch, slot accounting), so block processing and admission
order are exercised deterministically by calling the scheduler's
internals directly — no engine thread, no timing races.

Covers the perf-PR contracts:
  - ONE emit flush per decode block carrying every active slot's delta
    (the O(1)-writes-per-block property the batched host frame rides on)
  - vectorized finish scan fidelity: EOS mid-block, token-budget finish,
    EOS-at-budget-boundary precedence, cancel-mid-block discard
  - budget-deferred admissions drain in arrival order (FIFO), not from
    the inbox tail
"""

import numpy as np

from symmetry_tpu.engine.engine import SamplingParams
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler
from symmetry_tpu.engine.tokenizer import ByteTokenizer


class FakeEngine:
    """The scheduler-facing engine contract, minus the device."""

    def __init__(self, slots=8, block=8, capacity=4096,
                 buckets=(16, 32), batch_cap=4):
        self.max_slots = slots
        self.decode_block = block
        self.slot_capacity = capacity
        self.tokenizer = ByteTokenizer()
        self.prefill_buckets = buckets
        self._batch_cap = batch_cap
        self.prefill_order: list[bytes] = []  # prompts, in dispatch order
        self.released: list[int] = []

    def bucket_for(self, n):
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket")

    def prefill_batches_for(self, bucket):
        return (self._batch_cap,)

    def prefill_and_insert(self, slot, ids, sampling):
        self.prefill_order.append(bytes(ids))
        return ord("A")

    def prefill_and_insert_many(self, group):
        firsts = []
        for _slot, ids, _sampling in group:
            self.prefill_order.append(bytes(ids))
            firsts.append(ord("A"))
        return firsts

    def decode_steps_dispatch(self):  # pragma: no cover — loop not started
        raise AssertionError("tests drive _process_block directly")

    def release_slot(self, slot):
        self.released.append(slot)

    def slot_length(self, slot):
        return 0


def make_scheduler(eng, **kw):
    batches = []
    sched = Scheduler(eng, emit_batch=batches.append, **kw)
    return sched, batches


def submit(sched, prompt: bytes, max_new=100, cancelled=None):
    sched.submit(GenRequest(
        prompt_ids=list(prompt), sampling=SamplingParams(),
        max_new_tokens=max_new, emit=lambda ev: None,
        cancelled=cancelled or (lambda: False), id=prompt.decode()))


def events_of(batch, req_id):
    return [ev for req, ev in batch if req.id == req_id]


class TestBatchedBlockEmit:
    def test_one_flush_per_block_for_all_slots(self):
        """3 active slots × an 8-token block must leave as ONE emit flush
        with one coalesced event per slot — not 24 per-token emits."""
        eng = FakeEngine(slots=4, block=8)
        sched, batches = make_scheduler(eng)
        for rid in (b"r0", b"r1", b"r2"):
            submit(sched, rid)
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        assert len(batches) == 1  # activation: 3 first tokens, 1 flush
        assert len(batches[0]) == 3

        toks = np.full((8, 4), ord("b"), dtype=np.int32)
        sched._process_block(toks, dict(sched._slots))
        sched._flush_events()
        assert len(batches) == 2
        block_batch = batches[1]
        assert len(block_batch) == 3  # one event per slot, whole block
        for _req, ev in block_batch:
            assert ev.text == "b" * 8
            assert ev.tokens_generated == 9  # 1 (prefill) + 8 (block)
            assert ev.tokens_emitted == 9    # all 9 streamed as text
            assert not ev.done
        assert sched.metrics["emit_flushes"] == 2
        assert sched.metrics["emit_events"] == 6
        # tokens counts EMITTED tokens: 3 activation firsts + 24 block
        assert sched.metrics["tokens"] == 27

    def test_an_entry_read_through_the_queue_leaves_at_its_read(self):
        """The loop's path (PR 38): `_process_pending` hands on what the
        entry buffered, so a block read off the in-flight queue is ONE
        flush with no call of the caller's — and the flush the loop still
        makes after its admission pass finds nothing and counts nothing.
        (`test_one_flush_per_block_for_all_slots` drives `_process_block`
        below that seam, so its count of 2 stands.)"""
        import time

        eng = FakeEngine(slots=4, block=8)
        sched, batches = make_scheduler(eng)
        for rid in (b"r0", b"r1", b"r2"):
            submit(sched, rid)
        sched._admit_new()
        sched._read_admissions()
        assert len(batches) == 1 and len(batches[0]) == 3
        assert sched.metrics["emit_flushes"] == 1
        toks = np.full((8, 4), ord("b"), dtype=np.int32)
        sched._process_pending(("decode_block", toks, dict(sched._slots),
                                time.monotonic(), None))
        assert len(batches) == 2 and len(batches[1]) == 3
        assert not sched._pending_events
        sched._flush_events()
        assert len(batches) == 2
        assert sched.metrics["emit_flushes"] == 2
        assert sched.metrics["emit_events"] == 6
        # no admission sat behind that block: nothing left "ahead"
        assert sched.stats()["flush_ahead"] == {"blocks": 0, "lead_s": 0.0}

    def test_eos_mid_block_finishes_and_discards_remainder(self):
        eng = FakeEngine(slots=2, block=8)
        sched, batches = make_scheduler(eng)
        submit(sched, b"r0")
        submit(sched, b"r1")
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        toks = np.full((8, 2), ord("b"), dtype=np.int32)
        slot0 = next(s for s, a in sched._slots.items() if a.req.id == "r0")
        toks[3, slot0] = ByteTokenizer.EOS
        sched._process_block(toks, dict(sched._slots))
        sched._flush_events()
        (ev0,) = events_of(batches[-1], "r0")
        assert ev0.done and ev0.finish_reason == "stop"
        assert ev0.text == "bbb"          # tokens past the EOS discarded
        assert ev0.tokens_generated == 5  # 1 + 3 text + the EOS token
        assert ev0.tokens_emitted == 4    # …but only 4 ever streamed
        (ev1,) = events_of(batches[-1], "r1")
        assert not ev1.done and ev1.text == "b" * 8
        assert slot0 in eng.released and slot0 in sched._free
        # The 4 tokens the block produced past r0's EOS (and the EOS
        # itself) are discarded AND uncounted: 2 activation firsts +
        # 3 pushed for r0 + 8 for r1 — the number that matches what a
        # client could actually stream (bench tokens_streamed).
        assert sched.metrics["tokens"] == 13

    def test_token_budget_finishes_mid_block(self):
        eng = FakeEngine(slots=1, block=8)
        sched, batches = make_scheduler(eng)
        submit(sched, b"r0", max_new=5)  # 1 at prefill + 4 in the block
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        toks = np.full((8, 1), ord("b"), dtype=np.int32)
        sched._process_block(toks, dict(sched._slots))
        sched._flush_events()
        (ev,) = events_of(batches[-1], "r0")
        assert ev.done and ev.finish_reason == "length"
        assert ev.text == "bbbb"
        assert ev.tokens_generated == 5

    def test_eos_wins_at_budget_boundary(self):
        """An EOS on the budget-exhausting token finishes as "stop" —
        EOS is checked before the length bound, like the per-token loop
        this pass replaced."""
        eng = FakeEngine(slots=1, block=8)
        sched, batches = make_scheduler(eng)
        submit(sched, b"r0", max_new=4)  # budget: 3 block tokens
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        toks = np.full((8, 1), ord("b"), dtype=np.int32)
        toks[2, 0] = ByteTokenizer.EOS  # the 3rd = budget-exhausting token
        sched._process_block(toks, dict(sched._slots))
        sched._flush_events()
        (ev,) = events_of(batches[-1], "r0")
        assert ev.done and ev.finish_reason == "stop"
        assert ev.text == "bb" and ev.tokens_generated == 4

    def test_cancel_mid_block_discards_block(self):
        eng = FakeEngine(slots=1, block=8)
        sched, batches = make_scheduler(eng)
        cancelled = []
        submit(sched, b"r0", cancelled=lambda: bool(cancelled))
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        tokens_before = sched.metrics["tokens"]
        cancelled.append(True)  # lands between dispatch and processing
        toks = np.full((8, 1), ord("b"), dtype=np.int32)
        sched._process_block(toks, dict(sched._slots))
        sched._flush_events()
        (ev,) = events_of(batches[-1], "r0")
        assert ev.done and ev.finish_reason == "cancelled"
        assert ev.text == "" and ev.token_id is None
        assert ev.tokens_generated == 1       # nothing from this block
        assert sched.metrics["tokens"] == tokens_before
        assert not sched._slots and 0 in eng.released

    def test_multibyte_held_across_blocks(self):
        """A UTF-8 codepoint split across two decode blocks must emit
        whole, on the block that completes it (push_many back-off)."""
        eng = FakeEngine(slots=1, block=2)
        sched, batches = make_scheduler(eng)
        submit(sched, b"r0")
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        two = "é".encode()  # 2-byte codepoint
        block1 = np.array([[ord("x")], [two[0]]], dtype=np.int32)
        sched._process_block(block1, dict(sched._slots))
        sched._flush_events()
        (ev1,) = events_of(batches[-1], "r0")
        assert ev1.text == "x"  # the dangling first byte held back
        block2 = np.array([[two[1]], [ord("y")]], dtype=np.int32)
        sched._process_block(block2, dict(sched._slots))
        sched._flush_events()
        (ev2,) = events_of(batches[-1], "r0")
        assert ev2.text == "éy"


class TestDeferredAdmissionFifo:
    def test_deferred_subgroups_keep_arrival_order(self):
        """A budget-deferred subgroup must be admitted BEFORE requests
        that arrived after it — the old inbox-tail re-queue put r2/r4
        behind r5/r6 on every deferral."""
        # Budget ~0: the first prefill dispatch exhausts it, so a group
        # spanning two buckets defers its second unit.
        eng = FakeEngine(slots=8, block=4, batch_cap=4)
        sched, batches = make_scheduler(
            eng, admit_seconds_per_block=1e-9)
        submit(sched, b"occ")       # occupier engages the admission budget
        sched._admit_new()
        sched._read_admissions()
        assert len(sched._slots) == 1

        short, long = b"r1", b"r3"  # bucket 16
        l2, l4, l5, l6 = (b"x2" + b"x" * 18, b"x4" + b"x" * 18,
                          b"x5" + b"x" * 18, b"x6" + b"x" * 18)  # bucket 32
        for p in (short, l2, long, l4, l5, l6):  # arrival order
            submit(sched, p)

        sched._spent_this_block = 0.0
        sched._admit_new()
        sched._read_admissions()
        # group [r1, l2, r3, l4] split by bucket: unit [r1, r3] dispatched,
        # unit [l2, l4] deferred on the exhausted budget
        assert [bytes(r.prompt_ids) for r in sched._deferred] == [l2, l4]
        assert l5 not in eng.prefill_order and l2 not in eng.prefill_order

        sched._spent_this_block = 0.0
        sched._admit_new()
        sched._read_admissions()
        order = eng.prefill_order
        # Deferred l2/l4 admit before the later arrivals l5/l6.
        assert order.index(l2) < order.index(l5)
        assert order.index(l4) < order.index(l5)
        assert order.index(l5) < order.index(l6)
        assert not sched._deferred

    def test_drain_condition_counts_deferred(self):
        """_admit_new must not report the queue drained while deferred
        requests wait (stop() would otherwise exit with work pending)."""
        eng = FakeEngine(slots=4, block=4, batch_cap=4)
        sched, _ = make_scheduler(eng, admit_seconds_per_block=1e-9)
        submit(sched, b"occ")
        sched._admit_new()
        sched._read_admissions()
        submit(sched, b"s1")                 # bucket 16
        submit(sched, b"x" * 20)             # bucket 32 -> second unit
        sched._spent_this_block = 0.0
        drained = sched._admit_new()
        assert sched._deferred and drained is False
