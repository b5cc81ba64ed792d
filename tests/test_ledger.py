"""symledger conservation and waste accounting (engine/ledger.py).

The ledger's correctness pin is CONSERVATION: every device second the
scheduler's own dispatch walls measure (admit_s + adopt_s + chunk_s +
sync_s) lands in exactly one request's `device_s` — or, for a block
sync whose every lane went stale, in the `unattributed` bucket — so
the per-request sum reconstructs the fleet total within 5%. The mixed
white-box run below drives every booking path on a fake engine (no
JAX, no threads — the test_scheduler_emit.py pattern): batched prefill,
radix-hit cached admission (saved_s), chunked prefill, a chunked
prefill killed mid-flight (killed_prefill), a speculative verify with
rejected drafts (spec_rejected), a mid-decode cancel (cancelled), a
deadline shed (deadline_shed, zero device by construction), and an
all-stale block (unattributed).

resume_discarded is booked relay-side (tpu_native prices deduped
resume tokens at the request's own decode rate); that module needs
`cryptography`, absent here, so the class is pinned at the ledger
level in the unit tests instead.

Disabled mode (tpu.ledger=false) is the overhead contract: track()
returns None, every booking site is one `is not None` branch, no entry
is ever allocated, no costs ride the events, and no ledger block rides
stats().
"""

import time

import numpy as np
import pytest

from symmetry_tpu.engine.engine import SamplingParams
from symmetry_tpu.engine.ledger import LedgerEntry, RequestLedger
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler
from symmetry_tpu.engine.tokenizer import ByteTokenizer

# Large enough that perf_counter resolution noise cannot move a phase
# attribution by anything near the 5% conservation bound.
DISPATCH_SLEEP = 0.002

HIT_LEN = 16


class FakeHit:
    """The prefix_lookup handle contract _place_group consumes."""

    def __init__(self, length=HIT_LEN):
        self.length = length
        self.group_key = ("radix-node", length)
        self.released = 0

    def release(self):
        self.released += 1


class FakeJob:
    def __init__(self, slot):
        self.slot = slot
        self.chunks = 0


class LazyBlock:
    """A device-side token block: the scheduler's np.asarray sync
    blocks on it, so the sync wall the ledger apportions is real."""

    def __init__(self, arr):
        self.arr = arr

    def __array__(self, dtype=None, copy=None):
        time.sleep(DISPATCH_SLEEP)
        return self.arr

    @property
    def shape(self):
        return self.arr.shape


class FakeEngine:
    """Scheduler-facing engine with every admission path the ledger
    prices: batched prefill, cached (radix-hit) prefill, and chunked
    prefill. Dispatches sleep a fixed wall so attribution rates are
    well above timer noise."""

    def __init__(self, slots=8, block=4, capacity=4096,
                 buckets=(32, 128)):
        self.max_slots = slots
        self.decode_block = block
        self.slot_capacity = capacity
        self.tokenizer = ByteTokenizer()
        self.prefill_buckets = buckets
        self.prefix_align = HIT_LEN
        self.dispatches = 0
        self.released: list[int] = []

    def bucket_for(self, n):
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket")

    def prefill_batches_for(self, bucket):
        return (8,)

    # Radix hits: prompts starting with 16 "H" bytes share a cached
    # prefix of that length.
    def prefix_lookup(self, ids):
        if ids[:HIT_LEN] == [ord("H")] * HIT_LEN and len(ids) > HIT_LEN:
            return FakeHit()
        return None

    def wants_chunked(self, n):
        return n >= 64

    def start_chunked_prefill(self, slot, ids, sampling, hit=None):
        return FakeJob(slot)

    def advance_chunked_prefill(self, job):
        time.sleep(DISPATCH_SLEEP)
        job.chunks += 1
        return ord("A") if job.chunks >= 2 else None

    def prefill_and_insert(self, slot, ids, sampling):
        time.sleep(DISPATCH_SLEEP)
        return ord("A")

    def prefill_and_insert_many(self, group):
        time.sleep(DISPATCH_SLEEP)
        return [ord("A")] * len(group)

    def prefill_and_insert_cached(self, group, hit):
        time.sleep(DISPATCH_SLEEP)
        return [ord("A")] * len(group)

    def decode_steps_dispatch(self):
        self.dispatches += 1
        return LazyBlock(np.full(
            (self.decode_block, self.max_slots), ord("b"),
            dtype=np.int32))

    def release_slot(self, slot):
        self.released.append(slot)

    def slot_length(self, slot):
        return 0


def submit(sched, rid, prompt_ids, max_new=100, cancelled=None,
           deadline_at=None):
    sched.submit(GenRequest(
        prompt_ids=list(prompt_ids), sampling=SamplingParams(),
        max_new_tokens=max_new, emit=lambda ev: None,
        cancelled=cancelled or (lambda: False), id=rid,
        deadline_at=deadline_at))


def finals_of(batches):
    return {req.id: ev for batch in batches for req, ev in batch
            if ev.done}


class TestConservation:
    """Mixed traffic, then the books must balance."""

    def _drive_mixed(self, ledger_enabled=True):
        """One deterministic mixed-traffic run; returns (sched,
        batches, engine). Finish census: r0/rhit "length", r1
        "cancelled" mid-decode, rchunk "cancelled" mid-prefill
        (killed_prefill), rchunk2 "length", rlate "expired"."""
        eng = FakeEngine()
        batches: list = []
        sched = Scheduler(eng, emit_batch=batches.append,
                          prefill_chunks_per_block=1,
                          ledger_enabled=ledger_enabled)
        cancel_r1: list = []
        cancel_chunk: list = []
        submit(sched, "r0", b"plain zero", max_new=9)
        submit(sched, "r1", b"plain one", max_new=100,
               cancelled=lambda: bool(cancel_r1))
        submit(sched, "rhit", [ord("H")] * HIT_LEN + list(b"suffix"),
               max_new=9)
        submit(sched, "rchunk", b"L" * 64, max_new=100,
               cancelled=lambda: bool(cancel_chunk))
        submit(sched, "rchunk2", b"M" * 64, max_new=5)
        sched._admit_new()
        sched._read_admissions()
        sched._flush_events()
        # Chunked prefills, one chunk per pass (chunks_per_block=1,
        # FIFO head-first): rchunk runs one chunk, is cancelled before
        # its second — the accumulated chunk wall becomes
        # killed_prefill waste — then rchunk2 runs its two and
        # activates.
        sched._advance_prefills()          # rchunk chunk 1
        cancel_chunk.append(True)
        sched._advance_prefills()          # rchunk killed; rchunk2 chunk 1
        sched._advance_prefills()          # rchunk2 chunk 2 -> lane live
        sched._read_admissions()           # ... its first token read
        sched._flush_events()
        assert {a.req.id for a in sched._slots.values()} == {
            "r0", "r1", "rhit", "rchunk2"}
        # Block 1: four live lanes split the sync wall; rchunk2
        # (activation token + 4) exhausts max_new=5 and finishes.
        snap1 = dict(sched._slots)
        toks1 = eng.decode_steps_dispatch()
        sched._process_pending(
            ("decode_block", toks1, snap1, time.monotonic(), None))
        sched._flush_events()
        # Verify block: r0's lane drafted 3 and kept 1 (2 rejected
        # drafts -> spec_rejected share), r1's drafted 3 and kept all.
        slot_of = {a.req.id: s for s, a in sched._slots.items()}
        n_draft = np.zeros(eng.max_slots, dtype=np.int64)
        n_emit = np.ones(eng.max_slots, dtype=np.int64)
        n_draft[slot_of["r0"]], n_emit[slot_of["r0"]] = 3, 2
        n_draft[slot_of["r1"]], n_emit[slot_of["r1"]] = 3, 4
        snap_v = dict(sched._slots)
        sched._process_pending(
            ("verify", eng.decode_steps_dispatch(), snap_v,
             time.monotonic(), (n_emit, n_draft, 6)))
        sched._flush_events()
        # Block 3: r1's cancel lands with the block in flight — its
        # lane share books device AND cancelled waste; r0/rhit finish
        # by length.
        cancel_r1.append(True)
        snap3 = dict(sched._slots)
        sched._process_pending(
            ("decode_block", eng.decode_steps_dispatch(), snap3,
             time.monotonic(), None))
        sched._flush_events()
        assert not sched._slots
        # All-stale block (every snap1 lane finished above): the sync
        # wall has no live owner and must book unattributed.
        sched._process_pending(
            ("decode_block", eng.decode_steps_dispatch(), snap1,
             time.monotonic(), None))
        # Deadline shed: zero device seconds, class still booked.
        submit(sched, "rlate", b"too late",
               deadline_at=time.monotonic() - 0.01)
        sched._admit_new()
        sched._flush_events()
        return sched, batches, eng

    def test_device_seconds_conserve_within_5pct(self):
        sched, batches, _eng = self._drive_mixed()
        m = sched.metrics
        rhs = (m["admit_s"] + m["adopt_s"] + m["chunk_s"] + m["sync_s"])
        led = sched.stats()["ledger"]
        lhs = led["device_total_s"]
        assert rhs > 0
        assert abs(lhs - rhs) <= max(0.05 * rhs, 1e-4), (lhs, rhs)
        # Per-request reconstruction: with every entry closed, the ring
        # blocks plus the unattributed residue ARE the fleet total.
        assert led["live"] == 0 and led["finished"] == 6
        ring_sum = sum(b["device_total_s"] for b in led["ring"])
        unattr = led["device_s"].get("unattributed", 0.0)
        assert unattr > 0  # the all-stale block really had no owner
        assert abs((ring_sum + unattr) - lhs) <= 1e-3

    def test_every_waste_class_booked(self):
        sched, batches, _eng = self._drive_mixed()
        led = sched.stats()["ledger"]
        assert {"cancelled", "killed_prefill", "spec_rejected",
                "deadline_shed"} <= set(led["wasted_s"])
        assert led["wasted_s"]["deadline_shed"] == 0.0
        assert led["wasted_s"]["cancelled"] > 0
        assert led["wasted_s"]["killed_prefill"] > 0
        assert led["wasted_s"]["spec_rejected"] > 0
        assert led["wasted_tokens"]["spec_rejected"] == 2
        finals = finals_of(batches)
        # killed_prefill reclassifies the whole accumulated chunk wall.
        kp = finals["rchunk"].costs
        assert kp["finish"] == "cancelled"
        assert kp["wasted_s"]["killed_prefill"] > 0
        assert abs(kp["wasted_s"]["killed_prefill"]
                   - kp["device_s"]["chunk"]) <= 1e-5
        # The mid-decode cancel wasted exactly its final block share.
        cc = finals["r1"].costs
        assert cc["wasted_s"]["cancelled"] > 0
        assert cc["wasted_tokens"]["cancelled"] == 4
        by = led["by_finish"]
        assert {"length", "cancelled", "expired"} <= set(by)

    def test_costs_ride_every_terminal_event(self):
        sched, batches, _eng = self._drive_mixed()
        finals = finals_of(batches)
        assert set(finals) == {"r0", "r1", "rhit", "rchunk", "rchunk2",
                               "rlate"}
        for rid, ev in finals.items():
            costs = ev.costs
            assert isinstance(costs, dict), rid
            assert costs["finish"] == ev.finish_reason, rid
            assert costs["source"] == "blocked", rid
            assert costs["queue_s"] >= 0.0, rid
        # Streaming finishes attributed real device time; the shed one
        # attributed none.
        assert finals["r0"].costs["device_total_s"] > 0
        assert finals["rlate"].costs["device_total_s"] == 0
        assert finals["r0"].costs["tokens"] > 0
        # The radix hit priced its avoided prefix at the admitting
        # dispatch's own rate.
        hit = finals["rhit"].costs
        assert hit["saved_s"] > 0 and hit["saved_tokens"] == HIT_LEN
        led = sched.stats()["ledger"]
        assert led["saved_tokens"] == HIT_LEN
        assert led["tokens_per_device_s"] > 0

    def test_disabled_mode_books_nothing(self):
        """tpu.ledger=false: the identical run allocates zero entries,
        ships zero cost blocks, and stats() carries no ledger rider —
        the overhead contract behind the one guarded branch."""
        sched, batches, _eng = self._drive_mixed(ledger_enabled=False)
        assert sched.ledger.enabled is False
        assert sched.ledger.track("x") is None
        assert sched.ledger._live == 0 and sched.ledger._finished == 0
        assert not sched.ledger._ring
        finals = finals_of(batches)
        assert set(finals) == {"r0", "r1", "rhit", "rchunk", "rchunk2",
                               "rlate"}
        assert all(ev.costs is None for ev in finals.values())
        assert "ledger" not in sched.stats()

    def test_disabled_mode_overhead_guard(self):
        """The disabled run does strictly less work than the enabled
        one on identical traffic — a generous wall bound (pure fake
        dispatches dominated by fixed sleeps) that would only trip if
        the disabled path grew real per-token work."""
        t0 = time.perf_counter()
        self._drive_mixed(ledger_enabled=True)
        on_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        self._drive_mixed(ledger_enabled=False)
        off_s = time.perf_counter() - t0
        assert off_s <= on_s * 1.5 + 0.05, (off_s, on_s)


class TestLedgerUnit:
    def test_finish_idempotent_and_fold(self):
        led = RequestLedger()
        e = led.track("a")
        assert isinstance(e, LedgerEntry)
        e.book_device("decode", 0.5, tokens=10)
        e.book_queue(0.2)
        e.book_queue(0.1)  # set-not-add: the re-pick is the true wait
        block = e.finish("stop")
        assert block["finish"] == "stop"
        assert block["device_s"] == {"decode": 0.5}
        assert block["queue_s"] == 0.1
        assert e.finish("stop") is None  # second close books nothing
        stats = led.stats()
        assert stats["finished"] == 1 and stats["live"] == 0
        assert stats["by_finish"]["stop"]["tokens"] == 10
        assert stats["ring"][-1]["id"] == "a"

    def test_release_folds_without_wire_block(self):
        led = RequestLedger()
        e = led.track("h")
        e.book_device("prefill", 0.3)
        e.release("handoff")
        e.release("handoff")  # idempotent
        stats = led.stats()
        assert stats["by_finish"]["handoff"]["requests"] == 1
        assert stats["device_total_s"] == 0.3

    def test_resume_discarded_class(self):
        """The relay-side class (tpu_native prices deduped resume
        tokens at the request's decode rate) pinned at ledger level."""
        led = RequestLedger()
        e = led.track("r")
        e.book_device("decode", 1.0, tokens=20)
        e.book_wasted("resume_discarded", 0.25, 5)
        block = e.finish("stop")
        assert block["wasted_s"]["resume_discarded"] == 0.25
        assert block["wasted_tokens"]["resume_discarded"] == 5
        assert led.stats()["wasted_s"]["resume_discarded"] == 0.25

    def test_saved_at_phase_rate(self):
        led = RequestLedger()
        e = led.track("s")
        e.book_device("chunk", 1.0)  # 100-token suffix -> 10ms/token
        e.book_saved_at_phase_rate("chunk", 100, 50)
        block = e.finish("stop")
        assert abs(block["saved_s"] - 0.5) <= 1e-9
        assert block["saved_tokens"] == 50

    def test_booking_after_close_keeps_fleet_totals_only(self):
        """A late book (emit flush racing the finish) must not mutate
        the closed entry but still lands in the fleet totals, so
        conservation holds across the race."""
        led = RequestLedger()
        e = led.track("late")
        e.finish("stop")
        e.book_device("decode", 0.2)
        e.book_emit(0.1)
        assert led.device_total_s() == 0.2
        assert led.stats()["emit_s"] == 0.1
        assert led.stats()["ring"][-1]["device_total_s"] == 0.0

    def test_source_is_the_dispatch_walls(self):
        """One source: dispatch-thread block time. The rider keeps the
        field so the wire shape stands."""
        led = RequestLedger()
        assert led.source == "blocked"
        assert led.stats()["source"] == "blocked"
        with pytest.raises(TypeError):
            RequestLedger(measured=True)

    def test_unattributed_counts_toward_conservation(self):
        led = RequestLedger()
        led.book_unattributed(0.4)
        assert led.device_total_s() == 0.4
        assert led.stats()["device_s"]["unattributed"] == 0.4
