"""Radix-tree prefix cache over a paged KV block pool.

Covers the acceptance surface of the radix/paged-KV PR:

  - exact hit: a repeated prompt pays ZERO full-prefill dispatches — one
    block gather + one suffix dispatch covers admission
  - block-granular matching: an UNALIGNED mid-bucket shared prefix
    (any whole-block length) hits — impossible in the old aligned store
  - decode equivalence: greedy AND seeded-sampled tokens are identical
    with the cache on vs off (the cache must be invisible to outputs)
  - zero steady-state recompiles under mixed hit/miss traffic with
    unaligned history lengths (engine.compile_cache_sizes() pinned)
  - BlockPool/RadixIndex semantics: refcounted free list, pinning,
    leaf-LRU eviction that frees blocks, two-phase insert — including a
    randomized model-based test against a plain-dict reference
  - scheduler integration: hit/miss requests partition into separate
    dispatch units inside _place_group and streams match the sequential
    reference; counters flow through scheduler.stats()
"""

import random
import threading

import jax
import jax.numpy as jnp
import pytest

from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
from symmetry_tpu.engine.prefix_cache import BlockPool, RadixIndex
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler
from symmetry_tpu.engine.tokenizer import ByteTokenizer
from symmetry_tpu.models import forward, init_cache, init_params, preset


@pytest.fixture(scope="module")
def setup():
    cfg = preset("tiny")
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    return cfg, params


def make_engine(cfg, params, slots=4, cache_mb=16, chunk=8,
                buckets=(16, 32), block=8):
    return InferenceEngine(
        cfg, params, ByteTokenizer(), max_slots=slots, max_seq_len=64,
        prefill_buckets=buckets, cache_dtype=jnp.float32,
        prefill_chunk=chunk, prefix_cache_bytes=cache_mb * 2**20,
        prefix_block_tokens=block)


def reference_greedy(cfg, params, prompt_ids, n_tokens):
    cache = init_cache(cfg, 1, 64, jnp.float32)
    tokens = jnp.asarray([prompt_ids], jnp.int32)
    logits, cache = forward(params, cfg, tokens, cache)
    out = []
    last = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    out.append(int(last[0]))
    for _ in range(n_tokens - 1):
        logits, cache = forward(params, cfg, last[:, None], cache)
        last = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        out.append(int(last[0]))
    return out


def count_dispatches(engine):
    """Wrap the full-prefill and suffix jits with call counters."""
    counts = {"prefill": 0, "chunk_final": 0, "chunk_step": 0}
    real_prefill, real_final = engine._prefill, engine._chunk_final
    real_step = engine._chunk_step

    def prefill(*a, **kw):
        counts["prefill"] += 1
        return real_prefill(*a, **kw)

    def final(*a, **kw):
        counts["chunk_final"] += 1
        return real_final(*a, **kw)

    def step(*a, **kw):
        counts["chunk_step"] += 1
        return real_step(*a, **kw)

    engine._prefill = prefill
    engine._chunk_final = final
    engine._chunk_step = step
    return counts


BASE = list(b"hello world prefix!")  # 19 tokens -> 2 whole blocks @ 8


class TestEngineHitPaths:
    def test_exact_hit_skips_full_prefill(self, setup):
        """Second identical prompt: zero full-prefill dispatches — one
        block gather + one suffix dispatch covers admission."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        want = reference_greedy(cfg, params, BASE, 6)

        first = engine.prefill_and_insert(0, BASE, SamplingParams())
        got_miss = [first] + [int(engine.decode_step()[0])
                              for _ in range(5)]
        assert got_miss == want
        # (hit/miss counters tick per ADMITTED request — the direct
        # engine call here only stores.)
        st = engine.prefix_index.stats()
        assert st["insertions"] == 1 and st["blocks_in_use"] == 2

        counts = count_dispatches(engine)
        hit = engine.prefix_lookup(BASE)
        assert hit is not None and hit.length == 16
        assert len(hit.blocks) == 2
        firsts = engine.prefill_and_insert_cached(
            [(1, BASE, SamplingParams())], hit)
        assert counts["prefill"] == 0  # cached portion: no prefill
        assert counts["chunk_final"] == 1  # suffix-only dispatch
        got_hit = list(firsts) + [int(engine.decode_step()[1])
                                  for _ in range(5)]
        assert got_hit == want
        st = engine.prefix_index.stats()
        assert st["hits"] == 1 and st["tokens_reused"] == 16

    def test_unaligned_mid_bucket_prefix_hits(self, setup):
        """THE new capability: a shared prefix of arbitrary (non-bucket,
        non-chunk-aligned) length hits at block granularity. 13 shared
        tokens match at 8 (one whole block) — the old aligned store
        could only match multiples of prefix_align AND only at lengths
        some entry was stored at."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.prefill_and_insert(0, BASE, SamplingParams())

        other = BASE[:13] + list(b"XYZ")  # 16 tokens, shares 13
        want = reference_greedy(cfg, params, other, 4)
        counts = count_dispatches(engine)
        hit = engine.prefix_lookup(other)
        assert hit is not None and hit.length == 8
        assert hit.tokens == tuple(other[:8])
        firsts = engine.prefill_and_insert_cached(
            [(1, other, SamplingParams())], hit)
        assert counts["prefill"] == 0 and counts["chunk_final"] == 1
        got = list(firsts) + [int(engine.decode_step()[1])
                              for _ in range(3)]
        assert got == want
        # The cached admission EXTENDED the tree with `other`'s own
        # whole-block prefix — the multi-turn session-cache mechanism.
        assert engine.prefix_index.covers(other[:16])

    def test_partial_hit_suffix_only(self, setup):
        """A prompt sharing whole blocks prefills only its own suffix
        and still matches the sequential reference."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.prefill_and_insert(0, BASE, SamplingParams())

        other = BASE[:16] + list(b"XYZ")
        want = reference_greedy(cfg, params, other, 6)
        counts = count_dispatches(engine)
        hit = engine.prefix_lookup(other)
        assert hit is not None and hit.length == 16
        firsts = engine.prefill_and_insert_cached(
            [(1, other, SamplingParams())], hit)
        assert counts["prefill"] == 0 and counts["chunk_final"] == 1
        got = list(firsts) + [int(engine.decode_step()[1])
                              for _ in range(5)]
        assert got == want

    def test_sub_prefix_of_longer_entry_hits(self, setup):
        """KV is causal: the first block of a 2-block entry IS the
        8-token prefix's KV, so a prompt sharing only 8 tokens still
        hits at 8 — and the radix tree serves it from the SAME blocks."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.prefill_and_insert(0, BASE, SamplingParams())

        other = BASE[:8] + list(b"tail998")
        want = reference_greedy(cfg, params, other, 4)
        hit = engine.prefix_lookup(other)
        assert hit is not None and hit.length == 8
        firsts = engine.prefill_and_insert_cached(
            [(1, other, SamplingParams())], hit)
        got = list(firsts) + [int(engine.decode_step()[1])
                              for _ in range(3)]
        assert got == want

    def test_long_suffix_runs_seeded_chunked(self, setup):
        """Suffix beyond one alignment unit: the hit seeds a chunked
        prefill instead (blocks gathered, chunks cover only the
        suffix), and the finished buffer's NEW blocks extend the tree."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.prefill_and_insert(0, BASE, SamplingParams())

        prompt = BASE[:8] + list(b"different tail..")  # 24 tok, sfx 16
        want = reference_greedy(cfg, params, prompt, 4)
        hit = engine.prefix_lookup(prompt)
        assert hit is not None and hit.length == 8
        assert engine.seeded_chunk_ok(len(prompt))
        counts = count_dispatches(engine)
        job = engine.start_chunked_prefill(1, prompt, SamplingParams(),
                                           hit=hit)
        assert job.start_pos == 8 and job.suffix_len == 16
        first = None
        while first is None:
            first = engine.advance_chunked_prefill(job)
        assert counts["prefill"] == 0
        got = [first] + [int(engine.decode_step()[1]) for _ in range(3)]
        assert got == want
        # tail adoption: the completed 24-token prefix is covered, and
        # the shared first block was NOT duplicated (3 new blocks only)
        assert engine.prefix_index.covers(prompt[:24])
        st = engine.prefix_index.stats()
        assert st["blocks_in_use"] == 4  # 2 (BASE) + 2 (new tail)
        # ... so the NEXT turn of the session (this whole history plus a
        # new message) hits at all of it, not at the first turn's blocks
        turn3 = engine.prefix_lookup(prompt + list(b" and a reply"))
        assert turn3.length == 24
        turn3.release()

    def test_coalesced_hit_group_with_pad_rows(self, setup):
        """Several requests sharing one (node, matched_len) admit as ONE
        cached unit (batch padded to the compiled width) and each stream
        matches its own sequential reference."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.prefill_and_insert(0, BASE, SamplingParams())
        prompts = [BASE[:16] + list(b"A%d" % i) for i in range(3)]
        wants = [reference_greedy(cfg, params, p, 3) for p in prompts]

        hit = engine.prefix_lookup(prompts[0])
        firsts = engine.prefill_and_insert_cached(
            [(i, p, SamplingParams()) for i, p in enumerate(prompts)], hit)
        gots = [[f] for f in firsts]
        for _ in range(2):
            toks = engine.decode_step()
            for i in range(3):
                gots[i].append(int(toks[i]))
        assert gots == wants

    def test_seeded_sampling_identical_cache_on_off(self, setup):
        """A seeded sampled request reproduces its EXACT completion
        whether admission went through the cache or a full prefill."""
        cfg, params = setup
        sp = SamplingParams(temperature=0.9, top_p=0.95, seed=42)

        engine_off = make_engine(cfg, params, cache_mb=0)
        assert engine_off.prefix_index is None
        toks_off = [engine_off.prefill_and_insert(0, BASE, sp)]
        toks_off += [int(engine_off.decode_step()[0]) for _ in range(5)]

        engine_on = make_engine(cfg, params)
        engine_on.prefill_and_insert(0, BASE, SamplingParams(seed=7))
        hit = engine_on.prefix_lookup(BASE)
        assert hit is not None
        toks_on = list(engine_on.prefill_and_insert_cached(
            [(1, BASE, sp)], hit))
        toks_on += [int(engine_on.decode_step()[1]) for _ in range(5)]
        assert toks_on == toks_off

    def test_warmup_then_hit_path(self, setup):
        """warmup() with the cache enabled (extra compile grid) must not
        perturb subsequent cached admissions."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.warmup()
        want = reference_greedy(cfg, params, BASE, 4)
        engine.prefill_and_insert(0, BASE, SamplingParams())
        hit = engine.prefix_lookup(BASE)
        firsts = engine.prefill_and_insert_cached(
            [(1, BASE, SamplingParams())], hit)
        got = list(firsts) + [int(engine.decode_step()[1])
                              for _ in range(3)]
        assert got == want

    def test_zero_steady_state_recompiles_unaligned_traffic(self, setup):
        """After warmup, mixed hit/miss traffic with UNALIGNED history
        lengths must not grow any jit cache — block-granular matching
        moves lengths into data (ids vectors, traced scalars), never
        into shapes."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.warmup()
        baseline = engine.compile_cache_sizes()
        assert baseline["_insert_from_blocks"] > 0
        assert baseline["_write_blocks"] > 0
        # Prime the cache so the burst's shared-prefix members hit
        # deterministically (a cold burst looks everything up before
        # anything stores).
        engine.prefill_and_insert(0, BASE, SamplingParams())
        engine.release_slot(0)
        prompts = [BASE,                      # exact hit
                   BASE[:13] + list(b"XY"),   # unaligned 13-shared hit
                   BASE[:11] + list(b"qrs"),  # unaligned 11-shared hit
                   list(b"totally new one!"),  # miss
                   BASE[:8] + list(b"different tail..")]  # seeded chunk
        sched, results = run_scheduler_requests(
            engine, [(p, SamplingParams(), 3) for p in prompts])
        for evs in results.values():
            assert evs and evs[-1].done
            assert evs[-1].finish_reason in ("stop", "length")
        assert engine.compile_cache_sizes() == baseline, \
            "steady-state traffic recompiled a serving program"
        assert engine.prefix_index.stats()["hits"] >= 2


# ---------------------------------------------------------------------
# BlockPool / RadixIndex semantics (no engine, no device)


def mk_index(n_blocks=16, bs=4):
    return RadixIndex(BlockPool(n_blocks, bs, block_bytes=100))


def do_insert(idx, tokens):
    plan = idx.plan_insert(tokens)
    if plan is not None:
        plan.commit()
    return plan


class TestRadixSemantics:
    def test_two_phase_insert_and_reuse(self):
        idx = mk_index()
        plan = idx.plan_insert(list(range(8)))
        assert plan.matched_len == 0 and len(plan.new_ids) == 2
        plan.commit()
        # extension allocates only the tail
        plan2 = idx.plan_insert(list(range(12)))
        assert plan2.matched_len == 8 and len(plan2.new_ids) == 1
        plan2.commit()
        assert idx.pool.in_use == 3
        # fully resident -> no plan
        assert idx.plan_insert(list(range(12))) is None

    def test_abort_returns_blocks(self):
        idx = mk_index(n_blocks=4)
        plan = idx.plan_insert(list(range(16)))
        assert plan is not None and idx.pool.free_count == 0
        plan.abort()
        assert idx.pool.free_count == 4 and idx.pool.in_use == 0
        assert idx.match_len(list(range(16))) == 0

    def test_lookup_strictly_partial_and_pinned(self):
        idx = mk_index()
        do_insert(idx, list(range(8)))
        hit = idx.lookup(list(range(8)))
        # suffix must keep >= 1 token: an exact-length prompt matches
        # only its first block
        assert hit.length == 4
        assert idx.pool.refcount(hit.blocks[0]) == 2
        assert idx.pool.pinned == 1
        hit.release()
        hit.release()  # idempotent
        assert idx.pool.pinned == 0

    def test_pinned_blocks_survive_eviction_pressure(self):
        idx = mk_index(n_blocks=3)
        do_insert(idx, [1, 2, 3, 4])
        hit = idx.lookup([1, 2, 3, 4, 9])
        assert hit is not None
        # needs 3 blocks, pool has 2 free + 1 pinned: insert must be
        # REJECTED, not evict the pinned block
        assert idx.plan_insert([5, 6, 7, 8, 9, 10, 11, 12,
                                13, 14, 15, 16]) is None
        st = idx.stats()
        assert st["rejected"] == 1 and st["evictions"] == 0
        assert idx.match_len([1, 2, 3, 4]) == 4
        hit.release()
        # released: leaf-LRU eviction frees the block for the retry
        plan = idx.plan_insert([5, 6, 7, 8, 9, 10, 11, 12,
                                13, 14, 15, 16])
        assert plan is not None
        plan.commit()
        assert idx.match_len([1, 2, 3, 4]) == 0  # evicted
        assert idx.stats()["evictions"] == 1

    def test_plan_pins_its_own_matched_prefix(self):
        """Regression: extending a resident prefix under pool pressure
        must never evict the matched prefix itself (the plan pins it) —
        the insert is rejected instead, and an unrelated cold leaf is
        still fair game."""
        idx = mk_index(n_blocks=2)
        do_insert(idx, [1, 2, 3, 4])
        # needs 2 new blocks, 1 free, and the only evictable leaf is
        # the matched prefix: must reject, not crash in commit
        assert idx.plan_insert(list(range(1, 13))) is None
        assert idx.match_len([1, 2, 3, 4]) == 4
        assert idx.stats()["rejected"] == 1
        assert idx.pool.pinned == 0  # plan released its pin on failure
        # an unrelated cold leaf still evicts to make room
        idx2 = mk_index(n_blocks=3)
        do_insert(idx2, [9, 9, 9, 9])
        do_insert(idx2, [1, 2, 3, 4])
        plan = idx2.plan_insert([1, 2, 3, 4, 5, 6, 7, 8, 1, 1, 1, 1])
        assert plan is not None
        plan.commit()
        assert idx2.match_len([9, 9, 9, 9]) == 0
        assert idx2.match_len([1, 2, 3, 4, 5, 6, 7, 8, 1, 1, 1, 1]) == 12
        assert idx2.pool.pinned == 0

    def test_leaf_lru_eviction_order(self):
        """The least-recently-touched LEAF goes first; interior nodes
        only become evictable once their children are gone."""
        idx = mk_index(n_blocks=4)
        do_insert(idx, [1, 2, 3, 4])              # parent-to-be
        do_insert(idx, [1, 2, 3, 4, 5, 6, 7, 8])  # child A (leaf)
        do_insert(idx, [1, 2, 3, 4, 9, 9, 9, 9])  # child B (leaf)
        assert idx.pool.free_count == 1
        idx.lookup([1, 2, 3, 4, 5, 6, 7, 8, 0]).release()  # A is MRU
        plan = idx.plan_insert([7, 7, 7, 7, 8, 8, 8, 8])  # needs 2
        assert plan is not None
        plan.commit()
        # B (LRU leaf) was evicted; A and the shared parent survive
        assert idx.match_len([1, 2, 3, 4, 9, 9, 9, 9]) == 4
        assert idx.match_len([1, 2, 3, 4, 5, 6, 7, 8]) == 8

    def test_divergent_insert_splits_edge(self):
        """Inserting a sequence that diverges INSIDE an existing edge
        splits at the block boundary; both descendants keep hitting."""
        idx = mk_index()
        do_insert(idx, list(range(12)))           # one 3-block edge
        do_insert(idx, list(range(8)) + [77, 77, 77, 77])
        assert idx.pool.in_use == 4  # 3 + 1 new (2 shared by reference)
        assert idx.match_len(list(range(12))) == 12
        assert idx.match_len(list(range(8)) + [77, 77, 77, 77]) == 12
        h = idx.lookup(list(range(12)) + [0])
        h2 = idx.lookup(list(range(8)) + [77, 77, 77, 77, 0])
        assert h.blocks[:2] == h2.blocks[:2]  # shared by reference
        assert h.blocks[2] != h2.blocks[2]
        h.release()
        h2.release()

    def test_partial_tail_never_stored(self):
        """plan_insert refuses non-whole-block lengths (callers floor
        to whole blocks); a partial tail never becomes a tree node."""
        idx = mk_index()
        assert idx.plan_insert([1, 2, 3]) is None       # < one block
        assert idx.plan_insert([1, 2, 3, 4, 5]) is None  # ragged tail
        assert idx.pool.in_use == 0
        assert idx.match_len([1, 2, 3, 4, 5]) == 0

    def test_hbm_high_water_tracks_peak(self):
        idx = mk_index(n_blocks=4)
        do_insert(idx, [1, 2, 3, 4, 5, 6, 7, 8])
        assert idx.stats()["hbm_high_water_bytes"] == 200
        # eviction lowers in_use but never the high-water mark
        p = idx.plan_insert([9, 9, 9, 9, 8, 8, 8, 8, 7, 7, 7, 7])
        p.commit()
        st = idx.stats()
        assert st["blocks_in_use"] == 3
        assert st["hbm_high_water_bytes"] == 300

    def test_randomized_model_based(self):
        """A few hundred scripted insert/lookup/evict/refcount ops
        checked against a plain-dict reference model. Phase 1 (no
        eviction pressure): the reference predicts every match length
        exactly. Phase 2 (tight pool): structural invariants — block
        conservation, refcount exactness, pins never freed, matched
        tokens always a true prefix."""
        rng = random.Random(1234)
        bs = 4

        # ---- phase 1: big pool, exact-match reference
        idx = mk_index(n_blocks=512, bs=bs)
        covered: set[tuple] = set()  # every committed block's context

        def ref_match(seq):
            n = 0
            while (n + 1) * bs <= len(seq) and \
                    tuple(seq[:(n + 1) * bs]) in covered:
                n += 1
            return n * bs

        pool_seqs = [[rng.randrange(5) for _ in range(rng.randrange(
            bs, 8 * bs))] for _ in range(40)]
        for _ in range(300):
            seq = rng.choice(pool_seqs)
            op = rng.random()
            if op < 0.5:
                p = bs * (len(seq) // bs)
                plan = idx.plan_insert(seq[:p])
                want_new = (p - ref_match(seq[:p])) // bs
                if want_new == 0 or p == 0:
                    assert plan is None
                else:
                    assert plan is not None
                    assert len(plan.new_ids) == want_new
                    plan.commit()
                    for j in range(p // bs):
                        covered.add(tuple(seq[:(j + 1) * bs]))
            else:
                m = ref_match(seq)
                assert idx.match_len(seq) == m
                hit = idx.lookup(seq)
                want = min(m, bs * ((len(seq) - 1) // bs))
                if want == 0:
                    assert hit is None
                else:
                    assert hit is not None and hit.length == want
                    assert hit.tokens == tuple(seq[:want])
                    hit.release()
        assert idx.pool.in_use == len(covered)
        assert idx.pool.in_use + idx.pool.free_count == 512

        # ---- phase 2: tight pool, invariants under churn
        idx = mk_index(n_blocks=8, bs=bs)
        held = []
        for _ in range(300):
            seq = [rng.randrange(4) for _ in range(rng.randrange(
                bs, 6 * bs))]
            op = rng.random()
            if op < 0.45:
                p = bs * (len(seq) // bs)
                plan = idx.plan_insert(seq[:p])
                if plan is not None:
                    if rng.random() < 0.1:
                        plan.abort()
                    else:
                        plan.commit()
            elif op < 0.8:
                hit = idx.lookup(seq)
                if hit is not None:
                    assert hit.length % bs == 0
                    assert hit.length < len(seq)
                    assert hit.tokens == tuple(seq[:hit.length])
                    if rng.random() < 0.3 and len(held) < 3:
                        held.append(hit)
                    else:
                        hit.release()
            elif held:
                held.pop(rng.randrange(len(held))).release()
            # invariants, every op
            pool = idx.pool
            assert pool.in_use + pool.free_count == pool.n_blocks
            assert pool.in_use * pool.block_bytes == idx.bytes_used
            for h in held:
                for b in h.blocks:
                    assert pool.refcount(b) >= 2  # pinned, never freed
            st = idx.stats()
            assert st["blocks_in_use"] == pool.in_use
        for h in held:
            h.release()
        assert idx.pool.pinned == 0


# ---------------------------------------------------------------------
# Scheduler integration


def run_scheduler_requests(engine, requests):
    sched = Scheduler(engine, debug_invariants=True)
    results = {i: [] for i in range(len(requests))}
    done = {i: threading.Event() for i in range(len(requests))}
    for i, (ids, sampling, max_new) in enumerate(requests):
        def emit(ev, i=i):
            results[i].append(ev)
            if ev.done:
                done[i].set()
        sched.submit(GenRequest(prompt_ids=ids, sampling=sampling,
                                max_new_tokens=max_new, emit=emit,
                                id=f"r{i}"))
    sched.start()
    for ev in done.values():
        assert ev.wait(120), "request did not complete"
    sched.stop()
    return sched, results


class TestSchedulerIntegration:
    def test_hit_miss_partition_streams_match_reference(self, setup):
        """A mixed burst (one novel prompt + several sharing cached
        blocks, INCLUDING an unaligned-history one) partitions into miss
        and hit dispatch units and every stream equals the sequential
        reference."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.prefill_and_insert(0, BASE, SamplingParams())
        engine.release_slot(0)

        prompts = [list(b"a fresh novel one"),
                   BASE[:16] + list(b"Q1"),
                   BASE[:16] + list(b"Q2"),
                   BASE[:13] + list(b"Q3")]  # unaligned 13-token share
        sched, results = run_scheduler_requests(
            engine, [(p, SamplingParams(), 5) for p in prompts])
        for i, p in enumerate(prompts):
            want = ByteTokenizer().decode(
                reference_greedy(cfg, params, p, 5))
            got = "".join(ev.text for ev in results[i])
            assert got.rstrip("�") == want.rstrip("�"), i
        st = engine.prefix_index.stats()
        assert st["hits"] >= 3

    def test_counters_flow_through_scheduler_stats(self, setup):
        cfg, params = setup
        # One slot: the second request admits only after the first
        # completed (and populated the pool), so it must HIT.
        engine = make_engine(cfg, params, slots=1)
        sched, _ = run_scheduler_requests(
            engine, [(BASE, SamplingParams(), 3),
                     (BASE, SamplingParams(), 3)])
        stats = sched.stats()
        assert "prefix_cache" in stats
        pc = stats["prefix_cache"]
        for key in ("hits", "misses", "evictions", "bytes",
                    "budget_bytes", "hit_rate", "blocks_in_use",
                    "blocks_total", "block_tokens",
                    "hbm_high_water_bytes"):
            assert key in pc, key
        assert pc["hits"] >= 1
        # New admission-backlog gauges ride the same stats snapshot.
        assert stats["deferred_depth"] == 0
        assert stats["prefill_jobs_active"] == 0

    def test_disabled_cache_reports_nothing(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params, cache_mb=0)
        sched, _ = run_scheduler_requests(
            engine, [(BASE, SamplingParams(), 3)])
        assert "prefix_cache" not in sched.stats()

    def test_stage_stamps_on_first_event(self, setup):
        """The first event of each request carries the recv/picked/first
        stage stamps (the TTFT attribution chain's scheduler leg)."""
        cfg, params = setup
        engine = make_engine(cfg, params, cache_mb=0)
        _sched, results = run_scheduler_requests(
            engine, [(BASE, SamplingParams(), 4)])
        staged = [ev for ev in results[0] if ev.stages]
        assert len(staged) == 1
        stages = staged[0].stages
        assert stages["recv"] <= stages["picked"] <= stages["first"]


class TestLifecycleLeakRegressions:
    """Regressions for the real L4xx findings symlint's lifecycle
    checker surfaced (the PR-12 crash class, path-sensitively): every
    failure between a plan's acquisition and its commit must abort the
    plan, or the matched-prefix pins and freshly allocated blocks leak
    until restart."""

    def test_plan_insert_eviction_failure_releases_everything(self):
        idx = mk_index(n_blocks=4)
        do_insert(idx, [1, 2, 3, 4, 5, 6, 7, 8])
        do_insert(idx, [9, 10, 11, 12, 13, 14, 15, 16])
        assert idx.pool.free_count == 0

        def boom():
            raise RuntimeError("eviction exploded")

        idx._evict_one = boom
        with pytest.raises(RuntimeError, match="eviction exploded"):
            # shares the first 2 blocks (pinned by the plan), needs a
            # third → alloc fails → eviction raises mid-plan
            idx.plan_insert([1, 2, 3, 4, 5, 6, 7, 8,
                             91, 92, 93, 94])
        del idx._evict_one
        # the matched-prefix pins were released and nothing leaked:
        # tree ownership is the only reference again
        assert idx.pool.pinned == 0
        assert idx.pool.in_use == 4 and idx.pool.free_count == 0
        # the index is still healthy — the same insert succeeds once
        # eviction works again (evicts the other entry's leaf)
        plan = idx.plan_insert([1, 2, 3, 4, 5, 6, 7, 8, 91, 92, 93, 94])
        assert plan is not None and plan.matched_len == 8
        plan.commit()
        assert idx.match_len([1, 2, 3, 4, 5, 6, 7, 8, 91, 92, 93, 94,
                              0]) == 12

    def test_store_prefix_extract_failure_aborts_plan(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params)

        def boom(*a, **kw):
            raise RuntimeError("device error in extract")

        engine._extract_prefix_row = boom
        with pytest.raises(RuntimeError, match="device error"):
            engine._maybe_store_prefix(
                [(0, list(range(16)), SamplingParams())], None)
        pool = engine.prefix_index.pool
        # plan aborted: no pins held, every allocated block returned
        assert pool.pinned == 0 and pool.in_use == 0


class TestGossipSummary:
    """Engine-level contract for the pool-gossip rider cadence."""

    def test_gossip_s_zero_means_always_fresh(self, setup):
        cfg, params = setup
        engine = InferenceEngine(
            cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=64,
            prefill_buckets=(16, 32), cache_dtype=jnp.float32,
            prefill_chunk=8, prefix_cache_bytes=16 * 2**20,
            prefix_block_tokens=8, prefix_gossip_blocks=8,
            prefix_gossip_s=0.0)
        # empty tree gossips nothing — and an explicit 0.0 cadence must
        # not CACHE that None (a heartbeat probe right after the first
        # insertion has to see the summary, not a stale empty walk)
        assert engine.prefix_cache_summary() is None
        plan = engine.prefix_index.plan_insert(list(range(16)))
        assert plan is not None
        plan.commit()
        s = engine.prefix_cache_summary()
        assert s is not None and s["block_tokens"] == 8
        assert len(s["digests"]) == 2

    def test_gossip_s_caches_the_walk(self, setup):
        cfg, params = setup
        engine = InferenceEngine(
            cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=64,
            prefill_buckets=(16, 32), cache_dtype=jnp.float32,
            prefill_chunk=8, prefix_cache_bytes=16 * 2**20,
            prefix_block_tokens=8, prefix_gossip_blocks=8,
            prefix_gossip_s=60.0)
        assert engine.prefix_cache_summary() is None
        plan = engine.prefix_index.plan_insert(list(range(16)))
        plan.commit()
        # within the cadence window the cached (empty) walk is reused
        assert engine.prefix_cache_summary() is None

    def test_gossip_blocks_zero_disables_rider(self, setup):
        cfg, params = setup
        engine = InferenceEngine(
            cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=64,
            prefill_buckets=(16, 32), cache_dtype=jnp.float32,
            prefill_chunk=8, prefix_cache_bytes=16 * 2**20,
            prefix_block_tokens=8, prefix_gossip_blocks=0)
        plan = engine.prefix_index.plan_insert(list(range(16)))
        plan.commit()
        # a populated tree still gossips nothing when the rider is off
        assert engine.prefix_cache_summary() is None
