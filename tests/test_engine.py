"""Engine + continuous-batching scheduler tests (tiny model, CPU).

The key property: a continuous batch must be invisible to each request —
greedy tokens from a slot-batched engine equal tokens from a plain
sequential forward loop, regardless of what the other slots are doing.
"""

import asyncio
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.engine.engine import (
    EngineError,
    InferenceEngine,
    SamplingParams,
)
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler, TokenEvent
from symmetry_tpu.engine.tokenizer import ByteTokenizer
from symmetry_tpu.models import forward, init_cache, init_params, preset


@pytest.fixture(scope="module")
def setup():
    cfg = preset("tiny")
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    return cfg, params


def make_engine(cfg, params, slots=2, seq=64, buckets=(16, 32), block=1,
                prefill_chunk=256):
    return InferenceEngine(cfg, params, ByteTokenizer(), max_slots=slots,
                           max_seq_len=seq, prefill_buckets=buckets,
                           cache_dtype=jnp.float32, decode_block=block,
                           prefill_chunk=prefill_chunk)


def reference_greedy(cfg, params, prompt_ids, n_tokens):
    """Plain sequential decode loop — the engine must reproduce this."""
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


def run_scheduler_requests(engine, requests):
    """Drive a Scheduler synchronously; returns per-request event lists."""
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
    return results


class TestEnginePrimitives:
    def test_greedy_matches_reference(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params)
        prompt = list(b"hello world")
        want = reference_greedy(cfg, params, prompt, 8)

        first = engine.prefill_and_insert(0, prompt, SamplingParams())
        got = [first]
        for _ in range(7):
            got.append(int(engine.decode_step()[0]))
        assert got == want

    def test_two_slots_independent(self, setup):
        """Slot 1's stream must not perturb slot 0's greedy tokens."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        pa, pb = list(b"first prompt"), list(b"second, quite different")
        want_a = reference_greedy(cfg, params, pa, 6)
        want_b = reference_greedy(cfg, params, pb, 6)

        got_a = [engine.prefill_and_insert(0, pa, SamplingParams())]
        # Interleave: insert b after a has started decoding.
        got_a.append(int(engine.decode_step()[0]))
        got_b = [engine.prefill_and_insert(1, pb, SamplingParams())]
        for _ in range(4):
            toks = engine.decode_step()
            got_a.append(int(toks[0]))
            got_b.append(int(toks[1]))
        got_b.append(int(engine.decode_step()[1]))
        assert got_a == want_a
        assert got_b == want_b

    def test_released_and_fresh_lanes_stay_parked(self, setup):
        """Decode attention reads each lane up to its length, so a lane
        nobody owns must not keep one: a fresh lane stays at 0 through
        decode steps, a released lane returns to 0 and stays, and neither
        disturbs the live lane's greedy tokens."""
        cfg, params = setup
        engine = make_engine(cfg, params, slots=3)
        pa, pb = list(b"first prompt"), list(b"second, quite different")
        want_a = reference_greedy(cfg, params, pa, 7)

        got_a = [engine.prefill_and_insert(0, pa, SamplingParams())]
        engine.prefill_and_insert(1, pb, SamplingParams())
        got_a.append(int(engine.decode_step()[0]))
        assert [engine.slot_length(s) for s in range(3)] == [
            len(pa) + 1, len(pb) + 1, 0]
        engine.release_slot(1)
        for _ in range(5):
            got_a.append(int(engine.decode_step()[0]))
        assert [engine.slot_length(s) for s in range(3)] == [
            len(pa) + 6, 0, 0]
        assert got_a == want_a
        # the lane is reusable, and its request decodes like any other
        want_b = reference_greedy(cfg, params, pb, 3)
        got_b = [engine.prefill_and_insert(1, pb, SamplingParams())]
        for _ in range(2):
            got_b.append(int(engine.decode_step()[1]))
        assert got_b == want_b

    def test_a_lane_reused_before_the_next_decode_is_not_parked(self, setup):
        """Parking rides the next decode dispatch; a closed loop refills
        a finished lane before that dispatch, and the new request must
        keep its prompt."""
        cfg, params = setup
        engine = make_engine(cfg, params, slots=2)
        pa, pb = list(b"first prompt"), list(b"second, quite different")
        engine.prefill_and_insert(0, pa, SamplingParams())
        engine.decode_step()
        engine.release_slot(0)
        want_b = reference_greedy(cfg, params, pb, 4)
        got_b = [engine.prefill_and_insert(0, pb, SamplingParams())]
        for _ in range(3):
            got_b.append(int(engine.decode_step()[0]))
        assert got_b == want_b
        assert engine.slot_length(0) == len(pb) + 3

    def test_a_release_is_a_command_every_process_follows(self):
        """Across hosts the lanes to park are an input of the next decode
        program: a follower has to note the same release as the leader
        (the wire form and what a follower does with it; the two-process
        run is tests/test_multihost.py)."""
        from symmetry_tpu.parallel.multihost import (
            CMD_RELEASE, Command, CommandLoop)

        class Follower:
            prefill_buckets = (16,)

            def __init__(self):
                self.released = []

            def release_slot(self, slot):
                self.released.append(slot)

        engine = Follower()
        loop = CommandLoop(engine, is_coordinator=False)
        wire = Command(kind=CMD_RELEASE, slot=5).encode(loop.max_bucket)
        loop._execute(Command.decode(wire))
        assert engine.released == [5]

    def test_prompt_too_long_raises(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params, buckets=(16,))
        with pytest.raises(EngineError, match="exceeds"):
            engine.prefill_and_insert(0, list(range(40)), SamplingParams())

    def test_bucket_selection(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params, buckets=(16, 32))
        assert engine.bucket_for(3) == 16
        assert engine.bucket_for(16) == 16
        assert engine.bucket_for(17) == 32


class TestScheduler:
    def test_streams_match_reference(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params)
        pa, pb = list(b"alpha beta"), list(b"gamma")
        results = run_scheduler_requests(engine, [
            (pa, SamplingParams(), 6),
            (pb, SamplingParams(), 6),
        ])
        for ids, res in ((pa, results[0]), (pb, results[1])):
            want = reference_greedy(cfg, params, ids, 6)
            want_text = ByteTokenizer().decode(want)
            got_text = "".join(ev.text for ev in res)
            # Events carry only completed text; the concatenation must equal
            # the reference decode (modulo a trailing incomplete codepoint,
            # which flush renders as replacement chars).
            assert got_text.rstrip("�") == want_text.rstrip("�")
            assert res[-1].done
            assert res[-1].finish_reason in ("length", "stop")

    def test_more_requests_than_slots(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params, slots=2)
        sched = Scheduler(engine, debug_invariants=True)
        results = {i: [] for i in range(5)}
        done = {i: threading.Event() for i in range(5)}
        for i in range(5):
            def emit(ev, i=i):
                results[i].append(ev)
                if ev.done:
                    done[i].set()
            sched.submit(GenRequest(prompt_ids=list(b"req %d" % i),
                                    sampling=SamplingParams(),
                                    max_new_tokens=4, emit=emit, id=f"r{i}"))
        sched.start()
        for ev in done.values():
            assert ev.wait(120)
        assert all(res[-1].done for res in results.values())
        # All slots free after drain; none leaked.
        assert sched.occupancy == 0
        assert sorted(sched._free) == [0, 1]
        sched.stop()

    def test_block_decode_matches_single_step(self, setup):
        """decode_block=4 must stream the same text as decode_block=1."""
        cfg, params = setup
        prompt = list(b"block decoding test")
        out = {}
        for block in (1, 4):
            engine = make_engine(cfg, params, block=block)
            results = run_scheduler_requests(
                engine, [(prompt, SamplingParams(), 10)])
            out[block] = ("".join(ev.text for ev in results[0]),
                          results[0][-1].finish_reason,
                          results[0][-1].tokens_generated)
        assert out[1] == out[4]

    def test_eos_finishes_stream(self, setup):
        """EOS finishes the stream as "stop" at exactly the position the
        reference sequential decode produces it, and the EOS token itself
        is never emitted as text.

        This test used to bias the lm head's EOS column to a constant
        (lm[:, eos] = 10.0) and assert EOS won within 2 tokens. That was
        not a scheduler race — it was a sign-fragile construction: the
        EOS logit becomes 10·sum(hidden), so whether (and when) EOS is
        the argmax depends on the hidden-state sum, which sits near a
        sign threshold for this prompt/seed. Any numerics drift (BLAS
        kernel order, matmul precision defaults) moved the first-EOS
        position and the `<= 2` bound failed on an unmodified tree.
        Pinning the expectation to the reference decode of the SAME
        biased head asserts the property the test always meant — the
        scheduler stops at the first EOS the model actually produces —
        independent of where that EOS lands."""
        cfg, params = setup
        eos = ByteTokenizer().EOS
        biased = dict(params)
        lm = np.array(params["lm_head"])
        lm[:, eos] = 10.0
        biased["lm_head"] = jnp.asarray(lm)
        budget = 16
        want = reference_greedy(cfg, biased, list(b"hi"), budget)
        assert eos in want, \
            f"lm-head bias no longer yields EOS within {budget} tokens; " \
            f"rebuild the test fixture (got {want})"
        k = want.index(eos) + 1  # tokens_generated counts the EOS
        engine = make_engine(cfg, biased)
        results = run_scheduler_requests(
            engine, [(list(b"hi"), SamplingParams(), budget)])
        last = results[0][-1]
        assert last.finish_reason == "stop"
        assert last.tokens_generated == k
        assert last.tokens_emitted == k - 1  # EOS never streams as text

    def test_capacity_eviction(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params, seq=20, buckets=(16,))
        results = run_scheduler_requests(
            engine, [(list(b"0123456789"), SamplingParams(), 500)])
        last = results[0][-1]
        assert last.done and last.finish_reason == "length"
        # 10 prompt + g generated reaches capacity 20 at g=10.
        assert last.tokens_generated == 10

    def test_cancellation_frees_slot(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params, slots=1)
        sched = Scheduler(engine, debug_invariants=True)
        events: list[TokenEvent] = []
        done = threading.Event()
        cancelled = threading.Event()

        def emit(ev):
            events.append(ev)
            if len(events) >= 2:
                cancelled.set()
            if ev.done:
                done.set()

        sched.submit(GenRequest(
            prompt_ids=list(b"cancel me"), sampling=SamplingParams(),
            max_new_tokens=10_000, emit=emit,
            cancelled=cancelled.is_set, id="c"))
        sched.start()
        assert done.wait(120)
        assert events[-1].finish_reason == "cancelled"
        # Slot must be reusable afterwards.
        done2 = threading.Event()
        sched.submit(GenRequest(
            prompt_ids=list(b"next"), sampling=SamplingParams(),
            max_new_tokens=3, emit=lambda ev: ev.done and done2.set(),
            id="n"))
        assert done2.wait(120)
        sched.stop()

    def test_engine_crash_fails_open_streams(self, setup):
        """A dying engine loop must emit error events, never hang streams."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.decode_steps_dispatch = lambda: (_ for _ in ()).throw(
            RuntimeError("device wedged"))
        sched = Scheduler(engine)
        events = []
        done = threading.Event()

        def emit(ev):
            events.append(ev)
            if ev.done:
                done.set()

        sched.submit(GenRequest(prompt_ids=list(b"boom"),
                                sampling=SamplingParams(),
                                max_new_tokens=10, emit=emit, id="x"))
        sched.start()
        assert done.wait(60)
        assert events[-1].finish_reason == "error"
        assert "device wedged" in events[-1].error

    def test_cancelled_while_queued_gets_terminal_event(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params, slots=1)
        sched = Scheduler(engine)
        ev_a_done = threading.Event()
        ev_b = []
        ev_b_done = threading.Event()
        b_cancelled = threading.Event()
        b_cancelled.set()  # cancelled before it ever reaches a slot

        sched.submit(GenRequest(prompt_ids=list(b"occupier"),
                                sampling=SamplingParams(), max_new_tokens=6,
                                emit=lambda ev: ev.done and ev_a_done.set(),
                                id="a"))
        sched.submit(GenRequest(prompt_ids=list(b"queued"),
                                sampling=SamplingParams(), max_new_tokens=6,
                                emit=lambda ev: (ev_b.append(ev),
                                                 ev.done and ev_b_done.set()),
                                cancelled=b_cancelled.is_set, id="b"))
        sched.start()
        assert ev_a_done.wait(120)
        assert ev_b_done.wait(120)
        assert ev_b[-1].finish_reason == "cancelled"
        sched.stop()

    def test_overlong_prompt_finishes_immediately(self, setup):
        """Prompt with no decode headroom: first token, then length-finish —
        never a decode block whose KV writes would be dropped."""
        cfg, params = setup
        engine = make_engine(cfg, params, seq=20, buckets=(16,), block=8)
        results = run_scheduler_requests(
            engine, [(list(b"0123456789abcdef"), SamplingParams(), 100)])
        last = results[0][-1]
        assert last.done and last.finish_reason == "length"
        assert last.tokens_generated == 1

    def test_ttft_metric_reported(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params)
        results = run_scheduler_requests(
            engine, [(list(b"metrics"), SamplingParams(), 3)])
        ttfts = [ev.ttft_s for ev in results[0] if ev.ttft_s is not None]
        assert ttfts and all(t >= 0 for t in ttfts)


class TestTpuNativeBackend:
    def test_openai_sse_stream(self, setup):
        from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
        from symmetry_tpu.provider.config import ConfigManager

        cfg_mgr = ConfigManager(config={
            "name": "t", "public": False, "serverKey": "00" * 32,
            "modelName": "tiny-test", "apiProvider": "tpu_native",
            "tpu": {"model_preset": "tiny", "dtype": "float32",
                    "max_batch_size": 2, "max_seq_len": 64,
                    "prefill_buckets": [16, 32]},
        })

        async def drive():
            import json as _json

            backend = TpuNativeBackend(cfg_mgr)
            await backend.start()
            assert await backend.healthy()
            chunks = []
            from symmetry_tpu.provider.backends.base import InferenceRequest

            async for ch in backend.stream(InferenceRequest(
                    messages=[{"role": "user", "content": "ping"}],
                    max_tokens=5)):
                chunks.append(ch)
            await backend.stop()
            assert not await backend.healthy()

            assert chunks[0].raw.startswith("data: ")
            first = _json.loads(chunks[0].raw[6:])
            assert first["choices"][0]["delta"] == {"role": "assistant"}
            assert first["model"] == "tiny-test"
            assert chunks[-1].raw == "data: [DONE]"
            assert chunks[-1].done
            fin = _json.loads(chunks[-2].raw[6:])
            assert fin["choices"][0]["finish_reason"] in ("length", "stop")
            return True

        assert asyncio.run(asyncio.wait_for(drive(), 180))


class TestWarmup:
    def test_warmup_then_serve_matches_reference(self, setup):
        """warmup() (pre-traffic decode compile) must not perturb later
        requests: its garbage device writes land beyond every slot's valid
        length and insert resets the lanes it uses."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        engine.warmup()
        prompt = list(b"hello world")
        want = reference_greedy(cfg, params, prompt, 8)
        got = [engine.prefill_and_insert(0, prompt, SamplingParams())]
        for _ in range(7):
            got.append(int(engine.decode_step()[0]))
        assert got == want


class TestSeededReproducibility:
    def test_same_seed_reproduces_full_completion(self, setup):
        """A seeded sampled request must reproduce its ENTIRE completion —
        per-slot RNG streams, not a shared global one — and must be immune
        to other slots' traffic."""
        cfg, params = setup
        engine = make_engine(cfg, params)
        sp = SamplingParams(temperature=0.9, top_p=0.95, seed=123)

        def generate(slot, with_noise):
            toks = [engine.prefill_and_insert(slot, list(b"seeded run"), sp)]
            if with_noise:  # concurrent unseeded stream in the other slot
                engine.prefill_and_insert(1 - slot,
                                          list(b"noise traffic"),
                                          SamplingParams(temperature=1.0))
            for _ in range(8):
                toks.append(int(engine.decode_step()[slot]))
            return toks

        a = generate(0, with_noise=False)
        b = generate(0, with_noise=True)
        c = generate(1, with_noise=False)  # different slot, same seed
        assert a == b == c

    def test_different_seeds_diverge(self, setup):
        cfg, params = setup
        engine = make_engine(cfg, params)

        def gen(seed):
            sp = SamplingParams(temperature=1.0, seed=seed)
            toks = [engine.prefill_and_insert(0, list(b"diverge"), sp)]
            for _ in range(8):
                toks.append(int(engine.decode_step()[0]))
            return toks

        assert gen(1) != gen(2)

    @pytest.mark.parametrize("sp", [
        SamplingParams(),
        SamplingParams(temperature=0.9, top_p=0.95, seed=123),
        SamplingParams(temperature=1.2, top_k=40, seed=7),
    ], ids=["greedy", "top_p", "top_k"])
    def test_grouped_top_k_programs_emit_the_same_tokens(self, setup, sp,
                                                         monkeypatch):
        """The served programs (prefill, chunk_final, decode) with the
        sampler's staged selection engaged — tiny's 512 logits as 128
        groups of 4, the 256 kept as 128 sub-groups of 2 — emit the tokens
        the single lax.top_k gives."""
        from symmetry_tpu.ops import sampling
        cfg, params = setup
        prompt = list(b"a prompt long enough to be chunked in two")

        def generate(chunk):
            engine = make_engine(cfg, params, buckets=(16, 32, 64),
                                 prefill_chunk=chunk)
            toks = [engine.prefill_and_insert(0, prompt, sp)]
            return toks + [int(engine.decode_step()[0]) for _ in range(8)]

        want = [generate(256), generate(16)]
        monkeypatch.setattr(sampling, "TOP_K_GROUP_WIDTH", 4)
        monkeypatch.setattr(sampling, "TOP_K_SUBGROUP_WIDTH", 2)
        assert sampling.top_k_route(cfg.vocab_size) == {
            "top_k": "grouped", "cap": 64, "ranked": 128,
            "stages": [{"groups": 128, "width": 4},
                       {"groups": 128, "width": 2}]}
        assert [generate(256), generate(16)] == want


class TestCoalescedPrefill:
    def test_prefill_many_matches_sequential(self, setup):
        """A coalesced 3-prompt prefill must produce exactly what three
        sequential prefills produce (greedy), then decode correctly."""
        cfg, params = setup
        prompts = [list(b"first"), list(b"the second one"), list(b"third!")]
        wants = [reference_greedy(cfg, params, p, 5) for p in prompts]

        engine = make_engine(cfg, params, slots=4)
        firsts = engine.prefill_and_insert_many(
            [(i, p, SamplingParams()) for i, p in enumerate(prompts)])
        got = [[f] for f in firsts]
        for _ in range(4):
            toks = engine.decode_step()
            for i in range(3):
                got[i].append(int(toks[i]))
        assert got == wants

    def test_prefill_many_mixed_buckets(self, setup):
        """Prompts from different buckets coalesce at the largest bucket."""
        cfg, params = setup
        engine = make_engine(cfg, params, slots=4, buckets=(16, 32))
        short, long = list(b"abc"), list(range(1, 25))
        w_short = reference_greedy(cfg, params, short, 3)
        w_long = reference_greedy(cfg, params, long, 3)
        firsts = engine.prefill_and_insert_many(
            [(0, short, SamplingParams()), (1, long, SamplingParams())])
        got0, got1 = [firsts[0]], [firsts[1]]
        for _ in range(2):
            toks = engine.decode_step()
            got0.append(int(toks[0]))
            got1.append(int(toks[1]))
        assert got0 == w_short
        assert got1 == w_long

    def test_scheduler_coalesces_burst(self, setup):
        """A burst of queued requests admits in grouped prefills and every
        stream still matches the sequential reference."""
        cfg, params = setup
        engine = make_engine(cfg, params, slots=4)
        prompts = [list(b"r0"), list(b"req one"), list(b"request two"),
                   list(b"rrr three")]
        results = run_scheduler_requests(
            engine, [(p, SamplingParams(), 5) for p in prompts])
        for i, p in enumerate(prompts):
            want_text = ByteTokenizer().decode(reference_greedy(
                cfg, params, p, 5))
            got_text = "".join(ev.text for ev in results[i])
            assert got_text.rstrip("�") == want_text.rstrip("�")

    def test_empty_prompt_fails_alone_in_batch(self, setup):
        """An empty prompt in an admission burst must error individually,
        not poison the coalesced group."""
        cfg, params = setup
        engine = make_engine(cfg, params, slots=4)
        good = list(b"fine")
        want = reference_greedy(cfg, params, good, 4)
        results = run_scheduler_requests(engine, [
            (good, SamplingParams(), 4),
            ([], SamplingParams(), 4),
            (good, SamplingParams(), 4),
        ])
        assert results[1][-1].finish_reason == "error"
        for idx in (0, 2):
            got = "".join(ev.text for ev in results[idx])
            want_text = ByteTokenizer().decode(want)
            assert got.rstrip("�") == want_text.rstrip("�")


class TestChunkedPrefill:
    """Chunked prefill (engine.ChunkedPrefill): a long prompt's prefix is
    built chunk-by-chunk so admission never stalls active decode streams —
    and the result must be BIT-IDENTICAL to the monolithic prefill."""

    def test_matches_monolithic_prefill(self, setup):
        cfg, params = setup
        prompt = list(b"a fairly long prompt that spans several chunks!")
        want = reference_greedy(cfg, params, prompt, 6)

        engine = make_engine(cfg, params, buckets=(64,), prefill_chunk=16)
        assert engine.wants_chunked(len(prompt))
        job = engine.start_chunked_prefill(0, prompt, SamplingParams())
        assert job.n_chunks == 3
        first = None
        steps = 0
        while first is None:
            first = engine.advance_chunked_prefill(job)
            steps += 1
        assert steps == job.n_chunks  # one device dispatch per chunk
        got = [first]
        for _ in range(5):
            got.append(int(engine.decode_step()[0]))
        assert got == want

    def test_chunked_alongside_active_decode(self, setup):
        """A chunked prefill must not perturb an active slot's stream."""
        cfg, params = setup
        pa = list(b"short")
        pb = list(b"a fairly long prompt that spans several chunks!")
        want_a = reference_greedy(cfg, params, pa, 10)
        want_b = reference_greedy(cfg, params, pb, 4)

        engine = make_engine(cfg, params, buckets=(16, 64), prefill_chunk=16)
        got_a = [engine.prefill_and_insert(0, pa, SamplingParams())]
        got_a.append(int(engine.decode_step()[0]))
        job = engine.start_chunked_prefill(1, pb, SamplingParams())
        first_b = engine.advance_chunked_prefill(job)
        assert first_b is None
        got_a.append(int(engine.decode_step()[0]))  # decode between chunks
        first_b = engine.advance_chunked_prefill(job)
        got_a.append(int(engine.decode_step()[0]))
        first_b = engine.advance_chunked_prefill(job)
        assert first_b is not None
        got_b = [first_b]
        for _ in range(3):
            toks = engine.decode_step()
            got_a.append(int(toks[0]))
            got_b.append(int(toks[1]))
        for _ in range(3):
            got_a.append(int(engine.decode_step()[0]))
        assert got_a == want_a
        assert got_b == want_b

    def test_scheduler_routes_long_prompts_through_chunks(self, setup):
        cfg, params = setup
        prompt = list(b"a fairly long prompt that spans several chunks!")
        want = reference_greedy(cfg, params, prompt, 6)
        want_text = ByteTokenizer().decode(want)

        engine = make_engine(cfg, params, buckets=(16, 64), prefill_chunk=16)
        results = run_scheduler_requests(
            engine, [(prompt, SamplingParams(), 6)])
        got_text = "".join(ev.text for ev in results[0])
        assert got_text.rstrip("�") == want_text.rstrip("�")
        assert results[0][-1].done


class TestCoalescedPadRows:
    def test_pad_row_overwrite_is_identical(self, setup):
        """A non-full coalesced batch pads by replaying the LAST request —
        with the SAME PRNG keys, so the pad row's overwrite of that slot
        is bit-identical. A fresh-entropy pad would sample a different
        first token and leave decode conditioned on a token the client
        never received (round-3 review finding)."""
        cfg, params = setup
        engine = make_engine(cfg, params, slots=4)
        reqs = [(s, list(b"pad row check %d" % s),
                 SamplingParams(temperature=0.9))  # unseeded + sampled
                for s in range(3)]  # 3 requests -> batch pads to 4
        firsts = engine.prefill_and_insert_many(reqs)
        # the device state each slot will decode from must be exactly the
        # token the caller returned to the stream
        for (slot, _, _), first in zip(reqs, firsts):
            assert int(engine.state.last_token[slot]) == first


class TestGroupKeys:
    """One dispatch derives a whole batch's PRNG keys (PR 31): each row
    must be bit-identical to the one-request derivation every other
    admission path uses, pad rows included."""

    SAMPLINGS = [
        SamplingParams(),
        SamplingParams(temperature=0.8, seed=7),
        SamplingParams(temperature=1.0),
        SamplingParams(temperature=0.5, seed=2**31 + 5),
        SamplingParams(seed=9, rng_skip=3),
        SamplingParams(seed=2**40 + 12345),
        SamplingParams(seed=0),
        SamplingParams(seed=2**32 - 1),
    ]

    @pytest.mark.parametrize("rows,batch", [
        ((0, 1, 2), 4), ((0, 1), 2), ((0, 1, 2, 3, 4), 8), ((2,), 1),
        ((1, 3), 4), ((0, 2), 2), ((5, 6, 7), 4), ((1, 5, 3, 4), 16),
        ((4,), 2)])
    def test_rows_match_request_keys(self, setup, rows, batch):
        cfg, params = setup
        engine = make_engine(cfg, params, slots=4)
        group = [self.SAMPLINGS[i] for i in rows]
        engine._requests_served = 10
        want = [engine._request_keys(s) for s in group]
        served = engine._requests_served
        engine._requests_served = 10
        prefill_keys, decode_keys = engine._group_keys(group, batch)
        assert engine._requests_served == served
        assert prefill_keys.shape == decode_keys.shape == (batch,)
        for i in range(batch):
            pk, dk = want[min(i, len(group) - 1)]
            for got, ref in ((prefill_keys[i], pk), (decode_keys[i], dk)):
                assert np.array_equal(jax.random.key_data(got),
                                      jax.random.key_data(ref)), (i, rows)


class TestPrefillScratchPool:
    def test_pool_is_lru_bounded(self, setup):
        """The persistent prefill scratch pool must stay bounded: pinning
        every (batch, bucket) grid shape forever would cost more steady
        HBM than the per-dispatch churn it replaces (round-4 review)."""
        cfg, params = setup
        engine = InferenceEngine(
            cfg, params, ByteTokenizer(), max_slots=8, max_seq_len=64,
            prefill_buckets=(16, 32), cache_dtype=jnp.float32,
            prefill_token_budget=64)
        engine.warmup()  # touches the whole grid
        lanes = sum(b * bk for (b, bk) in engine._prefill_scratch)
        assert lanes <= 3 * engine.prefill_token_budget, lanes

        # a shape in active reuse stays pooled (no realloc churn)
        prompt = list(b"twelve bytes")
        engine.prefill_and_insert_many(
            [(s, prompt, SamplingParams()) for s in range(4)])
        key = (4, 16)
        pooled = engine._prefill_scratch.get(key)
        assert pooled is not None
        engine.prefill_and_insert_many(
            [(s, prompt, SamplingParams()) for s in range(4, 8)])
        assert engine._prefill_scratch.get(key) is not None

    def test_scratch_reuse_is_correct(self, setup):
        """Back-to-back same-shape prefills through the donated scratch
        must match fresh sequential references (dirty-buffer reuse)."""
        cfg, params = setup
        engine = make_engine(cfg, params, slots=2)
        p1, p2 = list(b"hello scratch"), list(b"other prompt!")
        want1 = reference_greedy(cfg, params, p1, 3)
        want2 = reference_greedy(cfg, params, p2, 3)
        got1 = [engine.prefill_and_insert(0, p1, SamplingParams())]
        got2 = [engine.prefill_and_insert(1, p2, SamplingParams())]
        for _ in range(2):
            toks = engine.decode_step()
            got1.append(int(toks[0]))
            got2.append(int(toks[1]))
        assert got1 == want1 and got2 == want2


def test_a_mesh_with_a_stage_axis_is_refused_at_engine_build(setup):
    """No stage schedule exists: a mesh with stage > 1 is refused with the
    axis named — by the constructor, which from_tpu_config ends in — not
    served as replication."""
    from symmetry_tpu.parallel.mesh import MeshSpec, build_mesh
    from symmetry_tpu.provider.config import TpuConfig

    cfg, params = setup
    mesh = build_mesh(MeshSpec(stage=2), jax.devices()[:2])
    with pytest.raises(EngineError, match="'stage'.*no stage schedule"):
        InferenceEngine(cfg, params, ByteTokenizer(), mesh=mesh)
    with pytest.raises(EngineError, match="'stage'.*no stage schedule"):
        InferenceEngine.from_tpu_config(
            TpuConfig(model_preset="tiny", mesh={"stage": 2, "model": 1}))
    # the axis at size 1 is every other mesh: accepted
    InferenceEngine(cfg, params, ByteTokenizer(), max_slots=2,
                    max_seq_len=64, prefill_buckets=(16,),
                    cache_dtype=jnp.float32,
                    mesh=build_mesh(MeshSpec(stage=1), jax.devices()[:1]))

