#!/usr/bin/env python3
"""CI smoke for the overlapped scheduler pipeline (no TPU, no network).

Phase 1 — token identity across pipeline depths: the SAME mixed traffic
(greedy + seeded sampled, radix-hitting shared prefixes + cold misses,
speculation enabled with an LM-head bias that actually drafts) runs on
a tiny CPU engine at pipeline_depth 1 (the pre-pipeline double buffer)
and 2 (the overlapped default), two waves each so wave 2 re-admits
through warm radix hits. Every request's event stream must match across
depths — text, token ids, generated/emitted counts, finish reason — in
strict per-request order, the speculative counters must agree exactly,
and NEITHER depth may compile anything after its first wave
(compile_cache_sizes pinned between waves = zero steady-state
recompiles).

Phase 2 — the split the tentpole promises: depth-2 stats must carry the
dispatch-thread vs offloaded wall split, the configured + live depth
gauges, the emit-queue depth, and evidence the emit worker actually
absorbed work (offloaded_s > 0, flushes > 0).

Run: python tools/overlap_smoke.py
"""

from __future__ import annotations

import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def log(msg: str) -> None:
    print(f"[overlap_smoke] {msg}", flush=True)


# Shared prefix long enough to span whole radix blocks (prefix_block 8)
# so the second admission of the pair reuses cached KV; the loner prompt
# shares nothing and stays a miss on wave 1.
_BASE = list(b"shared prefix radix AAAA")
_PROMPTS = [
    _BASE + list(b" one"),
    _BASE + list(b" two"),
    list(b"a completely different cold prompt"),
    list(b"x!"),
]


def _requests():
    from symmetry_tpu.engine.engine import SamplingParams

    reqs = [(p, SamplingParams(), 24) for p in _PROMPTS]
    # One seeded sampled stream rides along: depth must not perturb the
    # per-slot RNG chain either (same host decisions => same draws).
    reqs.append((list(b"seeded sampled stream"),
                 SamplingParams(temperature=0.8, top_k=8, seed=1234), 24))
    return reqs


def _run_wave(sched, reqs, wave: int):
    from symmetry_tpu.engine.scheduler import GenRequest

    results = {i: [] for i in range(len(reqs))}
    done = {i: threading.Event() for i in range(len(reqs))}
    for i, (ids, sampling, max_new) in enumerate(reqs):
        def emit(ev, i=i):
            results[i].append(ev)
            if ev.done:
                done[i].set()
        sched.submit(GenRequest(prompt_ids=list(ids), sampling=sampling,
                                max_new_tokens=max_new, emit=emit,
                                id=f"w{wave}r{i}"))
    for i, ev in done.items():
        assert ev.wait(180), f"wave {wave} request {i} did not complete"
    return results


def _signature(events):
    """Order-sensitive identity signature of one request's stream."""
    text = "".join(ev.text for ev in events)
    ids = [ev.token_id for ev in events if ev.token_id is not None]
    last = events[-1]
    return (text, ids, last.tokens_generated, last.tokens_emitted,
            last.finish_reason)


def _check_order(events, label: str) -> None:
    assert events, f"{label}: no events"
    assert events[-1].done, f"{label}: last event is not done"
    assert sum(1 for ev in events if ev.done) == 1, \
        f"{label}: more than one done event"
    gen = [ev.tokens_generated for ev in events]
    assert gen == sorted(gen), \
        f"{label}: tokens_generated not monotonic: {gen}"


def _run_depth(depth: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from symmetry_tpu.engine.engine import InferenceEngine
    from symmetry_tpu.engine.scheduler import Scheduler
    from symmetry_tpu.engine.spec import SpecConfig
    from symmetry_tpu.engine.tokenizer import ByteTokenizer
    from symmetry_tpu.models import init_params, preset

    cfg = preset("tiny")
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    # Bias the LM head toward one token so the n-gram drafter matches
    # often enough to drive real verify dispatches through the pipeline
    # (the test_spec.py cycling idiom).
    lm = np.array(params["lm_head"])
    lm[:, 120] = 10.0
    params = dict(params)
    params["lm_head"] = jnp.asarray(lm)

    engine = InferenceEngine(
        cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=128,
        prefill_buckets=(16, 48), cache_dtype=jnp.float32,
        decode_block=4, prefill_chunk=16,
        prefix_cache_bytes=8 * 2**20, prefix_block_tokens=8,
        speculative=SpecConfig(k_draft=4))
    engine.warmup()
    sched = Scheduler(engine, debug_invariants=True, pipeline_depth=depth)
    sched.start()
    try:
        reqs = _requests()
        wave1 = _run_wave(sched, reqs, 1)
        sizes1 = engine.compile_cache_sizes()
        wave2 = _run_wave(sched, reqs, 2)
        sizes2 = engine.compile_cache_sizes()
    finally:
        sched.stop()
    assert sizes1 == sizes2, \
        (f"depth {depth}: steady-state recompile between waves: "
         f"{sizes1} -> {sizes2}")
    stats = sched.stats()
    for wave, results in (("w1", wave1), ("w2", wave2)):
        for i, events in results.items():
            _check_order(events, f"depth {depth} {wave} r{i}")
    sigs = {wave: {i: _signature(evs) for i, evs in results.items()}
            for wave, results in (("w1", wave1), ("w2", wave2))}
    return sigs, stats


def phase1_identity():
    sigs1, stats1 = _run_depth(1)
    sigs2, stats2 = _run_depth(2)
    for wave in ("w1", "w2"):
        for i in sigs1[wave]:
            assert sigs1[wave][i] == sigs2[wave][i], (
                f"depth 1 vs 2 diverged on {wave} r{i}:\n"
                f"  depth1={sigs1[wave][i]}\n  depth2={sigs2[wave][i]}")
    # The identity claim must not be vacuous: both depths drove real
    # speculative verify traffic and real radix reuse, identically.
    for stats, d in ((stats1, 1), (stats2, 2)):
        spec = stats.get("speculative") or {}
        assert spec.get("verify_blocks", 0) > 0, \
            f"depth {d}: no verify blocks ran — spec path unexercised"
        assert spec.get("drafted", 0) > 0, f"depth {d}: nothing drafted"
        pc = stats.get("prefix_cache") or {}
        assert pc.get("hits", 0) > 0, f"depth {d}: no radix hits"
        assert pc.get("misses", 0) > 0, f"depth {d}: no radix misses"
    s1, s2 = stats1["speculative"], stats2["speculative"]
    for key in ("verify_blocks", "drafted", "accepted", "rolled_back"):
        assert s1[key] == s2[key], \
            f"speculative counter {key} differs: {s1[key]} vs {s2[key]}"
    log(f"phase 1 OK: {len(sigs1['w1'])} streams x 2 waves identical at "
        f"depth 1 and 2 (spec: {s1['verify_blocks']} verify blocks, "
        f"{s1['accepted']}/{s1['drafted']} accepted; zero recompiles)")
    return stats1, stats2


def phase2_split(stats1, stats2) -> None:
    assert stats1["pipeline_depth"] == 1, stats1["pipeline_depth"]
    assert stats2["pipeline_depth"] == 2, stats2["pipeline_depth"]
    for stats, d in ((stats1, 1), (stats2, 2)):
        assert "pipeline_live_depth" in stats, f"depth {d}: no live gauge"
        assert "emit_queue_depth" in stats, f"depth {d}: no queue gauge"
        assert stats.get("dispatch_thread_s", 0) > 0, \
            f"depth {d}: no dispatch-thread wall recorded"
        assert stats.get("emit_flushes", 0) > 0, f"depth {d}: no flushes"
        dtb = stats.get("dispatch_thread_block_s") or {}
        assert dtb.get("p50") is not None, \
            f"depth {d}: no dispatch-thread block histogram"
    # Depth 1 is the pre-pipeline A/B baseline: emit stays INLINE on the
    # engine thread (zero offloaded wall); depth 2's emit worker must
    # have actually absorbed the per-block work.
    assert stats1.get("offloaded_s", 0) == 0, \
        f"depth 1 offloaded work ({stats1['offloaded_s']}s) — the A/B " \
        f"baseline must keep the inline emit path"
    assert stats2.get("offloaded_s", 0) > 0, \
        "depth 2: emit worker absorbed no work"
    log(f"phase 2 OK: dispatch_thread_s/offloaded_s split present "
        f"(depth 2: {stats2['dispatch_thread_s']}s thread / "
        f"{stats2['offloaded_s']}s offloaded)")


def main() -> int:
    t0 = time.monotonic()
    stats1, stats2 = phase1_identity()
    phase2_split(stats1, stats2)
    log(f"ALL PHASES OK in {time.monotonic() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
