"""CI disagg smoke: two-host prefill→decode handoff, then a prefill
crash that completes via the restarting-shed/failover path, then the
CROSS-MACHINE link variant with an injected mid-handoff link drop.

The provider's tpu_native backend runs in `tpu.role: disagg` — a REAL
prefill engine host and a REAL decode engine host (tiny CPU preset, own
OS processes, JSON-lines pipes) with versioned KV handoff frames between
them — and the smoke asserts:

  phase 1 (happy path): a streamed request completes; the engine stats
  carry the handoff ledger (frames/bytes > 0, the decode host reporting
  role "decode" with ZERO admission-prefill dispatches, the prefill
  host nested with role "prefill" and its serialize counters) — the
  host stats → provider stats contract of the acceptance criteria,
  end to end through real pipes.

  phase 2 (fault injection): `disagg.handoff=crash@nth=2` is armed in
  the PREFILL tier only (per-tier faults via tpu.disagg.prefill.faults)
  — the second request's handoff kills the prefill host mid-request,
  with the prompt's KV built but unshipped. The in-flight stream must
  get the retryable restarting shed, the supervisor must respawn the
  PAIR (exactly one restart, circuit breaker closed), and a retry must
  complete on the new pair. (nth=2 counts per host LIFE: life 1 serves
  request 1 then dies on request 2's handoff; life 2 serves the retry —
  its first handoff — untouched.)

  phase 3 (TCP link chaos, always runs after either mode): the backend
  runs in NETWORK mode (`tpu.disagg.peer` + inline PrefillNode) — the
  tiers connected ONLY through the chunked/credit-gated handoff link
  over real TCP loopback (engine/disagg/net.py). Request 1 proves the
  happy path and the wire-split stats (wire_frames/wire_s beside the
  prefill host's serialize_s). Then `disagg.net.drop_link=drop_frame@
  nth=2` cuts the link mid-handoff on request 2: the decode tier must
  DISCARD the partial transfer (zero partial adoptions — the decode
  host's adopt error counter stays 0), shed the in-flight request
  structured-retryable, reconnect with backoff, and complete the retry
  on the re-established link.

  phase 4 (pool churn): a 2×1 elastic pool loses a prefill node under
  load; everything completes via retryable shed + re-placement.

  phase 5 (cache affinity): a 2×2 pool serves a multi-turn session —
  turn 2 must affinity-route back to the member whose gossiped radix
  summary covers the session prefix (counter asserted), the per-member
  shipped-block ledger must make the warm handoff partial, and killing
  the warm member must degrade to a clean cold re-place.

Two modes for phases 1–2, same contracts:
  - full path (default): client → server → provider over the in-memory
    transport, recovery via client failover (ChatRestart sentinel);
  - backend-direct (fallback when the `cryptography` network dependency
    is absent): TpuNativeBackend driven directly, recovery via the
    BackendRestartingError retry loop the provider/client implement.

Exit 0 on success; exit 1 with a reason otherwise.

Run: python tools/disagg_smoke.py
"""

from __future__ import annotations

import asyncio
import os
import sys

# CPU pinning BEFORE any jax import (the engine hosts inherit this
# environment). Every host resolves the same compile cache
# (utils/compile_cache.py), which makes the post-crash respawn a warm
# start and keeps this smoke affordable.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Sized to fit the 64 bucket with the byte-tokenizer chat template
# (~19 ids) while spanning >= 2 alignment boundaries (align 16), so the
# handoff carries real KV and the decode tier admits through adoption.
PROMPT = "tell me about disagg serving"


def provider_config_dict() -> dict:
    return {
        "name": "disagg-smoke-prov", "public": True,
        "serverKey": "00" * 32,
        "modelName": "tiny:disagg", "apiProvider": "tpu_native",
        "dataCollectionEnabled": False,
        "flightRecorder": {"enabled": False},
        "tpu": {
            "model_preset": "tiny", "dtype": "float32",
            "max_batch_size": 4, "max_seq_len": 128,
            "prefill_buckets": [32, 64], "prefill_chunk": 16,
            "role": "disagg",
            "supervisor": {"heartbeat_s": 2.0, "wedge_timeout_s": 5.0,
                           "backoff_base_s": 0.2, "backoff_max_s": 1.0,
                           "max_respawns": 3, "spawn_timeout_s": 300.0,
                           "stop_grace_s": 5.0, "min_stable_s": 0.5},
            # Per-tier fault: the PREFILL host's THIRD handoff crashes
            # it (phase 2 — handoffs 1 and 2 are phase 1's cold request
            # and phase 1b's warm block-manifest request); the decode
            # host is never armed.
            "disagg": {"prefill": {
                "faults": {"disagg.handoff": "crash@nth=3"}}},
        },
    }


# Phase 1b: a SECOND request that extends PROMPT — it shares every
# whole block of the first request's prefix, so its handoff frame must
# ship only the non-resident tail blocks (the shared ones ride the
# digest manifest and are adopted by reference on the decode tier).
PROMPT_WARM = PROMPT + " blocks"  # still fits the 64 bucket


def assert_warm_handoff(dg_cold: dict, dg_warm: dict) -> tuple[int, int]:
    """Counter-assert the incremental handoff: the warm frame shipped
    strictly fewer bytes than the cold one, some blocks were
    manifest-only (skipped), and some still shipped (the new tail)."""
    cold_bytes = dg_cold["handoff_bytes"]
    warm_bytes = dg_warm["handoff_bytes"] - cold_bytes
    assert 0 < warm_bytes < cold_bytes, \
        f"warm handoff not incremental: cold={cold_bytes} warm={warm_bytes}"
    blocks = dg_warm.get("blocks", 0) - dg_cold.get("blocks", 0)
    shipped = (dg_warm.get("blocks_shipped", 0)
               - dg_cold.get("blocks_shipped", 0))
    assert blocks > 0, f"warm handoff carried no block manifest: {dg_warm}"
    assert shipped < blocks, \
        f"warm handoff shipped every block ({shipped}/{blocks}) — " \
        f"the resident-prefix skip never engaged"
    return warm_bytes, cold_bytes


def assert_phase1_stats(stats: dict) -> dict:
    assert stats.get("role") == "decode", \
        f"decode host role wrong: {stats.get('role')}"
    # Decode tier books ADOPTION, not admission prefill: the prompt is
    # long enough for an aligned prefix, so zero admit dispatches.
    assert stats.get("admit_dispatches") == 0, \
        f"decode host inherited unified admission accounting: " \
        f"{stats.get('admit_dispatches')} admit dispatches"
    assert stats.get("adopt_dispatches", 0) >= 1, "no adoption dispatch"
    dg = stats.get("disagg") or {}
    assert dg.get("handoff_frames", 0) >= 1, f"no handoff counted: {dg}"
    assert dg.get("handoff_bytes", 0) > 0
    assert (dg.get("prefill_tier_s") or {}).get("count", 0) >= 1
    ph = dg.get("prefill_host") or {}
    assert ph.get("role") == "prefill", f"prefill host stats: {ph}"
    assert (ph.get("handoff") or {}).get("frames", 0) >= 1
    assert ph.get("handoffs", 0) >= 1  # scheduler-side counter
    # Prefill work lives HERE (this prompt spans > 1 chunk, so it lands
    # as chunk dispatches; short prompts would land as admit dispatches)
    assert (ph.get("admit_dispatches", 0)
            + ph.get("chunk_dispatches", 0)) >= 1
    return dg


async def run_backend_direct() -> int:
    """The two-host contract without the network layer (used when the
    `cryptography` dependency for the wire path is unavailable)."""
    from symmetry_tpu.provider.backends.base import (
        BackendRestartingError, InferenceRequest)
    from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
    from symmetry_tpu.provider.config import ConfigManager

    async def collect(backend, content):
        text = []
        async for chunk in backend.stream(InferenceRequest(
                messages=[{"role": "user", "content": content}],
                max_tokens=8, temperature=0.0)):
            if chunk.text:
                text.append(chunk.text)
        return "".join(text)

    backend = TpuNativeBackend(ConfigManager(
        config=provider_config_dict()))
    restarts_seen = []
    try:
        await backend.start()
        backend.on_host_restart = restarts_seen.append

        # phase 1: happy-path handoff
        text1 = await collect(backend, PROMPT)
        assert text1, "phase 1 streamed no text"
        dg = assert_phase1_stats(await backend.engine_stats())
        print(f"disagg smoke: phase 1 streamed {len(text1)} chars; "
              f"{dg['handoff_frames']} handoff frame(s), "
              f"{dg['handoff_bytes']} bytes, prefill-tier p50 "
              f"{(dg.get('prefill_tier_s') or {}).get('p50')}s")

        # phase 1b: block-manifest incremental handoff — the second
        # request extends PROMPT, its shared prefix blocks are already
        # resident on the decode tier, and the wire must carry only the
        # non-resident tail.
        text1b = await collect(backend, PROMPT_WARM)
        assert text1b, "phase 1b streamed no text"
        dg1b = (await backend.engine_stats()).get("disagg") or {}
        warm_bytes, cold_bytes = assert_warm_handoff(dg, dg1b)
        print(f"disagg smoke: phase 1b warm handoff shipped "
              f"{warm_bytes} bytes vs {cold_bytes} cold "
              f"({dg1b.get('blocks_shipped', 0) - dg.get('blocks_shipped', 0)}"
              f"/{dg1b.get('blocks', 0) - dg.get('blocks', 0)} blocks "
              f"on the wire)")

        # phase 2: prefill-host crash mid-request → restarting shed →
        # respawned pair serves the retry
        shed = False
        try:
            await collect(backend, PROMPT + " again?")
        except BackendRestartingError as exc:
            shed = True
            assert exc.retry_after_s is not None
        assert shed, "prefill crash did not shed as restarting"
        # The respawn (and its flight-recorder hook) runs async in the
        # supervisor — give it a beat before asserting on the hook.
        for _ in range(100):
            if restarts_seen:
                break
            await asyncio.sleep(0.1)
        assert restarts_seen == ["crash"], f"hook saw {restarts_seen}"
        text2 = None
        for _ in range(200):  # retry through the respawn window
            try:
                text2 = await collect(backend, PROMPT + " again?")
                break
            except BackendRestartingError:
                await asyncio.sleep(0.25)
        assert text2, "retry never completed on the respawned pair"
        stats2 = await backend.engine_stats()
        sup = stats2.get("supervisor") or {}
        assert sup.get("restarts", 0) >= 1, f"no restart recorded: {sup}"
        assert not sup.get("circuit_open"), "circuit breaker tripped"
        assert await backend.healthy()
        print(f"disagg smoke: phase 2 crash → restarting shed → retry "
              f"completed {len(text2)} chars on the respawned pair "
              f"(supervisor restarts={sup.get('restarts')})")
    finally:
        await backend.stop()
    return 0


async def run_network() -> int:
    """The full path: client → server → provider on the in-memory
    transport, recovery via client failover."""
    from symmetry_tpu.client.client import ChatRestart, SymmetryClient
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.provider.config import ConfigManager
    from symmetry_tpu.provider.provider import SymmetryProvider
    from symmetry_tpu.server.broker import SymmetryServer
    from symmetry_tpu.transport.memory import MemoryTransport

    hub = MemoryTransport()
    server_ident = Identity.from_name("disagg-smoke-server")
    server = SymmetryServer(server_ident, hub, ping_interval_s=30.0)
    await server.start("mem://server")

    cfg_dict = provider_config_dict()
    cfg_dict["serverKey"] = server_ident.public_hex
    provider = SymmetryProvider(
        ConfigManager(config=cfg_dict), transport=hub,
        identity=Identity.from_name("disagg-smoke-p"),
        server_address="mem://server")
    await provider.start("mem://disagg-smoke-p")
    await provider.wait_registered()

    client = SymmetryClient(Identity.from_name("disagg-smoke-cli"), hub)

    # phase 1: happy-path handoff through the wire
    deltas = []
    async for item in client.chat_failover(
            "mem://server", server_ident.public_key, "tiny:disagg",
            [{"role": "user", "content": PROMPT}], max_tokens=8,
            temperature=0.0):
        deltas.append(item)
    assert not any(isinstance(d, ChatRestart) for d in deltas), \
        "phase 1 must not restart"
    text1 = "".join(d for d in deltas if isinstance(d, str))
    assert text1, "phase 1 streamed no text"
    dg = assert_phase1_stats(await provider.backend.engine_stats())
    print(f"disagg smoke: phase 1 streamed {len(text1)} chars over the "
          f"wire; {dg['handoff_frames']} handoff frame(s), "
          f"{dg['handoff_bytes']} bytes, prefill-tier p50 "
          f"{(dg.get('prefill_tier_s') or {}).get('p50')}s")

    # phase 1b: warm block-manifest handoff through the wire — shared
    # prefix blocks ride the manifest only, the tail ships.
    deltas1b = []
    async for item in client.chat_failover(
            "mem://server", server_ident.public_key, "tiny:disagg",
            [{"role": "user", "content": PROMPT_WARM}], max_tokens=8,
            temperature=0.0):
        deltas1b.append(item)
    text1b = "".join(d for d in deltas1b if isinstance(d, str))
    assert text1b, "phase 1b streamed no text"
    dg1b = (await provider.backend.engine_stats()).get("disagg") or {}
    warm_bytes, cold_bytes = assert_warm_handoff(dg, dg1b)
    print(f"disagg smoke: phase 1b warm handoff shipped {warm_bytes} "
          f"bytes vs {cold_bytes} cold")

    # phase 2: prefill-host crash mid-request → restarting shed →
    # client failover retry completes on the respawned pair
    restarts_seen = []
    provider.backend.on_host_restart = restarts_seen.append
    events = []
    async for item in client.chat_failover(
            "mem://server", server_ident.public_key, "tiny:disagg",
            [{"role": "user", "content": PROMPT + " again?"}],
            max_tokens=8, temperature=0.0, busy_retry_rounds=8):
        events.append(item)
    restarts = [e for e in events if isinstance(e, ChatRestart)]
    assert restarts, "prefill crash produced no failover restart"
    cut = events.index(restarts[-1])
    text2 = "".join(e for e in events[cut + 1:] if isinstance(e, str))
    assert text2, "no text after failover — request never completed"
    assert restarts_seen and restarts_seen[0] == "crash", \
        f"supervisor saw {restarts_seen}, expected a crash"

    for _ in range(100):  # let the supervisor bookkeeping settle
        if provider.backend._restarts >= 1 \
                and not provider.backend._restarting:
            break
        await asyncio.sleep(0.1)
    stats2 = await provider.backend.engine_stats()
    sup = stats2.get("supervisor") or {}
    assert sup.get("restarts", 0) >= 1, f"no restart recorded: {sup}"
    assert not sup.get("circuit_open"), "circuit breaker tripped"
    print(f"disagg smoke: phase 2 crash → restarting shed → "
          f"{len(restarts)} failover restart(s) → completed "
          f"{len(text2)} chars on the respawned pair "
          f"(supervisor restarts={sup.get('restarts')})")

    await provider.stop(drain_timeout_s=2)
    await server.stop()
    return 0


async def run_link_chaos() -> int:
    """Phase 3: the two tiers joined ONLY by the TCP handoff link, with
    a mid-handoff link drop injected via the disagg.net.drop_link seam."""
    from symmetry_tpu.provider.backends.base import (
        BackendRestartingError, InferenceRequest)
    from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
    from symmetry_tpu.provider.config import ConfigManager
    from symmetry_tpu.utils.faults import FAULTS

    cfg = provider_config_dict()
    cfg["name"] = "disagg-link-prov"
    # Network mode: inline PrefillNode over real TCP loopback; small
    # chunks so every handoff is genuinely multi-chunk on the wire; no
    # per-tier handoff-crash fault here (that was phases 1–2).
    cfg["tpu"]["disagg"] = {"peer": "tcp://127.0.0.1:0", "inline": True,
                            "chunk_kb": 4, "reconnect_base_s": 0.2}
    # The drop_link seam counts one hit per transfer attempt (fired
    # after the first chunk): request 1's handoff is hit 1 (clean),
    # request 2's handoff is hit 2 → the cable pull, mid-transfer.
    FAULTS.load({"disagg.net.drop_link": "drop_frame@nth=2"})

    async def collect(backend, content):
        text = []
        async for chunk in backend.stream(InferenceRequest(
                messages=[{"role": "user", "content": content}],
                max_tokens=8, temperature=0.0)):
            if chunk.text:
                text.append(chunk.text)
        return "".join(text)

    backend = TpuNativeBackend(ConfigManager(config=cfg))
    try:
        await backend.start()

        # happy path over the wire + the serialize-vs-wire split
        text1 = await collect(backend, PROMPT)
        assert text1, "link phase streamed no text"
        dg = assert_phase1_stats(await backend.engine_stats())
        assert dg.get("wire_frames", 0) >= 1, f"no wire split: {dg}"
        assert (dg.get("wire_s") or {}).get("count", 0) >= 1
        assert dg.get("wire_bytes", 0) > 0
        ho = ((dg.get("prefill_host") or {}).get("handoff") or {})
        assert ho.get("serialize_s", 0) > 0, \
            "serialize wall missing beside the wire split"
        link = dg.get("link") or {}
        assert link.get("connected") is True, f"link stats: {link}"
        node = dg.get("node") or {}
        assert node.get("handoffs_sent", 0) >= 1, f"node stats: {node}"
        print(f"disagg smoke: link phase streamed {len(text1)} chars "
              f"over TCP; wire p50 "
              f"{(dg.get('wire_s') or {}).get('p50')}s beside "
              f"serialize {ho.get('serialize_s')}s")

        # mid-handoff link drop → retryable shed → reconnect → retry
        shed = False
        try:
            await collect(backend, PROMPT + " once more")
        except BackendRestartingError:
            shed = True
        assert shed, "link drop did not shed the in-flight request"
        text2 = None
        for _ in range(200):  # retry through the reconnect window
            try:
                text2 = await collect(backend, PROMPT + " once more")
                break
            except BackendRestartingError:
                await asyncio.sleep(0.25)
        assert text2, "retry never completed on the re-dialed link"
        stats = await backend.engine_stats()
        dg = stats.get("disagg") or {}
        link = dg.get("link") or {}
        assert link.get("connects", 0) >= 2, f"no reconnect: {link}"
        assert link.get("drops", 0) >= 1, f"no drop recorded: {link}"
        assert link.get("partial_discards", 0) >= 1, \
            f"partial transfer not discarded: {link}"
        # ZERO partial adoptions: the decode host only ever saw intact,
        # CRC-verified frames (its adopt path booked no errors).
        ad = stats.get("adopt") or {}
        assert ad.get("errors", 0) == 0, f"decode host adopt stats: {ad}"
        sup = stats.get("supervisor") or {}
        assert sup.get("restarts", 0) == 0, \
            f"link loss must not restart the decode host: {sup}"
        print(f"disagg smoke: link phase drop → shed → reconnect "
              f"(connects={link.get('connects')}, "
              f"drops={link.get('drops')}, partial_discards="
              f"{link.get('partial_discards')}) → retry completed "
              f"{len(text2)} chars; zero partial adoptions")
    finally:
        await backend.stop()
        FAULTS.clear()
    return 0


async def run_pool_chaos() -> int:
    """Phase 4: the ELASTIC POOL churn contract. A 2×1 pool (two inline
    prefill nodes over the memory link, one decode host) takes sustained
    traffic; one prefill node is KILLED mid-traffic (crash — no drain,
    no leave). Every in-flight request must complete via the retryable
    shed + re-placement path on the survivor: zero non-retryable client
    outcomes, zero partial adoptions (decode adopt errors stay 0), zero
    decode-host restarts, and the pool metrics account the churn
    (member lost, re-placements counted)."""
    from symmetry_tpu.provider.backends.base import (
        BackendRestartingError, InferenceRequest)
    from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
    from symmetry_tpu.provider.config import ConfigManager

    cfg = provider_config_dict()
    cfg["name"] = "disagg-pool-prov"
    # Every prefill host's FIRST handoff stalls 2 s (delay seam on the
    # engine thread) — the deterministic window in which the node kill
    # lands with migrations genuinely in flight. No crash faults here.
    cfg["tpu"]["disagg"] = {
        "peer": "mem://pool-smoke", "reconnect_base_s": 0.2,
        "pool": {"prefill": 2, "decode": 1, "heartbeat_s": 1.0},
        "prefill": {"faults": {"disagg.handoff": "delay(2.0)@once"}},
    }

    async def collect(backend, content):
        text = []
        async for chunk in backend.stream(InferenceRequest(
                messages=[{"role": "user", "content": content}],
                max_tokens=8, temperature=0.0)):
            if chunk.text:
                text.append(chunk.text)
        return "".join(text)

    async def collect_retrying(backend, content):
        # The retryable shed is an ALLOWED outcome (client failover
        # retries through it); anything non-retryable fails the smoke.
        for _ in range(200):
            try:
                return await collect(backend, content)
            except BackendRestartingError:
                await asyncio.sleep(0.25)
        raise AssertionError(f"{content!r} never completed")

    backend = TpuNativeBackend(ConfigManager(config=cfg))
    try:
        await backend.start()
        tasks = [asyncio.ensure_future(
            collect_retrying(backend, f"{PROMPT} #{i}"))
            for i in range(4)]
        await asyncio.sleep(0.7)  # placements made; handoffs mid-delay
        pending_before = backend._broker.pending
        await backend._inline_nodes[0].kill()  # node death mid-traffic
        texts = await asyncio.gather(*tasks)
        assert all(texts), f"incomplete streams: {[len(t) for t in texts]}"
        stats = await backend.engine_stats()
        pool = (stats.get("disagg") or {}).get("pool") or {}
        members = pool.get("members") or {}
        assert members.get("prefill-0", {}).get("state") == "lost", members
        assert members.get("prefill-1", {}).get("state") == "healthy", \
            members
        assert pool.get("losses", 0) >= 1, pool
        assert pool.get("re_placements", 0) >= 1, \
            f"no re-placement counted (pending at kill: " \
            f"{pending_before}): {pool}"
        sup = stats.get("supervisor") or {}
        assert sup.get("restarts", 0) == 0, \
            f"node death must not restart a decode host: {sup}"
        ad = stats.get("adopt") or {}
        assert ad.get("errors", 0) == 0, \
            f"partial/garbage adoption on the decode host: {ad}"
        print(f"disagg smoke: pool phase — killed prefill-0 of 2×1 "
              f"under load ({pending_before} migrations in flight); "
              f"all 4 requests completed, re_placements="
              f"{pool.get('re_placements')}, losses="
              f"{pool.get('losses')}, decode restarts 0, adopt errors 0")
    finally:
        await backend.stop()
    return 0


async def run_pool_affinity() -> int:
    """Phase 5: cache-affine session routing across a 2×2 pool. A
    session's turn 1 lands cold somewhere; its gossiped radix summary
    then makes turn 2 (same conversation, resubmitted full prefix)
    affinity-route back to the member holding the cache (counter
    asserted), and the per-member shipped-block ledger makes the warm
    handoff ship fewer bytes than the cold one. Killing the warm member
    must drop it to a clean cold re-place on the survivor — never an
    error, never a stale-ledger skip against the respawn's empty cache."""
    from symmetry_tpu.provider.backends.base import (
        BackendRestartingError, InferenceRequest)
    from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
    from symmetry_tpu.provider.config import ConfigManager

    cfg = provider_config_dict()
    cfg["name"] = "disagg-affinity-prov"
    # 2×2 pool, fast heartbeat so summaries gossip between turns; the
    # engine-side summary cache refreshes faster than the heartbeat
    # asks. No fault seams in this phase.
    cfg["tpu"]["disagg"] = {
        "peer": "mem://pool-affinity", "reconnect_base_s": 0.2,
        "pool": {"prefill": 2, "decode": 2, "heartbeat_s": 0.3},
    }
    cfg["tpu"]["prefix_gossip_s"] = 0.1

    async def collect(backend, content):
        text = []
        async for chunk in backend.stream(InferenceRequest(
                messages=[{"role": "user", "content": content}],
                max_tokens=8, temperature=0.0)):
            if chunk.text:
                text.append(chunk.text)
        return "".join(text)

    async def collect_retrying(backend, content):
        for _ in range(200):
            try:
                return await collect(backend, content)
            except BackendRestartingError:
                await asyncio.sleep(0.25)
        raise AssertionError(f"{content!r} never completed")

    async def pool_stats(backend):
        stats = await backend.engine_stats()
        return stats, (stats.get("disagg") or {}).get("pool") or {}

    backend = TpuNativeBackend(ConfigManager(config=cfg))
    try:
        await backend.start()

        # turn 1: cold — no summaries gossiped yet, so the placement
        # books a non-hit outcome and ships the full frame.
        text1 = await collect(backend, PROMPT)
        assert text1, "affinity phase turn 1 streamed no text"
        stats, pool = await pool_stats(backend)
        assert pool.get("affinity_hit", 0) == 0, pool
        assert (pool.get("affinity_cold", 0)
                + pool.get("affinity_load_only", 0)) >= 1, pool
        dg1 = stats.get("disagg") or {}
        per1 = (dg1.get("per_member") or {})

        # let the gossip land: a few heartbeats carry the prefill
        # members' radix summaries (and the decode members') back to
        # the router.
        for _ in range(40):
            _, pool = await pool_stats(backend)
            if any((m.get("summary_digests") or 0) > 0
                   for m in (pool.get("members") or {}).values()):
                break
            await asyncio.sleep(0.15)
        members = pool.get("members") or {}
        assert any((m.get("summary_digests") or 0) > 0
                   for m in members.values()), \
            f"no radix summary ever gossiped: {members}"

        # turn 2: the same conversation grown by one exchange — the
        # shared prefix must pull it back to the warm member.
        text2 = await collect(backend, PROMPT + " and why it helps")
        assert text2, "affinity phase turn 2 streamed no text"
        stats, pool = await pool_stats(backend)
        assert pool.get("affinity_hit", 0) >= 1, \
            f"turn 2 was not affinity-routed: {pool}"
        members = pool.get("members") or {}
        warm = [mid for mid, m in members.items()
                if m.get("tier") == "prefill" and m.get("hit_blocks", 0) > 0]
        assert warm, f"no prefill member banked predicted hits: {members}"
        # per-member ledger: the decode member the warm handoff reached
        # shipped fewer blocks than the frame covers (the cold turn
        # shipped everything).
        dg2 = stats.get("disagg") or {}
        per2 = dg2.get("per_member") or {}
        warm_members = [
            mid for mid, led in per2.items()
            if (led.get("warm_frames", 0)
                > (per1.get(mid) or {}).get("warm_frames", 0))]
        assert warm_members, \
            f"no per-member warm handoff: before={per1} after={per2}"

        # kill the warm prefill member: the session must drop to a cold
        # re-place on the survivor — completed stream, no adopt errors,
        # and the loss accounted.
        warm_idx = int(warm[0].rsplit("-", 1)[1])
        await backend._inline_nodes[warm_idx].kill()
        # Same session prompt re-asked: its warm member is gone, so the
        # digests match nothing placeable — a cold re-place, not a
        # stale-affinity pull toward the corpse.
        text3 = await collect_retrying(backend,
                                       PROMPT + " and why it helps")
        assert text3, "post-kill turn streamed no text"
        stats, pool = await pool_stats(backend)
        members = pool.get("members") or {}
        assert members.get(warm[0], {}).get("state") == "lost", members
        assert pool.get("losses", 0) >= 1, pool
        ad = stats.get("adopt") or {}
        assert ad.get("errors", 0) == 0, \
            f"stale ledger/summary corrupted adoption: {ad}"
        print(f"disagg smoke: affinity phase — turn 2 affinity-routed "
              f"(hit placements={pool.get('affinity_hit')}, predicted "
              f"blocks on {warm[0]}={members.get(warm[0], {}).get('hit_blocks')}), "
              f"warm handoff ledger {warm_members} shipped partial "
              f"frames; killed {warm[0]} → cold re-place completed "
              f"{len(text3)} chars with zero adopt errors")
    finally:
        await backend.stop()
    return 0


def main() -> int:
    try:
        import cryptography  # noqa: F401 — wire-path dependency probe

        runner = run_network()
    except ImportError:
        print("disagg smoke: cryptography unavailable — running the "
              "backend-direct mode (same two-host contracts, no wire)",
              file=sys.stderr)
        runner = run_backend_direct()
    loop = asyncio.new_event_loop()
    try:
        rc = loop.run_until_complete(asyncio.wait_for(runner, 900))
        if rc == 0:
            rc = loop.run_until_complete(
                asyncio.wait_for(run_link_chaos(), 900))
        if rc == 0:
            rc = loop.run_until_complete(
                asyncio.wait_for(run_pool_chaos(), 900))
        if rc == 0:
            rc = loop.run_until_complete(
                asyncio.wait_for(run_pool_affinity(), 900))
        return rc
    except AssertionError as exc:
        print(f"disagg smoke FAILED: {exc}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
