"""CI autoscale smoke: the telemetry→topology loop closed end to end.

A REAL tpu_native backend boots a 1×1 elastic pool (tiny CPU preset,
real engine-host subprocesses), then a synthetic burst lights the SLO
burn monitor the pool heartbeat feeds to PoolAutoscaler
(engine/disagg/autoscale.py), and the smoke asserts the full round
trip:

  phase 1 (scale up): a burst of over-target TTFT observations drives
  the fast-window burn ≫ 1; within a few heartbeats the controller
  books a SPAWN decision (decision counter increments) and the backend
  actuates it — a second REAL prefill member (inline node + handoff
  link) joins the pool and reaches HEALTHY. Requests streamed across
  the transition must all complete: scaling UP sheds nothing.

  phase 2 (new member serves): with the pool at 2×1, fresh requests
  place onto the joined member (placement counter asserted) — the
  spawned capacity is capacity, not a spectator.

  phase 3 (scale down): the load stops, the burn window empties, and
  after the idle-streak hysteresis the controller books a DRAIN; the
  idle member drains (zero in-flight sheds — drain-before-kill) and is
  retired back to 1×1, its chip-seconds banked in the pool ledger.

Zero failed client requests across all phases, and every decision is
visible in the pool stats' autoscale block.

Exit 0 on success; exit 1 with a reason otherwise.

Run: python tools/autoscale_smoke.py
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

# CPU pinning BEFORE any jax import (the engine hosts inherit this
# environment). Every host resolves the same compile cache
# (utils/compile_cache.py); warm, it makes the mid-run member spawn
# affordable.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def provider_config_dict() -> dict:
    return {
        "name": "autoscale-smoke-prov", "public": False,
        "serverKey": "00" * 32,
        "modelName": "tiny:autoscale", "apiProvider": "tpu_native",
        "dataCollectionEnabled": False,
        "flightRecorder": {"enabled": False},
        "tpu": {
            "model_preset": "tiny", "dtype": "float32",
            "max_batch_size": 4, "max_seq_len": 128,
            "prefill_buckets": [32, 64], "prefill_chunk": 16,
            "role": "disagg",
            "supervisor": {"heartbeat_s": 30.0, "wedge_timeout_s": 10.0,
                           "backoff_base_s": 0.2, "backoff_max_s": 1.0,
                           "max_respawns": 3, "spawn_timeout_s": 300.0,
                           "stop_grace_s": 5.0, "min_stable_s": 0.5},
            # Smoke-speed hysteresis: dwell and the idle streak are
            # heartbeats, not minutes; the churn cooldown stays long —
            # no churn is expected, and tripping it would be a bug.
            # drain_ticks 25 × 0.2s heartbeat = 5s of genuine idle
            # before the scale-down — enough for phases 1–2 to assert
            # against the joined member without racing the drain.
            "autoscale": {"max_members": 2, "dwell_s": 0.5,
                          "churn_cooldown_s": 60.0,
                          "drain_load": 0.25, "drain_ticks": 25},
            "disagg": {"peer": "mem://autoscale-smoke",
                       "reconnect_base_s": 0.05,
                       "pool": {"prefill": 1, "decode": 1,
                                "heartbeat_s": 0.2}},
        },
    }


async def run_smoke() -> int:
    from symmetry_tpu.provider.backends.base import (
        BackendRestartingError, InferenceRequest)
    from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
    from symmetry_tpu.provider.config import ConfigManager
    from symmetry_tpu.utils.metrics import SloMonitor

    async def collect(backend, content: str) -> str:
        text = []
        for _ in range(40):  # retry through any respawn window
            try:
                async for chunk in backend.stream(InferenceRequest(
                        messages=[{"role": "user", "content": content}],
                        max_tokens=8, temperature=0.0)):
                    if chunk.text:
                        text.append(chunk.text)
                break
            except BackendRestartingError as exc:
                await asyncio.sleep(exc.retry_after_s or 0.25)
        else:
            raise AssertionError(f"request never completed: {content!r}")
        return "".join(text)

    async def pool_autoscale(backend) -> tuple[dict, dict]:
        stats = await backend.engine_stats()
        pool = (stats.get("disagg") or {}).get("pool") or {}
        return pool, pool.get("autoscale") or {}

    backend = TpuNativeBackend(ConfigManager(config=provider_config_dict()))
    failures = 0
    try:
        await backend.start()
        # The provider's SLO burn monitor, attached exactly as
        # provider.py does; the pool heartbeat hands its per-SLO burns
        # to the controller every tick.
        monitor = SloMonitor({"ttft_s": 0.01, "objective": 0.9,
                              "fast_window_s": 4.0})
        backend.attach_slo_monitor(monitor)

        pool, asc = await pool_autoscale(backend)
        assert pool.get("healthy") == {"prefill": 1, "decode": 1}, \
            f"pool did not boot 1x1: {pool.get('healthy')}"
        assert asc, "autoscale block missing from pool stats"
        members_before = set(pool.get("members") or {})

        # phase 1: synthetic burst — a spike of over-target TTFTs.
        # Requests keep streaming across the scale-up the whole time.
        for _ in range(12):
            monitor.observe("ttft", 0.5)
        inflight = [asyncio.ensure_future(
            collect(backend, f"burst request {i} rides the spike"))
            for i in range(3)]
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if backend._pool.healthy_count("prefill") == 2:
                break
            await asyncio.sleep(0.1)
        pool, asc = await pool_autoscale(backend)
        assert backend._pool.healthy_count("prefill") == 2, \
            f"burst never scaled the pool to 2x1: {pool}"
        assert asc.get("spawns", 0) >= 1, f"no spawn decision: {asc}"
        assert any(d.get("action") == "spawn"
                   for d in asc.get("actions", [])), \
            f"spawn missing from the action log: {asc.get('actions')}"
        joined = set(pool.get("members") or {}) - members_before
        assert len(joined) == 1, f"expected one joined member: {joined}"
        new_member = joined.pop()
        texts = await asyncio.gather(*inflight)
        assert all(texts), "a burst request streamed no text"
        print(f"autoscale smoke: phase 1 burn spike → spawn decision → "
              f"{new_member} joined (2x1); {len(texts)} requests "
              f"streamed across the scale-up")

        # phase 2: the joined member takes placements — least-loaded
        # routing sends fresh work its way. The burn stays lit so the
        # idle streak cannot start under the asserts.
        for i in range(4):
            monitor.observe("ttft", 0.5)
            await collect(backend, f"post-scale request {i} lands wide")
        pool, asc = await pool_autoscale(backend)
        placed = (pool.get("members", {}).get(new_member) or {}
                  ).get("placements", 0)
        assert placed >= 1, \
            f"joined member {new_member} never served: {pool}"
        print(f"autoscale smoke: phase 2 {new_member} took {placed} "
              f"placement(s) at 2x1")

        # phase 3: load stops → burn window empties → idle streak →
        # DRAIN decision → drain-before-kill retire back to 1x1.
        # Poll the retire, not the drain: DRAINING drops the healthy
        # count immediately, but the drain-before-kill teardown takes
        # another beat to bank the member into the ledger.
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            pool, asc = await pool_autoscale(backend)
            if (pool.get("retires", 0) >= 1
                    and backend._pool.healthy_count("prefill") == 1):
                break
            await asyncio.sleep(0.2)
        assert backend._pool.healthy_count("prefill") == 1, \
            f"idle pool never drained back to 1x1: {pool}"
        assert asc.get("drains", 0) >= 1, f"no drain decision: {asc}"
        assert pool.get("retires", 0) >= 1, \
            f"drained member was not retired: {pool}"
        assert pool.get("re_placements", 0) == 0, \
            f"scaling shed in-flight work: {pool}"
        assert pool.get("chip_seconds", 0) > 0
        # The retired member still serves the ledger: its alive time
        # stays banked in the pool's chip-second total.
        final = await collect(backend, "the pool is 1x1 again")
        assert final, "post-drain request streamed no text"
        print(f"autoscale smoke: phase 3 idle drain → retired back to "
              f"1x1 (chip-seconds {pool.get('chip_seconds')}, "
              f"0 re-placements, 0 failed requests)")
    finally:
        try:
            await backend.stop()
        except Exception as exc:  # noqa: BLE001 — teardown must not mask
            print(f"autoscale smoke: teardown error: {exc!r}",
                  file=sys.stderr)
            failures += 1
    return 1 if failures else 0


def main() -> int:
    try:
        return asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(run_smoke(), timeout=600))
    except AssertionError as exc:
        print(f"autoscale smoke FAILED: {exc}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
