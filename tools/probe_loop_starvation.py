"""Measure asyncio loop starvation while the engine thread drives the TPU.

Serving symptom (bench --e2e): every client's TTFT ≈ wall time — token
events flush only when the engine goes idle. Hypothesis: the engine
thread's JAX calls (dispatch / np.asarray device sync) hold
the GIL, starving the provider's event loop.

This runs a 10 ms asyncio ticker while the engine thread executes decode
blocks, and prints the largest loop stalls per phase plus where in the
engine call they occur.

Run: python tools/probe_loop_starvation.py [--preset llama3.2-1b]
"""

from __future__ import annotations

import argparse
import asyncio
import threading
import time


async def ticker(gaps: list, stop: threading.Event) -> None:
    last = time.perf_counter()
    while not stop.is_set():
        await asyncio.sleep(0.01)
        now = time.perf_counter()
        gaps.append(now - last)
        last = now


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="llama3.2-1b")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--block", type=int, default=16)
    args = ap.parse_args()

    import jax.numpy as jnp

    from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
    from symmetry_tpu.engine.tokenizer import ByteTokenizer
    from symmetry_tpu.models import init_params, preset

    cfg = preset(args.preset)
    params = init_params(cfg, __import__("jax").random.key(0), jnp.bfloat16,
                         quantize=True)
    engine = InferenceEngine(
        cfg, params, ByteTokenizer(), max_slots=args.slots, max_seq_len=256,
        prefill_buckets=(64,), cache_dtype=jnp.bfloat16,
        decode_block=args.block, kv_quant=True)
    engine.warmup()
    engine.prefill_and_insert(0, list(b"probe prompt"), SamplingParams())

    async def run() -> None:
        stop = threading.Event()
        phases: dict[str, list] = {}

        def engine_work() -> None:
            # phase 1: decode dispatch only (async)
            t0 = time.perf_counter()
            pending = []
            while time.perf_counter() - t0 < 3:
                pending.append(engine.decode_steps_dispatch())
            # phase 2: dispatch + sync (the serving loop's real shape)
            t0 = time.perf_counter()
            import numpy as np

            while time.perf_counter() - t0 < 5:
                np.asarray(engine.decode_steps_dispatch())
            stop.set()

        gaps: list = []
        phases["all"] = gaps
        thread = threading.Thread(target=engine_work, daemon=True)
        tick = asyncio.get_running_loop().create_task(ticker(gaps, stop))
        t_start = time.perf_counter()
        thread.start()
        await tick
        dur = time.perf_counter() - t_start
        gaps.sort(reverse=True)
        ticks = len(gaps)
        print(f"{dur:.1f}s, {ticks} ticks (expected ~{int(dur / 0.01)}), "
              f"worst loop stalls: "
              f"{[round(g, 3) for g in gaps[:8]]}", flush=True)

    asyncio.new_event_loop().run_until_complete(run())


if __name__ == "__main__":
    main()
