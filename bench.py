"""Serving benchmark: aggregate decode throughput of the tpu_native engine.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
vs_baseline is measured against the BASELINE.json north-star target of
2000 tok/s aggregate (llama3:8b streaming on v5e-8 — reference publishes no
numbers of its own, SURVEY §6, so the target is the yardstick).

Modes:
  python bench.py            # NORTH STAR: full serving path (server +
                             # tpu_native provider subprocess + 128
                             # streaming TCP clients), llama3-8b int8.
                             # One attempt: a failure is a non-zero exit.
  python bench.py --engine   # engine-only decode loop (no wire)
  python bench.py --smoke    # CPU-safe tiny model (used by /verify)
  python bench.py --e2e --clients 64 --max-new 128 ...

The default mode and --engine measure a TPU and fail on anything else;
every result names the device the ENGINE reported (the host's READY
frame / stats), never one this process guessed. --disagg*, --autoscale
and --chaos put several engine hosts behind one provider; a chip belongs
to one process, so until each member can be given a chip of its own they
are CPU-only (run them with JAX_PLATFORMS=cpu) and say so in their output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _rnd(x, nd: int = 3):
    return round(x, nd) if isinstance(x, (int, float)) else x


# Bench-JSON schema version: bumped when the capture's SHAPE changes in
# a way tools/benchdiff.py must know about (v1 = the stamped format —
# schema + git_sha + resolved-knob config fingerprint on every capture).
BENCH_SCHEMA = 1


def _git_sha() -> str | None:
    """The repo HEAD this capture ran at (None outside a git checkout) —
    benchdiff prints both SHAs so a delta names its endpoints."""
    import os
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def stamp_result(result: dict, config: dict, mode: str) -> dict:
    """Stamp a bench capture with its identity: schema version, git SHA,
    and the RESOLVED-knob config fingerprint (every knob that shapes the
    measurement, post-default-resolution — not the raw argv). benchdiff
    refuses to compare captures whose fingerprints disagree: a tok/s
    delta between a 128-slot run and a 96-slot run is a config diff
    wearing a regression costume, and the old eyeballed-JSON workflow
    produced exactly that garbage silently."""
    import hashlib

    cfg = {"mode": mode, **{k: config[k] for k in sorted(config)}}
    digest = hashlib.blake2b(
        json.dumps(cfg, sort_keys=True, separators=(",", ":")).encode(),
        digest_size=8).hexdigest()
    result["schema"] = BENCH_SCHEMA
    result["git_sha"] = _git_sha()
    result["written_at"] = round(time.time(), 1)
    result["config"] = cfg
    result["config_fingerprint"] = digest
    return result


def arrival_times(kind: str, n: int, *, duration_s: float,
                  seed: int = 0) -> list[float]:
    """Deterministic arrival-offset traces for the open-loop workloads
    (`--arrival`): n send offsets in [0, duration_s), sorted. Seeded so
    every arm of a comparison bench (run_autoscale) replays the SAME
    trace — the topology is the only variable.

    - poisson: homogeneous Poisson arrivals (exponential inter-arrival
      gaps at rate n/duration), rescaled to span the window exactly.
    - diurnal: inhomogeneous Poisson with a sinusoidal intensity —
      trough at both ends, one peak mid-trace at ~19x the trough rate
      (lam(t) = 1 - 0.9*cos(2*pi*t/D)); sampled by inverting the
      closed-form cumulative intensity. The day-curve in miniature:
      the shape where a static topology must provision for the peak.
    - burst: 4 near-simultaneous waves evenly spaced through the
      window — the thundering-herd shape the autoscale smoke uses.
    """
    import math
    import random

    if n <= 0:
        return []
    rnd = random.Random(seed)
    if kind == "poisson":
        rate = n / max(duration_s, 1e-9)
        t, out = 0.0, []
        for _ in range(n):
            t += rnd.expovariate(rate)
            out.append(t)
        scale = duration_s / max(out[-1], 1e-9)
        return [x * scale for x in out]
    if kind == "diurnal":
        amp = 0.9

        def cum(t: float) -> float:  # normalized cumulative intensity
            return (t - amp * duration_s / (2 * math.pi)
                    * math.sin(2 * math.pi * t / duration_s)) / duration_s

        out = []
        for i in range(n):
            # Stratified uniforms keep the realized trace close to the
            # intensity curve even at small n.
            u = (i + rnd.random()) / n
            lo, hi = 0.0, duration_s
            for _ in range(48):
                mid = (lo + hi) / 2
                if cum(mid) < u:
                    lo = mid
                else:
                    hi = mid
            out.append((lo + hi) / 2)
        return sorted(out)
    if kind == "burst":
        waves = 4
        per = -(-n // waves)
        jitter = 0.02 * duration_s / waves
        return sorted((i // per + 0.5) * duration_s / waves
                      + rnd.random() * jitter for i in range(n))
    raise ValueError(f"unknown arrival kind {kind!r} "
                     f"(want poisson|diurnal|burst)")


import contextlib

# Stamped on every mode that puts several engine hosts behind one
# provider (--disagg*, --autoscale, --chaos): see the module docstring.
CPU_ONLY_NOTE = ("CPU-only mode: it runs several engine hosts and a chip "
                 "belongs to one process, so nothing here is a device rate")


@contextlib.asynccontextmanager
async def _provider_process(cfg: dict, server, model_name: str, *,
                            timeout_s: float, stdout):
    """Spawn `python -m symmetry_tpu.provider` on a temp config and wait
    for it to register with `server`; yields (proc, startup_s). One
    definition of the launch/registration/teardown lifecycle for every
    bench mode — the registration wait and the teardown live in the same
    try/finally, so a never-registering provider cannot leak the
    subprocess or the temp config (it holds privateSeed)."""
    import asyncio
    import os
    import subprocess
    import sys
    import tempfile
    import time as _time

    import yaml

    with tempfile.NamedTemporaryFile("w", suffix=".yaml",
                                     delete=False) as fh:
        yaml.safe_dump(cfg, fh)
        cfg_path = fh.name
    proc = subprocess.Popen(
        [sys.executable, "-m", "symmetry_tpu.provider", "-c", cfg_path],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        stdout=stdout, stderr=subprocess.STDOUT)
    try:
        t_start = _time.monotonic()
        deadline = t_start + timeout_s
        while server.registry.select_provider(model_name) is None:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"provider process exited rc={proc.returncode}")
            if _time.monotonic() > deadline:
                raise TimeoutError("provider never registered")
            await asyncio.sleep(0.5)
        yield proc, _time.monotonic() - t_start
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        os.unlink(cfg_path)


def run_bench(preset_name: str, *, slots: int, steps: int, prompt_len: int,
              max_seq: int, dtype_name: str, mesh_model: int,
              block: int = 1, quant: str | None = None,
              kv_quant: bool = False, fused_dequant: bool = False,
              pipeline_depth: int = 1) -> dict:
    import jax
    import jax.numpy as jnp

    from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
    from symmetry_tpu.engine.tokenizer import ByteTokenizer
    from symmetry_tpu.models import init_params, param_logical_axes, preset
    from symmetry_tpu.parallel import MeshSpec, build_mesh, shardings_for

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype_name]
    config = preset(preset_name)

    if mesh_model > 1:
        mesh = build_mesh(MeshSpec(data=1, model=mesh_model))
        params = jax.device_put(
            init_params(config, jax.random.key(0), dtype),
            shardings_for(param_logical_axes(config), mesh))
        # Quantize AFTER placement: the dense sharding tree doesn't
        # prefix-match QuantizedTensor leaves; jitted quantize preserves
        # input shardings.
        if quant == "int8":
            from symmetry_tpu.models.llama import quantize_params

            params = quantize_params(params)
    else:
        mesh = None
        # Single chip: init leaves directly in int8 so models whose bf16
        # form exceeds HBM (llama3-8b on v5e) still fit.
        params = init_params(config, jax.random.key(0), dtype,
                             quantize=quant == "int8")

    engine = InferenceEngine(
        config, params, ByteTokenizer(), mesh=mesh, max_slots=slots,
        max_seq_len=max_seq, prefill_buckets=(prompt_len,),
        cache_dtype=dtype, decode_block=block, kv_quant=kv_quant,
        fused_dequant=fused_dequant)

    # Compile the decode program BEFORE inserting real requests (warmup's
    # garbage device writes are only harmless pre-insert).
    engine.warmup()

    prompt = list(range(1, prompt_len + 1))
    t_prefill0 = time.perf_counter()
    group = max(engine.PREFILL_BATCHES)
    for start in range(0, slots, group):
        engine.prefill_and_insert_many(
            [(slot, [p % 200 for p in prompt],
              SamplingParams(temperature=0.7, seed=slot))
             for slot in range(start, min(start + group, slots))])
    prefill_s = time.perf_counter() - t_prefill0

    import numpy as np

    # One warm dispatch, then measure. `steps` counts decode steps; each
    # dispatch advances `block` of them. Pipelined like the serving
    # scheduler (--pipeline-depth, default 1 = the historical double
    # buffer: block N+1 dispatched before syncing block N's tokens):
    # `depth` blocks stay in flight, the oldest is synced once the
    # pipeline is full. Per-iteration host wall is sampled so the bench
    # JSON carries the dispatch-thread-per-block number the scheduler's
    # stats() splits out (here there is no emit work, so this is the
    # floor: dispatch + sync cost alone).
    from collections import deque

    engine.decode_steps()
    n_disp = max(1, steps // block)
    depth = max(1, pipeline_depth)
    in_flight: deque = deque()
    iter_walls: list[float] = []
    t0 = time.perf_counter()
    for _ in range(n_disp):
        t_it = time.perf_counter()
        in_flight.append(engine.decode_steps_dispatch())
        if len(in_flight) > depth:
            np.asarray(in_flight.popleft())
        iter_walls.append(time.perf_counter() - t_it)
    while in_flight:
        np.asarray(in_flight.popleft())
    dt = time.perf_counter() - t0
    walls = sorted(iter_walls)
    disp_wall = {
        "p50": round(walls[len(walls) // 2], 6),
        "p99": round(walls[min(len(walls) - 1,
                               int(len(walls) * 0.99))], 6),
    }

    done_steps = n_disp * block
    tok_s = slots * done_steps / dt
    dtype_label = f"{dtype_name}+{quant}" if quant else dtype_name
    if kv_quant:
        dtype_label += "+kv8"
    if fused_dequant:
        dtype_label += "+fused"
    dtype_name = dtype_label
    # Convert-wall accounting: the weight bytes every decode step streams
    # and the effective HBM rate they moved at — the number the fused-
    # dequant A/B exists to raise (BASELINE.md decode-floor section).
    step_s = dt / done_steps
    weight_bytes = engine.weight_stream_bytes()
    # Per-device stream: with the packed layout sharded over the mesh
    # each chip reads only its weight shard per step — THIS is the
    # number a per-chip HBM roofline bounds, and the TP A/B gate
    # (BASELINE.md round-19) compares. Equals the aggregate figure
    # on a single device.
    weight_bytes_dev = engine.weight_stream_bytes_per_device()
    return {
        "metric": f"aggregate decode tok/s ({preset_name} {dtype_name}, "
                  f"{slots} slots, block {block}, "
                  f"{jax.device_count()} {jax.default_backend()} dev)",
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": jax.device_count()},
        "value": round(tok_s, 1),
        "unit": "tok/s",
        "vs_baseline": round(tok_s / 2000.0, 3),
        "per_slot_tok_s": round(tok_s / slots, 1),
        "prefill_s_per_slot": round(prefill_s / slots, 3),
        "decode_step_ms": round(1e3 * step_s, 2),
        "weight_bytes_per_step": weight_bytes,
        "weight_stream_gbs": round(weight_bytes / step_s / 1e9, 1),
        "weight_stream_gbs_per_device": round(
            weight_bytes_dev / step_s / 1e9, 1),
        "pipeline_depth": depth,
        "dispatch_thread_block_s": disp_wall,
    }


def run_e2e_client_worker() -> int:
    """One shard of the e2e bench's client fleet, in its OWN process.

    Round 4 measured the 128-client wire tail through a saturated
    instrument: 128 concurrent Noise-decrypting asyncio streams in ONE
    event loop meant the reported inter-chunk gap p99 (1.25-2.0 s) partly
    measured the bench client itself — the engine-side histogram said
    p99 ≤ 0.63 s. Sharding the fleet over N OS processes removes the
    client loop from the measurement.

    Protocol (parent = run_e2e): read one JSON config line on stdin →
    connect every assigned session → print "READY <n>" → block for the
    "GO" line (the cross-process burst barrier) → run the clients →
    print "RESULTS <json>". All timestamps are time.monotonic(), which is
    CLOCK_MONOTONIC — one clock across processes on Linux, so the parent
    can aggregate absolute stamps from every shard."""
    import asyncio
    import time as _time

    from symmetry_tpu.client.client import ProviderBusyError, SymmetryClient
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.transport.tcp import TcpTransport

    spec = json.loads(sys.stdin.readline())
    server_address = spec["server_address"]
    server_key = bytes.fromhex(spec["server_key_hex"])
    model_name = spec["model_name"]
    indices: list[int] = spec["indices"]
    # Per-session prompts (aligned with `indices`): the shared-prefix
    # workload gives every client its own prompt; uniform workloads send
    # the same string for all. Legacy "prompt" still accepted.
    prompts: list[str] = (spec.get("prompts")
                          or [spec["prompt"]] * len(indices))
    max_new: int = spec["max_new"]
    stagger_s: float = spec["stagger_s"]
    # Open-loop arrival trace (--arrival): per-session send offsets
    # aligned with `indices`, overriding the linear stagger.
    arrivals: list[float] | None = spec.get("arrivals")
    # Wave-level request controls: the speculative bench runs a greedy
    # (temperature 0) workload, wave A opting every request out of
    # drafting ("speculative": false) so the same provider measures the
    # plain path and the speculative path on identical prompts.
    temperature: float = spec.get("temperature", 0.7)
    spec_flag: bool | None = spec.get("speculative")

    async def main() -> list[dict]:
        ready = asyncio.Event()

        async def one_client(i: int, prompt: str, delay_s: float) -> dict:
            client = SymmetryClient(Identity.from_name(f"bench-cli-{i}"),
                                    TcpTransport())
            details = await client.request_provider(
                server_address, server_key, model_name)
            session = await client.connect(details)
            sessions_up[0] += 1
            if sessions_up[0] == len(indices):
                all_connected.set()
            await ready.wait()
            # Global arrival order by GLOBAL index — the shards together
            # reproduce exactly the single-process arrival pattern.
            await asyncio.sleep(delay_s)
            t_send = _time.monotonic()
            t_first = None
            chars = 0
            stamps: list[tuple[float, int]] = []
            try:
                async for delta in session.chat(
                        [{"role": "user", "content": prompt}],
                        max_tokens=max_new, temperature=temperature,
                        seed=i, speculative=spec_flag):
                    now = _time.monotonic()
                    if t_first is None and delta:
                        t_first = now
                    chars += len(delta)
                    stamps.append((now, len(delta)))
                tokens = int((session.last_usage or {}).get("tokens", 0))
            except ProviderBusyError as exc:
                return {"rejected": True,
                        "reject_s": _time.monotonic() - t_send,
                        "queue_depth": exc.queue_depth}
            finally:
                await session.close()
            t_done = _time.monotonic()
            # symledger cost block from the end frame (tpu.ledger on):
            # the request's attributed device time rides the capture so
            # the parent can report cost percentiles + wasted share.
            costs = getattr(session, "last_costs", None)
            return {"ttft": (t_first or t_done) - t_send,
                    "e2e": t_done - t_send, "chars": chars,
                    "tokens": tokens, "t_first": t_first or t_done,
                    "t_done": t_done, "stamps": stamps,
                    **({"costs": costs} if costs else {})}

        sessions_up = [0]
        all_connected = asyncio.Event()
        tasks = [asyncio.ensure_future(one_client(
                     i, prompts[k],
                     arrivals[k] if arrivals is not None
                     else i * stagger_s))
                 for k, i in enumerate(indices)]
        await asyncio.wait_for(all_connected.wait(), timeout=120)
        print(f"READY {len(indices)}", flush=True)
        loop = asyncio.get_running_loop()
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line.startswith("GO"):
            raise RuntimeError(f"expected GO, got {line!r}")
        ready.set()
        return list(await asyncio.gather(*tasks))

    results = asyncio.new_event_loop().run_until_complete(main())
    print("RESULTS " + json.dumps(results), flush=True)
    return 0


def run_chaos(preset_name: str, *, clients: int, slots: int, max_new: int,
              prompt_chars: int, max_seq: int, dtype_name: str, block: int,
              bucket: int, seam: str) -> dict:
    """The kill-under-load robustness bench (`--chaos`): arm ONE named
    fault seam on provider 1's engine host (default: a pipe-write crash
    that lands mid-stream), drive a concurrent client fleet through
    chat_failover, and run the SAME drill twice — stream resumption on
    (the default failure model) vs off (legacy discard-and-restart).
    The headline is WASTED WORK: tokens generated and then thrown away
    (restart arm: every discarded partial; resume arm: only offset-dedup
    drops and refused-resume fallbacks) plus the recovery latency from
    the failure sentinel to the next delivered delta (post-kill TTFT).

    Providers live in this process over the in-memory transport (the
    engine hosts are still real subprocesses) — this bench measures
    recovery behavior and wasted work, not peak wire throughput; the
    north-star numbers stay with --e2e."""
    import asyncio
    import statistics
    import time as _time

    from symmetry_tpu.client.client import (
        ChatRestart,
        ChatResume,
        ClientError,
        SymmetryClient,
    )
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.provider.config import ConfigManager
    from symmetry_tpu.provider.provider import SymmetryProvider
    from symmetry_tpu.server.broker import SymmetryServer
    from symmetry_tpu.transport.memory import MemoryTransport
    from symmetry_tpu.utils.faults import FAULTS

    seam_name, sep, seam_spec = seam.partition("=")
    if not sep or not seam_name or not seam_spec:
        raise RuntimeError(f"--chaos-seam wants seam=action@trigger, "
                           f"got {seam!r}")

    def pct(vals, p):
        if not vals:
            return None
        vals = sorted(vals)
        return round(vals[min(len(vals) - 1,
                              max(0, -(-p * len(vals) // 100) - 1))], 4)

    async def run_arm(resume_on: bool) -> dict:
        FAULTS.clear()
        hub = MemoryTransport()
        ident = Identity.from_name("chaos-bench-server")
        server = SymmetryServer(ident, hub, ping_interval_s=60.0)
        await server.start("mem://chaos-server")

        def provider_cfg(name: str, faults: dict | None) -> ConfigManager:
            return ConfigManager(config={
                "name": name, "public": True,
                "serverKey": ident.public_hex,
                "modelName": f"{preset_name}:chaos",
                "apiProvider": "tpu_native",
                "dataCollectionEnabled": False,
                "maxConnections": clients + 8,
                "flightRecorder": {"enabled": False},
                **({"faults": faults} if faults else {}),
                "tpu": {"model_preset": preset_name, "dtype": dtype_name,
                        "max_batch_size": slots, "max_seq_len": max_seq,
                        "prefill_buckets": [bucket],
                        "decode_block": block,
                        # The resume admission path seeds through the
                        # radix cache — on, so resumes are cheap
                        # re-prefills, the contract under test.
                        "prefix_cache_mb": 64.0},
            })

        providers = []
        for name, faults in (("chaos-p1", {seam_name: seam_spec}),
                             ("chaos-p2", None)):
            prov = SymmetryProvider(
                provider_cfg(name, faults), transport=hub,
                identity=Identity.from_name(name),
                server_address="mem://chaos-server")
            await prov.start(f"mem://{name}")
            await prov.wait_registered()
            providers.append(prov)
        p1, p2 = providers
        # Steer the first wave at the faulted provider.
        server.registry.set_connections(p2.identity.public_hex, 5)

        prompts = [(f"req {i:04d} " + "resume the work under fire "
                    * 64)[:prompt_chars] for i in range(clients)]
        per_req: list[dict] = []

        async def one(i: int) -> None:
            client = SymmetryClient(
                Identity.from_name(f"chaos-cli-{i}"), hub)
            row = {"completed": False, "resumes": 0, "restarts": 0,
                   "resumed_tokens": 0, "discarded_tokens": 0,
                   "recovery_s": []}
            t_fail = None
            try:
                async for item in client.chat_failover(
                        "mem://chaos-server", ident.public_key,
                        f"{preset_name}:chaos",
                        [{"role": "user", "content": prompts[i]}],
                        max_tokens=max_new, resume=resume_on,
                        attempts=4, busy_retry_rounds=2):
                    if isinstance(item, ChatResume):
                        row["resumes"] += 1
                        row["resumed_tokens"] += item.resumed_tokens or 0
                        t_fail = _time.monotonic()
                    elif isinstance(item, ChatRestart):
                        row["restarts"] += 1
                        row["discarded_tokens"] += (
                            item.discarded_tokens or 0)
                        t_fail = _time.monotonic()
                    elif item and t_fail is not None:
                        row["recovery_s"].append(
                            _time.monotonic() - t_fail)
                        t_fail = None
                row["completed"] = True
            except ClientError as exc:
                row["error"] = str(exc)
            per_req.append(row)

        t0 = _time.monotonic()
        await asyncio.gather(*[one(i) for i in range(clients)])
        wall = _time.monotonic() - t0
        tokens_streamed = sum(p.metrics["tokens_out"] for p in providers)
        dedup = sum(p.backend.resume_stats["dedup_dropped"]
                    for p in providers
                    if hasattr(p.backend, "resume_stats"))
        for prov in providers:
            await prov.stop(drain_timeout_s=2)
        await server.stop()
        FAULTS.clear()
        recoveries = [r for row in per_req for r in row["recovery_s"]]
        discarded = sum(r["discarded_tokens"] for r in per_req)
        return {
            "resumption": resume_on,
            "requests": clients,
            "completed": sum(r["completed"] for r in per_req),
            "failed": sum(not r["completed"] for r in per_req),
            "wall_s": round(wall, 2),
            "tokens_streamed": tokens_streamed,
            "resumes": sum(r["resumes"] for r in per_req),
            "restarts": sum(r["restarts"] for r in per_req),
            "resumed_tokens": sum(r["resumed_tokens"] for r in per_req),
            # Wasted work = tokens generated then thrown away: discarded
            # partials (restart path) + overlap the relay dedup dropped
            # (resume path) — regenerated − resumed, per the Round-14
            # protocol.
            "wasted_tokens": discarded + dedup,
            "discarded_tokens": discarded,
            "dedup_dropped_tokens": dedup,
            "recovery_s": {"n": len(recoveries),
                           "p50": pct(recoveries, 50),
                           "p99": pct(recoveries, 99),
                           "mean": (round(statistics.mean(recoveries), 4)
                                    if recoveries else None)},
        }

    async def main() -> dict:
        arms = {}
        for resume_on in (True, False):
            label = "resume" if resume_on else "restart"
            print(f"[chaos] arm {label}: {clients} clients, seam {seam}",
                  file=sys.stderr)
            arms[label] = await run_arm(resume_on)
            print(f"[chaos] arm {label}: "
                  f"{arms[label]['completed']}/{clients} completed, "
                  f"wasted {arms[label]['wasted_tokens']} tok, "
                  f"resumed {arms[label]['resumed_tokens']} tok",
                  file=sys.stderr)
        saved = (arms["restart"]["wasted_tokens"]
                 - arms["resume"]["wasted_tokens"])
        return {
            "kind": "chaos",
            "device_note": CPU_ONLY_NOTE,
            "preset": preset_name,
            "clients": clients, "slots": slots, "max_new": max_new,
            "seam": seam,
            "arms": arms,
            # The robustness headline: wasted-work tokens the resume
            # path saved vs shed-and-retry, at identical kill schedules.
            "wasted_tokens_saved": saved,
        }

    return asyncio.new_event_loop().run_until_complete(main())


def run_autoscale(preset_name: str, *, clients: int, slots: int,
                  max_new: int, prompt_chars: int, max_seq: int,
                  dtype_name: str, block: int, bucket: int,
                  arrival: str, duration_s: float, seed: int,
                  slo_ttft_s: float, slo_chunk_s: float,
                  objective: float, static_shapes: tuple[str, ...],
                  max_members: int) -> dict:
    """The SLO-goodput autoscaling bench (`--autoscale`): replay ONE
    seeded arrival trace (default: the diurnal curve — trough, peak,
    trough) against an autoscaled pool and against each static MxN
    control, all in one invocation. The autoscaled arm starts at the
    FIRST static shape — the hand-picked constant under test — and the
    controller right-sizes it against the trace (floor 1x1, ceiling
    tpu.autoscale.max_members). Every arm reports SLO attainment
    (client-side TTFT + inter-chunk gap vs the targets), CHIP-SECONDS
    (sum of pool-member alive time over the TRACE window — boot warmup
    is excluded so arms compare provisioning, not compile-cache state;
    members spawned mid-trace pay their whole life, warmup included),
    and the headline GOODPUT: SLO-attaining tokens per chip-second.

    The autoscaled arm runs the real closed loop: a SloMonitor observes
    the same traffic (the bench performs the provider's exact observe
    calls — TTFT on first delta, inter-chunk gaps as they arrive), the
    pool heartbeat feeds burn rates + queue gauges + the ledger's busy-time
    into PoolAutoscaler (engine/disagg/autoscale.py), and its decisions
    spawn/drain real members mid-trace. The verdict the capture
    records: does the autoscaled arm meet the SLOs with fewer
    chip-seconds than every static shape that also meets them?

    Backend-direct like disagg_smoke's fallback mode: the fleet drives
    TpuNativeBackend in this process (engine hosts are still real
    subprocesses) with no server/client wire between — this measures
    topology economics, not wire throughput, and stays runnable where
    the `cryptography` network dependency is absent. Tokens are counted
    as streamed chars (exact under the byte tokenizer every preset here
    serves)."""
    import asyncio
    import time as _time
    import uuid as _uuid

    # Every engine host (including members the controller spawns
    # mid-trace) resolves the same compile cache (utils/compile_cache.py),
    # so each warmup after the first is a warm start: arm order and
    # mid-trace spawns measure provisioning economics, not XLA compile
    # variance.
    from symmetry_tpu.provider.backends.base import (
        BackendError,
        BackendRestartingError,
        InferenceRequest,
    )
    from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
    from symmetry_tpu.provider.config import ConfigManager
    from symmetry_tpu.utils.metrics import SloMonitor

    def pct(vals, p):
        if not vals:
            return None
        vals = sorted(vals)
        return round(vals[min(len(vals) - 1,
                              max(0, -(-p * len(vals) // 100) - 1))], 4)

    # One trace, every arm: the topology is the only variable.
    offsets = arrival_times(arrival, clients, duration_s=duration_s,
                            seed=seed)
    prompts = [(f"req {i:04d} " + "the day curve rises and falls "
                * 64)[:prompt_chars] for i in range(clients)]

    async def run_arm(label: str, m: int, n: int,
                      autoscaled: bool) -> dict:
        tag = _uuid.uuid4().hex[:8]
        backend = TpuNativeBackend(ConfigManager(config={
            "name": f"scale-{label}", "public": False,
            "serverKey": "00" * 32,
            "modelName": f"{preset_name}:scale",
            "apiProvider": "tpu_native",
            "dataCollectionEnabled": False,
            "tpu": {"model_preset": preset_name, "dtype": dtype_name,
                    "max_batch_size": slots, "max_seq_len": max_seq,
                    "prefill_buckets": [bucket],
                    "decode_block": block,
                    "role": "disagg",
                    # Bench-tightened hysteresis (production defaults
                    # are 30s/60s): dwell and cooldown scale down with
                    # the compressed diurnal day, but the spawn
                    # thresholds go UP, not down — one arrival clump in
                    # the 5s fast window must not trigger a mid-trace
                    # boot (whose compile steals the serving cores and
                    # manufactures the very breaches it reacts to).
                    # spawn_burn 1.5 = sustained 1.5x the error budget;
                    # spawn_queue scales with the slot count (2x slots,
                    # sustained): a queue the member batches through in
                    # a couple of waves is throughput, not pressure —
                    # only a backlog beyond that, or measured burn, is
                    # allowed to buy a mid-trace boot.
                    **({"autoscale": {"max_members": max_members,
                                      "dwell_s": 4.0,
                                      "churn_cooldown_s": 15.0,
                                      "spawn_burn": 1.5,
                                      "spawn_queue": max(2.0 * slots,
                                                         4.0),
                                      "spawn_queue_ticks": 8,
                                      "drain_load": 0.25,
                                      "drain_ticks": 12}}
                       if autoscaled else {}),
                    "disagg": {"peer": f"mem://scale-{tag}",
                               "reconnect_base_s": 0.05,
                               "pool": {"prefill": m, "decode": n,
                                        "heartbeat_s": 0.5}}},
        }))
        await backend.start()
        # The REAL sensor: the burn-rate monitor the pool heartbeat
        # hands to the controller, fed with the provider's exact
        # observe calls by the fleet below.
        monitor = SloMonitor({"ttft_s": slo_ttft_s,
                              "inter_chunk_s": slo_chunk_s,
                              "objective": objective,
                              "fast_window_s": 5.0,
                              "slow_window_s": 60.0})
        backend.attach_slo_monitor(monitor)

        per_req: list[dict] = []

        async def one(i: int) -> None:
            await asyncio.sleep(offsets[i])
            row = {"completed": False, "tokens": 0,
                   "ttft": None, "max_gap": None}
            t_send = _time.monotonic()
            t_prev = None
            gaps: list[float] = []
            attempts = 0
            while True:
                try:
                    async for chunk in backend.stream(InferenceRequest(
                            messages=[{"role": "user",
                                       "content": prompts[i]}],
                            max_tokens=max_new, temperature=0.7,
                            seed=i)):
                        if not chunk.text:
                            continue
                        now = _time.monotonic()
                        if row["ttft"] is None:
                            row["ttft"] = now - t_send
                            monitor.observe("ttft", row["ttft"])
                        elif t_prev is not None:
                            gaps.append(now - t_prev)
                            monitor.observe("inter_chunk", gaps[-1])
                        t_prev = now
                        row["tokens"] += len(chunk.text)
                    row["completed"] = True
                    row["max_gap"] = max(gaps, default=0.0)
                    monitor.observe("e2e", _time.monotonic() - t_send)
                except BackendRestartingError as exc:
                    # The provider/client retry loop in miniature:
                    # structured-retryable sheds (member churn,
                    # respawn windows) back off and resend.
                    attempts += 1
                    if attempts <= 6:
                        await asyncio.sleep(exc.retry_after_s or 0.25)
                        continue
                    row["error"] = f"shed x{attempts}: {exc}"
                except BackendError as exc:
                    row["error"] = str(exc)
                break
            per_req.append(row)

        # Chip-second accounting starts HERE: boot warmup is excluded
        # (it would measure arm order and compile-cache state, not
        # provisioning), but members the controller spawns mid-trace
        # pay their whole life — warmup included — inside the window.
        stats0 = await backend.engine_stats()
        chip0 = float(((stats0.get("disagg") or {}).get("pool") or {})
                      .get("chip_seconds") or 0.0)
        t0 = _time.monotonic()
        await asyncio.gather(*[one(i) for i in range(clients)])
        wall = _time.monotonic() - t0
        stats = await backend.engine_stats()
        pool = (stats.get("disagg") or {}).get("pool") or {}
        await backend.stop()

        def good(r: dict) -> bool:
            return (r["completed"] and r["ttft"] is not None
                    and r["ttft"] <= slo_ttft_s
                    and (r["max_gap"] or 0.0) <= slo_chunk_s)

        goods = [r for r in per_req if good(r)]
        tokens = sum(r["tokens"] for r in per_req)
        good_tokens = sum(r["tokens"] for r in goods)
        chip_s = max(
            float(pool.get("chip_seconds") or 0.0) - chip0, 0.0)
        attainment = len(goods) / max(len(per_req), 1)
        asc = pool.get("autoscale") or {}
        ttfts = [r["ttft"] for r in per_req if r["ttft"] is not None]
        gaps = [r["max_gap"] for r in per_req
                if r["max_gap"] is not None]
        return {
            "shape": label, "autoscaled": autoscaled,
            "requests": clients,
            "completed": sum(r["completed"] for r in per_req),
            "failed": sum(not r["completed"] for r in per_req),
            "wall_s": round(wall, 2),
            "tokens": tokens, "good_tokens": good_tokens,
            "slo_attainment": round(attainment, 4),
            "meets_slo": attainment >= objective,
            # The full tail ladder, not just p50/p99: with an
            # attainment objective the SLO verdict pivots on the
            # percentile AT the objective (p90 for 0.9), so the row
            # records where each arm's distribution actually sits.
            "ttft_p50_s": pct(ttfts, 50), "ttft_p90_s": pct(ttfts, 90),
            "ttft_p95_s": pct(ttfts, 95), "ttft_p99_s": pct(ttfts, 99),
            "max_gap_p90_s": pct(gaps, 90),
            "max_gap_p99_s": pct(gaps, 99),
            "chip_seconds": round(chip_s, 2),
            "goodput_tokens_per_chip_s": (round(good_tokens / chip_s, 2)
                                          if chip_s > 0 else None),
            "members_final": pool.get("healthy"),
            **({"scale": {
                    "spawns": asc.get("spawns"),
                    "drains": asc.get("drains"),
                    "rebalances": asc.get("rebalances"),
                    "target": asc.get("target"),
                    "decisions": asc.get("actions", [])}}
               if autoscaled else {}),
        }

    async def main() -> dict:
        arms: dict[str, dict] = {}
        # The autoscaled arm STARTS at the first static shape — the
        # hand-picked constant the pool would otherwise run all day —
        # with the controller closing the loop on it: right-size down
        # through the troughs (floor 1×1), grow back if the trace
        # demands it. The statics are the same shape(s) pinned for the
        # whole trace; the only variable is whether the loop is closed.
        m0, n0 = (int(x) for x in
                  static_shapes[0].lower().split("x"))
        shapes = [("autoscaled", m0, n0, True)]
        for s in static_shapes:
            m, n = (int(x) for x in s.lower().split("x"))
            shapes.append((f"static-{m}x{n}", m, n, False))
        for label, m, n, autoscaled in shapes:
            print(f"[autoscale] arm {label}: {clients} clients, "
                  f"{arrival} trace over {duration_s:g}s",
                  file=sys.stderr)
            arms[label] = await run_arm(label, m, n, autoscaled)
            a = arms[label]
            print(f"[autoscale] arm {label}: attainment "
                  f"{a['slo_attainment']} ({'meets' if a['meets_slo'] else 'MISSES'} "
                  f"SLO), {a['chip_seconds']} chip-s, goodput "
                  f"{a['goodput_tokens_per_chip_s']} tok/chip-s",
                  file=sys.stderr)
        auto = arms["autoscaled"]
        statics = [a for a in arms.values() if not a["autoscaled"]]
        # Compare against the static shapes that also meet the SLOs —
        # a cheaper static arm that misses them is not provisioning,
        # it is failing. If none meet, compare against all.
        comparators = [a for a in statics if a["meets_slo"]] or statics
        best_static = min(comparators, key=lambda a: a["chip_seconds"])
        wins = (auto["meets_slo"]
                and auto["chip_seconds"] < best_static["chip_seconds"])
        return {
            "kind": "autoscale",
            "metric": f"SLO goodput ({preset_name}, {clients} clients, "
                      f"{arrival} arrivals over {duration_s:g}s, "
                      f"ttft<={slo_ttft_s}s gap<={slo_chunk_s}s @ "
                      f"{objective:.0%}, autoscaled from "
                      f"{static_shapes[0]} vs static "
                      f"{','.join(static_shapes)}; CPU-only mode)",
            "device_note": CPU_ONLY_NOTE,
            "value": auto["goodput_tokens_per_chip_s"],
            "unit": "tok/chip-s",
            "goodput_tokens_per_chip_s":
                auto["goodput_tokens_per_chip_s"],
            "arrival": {"kind": arrival, "duration_s": duration_s,
                        "seed": seed},
            "slo": {"ttft_s": slo_ttft_s, "inter_chunk_s": slo_chunk_s,
                    "objective": objective},
            "arms": arms,
            "autoscaled_chip_seconds": auto["chip_seconds"],
            "best_static_chip_seconds": best_static["chip_seconds"],
            "best_static_shape": best_static["shape"],
            "verdict": ("autoscaled-wins" if wins else
                        "static-wins" if auto["meets_slo"] else
                        "autoscaled-misses-slo"),
        }

    return asyncio.new_event_loop().run_until_complete(main())


def run_e2e(preset_name: str, *, clients: int, slots: int, max_new: int,
            prompt_chars: int, max_seq: int, dtype_name: str, block: int,
            quant: str | None, kv_quant: bool, bucket: int,
            stagger_s: float = 0.0, max_queue: int | None = None,
            max_ttft_s: float | None = None, client_procs: int = 1,
            shared_prefix: bool = False,
            prefix_cache_mb: float | None = None,
            speculative: bool = False, draft_k: int = 8,
            fused_dequant: bool = False, trace_out: str | None = None,
            tracing: bool = True, disagg: bool = False,
            disagg_transport: str | None = None,
            disagg_pool: tuple[int, int] | None = None,
            multi_turn: int = 1,
            metrics_out: str | None = None,
            pipeline_depth: int | None = None,
            arrival: str | None = None,
            arrival_duration_s: float = 45.0,
            arrival_seed: int = 0) -> dict:
    """The NORTH-STAR measurement (BASELINE.json metric): aggregate WIRE
    tok/s and p50/p99 TTFT through the full serving path — server +
    tpu_native provider + N concurrent streaming clients over TCP
    loopback. This is the serving-path analog of the reference's hot loop
    (reference: src/provider.ts:240-258), where the engine-only bench
    (run_bench) measures just the decode kernel underneath it.

    The provider runs as its OWN OS PROCESS (the real deployment shape,
    `python -m symmetry_tpu.provider -c …`). Sharing one process with
    128 clients measured garbage: the engine thread's device syncs starve
    the shared event loop, so every token event flushed at the end and
    TTFT p50 == wall time."""
    import asyncio
    import os
    import statistics
    import subprocess
    import sys
    import tempfile
    import time as _time

    import yaml

    from symmetry_tpu.client.client import ProviderBusyError, SymmetryClient
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.server.broker import SymmetryServer
    from symmetry_tpu.transport.tcp import TcpTransport

    model_name = f"{preset_name}:bench"
    server_ident = Identity.from_name("bench-server")

    async def main() -> dict:
        server = SymmetryServer(server_ident, TcpTransport(),
                                ping_interval_s=60.0)
        await server.start("tcp://127.0.0.1:0")

        cfg = {
            "name": "bench-prov",
            "public": True,
            "serverKey": server_ident.public_hex,
            "serverAddress": server.address,
            "modelName": model_name,
            "apiProvider": "tpu_native",
            "dataCollectionEnabled": False,
            "maxConnections": clients + 8,
            "listenHost": "127.0.0.1",
            "privateSeed": __import__("hashlib").blake2b(
                b"bench-prov-seed", digest_size=32).hexdigest(),
            "tpu": {
                "model_preset": preset_name,
                "dtype": dtype_name,
                "quantization": quant,
                "kv_quantization": "int8" if kv_quant else None,
                "max_batch_size": slots,
                "max_seq_len": max_seq,
                "prefill_buckets": [bucket],
                "decode_block": block,
                **({"max_queue": max_queue} if max_queue is not None
                   else {}),
                **({"max_ttft_s": max_ttft_s} if max_ttft_s is not None
                   else {}),
                **({"prefix_cache_mb": prefix_cache_mb}
                   if prefix_cache_mb else {}),
                **({"speculative": {"k_draft": draft_k}}
                   if speculative else {}),
                **({"fused_dequant": True} if fused_dequant else {}),
                # --pipeline-depth: in-flight decode blocks on the
                # scheduler (1 = the pre-pipeline double buffer, the
                # depth A/B baseline; unset = the config default).
                **({"pipeline_depth": pipeline_depth}
                   if pipeline_depth is not None else {}),
                # Disaggregated prefill/decode: the provider runs a
                # prefill host + decode host pair with KV handoff
                # (engine/disagg/); handoff counters land in the JSON's
                # engine.disagg block. --disagg-transport swaps the
                # local pipes for the cross-machine handoff link (an
                # inline prefill node inside the provider process,
                # reached ONLY over the mem:// or tcp:// link).
                **({"role": "disagg"} if disagg else {}),
                # --disagg-pool MxN: the elastic pool (inline prefill
                # members + N local decode hosts, engine/disagg/pool.py)
                # instead of the fixed pair; --disagg-transport picks
                # the member-link transport (memory default).
                **({"disagg": {
                        "peer": ("tcp://127.0.0.1:0"
                                 if disagg_transport == "tcp"
                                 else "mem://bench-disagg"),
                        **({"inline": True} if not disagg_pool else {}),
                        # Pool × --multi-turn: tighten the heartbeat so
                        # gossiped radix summaries land BETWEEN a
                        # session's turns (default 5s would outlive a
                        # short bench window and every turn-2 placement
                        # would score cold).
                        **({"pool": {"prefill": disagg_pool[0],
                                     "decode": disagg_pool[1],
                                     **({"heartbeat_s": 0.5}
                                        if multi_turn > 1 else {})}}
                           if disagg_pool else {})}}
                   if disagg and (disagg_transport or disagg_pool)
                   else {}),
                # Same reason: recompute the gossiped summary faster
                # than the tightened heartbeat asks for it.
                **({"prefix_gossip_s": 0.25}
                   if disagg_pool and multi_turn > 1 else {}),
                # tracing=False empties the engine-side span rings — the
                # A/B knob for proving the recorder's overhead stays
                # under 1% of greedy decode tok/s (--no-trace vs default
                # at otherwise identical settings).
                **({"tracing": False} if not tracing else {}),
            },
        }
        # Provider log is ALWAYS captured (round-3 verdict #1: a 6-line
        # log could not explain a 2x-outlier capture); the tail is echoed
        # to stderr after the run. Per-run file — a fixed path would be
        # clobbered by a concurrent bench on the same machine.
        log_path = os.environ.get("BENCH_PROVIDER_LOG")
        if not log_path:
            with tempfile.NamedTemporaryFile(
                    "w", prefix="bench_provider_", suffix=".log",
                    delete=False) as lf:
                log_path = lf.name
        print(f"[bench] provider log: {log_path}", file=sys.stderr)
        log_fh = open(log_path, "w")


        prompts = ["x" * prompt_chars] * clients
        if speculative:
            # Repetition-heavy, code-like prompts (the prompt-lookup
            # drafter's home turf: keyed records whose n-grams recur), run
            # GREEDY — greedy is both the decode-equivalence contract
            # (wave A and wave B must stream identical text) and the
            # regime where a model's own repetitive continuation keeps
            # matching its context.
            unit = "cfg[{0}].key{0} = value{0}; "
            rep = "".join(unit.format(j % 7) for j in range(64))
            prompts = [("repeat the config table verbatim: "
                        + rep)[:prompt_chars]] * clients
        wave_a_prompts = wave_b_prompts = None
        if shared_prefix:
            # Shared-prefix workload: wave A is the UNCACHED comparison
            # (every client's preamble is unique from its first token, so
            # every admission is a full-prefill miss that churns the LRU),
            # wave B is the CACHED path (one shared preamble; the first
            # dispatch populates the store, everyone after hits). Both
            # waves have identical prompt shapes and arrival patterns, so
            # the TTFT delta between them is the prefix cache's doing.
            # The preamble is sized so the shared portion ends exactly at
            # a prefix-align boundary (min(prefill_chunk=256, bucket) —
            # mirrors engine.prefix_align) and the unique tail fits one
            # suffix dispatch.
            align = min(256, bucket)
            shared_tok = align * max(1, (bucket * 3 // 4) // align)
            # ByteTokenizer chat template wraps content as BOS + "user: "
            # (7 ids, part of the SHARED prefix) … "\nassistant: " (12
            # trailing ids that count against the tail room).
            shared_chars = shared_tok - 7
            tail_room = bucket - shared_tok - 12

            def tail(i: int) -> str:
                return f" client {i:04d} asks question {i:04d}."

            if shared_chars < 8 or tail_room < len(tail(0)):
                raise RuntimeError(
                    f"--prompt-len {bucket} too small for shared-prefix "
                    f"mode (needs room for an aligned preamble + tail + "
                    f"chat template)")

            wave_a_prompts = [f"{i:05d}" + "u" * (shared_chars - 5)
                              + tail(i) for i in range(clients)]
            wave_b_prompts = ["s" * shared_chars + tail(i)
                              for i in range(clients)]
        # All sessions handshake BEFORE any chat is sent (barrier below):
        # the burst then measures the SERVING path against truly
        # simultaneous arrivals — the worst case for admission — instead
        # of smearing 128 Noise handshakes into the ramp, which both
        # inflated TTFT with connection setup and made the measurement
        # sensitive to handshake scheduling variance (round-4 finding:
        # identical engine work, 6.2-9.2 s wire ramp across runs).
        ready = asyncio.Event()
        all_connected = asyncio.Event()
        connected = 0
        # Open-loop arrival trace (--arrival): pre-computed send offsets
        # replace the linear stagger ramp — same barrier, shaped release.
        arrivals = (arrival_times(arrival, clients,
                                  duration_s=arrival_duration_s,
                                  seed=arrival_seed)
                    if arrival else None)

        async def run_sharded_fleet(fleet_prompts: list[str],
                                    temperature: float = 0.7,
                                    spec_flag: bool | None = None
                                    ) -> tuple[list, float, float]:
            """The client fleet split over `client_procs` OS processes
            (run_e2e_client_worker), so the measured tails are the
            SERVICE's, not the client event loop's. Returns (results, t0,
            elapsed) with all stamps on the shared CLOCK_MONOTONIC."""
            shards = [list(range(k, clients, client_procs))
                      for k in range(client_procs)]
            shards = [s for s in shards if s]
            t_connect0 = _time.monotonic()
            procs = []
            try:
                for shard in shards:
                    p = await asyncio.create_subprocess_exec(
                        sys.executable, os.path.abspath(__file__),
                        "--e2e-client-worker",
                        stdin=asyncio.subprocess.PIPE,
                        stdout=asyncio.subprocess.PIPE,
                        limit=1 << 26)  # RESULTS line >> 64 KiB default
                    spec = {"server_address": server.address,
                            "server_key_hex": server_ident.public_hex,
                            "model_name": model_name, "indices": shard,
                            "prompts": [fleet_prompts[i] for i in shard],
                            "max_new": max_new,
                            "stagger_s": stagger_s,
                            **({"arrivals": [arrivals[i] for i in shard]}
                               if arrivals is not None else {}),
                            "temperature": temperature,
                            **({"speculative": spec_flag}
                               if spec_flag is not None else {})}
                    p.stdin.write((json.dumps(spec) + "\n").encode())
                    await p.stdin.drain()
                    procs.append(p)

                async def read_until(p, prefix: str) -> str:
                    while True:
                        raw = await p.stdout.readline()
                        if not raw:
                            raise RuntimeError(
                                f"client worker exited before {prefix}")
                        line = raw.decode()
                        if line.startswith(prefix):
                            return line

                counts = await asyncio.gather(*(
                    asyncio.wait_for(read_until(p, "READY"), 120)
                    for p in procs))
                n_conn = sum(int(c.split()[1]) for c in counts)
                print(f"[bench] {n_conn}/{clients} sessions connected "
                      f"across {len(procs)} client processes in "
                      f"{_time.monotonic() - t_connect0:.1f}s; releasing "
                      f"the burst", file=sys.stderr)
                t0 = _time.monotonic()
                for p in procs:
                    p.stdin.write(b"GO\n")
                await asyncio.gather(*(p.stdin.drain() for p in procs))
                payloads = await asyncio.gather(*(
                    read_until(p, "RESULTS ") for p in procs))
            finally:
                for p in procs:
                    if p.returncode is None and p.stdin is not None:
                        p.stdin.close()
            shard_results = [json.loads(pl[len("RESULTS "):])
                             for pl in payloads]
            await asyncio.gather(*(p.wait() for p in procs))
            results = [r for shard in shard_results for r in shard]
            done_ts = [r["t_done"] for r in results
                       if not r.get("rejected")]
            elapsed = (max(done_ts) - t0) if done_ts else 0.0
            return results, t0, elapsed

        # Multi-turn conversation workload (ROADMAP item 5): each client
        # holds ONE session of `multi_turn` turns, re-submitting the full
        # history every turn — the traffic shape where the prefix cache
        # acts as a session cache (turn N's prompt extends turn N-1's
        # prompt + reply, so its aligned prefix is already cached) and
        # where disaggregation + prefix handoff should shine: turn-2+
        # admissions pay only the new tokens. Greedy, so history growth
        # is deterministic per client. Per-turn content is sized so every
        # turn's full prompt still fits the bucket: budget the bucket
        # over the turns, minus the reply and template overhead.
        turn_room = (bucket // multi_turn - max_new - 24
                     if multi_turn > 1 else 0)
        if multi_turn > 1 and turn_room < 8:
            raise RuntimeError(
                f"--multi-turn {multi_turn} does not fit --prompt-len "
                f"{bucket} with --max-new {max_new}: each turn needs "
                f">= 8 chars of user content after the reply and chat "
                f"template (have {turn_room})")

        async def one_client(i: int) -> dict:
            # stagger_s > 0 = steady-operation arrival pattern (one client
            # every stagger_s); 0 = thundering herd (worst-case TTFT).
            # One code path serves both workload shapes: the default is a
            # single turn of prompts[i] (sampled, seeded); multi_turn > 1
            # runs a whole conversation on the session, greedy, growing
            # the history each turn and recording per-turn TTFT.
            nonlocal connected
            client = SymmetryClient(Identity.from_name(f"bench-cli-{i}"),
                                    TcpTransport())
            details = await client.request_provider(
                server.address, server_ident.public_key, model_name)
            session = await client.connect(details)
            connected += 1
            if connected == clients:
                all_connected.set()
            await ready.wait()
            await asyncio.sleep(arrivals[i] if arrivals is not None
                                else i * stagger_s)
            history: list[dict] = []
            turn_ttfts: list[float] = []
            stamps: list[tuple[float, int]] = []  # (arrival, chars)
            cost_blocks: list[dict] = []  # per-turn symledger blocks
            tokens = 0
            t_first_any = None
            t_begin = _time.perf_counter()
            try:
                for turn in range(max(multi_turn, 1)):
                    history.append({
                        "role": "user",
                        "content": (prompts[i] if multi_turn <= 1 else
                                    f"turn {turn}: client {i:04d} asks "
                                    + "m" * max(1, turn_room - 30))})
                    t_send = _time.perf_counter()
                    t_first = None
                    reply: list[str] = []
                    try:
                        async for delta in session.chat(
                                history, max_tokens=max_new,
                                temperature=(0.0 if multi_turn > 1
                                             else 0.7), seed=i):
                            now = _time.perf_counter()
                            if t_first is None and delta:
                                t_first = now
                                if t_first_any is None:
                                    t_first_any = now
                            reply.append(delta)
                            stamps.append((now, len(delta)))
                        tokens += int(
                            (session.last_usage or {}).get("tokens", 0))
                        costs = getattr(session, "last_costs", None)
                        if costs:
                            cost_blocks.append(costs)
                    except ProviderBusyError as exc:
                        # Overload shedding: an explicit, immediate
                        # rejection — the bounded-latency alternative to
                        # unbounded queueing. Counted separately; never
                        # mixed into serving latency.
                        return {"rejected": True,
                                "reject_s": _time.perf_counter() - t_send,
                                "queue_depth": exc.queue_depth}
                    turn_ttfts.append(
                        (t_first or _time.perf_counter()) - t_send)
                    history.append({"role": "assistant",
                                    "content": "".join(reply)})
            finally:
                await session.close()
            t_done = _time.perf_counter()
            return {"ttft": turn_ttfts[0], "e2e": t_done - t_begin,
                    "chars": sum(c for _, c in stamps), "tokens": tokens,
                    "t_first": t_first_any or t_done, "t_done": t_done,
                    "stamps": stamps, "turn_ttfts": turn_ttfts,
                    **({"cost_blocks": cost_blocks} if cost_blocks
                       else {})}

        engine_stats: dict | None = None
        provider_stats: dict | None = None
        metrics_block: dict | None = None
        # Engine build + warmup runs in the provider process (minutes for
        # 8B cold: weight init + XLA compiles); none of it counts toward
        # the measured window. Registration marks readiness. The log fh is
        # closed in the finally — the early-exception paths (provider
        # never registers, client failure) must not leak the fd, and the
        # tail read below needs the buffer flushed.
        try:
            async with _provider_process(cfg, server, model_name,
                                         timeout_s=1800,
                                         stdout=log_fh) as (_proc,
                                                            startup_s):
                print(f"[bench] provider registered after {startup_s:.0f}s "
                      f"(weight init + XLA compile + warmup; excluded from "
                      f"the measured window)", file=sys.stderr)
                async def fetch_engine_block(field: str) -> dict | None:
                    """One stats round-trip, one engine-stats block (the
                    prefix-cache or speculative counters) — used to
                    snapshot cumulative counters between waves."""
                    try:
                        c = SymmetryClient(
                            Identity.from_name("bench-stats-mid"),
                            TcpTransport())
                        details = await c.request_provider(
                            server.address, server_ident.public_key,
                            model_name)
                        s = await c.connect(details)
                        try:
                            stats = await s.stats()
                        finally:
                            await s.close()
                        return (stats.get("engine") or {}).get(field)
                    except Exception as exc:  # noqa: BLE001 — diag only
                        print(f"[bench] mid-run stats fetch failed: "
                              f"{exc!r}", file=sys.stderr)
                        return None

                results_uncached = None
                pc_after_wave_a = None
                results_plain = None
                plain_elapsed = None
                spec_after_wave_a = None
                if speculative:
                    # Wave A: identical prompts with every request opted
                    # OUT of drafting ("speculative": false) — the plain
                    # decode path on the same provider. Wave B: drafting
                    # on. Both greedy, so the text is token-identical and
                    # the tok/s delta is speculation's doing alone.
                    print("[bench] speculative wave A (drafting off, "
                          "plain decode)", file=sys.stderr)
                    results_plain, _t0a, plain_elapsed = \
                        await run_sharded_fleet(prompts, temperature=0.0,
                                                spec_flag=False)
                    spec_after_wave_a = await fetch_engine_block(
                        "speculative")
                    print("[bench] speculative wave B (n-gram drafting + "
                          "batched verify)", file=sys.stderr)
                    results, t0, elapsed = await run_sharded_fleet(
                        prompts, temperature=0.0)
                elif shared_prefix:
                    # Wave A (unique preambles — all misses) runs to
                    # completion, then wave B (shared preamble — hits
                    # after the first dispatch) on the SAME provider.
                    # Headline metrics come from the cached wave; wave A
                    # supplies the same-run uncached comparison. The
                    # prefix counters are SNAPSHOTTED between waves so
                    # the reported cached-wave hit rate is wave B's
                    # delta, not diluted by wave A's intentional misses.
                    print("[bench] shared-prefix wave A (uncached, unique "
                          "preambles)", file=sys.stderr)
                    results_uncached, _t0a, _el_a = await run_sharded_fleet(
                        wave_a_prompts)
                    pc_after_wave_a = await fetch_engine_block(
                        "prefix_cache")
                    print("[bench] shared-prefix wave B (cached, shared "
                          "preamble)", file=sys.stderr)
                    results, t0, elapsed = await run_sharded_fleet(
                        wave_b_prompts)
                elif client_procs > 1 and multi_turn <= 1:
                    results, t0, elapsed = await run_sharded_fleet(prompts)
                else:
                    tasks = [asyncio.ensure_future(one_client(i))
                             for i in range(clients)]
                    # Release the burst only once every session is
                    # connected; a wedged/failed connection surfaces
                    # through the gather below.
                    t_connect0 = _time.perf_counter()
                    done_any = asyncio.ensure_future(
                        asyncio.wait(tasks,
                                     return_when=asyncio.FIRST_EXCEPTION))
                    await asyncio.wait(
                        [asyncio.ensure_future(all_connected.wait()),
                         done_any],
                        timeout=120, return_when=asyncio.FIRST_COMPLETED)
                    connect_s = _time.perf_counter() - t_connect0
                    print(f"[bench] {connected}/{clients} sessions "
                          f"connected in {connect_s:.1f}s; releasing the "
                          f"burst", file=sys.stderr)
                    t0 = _time.perf_counter()
                    ready.set()
                    results = await asyncio.gather(*tasks)
                    elapsed = _time.perf_counter() - t0
                # Engine-side breakdown (scheduler phase counters, engine
                # TTFT, admission dispatch + block-interval percentiles) —
                # fetched while the provider is still up, so the capture
                # can attribute a slow run to engine vs relay/wire.
                try:
                    stats_client = SymmetryClient(
                        Identity.from_name("bench-stats"), TcpTransport())
                    details = await stats_client.request_provider(
                        server.address, server_ident.public_key, model_name)
                    stats_session = await stats_client.connect(details)
                    try:
                        provider_stats = await stats_session.stats()
                        engine_stats = provider_stats.get("engine")
                        # Final metrics-registry snapshot (the stats
                        # reply's tier-labeled `metrics` block): inlined
                        # into the bench JSON so every BENCH_r*.json is
                        # self-describing, and optionally its own file
                        # (--metrics-out) for offline diffing.
                        metrics_block = provider_stats.get("metrics")
                        if metrics_out and metrics_block:
                            with open(metrics_out, "w") as mf:
                                json.dump(metrics_block, mf, indent=1)
                            n_fams = sum(
                                len(s.get("snapshot", {})
                                    .get("families") or {})
                                for s in metrics_block.get("snapshots",
                                                           []))
                            print(f"[bench] metrics snapshot → "
                                  f"{metrics_out} ({n_fams} families)",
                                  file=sys.stderr)
                        if trace_out:
                            # Distributed-trace capture (utils/trace.py):
                            # one traced request measures the session's
                            # provider clock offset AND threads its trace
                            # id through provider → host → scheduler, then
                            # the merged component rings (the whole run's
                            # recent window — this request and the fleet's
                            # tail) export as one Perfetto timeline.
                            # After the stats read so counters above are
                            # unaffected; provider still up.
                            async for _ in stats_session.chat(
                                    [{"role": "user",
                                      "content": "trace capture probe"}],
                                    max_tokens=8, temperature=0.0):
                                pass
                            perfetto = await stats_client.export_trace(
                                stats_session)
                            with open(trace_out, "w") as tf:
                                json.dump(perfetto, tf)
                            comps = {e["args"]["name"]
                                     for e in perfetto["traceEvents"]
                                     if e.get("name") == "process_name"}
                            print(f"[bench] perfetto trace → {trace_out} "
                                  f"({len(perfetto['traceEvents'])} events "
                                  f"from {sorted(comps)})", file=sys.stderr)
                    finally:
                        await stats_session.close()
                except Exception as exc:  # noqa: BLE001 — diagnostics only
                    print(f"[bench] engine stats fetch failed: {exc!r}",
                          file=sys.stderr)
            await server.stop()
        finally:
            log_fh.close()

        def pct(xs, p):
            return xs[min(len(xs) - 1, int(p * len(xs)))]

        # Shed requests got an explicit busy rejection (bounded-latency
        # admission) — reported separately, excluded from every serving
        # percentile. reject_s records how fast the rejection came back.
        # p99 is the same nearest-rank estimate used everywhere else, not
        # the max it used to be mislabeled as.
        rejected = [r for r in results if r.get("rejected")]
        results = [r for r in results if not r.get("rejected")]
        if rejected:
            rj = sorted(r["reject_s"] for r in rejected)
            print(f"[bench] {len(rejected)}/{clients} requests shed "
                  f"(busy), rejection latency p50/p99 "
                  f"{pct(rj, 0.50):.2f}/{pct(rj, 0.99):.2f}s",
                  file=sys.stderr)
        if not results:
            raise RuntimeError("every request was shed — queue bound too "
                               "tight for this arrival pattern")

        # Exact wire token counts: inferenceEnded carries the engine's
        # per-request totals (ByteTokenizer chars under-count — multi-byte
        # UTF-8 assemblies collapse several byte tokens into one char).
        tokens = sum(r["tokens"] for r in results)
        ttfts = sorted(r["ttft"] for r in results)
        e2es = sorted(r["e2e"] for r in results)

        tok_s = tokens / elapsed

        # Inter-chunk gap p99: the longest stall any active stream saw
        # between consecutive deltas. The admission cap + chunked prefill
        # exist to bound this near one decode-block time — an unbounded
        # value means admissions are freezing active streams.
        gaps: list[float] = []
        for r in results:
            ts = [t for (t, _) in r["stamps"]]
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        gaps.sort()
        gap_p99 = pct(gaps, 0.99) if gaps else None

        # STEADY-STATE wire rate: the window where every client is live
        # (after the admission ramp, before the first completion) — the
        # number comparable to the engine-only bench. Char arrivals in
        # the window are scaled to tokens by each client's own
        # tokens/chars ratio.
        t1 = max(r["t_first"] for r in results)
        t2 = min(r["t_done"] for r in results)
        steady_tok_s = None
        if t2 > t1 + 0.5:
            window_tokens = 0.0
            for r in results:
                if not r["chars"]:
                    continue
                ratio = r["tokens"] / r["chars"]
                window_tokens += ratio * sum(
                    c for (t, c) in r["stamps"] if t1 < t <= t2)
            steady_tok_s = window_tokens / (t2 - t1)
        dtype_label = f"{dtype_name}+{quant}" if quant else dtype_name
        if kv_quant:
            dtype_label += "+kv8"
        if fused_dequant:
            dtype_label += "+fused"

        # ------------------------------------------------------------------
        # Per-phase breakdown (round-3 verdict #1): the capture must carry
        # its own explanation. Ramp = burst start → every client streaming;
        # steady = every client live; tail = first completion → last.
        ramp_s = t1 - t0
        steady_s = max(t2 - t1, 0.0)
        tail_s = max(elapsed - (t2 - t0), 0.0)
        phases = {
            "startup_s": round(startup_s, 1),
            "ramp_s": round(ramp_s, 2),
            "steady_s": round(steady_s, 2),
            "tail_s": round(tail_s, 2),
        }
        print(f"[bench] phases: startup {startup_s:.0f}s (excluded) | "
              f"ramp {ramp_s:.1f}s (admission of {clients} prompts) | "
              f"steady {steady_s:.1f}s @ "
              f"{steady_tok_s and round(steady_tok_s) or '?'} tok/s | "
              f"tail {tail_s:.1f}s", file=sys.stderr)

        # The device is whatever the ENGINE HOST reported (READY frame →
        # stats `startup` block); this process never asks JAX. A run whose
        # engine was not on a TPU measured nothing this bench reports —
        # except the multi-host modes, which are CPU-only for now and
        # carry that in their metric line and `device` block.
        host_device = ((engine_stats or {}).get("startup")
                       or {}).get("device")
        if (host_device or {}).get("platform") != "tpu" and not disagg:
            raise RuntimeError(
                f"e2e bench: the engine host reported device "
                f"{host_device}, not a TPU — no rate measured there is a "
                f"device number")
        device_label = (f"{host_device['device_count']} "
                        f"{host_device['platform']} dev"
                        if host_device else "device not reported")

        diag: dict = {}
        ttft_stages = None
        spec_stats = None
        if engine_stats:
            # Three TTFT vantage points bracket any stall: engine (first
            # sampled token), provider (first chunk leaving the backend
            # for the wire), client (first delta received). engine ≈
            # provider << client → the stall is wire/client-loop;
            # provider >> engine → the host→provider relay.
            prov_ttft = (provider_stats or {}).get("ttft_s") or {}
            ttft_h = engine_stats.get("engine_ttft_s") or {}
            admit_h = engine_stats.get("admit_dispatch_s") or {}
            ival_h = engine_stats.get("block_interval_s") or {}
            diag = {
                "provider_ttft_p50_s": _rnd(prov_ttft.get("p50")),
                "provider_ttft_p99_s": _rnd(prov_ttft.get("p99")),
                "engine_ttft_p50_s": _rnd(ttft_h.get("p50")),
                "engine_ttft_p99_s": _rnd(ttft_h.get("p99")),
                "admit_dispatches": engine_stats.get("admit_dispatches"),
                "admit_dispatch_p99_s": _rnd(admit_h.get("p99")),
                "admit_total_s": _rnd(engine_stats.get("admit_s")),
                "block_interval_p50_s": _rnd(ival_h.get("p50")),
                "block_interval_p99_s": _rnd(ival_h.get("p99")),
                "block_syncs": engine_stats.get("block_syncs"),
                "sync_total_s": _rnd(engine_stats.get("sync_s")),
            }
            # Emit-path accounting (block-coalesced host protocol + wire
            # corking): pipe writes per decode block should sit near 1 —
            # O(slots) would mean the batched `events` frame regressed —
            # and wire writes below wire frames means per-peer corking is
            # collapsing the fan-out.
            emit_h = engine_stats.get("emit") or {}
            wire = (provider_stats or {}).get("wire") or {}
            blocks = engine_stats.get("block_syncs") or 0
            if emit_h:
                diag["pipe_writes"] = emit_h.get("pipe_writes")
                diag["pipe_event_writes"] = emit_h.get("pipe_event_writes")
                diag["pipe_events"] = emit_h.get("pipe_events")
                if blocks:
                    # Event-carrying writes only: ready/stats frames are
                    # pipe traffic but not emit-path traffic, and must
                    # not smear the O(1)-writes-per-block reading.
                    diag["pipe_writes_per_block"] = _rnd(
                        (emit_h.get("pipe_event_writes") or 0) / blocks)
            if wire:
                diag["wire_writes"] = wire.get("writes")
                diag["wire_frames"] = wire.get("frames")
                diag["wire_coalesced_frames"] = wire.get("coalesced_frames")
                diag["wire_bytes"] = wire.get("bytes")
            emit_parts = []
            if emit_h:
                wpb = (f" ({diag['pipe_writes_per_block']} writes/block)"
                       if blocks else "")
                emit_parts.append(
                    f"{diag.get('pipe_event_writes')} event pipe writes "
                    f"/ {diag.get('pipe_events')} events over {blocks} "
                    f"blocks{wpb}")
            if wire:
                emit_parts.append(
                    f"wire {diag.get('wire_writes')} writes / "
                    f"{diag.get('wire_frames')} frames "
                    f"({diag.get('wire_coalesced_frames')} corked)")
            if emit_parts:
                print("[bench] emit path: " + " | ".join(emit_parts),
                      file=sys.stderr)
            # Convert-wall metrics (scheduler stats): per-step decode
            # wall + the weight bytes it streams — the decode-floor
            # number now lands in every BENCH_r*.json engine block, not
            # only the engine-only bench (fused-dequant A/B reads it).
            for key in ("decode_step_ms", "weight_bytes_per_step",
                        "weight_stream_gbs", "weight_stream_gbs_per_device"):
                if engine_stats.get(key) is not None:
                    diag[key] = engine_stats[key]
            if diag.get("decode_step_ms") is not None:
                wb = diag.get("weight_bytes_per_step") or 0
                print(f"[bench] decode step {diag['decode_step_ms']} ms | "
                      f"weight stream {wb / 1e6:.0f} MB/step @ "
                      f"{diag.get('weight_stream_gbs')} GB/s effective",
                      file=sys.stderr)
            # Overlapped-scheduler split (round-16): how much of the
            # engine thread's wall was spent on the dispatch loop proper
            # vs work the emit worker absorbed, plus the configured
            # pipeline depth — the A/B number for depth 1 vs 2 rides
            # every BENCH_r*.json engine block.
            if engine_stats.get("pipeline_depth") is not None:
                diag["pipeline_depth"] = engine_stats["pipeline_depth"]
                diag["dispatch_thread_s"] = _rnd(
                    engine_stats.get("dispatch_thread_s"))
                diag["offloaded_s"] = _rnd(engine_stats.get("offloaded_s"))
                dtb = engine_stats.get("dispatch_thread_block_s") or {}
                if dtb:
                    diag["dispatch_thread_block_p50_s"] = _rnd(
                        dtb.get("p50"), 5)
                    diag["dispatch_thread_block_p99_s"] = _rnd(
                        dtb.get("p99"), 5)
                print(f"[bench] pipeline depth "
                      f"{diag['pipeline_depth']} | dispatch thread "
                      f"{diag['dispatch_thread_s']}s | offloaded "
                      f"{diag['offloaded_s']}s | dispatch-thread block "
                      f"p50/p99 {diag.get('dispatch_thread_block_p50_s')}/"
                      f"{diag.get('dispatch_thread_block_p99_s')}s",
                      file=sys.stderr)
            print(
                "[bench] engine: "
                f"ttft p50/p99 {diag['engine_ttft_p50_s']}/"
                f"{diag['engine_ttft_p99_s']}s | provider ttft p50/p99 "
                f"{diag['provider_ttft_p50_s']}/"
                f"{diag['provider_ttft_p99_s']}s | "
                f"{diag['admit_dispatches']} admit dispatches "
                f"(p99 {diag['admit_dispatch_p99_s']}s, "
                f"total {diag['admit_total_s']}s) | "
                f"block interval p50/p99 {diag['block_interval_p50_s']}/"
                f"{diag['block_interval_p99_s']}s over "
                f"{diag['block_syncs']} blocks",
                file=sys.stderr)
            # Per-stage TTFT attribution (round-4 task #3): where the
            # time between client send and first delta actually went —
            # submit (provider→pipe), pipe_in (pipe + host tokenize),
            # queue (scheduler inbox), prefill (placement→first token),
            # emit (block-flush hold), relay (pipe out + provider loop).
            # The FULL per-stage breakdown (not just the printed p50
            # line) rides the final JSON as `ttft_stages`, so BENCH_r*.json
            # captures it for trajectory analysis.
            stages = engine_stats.get("stages") or {}
            if stages:
                order = ("submit", "pipe_in", "queue", "prefill",
                         "emit", "relay")
                diag["stage_p50_s"] = {
                    k: _rnd((stages.get(k) or {}).get("p50"))
                    for k in order if k in stages}
                diag["stage_p99_s"] = {
                    k: _rnd((stages.get(k) or {}).get("p99"))
                    for k in order if k in stages}
                ttft_stages = {
                    k: {m: _rnd(v, 4) for m, v in (stages[k] or {}).items()}
                    for k in order if k in stages}
                print("[bench] ttft stages p50 (s): "
                      + " | ".join(f"{k} {diag['stage_p50_s'][k]}"
                                   for k in order
                                   if k in diag["stage_p50_s"]),
                      file=sys.stderr)
            # Shared-prefix KV cache counters (host stats → provider
            # stats → here): hit rate, reuse volume, eviction churn.
            pc = engine_stats.get("prefix_cache")
            if pc:
                diag["prefix_cache"] = pc
                print(f"[bench] prefix cache: hit rate {pc.get('hit_rate')} "
                      f"({pc.get('hits')} hits / {pc.get('misses')} misses)"
                      f" | {pc.get('tokens_reused')} prefill tokens reused"
                      f" | {pc.get('insertions')} stored, "
                      f"{pc.get('evictions')} evicted, "
                      f"{pc.get('bytes')} / {pc.get('budget_bytes')} bytes",
                      file=sys.stderr)
            # Speculative decoding counters (host stats → provider stats
            # → here): drafted/accepted volume, acceptance rate, and the
            # tokens-per-verify-dispatch distribution.
            spec_stats = engine_stats.get("speculative")
            if spec_stats:
                tpd = spec_stats.get("tokens_per_dispatch") or {}
                print(f"[bench] speculative: "
                      f"{spec_stats.get('verify_blocks')} verify blocks | "
                      f"{spec_stats.get('drafted')} drafted, "
                      f"{spec_stats.get('accepted')} accepted "
                      f"(rate {spec_stats.get('acceptance_rate')}), "
                      f"{spec_stats.get('rolled_back')} rolled back | "
                      f"tokens/dispatch p50/p99 "
                      f"{_rnd(tpd.get('p50'))}/{_rnd(tpd.get('p99'))}",
                      file=sys.stderr)
            # Disaggregation ledger (broker counters + the prefill
            # host's own stats, nested under engine.disagg): handoff
            # frames/bytes, prefill-tier residency percentiles, and the
            # per-tier serialize/adopt walls — the acceptance contract
            # is that these flow host stats → provider stats → HERE.
            dg = engine_stats.get("disagg")
            if dg:
                diag["disagg"] = dg
                pt = dg.get("prefill_tier_s") or {}
                ph = dg.get("prefill_host") or {}
                ho = ph.get("handoff") or {}
                ad = engine_stats.get("adopt") or {}
                # The handoff cost SPLIT as explicit top-level fields
                # (they used to be one opaque number inside nested host
                # stats): serialize = the prefill host's frame-encode
                # wall; wire = emit → broker receipt through the pipe
                # (local pair) or the chunked link (network mode), on
                # reconciled clocks. Link counters (retries, credit
                # stalls) ride when the cross-machine link is in play.
                ws = dg.get("wire_s") or {}
                diag["disagg"]["handoff_serialize_s"] = \
                    ho.get("serialize_s")
                diag["disagg"]["handoff_wire_s_total"] = \
                    dg.get("wire_s_total")
                node = dg.get("node") or {}
                link = dg.get("link") or {}
                if node or link:
                    diag["disagg"]["handoff_wire"] = {
                        "retries": node.get("retries"),
                        "failed": node.get("failed"),
                        "credit_stalls": node.get("credit_stalls"),
                        "credit_stall_s": node.get("credit_stall_s"),
                        "connects": link.get("connects"),
                        "drops": link.get("drops"),
                        "partial_discards": link.get("partial_discards"),
                    }
                # Elastic-pool block (--disagg-pool): per-node
                # membership + placements and the churn ledger
                # (re-placements after any node loss during the run) —
                # the 2×2-vs-1×1 row schema of the pre-registered
                # BASELINE.md pool protocol.
                pool = dg.get("pool")
                if pool:
                    diag["disagg"]["pool"] = pool
                    per_node = {mid: m.get("placements")
                                for mid, m in
                                (pool.get("members") or {}).items()}
                    print(f"[bench] disagg pool: healthy "
                          f"{pool.get('healthy')} | placements "
                          f"{per_node} | re-placements "
                          f"{pool.get('re_placements')} | losses "
                          f"{pool.get('losses')} | drains "
                          f"{pool.get('drains')}", file=sys.stderr)
                print(f"[bench] disagg: {dg.get('handoff_frames')} "
                      f"handoffs / {dg.get('handoff_bytes')} bytes "
                      f"({dg.get('prefix_tokens')} prefix tokens, "
                      f"{dg.get('routing_only')} routing-only) | "
                      f"prefill tier p50/p99 {_rnd(pt.get('p50'))}/"
                      f"{_rnd(pt.get('p99'))}s | serialize "
                      f"{ho.get('serialize_s')}s | wire p50/p99 "
                      f"{_rnd(ws.get('p50'))}/{_rnd(ws.get('p99'))}s "
                      f"(total {_rnd(dg.get('wire_s_total'))}s"
                      + (f", {node.get('retries')} retries, "
                         f"{node.get('credit_stalls')} credit stalls"
                         if node else "")
                      + f") | adopt {ad.get('deserialize_s')}s "
                      f"host-side, "
                      f"{_rnd(engine_stats.get('adopt_s'))}s dispatch",
                      file=sys.stderr)
            # The attribution that mattered in round 3: wire TTFT far above
            # engine TTFT means the stall is relay/wire/client-loop, not
            # admission.
            wire_p50 = pct(ttfts, 0.50)
            eng_p50 = ttft_h.get("p50")
            if eng_p50 and wire_p50 > 2.0 * eng_p50 + 1.0:
                print(f"[bench] WARNING: wire TTFT p50 {wire_p50:.1f}s >> "
                      f"engine TTFT p50 {eng_p50:.1f}s — the gap is in the "
                      f"relay/wire/client loop, not the engine",
                      file=sys.stderr)
        try:
            with open(log_path) as lf:
                tail_lines = lf.readlines()[-8:]
            print("[bench] provider log tail:", file=sys.stderr)
            for ln in tail_lines:
                print(f"  {ln.rstrip()}", file=sys.stderr)
        except OSError:
            pass

        speculative_block = None
        if speculative and results_plain is not None:
            ok_p = [r for r in results_plain if not r.get("rejected")]
            plain_tokens = sum(r["tokens"] for r in ok_p)
            plain_tok_s = (plain_tokens / plain_elapsed
                           if plain_elapsed else None)
            tp = sorted(r["ttft"] for r in ok_p)
            speculative_block = {
                "tok_s_plain": _rnd(plain_tok_s, 1),
                "tok_s_speculative": round(tok_s, 1),
                "speedup": (round(tok_s / plain_tok_s, 3)
                            if plain_tok_s else None),
                "ttft_p50_plain_s": (round(pct(tp, 0.50), 3)
                                     if tp else None),
                "ttft_p50_speculative_s": round(pct(ttfts, 0.50), 3),
            }
            if spec_stats:
                # Wave-B delta: cumulative counters minus the between-
                # waves snapshot. Wave A requests opt out of drafting, so
                # its contribution should be ~0, but the subtraction
                # keeps the quoted numbers honest either way.
                base = spec_after_wave_a or {}
                for key in ("verify_blocks", "drafted", "accepted",
                            "rolled_back", "spec_tokens"):
                    speculative_block[key] = (spec_stats.get(key, 0)
                                              - base.get(key, 0))
                drafted = speculative_block["drafted"]
                speculative_block["acceptance_rate"] = (
                    round(speculative_block["accepted"] / drafted, 4)
                    if drafted else None)
                speculative_block["tokens_per_dispatch"] = (
                    spec_stats.get("tokens_per_dispatch"))
            print(f"[bench] speculative vs plain (same prompts, same "
                  f"provider): {speculative_block['tok_s_plain']} tok/s "
                  f"plain → {speculative_block['tok_s_speculative']} "
                  f"tok/s speculative "
                  f"(x{speculative_block['speedup']})", file=sys.stderr)

        shared_block = None
        if shared_prefix and results_uncached is not None:
            ok_a = [r for r in results_uncached if not r.get("rejected")]
            ta = sorted(r["ttft"] for r in ok_a)
            shared_block = {
                "uncached_admitted": len(ok_a),
                "ttft_p50_uncached_s": (round(pct(ta, 0.50), 3)
                                        if ta else None),
                "ttft_p99_uncached_s": (round(pct(ta, 0.99), 3)
                                        if ta else None),
                "ttft_p50_cached_s": round(pct(ttfts, 0.50), 3),
                "ttft_p99_cached_s": round(pct(ttfts, 0.99), 3),
            }
            pc_end = diag.get("prefix_cache")
            if pc_end:
                # Wave-B delta: cumulative counters minus the between-
                # waves snapshot, so the quoted hit rate is the cached
                # wave's own, undiluted by wave A's intentional misses.
                base = pc_after_wave_a or {}
                d_hits = pc_end.get("hits", 0) - base.get("hits", 0)
                d_miss = pc_end.get("misses", 0) - base.get("misses", 0)
                shared_block["cached_wave_hits"] = d_hits
                shared_block["cached_wave_misses"] = d_miss
                shared_block["hit_rate"] = (
                    round(d_hits / (d_hits + d_miss), 4)
                    if d_hits + d_miss else None)
            if ta:
                print(f"[bench] shared-prefix: TTFT p50 uncached "
                      f"{shared_block['ttft_p50_uncached_s']}s → cached "
                      f"{shared_block['ttft_p50_cached_s']}s (p99 "
                      f"{shared_block['ttft_p99_uncached_s']} → "
                      f"{shared_block['ttft_p99_cached_s']})",
                      file=sys.stderr)

        multi_turn_block = None
        if multi_turn > 1:
            first = sorted(r["turn_ttfts"][0] for r in results
                           if r.get("turn_ttfts"))
            later = sorted(t for r in results
                           for t in r.get("turn_ttfts", [])[1:])
            if first and later:
                # The per-turn TTFT CURVE vs history length — the radix
                # cache's "done" evidence (ROADMAP item 3): every turn's
                # prompt is strictly longer than the last, so a flat or
                # falling curve means admission cost tracks the NEW
                # tokens, not the history.
                by_turn = []
                for t in range(multi_turn):
                    vals = sorted(r["turn_ttfts"][t] for r in results
                                  if len(r.get("turn_ttfts", [])) > t)
                    by_turn.append(round(pct(vals, 0.50), 3)
                                   if vals else None)
                multi_turn_block = {
                    "turns": multi_turn,
                    "sessions": len(results),
                    "ttft_turn1_p50_s": round(pct(first, 0.50), 3),
                    "ttft_turn1_p99_s": round(pct(first, 0.99), 3),
                    "ttft_turn2plus_p50_s": round(pct(later, 0.50), 3),
                    "ttft_turn2plus_p99_s": round(pct(later, 0.99), 3),
                    "ttft_by_turn_p50_s": by_turn,
                    # > 1 means later turns admit faster than turn 1
                    # even though their prompts are LONGER — the session
                    # cache (and, disaggregated, the prefix handoff)
                    # paying for itself.
                    "turn2plus_speedup": (
                        round(pct(first, 0.50) / pct(later, 0.50), 3)
                        if pct(later, 0.50) else None),
                }
                pc = (diag or {}).get("prefix_cache") or {}
                if pc.get("blocks_total"):
                    # Session-cache memory economics: peak pool
                    # occupancy and blocks in use at run end, per the
                    # paged-KV accounting in engine/prefix_cache.py.
                    multi_turn_block["prefix"] = {
                        "block_tokens": pc.get("block_tokens"),
                        "blocks_in_use": pc.get("blocks_in_use"),
                        "blocks_total": pc.get("blocks_total"),
                        "hbm_high_water_bytes": pc.get(
                            "hbm_high_water_bytes"),
                        "hit_rate": pc.get("hit_rate"),
                    }
                print(f"[bench] multi-turn: TTFT p50 turn-1 "
                      f"{multi_turn_block['ttft_turn1_p50_s']}s → "
                      f"turn-2+ "
                      f"{multi_turn_block['ttft_turn2plus_p50_s']}s "
                      f"(x{multi_turn_block['turn2plus_speedup']} though "
                      f"later prompts are longer; p99 "
                      f"{multi_turn_block['ttft_turn1_p99_s']} → "
                      f"{multi_turn_block['ttft_turn2plus_p99_s']})",
                      file=sys.stderr)
                print(f"[bench] multi-turn TTFT p50 by turn: "
                      f"{multi_turn_block['ttft_by_turn_p50_s']}",
                      file=sys.stderr)
                if "prefix" in multi_turn_block:
                    px = multi_turn_block["prefix"]
                    print(f"[bench] prefix pool: "
                          f"{px['blocks_in_use']}/{px['blocks_total']} "
                          f"blocks x {px['block_tokens']} tok, HBM "
                          f"high-water {px['hbm_high_water_bytes']} B, "
                          f"hit rate {px['hit_rate']}", file=sys.stderr)

        # symledger rollup: per-request cost blocks from the end frames
        # (client-observed, so percentiles are over exactly the admitted
        # fleet) + the provider's own SLO-gated goodput window. Absent
        # when tpu.ledger is off — the A/B overhead run's other arm.
        ledger_block = None
        cost_blocks = [r["costs"] for r in results if r.get("costs")]
        for r in results:
            cost_blocks.extend(r.get("cost_blocks") or [])
        if cost_blocks:
            devs = sorted(float(c.get("device_total_s") or 0.0)
                          for c in cost_blocks)
            queues = sorted(float(c.get("queue_s") or 0.0)
                            for c in cost_blocks)
            device = sum(devs)
            wasted = sum(float(c.get("wasted_total_s") or 0.0)
                         for c in cost_blocks)
            saved = sum(float(c.get("saved_s") or 0.0)
                        for c in cost_blocks)
            ctokens = sum(int(c.get("tokens") or 0) for c in cost_blocks)
            ledger_block = {
                "requests": len(cost_blocks),
                "source": cost_blocks[0].get("source"),
                "device_s_p50": round(pct(devs, 0.50), 6),
                "device_s_p99": round(pct(devs, 0.99), 6),
                "device_s_total": round(device, 6),
                "queue_s_p99": round(pct(queues, 0.99), 6),
                "wasted_s_total": round(wasted, 6),
                "wasted_share": (round(wasted / (device + wasted), 4)
                                 if device + wasted > 0 else None),
                "saved_s_total": round(saved, 6),
                "goodput_tokens_per_device_s": (
                    round(ctokens / device, 2) if device > 0 else None),
            }
            gp = (provider_stats or {}).get("goodput")
            if gp:
                # The provider-side verdict (SLO-attaining tokens only)
                # next to the raw client-side ratio above.
                ledger_block["slo_goodput"] = gp
            print(f"[bench] ledger ({ledger_block['source']}): device "
                  f"p50/p99 {ledger_block['device_s_p50']}/"
                  f"{ledger_block['device_s_p99']}s per request | wasted "
                  f"share {ledger_block['wasted_share']} | goodput "
                  f"{ledger_block['goodput_tokens_per_device_s']} "
                  f"tok/device-s", file=sys.stderr)

        return {
            "metric": f"e2e serving tok/s ({preset_name} {dtype_label}, "
                      f"{clients} streaming clients over TCP"
                      + (f" ({arrival} arrivals over "
                         f"{arrival_duration_s:g}s)" if arrival
                         else f" @ {stagger_s}s stagger" if stagger_s
                         else " (burst)")
                      + (", shared-prefix cached wave" if shared_prefix
                         else "")
                      + (f", speculative wave (k={draft_k})" if speculative
                         else "")
                      + ((", disagg "
                          + (f"{disagg_pool[0]}x{disagg_pool[1]} pool"
                             if disagg_pool else "prefill/decode tiers")
                          + (f" over {disagg_transport} link"
                             if disagg_transport else ""))
                         if disagg else "")
                      + (f", {multi_turn}-turn sessions" if multi_turn > 1
                         else "")
                      + f", {max_new} tok/req, {slots} slots, block {block}, "
                        f"provider subprocess, {device_label})",
            "device": host_device,
            **({"device_note": CPU_ONLY_NOTE} if disagg else {}),
            "value": round(tok_s, 1),
            "unit": "tok/s",
            "vs_baseline": round(tok_s / 2000.0, 3),
            "ttft_p50_s": round(pct(ttfts, 0.50), 3),
            "ttft_p99_s": round(pct(ttfts, 0.99), 3),
            "e2e_p50_s": round(pct(e2es, 0.50), 3),
            "e2e_p99_s": round(pct(e2es, 0.99), 3),
            "tokens_streamed": tokens,
            "wall_s": round(elapsed, 2),
            "mean_ttft_s": round(statistics.mean(ttfts), 3),
            "steady_state_tok_s": (round(steady_tok_s, 1)
                                   if steady_tok_s else None),
            "inter_chunk_gap_p99_s": (round(gap_p99, 3)
                                      if gap_p99 is not None else None),
            "phases": phases,
            **({"client_procs": client_procs} if client_procs > 1 else {}),
            **({"arrival": {"kind": arrival,
                            "duration_s": arrival_duration_s,
                            "seed": arrival_seed}}
               if arrival else {}),
            **({"admitted": len(results), "rejected": len(rejected),
                "reject_p99_s": round(pct(rj, 0.99), 3)}
               if rejected else {}),
            **({"shared_prefix": shared_block} if shared_block else {}),
            **({"speculative": speculative_block}
               if speculative_block else {}),
            **({"multi_turn": multi_turn_block} if multi_turn_block
               else {}),
            # symledger rollup: cost percentiles, wasted share, and the
            # goodput row — the capture's attribution headline.
            **({"ledger": ledger_block} if ledger_block else {}),
            # Satellite of the speculative PR: the per-stage TTFT
            # breakdown lands in the JSON capture, not just stderr text.
            **({"ttft_stages": ttft_stages} if ttft_stages else {}),
            **({"engine": diag} if diag else {}),
            # Final metrics-registry snapshot (tier-labeled): the bench
            # artifact carries the fleet-telemetry cut it ended with.
            **({"metrics": metrics_block} if metrics_block else {}),
        }

    return asyncio.new_event_loop().run_until_complete(main())


def run_proxy(*, clients: int, max_new: int, token_delay_s: float) -> dict:
    """The PR1 REFERENCE POINT (BASELINE config 1): the reference's own
    architecture — P2P glue proxying to an external OpenAI-compatible
    HTTP server (reference hot loop: src/provider.ts:240-258). An in-repo
    fake Ollama (tools/fake_ollama.py) stands in for the backend emitting
    instantly, so the measured number is the proxy path's own throughput
    ceiling and per-chunk overhead — the baseline the tpu_native numbers
    are compared against."""
    import asyncio
    import hashlib
    import os
    import statistics
    import subprocess
    import sys
    import tempfile
    import time as _time

    import yaml

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from fake_ollama import start_server

    from symmetry_tpu.client.client import SymmetryClient
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.server.broker import SymmetryServer
    from symmetry_tpu.transport.tcp import TcpTransport

    model_name = "llama3:8b"
    server_ident = Identity.from_name("bench-proxy-server")

    async def main() -> dict:
        backend_runner, backend_port = await start_server(
            "127.0.0.1", 0, token_delay_s)
        server = SymmetryServer(server_ident, TcpTransport(),
                                ping_interval_s=60.0)
        await server.start("tcp://127.0.0.1:0")
        cfg = {
            "name": "bench-proxy-prov",
            "public": True,
            "serverKey": server_ident.public_hex,
            "serverAddress": server.address,
            "modelName": model_name,
            "apiProvider": "ollama",
            "apiProtocol": "http",
            "apiHostname": "127.0.0.1",
            "apiPort": backend_port,
            "apiPath": "/v1/chat/completions",
            "dataCollectionEnabled": False,
            "maxConnections": clients + 8,
            "listenHost": "127.0.0.1",
            "privateSeed": hashlib.blake2b(
                b"bench-proxy-seed", digest_size=32).hexdigest(),
        }
        async def one_client(i: int) -> dict:
            client = SymmetryClient(
                Identity.from_name(f"bench-proxy-cli-{i}"), TcpTransport())
            details = await client.request_provider(
                server.address, server_ident.public_key, model_name)
            session = await client.connect(details)
            t_send = _time.perf_counter()
            t_first = None
            chunks = 0
            try:
                async for delta in session.chat(
                        [{"role": "user", "content": "benchmark prompt"}],
                        max_tokens=max_new):
                    now = _time.perf_counter()
                    if t_first is None and delta:
                        t_first = now
                    chunks += 1
            finally:
                await session.close()
            t_done = _time.perf_counter()
            return {"ttft": (t_first or t_done) - t_send,
                    "e2e": t_done - t_send, "chunks": chunks}

        try:
            async with _provider_process(cfg, server, model_name,
                                         timeout_s=120,
                                         stdout=subprocess.DEVNULL):
                t0 = _time.perf_counter()
                results = await asyncio.gather(
                    *(one_client(i) for i in range(clients)))
                elapsed = _time.perf_counter() - t0
        finally:
            await server.stop()
            await backend_runner.cleanup()

        chunks = sum(r["chunks"] for r in results)
        ttfts = sorted(r["ttft"] for r in results)
        tok_s = chunks / elapsed
        return {
            "metric": f"proxy-path serving tok/s (reference architecture: "
                      f"fake-Ollama SSE backend, {clients} streaming "
                      f"clients over TCP, provider subprocess)",
            "value": round(tok_s, 1),
            "unit": "tok/s",
            "vs_baseline": round(tok_s / 2000.0, 3),
            "ttft_p50_s": round(ttfts[len(ttfts) // 2], 4),
            "ttft_p99_s": round(ttfts[min(len(ttfts) - 1,
                                          int(0.99 * len(ttfts)))], 4),
            "mean_e2e_s": round(statistics.mean(r["e2e"] for r in results), 3),
            "chunks_streamed": chunks,
            "per_chunk_overhead_ms": round(
                1e3 * clients * elapsed / max(chunks, 1), 3),
            "wall_s": round(elapsed, 2),
        }

    return asyncio.new_event_loop().run_until_complete(main())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-safe tiny-model run (verification, not perf)")
    ap.add_argument("--e2e", action="store_true",
                    help="full serving path: server + provider + N "
                         "streaming clients over TCP (north-star metric; "
                         "the DEFAULT when no mode flag is given)")
    ap.add_argument("--engine", action="store_true",
                    help="engine-only decode loop (no serving stack)")
    ap.add_argument("--proxy", action="store_true",
                    help="PR1 reference point: proxy backend against an "
                         "in-repo fake-Ollama SSE server (no TPU)")
    ap.add_argument("--proxy-delay", type=float, default=0.0,
                    help="fake backend's per-chunk delay seconds (--proxy)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-prefix workload (--e2e): wave A of "
                         "unique-preamble prompts (uncached), then wave B "
                         "sharing one long preamble — the prefix KV cache "
                         "serves wave B's admissions from cached KV and "
                         "the run reports cached vs uncached TTFT on the "
                         "same provider (tpu.prefix_cache_mb)")
    ap.add_argument("--prefix-cache-mb", type=float, default=None,
                    help="shared-prefix KV cache HBM budget in MiB "
                         "(tpu.prefix_cache_mb). Default: 128 in "
                         "--shared-prefix mode, disabled otherwise")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative-decoding workload (--e2e): a "
                         "repetition-heavy greedy workload runs twice on "
                         "one provider with tpu.speculative on — wave A "
                         "opts every request out of drafting (plain "
                         "decode), wave B drafts with n-gram prompt "
                         "lookup and batched verify — and the run reports "
                         "speculative vs plain tok/s plus drafted/"
                         "accepted/acceptance-rate counters")
    ap.add_argument("--draft-k", type=int, default=8,
                    help="draft tokens per slot per verify dispatch "
                         "(tpu.speculative k_draft; --speculative only)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode (--e2e): the "
                         "provider runs a prefill host + decode host "
                         "pair (tpu.role: disagg) with versioned KV "
                         "handoff frames between them; handoff "
                         "frames/bytes and prefill-tier latency land in "
                         "the JSON's engine.disagg block. The disagg "
                         "A/B is this flag on vs off at otherwise "
                         "identical settings")
    ap.add_argument("--disagg-transport", default=None,
                    choices=("memory", "tcp"),
                    help="run the disagg pair over the CROSS-MACHINE "
                         "handoff link (engine/disagg/net.py) instead "
                         "of local pipes: the provider runs the decode "
                         "tier + an inline prefill node joined only by "
                         "the chunked/credit-gated link (memory = "
                         "in-process frame queues, tcp = real loopback "
                         "sockets). Adds handoff wire latency/bytes/"
                         "retries/credit-stalls to the JSON beside the "
                         "serialize wall (--disagg only)")
    ap.add_argument("--disagg-pool", default=None, metavar="MxN",
                    help="elastic M-prefill × N-decode pool (implies "
                         "--disagg): M inline prefill members + N local "
                         "decode hosts joined by per-member handoff "
                         "links (engine/disagg/pool.py), least-loaded "
                         "placement, per-node supervision. Per-node "
                         "placements and churn re-placements land in "
                         "the JSON's engine.disagg.pool block — the "
                         "2x2-vs-1x1 row schema of the BASELINE.md "
                         "pool protocol. Transport from "
                         "--disagg-transport (memory default)")
    ap.add_argument("--chaos", action="store_true",
                    help="kill-under-load robustness bench: arm "
                         "--chaos-seam on provider 1's engine host, run "
                         "the client fleet through chat_failover with "
                         "stream resumption ON then OFF, and report "
                         "wasted-work tokens (regenerated − resumed) "
                         "plus post-kill recovery latency per arm "
                         "(BASELINE.md Round 14). Sized small by "
                         "default (8 clients × 64 tok); --clients/"
                         "--max-new/--preset rescale it")
    ap.add_argument("--chaos-seam", default="host.pipe_write=crash@nth=12",
                    metavar="SEAM=ACTION@TRIGGER",
                    help="the fault armed on provider 1's host for "
                         "--chaos (utils/faults.py grammar). The default "
                         "crash lands a few event frames into the first "
                         "wave at the default chaos shape; retune nth "
                         "for bigger fleets")
    ap.add_argument("--autoscale", action="store_true",
                    help="SLO-goodput autoscaling bench: replay one "
                         "seeded --arrival trace (diurnal default) "
                         "against an autoscaled 1x1 pool (tpu.autoscale "
                         "closed loop, engine/disagg/autoscale.py) and "
                         "each --autoscale-static MxN control in ONE "
                         "invocation; per arm: SLO attainment, "
                         "chip-seconds (Σ member-alive time), and "
                         "goodput = SLO-attaining tokens per "
                         "chip-second (BASELINE.md Round 18). Sized "
                         "small by default (24 clients x 48 tok)")
    ap.add_argument("--autoscale-static", default="1x1,2x1,2x2",
                    metavar="MxN[,MxN...]",
                    help="static control shapes for --autoscale; the "
                         "verdict compares the autoscaled arm's "
                         "chip-seconds against the cheapest control "
                         "that also meets the SLOs")
    ap.add_argument("--autoscale-max-members", type=int, default=2,
                    help="per-tier member ceiling for the autoscaled "
                         "arm (tpu.autoscale.max_members)")
    ap.add_argument("--arrival", default=None,
                    choices=("poisson", "diurnal", "burst"),
                    help="open-loop arrival trace replacing the "
                         "--stagger ramp: seeded per-client send "
                         "offsets over --arrival-duration (poisson = "
                         "memoryless steady load, diurnal = "
                         "trough-peak-trough day curve, burst = 4 "
                         "thundering-herd waves). Works under --e2e "
                         "and --autoscale (where diurnal is the "
                         "default)")
    ap.add_argument("--arrival-duration", type=float, default=45.0,
                    metavar="S",
                    help="window the --arrival trace spans, seconds")
    ap.add_argument("--arrival-seed", type=int, default=0,
                    help="RNG seed for the --arrival trace (same seed "
                         "= same offsets, across runs and arms)")
    ap.add_argument("--slo-ttft", type=float, default=2.5, metavar="S",
                    help="--autoscale TTFT target: a request attains "
                         "its SLO only if first token lands within "
                         "this; also the provider slo: block's ttft_s "
                         "(the burn the controller scales on)")
    ap.add_argument("--slo-chunk", type=float, default=1.5, metavar="S",
                    help="--autoscale inter-chunk gap target "
                         "(slo: inter_chunk_s)")
    ap.add_argument("--slo-objective", type=float, default=0.9,
                    help="fraction of requests that must attain their "
                         "SLOs for an arm to count as meeting them")
    ap.add_argument("--multi-turn", type=int, default=1, metavar="N",
                    help="conversation workload (--e2e): every client "
                         "runs one N-turn session, re-submitting the "
                         "full history each turn, greedy. Reports "
                         "turn-1 vs turn-2+ TTFT — the session-cache "
                         "workload where the prefix cache (enabled by "
                         "default here) and --disagg prefix handoff "
                         "should shine. Runs the inline client fleet "
                         "(client-procs forced to 1)")
    ap.add_argument("--preset", default="llama3-8b")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode slots (default 128; 96 in shared-prefix "
                         "mode — the larger prompt bucket plus the cache "
                         "budget must leave the ~95%%-full default HBM "
                         "point some slack)")
    ap.add_argument("--steps", type=int, default=192)
    ap.add_argument("--clients", type=int, default=None,
                    help="concurrent streaming clients (--e2e; default "
                         "128, 96 in shared-prefix mode)")
    ap.add_argument("--stagger", type=float, default=0.0,
                    help="seconds between client arrivals (--e2e); 0 = "
                         "thundering-herd burst, the worst-case TTFT")
    ap.add_argument("--max-new", type=int, default=None,
                    help="tokens per client request (--e2e). Default 480: "
                         "~500 keeps the decode phase dominant over the "
                         "admission ramp, so the aggregate number measures "
                         "serving throughput rather than mostly ramp "
                         "(round-3 verdict #1); 480 exactly fills the 640 "
                         "capacity with the 128 bucket + 2 lookahead blocks")
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="prefill bucket / prompt size (default 128; 384 "
                         "in shared-prefix mode so the shared preamble "
                         "spans a full 256-token alignment boundary)")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="KV capacity per slot. Default 640 = 128-token "
                         "bucket + 480 new tokens + 2 lookahead blocks "
                         "(the scheduler's capacity guard) AND "
                         "128-aligned: a non-multiple-of-128 capacity "
                         "costs ~2 ms/step in the XLA attention path "
                         "(672 vs 640 measured); 704 additionally tripped "
                         "a marginal HBM RESOURCE_EXHAUSTED under a "
                         "simultaneous 128-burst")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis mesh size (tensor parallelism)")
    ap.add_argument("--block", type=int, default=None,
                    help="decode steps per device dispatch (default: 16 "
                         "for serving — measured same throughput as 64 "
                         "with 2x lower TTFT/inter-chunk latency — and "
                         "64 for --engine/--smoke)")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    metavar="N",
                    help="decode blocks kept in flight on the device "
                         "(tpu.pipeline_depth). 1 = the pre-pipeline "
                         "double buffer, the A/B baseline; 2 (the config "
                         "default) overlaps host emit/admission under "
                         "device compute. --engine mode pipelines its "
                         "dispatch loop to the same depth and reports "
                         "dispatch_thread_block_s; unset keeps each "
                         "mode's default (1 for --engine/--smoke, config "
                         "default for --e2e)")
    ap.add_argument("--quant", default="int8", choices=("none", "int8"),
                    help="weight quantization")
    ap.add_argument("--kv-quant", default="int8", choices=("none", "int8"),
                    help="KV cache quantization")
    ap.add_argument("--fused-dequant", action="store_true",
                    help="route int8 weight matmuls through the W8A16 "
                         "fused-dequant Pallas kernel (tpu.fused_dequant): "
                         "weights pre-packed to the kernel tile layout, "
                         "dequantized in VMEM inside the double-buffered "
                         "DMA/matmul pipeline. The convert-wall A/B is "
                         "this flag on vs off at otherwise identical "
                         "settings (BASELINE.md decode-floor section)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="requests allowed to queue beyond the decode "
                         "slots before the provider sheds with a busy "
                         "error (--e2e; default: one full extra wave = "
                         "slots). Small values + --stagger model the "
                         "bounded-latency overload row")
    ap.add_argument("--max-ttft", type=float, default=None,
                    help="TTFT-bounded admission (--e2e): shed when the "
                         "provider's estimated first-token wait exceeds "
                         "this many seconds (tpu.max_ttft_s). Default: "
                         "disabled")
    ap.add_argument("--client-procs", type=int, default=None,
                    help="shard the client fleet over N OS processes so "
                         "wire tails measure the service, not one client "
                         "event loop (default: 8 when clients >= 64, "
                         "else 1)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a merged Perfetto/Chrome-trace JSON "
                         "(client + provider + host + scheduler spans on "
                         "one reconciled clock) captured from the "
                         "provider at the end of the run (--e2e). Load "
                         "at ui.perfetto.dev; BASELINE.md bench rounds "
                         "attach this artifact")
    ap.add_argument("--no-trace", action="store_true",
                    help="disable the engine-side span rings "
                         "(tpu.tracing=false). The tracing-overhead A/B "
                         "is this flag on vs off at otherwise identical "
                         "settings; acceptance: within 1%% tok/s")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the provider's final metrics-registry "
                         "snapshot (tier-labeled JSON, utils/metrics.py "
                         "shape) beside the run; the same snapshot is "
                         "inlined under the result's `metrics` block "
                         "either way, so BENCH_r*.json artifacts are "
                         "self-describing (--e2e)")
    ap.add_argument("--e2e-client-worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one fleet shard
    args = ap.parse_args()
    if args.e2e_client_worker:
        return run_e2e_client_worker()
    # Per-mode defaults: the shared-prefix workload needs a bucket that
    # spans an alignment boundary plus slack for the cache budget, so its
    # defaults trade a few slots for the bigger bucket; everything else
    # keeps the BENCH_r05-comparable point.
    if args.speculative and args.shared_prefix:
        ap.error("--speculative and --shared-prefix are separate "
                 "two-wave workloads; pick one")
    if args.multi_turn < 1:
        ap.error("--multi-turn must be >= 1")
    if args.multi_turn > 1 and (args.shared_prefix or args.speculative):
        ap.error("--multi-turn is its own workload; drop "
                 "--shared-prefix/--speculative")
    if args.disagg_transport and not args.disagg:
        ap.error("--disagg-transport selects the handoff link for the "
                 "disagg pair; it needs --disagg")
    pool_mn = None
    if args.disagg_pool:
        try:
            m, n = args.disagg_pool.lower().split("x")
            pool_mn = (int(m), int(n))
        except ValueError:
            pool_mn = None
        if pool_mn is None or pool_mn[0] < 1 or pool_mn[1] < 1:
            ap.error("--disagg-pool wants MxN with M,N >= 1 (e.g. 2x2)")
        args.disagg = True  # the pool IS a disagg topology
    if args.chaos:
        # Chaos-mode defaults: a recovery drill, not a throughput run —
        # small fleet, short streams, the default seam's nth tuned to
        # land mid-first-wave at exactly this shape.
        args.clients = args.clients if args.clients is not None else 8
        args.slots = args.slots if args.slots is not None else 4
        args.max_new = args.max_new if args.max_new is not None else 64
        args.prompt_len = (args.prompt_len if args.prompt_len is not None
                           else 128)
        args.max_seq = (args.max_seq if args.max_seq is not None
                        else 384)
    if args.autoscale:
        # Autoscale-mode defaults: topology economics, not throughput —
        # a fleet the 1x1 trough shape serves comfortably but whose
        # diurnal peak overloads it, so the static controls must
        # overprovision to meet the SLOs.
        args.arrival = args.arrival or "diurnal"
        args.clients = args.clients if args.clients is not None else 24
        args.slots = args.slots if args.slots is not None else 4
        args.max_new = args.max_new if args.max_new is not None else 48
        args.prompt_len = (args.prompt_len if args.prompt_len is not None
                           else 128)
        args.max_seq = (args.max_seq if args.max_seq is not None
                        else 384)
        for s in args.autoscale_static.split(","):
            parts = s.lower().split("x")
            if (len(parts) != 2 or not all(p.isdigit() for p in parts)
                    or int(parts[0]) < 1 or int(parts[1]) < 1):
                ap.error(f"--autoscale-static wants MxN[,MxN...] with "
                         f"M,N >= 1, got {s!r}")
    if args.clients is None:
        args.clients = (32 if args.multi_turn > 1
                        else 96 if (args.shared_prefix or args.speculative)
                        else 128)
    if args.slots is None:
        args.slots = (32 if args.multi_turn > 1
                      else 96 if (args.shared_prefix or args.speculative)
                      else 128)
    if args.prompt_len is None:
        # Multi-turn: the LAST turn's full history must fit the bucket,
        # and turn-2+ hits need each turn to cross a 256-token alignment
        # boundary — 2048 leaves ~512 tokens of budget per turn at the
        # default 4 turns.
        args.prompt_len = (2048 if args.multi_turn > 1
                           else 384 if args.shared_prefix else 128)
    if ((args.shared_prefix or args.multi_turn > 1)
            and args.prefix_cache_mb is None):
        args.prefix_cache_mb = 128.0
    if args.multi_turn > 1:
        # Per-turn TTFT stamps come from the inline fleet; the sharded
        # worker protocol only carries whole-request results.
        args.client_procs = 1
    if args.client_procs is None:
        args.client_procs = 8 if args.clients >= 64 else 1
    if args.block is None:
        args.block = 64 if (args.engine or args.smoke) else 16
    if args.max_new is None:
        # Speculative mode trims the per-request budget like shared-prefix:
        # two waves on one provider must fit the same wall budget.
        # Multi-turn trims further: every turn's reply re-enters the
        # next turn's prompt, so the reply budget trades against turns.
        args.max_new = (96 if args.multi_turn > 1
                        else 192 if (args.shared_prefix or args.speculative)
                        else 480)
    if args.max_seq is None:
        if args.multi_turn > 1:
            # Bucket + one reply + lookahead, rounded up to 128 (the
            # measured XLA-attention alignment sweet spot).
            need = args.prompt_len + args.max_new + 2 * args.block
            args.max_seq = -(-need // 128) * 128
        else:
            args.max_seq = 640

    # Capture identity (stamp_result): the RESOLVED knobs that shape the
    # measurement — benchdiff refuses to diff two captures whose
    # fingerprints disagree. Per MODE on purpose: a knob the measured
    # path ignores must not enter the stamp, or two identical
    # measurements launched with different inert flags false-refuse
    # (the exact garbage-delta class the guard exists to stop).
    mode = ("smoke" if args.smoke else "chaos" if args.chaos
            else "autoscale" if args.autoscale
            else "engine" if args.engine else "proxy" if args.proxy
            else "e2e")

    def engine_fp(preset: str, slots: int, steps: int, prompt_len: int,
                  max_seq: int, dtype: str, block: int, mesh_model: int,
                  quant, kv_quant, fused_dequant: bool,
                  pipeline_depth: int = 1) -> dict:
        return {"preset": preset, "slots": slots, "steps": steps,
                "prompt_len": prompt_len, "max_seq": max_seq,
                "dtype": dtype, "block": block, "mesh_model": mesh_model,
                "quant": quant, "kv_quant": kv_quant,
                "fused_dequant": fused_dequant,
                "pipeline_depth": pipeline_depth}

    if mode == "smoke":
        fp_cfg = engine_fp("tiny", 2, 8, 16, 64, "float32", 2, 1,
                           None, None, False,
                           pipeline_depth=args.pipeline_depth or 1)
    elif mode == "chaos":
        fp_cfg = {"preset": args.preset, "clients": args.clients,
                  "slots": args.slots, "max_new": args.max_new,
                  "prompt_len": args.prompt_len, "max_seq": args.max_seq,
                  "dtype": args.dtype, "block": args.block,
                  "chaos_seam": args.chaos_seam}
    elif mode == "autoscale":
        fp_cfg = {"preset": args.preset, "clients": args.clients,
                  "slots": args.slots, "max_new": args.max_new,
                  "prompt_len": args.prompt_len, "max_seq": args.max_seq,
                  "dtype": args.dtype, "block": args.block,
                  "arrival": args.arrival,
                  "arrival_duration": args.arrival_duration,
                  "arrival_seed": args.arrival_seed,
                  "slo_ttft": args.slo_ttft,
                  "slo_chunk": args.slo_chunk,
                  "slo_objective": args.slo_objective,
                  "static_shapes": args.autoscale_static,
                  "max_members": args.autoscale_max_members}
    elif mode == "engine":
        fp_cfg = engine_fp(args.preset, args.slots, args.steps,
                           args.prompt_len, args.max_seq, args.dtype,
                           args.block, args.mesh_model, args.quant,
                           args.kv_quant, args.fused_dequant,
                           pipeline_depth=args.pipeline_depth or 1)
    elif mode == "proxy":
        fp_cfg = {"clients": args.clients, "max_new": args.max_new,
                  "proxy_delay": args.proxy_delay}
    else:
        fp_cfg = {
            "preset": args.preset, "slots": args.slots,
            "clients": args.clients, "max_new": args.max_new,
            "prompt_len": args.prompt_len, "max_seq": args.max_seq,
            "dtype": args.dtype, "block": args.block,
            "quant": args.quant, "kv_quant": args.kv_quant,
            "fused_dequant": args.fused_dequant,
            "pipeline_depth": args.pipeline_depth,
            "shared_prefix": args.shared_prefix,
            "prefix_cache_mb": args.prefix_cache_mb,
            "speculative": args.speculative,
            "draft_k": args.draft_k if args.speculative else None,
            "disagg": args.disagg,
            "disagg_transport": args.disagg_transport,
            "disagg_pool": args.disagg_pool,
            "multi_turn": args.multi_turn, "stagger": args.stagger,
            **({"arrival": args.arrival,
                "arrival_duration": args.arrival_duration,
                "arrival_seed": args.arrival_seed}
               if args.arrival else {}),
            "max_queue": args.max_queue, "max_ttft": args.max_ttft,
            "client_procs": args.client_procs,
            "tracing": not args.no_trace,
        }
    if args.smoke:
        # Smoke mode must not touch a TPU: pin the CPU backend by name
        # before any jax usage, whatever the environment says.
        import jax

        jax.config.update("jax_platforms", "cpu")
        result = run_bench("tiny", slots=2, steps=8, prompt_len=16,
                           max_seq=64, dtype_name="float32", mesh_model=1,
                           block=2,
                           pipeline_depth=args.pipeline_depth or 1)
    elif args.chaos:
        result = run_chaos(
            args.preset, clients=args.clients, slots=args.slots,
            max_new=args.max_new,
            prompt_chars=max(1, args.prompt_len - 24),
            max_seq=args.max_seq, dtype_name=args.dtype,
            block=args.block, bucket=args.prompt_len,
            seam=args.chaos_seam)
    elif args.autoscale:
        result = run_autoscale(
            args.preset, clients=args.clients, slots=args.slots,
            max_new=args.max_new,
            prompt_chars=max(1, args.prompt_len - 24),
            max_seq=args.max_seq, dtype_name=args.dtype,
            block=args.block, bucket=args.prompt_len,
            arrival=args.arrival, duration_s=args.arrival_duration,
            seed=args.arrival_seed, slo_ttft_s=args.slo_ttft,
            slo_chunk_s=args.slo_chunk, objective=args.slo_objective,
            static_shapes=tuple(args.autoscale_static.split(",")),
            max_members=args.autoscale_max_members)
    elif args.engine:
        import jax

        if jax.default_backend() != "tpu":
            sys.exit(f"bench.py --engine measures a TPU; JAX gave this "
                     f"process {jax.default_backend()!r} (--smoke is the "
                     f"CPU run)")
        result = run_bench(
            args.preset, slots=args.slots, steps=args.steps,
            prompt_len=args.prompt_len, max_seq=args.max_seq,
            dtype_name=args.dtype, mesh_model=args.mesh_model,
            block=args.block,
            quant=None if args.quant == "none" else args.quant,
            kv_quant=args.kv_quant == "int8",
            fused_dequant=args.fused_dequant,
            pipeline_depth=args.pipeline_depth or 1)
    elif args.proxy:
        result = run_proxy(clients=args.clients, max_new=args.max_new,
                           token_delay_s=args.proxy_delay)
    else:
        # Default = the north-star serving measurement (round-2 verdict
        # item 1: wire tok/s + TTFT percentiles). ONE attempt at the
        # point that was asked for: a run that fails is a failed run — an
        # exception here is a non-zero exit, never a different
        # measurement under the same name.
        if os.environ.get("JAX_PLATFORMS") == "cpu" and not args.disagg:
            # The engine host obeys a CPU pinned by name, so the verdict
            # run_e2e would reach (host device is not a TPU) is known
            # before a full-width model is built there.
            sys.exit("bench.py (e2e) measures a TPU; JAX_PLATFORMS=cpu "
                     "pins the engine host to the CPU (--smoke is the CPU "
                     "run; the --disagg* modes are CPU-only)")
        result = run_e2e(
            args.preset, clients=args.clients, slots=args.slots,
            max_new=args.max_new,
            # ~24 tokens of headroom for the chat template + BOS so the
            # rendered prompt still fits the --prompt-len bucket
            prompt_chars=max(1, args.prompt_len - 24),
            max_seq=args.max_seq, dtype_name=args.dtype,
            block=args.block,
            quant=None if args.quant == "none" else args.quant,
            kv_quant=args.kv_quant == "int8", bucket=args.prompt_len,
            stagger_s=args.stagger, max_queue=args.max_queue,
            max_ttft_s=args.max_ttft, client_procs=args.client_procs,
            shared_prefix=args.shared_prefix,
            prefix_cache_mb=args.prefix_cache_mb,
            speculative=args.speculative, draft_k=args.draft_k,
            fused_dequant=args.fused_dequant,
            trace_out=args.trace_out, tracing=not args.no_trace,
            disagg=args.disagg,
            disagg_transport=args.disagg_transport,
            disagg_pool=pool_mn,
            multi_turn=args.multi_turn,
            metrics_out=args.metrics_out,
            pipeline_depth=args.pipeline_depth,
            arrival=args.arrival,
            arrival_duration_s=args.arrival_duration,
            arrival_seed=args.arrival_seed)
    stamp_result(result, fp_cfg, mode)
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
