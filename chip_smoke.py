"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call:

    in-process SymmetryServer on TCP loopback
      → `python -m symmetry_tpu.provider -c <yaml>` (apiProvider tpu_native,
        engine_isolation process: the engine is the symmetry_tpu.engine.host
        child, the one process that takes the chip)
      → SymmetryClient streams

at the full width of mistral-7b (v0.3: 4096 / 32 L / 32 Q / 8 KV / 14336 /
vocab 32768), bf16 with int8 weights and int8 KV, random seeded weights, byte
tokenizer; 8 slots × 4096 capacity, prefill bucket 128, decode block 16 — a
shape that puts the flash-prefill and ragged decode-attention Pallas kernels
inside the served programs. Traffic: 8 concurrent streaming chats of 64 new
tokens, then one lone greedy request sent twice.

It exits 0 only when every phase passed on a TPU, and then prints two JSON
lines: the run's report (device, shape, start-up seconds, compile-cache
entries, HBM, attention paths, the sampler's top-k route, burst TTFT and
tok/s — reported, not judged) and, as the LAST line of stdout, the verdict
with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It exits non-zero, printing no result but the reasons and the provider/host
log tail on stderr, when: the engine host reports a
platform other than tpu; a stream ends without tokens or with an error; the
wire's token count differs from the host's; the two greedy requests differ;
the supervisor respawned the host; the provider or host exits non-zero on
drain; a child is left alive; a kernel ran interpreted or attention left the
Pallas path; or the first run added no compile-cache entries. No phase is
wrapped in an `except` that lets the run end in 0.

This process never imports JAX (asserted at exit): a parent that touches JAX
contends for the chip its child needs.

    python chip_smoke.py                       # the chip run
    python chip_smoke.py --mesh-model 4        # one host, four chips, TP
    python chip_smoke.py --preset mixtral-8x7b --mesh-model 4 --parity
        # the 47 B expert model at full widths over four chips (the report
        # carries `moe`: layout, route, the quantised-leaf route), then —
        # once the provider has drained and the chips are free — the
        # depth-2 full-width logits parity of tools/moe_parity.py
    python chip_smoke.py --preset granite-4.0-h-small
    python chip_smoke.py --preset qwen3-next-80b-a3b
        # the one-chip hybrid models (a per-slot recurrent state beside an
        # attention layer; the report carries `ssm`: the kind, its state
        # and each program's form — the decode step of either kind must
        # read `pallas`: ops/ssm_step.py — and `moe`, whose
        # `grouped_matmul` must read `pallas` on one chip, its `operand`
        # the layers' stack and not a stack of one: ops/gmm.py)
    python chip_smoke.py --preset keye-vl-2.0-30b-a3b
        # learned sparse attention on one chip (a lightning indexer with a
        # key cache of its own; the report's `attention.sparse` carries
        # topk, the form of each program — the smoke fails without one —
        # and the index cache's bytes), 128 experts top 8 (`moe`)
    python chip_smoke.py --preset lfm2-8b-a1b
        # LFM2-8B-A1B whole on one chip (18 gated short convolutions whose
        # state is a two-position tail, six GQA layers of 64-wide heads,
        # two dense layers, 22 x 32 experts top 4 by sigmoid scores + a
        # selection bias; `ssm.kind` short_conv, `moe.router.score`
        # sigmoid, `moe.grouped_matmul` pallas): its heads of 64 are no
        # lane tile, so decode attention is held to `xla` WITH its reason
        # and prefill to the flash kernel
    JAX_PLATFORMS=cpu python chip_smoke.py --preset tiny
    JAX_PLATFORMS=cpu python chip_smoke.py --preset tiny-bd
    python chip_smoke.py --preset kanana-2-30b-a3b
        # latent attention on one chip (one cached row of 576 values in 640
        # lanes, no int8 form: `kv_quantization` null); the report's
        # `attention.kind` reads `latent` and it carries `cache`
    python chip_smoke.py --preset smallthinker-21b-a3b
        # window (RoPE, 4,096) and full (NoPE) attention layers in one
        # cache: the report's `attention.kind` reads `window+full` with a
        # route a kind — the decode of BOTH must read `pallas`, a window
        # layer's over its ring of 4,096 rows — and it carries `cache`
        # (`cache_bytes` against `uniform_cache_bytes`)
    JAX_PLATFORMS=cpu python chip_smoke.py --preset tiny-mla
    JAX_PLATFORMS=cpu python chip_smoke.py --preset tiny-swa
        # CPU dry run of every phase; ends non-zero: "platform is cpu"
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SLOTS, MAX_SEQ, BUCKET, BLOCK, MAX_NEW = 8, 4096, 128, 16, 64
START_TIMEOUT_S = 900.0   # build + cold compile of every served program
SERVE_TIMEOUT_S = 150.0   # all ten requests and the stats read
DRAIN_TIMEOUT_S = 120.0


# presets whose attention runs under a learned selection (by name: this
# process may not import jax to ask)
SPARSE_PRESETS = ("keye-vl-2.0-30b-a3b", "tiny-dsa")
# presets that generate by diffusion over blocks: a decode forward carries a
# block of queries a slot, which the decode kernel takes as its q tile where
# the cache has a geometry (`decode_queries`; tiny-bd's head of 16 has none
# and says why), and the report carries `diffusion`
DIFFUSION_PRESETS = ("sdar-30b-a3b-chat", "tiny-bd")
# presets with latent attention: a cached position is one row (no int8 form:
# `kv_quantization` null), the report carries `cache` and the decode step
# runs absorbed through ops/mla_attention.py
LATENT_PRESETS = ("kanana-2-30b-a3b", "tiny-mla")
# presets with window AND full attention layers: a route a kind under
# `attention.full` / `attention.window`, and `cache` with both leaves' bytes
WINDOW_PRESETS = ("smallthinker-21b-a3b", "tiny-swa")


class SmokeFailure(Exception):
    pass


def provider_config(preset: str, mesh_model: int) -> dict:
    """The provider.yaml of the smoke, minus the server's key and address
    (serve_and_check fills those in once its server is up)."""
    return {
        "name": "chip-smoke-provider", "public": True,
        "modelName": f"{preset}:smoke", "apiProvider": "tpu_native",
        "dataCollectionEnabled": False, "maxConnections": SLOTS + 8,
        "listenHost": "127.0.0.1",
        "privateSeed": hashlib.blake2b(b"chip-smoke-provider",
                                       digest_size=32).hexdigest(),
        "tpu": {
            "model_preset": preset, "dtype": "bfloat16",
            "quantization": "int8",
            "kv_quantization": (None if preset in LATENT_PRESETS
                                else "int8"),
            "max_batch_size": SLOTS, "max_seq_len": MAX_SEQ,
            "prefill_buckets": [BUCKET], "decode_block": BLOCK,
            **({"mesh": {"model": mesh_model}} if mesh_model > 1 else {}),
            # No prompt of the smoke is chunked (one bucket of 128 under
            # the default chunk of 256), so say so: chunked prefill is
            # REFUSED for a model with recurrent layers (`--preset
            # granite-4.0-h-small`; models/residents.py refusals), and
            # this process may not import jax to ask which preset is one.
            "prefill_chunk": None,
        },
    }


def cache_entries(directory: str) -> int:
    if not os.path.isdir(directory):
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(directory))


def descendants(pid: int) -> list[int]:
    """Live pids below `pid` (the engine host and anything it started)."""
    children: dict[int, list[int]] = {}
    for entry in os.listdir("/proc"):
        if entry.isdigit():
            try:
                with open(f"/proc/{entry}/stat") as fh:
                    ppid = int(fh.read().rsplit(")", 1)[1].split()[1])
            except (OSError, ValueError, IndexError):
                continue  # the process exited while we looked
            children.setdefault(ppid, []).append(int(entry))
    out, stack = [], [pid]
    while stack:
        for child in children.get(stack.pop(), []):
            out.append(child)
            stack.append(child)
    return out


def loaded_libtpu(pid: int) -> bool:
    with open(f"/proc/{pid}/maps") as fh:
        return "libtpu" in fh.read()


async def chat(client_name: str, server, server_ident, model: str,
               content: str, **sampling) -> dict:
    """One streaming chat on its own connection; raises on an error frame
    or an empty stream."""
    from symmetry_tpu.client.client import SymmetryClient
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.transport.tcp import TcpTransport

    client = SymmetryClient(Identity.from_name(client_name), TcpTransport())
    details = await client.request_provider(
        server.address, server_ident.public_key, model)
    session = await client.connect(details)
    try:
        t0 = time.monotonic()
        t_first = None
        parts = []
        async for delta in session.chat(
                [{"role": "user", "content": content}],
                max_tokens=MAX_NEW, **sampling):
            if t_first is None:
                t_first = time.monotonic()
            parts.append(delta)
        tokens = int((session.last_usage or {}).get("tokens", 0))
        if t_first is None or tokens < 1:
            raise SmokeFailure(f"{client_name}: the stream ended without "
                               f"tokens (usage {session.last_usage})")
        return {"text": "".join(parts), "tokens": tokens,
                "ttft_s": t_first - t0, "e2e_s": time.monotonic() - t0}
    finally:
        await session.close()


async def provider_stats(server, server_ident, model: str) -> dict:
    from symmetry_tpu.client.client import SymmetryClient
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.transport.tcp import TcpTransport

    client = SymmetryClient(Identity.from_name("chip-smoke-stats"),
                            TcpTransport())
    details = await client.request_provider(
        server.address, server_ident.public_key, model)
    session = await client.connect(details)
    try:
        return await session.stats()
    finally:
        await session.close()


async def traffic(server, server_ident, model: str):
    # Phase 1: 8 concurrent streaming chats, sampled, seeded.
    t0 = time.monotonic()
    burst = await asyncio.gather(*[
        chat(f"chip-smoke-client-{i}", server, server_ident, model,
             f"client {i}: describe the road from the harbour to the hill "
             f"fort in a few plain sentences.", temperature=0.7, seed=i)
        for i in range(SLOTS)])
    burst_wall = time.monotonic() - t0
    # Phase 2: one lone greedy request, twice — same text both times.
    lone = [await chat(f"chip-smoke-greedy-{n}", server, server_ident,
                       model, "name three rivers and the seas they reach.",
                       temperature=0.0)
            for n in range(2)]
    return burst, burst_wall, lone, await provider_stats(
        server, server_ident, model)


async def serve_and_check(cfg: dict, log_path: str) -> dict:
    """Every phase, in order. Returns the report line's fields; raises
    SmokeFailure (or whatever a phase raised) otherwise."""
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.server.broker import SymmetryServer
    from symmetry_tpu.transport.tcp import TcpTransport
    from symmetry_tpu.utils.compile_cache import cache_dir

    import yaml

    model = cfg["modelName"]
    server_ident = Identity.from_name("chip-smoke-server")
    server = SymmetryServer(server_ident, TcpTransport(),
                            ping_interval_s=60.0)
    await server.start("tcp://127.0.0.1:0")
    cfg = {**cfg, "serverKey": server_ident.public_hex,
           "serverAddress": server.address}
    cache = cache_dir(cfg["tpu"].get("compile_cache", True))
    entries_before = cache_entries(cache)

    with tempfile.NamedTemporaryFile("w", suffix=".yaml",
                                     delete=False) as fh:
        yaml.safe_dump(cfg, fh)
        cfg_path = fh.name
    log_fh = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "symmetry_tpu.provider", "-c", cfg_path],
        cwd=REPO, stdout=log_fh, stderr=subprocess.STDOUT)
    family: list[int] = []   # the engine host and anything below it
    orphans: list[int] = []  # of those, the ones that outlived the provider
    try:
        t_start = time.monotonic()
        while server.registry.select_provider(model) is None:
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"the provider exited with code {proc.returncode} "
                    f"before it registered")
            if time.monotonic() - t_start > START_TIMEOUT_S:
                raise SmokeFailure(
                    f"the provider did not register within "
                    f"{START_TIMEOUT_S:.0f}s")
            await asyncio.sleep(0.5)
        startup_s = time.monotonic() - t_start
        family = descendants(proc.pid)

        burst, burst_wall, lone, stats = await asyncio.wait_for(
            traffic(server, server_ident, model), SERVE_TIMEOUT_S)
        provider_has_libtpu = loaded_libtpu(proc.pid)
    finally:
        # Drain: SIGTERM is the provider CLI's graceful stop; it shuts
        # the host down and exits 0 only if the host did.
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=DRAIN_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        for pid in family:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                continue
            orphans.append(pid)
        log_fh.close()
        os.unlink(cfg_path)
        await server.stop()

    engine = stats.get("engine") or {}
    startup = engine.get("startup") or {}
    device = startup.get("device") or {}
    attention = startup.get("attention") or {}
    supervisor = engine.get("supervisor") or {}
    burst_tokens = sum(r["tokens"] for r in burst)
    wire_tokens = burst_tokens + sum(r["tokens"] for r in lone)
    entries_after = cache_entries(cache)

    failures = []
    if lone[0]["text"] != lone[1]["text"]:
        failures.append(f"two identical greedy requests differ: "
                        f"{lone[0]['text']!r} vs {lone[1]['text']!r}")
    if engine.get("tokens") != wire_tokens:
        failures.append(f"the wire carried {wire_tokens} tokens, the host "
                        f"counted {engine.get('tokens')}")
    if supervisor.get("restarts") != 0 or supervisor.get(
            "respawn_failures") != 0:
        failures.append(f"the supervisor respawned the host: {supervisor}")
    if proc.returncode != 0:
        failures.append(f"the provider exited with code {proc.returncode} "
                        f"on drain (non-zero: it or its host died, or "
                        f"outlived {DRAIN_TIMEOUT_S:.0f}s)")
    if orphans:
        failures.append(f"children outlived the provider and were killed: "
                        f"{orphans}")
    if provider_has_libtpu:
        failures.append("the provider process loaded libtpu: only the "
                        "engine host may take the chip")
    if entries_after == 0:
        failures.append(f"the compile cache at {cache} holds no entries "
                        f"after a full start-up")
    # One chip or a mesh (8 x 4,096 is over the sharded trunk's floor):
    # both programs through compiled kernels (lfm2-8b-a1b's heads of 64
    # too: they lie in the cache in pairs, models/llama.py kv_row).
    diffusion = cfg["tpu"]["model_preset"] in DIFFUSION_PRESETS
    if diffusion:
        bd = startup.get("diffusion") or {}
        block_tile = (attention.get("decode") == "pallas"
                      and attention.get("decode_queries") == bd.get("block"))
        tiny_head = (attention.get("decode") == "xla"
                     and "head of 16" in attention.get("decode_why", ""))
        if attention.get("prefill") != "pallas" or not (block_tile
                                                        or tiny_head):
            failures.append(f"a block-diffusion model's prefill did not run "
                            f"the compiled flash kernel, or its decode "
                            f"forwards did not take the decode kernel with "
                            f"the block as its q tile: {attention}")
        if "opening_block" not in attention:
            failures.append(f"no route reported for the admission's opening "
                            f"block: {attention}")
        if not bd.get("block") or bd.get("programs") != {
                "prefill": "bd_prefill", "decode": "bd_decode_block"}:
            failures.append(f"a block-diffusion model reported no "
                            f"`diffusion` block: {bd}")
    elif (attention.get("prefill"), attention.get("decode")) != (
            "pallas", "pallas"):
        failures.append(f"attention did not run compiled Pallas kernels "
                        f"in both programs: {attention}")
    if cfg["tpu"]["model_preset"] in LATENT_PRESETS and (
            attention.get("kind") != "latent"
            or (startup.get("cache") or {}).get("kind") != "latent"):
        failures.append(f"a model with latent attention reported no latent "
                        f"cache: {attention} {startup.get('cache')}")
    if cfg["tpu"]["model_preset"] in WINDOW_PRESETS and (
            attention.get("kind") != "window+full"
            or {(attention.get(kind) or {}).get("decode")
                for kind in ("full", "window")} != {"pallas"}
            or (startup.get("cache") or {}).get("kind") != "window+full"):
        failures.append(f"a model with window and full attention layers did "
                        f"not decode both kinds through the kernel, or "
                        f"reported no window+full cache: {attention} "
                        f"{startup.get('cache')}")
    sparse = attention.get("sparse") or {}
    if (cfg["tpu"]["model_preset"] in SPARSE_PRESETS
            and not (sparse.get("form") or {}).get("decode")):
        failures.append(f"a model with learned sparse attention reported "
                        f"no sparse form: {attention}")
    # its selection has a kernel (ops/sparse_attention.py dsa_select): the
    # smoke's caches have the decode form's layout
    if sparse and set((sparse.get("select") or {}).values()) != {
            "dsa_select kernel"}:
        failures.append(f"the selection did not run the dsa_select kernel "
                        f"in both programs: {sparse.get('select')}")
    ssm = startup.get("ssm") or {}
    ssm_decode = ssm.get("decode")
    # Mamba-2's step and the Gated DeltaNet's (`--preset qwen3-next-80b-a3b`)
    # each have a kernel (ops/ssm_step.py); a short convolution's tail is
    # two positions, stepped in jnp
    if (ssm_decode is not None and ssm.get("kind", "mamba2") != "short_conv"
            and ssm_decode.get("form") != "pallas"):
        failures.append(f"the recurrent layers' decode step did not run "
                        f"the compiled Pallas kernel: {ssm_decode}")
    failures += grouped_matmul_failures(
        (startup.get("moe") or {}).get("grouped_matmul"),
        meshed="mesh" in cfg["tpu"])
    if device.get("platform") != "tpu":
        failures.append(f"the engine host's platform is "
                        f"{device.get('platform')}, not tpu")
    if failures:
        raise SmokeFailure("; ".join(failures))

    ttfts = sorted(r["ttft_s"] for r in burst)
    return {
        "ok": True,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": device["device_count"]},
        "model": cfg["tpu"]["model_preset"],
        "shape": {k: v for k, v in cfg["tpu"].items()
                  if k != "model_preset"},
        "attention": attention,
        "sampling": startup.get("sampling"),
        **({"moe": startup["moe"]} if startup.get("moe") else {}),
        **({"ssm": startup["ssm"]} if startup.get("ssm") else {}),
        **({"cache": startup["cache"]} if startup.get("cache") else {}),
        "startup_s": round(startup_s, 1),
        "build_s": startup.get("build_s"),
        "warmup_s": startup.get("warmup_s"),
        "compile_cache": {"dir": cache, "entries_before": entries_before,
                          "entries_after": entries_after},
        "hbm": device.get("hbm"),
        "burst": {"clients": SLOTS, "max_new": MAX_NEW,
                  "tokens": burst_tokens,
                  "ttft_p50_s": round(ttfts[len(ttfts) // 2], 3),
                  "ttft_max_s": round(ttfts[-1], 3),
                  "tok_s": round(burst_tokens / burst_wall, 1)},
        "greedy": {"tokens": lone[0]["tokens"],
                   "ttft_s": round(lone[1]["ttft_s"], 3)},
        "host_restarts": supervisor.get("restarts"),
    }


def grouped_matmul_failures(gmm_form: dict | None, meshed: bool) -> list:
    """What `startup.moe.grouped_matmul` may not read on one chip's int8
    expert stacks: the routed form runs over the compiled grouped-matmul
    kernel (ops/gmm.py; under a mesh `lax.ragged_dot`), whose weight
    operand is the layers' stack as it lies (models/llama.py run_layers,
    models/hybrid.py: `moe_mlp(stack=)`)."""
    if gmm_form is None or meshed:
        return []
    if gmm_form.get("form") != "pallas":
        return [f"the routed expert FFN's grouped matmul did not run the "
                f"compiled Pallas kernel: {gmm_form}"]
    if gmm_form.get("operand") == "stack of one":
        return [f"the grouped-matmul kernel is given a layer's slice of "
                f"the expert stacks, which XLA copies out before every "
                f"call: {gmm_form}"]
    return []


def verdict(report: dict) -> dict:
    """The last line of stdout: `ok` and the device as the engine host's JAX
    reported it (platform, device_kind, len(jax.devices())) — these keys and
    no others; everything else the run learned is the report line above it."""
    device = report["device"]
    return {"ok": report["ok"],
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="tpu.mesh {model: N}: tensor parallelism over N "
                         "chips of one host (default: one chip)")
    ap.add_argument("--preset", default="mistral-7b",
                    help="model preset; `tiny` is the CPU dry run")
    ap.add_argument("--parity", action="store_true",
                    help="after the smoke, run tools/moe_parity.py on the "
                         "same mesh (expert models: full widths, depth 2, "
                         "logits against the float32 reference)")
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS") == "cpu" and args.preset not in (
            "tiny", "tiny-bd", "tiny-mla", "tiny-swa"):
        # The engine host obeys a CPU pinned by name (utils/device.py), so
        # the verdict is known before anything starts — and a full-width
        # model is not built on a CPU to reach it.
        print(f"chip_smoke: FAIL: JAX_PLATFORMS=cpu pins the engine host "
              f"to the CPU: its platform is cpu, not tpu ({args.preset} is "
              f"not built there; `--preset tiny`, `tiny-bd`, `tiny-mla` and "
              f"`tiny-swa` are the CPU dry runs)",
              file=sys.stderr)
        return 1

    log_path = os.path.join(tempfile.gettempdir(),
                            f"chip_smoke_provider_{os.getpid()}.log")
    cfg = provider_config(args.preset, args.mesh_model)
    try:
        result = asyncio.run(serve_and_check(cfg, log_path))
    except BaseException as exc:
        print(f"chip_smoke: FAIL: {exc!r}", file=sys.stderr)
        if os.path.exists(log_path):
            with open(log_path, errors="replace") as fh:
                tail = fh.read()[-6000:]
            print(f"--- provider + engine host log tail ({log_path}) ---\n"
                  f"{tail}", file=sys.stderr)
        raise
    finally:
        assert "jax" not in sys.modules, "chip_smoke imported jax"
    with open(log_path, errors="replace") as fh:
        for line in fh:
            if "engine host ready" in line:  # the host's own account
                print(line.rstrip(), file=sys.stderr)
    os.unlink(log_path)
    if args.parity:
        # Its own process, now that the provider's host has let go of the
        # chips; its JSON line joins the report, its exit code the verdict.
        parity = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "moe_parity.py"),
             "--preset", args.preset, "--mesh-model", str(args.mesh_model)],
            cwd=REPO, capture_output=True, text=True)
        lines = parity.stdout.strip().splitlines()
        if parity.returncode != 0 or not lines:
            print(f"chip_smoke: FAIL: the parity check exited with code "
                  f"{parity.returncode}: {lines[-1:] or ''}\n"
                  f"{parity.stderr[-3000:]}", file=sys.stderr)
            return 1
        result["parity"] = json.loads(lines[-1])
    print(json.dumps(result))
    print(json.dumps(verdict(result)), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure:
        sys.exit(1)
