"""One shard of the benchmark's client fleet, in a process of its own.

A shard is an OS process of its own, not a task in the parent (sharding the
fleet over OS processes keeps the client event loop out of the measured
tails), rebuilt for schedules: closed-loop clients that cycle through a
request list, and an open-loop schedule multiplexed over a pool of sessions.

Protocol with the parent (`lib/harness.py`), one JSON or word per line:

    stdin : {"server_address", "server_key_hex", "model_name", "temperature",
             "template_tokens", "shard",
             "closed": [[[prompt_tokens, max_new, seed, warm], ...], ...]   or
             "open": {"sessions": n,
                      "arrivals": [[due_s, prompt_tokens, max_new, seed,
                                    warm], ...]}}
    stdout: READY <sessions>
    stdin : GO <t0> <t_stop>     absolute CLOCK_MONOTONIC seconds: traffic
                                 starts at t0; a closed client starts no
                                 request at or after t_stop
    stdout: RESULTS <json list of records>   (see lib/window.py)

Every stamp is time.monotonic(): CLOCK_MONOTONIC, one clock across the
processes of a machine.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))           # benchmarks/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # the checkout

from lib.traffic import Request, prompt_text  # noqa: E402


async def one_request(session, req: Request, due: float,
                      template_tokens: int, temperature: float) -> dict:
    rec = {"due": due, "t_send": time.monotonic(), "stamps": [],
           "t_done": None, "tokens": None, "finish": None, "error": None,
           "prompt_tokens": req.prompt_tokens, "max_new": req.max_new,
           "warm": req.warm}
    try:
        async for delta in session.chat(
                [{"role": "user",
                  "content": prompt_text(req, template_tokens)}],
                max_tokens=req.max_new, temperature=temperature,
                seed=req.seed):
            rec["stamps"].append((time.monotonic(), len(delta)))
        usage = session.last_usage if isinstance(session.last_usage,
                                                 dict) else {}
        rec["tokens"] = int(usage.get("tokens", 0))
        rec["finish"] = (usage.get("costs") or {}).get("finish")
    except Exception as exc:  # noqa: BLE001 — a failed request is a result
        rec["error"] = f"{type(exc).__name__}: {exc}"
    rec["t_done"] = time.monotonic()
    return rec


async def closed_client(session, requests: list[Request], t0: float,
                        t_stop: float, spec: dict) -> list[dict]:
    await asyncio.sleep(max(0.0, t0 - time.monotonic()))
    out, k = [], 0
    while time.monotonic() < t_stop:
        # requests[0] is the cut opener; the cycle proper follows it
        req = requests[0] if k == 0 else requests[
            1 + (k - 1) % (len(requests) - 1)]
        rec = await one_request(session, req, time.monotonic(),
                                spec["template_tokens"], spec["temperature"])
        out.append(rec)
        k += 1
        if rec["error"]:
            await asyncio.sleep(0.05)  # never spin on a failing provider
    return out


async def open_schedule(sessions: list, arrivals: list[Request], t0: float,
                        spec: dict) -> list[dict]:
    async def fire(i: int, req: Request) -> dict:
        due = t0 + req.due_s
        await asyncio.sleep(max(0.0, due - time.monotonic()))
        return await one_request(sessions[i % len(sessions)], req, due,
                                 spec["template_tokens"],
                                 spec["temperature"])

    return list(await asyncio.gather(
        *(fire(i, r) for i, r in enumerate(arrivals))))


async def main() -> int:
    from symmetry_tpu.client.client import SymmetryClient
    from symmetry_tpu.identity import Identity
    from symmetry_tpu.transport.tcp import TcpTransport

    loop = asyncio.get_running_loop()
    spec = json.loads(await loop.run_in_executor(None, sys.stdin.readline))
    server_key = bytes.fromhex(spec["server_key_hex"])
    closed = [[Request(p, n, s, warm=w) for p, n, s, w in client]
              for client in spec.get("closed") or []]
    arrivals = [Request(p, n, s, due_s=d, warm=w)
                for d, p, n, s, w in (spec.get("open") or {}).get(
                    "arrivals", [])]
    n_sessions = len(closed) or int(spec["open"]["sessions"])

    async def connect(i: int):
        client = SymmetryClient(
            Identity.from_name(f"bench-cli-{spec['shard']}-{i}"),
            TcpTransport())
        details = await client.request_provider(
            spec["server_address"], server_key, spec["model_name"])
        return await client.connect(details)

    sessions = list(await asyncio.gather(
        *(connect(i) for i in range(n_sessions))))
    try:
        print(f"READY {len(sessions)}", flush=True)
        line = await loop.run_in_executor(None, sys.stdin.readline)
        word, t0, t_stop = line.split()
        if word != "GO":
            raise RuntimeError(f"expected GO, got {line!r}")
        t0, t_stop = float(t0), float(t_stop)
        if closed:
            per_client = await asyncio.gather(*(
                closed_client(s, reqs, t0, t_stop, spec)
                for s, reqs in zip(sessions, closed)))
            records = [r for c in per_client for r in c]
        else:
            records = await open_schedule(sessions, arrivals, t0, spec)
    finally:
        for s in sessions:
            await s.close()
    print("RESULTS " + json.dumps(records), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
