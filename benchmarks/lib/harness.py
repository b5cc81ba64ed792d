"""Start the system under test, drive a cell's traffic at it, collect what
the run left: client records, stats samples, the device trace.

The served path is the one `chip_smoke.py` drives (an
in-process SymmetryServer, `python -m symmetry_tpu.provider` as its own OS
process whose engine host is the only process that touches JAX, clients over
TCP loopback with Noise on); the provider-config builder and the process
lifecycle are copies of theirs, kept here so that a later change to the
program cannot change the yardstick. `symmetry_tpu` is imported only for
what is being measured: server, client, identity, transport.

Nothing here knows a cell by name: a cell is its three files.
"""

from __future__ import annotations

import asyncio
import contextlib
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field

from . import traffic as traffic_lib

LIB = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(LIB)
CHECKOUT = os.path.dirname(BENCH_DIR)

START_TIMEOUT_S = 1100.0   # build + cold compile of every served program
CONNECT_TIMEOUT_S = 120.0
DRAIN_TIMEOUT_S = 90.0
HOST_EXIT_NO_CHIP = 87


class BenchFailure(Exception):
    """The run cannot produce a result; the process exits non-zero and prints
    no result line."""


# ------------------------------------------------------------------ the cell

@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    manifest: dict
    root: str = BENCH_DIR

    @property
    def tpu(self) -> dict:
        return self.config["tpu"]

    @property
    def slot_token_limit(self) -> int:
        """Prompt + output a slot can hold and still finish by `max_new`:
        the scheduler ends a stream whose cache could not absorb two more
        decode blocks (`engine/scheduler.py`: prompt + generated +
        2 × block writes ≤ capacity + 1)."""
        return int(self.tpu["max_seq_len"]) - 2 * int(
            self.tpu.get("decode_block", 16))


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(workload: str, manifest_path: str | None = None) -> Cell:
    """Find a cell's three files by the names its manifest entry gives."""
    manifest_path = manifest_path or os.path.join(CHECKOUT, "BENCHMARK.json")
    manifest = load_json(manifest_path)
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise BenchFailure(
            f"no workload {workload!r} in {manifest_path}; it has "
            f"{[w['name'] for w in manifest['workloads']]}")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    base = os.path.dirname(os.path.abspath(manifest_path))
    config = load_json(os.path.join(base, cfg_entry["file"]))
    root = os.path.dirname(os.path.dirname(
        os.path.join(base, cfg_entry["file"])))
    traffic = load_json(os.path.join(root, "traffic",
                                     entry["traffic"] + ".json"))
    cell = Cell(entry["name"], int(entry["chips"]), entry["config"],
                config, traffic, manifest, root)
    traffic_lib.check_fits(traffic, cell.slot_token_limit)
    return cell


def provider_config(cell: Cell, server_key_hex: str, server_address: str,
                    profile_dir: str) -> dict:
    """The provider.yaml of a run: the configuration file's `tpu:` section
    verbatim, under the fields every provider needs."""
    sessions = int(cell.traffic["clients"])
    return {
        "name": "bench-provider", "public": True,
        "serverKey": server_key_hex, "serverAddress": server_address,
        "modelName": f"{cell.config_name}:bench",
        "apiProvider": "tpu_native", "dataCollectionEnabled": False,
        "maxConnections": sessions + 16, "listenHost": "127.0.0.1",
        "privateSeed": hashlib.blake2b(b"bench-provider",
                                       digest_size=32).hexdigest(),
        "profiler": {"dir": profile_dir},
        "tpu": dict(cell.tpu),
    }


# ------------------------------------------------------------- process utils

def descendants(pid: int) -> list[int]:
    """Live pids below `pid` (copy of chip_smoke.descendants)."""
    children: dict[int, list[int]] = {}
    for entry in os.listdir("/proc"):
        if entry.isdigit():
            try:
                with open(f"/proc/{entry}/stat") as fh:
                    ppid = int(fh.read().rsplit(")", 1)[1].split()[1])
            except (OSError, ValueError, IndexError):
                continue
            children.setdefault(ppid, []).append(int(entry))
    out, stack = [], [pid]
    while stack:
        for child in children.get(stack.pop(), []):
            out.append(child)
            stack.append(child)
    return out


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ------------------------------------------------------------- client fleet

class Fleet:
    """The client worker processes of one traffic phase."""

    def __init__(self, n_procs: int) -> None:
        self.n_procs = n_procs
        self.procs: list[asyncio.subprocess.Process] = []

    async def spawn(self) -> None:
        """Start the workers; they import and then wait for their spec, so
        this can run while the engine host is still building."""
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        for _ in range(self.n_procs):
            self.procs.append(await asyncio.create_subprocess_exec(
                sys.executable, os.path.join(LIB, "client_worker.py"),
                stdin=asyncio.subprocess.PIPE,
                stdout=asyncio.subprocess.PIPE, limit=1 << 28, env=env,
                cwd=CHECKOUT))

    @staticmethod
    async def _read_until(proc, prefix: str) -> str:
        while True:
            raw = await proc.stdout.readline()
            if not raw:
                raise BenchFailure(
                    f"a client worker exited before {prefix.strip()} "
                    f"(code {proc.returncode})")
            line = raw.decode()
            if line.startswith(prefix):
                return line

    async def connect(self, base_spec: dict, shards: list[dict]) -> int:
        for k, (proc, shard) in enumerate(zip(self.procs, shards)):
            proc.stdin.write((json.dumps(
                {**base_spec, "shard": k, **shard}) + "\n").encode())
        await asyncio.gather(*(p.stdin.drain() for p in self.procs))
        ready = await asyncio.gather(*(
            asyncio.wait_for(self._read_until(p, "READY"), CONNECT_TIMEOUT_S)
            for p in self.procs))
        return sum(int(line.split()[1]) for line in ready)

    async def go(self, t0: float, t_stop: float) -> None:
        for proc in self.procs:
            proc.stdin.write(f"GO {t0!r} {t_stop!r}\n".encode())
        await asyncio.gather(*(p.stdin.drain() for p in self.procs))

    async def results(self, timeout_s: float) -> list[dict]:
        payloads = await asyncio.gather(*(
            asyncio.wait_for(self._read_until(p, "RESULTS "), timeout_s)
            for p in self.procs))
        await asyncio.gather(*(p.wait() for p in self.procs))
        records = []
        for payload in payloads:
            records.extend(json.loads(payload[len("RESULTS "):]))
        return records

    async def kill(self) -> None:
        for proc in self.procs:
            if proc.returncode is None:
                with contextlib.suppress(ProcessLookupError):
                    proc.kill()
        await asyncio.gather(*(p.wait() for p in self.procs))


def fleet_size(cell: Cell) -> int:
    """Client processes: as the traffic file says, never more than clients."""
    return max(1, min(int(cell.traffic.get("client_procs", 4)),
                      int(cell.traffic["clients"])))


def shard_traffic(cell: Cell, seed: int, seconds: float,
                  rate: float | None) -> tuple[list[dict], list]:
    """Split a cell's traffic over its client processes. Returns (one spec
    fragment per worker, the flat list of offered requests)."""
    t = cell.traffic
    procs = fleet_size(cell)
    if t["loop"] == "closed":
        clients = traffic_lib.closed_loop(t, seed)
        shards = [{"closed": [
            [[r.prompt_tokens, r.max_new, r.seed, r.warm] for r in c]
            for c in clients[k::procs]]} for k in range(procs)]
        return shards, [r for c in clients for r in c]
    if t["loop"] == "open":
        arrivals = traffic_lib.open_loop(t, seed, seconds, rate)
        sessions = max(1, int(t["clients"]) // procs)
        shards = [{"open": {"sessions": sessions, "arrivals": [
            [r.due_s, r.prompt_tokens, r.max_new, r.seed, r.warm]
            for r in arrivals[k::procs]]}} for k in range(procs)]
        return shards, arrivals
    raise BenchFailure(f"unknown loop kind {t['loop']!r}")


# --------------------------------------------------------- the served system

@dataclass
class Phase:
    """What one traffic phase (warm + window + drain) left behind."""

    w0: float
    w1: float
    records: list[dict]
    samples: list[tuple[float, dict]]   # (monotonic, provider stats)
    stats_start: dict
    stats_end: dict
    offered: list
    trace_path: str | None = None
    trace_error: str | None = None
    timings: dict = field(default_factory=dict)


class Serving:
    """The server, the provider process and a control session, for the life
    of a `with` block."""

    def __init__(self, cell: Cell, t_process_start: float) -> None:
        self.cell = cell
        self.t_start = t_process_start
        self.tmp = tempfile.mkdtemp(prefix="symbench_")
        self.profile_dir = os.path.join(self.tmp, "profiles")
        self.log_path = os.path.join(self.tmp, "provider.log")
        self.proc: subprocess.Popen | None = None
        self.family: list[int] = []
        self.orphans: list[int] = []
        self.timings: dict[str, float] = {}
        self.provider_rc: int | None = None
        self.model = f"{cell.config_name}:bench"
        self.control = None
        self._log_fh = None

    async def __aenter__(self) -> "Serving":
        from symmetry_tpu.identity import Identity
        from symmetry_tpu.server.broker import SymmetryServer
        from symmetry_tpu.transport.tcp import TcpTransport

        import yaml

        self.server_ident = Identity.from_name("bench-server")
        self.server = SymmetryServer(self.server_ident, TcpTransport(),
                                     ping_interval_s=60.0)
        await self.server.start("tcp://127.0.0.1:0")
        cfg = provider_config(self.cell, self.server_ident.public_hex,
                              self.server.address, self.profile_dir)
        cfg_path = os.path.join(self.tmp, "provider.yaml")
        with open(cfg_path, "w") as fh:
            yaml.safe_dump(cfg, fh)
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        self._log_fh = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "symmetry_tpu.provider", "-c", cfg_path],
            cwd=CHECKOUT, stdout=self._log_fh, stderr=subprocess.STDOUT,
            env=env)
        self.timings["spawn_s"] = time.monotonic() - self.t_start
        return self

    async def registered(self) -> None:
        t0 = time.monotonic()
        while self.server.registry.select_provider(self.model) is None:
            rc = self.proc.poll()
            if rc is not None:
                why = (" (the engine host found no chip)"
                       if self._no_chip() else "")
                raise BenchFailure(
                    f"the provider exited with code {rc} before it "
                    f"registered{why}")
            if time.monotonic() - t0 > START_TIMEOUT_S:
                raise BenchFailure(
                    f"the provider did not register in {START_TIMEOUT_S}s")
            await asyncio.sleep(0.25)
        self.timings["register_s"] = time.monotonic() - t0
        self.family = descendants(self.proc.pid)
        self.control = await self._session("bench-control")

    def _no_chip(self) -> bool:
        with open(self.log_path, errors="replace") as fh:
            text = fh.read()
        return ("BackendNoChipError" in text
                or f"code {HOST_EXIT_NO_CHIP}" in text
                or "needs a chip" in text)

    async def _session(self, name: str):
        from symmetry_tpu.client.client import SymmetryClient
        from symmetry_tpu.identity import Identity
        from symmetry_tpu.transport.tcp import TcpTransport

        client = SymmetryClient(Identity.from_name(name), TcpTransport())
        details = await client.request_provider(
            self.server.address, self.server_ident.public_key, self.model)
        return await client.connect(details)

    def base_spec(self) -> dict:
        return {"server_address": self.server.address,
                "server_key_hex": self.server_ident.public_hex,
                "model_name": self.model,
                "temperature": float(self.cell.traffic.get("temperature",
                                                           0.7)),
                "template_tokens": int(self.cell.config["template_tokens"])}

    async def greedy_probe(self) -> tuple[list[str], int]:
        """The same greedy request twice; returns (texts, wire tokens)."""
        texts, tokens = [], 0
        for _ in range(2):
            parts = []
            async for delta in self.control.chat(
                    [{"role": "user", "content":
                      "name three rivers and the seas they reach."}],
                    max_tokens=16, temperature=0.0):
                parts.append(delta)
            texts.append("".join(parts))
            tokens += int((self.control.last_usage or {}).get("tokens", 0))
        return texts, tokens

    async def run_phase(self, fleet: Fleet, seed: int, seconds: float,
                        trace: bool, rate: float | None = None) -> Phase:
        """Connect the fleet, run warm traffic + the window, drain."""
        cell = self.cell
        shards, offered = shard_traffic(cell, seed, seconds, rate)
        t_c = time.monotonic()
        await fleet.connect(self.base_spec(), shards)
        timings = {"connect_s": time.monotonic() - t_c}
        warm_s = float(cell.traffic["warm_s"])
        if trace:
            # The process's first capture pays the profiler's cold start;
            # pay it here, before traffic, so the real capture is prompt.
            t_p = time.monotonic()
            await self.control.capture_profile(0.0)
            timings["profiler_init_s"] = time.monotonic() - t_p
        t0 = time.monotonic() + 0.25
        w0, w1 = t0 + warm_s, t0 + warm_s + seconds
        await fleet.go(t0, w1)

        samples: list[tuple[float, dict]] = []
        trace_out: dict = {}

        async def capture() -> None:
            at = w0 + float(cell.traffic.get("trace_at", 0.25)) * seconds
            await asyncio.sleep(max(0.0, at - time.monotonic()))
            trace_out.update(await self.control.capture_profile(
                min(float(cell.traffic.get("trace_s", 3.0)),
                    0.5 * seconds)))

        capture_task = asyncio.create_task(capture()) if trace else None
        period = float(cell.traffic.get("stats_period_s", 1.0))
        n_ticks = max(1, int(round(seconds / period)))
        for i in range(n_ticks + 1):
            at = w0 + i * seconds / n_ticks
            await asyncio.sleep(max(0.0, at - time.monotonic()))
            samples.append((time.monotonic(), await self.control.stats()))
        drain_s = float(cell.traffic.get("drain_s", 60.0))
        records = await fleet.results(drain_s + 30.0)
        if capture_task is not None:
            await capture_task
        stats_final = await self.control.stats()
        timings["warm_s"] = warm_s
        timings["drain_s"] = time.monotonic() - w1
        return Phase(w0, w1, records, samples, samples[0][1], stats_final,
                     offered, trace_out.get("path"), trace_out.get("error"),
                     timings)

    async def __aexit__(self, *exc) -> None:
        if self.control is not None:
            with contextlib.suppress(Exception):
                await self.control.close()
        proc = self.proc
        if proc is not None:
            # SIGTERM is the provider CLI's graceful stop: it shuts the
            # host down and exits 0 only if the host did.
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, lambda: proc.wait(timeout=DRAIN_TIMEOUT_S))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            self.provider_rc = proc.returncode
            for pid in self.family:
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    continue
                self.orphans.append(pid)
        if self._log_fh is not None:
            self._log_fh.close()
        await self.server.stop()

    def log_tail(self, n: int = 6000) -> str:
        try:
            with open(self.log_path, errors="replace") as fh:
                return fh.read()[-n:]
        except OSError:
            return ""

    def cleanup(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)


def reduce_trace(trace_path: str, decode_program: str) -> dict:
    """Run `lib/xplane.py` on a capture, in a process of its own pinned to
    the CPU (it reads a file; it must never reach for the chip)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"}
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, os.path.join(LIB, "xplane.py"), trace_path,
         decode_program], env=env, capture_output=True, text=True,
        timeout=300)
    if out.returncode != 0:
        raise BenchFailure(f"trace reduction failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])
