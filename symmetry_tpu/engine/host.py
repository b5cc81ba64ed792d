"""Engine host process: the TPU engine behind a pipe.

Why a separate process: the engine thread's JAX calls (dispatch and
device→host syncs over the TPU runtime) hold the GIL for long stretches.
In-process, that starves the provider's asyncio loop — measured in the
round-3 e2e bench as every client's TTFT collapsing to the wall time
(token events only flushed when the engine went idle). The reference
never hits this because its "engine" is an external HTTP server
(reference: src/provider.ts:210-214); this host process is our native
equivalent of that isolation, with a pipe instead of HTTP.

Roles (tpu.role, engine/disagg/): "unified" (default) serves the full
request; "prefill" builds each prompt's KV and emits it as a versioned
handoff frame instead of decoding; "decode" accepts `adopt` commands
carrying those frames, seeds its prefix store from them, and generates.
The disagg broker in the tpu_native backend runs a prefill+decode host
pair and pipes handoff → adopt between them.

Protocol: JSON lines.
  stdin  ← {"op": "submit", "id", "messages", "max_new", "sampling": {…},
            "speculative": bool?,   (optional per-request opt-out of
            speculative decoding; ignored unless tpu.speculative is on)
            "trace": str?,          (request trace id, threaded into
            scheduler spans so the request correlates across processes)
            "deadline_s": float?}   (seconds of end-to-end deadline left
            at submit; the scheduler sheds the request at admission with
            finish_reason "expired" if it has already passed)
           {"op": "cancel", "id"}
           {"op": "adopt", "id", "frame": base64 handoff frame,
            "max_new", "sampling", "speculative"?, "trace"?,
            "deadline_s"?}   (decode role only: adopt a handed-off KV
            prefix and resume the request; prompt tokens ride the frame,
            so no re-tokenization happens here)
           {"op": "clock", "t0": float}   (clock-offset handshake: the
            provider brackets our CLOCK_MONOTONIC read with its own —
            the NTP midpoint replaces the old assume-zero-offset policy)
           {"op": "trace"}   (span-ring snapshot for the Perfetto export)
           {"op": "metrics"}   (metrics-registry snapshot probe: the
            reply carries this process's utils/metrics.py families —
            the provider merges them tier-labeled into its Prometheus
            exposition and the peer-wire metrics reply)
           {"op": "profile", "duration_s": float?, "dir": str?}
            (on-demand jax.profiler capture, utils/devprof.py: runs a
            bounded device trace on its OWN thread — the serve loop
            and every stream keep flowing — and replies when done)
           {"op": "stats"} | {"op": "shutdown"}
  stdout → {"op": "ready", "model", "role", "slots", "max_seq_len",
            "build_s", "warmup_s", "compile_cache",
            "device": {"platform", "device_kind", "device_count",
                       "hbm": [{"bytes_in_use", "bytes_limit"}, …]},
            "attention": {"prefill", "decode"},
            "sampling": {"top_k", "cap"?, "stages"?: [{"groups",
                         "width"}, …], "ranked"?},
            "moe"?: {"experts", "top_k", "layout", "route": {"decode",
                     "prefill", "opening"?}, "quantized_leaf_route"},
            "timeline": [[name, t0, t1, parent], …], "origin",
            "warmup": {"programs", "wall_s", "compile_s", "retrieval_s",
                       "run_s", "cache_hits", "cache_misses", "slowest"},
            "warmup_programs": [{"program", "batch", "bucket", "t0",
                                 "wall_s", "trace_s", "lower_s",
                                 "backend_s", "retrieval_s", "cache_hits",
                                 "cache_misses"}, …]}
            (after warmup. `timeline` is this process's start-up in
            CLOCK_MONOTONIC seconds — `host.process`, `host.config`,
            `build.devices|params|state`, `warmup`, `host.scheduler`, the
            stamp `ready` — each span begun on the stamp that ended the
            one before; `origin` says where `host.process` begins
            (`kernel` | `package`: utils/trace.py process_start).
            `warmup_programs` is one record a program warm-up ran
            (engine.py `_warm`), READY only; `warmup` its totals and five
            slowest records. The stats reply's `startup` repeats
            everything here but `warmup_programs`.
            `moe` only for an expert model: engine.py
            moe_report. `device` is what JAX handed this process and
            its per-device memory_stats() once every program has
            compiled; `attention` is "pallas" | "pallas-interpret" |
            "xla" per program; `sampling.top_k` is "grouped" (with each
            stage's groups and width — of the vocabulary, then of what
            the stage before kept — and how many entries are `ranked`
            last) | "direct", ops/sampling.py top_k_route. The stats
            reply repeats all three.)
           {"op": "clock", "t0", "t": our monotonic at receipt}
           {"op": "trace", "clock", "components": [{name, spans,
            counters, clock_offset_s}, …]}   (host + scheduler rings,
            stamps on THIS process's clock)
           {"op": "event", "id", "text", "done", "finish_reason",
            "error", "ttft_s", "tokens", "tokens_new",
            "t": {"recv", "picked", "first", "out"}}   ("t" on the
            FIRST event of a request only: per-stage CLOCK_MONOTONIC
            stamps — host recv, placement pick, first sampled token,
            pipe write — so the provider can attribute its TTFT)
           {"op": "events", "events": [{…event fields, no "op"…}, …]}
           {"op": "handoff", "id", "p", "prompt_len", "nbytes",
            "frame": base64}   (prefill role only: the finished prompt's
            aligned KV prefix, serialized; p == 0 is routing-only — the
            prompt was too short for an aligned prefix and the decode
            tier prefills it whole)
           {"op": "metrics", "role", "families": {…}}   (registry
            snapshot, utils/metrics.py shape)
           {"op": "profile", "path"} | {"op": "profile", "error"}
            (capture finished: the trace-artifact directory, or why
            the capture could not run — e.g. one already in progress)
           {"op": "stats", …}   (scheduler counters incl. deferred_depth,
            prefill_jobs_active, the prefix_cache hit/miss/evict/bytes
            block when the shared-prefix KV cache is enabled, and the
            speculative drafted/accepted/acceptance-rate block when
            tpu.speculative is on; `loop_s` / `loop_iters`: the engine
            thread's seconds per loop phase; `compile`: what JAX traced,
            lowered and compiled in this process — counts, seconds,
            `at_ready` as they stood after warm-up, and the last 32
            as [monotonic t, kind, fun_name, seconds])

The batched `events` frame is the hot path: the scheduler coalesces each
decode block's per-slot deltas (plus any finishes and admission errors
from the same block) into ONE frame — one json.dumps, one pipe write,
one flush per block, instead of one per slot per block. Events inside a
frame are ordered; per-request order is the stream order. Single-event
flushes still go out as legacy `event` frames, so pre-batching consumers
keep working and the reader exercises both shapes; `ready`/`error`/
`stats` frames are always single. Emit-path counters (`pipe_writes`,
`pipe_event_writes`, `pipe_events`, `pipe_batched_frames`, `pipe_bytes`)
ride the stats reply under `emit` so the provider/bench can verify the
O(1)-writes-per-block contract end to end (`pipe_event_writes` is the
contract's numerator — ready/stats frames are not emit-path traffic).

Logs go to stderr. The host is intentionally synchronous: the scheduler's
block-boundary flush writes one line under a lock straight from the
engine thread — there is no latency-sensitive I/O in this process to
starve.

A host that finds itself on a platform other than `tpu` without the
CPU having been pinned by name (utils/device.py require_chip) builds
nothing: it says which platform it got on stderr and exits with
HOST_EXIT_NO_CHIP, which the backend reports as a failure it must not
respawn — a chip belongs to one process, so a second host on the same
chip lands here.

Run: python -m symmetry_tpu.engine.host <config.yaml>
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import TYPE_CHECKING, Any

from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler
from symmetry_tpu.protocol.keys import HOST_EXIT_NO_CHIP, HostOp
from symmetry_tpu.provider.config import ConfigManager
from symmetry_tpu.utils.device import NoChipError, device_report
from symmetry_tpu.utils.devprof import CompileWatch
from symmetry_tpu.utils.faults import FAULTS
from symmetry_tpu.utils.logging import logger, set_component
from symmetry_tpu.utils.metrics import METRICS, MetricName
from symmetry_tpu.utils.trace import Tracer

if TYPE_CHECKING:
    from symmetry_tpu.engine.scheduler import TokenEvent


# Raw-KV byte bound for one handoff frame. The frame travels the broker
# pipes as ONE base64 JSON line (~4/3 × raw), and the backend's
# StreamReader line limit in disagg mode is 1 GiB — a frame that
# overflows it kills the reader and crash-loops the supervised pair, so
# the prefill host must never emit one. Oversized prefixes are capped to
# the largest ALIGNED length that fits (KV at position i depends only on
# tokens <= i, so a shorter prefix is always sound — the decode tier
# just re-prefills a longer suffix).
HANDOFF_MAX_KV_BYTES = 384 * 1024 * 1024


class EngineHost:
    def __init__(self, config: ConfigManager,
                 t_main: float | None = None) -> None:
        self._config = config
        # Where `main()` began (CLOCK_MONOTONIC): the start-up timeline's
        # `host.process` ends and `host.config` begins there. A host made
        # without a `main()` (tests) begins its timeline here.
        self._t_main = time.monotonic() if t_main is None else t_main
        # Fault injection (utils/faults.py): env SYMMETRY_FAULTS is
        # inherited from the provider and already loaded at import; a
        # provider-config `faults:` mapping rides the config file here.
        # (config is None in protocol unit tests that never start().)
        if config is not None:
            FAULTS.load(config.get("faults"))
        self._engine: InferenceEngine | None = None
        self._scheduler: Scheduler | None = None
        # Filled by start(): build/warmup seconds, compile-cache
        # directory, the device JAX handed this process, the attention
        # path of each program and the sampler's top-k route (READY and
        # stats carry it).
        self._startup: dict[str, Any] = {}
        # What JAX traced, lowered and compiled in this process (stats
        # `compile` block); start() registers its listeners.
        self._compile = CompileWatch()
        self._wlock = threading.Lock()
        self._cancelled: set[str] = set()
        self._reported: dict[str, int] = {}  # id -> tokens already reported
        # The host's OWN trace ring (the pipe/framing layer): per-request
        # submit spans (pipe read → tokenized → enqueued) and per-frame
        # flush spans. The scheduler's ring lives on the scheduler; the
        # `trace` op ships both.
        self.tracer = Tracer()
        # Emit-path counters (under _wlock): every stdout line counts one
        # pipe_write; pipe_event_writes counts only lines that carry
        # TokenEvents (the writes-per-block contract is about THESE —
        # ready/stats frames are not emit-path traffic); pipe_events
        # counts TokenEvents carried (== event writes only if nothing
        # coalesces). The O(1)-writes-per-block assertion in tests and
        # the bench emit metrics both read these.
        self.emit_stats = {"pipe_writes": 0, "pipe_event_writes": 0,
                           "pipe_events": 0, "pipe_batched_frames": 0,
                           "pipe_bytes": 0}
        # Disaggregation (engine/disagg/): the host's tier role and its
        # side of the handoff accounting — serialize wall + frame bytes
        # on the prefill tier, deserialize/adoption outcomes on the
        # decode tier. Both ride the stats op (→ provider → bench).
        self._role = (getattr(config.tpu, "role", "unified") or "unified"
                      if config is not None else "unified")
        self.handoff_stats = {"frames": 0, "bytes": 0, "prefix_tokens": 0,
                              "routing_only": 0, "serialize_s": 0.0,
                              # Block-manifest accounting (frames v2):
                              # blocks covered by emitted manifests vs
                              # blocks whose payload actually shipped —
                              # the gap is the incremental-handoff win
                              # (asserted by the disagg smoke's
                              # warm-handoff leg).
                              "blocks": 0, "blocks_shipped": 0}
        # Digests of blocks already shipped from this prefill host,
        # PER DESTINATION MEMBER (LRU-bounded per member). A block in a
        # member's ledger is OMITTED from later frames to that member:
        # it adopts the block by reference from its radix tree, or — if
        # it evicted the block since — shortens the adopted prefix and
        # re-prefills a longer suffix (correct either way; the ledger
        # is a bytes optimization, never a correctness input). The
        # submit op's "ledger" field names the planned destination and
        # its ledger EPOCH (bumped by the router every time that member
        # goes lost); an advanced epoch drops the member's entries —
        # its respawned cache is empty, and while skipping blocks it no
        # longer holds stays CORRECT (shorter adopted prefix), it would
        # silently degrade every warm handoff to a full re-prefill.
        # Submits without the field (the fixed pair, old providers)
        # book under one default key — pool-of-1 degenerates to the
        # pair semantics. Gated by tpu.handoff_ledger (default on).
        from collections import OrderedDict

        self._ledger_on = bool(getattr(config.tpu, "handoff_ledger",
                                       False)) if config is not None \
            else False
        self._shipped: dict[str, OrderedDict[str, None]] = {}
        self._shipped_cap = 65536          # digests kept per member
        self._ledger_epochs: dict[str, int] = {}
        self._ledger_dest: dict[str, str] = {}  # req id -> member key
        self.adopt_stats = {"frames": 0, "bytes": 0, "adopted": 0,
                            "rejected": 0, "errors": 0,
                            "deserialize_s": 0.0}
        # Always-on registry families (utils/metrics.py): this process's
        # slice of the fleet time series, shipped to the provider via the
        # HostOp.METRICS probe and tier-labeled there. `metrics.enabled:
        # false` in the provider config disables the whole registry (the
        # host reads the same config copy in start()).
        self._m_pipe_writes = METRICS.counter(
            MetricName.HOST_PIPE_WRITES, "host stdout frames written")
        self._m_pipe_bytes = METRICS.counter(
            MetricName.HOST_PIPE_BYTES, "host stdout bytes written")
        self._m_pipe_events = METRICS.counter(
            MetricName.HOST_PIPE_EVENTS, "token events carried on the pipe")
        self._m_handoff_frames = METRICS.counter(
            MetricName.HOST_HANDOFF_FRAMES,
            "handoff frames emitted (prefill role)")
        self._m_handoff_bytes = METRICS.counter(
            MetricName.HOST_HANDOFF_BYTES, "handoff frame bytes emitted")
        self._m_handoff_serialize = METRICS.histogram(
            MetricName.HOST_HANDOFF_SERIALIZE,
            "handoff extract+serialize wall per frame")
        self._m_adopt_frames = METRICS.counter(
            MetricName.HOST_ADOPT_FRAMES,
            "handoff frames processed by the decode role",
            labels=("outcome",))
        self._m_adopt_deserialize = METRICS.histogram(
            MetricName.HOST_ADOPT_DESERIALIZE,
            "handoff decode+validate+insert wall per frame")

    # ---------------------------------------------------------------- wire

    def _write(self, obj: dict[str, Any], *, events: int = 0) -> None:
        if FAULTS.enabled and FAULTS.point("host.pipe_write"):
            return  # injected drop_frame: the frame is lost on the wire
        line = json.dumps(obj, separators=(",", ":"))
        if events > 0:
            # Event frames only (one per block): the flush hold is the
            # "emit" leg of the TTFT chain, worth a span; ready/stats
            # frames are not emit-path traffic.
            with self.tracer.phase("host.pipe_flush", ring="pipe_flush",
                                   events=events, bytes=len(line) + 1):
                self._write_line(line, events)
        else:
            self._write_line(line, events)

    def _write_line(self, line: str, events: int) -> None:
        with self._wlock:
            self.emit_stats["pipe_writes"] += 1
            self.emit_stats["pipe_events"] += events
            self.emit_stats["pipe_bytes"] += len(line) + 1
            if events > 0:
                self.emit_stats["pipe_event_writes"] += 1
            if events > 1:
                self.emit_stats["pipe_batched_frames"] += 1
            sys.stdout.write(line + "\n")
            sys.stdout.flush()
        self._m_pipe_writes.inc()
        self._m_pipe_bytes.inc(len(line) + 1)
        if events:
            self._m_pipe_events.inc(events)

    def _event_dict(self, req_id: str, ev: "TokenEvent") -> dict[str, Any]:
        """One event's wire fields (shared by legacy and batched frames),
        with the per-request delta bookkeeping. tokens_new deltas ride
        tokens_emitted — only tokens that actually streamed as text, so
        summing them reproduces the bench's tokens_streamed exactly (the
        EOS token and post-finish block remainders are excluded; the
        cumulative `tokens` field keeps the EOS-counting convention)."""
        prev = self._reported.get(req_id, 0)
        new = max(ev.tokens_emitted - prev, 0)
        self._reported[req_id] = max(ev.tokens_emitted, prev)
        out: dict[str, Any] = {"id": req_id, "text": ev.text,
                               "tokens": ev.tokens_generated,
                               "tokens_new": new}
        if ev.ttft_s is not None:
            out["ttft_s"] = round(ev.ttft_s, 4)
        if ev.stages:
            # First event of the request: forward the scheduler's stage
            # stamps and add the pipe-write moment, so the provider can
            # attribute its TTFT per stage (host recv → pick → first
            # token → pipe out; all CLOCK_MONOTONIC, one clock across
            # processes on Linux).
            out["t"] = {k: round(v, 4) for k, v in ev.stages.items()
                        if v is not None}
            out["t"]["out"] = round(time.monotonic(), 4)
        if ev.tokens_reused is not None:
            # First-event rider: radix tokens the admission reused
            # (resume admissions assert > 0 — the cheap-resume contract).
            out["reused"] = ev.tokens_reused
        if ev.resumed_from is not None:
            # Resume continuation start offset, in the client's token
            # numbering — the relay drops any overlap below the client's
            # own count (offset dedup: a resume never replays tokens the
            # client already has).
            out["resume_from"] = ev.resumed_from
        if ev.done:
            out["done"] = True
            out["finish_reason"] = ev.finish_reason
            if ev.error:
                out["error"] = ev.error
            if ev.costs is not None:
                # symledger terminal rider (engine/ledger.py): the
                # request's attributed cost block rides its finish
                # event to the provider, which stamps it on the final
                # stream frame behind tpu.ledger.
                out["costs"] = ev.costs
            self._reported.pop(req_id, None)
            self._cancelled.discard(req_id)
        return out

    def _emit_batch(self, batch: list[tuple[GenRequest, "TokenEvent"]]
                    ) -> None:
        """Scheduler block-boundary sink: the whole block's events leave
        as ONE pipe write+flush. A lone event keeps the legacy single
        `event` frame (wire-compatible with pre-batching readers)."""
        events = [self._event_dict(req.id, ev) for req, ev in batch]
        if len(events) == 1:
            self._write({"op": HostOp.EVENT, **events[0]}, events=1)
        else:
            self._write({"op": HostOp.EVENTS, "events": events},
                        events=len(events))

    # ------------------------------------------------------------- lifecycle

    def start(self) -> None:
        """Build, warm up, start the scheduler, write READY. Every step is
        a `start.*` span of `self.tracer`, each begun on the stamp that
        ended the one before it; they are frozen into `startup.timeline`
        once the last has closed, just before READY is written (PERF.md §3
        says which metric reads which row)."""
        from symmetry_tpu.utils.compile_cache import enable_compile_cache

        span = self.tracer.phase
        # the interpreter and the imports, JAX among them
        origin = self.tracer.process_span("start.host.process",
                                          self._t_main)
        step = span("start.host.config", t0=self._t_main, parent=None)
        with step:
            # Persistent XLA compile cache: without it every host start
            # recompiles the full serving grid; with it a config-identical
            # restart compiles ~nothing.
            cache_dir = enable_compile_cache(self._config.tpu)
            self._compile.register()
        self._engine = InferenceEngine.from_tpu_config(
            self._config.tpu, tracer=self.tracer,
            compile_watch=self._compile, t0=step.t1)
        t_build = self._engine.built_at - step.t1
        sched_engine = self._engine
        mh = self._config.tpu.multihost
        if mh and mh.get("num_processes", 1) > 1:
            # Rank 0 fronts the scheduler; its commands drive all ranks in
            # lockstep (parallel/multihost.py). Worker ranks run
            # `python -m symmetry_tpu.provider --worker` as before.
            from symmetry_tpu.parallel.multihost import (
                CommandLoop, MultihostEngine)

            self._command_loop = CommandLoop(self._engine,
                                             is_coordinator=True)
            sched_engine = MultihostEngine(self._command_loop)
        step = span("start.warmup", t0=self._engine.built_at, parent=None)
        with step:
            sched_engine.warmup()
            self._compile.mark_ready()
        t_warmup = step.t1 - step.t0
        step = span("start.host.scheduler", t0=step.t1, parent=None)
        with step:
            self._scheduler = Scheduler(
                sched_engine, emit_batch=self._emit_batch,
                pipeline_depth=int(getattr(self._config.tpu,
                                           "pipeline_depth", 2)),
                handoff=(self._handoff_sink if self._role == "prefill"
                         else None),
                ledger_enabled=bool(getattr(self._config.tpu,
                                            "ledger", True)),
                compile_watch=self._compile)
            # tpu.tracing=False empties every ring (the bench A/B knob);
            # the default leaves the bounded always-on recorder running.
            # The host's own ring is emptied below, once the start-up's
            # spans have been read out of it.
            tracing = bool(getattr(self._config.tpu, "tracing", True))
            self._scheduler.tracer.enabled = tracing
            # Metrics registry gate (metrics.enabled: false → every
            # registry op in this process is one branch) + the
            # structured-log component tag for this process's records.
            mcfg = self._config.get("metrics") or {}
            METRICS.enabled = bool(mcfg.get("enabled", True))
            set_component("host")
            self._scheduler.start()
            # What this process runs on, read once every program has
            # compiled and the caches are allocated: READY, the log line
            # and every stats reply carry the same block, so nobody
            # downstream has to touch JAX (and take the chip) to learn it.
            self._startup = {
                "build_s": round(t_build, 1),
                "warmup_s": round(t_warmup, 1),
                "compile_cache": cache_dir,
                "device": device_report(),
                "attention": self._engine.attention_paths(),
                "sampling": self._engine.sampling_route(),
                # what this model brings of its own (`moe`, `ssm`,
                # `diffusion`, `cache`)
                **self._engine.startup_reports()}
            warm = self._engine.warmup_report()
            self._startup["warmup"] = warm
        # The timeline, frozen: the stamp `ready` is the one that ended
        # `host.scheduler` — what follows is the frame's own write.
        # `origin` says where `host.process` begins (utils/trace.py
        # process_start: `kernel` or `package`).
        self._startup["timeline"] = self.tracer.timeline() + [
            ["ready", step.t1, step.t1, None]]
        self._startup["origin"] = origin
        self.tracer.enabled = tracing
        programs = self._engine.warmup_programs
        self._write({"op": HostOp.READY,
                     "model": self._config.model_name,
                     "role": self._role,
                     "slots": self._engine.max_slots,
                     "max_seq_len": self._engine.max_seq_len,
                     **self._startup,
                     # every record of the warm-up, here and in the log
                     # only: `startup.warmup` (stats, every second) keeps
                     # the totals and the five slowest
                     "warmup_programs": programs})
        # Startup breakdown to stderr: a slow start must carry its own
        # explanation in the provider log (round-3 verdict #1).
        dev, attn = self._startup["device"], self._startup["attention"]
        moe = self._startup.get("moe")
        samp = json.dumps(self._startup["sampling"], separators=(",", ":"))
        hbm = " ".join(f"{h['bytes_in_use'] / 2**30:.2f}/"
                       f"{h['bytes_limit'] / 2**30:.2f}GiB"
                       for h in dev["hbm"]) or "n/a"
        devices_s = next(t1 - t0 for name, t0, t1, _
                         in self._startup["timeline"]
                         if name == "build.devices")
        slowest = " ".join(
            "{}[{}]={:.1f}s".format(
                r["program"], ",".join(str(r[k]) for k in ("batch", "bucket")
                                       if r[k] is not None), r["wall_s"])
            for r in warm["slowest"][:3])
        # the whole record first, so the log's tail is the line a person
        # reads
        logger.info("engine host warm-up: " + json.dumps(
            {"timeline": self._startup["timeline"], "origin": origin,
             "programs": [{k: round(v, 6) if isinstance(v, float) else v
                           for k, v in r.items()} for r in programs]},
            separators=(",", ":")))
        logger.info(f"engine host ready: model={self._config.model_name} "
                    f"role={self._role} slots={self._engine.max_slots} "
                    f"platform={dev['platform']} "
                    f"device_kind={dev['device_kind']!r} "
                    f"device_count={dev['device_count']} hbm={hbm} "
                    f"attention={','.join(f'{k}:{v}' for k, v in attn.items())} "
                    f"sampling={samp} "
                    + (f"moe={json.dumps(moe)} " if moe else "") +
                    f"devices={devices_s:.1f}s "
                    f"build={t_build:.1f}s warmup={t_warmup:.1f}s "
                    f"compile={warm['compile_s']:.1f}s "
                    f"misses={warm['cache_misses']} slowest: {slowest} "
                    f"compile_cache={cache_dir or 'off'}")

    def serve_forever(self) -> int:
        self.start()
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            if FAULTS.enabled and FAULTS.point("host.pipe_read"):
                continue  # injected drop_frame: the command is lost
            try:
                msg = json.loads(line)
            except ValueError:
                logger.warning(f"host: bad command line {line[:80]!r}")
                continue
            op = msg.get("op")
            if op == HostOp.SUBMIT:
                self._submit(msg)
            elif op == HostOp.ADOPT:
                self._handle_adopt(msg)
            elif op == HostOp.CANCEL:
                req_id = str(msg.get("id", ""))
                if req_id in self._reported:  # only live requests; a late
                    self._cancelled.add(req_id)  # cancel must not leak ids
            elif op == HostOp.CLOCK:
                self._handle_clock(msg)
            elif op == HostOp.TRACE:
                self._handle_trace()
            elif op == HostOp.STATS:
                stats = getattr(self._scheduler, "stats", None)
                m = stats() if stats is not None else dict(
                    self._scheduler.metrics)
                m["op"] = HostOp.STATS
                # liveness of the engine thread — the wedged-decode-loop
                # signal the provider's health loop needs (SURVEY §5.3)
                thread = self._scheduler._thread
                m["engine_alive"] = bool(thread is not None
                                         and thread.is_alive())
                # Snapshot without _wlock — _write below takes it (non-
                # reentrant), and a dict-of-ints copy is GIL-atomic enough
                # for a stats read.
                m["emit"] = dict(self.emit_stats)
                m["role"] = self._role
                m["startup"] = self._startup
                m["compile"] = self._compile.stats()
                # Per-request emitted-token journal rider: the tokens
                # each live stream has had WRITTEN to the pipe. The
                # backend's supervisor keeps the last heartbeat's copy,
                # so a crash/wedge shed stamps an accurate `emitted`
                # count even for frames the relay never got to read —
                # the resume path's RNG-lane position. Tiny by
                # construction (one int per in-flight request). Listed
                # keys first: the engine thread mutates _reported
                # concurrently and iteration must not race a resize.
                m["journal"] = {k: self._reported.get(k, 0)
                                for k in list(self._reported)}
                # Pool-gossip rider: the engine's radix-cache summary
                # (hot-path block digests + depth histogram) rides every
                # stats reply — the provider's PoolRouter harvests it
                # off the heartbeat probe for cache-affine placement. A
                # payload field on an existing op, not a new op: the
                # wire contract (W101–W104) stays untouched, and members
                # that predate the field simply gossip nothing (the
                # router degrades to load-only for them).
                summary = getattr(self._engine, "prefix_cache_summary",
                                  None)
                if summary is not None:
                    ps = summary()
                    if ps is not None:
                        m["prefix_summary"] = ps
                if self._role == "prefill":
                    m["handoff"] = {**self.handoff_stats,
                                    "serialize_s": round(
                                        self.handoff_stats["serialize_s"],
                                        4)}
                elif self._role == "decode":
                    m["adopt"] = {**self.adopt_stats,
                                  "deserialize_s": round(
                                      self.adopt_stats["deserialize_s"],
                                      4)}
                if FAULTS.enabled:
                    # Armed-fault accounting: a chaos run's stats carry
                    # which seams fired, so the test/bench can assert the
                    # injection actually happened.
                    m["faults"] = FAULTS.counters()
                self._write(m)
            elif op == HostOp.METRICS:
                self._handle_metrics()
            elif op == HostOp.PROFILE:
                self._handle_profile(msg)
            elif op == HostOp.SHUTDOWN:
                break
        self._scheduler.stop()
        if getattr(self, "_command_loop", None) is not None:
            self._command_loop.stop()
        return 0

    def _handle_clock(self, msg: dict) -> None:
        """Clock-offset handshake: echo the provider's send stamp and add
        our CLOCK_MONOTONIC read. The provider brackets this read with its
        own stamps and takes the min-RTT NTP midpoint — the measured
        offset the per-stage TTFT attribution applies instead of clamping
        negative cross-process spans to zero."""
        self._write({"op": HostOp.CLOCK, "t0": msg.get("t0"),
                     "t": time.monotonic()})

    def _handle_metrics(self) -> None:
        """Metrics-registry snapshot: this process's families (compact —
        no recent-sample rings on the wire) plus the tier role, so the
        provider can merge them tier-labeled into its exposition."""
        snap = METRICS.snapshot(compact=True)
        self._write({"op": HostOp.METRICS, "role": self._role, **snap})

    def _handle_trace(self) -> None:
        """Span-ring snapshot: this process's host + scheduler rings,
        stamps on this process's clock (the provider adds its measured
        offset when merging)."""
        comps = [self.tracer.component("host")]
        trace_export = getattr(self._scheduler, "trace_export", None)
        if trace_export is not None:
            comps.append(trace_export())
        self._write({"op": HostOp.TRACE, "clock": time.monotonic(),
                     "components": comps})

    def _handle_profile(self, msg: dict) -> None:
        """On-demand jax.profiler capture (utils/devprof.py): the
        capture sleeps for its whole window, so it runs on its OWN
        daemon thread — the serve loop keeps reading commands and the
        engine keeps dispatching (the capture's entire point is to
        observe live traffic). The reply is written when the capture
        finishes; a concurrent capture request is refused loudly."""
        import tempfile

        from symmetry_tpu.utils.devprof import capture_device_profile

        # `is None`, not `or`: an explicit duration_s of 0 means the
        # minimal instant capture, not the 2 s default.
        raw = msg.get("duration_s")
        duration_s = 2.0 if raw is None else float(raw)
        out_dir = str(msg.get("dir") or "") or os.path.join(
            tempfile.gettempdir(), "symmetry_tpu_profiles")

        def run() -> None:
            t0 = time.monotonic()
            try:
                path = capture_device_profile(out_dir, duration_s)
            except Exception as exc:  # noqa: BLE001 — reply, never crash
                self._write({"op": HostOp.PROFILE, "error": str(exc)})
                return
            logger.info(f"device profile captured → {path} "
                        f"({duration_s:.1f}s window, "
                        f"{time.monotonic() - t0:.1f}s in all)")
            self._write({"op": HostOp.PROFILE, "path": path,
                         "duration_s": duration_s})

        threading.Thread(target=run, name="jax-profile",
                         daemon=True).start()

    # --------------------------------------------------------------- submit

    def _submit(self, msg: dict) -> None:
        """The pipe_in leg as a span: command read → tokenized →
        enqueued."""
        req_id = str(msg.get("id", ""))
        trace_id = str(msg.get("trace") or "")
        with self.tracer.phase("host.host_submit", ring="host_submit",
                               request_id=req_id,
                               trace_id=trace_id) as span:
            self._submit_request(msg, req_id, trace_id, span)

    def _submit_request(self, msg: dict, req_id: str, trace_id: str,
                        span: dict[str, Any]) -> None:
        t_recv = time.monotonic()
        s = msg.get("sampling") or {}
        resume = msg.get("resume") if isinstance(msg.get("resume"), dict) \
            else None
        max_new = int(msg.get("max_new", 512))
        resume_offset = 0
        try:
            prompt_ids = self._engine.tokenizer.apply_chat_template(
                msg.get("messages") or [])
            # Stream resumption (resolve_resume, tokenizer.py — ONE
            # implementation across every admission path): condition on
            # prompt + the emitted text the client already holds,
            # generate only the continuation. The emitted run re-enters
            # through the ordinary admission path — prompt+emitted
            # blocks hit the radix cache (only the unaligned tail
            # re-prefills) and the seed path treats it like any other
            # prompt; the resolved offset positions a seeded request's
            # RNG lane and offsets the token budget.
            from symmetry_tpu.engine.tokenizer import resolve_resume

            prompt_ids, max_new, resume_offset = resolve_resume(
                self._engine.tokenizer, resume, prompt_ids, max_new)
        except Exception as exc:  # noqa: BLE001 — tokenizer failure → event
            self._write({"op": HostOp.EVENT, "id": req_id, "text": "",
                         "done": True, "finish_reason": "error",
                         "error": f"tokenization failed: {exc}"}, events=1)
            return
        if resume is not None and max_new == 0:
            # The interrupted stream had already spent the whole token
            # budget — only the finish frame was lost. Complete NOW
            # (finish "length", zero new tokens) instead of generating
            # past the client's max_tokens.
            self._write({"op": HostOp.EVENT, "id": req_id, "text": "",
                         "done": True, "finish_reason": "length",
                         "tokens": resume_offset, "tokens_new": 0,
                         "resume_from": resume_offset}, events=1)
            return
        sampling = SamplingParams(
            temperature=float(s.get("temperature", 0.0)),
            top_p=float(s.get("top_p", 1.0)),
            top_k=int(s.get("top_k", 0)),
            seed=s.get("seed"),
            rng_skip=resume_offset,
        )
        led = msg.get("ledger")
        if self._role == "prefill" and isinstance(led, dict):
            # Pool routing told us which decode member this request's
            # handoff is planned for, and that member's ledger epoch.
            # An advanced epoch means the member respawned since we
            # last shipped to it: drop its ledger NOW, before this
            # request's handoff would skip blocks an empty cache
            # cannot adopt by reference.
            member = str(led.get("member") or "decode")
            epoch = int(led.get("epoch") or 0)
            with self._wlock:
                if epoch > self._ledger_epochs.get(member, 0):
                    self._ledger_epochs[member] = epoch
                    self._shipped.pop(member, None)
                self._ledger_dest[req_id] = member
                while len(self._ledger_dest) > self._shipped_cap:
                    # Requests that end without a handoff (cancel,
                    # deadline shed) leave their entry behind; bound it.
                    self._ledger_dest.pop(next(iter(self._ledger_dest)))
        if self._role == "prefill":
            pb = self._engine.prefix_block or 0
            if pb and (len(prompt_ids) - 1) // pb == 0:
                # Short-prompt fast path: no whole-block prefix can be
                # handed off, so running the prefill HERE would only
                # duplicate the decode tier's suffix dispatch. Route the
                # tokens straight through as a routing-only frame — the
                # decode host prefills the whole (tiny) prompt itself.
                self._emit_handoff(req_id, prompt_ids, 0, None)
                return
        self._reported[req_id] = 0

        def emit(ev, req_id=req_id) -> None:
            # Fallback path only: the scheduler delivers through the
            # emit_batch sink; this fires if batching is ever disabled.
            self._write({"op": HostOp.EVENT, **self._event_dict(req_id, ev)},
                        events=1)

        spec = msg.get("speculative")
        deadline = msg.get("deadline_s")
        self._scheduler.submit(GenRequest(
            prompt_ids=prompt_ids, sampling=sampling,
            max_new_tokens=max_new,
            emit=emit,
            cancelled=lambda: req_id in self._cancelled,
            id=req_id,
            speculative=spec if isinstance(spec, bool) else None,
            trace_id=trace_id,
            resume_offset=resume_offset,
            # deadline_s is RELATIVE (seconds left at provider submit);
            # anchor it to this process's clock at receipt so the
            # scheduler's admission check needs no cross-process offset.
            deadline_at=(t_recv + float(deadline)
                         if deadline is not None else None)))
        span["prompt_len"] = len(prompt_ids)

    # -------------------------------------------------------------- disagg

    def _handoff_sink(self, slot: int, req: Any, first: int) -> None:
        """Prefill-role scheduler terminal (runs on the engine thread):
        snapshot the slot lane's KV through the whole-block prefix
        length, serialize it blockwise, and emit the handoff frame. By
        return the lane is free — the np.asarray below syncs the
        extract before the scheduler can reuse the slot."""
        import numpy as np

        t0 = time.monotonic()
        n = len(req.prompt_ids)
        pb = self._engine.prefix_block or 0
        p = pb * ((n - 1) // pb) if pb else 0
        if p > 0:
            # Pipe-transport bound: cap to the largest whole-block
            # prefix whose frame fits the broker's line limit (see
            # HANDOFF_MAX_KV_BYTES). Shorter-than-built prefixes are
            # causally sound; the decode tier pays a longer suffix.
            max_p = pb * (HANDOFF_MAX_KV_BYTES
                          // self._engine.kv_bytes_per_token() // pb)
            p = min(p, max_p)
        arrays = None
        if p > 0:
            cache = self._engine.extract_slot_kv(slot, p)
            # Slice to p positions host-side: the frame ships only the
            # prefix the decode tier will adopt, not the lane's full
            # capacity — handoff bytes scale with the prompt, not the
            # engine's max_seq_len.
            arrays = {"k": np.asarray(cache.k)[:, :, :p],
                      "v": np.asarray(cache.v)[:, :, :p]}
            if self._engine.kv_quant:
                arrays["k_scale"] = np.asarray(cache.k_scale)[:, :, :, :p]
                arrays["v_scale"] = np.asarray(cache.v_scale)[:, :, :, :p]
        self._emit_handoff(req.id, req.prompt_ids, p, arrays, t0=t0)

    def _emit_handoff(self, req_id: str, prompt_ids: list[int], p: int,
                      arrays: Any, t0: float | None = None) -> None:
        from symmetry_tpu.engine.disagg import encode_kv_handoff
        from symmetry_tpu.engine.prefix_cache import block_digests

        if t0 is None:
            t0 = time.monotonic()
        # disagg.handoff seam: crash = the prefill host dies with the
        # request's KV built but unshipped (the smoke's mid-request
        # failure); drop_frame = the frame is lost and the request
        # silently vanishes (watchdog territory).
        if FAULTS.enabled and FAULTS.point("disagg.handoff"):
            return
        pb = self._engine.prefix_block or 0
        skip: list[int] = []
        digests: list[str] = []
        with self._wlock:
            # _submit's pipe-reader thread writes this map; this method
            # runs on the engine thread too (symlint C202).
            member = self._ledger_dest.pop(req_id, "decode")
        if p > 0 and pb and self._ledger_on:
            # Incremental handoff: blocks whose digest this host already
            # shipped TO THIS DESTINATION are omitted from the payload
            # (manifest-only). The ledger mutates under _wlock — this
            # method runs on the engine thread AND the pipe-reader
            # thread (fast path).
            digests = block_digests(prompt_ids, p, pb)
            with self._wlock:
                ledger = self._shipped.get(member)
                if ledger is not None:
                    skip = [j for j, d in enumerate(digests)
                            if d in ledger]
        frame = encode_kv_handoff(req_id, prompt_ids, p, arrays,
                                  kv_quant=self._engine.kv_quant,
                                  block_size=pb, skip=skip,
                                  digests=digests if digests else None)
        import base64

        b64 = base64.b64encode(frame).decode("ascii")
        dt = time.monotonic() - t0
        n_blocks = p // pb if (p and pb) else 0
        # Under _wlock: this method runs on the ENGINE thread via the
        # scheduler's handoff sink AND on the pipe-reader thread via the
        # short-prompt fast path in _submit — unlocked `dict[k] += 1`
        # from two threads loses updates (symlint C202).
        with self._wlock:
            self.handoff_stats["frames"] += 1
            self.handoff_stats["bytes"] += len(frame)
            self.handoff_stats["prefix_tokens"] += p
            self.handoff_stats["blocks"] += n_blocks
            self.handoff_stats["blocks_shipped"] += n_blocks - len(skip)
            if p == 0:
                self.handoff_stats["routing_only"] += 1
            self.handoff_stats["serialize_s"] += dt
            if digests:
                from collections import OrderedDict

                ledger = self._shipped.setdefault(member, OrderedDict())
                for d in digests:
                    ledger.pop(d, None)
                    ledger[d] = None  # most-recently-shipped last
                while len(ledger) > self._shipped_cap:
                    ledger.popitem(last=False)
        self._m_handoff_frames.inc()
        self._m_handoff_bytes.inc(len(frame))
        self._m_handoff_serialize.observe(dt)
        # This host's bookkeeping for the request ends here: token
        # events (and any cancel) now belong to the decode tier.
        self._reported.pop(req_id, None)
        self._cancelled.discard(req_id)
        self.tracer.record("handoff_emit", t0, dt, request_id=req_id,
                           p=p, bytes=len(frame))
        # "t": emit stamp (this clock) — the broker subtracts it
        # (through the measured pipe clock offset) from its receipt
        # time, splitting handoff WIRE latency from serialize wall.
        self._write({"op": HostOp.HANDOFF, "id": req_id, "p": p,
                     "prompt_len": len(prompt_ids),
                     "nbytes": len(frame), "frame": b64,
                     "blocks": n_blocks, "shipped": n_blocks - len(skip),
                     "t": round(time.monotonic(), 4)})

    def _handle_adopt(self, msg: dict) -> None:
        """Decode-role command: submit the migrated request with an
        adoption thunk the SCHEDULER runs at admission pick. EVERYTHING
        frame-heavy — base64 decode, crc, structural validation, bucket
        padding, the host→device transfer, the store insert — lives in
        the thunk, on the engine thread: the prefix store's mutation
        contract is engine-thread-only, and a burst of multi-hundred-MB
        frames processed on THIS serial command loop would starve stats
        replies past the supervisor's wedge deadline and delay every
        queued cancel/submit behind them. The request is submitted with
        an EMPTY prompt; the thunk fills prompt_ids from the frame's
        tokens before the scheduler's lookup. A frame that fails ANY
        check (truncated, corrupt, wrong version, wrong geometry) fails
        this one request with an error event through the scheduler's
        admission error path — never adopts questionable KV, never
        kills the loop."""
        t_recv = time.monotonic()
        req_id = str(msg.get("id", ""))
        frame_b64 = msg.get("frame")
        if not isinstance(frame_b64, str) or not frame_b64:
            # adopt_stats is written from this pipe-reader thread AND
            # from the adopt thunk on the engine thread; every mutation
            # holds _wlock (symlint C202).
            with self._wlock:
                self.adopt_stats["errors"] += 1
            self._m_adopt_frames.inc(outcome="error")
            self._write({"op": HostOp.EVENT, "id": req_id, "text": "",
                         "done": True, "finish_reason": "error",
                         "error": "handoff adoption failed: adopt op "
                                  "carries no frame"}, events=1)
            return

        def adopt(req, frame_b64=frame_b64, req_id=req_id) -> None:
            from symmetry_tpu.engine.disagg import decode_kv_handoff

            t0 = time.monotonic()
            try:
                import base64

                raw = base64.b64decode(frame_b64, validate=True)
                handoff = decode_kv_handoff(raw)
                if handoff.request_id != req_id:
                    raise ValueError(
                        f"frame carries id {handoff.request_id!r}, "
                        f"command says {req_id!r}")
                req.prompt_ids = list(handoff.tokens)
                ok = (self._engine.adopt_prefix(handoff)
                      if handoff.p else False)
            except Exception as exc:  # noqa: BLE001 — fail one request
                with self._wlock:
                    self.adopt_stats["errors"] += 1
                self._m_adopt_frames.inc(outcome="error")
                raise RuntimeError(
                    f"handoff adoption failed: {exc}") from exc
            dt = time.monotonic() - t0
            with self._wlock:
                self.adopt_stats["frames"] += 1
                self.adopt_stats["bytes"] += len(raw)
                self.adopt_stats["deserialize_s"] += dt
                if handoff.p:
                    if ok:
                        self.adopt_stats["adopted"] += 1
                    else:
                        # Store rejected (budget): full prefill fallback
                        # — slower but still token-identical for greedy.
                        self.adopt_stats["rejected"] += 1
            self._m_adopt_deserialize.observe(dt)
            if handoff.p:
                self._m_adopt_frames.inc(
                    outcome="adopted" if ok else "rejected")
            else:
                # p == 0 routing-only frames count too — the registry
                # total must agree with adopt_stats["frames"], the same
                # quantity on the stats() surface.
                self._m_adopt_frames.inc(outcome="routing_only")

        s = msg.get("sampling") or {}
        resume = msg.get("resume") if isinstance(msg.get("resume"), dict) \
            else None
        max_new = int(msg.get("max_new", 512))
        resume_offset = 0
        if resume is not None:
            try:
                # A resumed migration: the emitted tokens already ride
                # the frame (the prefill tier appended them to the
                # prompt), so the resolved ids are discarded — this
                # tier only restores the RNG lane position and the
                # remaining token budget (resolve_resume: the shared
                # implementation; a negative claim fails this one
                # request, never the loop).
                from symmetry_tpu.engine.tokenizer import resolve_resume

                _, max_new, resume_offset = resolve_resume(
                    self._engine.tokenizer, resume, [], max_new)
            except Exception as exc:  # noqa: BLE001 — bad resume → event
                with self._wlock:
                    self.adopt_stats["errors"] += 1
                self._m_adopt_frames.inc(outcome="error")
                self._write({"op": HostOp.EVENT, "id": req_id,
                             "text": "", "done": True,
                             "finish_reason": "error",
                             "error": f"handoff adoption failed: {exc}"},
                            events=1)
                return
            if resume is not None and max_new == 0:
                # Budget already spent by the interrupted stream — only
                # the finish frame was lost; complete without admitting.
                self._write({"op": HostOp.EVENT, "id": req_id,
                             "text": "", "done": True,
                             "finish_reason": "length",
                             "tokens": resume_offset, "tokens_new": 0,
                             "resume_from": resume_offset}, events=1)
                return
        sampling = SamplingParams(
            temperature=float(s.get("temperature", 0.0)),
            top_p=float(s.get("top_p", 1.0)),
            top_k=int(s.get("top_k", 0)),
            seed=s.get("seed"),
            rng_skip=resume_offset,
        )
        self._reported[req_id] = 0

        def emit(ev, req_id=req_id) -> None:
            self._write({"op": HostOp.EVENT, **self._event_dict(req_id, ev)},
                        events=1)

        spec = msg.get("speculative")
        deadline = msg.get("deadline_s")
        trace_id = str(msg.get("trace") or "")
        self._scheduler.submit(GenRequest(
            # Filled by the adopt thunk from the frame's tokens at
            # admission pick (the whole frame parse runs there).
            prompt_ids=[], sampling=sampling,
            max_new_tokens=max_new,
            emit=emit,
            cancelled=lambda: req_id in self._cancelled,
            id=req_id,
            speculative=spec if isinstance(spec, bool) else None,
            trace_id=trace_id,
            resume_offset=resume_offset,
            adopt=adopt,
            # Rebased by the broker for prefill-tier time already spent;
            # may arrive negative — the scheduler then sheds "expired".
            deadline_at=(t_recv + float(deadline)
                         if deadline is not None else None)))
        self.tracer.record("host_adopt", t_recv,
                           time.monotonic() - t_recv, request_id=req_id,
                           trace_id=trace_id, frame_b64_len=len(frame_b64))


def main() -> int:
    t_main = time.monotonic()
    if len(sys.argv) != 2:
        print("usage: python -m symmetry_tpu.engine.host <config.yaml>",
              file=sys.stderr)
        return 2
    host = EngineHost(ConfigManager(config_path=sys.argv[1]), t_main)
    try:
        return host.serve_forever()
    except NoChipError as exc:
        logger.error(f"engine host refused to start: {exc}")
        return HOST_EXIT_NO_CHIP


if __name__ == "__main__":
    sys.exit(main())
