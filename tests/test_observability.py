"""Request-scoped distributed tracing (PR 5): clock reconciliation,
Perfetto export, flight recorder, trace propagation, structured logs.

Layout mirrors the layer being tested:

  - Histogram snapshot consistency (the to_dict/mean race fix);
  - clock_handshake_offset + the tpu_native per-stage attribution with a
    MEASURED offset (the negative-span clamp's replacement), including a
    full fake-host pipe round trip with a deliberately skewed host clock;
  - export_perfetto schema + cross-component reconciliation;
  - FlightRecorder dump/window/rate-limit;
  - scheduler span/counter rings on a fake engine (trace_id propagation);
  - EngineHost clock/trace op handlers;
  - JSON log mode stamping trace_id/request_id from log_context;
  - (crypto-gated) echo-backend e2e: client → provider trace op → merged
    Perfetto export with >= 3 components on one reconciled clock.
"""

import asyncio
import json
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from symmetry_tpu.engine.host import EngineHost
from symmetry_tpu.provider.config import ConfigManager
from symmetry_tpu.utils.trace import (
    FlightRecorder,
    Histogram,
    Tracer,
    clock_handshake_offset,
    export_perfetto,
    new_trace_id,
)


class TestHistogramSnapshot:
    def test_to_dict_is_consistent_under_concurrent_observe(self):
        """count/total/min/max/reservoir are mutated together under the
        lock; a snapshot must read them together too. Every observation
        is exactly 1.0, so ANY consistent snapshot has mean == 1.0 —
        the old unlocked reads could pair a fresh total with a stale
        count and report a mean no prefix of the stream ever had."""
        h = Histogram()
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                h.observe(1.0)

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            last_count = 0
            for _ in range(300):
                d = h.to_dict()
                if d["count"]:
                    assert d["mean"] == 1.0
                    assert d["min"] == d["max"] == 1.0
                    assert d["p50"] == 1.0
                assert d["count"] >= last_count  # monotone snapshots
                last_count = d["count"]
                assert h.mean in (None, 1.0)
        finally:
            stop.set()
            for t in threads:
                t.join()

    def test_percentile_consistent_with_snapshot(self):
        h = Histogram()
        for v in (0.1, 0.2, 0.3):
            h.observe(v)
        d = h.to_dict()
        assert d["count"] == 3 and d["p50"] == 0.2
        assert h.mean == pytest.approx(0.2)


class TestClockHandshake:
    def test_midpoint_recovers_offset(self):
        # Symmetric RTT: the midpoint recovers the offset exactly.
        off = 5.0
        samples = [(t, (t + 0.001) + off, t + 0.002)
                   for t in (10.0, 11.0, 12.0)]
        assert clock_handshake_offset(samples) == pytest.approx(off)

    def test_min_rtt_sample_wins(self):
        # A slow, asymmetric round trip would estimate badly; the tight
        # sample must win regardless of order.
        good = (10.0, 10.0005 + 2.0, 10.001)
        bad = (11.0, 11.9 + 2.0, 12.0)  # 1s rtt, reply-heavy
        assert clock_handshake_offset([bad, good]) == pytest.approx(
            2.0, abs=1e-6)
        assert clock_handshake_offset([]) == 0.0

    def test_negative_offset(self):
        samples = [(100.0, 100.001 - 7.5, 100.002)]
        assert clock_handshake_offset(samples) == pytest.approx(-7.5)


def make_tpu_backend():
    from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend

    cfg = ConfigManager(config={
        "name": "t", "public": False, "serverKey": "00" * 32,
        "modelName": "tiny:test", "apiProvider": "tpu_native",
        "tpu": {"model_preset": "tiny", "max_batch_size": 2,
                "max_seq_len": 64, "prefill_buckets": [16]},
    })
    return TpuNativeBackend(cfg)


class TestStageOffsetReconciliation:
    """Regression for the tpu_native negative-span clamp: host stamps are
    now mapped through the MEASURED clock offset before differencing."""

    def test_offsets_applied_not_clamped(self):
        be = make_tpu_backend()
        # Host clock runs 5 s BEHIND the provider: every host stamp is
        # 5 s smaller than the provider stamps bracketing it, so naive
        # differencing makes pipe_in ≈ -5 s — the case the old code
        # clamped to zero (hiding the whole leg).
        be._clock_offset = -5.0
        t_recv = 1000.0
        t_submit = 1000.010
        host = -5.0  # host clock = provider clock + offset
        stamps = {"recv": round(1000.020 + host, 4),
                  "picked": round(1000.050 + host, 4),
                  "first": round(1000.200 + host, 4),
                  "out": round(1000.210 + host, 4)}
        be._observe_stages(t_recv, t_submit, stamps)
        get = lambda n: be.stage_hists[n].to_dict()  # noqa: E731
        assert get("submit")["mean"] == pytest.approx(0.010, abs=1e-6)
        # The leg that used to clamp: recv lands AFTER submit once the
        # offset is applied.
        assert get("pipe_in")["mean"] == pytest.approx(0.010, abs=1e-6)
        assert get("queue")["mean"] == pytest.approx(0.030, abs=1e-6)
        assert get("prefill")["mean"] == pytest.approx(0.150, abs=1e-6)
        assert get("emit")["mean"] == pytest.approx(0.010, abs=1e-6)
        # relay = real now - reconciled out: meaningless against these
        # fabricated stamps; just assert it was recorded (not dropped).
        assert get("relay")["count"] == 1

    def test_true_negative_span_not_hidden(self):
        """A genuinely mis-ordered stamp pair must surface as a negative
        observation — the clamp used to silently zero it."""
        be = make_tpu_backend()
        be._clock_offset = 0.0
        stamps = {"recv": 999.0, "picked": 999.0, "first": 999.0,
                  "out": 999.0}
        be._observe_stages(1000.0, 1000.5, stamps)
        d = be.stage_hists["pipe_in"].to_dict()
        assert d["count"] == 1
        assert d["mean"] == pytest.approx(-1.5)


FAKE_HOST = r'''
import json, sys, time
SKEW = float(sys.argv[1])

def mono():
    return time.monotonic() + SKEW

def write(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()

write({"op": "ready", "model": "fake"})
for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    msg = json.loads(line)
    op = msg.get("op")
    if op == "clock":
        write({"op": "clock", "t0": msg.get("t0"), "t": mono()})
    elif op == "submit":
        rid = msg["id"]
        t = mono()
        write({"op": "event", "id": rid, "text": "hi", "tokens": 1,
               "tokens_new": 1, "ttft_s": 0.001,
               "t": {"recv": round(t, 4), "picked": round(t + 0.001, 4),
                     "first": round(t + 0.002, 4),
                     "out": round(t + 0.003, 4)}})
        write({"op": "event", "id": rid, "text": "", "tokens": 2,
               "tokens_new": 0, "done": True, "finish_reason": "stop"})
    elif op == "trace":
        t = mono()
        write({"op": "trace", "clock": t, "components": [
            {"name": "host", "clock_offset_s": 0.0, "counters": [],
             "spans": [{"name": "host_submit", "start": t - 0.5,
                        "duration_s": 0.001, "request_id": "r1",
                        "trace_id": "tid-1"}]},
            {"name": "scheduler", "clock_offset_s": 0.0,
             "counters": [{"t": t - 0.4, "name": "occupancy", "value": 1}],
             "spans": [{"name": "prefill", "start": t - 0.4,
                        "duration_s": 0.1, "request_id": "r1",
                        "trace_id": "tid-1"}]}]})
    elif op == "shutdown":
        break
'''


class TestFakeHostPipe:
    """Process-isolation protocol against a scripted host whose clock is
    deliberately skewed: the startup handshake must MEASURE the skew, the
    per-stage attribution must reconcile through it (no clamping), and
    trace_components must stamp it onto the host/scheduler components."""

    SKEW = -5.0  # host monotonic runs 5 s behind the provider's

    @pytest.fixture()
    def backend(self, tmp_path, monkeypatch):
        script = tmp_path / "fake_host.py"
        script.write_text(FAKE_HOST)
        real_exec = asyncio.create_subprocess_exec

        async def fake_exec(*_args, **kw):
            return await real_exec(sys.executable, str(script),
                                   str(self.SKEW), **kw)

        monkeypatch.setattr(asyncio, "create_subprocess_exec", fake_exec)
        return make_tpu_backend()

    def test_handshake_stages_and_trace(self, backend):
        from symmetry_tpu.provider.backends.base import InferenceRequest

        async def main():
            await backend.start()
            # 1. The handshake measured the scripted skew (pipe RTT on
            # loopback bounds the error well under 50 ms).
            assert backend._clock_offset == pytest.approx(self.SKEW,
                                                          abs=0.05)
            # 2. Stream one request: the first event's host stamps are
            # ~5 s "in the past"; unreconciled, pipe_in/queue/prefill
            # would be hugely negative (old code: clamped to 0).
            chunks = []
            async for ch in backend.stream(InferenceRequest(
                    messages=[{"role": "user", "content": "x"}],
                    max_tokens=4, trace_id="tid-1")):
                chunks.append(ch)
            assert any(ch.done for ch in chunks)
            for stage in ("pipe_in", "queue", "prefill", "emit"):
                d = backend.stage_hists[stage].to_dict()
                assert d["count"] == 1
                # Reconciled: small positive (scripted micro-gaps plus
                # handshake residual), nowhere near -SKEW or a clamp.
                assert -0.1 < d["mean"] < 1.0, (stage, d)
            # 3. trace_components applies the measured offset to every
            # host-side component, so the merged export reconciles.
            comps = await backend.trace_components()
            names = {c["name"] for c in comps}
            assert names == {"host", "scheduler"}
            for c in comps:
                assert c["clock_offset_s"] == pytest.approx(self.SKEW,
                                                            abs=0.05)
            perfetto = export_perfetto(comps)
            xs = [e for e in perfetto["traceEvents"] if e["ph"] == "X"]
            assert xs and all(e["ts"] >= 0 for e in xs)
            assert {e["args"]["trace_id"] for e in xs} == {"tid-1"}
            await backend.stop()

        asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(main(), 60))


class TestPerfettoExport:
    def test_schema_and_reconciliation(self):
        # Two components, the second's clock 10 s ahead: a span that
        # STARTED LATER in real time but carries a bigger raw stamp must
        # still order correctly after reconciliation.
        provider = {"name": "provider", "clock_offset_s": 0.0,
                    "counters": [],
                    "spans": [{"name": "inference", "start": 100.0,
                               "duration_s": 1.0, "request_id": "r1",
                               "trace_id": "t1"}]}
        host = {"name": "host", "clock_offset_s": 10.0,
                "counters": [{"t": 110.3, "name": "occupancy", "value": 2}],
                "spans": [{"name": "prefill", "start": 110.2,
                           "duration_s": 0.5, "request_id": "r1",
                           "trace_id": "t1"}]}
        out = export_perfetto([provider, host])
        assert out["displayTimeUnit"] == "ms"
        events = out["traceEvents"]
        procs = {e["args"]["name"]: e["pid"] for e in events
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert set(procs) == {"provider", "host"}
        xs = {e["name"]: e for e in events if e["ph"] == "X"}
        assert xs["inference"]["ts"] == 0.0          # the earliest stamp
        assert xs["inference"]["dur"] == 1_000_000.0
        assert xs["prefill"]["ts"] == pytest.approx(200_000.0)  # +0.2 s
        assert xs["prefill"]["pid"] == procs["host"]
        cs = [e for e in events if e["ph"] == "C"]
        assert cs[0]["args"] == {"occupancy": 2}
        assert cs[0]["ts"] == pytest.approx(300_000.0)
        # every ts non-negative on the reconciled clock
        assert all(e["ts"] >= 0 for e in events if e["ph"] in "XC")

    def test_thread_rows_per_request(self):
        comp = {"name": "c", "clock_offset_s": 0.0, "counters": [],
                "spans": [
                    {"name": "a", "start": 1.0, "duration_s": 0.1,
                     "request_id": "r1", "trace_id": ""},
                    {"name": "b", "start": 1.2, "duration_s": 0.1,
                     "request_id": "r2", "trace_id": ""},
                    {"name": "c", "start": 1.4, "duration_s": 0.1,
                     "request_id": "r1", "trace_id": ""}]}
        events = export_perfetto([comp])["traceEvents"]
        xs = [e for e in events if e["ph"] == "X"]
        by_name = {e["name"]: e["tid"] for e in xs}
        assert by_name["a"] == by_name["c"] != by_name["b"]
        thread_names = {e["args"]["name"] for e in events
                        if e["ph"] == "M" and e["name"] == "thread_name"}
        assert thread_names == {"r1", "r2"}

    def test_empty(self):
        out = export_perfetto([])
        assert out["traceEvents"] == []
        assert json.loads(json.dumps(out)) == out


class TestFlightRecorder:
    def comps(self, now):
        return [{"name": "provider", "clock_offset_s": 0.0, "counters": [],
                 "spans": [
                     {"name": "old", "start": now - 120.0,
                      "duration_s": 0.1, "request_id": "", "trace_id": ""},
                     {"name": "recent", "start": now - 2.0,
                      "duration_s": 0.5, "request_id": "r", "trace_id": "t"},
                 ]}]

    def test_dump_is_loadable_and_windowed(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), window_s=30.0)
        now = time.monotonic()
        path = fr.dump("slo", self.comps(now), stats={"requests": 3},
                       now=now)
        with open(path) as fh:
            payload = json.load(fh)
        assert payload["reason"] == "slo"
        assert payload["stats"] == {"requests": 3}
        names = [e["name"] for e in payload["trace"]["traceEvents"]
                 if e["ph"] == "X"]
        assert names == ["recent"]  # the 2-minute-old span fell outside

    def test_rate_limit(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), min_interval_s=3600.0)
        assert fr.should_dump()
        assert not fr.should_dump()  # the first claim holds the slot

    def test_skewed_component_windowing(self, tmp_path):
        # A host-clock span 5 s in the "future" raw but recent reconciled
        # must survive the window filter (and vice versa).
        fr = FlightRecorder(str(tmp_path), window_s=10.0)
        now = time.monotonic()
        comp = {"name": "host", "clock_offset_s": 5.0, "counters": [],
                "spans": [{"name": "recent", "start": now + 4.0,
                           "duration_s": 0.1, "request_id": "",
                           "trace_id": ""},       # reconciled: now - 1
                          {"name": "stale", "start": now - 55.0,
                           "duration_s": 0.1, "request_id": "",
                           "trace_id": ""}]}     # reconciled: now - 60
        path = fr.dump("sigusr2", [comp], now=now)
        with open(path) as fh:
            names = [e["name"] for e in
                     json.load(fh)["trace"]["traceEvents"]
                     if e["ph"] == "X"]
        assert names == ["recent"]


class SpanFakeEngine:
    """Minimal scheduler-facing engine (cf. test_scheduler_emit)."""

    def __init__(self):
        from symmetry_tpu.engine.tokenizer import ByteTokenizer

        self.max_slots = 4
        self.decode_block = 4
        self.slot_capacity = 4096
        self.tokenizer = ByteTokenizer()
        self.prefill_buckets = (16,)

    def bucket_for(self, n):
        return 16

    def prefill_and_insert(self, slot, ids, sampling):
        return ord("A")

    def prefill_and_insert_many(self, group):
        return [ord("A")] * len(group)

    def release_slot(self, slot):
        pass

    def slot_length(self, slot):
        return 0


class TestSchedulerSpans:
    def make(self):
        from symmetry_tpu.engine.scheduler import Scheduler

        batches = []
        return Scheduler(SpanFakeEngine(), emit_batch=batches.append)

    def submit_one(self, sched, rid="req-1", tid="trace-1"):
        from symmetry_tpu.engine.engine import SamplingParams
        from symmetry_tpu.engine.scheduler import GenRequest

        sched.submit(GenRequest(
            prompt_ids=list(b"hello"), sampling=SamplingParams(),
            max_new_tokens=64, emit=lambda ev: None, id=rid,
            trace_id=tid))

    def test_admission_spans_carry_trace_id(self):
        sched = self.make()
        self.submit_one(sched)
        sched._admit_new()
        sched._read_admissions()
        spans = {s["name"]: s for s in sched.tracer.export()}
        assert "prefill_dispatch" in spans
        for name in ("queue", "prefill"):
            assert spans[name]["request_id"] == "req-1"
            assert spans[name]["trace_id"] == "trace-1"
        assert spans["queue"]["start"] <= spans["prefill"]["start"]

    def test_block_spans_and_counters(self):
        import numpy as np

        sched = self.make()
        self.submit_one(sched)
        sched._admit_new()
        sched._read_admissions()
        toks = np.full((4, 4), ord("x"), dtype=np.int64)
        t_disp = time.monotonic() - 0.01
        sched._process_block(toks, dict(sched._slots),
                             dispatched_at=t_disp)
        spans = [s for s in sched.tracer.export()
                 if s["name"] == "decode_block"]
        assert len(spans) == 1
        assert spans[0]["start"] == t_disp
        assert spans[0]["steps"] == 4 and spans[0]["slots"] == 1
        counters = {c["name"] for c in sched.tracer.export_counters()}
        assert {"occupancy", "queue_depth"} <= counters

    def test_generate_span_on_finish(self):
        import numpy as np

        sched = self.make()
        self.submit_one(sched)
        sched._admit_new()
        sched._read_admissions()
        eos = sched.engine.tokenizer.EOS
        toks = np.full((4, 4), eos, dtype=np.int64)
        sched._process_block(toks, dict(sched._slots))
        gen = [s for s in sched.tracer.export() if s["name"] == "generate"]
        assert len(gen) == 1
        assert gen[0]["trace_id"] == "trace-1"
        assert gen[0]["finish"] == "stop"

    def test_disabled_tracer_records_nothing(self):
        import numpy as np

        sched = self.make()
        sched.tracer.enabled = False
        self.submit_one(sched)
        sched._admit_new()
        sched._read_admissions()
        toks = np.full((4, 4), ord("x"), dtype=np.int64)
        sched._process_block(toks, dict(sched._slots),
                             dispatched_at=time.monotonic())
        assert sched.tracer.export() == []
        assert sched.tracer.export_counters() == []
        assert sched.trace_export()["spans"] == []


class TestHostTraceOps:
    def test_clock_echo(self, capsys):
        host = EngineHost(config=None)
        t_before = time.monotonic()
        host._handle_clock({"op": "clock", "t0": 123.456})
        reply = json.loads(capsys.readouterr().out.strip())
        assert reply["op"] == "clock" and reply["t0"] == 123.456
        assert t_before <= reply["t"] <= time.monotonic()

    def test_trace_op_ships_host_and_scheduler_rings(self, capsys):
        host = EngineHost(config=None)
        host.tracer.record("host_submit", 1.0, 0.01, request_id="r",
                           trace_id="t")
        sched_tracer = Tracer()
        sched_tracer.record("prefill", 2.0, 0.1)
        host._scheduler = SimpleNamespace(
            trace_export=lambda: sched_tracer.component("scheduler"))
        host._handle_trace()
        frame = json.loads(capsys.readouterr().out.strip())
        assert frame["op"] == "trace"
        names = [c["name"] for c in frame["components"]]
        assert names == ["host", "scheduler"]
        assert frame["components"][0]["spans"][0]["trace_id"] == "t"
        assert frame["components"][1]["spans"][0]["name"] == "prefill"

    def test_submit_threads_trace_id(self, capsys):
        host = EngineHost(config=None)
        seen = []
        host._scheduler = SimpleNamespace(submit=seen.append)
        host._engine = SimpleNamespace(tokenizer=SimpleNamespace(
            apply_chat_template=lambda msgs: [1, 2, 3]))
        host._submit({"op": "submit", "id": "r9", "trace": "tid-9",
                      "messages": [{"role": "user", "content": "x"}],
                      "max_new": 8})
        assert len(seen) == 1
        assert seen[0].trace_id == "tid-9"
        spans = host.tracer.export()
        assert spans and spans[-1]["name"] == "host_submit"
        assert spans[-1]["trace_id"] == "tid-9"
        assert spans[-1]["request_id"] == "r9"


class TestJsonLogging:
    def test_json_records_carry_trace_context(self, capsys):
        from symmetry_tpu.utils.logging import log_context, logger

        logger.set_json_mode(True)
        try:
            with log_context(trace_id="tr-1", request_id="rq-1"):
                logger.info("hello", "world")
            logger.info("outside")
        finally:
            logger.set_json_mode(False)
        lines = [json.loads(line) for line in
                 capsys.readouterr().err.strip().splitlines()]
        assert lines[0]["msg"] == "hello world"
        assert lines[0]["level"] == "info"
        assert lines[0]["trace_id"] == "tr-1"
        assert lines[0]["request_id"] == "rq-1"
        assert "trace_id" not in lines[1]  # context does not leak

    def test_nested_context_overrides_and_restores(self, capsys):
        from symmetry_tpu.utils.logging import log_context, logger

        logger.set_json_mode(True)
        try:
            with log_context(trace_id="outer"):
                with log_context(trace_id="inner", request_id="r"):
                    logger.warning("deep")
                logger.warning("shallow")
        finally:
            logger.set_json_mode(False)
        lines = [json.loads(line) for line in
                 capsys.readouterr().err.strip().splitlines()]
        assert lines[0]["trace_id"] == "inner"
        assert lines[0]["request_id"] == "r"
        assert lines[1]["trace_id"] == "outer"
        assert "request_id" not in lines[1]


class TestEchoTraceE2E:
    """Full client → server → provider (echo backend) path on the memory
    transport: trace propagation, the `trace` wire op, the merged
    Perfetto export, and the flight-recorder SLO trigger. Skips where the
    crypto stack isn't installed (same dependency as every peer test)."""

    def run_flow(self, tmp_path, slo_e2e_s=None):
        pytest.importorskip("cryptography")
        from symmetry_tpu.client.client import SymmetryClient
        from symmetry_tpu.identity import Identity
        from symmetry_tpu.provider.provider import SymmetryProvider
        from symmetry_tpu.server.broker import SymmetryServer
        from symmetry_tpu.transport.memory import MemoryTransport

        async def main():
            hub = MemoryTransport()
            server_ident = Identity.from_name("obs-server")
            server = SymmetryServer(server_ident, hub, ping_interval_s=30.0)
            await server.start("mem://server")
            cfg = ConfigManager(config={
                "name": "obs-prov", "public": True,
                "serverKey": server_ident.public_hex,
                "modelName": "echo:obs", "apiProvider": "echo",
                "dataCollectionEnabled": False,
                "flightRecorder": {"dir": str(tmp_path / "flight"),
                                   "minIntervalS": 0.0,
                                   **({"sloE2eS": slo_e2e_s}
                                      if slo_e2e_s is not None else {})},
            })
            provider = SymmetryProvider(
                cfg, transport=hub, identity=Identity.from_name("obs-prov"),
                server_address="mem://server")
            await provider.start("mem://obs-prov")
            await provider.wait_registered()
            client = SymmetryClient(Identity.from_name("obs-cli"), hub)
            details = await client.request_provider(
                "mem://server", server_ident.public_key, "echo:obs")
            session = await client.connect(details)
            trace_id = new_trace_id()
            try:
                text = "".join([d async for d in session.chat(
                    [{"role": "user", "content": "one two three"}],
                    trace_id=trace_id)])
                assert text == "one two three"
                assert session.clock_offset is not None  # tMono handshake
                perfetto = await client.export_trace(session)
                # Let the SLO-triggered dump task (spawned, not awaited
                # by the stream) finish before teardown.
                for _ in range(100):
                    if list((tmp_path / "flight").glob("*.json")):
                        break
                    await asyncio.sleep(0.02)
            finally:
                await session.close()
                await provider.stop()
                await server.stop()
            return perfetto, trace_id

        return asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(main(), 120))

    def test_trace_round_trip_three_components(self, tmp_path):
        perfetto, trace_id = self.run_flow(tmp_path)
        events = perfetto["traceEvents"]
        comp_by_pid = {e["pid"]: e["args"]["name"] for e in events
                       if e["ph"] == "M" and e["name"] == "process_name"}
        span_comps = {comp_by_pid[e["pid"]] for e in events
                      if e["ph"] == "X"}
        assert {"client", "provider", "echo"} <= span_comps
        traced = {comp_by_pid[e["pid"]] for e in events
                  if e["ph"] == "X"
                  and e.get("args", {}).get("trace_id") == trace_id}
        assert {"client", "provider", "echo"} <= traced
        assert all(e["ts"] >= 0 for e in events if e["ph"] in "XC")
        # valid Chrome-trace JSON end to end
        assert json.loads(json.dumps(perfetto)) == perfetto

    def test_tpu_native_inproc_scheduler_on_timeline(self):
        """One request through the REAL engine (tiny model, inproc): the
        client's trace id must key scheduler spans (queue/prefill/
        generate) in the merged export — the engine side of the
        end-to-end acceptance path (the host hop is covered by
        TestFakeHostPipe with a skewed clock)."""
        pytest.importorskip("cryptography")
        from symmetry_tpu.client.client import SymmetryClient
        from symmetry_tpu.identity import Identity
        from symmetry_tpu.provider.provider import SymmetryProvider
        from symmetry_tpu.server.broker import SymmetryServer
        from symmetry_tpu.transport.memory import MemoryTransport

        async def main():
            hub = MemoryTransport()
            server_ident = Identity.from_name("obs-tpu-server")
            server = SymmetryServer(server_ident, hub, ping_interval_s=30.0)
            await server.start("mem://server")
            cfg = ConfigManager(config={
                "name": "obs-tpu-prov", "public": True,
                "serverKey": server_ident.public_hex,
                "modelName": "tiny:test", "apiProvider": "tpu_native",
                "dataCollectionEnabled": False,
                "flightRecorder": {"enabled": False},
                "tpu": {"model_preset": "tiny", "dtype": "float32",
                        "max_batch_size": 2, "max_seq_len": 128,
                        "prefill_buckets": [32],
                        "engine_isolation": "inproc"},
            })
            provider = SymmetryProvider(
                cfg, transport=hub,
                identity=Identity.from_name("obs-tpu-prov"),
                server_address="mem://server")
            await provider.start("mem://obs-tpu-prov")
            await provider.wait_registered()
            client = SymmetryClient(Identity.from_name("obs-tpu-cli"), hub)
            details = await client.request_provider(
                "mem://server", server_ident.public_key, "tiny:test")
            session = await client.connect(details)
            trace_id = new_trace_id()
            try:
                async for _ in session.chat(
                        [{"role": "user", "content": "hi"}],
                        max_tokens=8, trace_id=trace_id):
                    pass
                comps = await session.trace_components()
            finally:
                await session.close()
                await provider.stop()
                await server.stop()
            return comps, trace_id

        comps, trace_id = asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(main(), 300))
        by_name = {c["name"]: c for c in comps}
        assert {"client", "provider", "scheduler"} <= set(by_name)
        sched_spans = {s["name"] for s in by_name["scheduler"]["spans"]
                       if s.get("trace_id") == trace_id}
        assert {"queue", "prefill", "generate"} <= sched_spans
        events = export_perfetto(comps)["traceEvents"]
        assert all(e["ts"] >= 0 for e in events if e["ph"] in "XC")

    def test_flight_recorder_slo_trigger_dump_loads(self, tmp_path):
        # SLO of 0 s: the very first completed request breaches it.
        self.run_flow(tmp_path, slo_e2e_s=1e-9)
        dumps = list((tmp_path / "flight").glob("flight_*_slo.json"))
        assert dumps, "SLO breach produced no flight-recorder dump"
        with open(dumps[0]) as fh:
            payload = json.load(fh)
        assert payload["reason"] == "slo"
        assert payload["stats"].get("requests", 0) >= 1
        xs = [e for e in payload["trace"]["traceEvents"]
              if e["ph"] == "X"]
        assert xs, "dump carries no spans"
