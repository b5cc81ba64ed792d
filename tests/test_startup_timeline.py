"""The start-up timeline (PR 56): every process stamps its start-up as
`start.*` spans of its tracer, each begun on the stamp that ended the one
before, and freezes them into `startup.timeline`; the engine's warm-up is
one record a program it ran (`warmup_programs`), with what JAX traced,
lowered, compiled or fetched inside it. READY carries both, stats the
timeline and the warm-up's totals — whatever `tpu.tracing` says."""

import asyncio
import io
import json
import os
import sys

import pytest

from symmetry_tpu.engine import host as host_mod
from symmetry_tpu.identity import Identity
from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
from symmetry_tpu.provider.config import ConfigManager
from symmetry_tpu.provider.provider import SymmetryProvider
from symmetry_tpu.server.broker import SymmetryServer
from symmetry_tpu.transport.memory import MemoryTransport

FAKE_HOST = os.path.join(os.path.dirname(__file__), "fake_host.py")

HOST_SPANS = ["host.process", "host.config", "build.devices", "build.params",
              "build.state", "warmup", "host.scheduler", "ready"]
COUNTS = ("trace_s", "lower_s", "backend_s", "retrieval_s", "cache_hits",
          "cache_misses")

TPU = {"model_preset": "tiny", "max_batch_size": 4, "max_seq_len": 128,
       "prefill_buckets": [32, 64], "prefill_chunk": None}
CONFIGS = {
    "dense": {},
    "chunked": {"prefill_chunk": 32},
    "prefix-cache": {"prefill_chunk": 16, "prefix_cache_mb": 1},
    "dense-untraced": {"tracing": False},
}


def host_config(**tpu):
    return ConfigManager(config={
        "name": "timeline", "public": True, "serverKey": "00" * 32,
        "modelName": "tiny", "apiProvider": "tpu_native",
        "tpu": {**TPU, **tpu}})


@pytest.fixture(scope="module", params=list(CONFIGS))
def started(request):
    """One engine host on the CPU, started, asked for its stats and shut
    down: (the host, its READY frame, its stats reply)."""
    stdin, stdout = sys.stdin, sys.stdout
    sys.stdin = io.StringIO('{"op": "stats"}\n{"op": "shutdown"}\n')
    sys.stdout = out = io.StringIO()
    try:
        host = host_mod.EngineHost(host_config(**CONFIGS[request.param]))
        assert host.serve_forever() == 0
    finally:
        sys.stdin, sys.stdout = stdin, stdout
    frames = [json.loads(line) for line in out.getvalue().splitlines()]
    ready = next(f for f in frames if f["op"] == "ready")
    stats = next(f for f in frames if f["op"] == "stats")
    return host, ready, stats


def grid_of(engine):
    """The programs `warmup()` runs for this engine, in its order, worked
    out from the engine's shapes alone: (program, batch, bucket, waits)."""
    buckets = engine.prefill_buckets
    grid = [(batch, bucket) for bucket in buckets
            for batch in engine.prefill_batches_for(bucket)]
    served = [(b, k) for b, k in grid if b <= engine.max_slots]
    out = [("rng_resume", None, None, None)]
    out += [("derive_keys", b, None, None) for b in engine.PREFILL_BATCHES]
    out += [("decode_block", None, None, None)]
    for batch, bucket in served:
        out += [("prefill", batch, bucket, None),
                ("insert_all", batch, bucket, None)]
    for bucket in buckets:
        widest = max(b for b, k in served if k == bucket)
        out += [("peak.decode_block", None, bucket, None),
                ("peak.prefill", widest, bucket, None),
                ("sync", widest, bucket, "peak.prefill")]
    chunk = engine.prefill_chunk
    for bucket in (b for b in buckets if chunk is not None and b > chunk):
        out += [("chunk_step", 1, bucket, None),
                ("chunk_final", 1, bucket, None)]
    if engine.prefix_index is not None:
        out += [("prefix.write_blocks", None, b, None) for b in buckets]
        for batch, bucket in grid:
            out += [("prefix.extract_row", batch, bucket, None),
                    ("prefix.insert_from_blocks", batch, bucket, None),
                    ("prefix.suffix", batch, bucket, None),
                    ("sync", batch, bucket, "prefix.suffix")]
    return out


class TestEngineHost:
    def test_the_timelines_names_are_in_order_and_its_spans_share_stamps(
            self, started):
        _, ready, _ = started
        rows = ready["timeline"]
        assert [r[0] for r in rows] == HOST_SPANS
        assert all(r[3] is None for r in rows)         # one level
        for (_, t0, t1, _), (_, nxt, _, _) in zip(rows, rows[1:]):
            assert t0 <= t1
            assert t1 == nxt    # the same clock read: no gap to explain
        assert rows[-1][1] == rows[-1][2]              # `ready` is a stamp
        assert ready["origin"] in ("kernel", "package")

    def test_the_warm_up_record_lists_exactly_the_grid_that_ran(
            self, started):
        host, ready, _ = started
        records = ready["warmup_programs"]
        keys = [(r["program"], r["batch"], r["bucket"], r.get("waits"))
                for r in records]
        settle = [k for k in keys if k[0] == "settle"]
        assert keys[:len(keys) - len(settle)] == grid_of(host._engine)
        # the serving-shaped rounds behind it: one record a round
        assert 1 <= len(settle) <= 6 and keys[-len(settle):] == settle
        assert [r["round"] for r in records[-len(settle):]] == list(
            range(len(settle)))
        # one record a (program, batch, bucket): nothing is booked twice
        assert len(set(keys[:-len(settle)])) == len(keys) - len(settle)
        for r in records:
            assert set(COUNTS) <= set(r) and r["wall_s"] >= 0

    def test_the_records_lie_inside_warmup_and_tile_it(self, started):
        _, ready, _ = started
        spans = {r[0]: r for r in ready["timeline"]}
        records = ready["warmup_programs"]
        _, w0, w1, _ = spans["warmup"]
        assert records[0]["t0"] == w0   # begun where `build.state` ended
        for a, b in zip(records, records[1:]):
            assert a["t0"] + a["wall_s"] == pytest.approx(b["t0"], abs=1e-9)
        end = records[-1]["t0"] + records[-1]["wall_s"]
        assert w0 <= end <= w1
        assert w1 - end < 0.5           # what warm-up does behind its last

    def test_the_totals_are_the_sums_of_the_records(self, started):
        _, ready, _ = started
        records, warm = ready["warmup_programs"], ready["warmup"]

        def total(*keys):
            return sum(r[k] for r in records for k in keys)

        assert warm["programs"] == len(records)
        assert warm["wall_s"] == pytest.approx(total("wall_s"))
        assert warm["compile_s"] == pytest.approx(
            total("trace_s", "lower_s", "backend_s"))
        assert warm["compile_s"] > 0    # the watch was listening
        assert warm["retrieval_s"] == pytest.approx(total("retrieval_s"))
        assert warm["run_s"] == pytest.approx(sum(
            r["wall_s"] for r in records if r["program"] == "sync"))
        assert warm["cache_hits"] == total("cache_hits")
        assert warm["cache_misses"] == total("cache_misses")
        if ready["compile_cache"]:
            # every compile-or-fetch of the warm-up hit or missed it
            assert warm["cache_hits"] + warm["cache_misses"] > 0
        slowest = warm["slowest"]
        assert len(slowest) == 5
        assert slowest == sorted(records, key=lambda r: -r["wall_s"])[:5]

    def test_ready_and_stats_carry_the_same_block(self, started):
        host, ready, stats = started
        block = stats["startup"]
        for key in ("timeline", "origin", "warmup", "build_s", "warmup_s"):
            assert block[key] == ready[key]
        # the whole list rides READY only: a stats reply a second does
        # not grow by the grid
        assert "warmup_programs" not in block
        assert "warmup_programs" not in stats
        spans = {r[0]: r for r in block["timeline"]}
        assert block["warmup_s"] == round(
            spans["warmup"][2] - spans["warmup"][1], 1)
        assert block["build_s"] == round(
            spans["build.state"][2] - spans["build.devices"][1], 1)
        # the watch's counts at READY are the record's, plus the build's
        at_ready = stats["compile"]["at_ready"]
        assert at_ready["cache_misses"] >= block["warmup"]["cache_misses"]

    def test_the_block_is_there_whatever_tpu_tracing_says(self, started):
        host, ready, _ = started
        untraced = host._config.tpu.tracing is False
        assert host.tracer.enabled is not untraced
        # the start-up's spans were read out of the ring before it was
        # switched off: with tracing off it takes nothing newer
        assert [r[0] for r in ready["timeline"]] == HOST_SPANS
        held = len(host.tracer.export())
        with host.tracer.phase("host.pipe_flush", ring="pipe_flush"):
            pass
        assert (len(host.tracer.export()) == held) is untraced
        assert host.tracer.phase_s["start.warm"] == pytest.approx(
            ready["warmup"]["wall_s"])


# ----------------------------------------------------------- the provider

class FakeHostBackend(TpuNativeBackend):
    def _host_argv(self, cfg_path):
        return [sys.executable, FAKE_HOST, cfg_path]


def run(coro):
    return asyncio.new_event_loop().run_until_complete(
        asyncio.wait_for(coro, 60))


def provider_config(server_key_hex, public):
    return ConfigManager(config={
        "name": "timeline-prov", "public": public,
        "serverKey": server_key_hex, "modelName": "fake:timeline",
        "apiProvider": "tpu_native", "dataCollectionEnabled": False,
        "flightRecorder": {"enabled": False},
        "tpu": {"engine_isolation": "process", "max_batch_size": 4,
                "supervisor": {"enabled": False}}})


@pytest.mark.parametrize("public", [True, False])
def test_the_provider_reports_its_own_spans_over_a_host_that_has_none(
        public):
    """tests/fake_host.py's READY holds no timeline: the provider starts
    all the same and reports the spans it stamped itself."""
    async def main():
        hub = MemoryTransport()
        ident = Identity.from_name("timeline-server")
        server = SymmetryServer(ident, hub)
        await server.start("mem://server")
        cfg = provider_config(ident.public_hex, public)
        backend = FakeHostBackend(cfg)
        provider = SymmetryProvider(
            cfg, transport=hub, backend=backend,
            identity=Identity.from_name("timeline-prov"),
            server_address="mem://server")
        await provider.start("mem://timeline-prov")
        try:
            if public:
                await provider.wait_registered()
            stats = provider.stats()
            engine = await backend.engine_stats()
        finally:
            await provider.stop()
            await server.stop()
        return stats, engine, backend.warmup_programs

    stats, engine, programs = run(main())
    rows = stats["startup"]["timeline"]
    top = [r for r in rows if r[3] is None]
    want = ["provider.process", "provider.backend", "provider.listen",
            "provider.dht"] + (["provider.server", "registered"]
                               if public else [])
    assert [r[0] for r in top] == want
    for (_, t0, t1, _), (_, nxt, _, _) in zip(top, top[1:]):
        assert t0 <= t1 and t1 == nxt
    # the backend's spans are children of `provider.backend`, share their
    # stamps and lie inside it
    spans = {r[0]: r for r in rows}
    children = [r for r in rows if r[3] == "provider.backend"]
    assert [r[0] for r in children] == ["backend.spawn", "backend.ready",
                                        "backend.clock"]
    for (_, t0, t1, _), (_, nxt, _, _) in zip(children, children[1:]):
        assert t1 == nxt
    assert spans["provider.backend"][1] <= children[0][1]
    assert children[-1][2] <= spans["provider.backend"][2]
    assert stats["startup"]["origin"] in ("kernel", "package")
    # the fake host said nothing of its own start-up, and nothing broke
    assert "timeline" not in (engine.get("startup") or {})
    assert programs is None
