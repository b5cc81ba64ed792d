"""Fleet-telemetry layer (utils/metrics.py + its wiring).

Covers the PR-10 acceptance surface:

  - registry concurrency: multi-thread increments are EXACT (one lock,
    no lost updates — the same class of bug symlint C202 hunts);
  - exposition-format golden test: render_prometheus output is pinned
    byte-for-byte (a scrape consumer parses this text; drift is a
    silently-broken dashboard);
  - SLO burn-rate monitor: multiwindow semantics, rate limiting, and
    the deterministic fake-clock path driving a FlightRecorder dump;
  - wire-op round-trip: the HostOp.METRICS probe reply parses and
    merges tier-labeled through the backend;
  - disabled-mode overhead guard: a disabled registry costs one branch
    per call site — cheap enough that the echo path's handful of sites
    stays under 1% of a 1 ms chunk budget.
"""

from __future__ import annotations

import json
import threading

import pytest

from symmetry_tpu.utils.metrics import (
    METRICS,
    LATENCY_BUCKETS,
    MetricName,
    MetricsRegistry,
    MetricsServer,
    SloMonitor,
    histogram_quantile,
    parse_prometheus_text,
    render_prometheus,
)


# ------------------------------------------------------------- registry


class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        r = MetricsRegistry()
        c = r.counter("t_req_total", "requests")
        c.inc()
        c.inc(3)
        assert c.value() == 4
        g = r.gauge("t_depth", "depth")
        g.set(7)
        g.add(-2)
        assert g.value() == 5
        h = r.histogram("t_lat_seconds", "latency")
        h.observe(0.002)
        h.observe(3.0)
        snap = r.snapshot()
        fam = snap["families"]["t_lat_seconds"]
        (s,) = fam["series"]
        assert s["count"] == 2
        assert s["sum"] == pytest.approx(3.002)
        assert s["min"] == 0.002 and s["max"] == 3.0
        # cumulative buckets end at the total count under +Inf
        assert s["buckets"][-1] == ["+Inf", 2]

    def test_labels_partition_series(self):
        r = MetricsRegistry()
        c = r.counter("t_shed_total", "sheds", labels=("reason",))
        c.inc(reason="busy")
        c.inc(2, reason="expired")
        assert c.value(reason="busy") == 1
        assert c.value(reason="expired") == 2
        assert c.value(reason="nope") == 0

    def test_reregistration_is_idempotent_but_kind_pinned(self):
        r = MetricsRegistry()
        r.counter("t_x_total", "x")
        r.counter("t_x_total")  # same kind+labels: fine
        with pytest.raises(ValueError):
            r.gauge("t_x_total")
        with pytest.raises(ValueError):
            r.counter("t_x_total", labels=("k",))

    def test_unlabeled_counters_materialize_at_zero(self):
        # A registered family must be visible from the first scrape —
        # an empty counter is a statement, a missing one is a question.
        r = MetricsRegistry()
        r.counter("t_zero_total", "never incremented")
        text = render_prometheus([{"snapshot": r.snapshot(), "labels": {}}])
        assert "t_zero_total 0" in text

    def test_multithread_increment_exactness(self):
        r = MetricsRegistry()
        c = r.counter("t_mt_total", "hammered", labels=("k",))
        h = r.histogram("t_mt_seconds", "hammered")
        n, threads = 2000, 8

        def hammer(i: int) -> None:
            for _ in range(n):
                c.inc(k="a")
                c.inc(0.5, k=f"t{i}")
                h.observe(0.01)

        ts = [threading.Thread(target=hammer, args=(i,))
              for i in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value(k="a") == n * threads
        for i in range(threads):
            assert c.value(k=f"t{i}") == n * 0.5
        snap = r.snapshot()
        (s,) = snap["families"]["t_mt_seconds"]["series"]
        assert s["count"] == n * threads
        assert s["buckets"][-1][1] == n * threads

    def test_disabled_mode_is_inert_and_cheap(self, loop_ratio):
        """The one-branch contract: a disabled registry's handles count
        nothing, create no series and take no lock, and a site costs a
        small multiple of an empty call (2-3x here), whatever the
        machine's load."""
        r = MetricsRegistry(enabled=False)
        c = r.counter("t_off_total", "off")
        h = r.histogram("t_off_seconds", "off")

        class Untouched:
            def __enter__(self):
                raise AssertionError("a disabled site took the lock")

            def __exit__(self, *exc):
                return None

        lock, r._lock = r._lock, Untouched()

        def site():
            c.inc()
            h.observe(0.1)

        ratio = loop_ratio(site, 50_000)
        r._lock = lock
        assert c.value() == 0 and h._fam.series == {}  # no record allocated
        assert ratio < 50, f"two disabled sites cost {ratio:.1f}x an empty call"

    def test_histogram_ring_is_bounded_time_series(self):
        r = MetricsRegistry()
        h = r.histogram("t_ring_seconds", "ring")
        for i in range(1000):
            h.observe(i * 1e-4)
        (s,) = r.snapshot()["families"]["t_ring_seconds"]["series"]
        from symmetry_tpu.utils.metrics import RING_CAPACITY

        assert len(s["recent"]) == RING_CAPACITY
        # compact drops the ring (the wire shape)
        (sc,) = r.snapshot(compact=True)[
            "families"]["t_ring_seconds"]["series"]
        assert "recent" not in sc
        assert sc["count"] == 1000


# ----------------------------------------------------------- exposition


GOLDEN = """\
# HELP g_req_total requests accepted
# TYPE g_req_total counter
g_req_total 3
# HELP g_shed_total sheds by reason
# TYPE g_shed_total counter
g_shed_total{reason="busy",tier="decode"} 2
# HELP g_lat_seconds latency
# TYPE g_lat_seconds histogram
g_lat_seconds_bucket{le="0.5"} 1
g_lat_seconds_bucket{le="5.0"} 2
g_lat_seconds_bucket{le="+Inf"} 2
g_lat_seconds_sum 1.1
g_lat_seconds_count 2
"""


class TestExposition:
    def test_render_golden(self):
        r = MetricsRegistry()
        r.counter("g_req_total", "requests accepted").inc(3)
        r.counter("g_shed_total", "sheds by reason",
                  labels=("reason", "tier")).inc(
                      2, reason="busy", tier="decode")
        h = r.histogram("g_lat_seconds", "latency", buckets=(0.5, 5.0))
        h.observe(0.1)
        h.observe(1.0)
        text = render_prometheus([{"snapshot": r.snapshot(), "labels": {}}])
        assert text == GOLDEN

    def test_extra_labels_stamp_every_series(self):
        r = MetricsRegistry()
        r.counter("g_x_total", "x").inc(1)
        text = render_prometheus(
            [{"snapshot": r.snapshot(), "labels": {"tier": "prefill"}}])
        assert 'g_x_total{tier="prefill"} 1' in text

    def test_parse_inverts_render(self):
        r = MetricsRegistry()
        r.counter("g_a_total", "a").inc(7)
        h = r.histogram("g_b_seconds", "b")
        h.observe(0.3)
        fams = parse_prometheus_text(render_prometheus(
            [{"snapshot": r.snapshot(), "labels": {"tier": "decode"}}]))
        assert fams["g_a_total"]["kind"] == "counter"
        (s,) = [s for s in fams["g_a_total"]["series"] if not s["suffix"]]
        assert s["value"] == 7 and s["labels"]["tier"] == "decode"
        count = [s for s in fams["g_b_seconds"]["series"]
                 if s["suffix"] == "_count"]
        assert count and count[0]["value"] == 1

    def test_label_escaping(self):
        r = MetricsRegistry()
        r.counter("g_esc_total", "esc", labels=("k",)).inc(
            k='we"ird\\nam\ne')
        text = render_prometheus([{"snapshot": r.snapshot(), "labels": {}}])
        assert '\\"' in text and "\\\\" in text and "\\n" in text
        assert "\nwe" not in text  # the raw newline never leaks

    def test_histogram_quantile_interpolates(self):
        # 100 samples uniform in le=1.0 bucket, none beyond.
        buckets = [(0.5, 0.0), (1.0, 100.0), ("+Inf", 100.0)]
        q50 = histogram_quantile(buckets, 0.50)
        assert 0.5 < q50 <= 1.0
        assert histogram_quantile([], 0.5) is None
        assert histogram_quantile([(0.5, 0.0), ("+Inf", 0.0)], 0.5) is None

    def test_http_server_serves_and_404s(self):
        import urllib.error
        import urllib.request

        srv = MetricsServer(lambda: "g_up 1\n", port=0)
        srv.start()
        try:
            url = f"http://127.0.0.1:{srv.port}"
            body = urllib.request.urlopen(f"{url}/metrics").read()
            assert body == b"g_up 1\n"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{url}/nope")
            assert ei.value.code == 404
        finally:
            srv.stop()


# ---------------------------------------------------------- SLO monitor


class FakeClock:
    def __init__(self) -> None:
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


def make_monitor(clock, breaches, **over):
    cfg = {"ttft_s": 1.0, "objective": 0.99, "fast_window_s": 60.0,
           "slow_window_s": 600.0, "burn_threshold": 10.0,
           "min_interval_s": 0.0, **over}
    return SloMonitor(cfg, clock=clock, on_breach=breaches.append)


class TestSloMonitor:
    def test_good_events_never_breach(self):
        clock, breaches = FakeClock(), []
        m = make_monitor(clock, breaches)
        for _ in range(100):
            clock.t += 0.5
            assert m.observe("ttft", 0.2) is None
        assert breaches == []

    def test_sustained_burn_breaches_both_windows(self):
        clock, breaches = FakeClock(), []
        m = make_monitor(clock, breaches)
        for _ in range(20):
            clock.t += 1.0
            m.observe("ttft", 5.0)  # every event over target
        assert breaches, "sustained 100x burn never breached"
        ev = breaches[0]
        assert ev["slo"] == "ttft"
        assert ev["burn_fast"] >= 10 and ev["burn_slow"] >= 10

    def test_fast_burst_alone_does_not_breach_slow_window(self):
        clock, breaches = FakeClock(), []
        # Slow window holds a long good history; a short burst tips the
        # fast window but not the slow one — the multiwindow guard.
        m = make_monitor(clock, breaches, fast_window_s=10.0,
                         slow_window_s=600.0, burn_threshold=50.0)
        for _ in range(500):
            clock.t += 1.0
            m.observe("ttft", 0.1)  # good history
        for _ in range(5):
            clock.t += 1.0
            m.observe("ttft", 9.0)  # bad burst
        assert breaches == []

    def test_cold_start_single_bad_request_does_not_page(self):
        # Right after startup both windows hold the SAME few events; the
        # min_samples floor keeps one slow cold-start request (100x
        # burn over a one-sample window) from paging a healthy fleet.
        clock, breaches = FakeClock(), []
        m = make_monitor(clock, breaches)  # default min_samples=12
        clock.t += 1.0
        assert m.observe("ttft", 30.0) is None
        assert breaches == []
        # …and a floor of 1 restores the old behavior for tests/smokes
        m1 = make_monitor(clock, breaches, min_samples=1)
        clock.t += 1.0
        assert m1.observe("ttft", 30.0) is not None

    def test_rate_limit_between_breaches(self):
        clock, breaches = FakeClock(), []
        m = make_monitor(clock, breaches, min_interval_s=300.0)
        for _ in range(50):
            clock.t += 1.0
            m.observe("ttft", 5.0)
        assert len(breaches) == 1  # 50 burning observes, one page
        clock.t += 301.0
        m.observe("ttft", 5.0)
        assert len(breaches) == 2

    def test_burn_rate_accessor_feeds_pool_gauges(self):
        """SloMonitor.burn_rate(): the live fast-window burn the
        tpu_native pool heartbeat feeds into PoolRouter.update_gauges —
        0 while healthy, > 0 under burn, decaying as the window prunes,
        and 0 with no SLO configured."""
        clock, breaches = FakeClock(), []
        m = make_monitor(clock, breaches)
        assert m.burn_rate() == 0.0
        for _ in range(10):
            clock.t += 1.0
            m.observe("ttft", 5.0)  # every event over target
        burn = m.burn_rate()
        assert burn >= 10.0
        # the router consumes it through update_gauges and the member's
        # placement score reflects it
        from symmetry_tpu.engine.disagg.pool import PoolRouter

        router = PoolRouter()
        router.add_member("d0", "decode")
        router.mark_healthy("d0")
        router.update_gauges("d0", queue_depth=0, burn_rate=burn)
        (member,) = router.members("decode")
        assert member.burn_rate == pytest.approx(burn)
        # window prune: far in the future the burn decays to zero
        clock.t += 10_000.0
        assert m.burn_rate() == 0.0
        assert SloMonitor(None, clock=clock).burn_rate() == 0.0

    def test_unknown_slo_and_disabled_config(self):
        clock, breaches = FakeClock(), []
        m = make_monitor(clock, breaches)
        assert m.observe("nope", 9.0) is None
        off = SloMonitor(None, clock=clock)
        assert not off.enabled
        assert off.observe("ttft", 9.0) is None

    def test_burn_gauges_exported(self):
        clock, breaches = FakeClock(), []
        m = make_monitor(clock, breaches)
        clock.t += 1.0
        m.observe("ttft", 5.0)
        g = METRICS.gauge(MetricName.SLO_BURN_RATE,
                          labels=("slo", "window"))
        assert g.value(slo="ttft", window="fast") > 0

    def test_breach_drives_flight_recorder_deterministically(self, tmp_path):
        """The acceptance-criteria chain: fake clock → burn → breach →
        FlightRecorder.dump, no wall-clock sleeps anywhere."""
        from symmetry_tpu.utils.trace import FlightRecorder

        clock, dumps = FakeClock(), []
        fr = FlightRecorder(str(tmp_path), min_interval_s=0.0)

        def on_breach(event):
            dumps.append(fr.dump(f"slo_burn_{event['slo']}", [],
                                 stats={"burn": event["burn_fast"]}))

        m = SloMonitor({"ttft_s": 0.5, "objective": 0.99,
                        "fast_window_s": 60.0, "slow_window_s": 600.0,
                        "burn_threshold": 10.0, "min_interval_s": 0.0},
                       clock=clock, on_breach=on_breach)
        for _ in range(20):
            clock.t += 1.0
            m.observe("ttft", 2.0)
        assert dumps, "breach never dumped"
        with open(dumps[0], encoding="utf-8") as fh:
            payload = json.load(fh)
        assert payload["reason"] == "slo_burn_ttft"
        assert payload["stats"]["burn"] >= 10


# ------------------------------------------------------ wire round-trip


class TestMetricsWireOp:
    def test_host_metrics_reply_parses(self, capsys):
        from symmetry_tpu.engine.host import EngineHost
        from symmetry_tpu.protocol.keys import HostOp

        host = EngineHost(config=None)
        host._m_pipe_bytes.inc(0)  # ensure at least the host families exist
        host._handle_metrics()
        frame = json.loads(capsys.readouterr().out.strip())
        assert frame["op"] == HostOp.METRICS
        assert frame["role"] == "unified"
        assert MetricName.HOST_PIPE_WRITES in frame["families"]
        # the reply itself was one pipe write — counted
        fam = frame["families"][MetricName.HOST_PIPE_WRITES]
        assert not fam["series"] or fam["series"][0]["value"] >= 0

    def test_backend_merge_is_tier_labeled(self):
        import asyncio

        from symmetry_tpu.provider.backends.tpu_native import (
            TpuNativeBackend)
        from symmetry_tpu.provider.config import ConfigManager

        cfg = ConfigManager(config={
            "name": "t", "public": False, "serverKey": "00" * 32,
            "modelName": "m", "apiProvider": "tpu_native",
            "tpu": {"role": "disagg"}})
        be = TpuNativeBackend(cfg)
        decode_snap = {"op": "metrics", "role": "decode", "t_mono": 1.0,
                       "enabled": True, "families": {"f": {
                           "kind": "counter", "help": "", "labels": [],
                           "series": [{"labels": {}, "value": 2}]}}}
        prefill_snap = {**decode_snap, "role": "prefill"}

        async def probe_decode(timeout=10.0):
            return dict(decode_snap)

        async def probe_prefill(timeout=10.0):
            return dict(prefill_snap)

        be._probe_host_metrics = probe_decode
        be._probe_prefill_metrics = probe_prefill
        be._proc = type("P", (), {"returncode": None})()
        be._prefill_proc = type("P", (), {"returncode": None})()
        snaps = asyncio.new_event_loop().run_until_complete(
            be.metrics_snapshots())
        tiers = [s["labels"]["tier"] for s in snaps]
        assert tiers == ["decode", "prefill"]
        assert all("op" not in s["snapshot"] for s in snaps)
        # the merged exposition carries the tier labels through
        text = render_prometheus(snaps)
        assert 'f{tier="decode"} 2' in text
        assert 'f{tier="prefill"} 2' in text


# ----------------------------------------------------- structured logs


class TestLoggingFields:
    def test_json_records_carry_t_mono_and_component(self, capsys):
        from symmetry_tpu.utils.logging import (log_context, logger,
                                                set_component)

        logger.set_json_mode(True)
        try:
            set_component("testproc")
            with log_context(trace_id="tr", component="slo"):
                logger.warning("burn")
            logger.info("plain")
        finally:
            logger.set_json_mode(False)
            set_component("")
        lines = [json.loads(line) for line in
                 capsys.readouterr().err.strip().splitlines()]
        assert lines[0]["component"] == "slo"       # context overrides
        assert lines[0]["trace_id"] == "tr"
        assert isinstance(lines[0]["t_mono"], float)
        assert lines[1]["component"] == "testproc"  # process default
        assert lines[0]["t_mono"] <= lines[1]["t_mono"]


# --------------------------------------------------------------- symtop


class TestSymtop:
    def test_rows_and_table_from_snapshots(self):
        import tools.symtop as symtop

        r = MetricsRegistry()
        r.counter(MetricName.PROVIDER_TOKENS_OUT, "t").inc(500)
        r.gauge(MetricName.PROVIDER_UPTIME, "u").set(10.0)
        r.gauge(MetricName.PROVIDER_IN_FLIGHT, "i").set(3)
        r.histogram(MetricName.PROVIDER_TTFT, "h",
                    buckets=LATENCY_BUCKETS).observe(0.2)
        sched = MetricsRegistry()
        sched.gauge(MetricName.SCHED_OCCUPANCY, "o").set(5)
        sched.gauge(MetricName.SCHED_QUEUE_DEPTH, "q").set(2)
        sched.histogram(MetricName.SCHED_TTFT, "t",
                        buckets=LATENCY_BUCKETS).observe(4.0)
        fams = symtop.families_from_snapshots([
            {"snapshot": r.snapshot(compact=True), "labels": {}},
            {"snapshot": sched.snapshot(compact=True),
             "labels": {"tier": "decode"}},
        ])
        rows = symtop.build_rows("prov-a", fams, None, now=0.0)
        assert rows[0]["tok_s"] == pytest.approx(50.0)
        assert rows[0]["in_flight"] == 3
        assert rows[0]["ttft_p50"] is not None
        assert rows[1]["tier"] == "decode"
        assert rows[1]["occupancy"] == 5 and rows[1]["queue"] == 2
        # tier TTFT is the ENGINE-side enqueue→first-token latency —
        # queue wait shows under overload, unlike dispatch wall
        assert rows[1]["ttft_p99"] == pytest.approx(4.0, abs=2.0)
        rows[0].pop("_sample", None)
        table = symtop.render_table(rows)
        assert "prov-a" in table and "decode" in table

    def test_depth_column(self):
        """Tier sub-rows carry the live pipeline depth, readable off the
        live table; the dispatch-gap column left with its gauge."""
        import tools.symtop as symtop

        r = MetricsRegistry()
        r.counter(MetricName.PROVIDER_TOKENS_OUT, "t").inc(100)
        r.gauge(MetricName.PROVIDER_UPTIME, "u").set(10.0)
        sched = MetricsRegistry()
        sched.gauge(MetricName.SCHED_OCCUPANCY, "o").set(2)
        sched.gauge(MetricName.SCHED_PIPELINE_DEPTH, "d").set(2)
        fams = symtop.families_from_snapshots([
            {"snapshot": r.snapshot(compact=True), "labels": {}},
            {"snapshot": sched.snapshot(compact=True),
             "labels": {"tier": "decode"}},
        ])
        rows = symtop.build_rows("prov-a", fams, None, now=0.0)
        assert rows[0].get("depth") is None     # provider row: engine-only
        tier = rows[1]
        assert "gap" not in tier
        assert tier["depth"] == 2
        rows[0].pop("_sample", None)
        table = symtop.render_table(rows)
        header = table.splitlines()[0]
        assert "DEPTH" in header and "GAP%" not in header

    def test_rate_from_previous_sample(self):
        import tools.symtop as symtop

        r = MetricsRegistry()
        r.counter(MetricName.PROVIDER_TOKENS_OUT, "t").inc(1000)
        r.counter(MetricName.PROVIDER_SHEDS, "s",
                  labels=("reason",)).inc(30, reason="busy")
        fams = symtop.families_from_snapshots(
            [{"snapshot": r.snapshot(compact=True), "labels": {}}])
        rows = symtop.build_rows(
            "p", fams, {"t": 0.0, "tok": 800.0, "shed": 20.0}, now=2.0)
        assert rows[0]["tok_s"] == pytest.approx(100.0)
        # shed is a RATE between polls, not the lifetime total
        assert rows[0]["shed"] == pytest.approx(5.0)

    def test_target_and_scale_columns(self):
        """Autoscaled pools surface TARGET (live MxN vs the
        controller's desired MxN) and SCALE (booked decisions/minute)
        on the provider row; non-autoscaled providers show dashes."""
        import tools.symtop as symtop

        r = MetricsRegistry()
        r.counter(MetricName.PROVIDER_TOKENS_OUT, "t").inc(100)
        r.gauge(MetricName.PROVIDER_UPTIME, "u").set(10.0)
        tgt = r.gauge(MetricName.AUTOSCALE_TARGET, "tm",
                      labels=("tier",))
        tgt.set(2, tier="prefill")
        tgt.set(1, tier="decode")
        r.counter(MetricName.AUTOSCALE_DECISIONS, "d",
                  labels=("action", "tier")).inc(
                      3, action="spawn", tier="prefill")
        st = r.gauge(MetricName.POOL_MEMBER_STATE, "s",
                     labels=("tier", "node"))
        st.set(1, tier="prefill", node="prefill-0")  # healthy
        st.set(1, tier="decode", node="decode-0")
        fams = symtop.families_from_snapshots(
            [{"snapshot": r.snapshot(compact=True), "labels": {}}])
        rows = symtop.build_rows("p", fams, None, now=0.0)
        # live 1x1 still converging toward the desired 2x1
        assert rows[0]["target"] == "1x1>2x1"
        assert rows[0]["scale"] == 3  # first poll: lifetime total
        rows2 = symtop.build_rows(
            "p", fams, {"t": 0.0, "tok": 0.0, "shed": 0.0, "dec": 1.0},
            now=30.0)
        assert rows2[0]["scale"] == pytest.approx(4.0)  # 2 in 30s /min
        rows[0].pop("_sample", None)
        table = symtop.render_table(rows)
        header = table.splitlines()[0]
        assert "TARGET" in header and "SCALE" in header
        assert "1x1>2x1" in table
        # steady state collapses to one MxN; no autoscaler → dashes
        st.set(1, tier="prefill", node="prefill-1")
        fams = symtop.families_from_snapshots(
            [{"snapshot": r.snapshot(compact=True), "labels": {}}])
        assert symtop.build_rows("p", fams, None,
                                 now=0.0)[0]["target"] == "2x1"
        bare = MetricsRegistry()
        bare.counter(MetricName.PROVIDER_TOKENS_OUT, "t").inc(1)
        fams = symtop.families_from_snapshots(
            [{"snapshot": bare.snapshot(compact=True), "labels": {}}])
        row = symtop.build_rows("p", fams, None, now=0.0)[0]
        assert row["target"] is None and row["scale"] is None


# ---------------------------------------- resume / pool family exposition


RESUME_POOL_GOLDEN = """\
# HELP sym_resume_requests_total resumes handled
# TYPE sym_resume_requests_total counter
sym_resume_requests_total{outcome="resumed"} 3
sym_resume_requests_total{outcome="refused"} 1
# HELP sym_resume_wasted_tokens_total overlap tokens dedup dropped
# TYPE sym_resume_wasted_tokens_total counter
sym_resume_wasted_tokens_total 17
# HELP sym_resume_reused_tokens_total radix tokens resumes reused
# TYPE sym_resume_reused_tokens_total counter
sym_resume_reused_tokens_total{tier="decode"} 96
# HELP sym_provider_flight_dumps_total flight-recorder dumps written
# TYPE sym_provider_flight_dumps_total counter
sym_provider_flight_dumps_total{reason="slo_burn_ttft"} 2
# HELP sym_pool_placements_total lifetime placements
# TYPE sym_pool_placements_total counter
sym_pool_placements_total{node="p0",tier="prefill"} 5
sym_pool_placements_total{node="p1",tier="prefill"} 3
# HELP sym_pool_member_state membership state code
# TYPE sym_pool_member_state gauge
sym_pool_member_state{node="p0",tier="prefill"} 1
sym_pool_member_state{node="p1",tier="prefill"} 3
"""


class TestResumePoolExposition:
    """PR-15 satellite: the PR-11/14 families symtop now renders get the
    same golden-exposition + parse-round-trip coverage the PR-10
    scheduler/provider families have — a format drift in THESE names is
    a silently-empty RESUME/DUMPS/STATE column, not an error."""

    def _registry(self) -> MetricsRegistry:
        r = MetricsRegistry()
        r.counter(MetricName.PROVIDER_RESUMES, "resumes handled",
                  labels=("outcome",)).inc(3, outcome="resumed")
        r.counter(MetricName.PROVIDER_RESUMES, "resumes handled",
                  labels=("outcome",)).inc(1, outcome="refused")
        r.counter(MetricName.RESUME_WASTED_TOKENS,
                  "overlap tokens dedup dropped").inc(17)
        r.counter(MetricName.SCHED_RESUME_REUSED,
                  "radix tokens resumes reused",
                  labels=("tier",)).inc(96, tier="decode")
        r.counter(MetricName.PROVIDER_FLIGHT_DUMPS,
                  "flight-recorder dumps written",
                  labels=("reason",)).inc(2, reason="slo_burn_ttft")
        pool = r.counter(MetricName.POOL_PLACEMENTS, "lifetime placements",
                         labels=("tier", "node"))
        pool.inc(5, tier="prefill", node="p0")
        pool.inc(3, tier="prefill", node="p1")
        state = r.gauge(MetricName.POOL_MEMBER_STATE,
                        "membership state code", labels=("tier", "node"))
        state.set(1, tier="prefill", node="p0")   # healthy
        state.set(3, tier="prefill", node="p1")   # lost
        return r

    def test_resume_pool_golden_exposition(self):
        text = render_prometheus(
            [{"snapshot": self._registry().snapshot(compact=True),
              "labels": {}}])
        assert text == RESUME_POOL_GOLDEN

    def test_resume_pool_parse_round_trip(self):
        r = self._registry()
        fams = parse_prometheus_text(render_prometheus(
            [{"snapshot": r.snapshot(compact=True), "labels": {}}]))
        res = fams[MetricName.PROVIDER_RESUMES]
        assert res["kind"] == "counter"
        assert {s["labels"]["outcome"]: s["value"]
                for s in res["series"]} == {"resumed": 3.0, "refused": 1.0}
        (wasted,) = fams[MetricName.RESUME_WASTED_TOKENS]["series"]
        assert wasted["value"] == 17.0
        (reused,) = fams[MetricName.SCHED_RESUME_REUSED]["series"]
        assert reused["labels"]["tier"] == "decode"
        assert reused["value"] == 96.0
        dumps = fams[MetricName.PROVIDER_FLIGHT_DUMPS]["series"]
        assert dumps[0]["labels"]["reason"] == "slo_burn_ttft"
        states = {s["labels"]["node"]: s["value"]
                  for s in fams[MetricName.POOL_MEMBER_STATE]["series"]}
        assert states == {"p0": 1.0, "p1": 3.0}

    def test_symtop_resume_and_dump_columns(self):
        """The provider row shows resumes/wasted/dumps; tier sub-rows
        show resume admissions + reused tokens (the cheap-resume
        contract reads straight off the table)."""
        import tools.symtop as symtop

        r = self._registry()
        r.counter(MetricName.PROVIDER_TOKENS_OUT, "t").inc(100)
        r.gauge(MetricName.PROVIDER_UPTIME, "u").set(10.0)
        sched = MetricsRegistry()
        sched.gauge(MetricName.SCHED_OCCUPANCY, "o").set(1)
        sched.counter(MetricName.SCHED_RESUMES, "resume admissions").inc(2)
        sched.counter(MetricName.SCHED_RESUME_REUSED,
                      "reused").inc(96)
        fams = symtop.families_from_snapshots([
            {"snapshot": r.snapshot(compact=True), "labels": {}},
            {"snapshot": sched.snapshot(compact=True),
             "labels": {"tier": "decode"}},
        ])
        rows = symtop.build_rows("prov-a", fams, None, now=0.0)
        assert rows[0]["resume"] == 4.0      # resumed + refused
        assert rows[0]["wasted"] == 17.0
        assert rows[0]["dumps"] == 2.0
        tier = rows[1]
        assert tier["tier"] == "decode"
        assert tier["resume"] == 2.0
        assert tier["reused"] == 96.0 * 2    # registry + sched snapshots
        rows[0].pop("_sample", None)
        table = symtop.render_table(rows)
        header = table.splitlines()[0]
        for col in ("RESUME", "WASTED", "REUSED", "DUMPS"):
            assert col in header
        assert "17" in table and "prov-a" in table
