"""Tracing + metrics (utils/trace.py): histograms, spans, provider stats."""

import time

from symmetry_tpu.utils.trace import Histogram, Tracer


class TestHistogram:
    def test_percentiles_exact_within_reservoir(self):
        h = Histogram()
        for ms in range(1, 1001):
            h.observe(ms / 1000.0)
        assert h.count == 1000
        p50, p90, p99 = h.percentile(50), h.percentile(90), h.percentile(99)
        # 1000 samples fit the reservoir: percentiles are EXACT order
        # statistics, not bucket edges (the round-4 p50==p99 artifact).
        assert p50 == 0.5
        assert p90 == 0.9
        assert p99 == 0.99
        assert p50 < p90 < p99

    def test_reservoir_estimate_beyond_cap(self):
        h = Histogram(reservoir=256)
        for i in range(10_000):
            h.observe((i % 1000 + 1) / 1000.0)
        assert h.count == 10_000
        p50 = h.percentile(50)
        # Uniform subsample of a uniform(0.001, 1.0) stream: the estimate
        # must land near the true median, far tighter than a 1.58x bucket.
        assert 0.35 <= p50 <= 0.65
        assert h.percentile(1) < p50 < h.percentile(99)

    def test_empty(self):
        h = Histogram()
        assert h.percentile(50) is None
        assert h.mean is None
        d = h.to_dict()
        assert d["count"] == 0 and d["p99"] is None

    def test_extremes_clamped(self):
        h = Histogram()
        h.observe(1e-9)   # below lowest edge
        h.observe(1e6)    # above highest edge
        assert h.count == 2
        assert h.min == 1e-9 and h.max == 1e6
        assert h.percentile(100) == 1e6


class TestTracer:
    def test_phase_records_and_aggregates(self):
        tr = Tracer()
        with tr.phase("prefill", request_id="r1", bucket=128):
            time.sleep(0.01)
        with tr.phase("prefill", request_id="r2", bucket=512):
            pass
        spans = tr.export()
        assert len(spans) == 2
        assert spans[0]["name"] == "prefill"
        assert spans[0]["bucket"] == 128
        assert spans[0]["duration_s"] >= 0.01
        assert tr.export(request_id="r2")[0]["bucket"] == 512
        # a phase is a ring record and seconds under its label; it feeds
        # no histogram (Tracer.record keeps its own)
        assert [s["name"] for s in spans].count("prefill") == 2
        assert tr.stats() == {}
        assert tr.phase_s["prefill"] >= 0.01
        tr.record("ttft", 0.0, 0.25)
        assert tr.stats()["ttft_s"]["count"] == 1

    def test_disabled_is_noop(self):
        tr = Tracer()
        tr.enabled = False
        with tr.phase("x"):
            pass
        tr.record("y", 0.0, 1.0)
        assert tr.export() == []
        assert tr.stats() == {}

    def test_ring_bounded(self):
        tr = Tracer(capacity=8)
        for i in range(20):
            tr.record("s", 0.0, 0.001, request_id=str(i))
        spans = tr.export()
        assert len(spans) == 8
        assert spans[0]["request_id"] == "12"  # oldest retained

    def test_annotate_inside_phase(self):
        tr = Tracer()
        with tr.phase("gen") as attrs:
            attrs["tokens"] = 42
        assert tr.export()[0]["tokens"] == 42
