"""symprof: on-device time attribution via sampling completion probes.

Every instrument before this module measured on HOST clocks: a
scheduler `decode_block` span covers dispatch → host sync, which
conflates device compute, host dispatch overhead, and scheduler idle.
The reckoning round (ROADMAP item 1) needs the split — what fraction of
a steady-state block interval is device time vs host gap — to decide
the W8A16/speculative/disagg knob defaults, and the rounds-3/4
steady-wire gap (~70% of engine-only) is SUSPECTED to be host idle
between device blocks, never yet measured directly.

`DeviceProfiler` is the measurement: a sampling-mode completion probe
around every engine dispatch kind (prefill / chunk / decode_block /
verify / adopt / seed_gather / scatter).

  - On a 1-in-N cadence (`tpu.profile_sample: N`; 0 = off), the probe
    `jax.block_until_ready`s the dispatch's output and timestamps both
    ends: `t_ready - t_begin` is that dispatch's DEVICE DURATION
    (queue + compute, from the moment the host started dispatching).
  - The probed sync drains the device pipeline, so the host time until
    the NEXT dispatch begins is genuine device idle: that interval is
    one DISPATCH GAP sample — the host-side work (emit, detokenize,
    admission bookkeeping) that double-buffering must hide, and the
    steady-wire suspect, finally measured on the device's own terms.
  - Off-mode cost is one attribute load + branch per dispatch (the
    engine guards every hook with `if devprof.enabled:`), the same
    contract as the metrics registry's disabled mode — CI-asserted by
    the overhead guard test. Sampling mode deliberately serializes 1
    dispatch in N (that IS the probe); keep N large enough that the
    tok/s A/B stays within 1% (BASELINE.md Round 15).

Results flow three ways, mirroring every other instrument:

  - `stats()` rides scheduler stats → host stats op → provider
    `engine` block → bench JSON (per-kind device-duration percentiles,
    the dispatch-gap distribution, and `gap_share`).
  - The always-on metrics registry gains `sym_device_*` /
    `sym_dispatch_gap_*` families (tier-labeled through the
    HostOp.METRICS probe like every scheduler family).
  - A dedicated Tracer ring records each probed dispatch as a span
    (name = kind) plus `dispatch_gap` spans, exported by the host's
    `trace` op as a per-host `device` component — the device track
    that renders beside the request spans in the merged Perfetto
    timeline.

`capture_device_profile` is the on-demand heavyweight complement: a
full `jax.profiler` trace (HLO timelines, HBM) for a bounded window,
triggered by the HostOp.PROFILE pipe op (provider wire op, SIGUSR1, or
the SLO burn-rate breach hook alongside the flight recorder) and
dumped as a linkable TensorBoard/Perfetto artifact. While it runs, every
`Tracer.phase` in the process also enters its `sym.*` TraceAnnotation
(utils/trace.py), so the capture's host plane carries the scheduler-loop
phases on the device rows' clock.

`CompileWatch` counts what JAX traces, lowers and compiles in the engine
host, from `jax.monitoring` events — the stats op's `compile` block.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any

from symmetry_tpu.utils.metrics import METRICS, MetricName
from symmetry_tpu.utils.trace import (
    Histogram, Tracer, set_capture_active)

# The dispatch kinds the engine wraps. A probe with an unknown kind
# still records (the set is documentation + the smoke's assertion
# vocabulary, not a gate).
DISPATCH_KINDS = ("prefill", "chunk", "decode_block", "verify", "adopt",
                  "seed_gather", "scatter")


class DeviceProfiler:
    """Sampling completion probe over engine dispatches.

    Thread model: `begin`/`probe` run on the ENGINE thread only (the
    engine's single-threaded contract); `stats()` may be called from
    the host's pipe-reader thread, so the shared tallies mutate and
    snapshot under one lock — probes fire 1-in-N, so the critical
    section is nowhere near the hot path's per-dispatch cost.
    """

    def __init__(self, sample_every: int = 0,
                 tracer: Tracer | None = None) -> None:
        self.sample_every = max(0, int(sample_every))
        self.enabled = self.sample_every > 0
        # Bounded span ring: probed dispatches + gaps, exported as the
        # per-host "device" Perfetto component. Smaller than the
        # scheduler ring — probes are 1-in-N by construction.
        self.tracer = tracer if tracer is not None else Tracer(capacity=2048)
        self.tracer.enabled = self.enabled
        self._lock = threading.Lock()
        # PER-KIND dispatch counters (every dispatch, probed or not):
        # the cadence is 1-in-N of EACH kind — a global counter would
        # let frequent decode_blocks absorb every probe slot and leave
        # rare kinds (prefill, verify, scatter) systematically unprobed.
        self._dispatches: dict[str, int] = {}
        self._probes: dict[str, int] = {}
        self._kind_hists: dict[str, Histogram] = {}
        self._gap_hist = Histogram()
        self._device_s = 0.0
        self._gap_s = 0.0
        # Completion stamp of the last probed dispatch; the NEXT
        # begin() closes it into one gap sample. Engine-thread-only.
        self._gap_from: float | None = None
        self._m_dispatch = METRICS.histogram(
            MetricName.DEVICE_DISPATCH,
            "probed device duration per dispatch kind", labels=("kind",))
        self._m_probes = METRICS.counter(
            MetricName.DEVICE_PROBES,
            "completion probes fired per dispatch kind", labels=("kind",))
        self._m_gap = METRICS.histogram(
            MetricName.DISPATCH_GAP,
            "host idle between a probed device completion and the next "
            "dispatch")
        self._m_gap_share = METRICS.gauge(
            MetricName.DISPATCH_GAP_SHARE,
            "dispatch-gap share of probed engine wall "
            "(gap / (gap + device))")

    # ------------------------------------------------------------ hot path

    def begin(self) -> float:
        """Stamp a dispatch's start. Closes the pending gap when the
        PREVIOUS dispatch was probed: the probe drained the pipeline,
        so start - last_ready is genuine device idle. Call on every
        dispatch while enabled (the caller's `if devprof.enabled:`
        guard is the whole off-mode cost)."""
        t = time.monotonic()
        gap_from = self._gap_from
        if gap_from is not None:
            self._gap_from = None
            gap = max(t - gap_from, 0.0)
            self._gap_hist.observe(gap)
            self._m_gap.observe(gap)
            with self._lock:
                self._gap_s += gap
                share = (self._gap_s / (self._gap_s + self._device_s)
                         if (self._gap_s + self._device_s) > 0 else 0.0)
            self._m_gap_share.set(round(share, 4))
            self.tracer.record("dispatch_gap", gap_from, gap)
        return t

    def probe(self, kind: str, value: Any, t0: float) -> None:
        """Maybe-probe a dispatch that began at `t0` (a begin() stamp):
        on the 1-in-N cadence, block until `value` (any jax pytree) is
        device-ready and book t_ready - t0 as the dispatch's device
        duration. Never raises — a probe failure must not fail the
        dispatch it rode."""
        if not self.enabled:
            return  # direct calls with the knob off are no-ops too
        n = self._dispatches.get(kind, 0) + 1
        self._dispatches[kind] = n
        if n % self.sample_every:
            return
        try:
            import jax

            jax.block_until_ready(value)
        except Exception:  # noqa: BLE001 — diagnostics must never fail work
            return
        t1 = time.monotonic()
        dur = max(t1 - t0, 0.0)
        with self._lock:
            self._probes[kind] = self._probes.get(kind, 0) + 1
            hist = self._kind_hists.get(kind)
            if hist is None:
                hist = self._kind_hists[kind] = Histogram()
            self._device_s += dur
        hist.observe(dur)
        self._m_dispatch.observe(dur, kind=kind)
        self._m_probes.inc(kind=kind)
        self.tracer.record(kind, t0, dur)
        self._gap_from = t1

    # ----------------------------------------------------------- snapshots

    def gap_share(self) -> float | None:
        """Gap fraction of probed engine wall, None before any gap
        sample — the steady-state device-idle share headline."""
        with self._lock:
            total = self._gap_s + self._device_s
            if self._gap_hist.count == 0 or total <= 0:
                return None
            return self._gap_s / total

    def stats(self) -> dict[str, Any]:
        """The bench/stats-op block: per-kind device-duration
        percentiles, the dispatch-gap distribution, and the share."""
        with self._lock:
            hists = dict(self._kind_hists)
            probes = dict(self._probes)
            dispatches = dict(self._dispatches)
            device_s, gap_s = self._device_s, self._gap_s
        out: dict[str, Any] = {
            "sample_every": self.sample_every,
            "dispatches": dispatches,
            "probes": probes,
            "device_s": {kind: h.to_dict() for kind, h in hists.items()},
            "device_s_total": round(device_s, 4),
            "dispatch_gap_s": self._gap_hist.to_dict(),
            "dispatch_gap_s_total": round(gap_s, 4),
        }
        share = self.gap_share()
        out["gap_share"] = round(share, 4) if share is not None else None
        return out

    def component(self, name: str = "device") -> dict[str, Any]:
        """The probe span ring as one export_perfetto component — the
        per-host device track beside the request spans."""
        return self.tracer.component(name)


# --------------------------------------------------- lowerings and compiles

class CompileWatch:
    """Counts what JAX traces, lowers and compiles in this process, from
    the `jax.monitoring` events JAX itself records — the operator's answer
    to "which step recompiled", and the benchmark's count of lowerings
    inside a window. Cumulative since `register()`; `mark_ready()` keeps
    the counts as they stood when warm-up ended, so growth past them is
    work the serving loop paid for. A listener body is an add and an
    append under a lock."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
        "/jax/core/compile/jaxpr_to_mlir_module_duration":
            ("lowerings", "lower_s"),
        "/jax/core/compile/backend_compile_duration":
            ("backend_compiles", "backend_s"),
    }
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    RECENT = 32

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: dict[str, float] = {
            key: 0 for pair in self.EVENTS.values() for key in pair}
        self._counts["cache_hits"] = 0
        self._recent: deque[tuple[float, str, str, float]] = deque(
            maxlen=self.RECENT)
        self._at_ready: dict[str, float] | None = None

    def register(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def unregister(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float,
                     **kwargs: Any) -> None:
        keys = self.EVENTS.get(event)
        if keys is None:
            return
        with self._lock:
            self._counts[keys[0]] += 1
            self._counts[keys[1]] += duration
            self._recent.append((time.monotonic(), keys[0],
                                 str(kwargs.get("fun_name", "")), duration))

    def _on_event(self, event: str, **kwargs: Any) -> None:
        if event == self.CACHE_HIT:
            with self._lock:
                self._counts["cache_hits"] += 1

    def _snapshot(self) -> dict[str, float]:
        out = dict(self._counts)
        out["host_s"] = out["trace_s"] + out["lower_s"] + out["backend_s"]
        return out

    def mark_ready(self) -> None:
        with self._lock:
            self._at_ready = self._snapshot()

    def stats(self) -> dict[str, Any]:
        with self._lock:
            out: dict[str, Any] = self._snapshot()
            out["at_ready"] = self._at_ready
            out["recent"] = [[round(t, 4), kind, name, round(s, 6)]
                             for t, kind, name, s in self._recent]
        return out


# ------------------------------------------------------ on-demand capture

# One capture at a time per process: jax.profiler refuses concurrent
# traces, and the error it raises mid-serve is worth preventing, not
# catching. The busy flag is guarded by the lock (never held across
# the capture window — the window is seconds long on purpose).
_capture_lock = threading.Lock()
_capture_busy = False


def capture_device_profile(out_dir: str, duration_s: float = 2.0) -> str:
    """Run one bounded jax.profiler capture and return the trace
    directory (TensorBoard-loadable; xplane/trace.json inside are the
    linkable artifacts). Raises RuntimeError when a capture is already
    in progress — callers surface that, never queue behind it."""
    global _capture_busy

    import jax

    with _capture_lock:
        if _capture_busy:
            raise RuntimeError(
                "a device profile capture is already running")
        _capture_busy = True
    try:
        import uuid

        # Timestamp for the operator's eye + a uuid tail for uniqueness:
        # two captures inside the same second must not intermix their
        # artifacts in one directory.
        path = os.path.join(
            os.path.expanduser(out_dir),
            f"profile_{int(time.time())}_{uuid.uuid4().hex[:8]}")
        os.makedirs(path, exist_ok=True)
        jax.profiler.start_trace(path)
        try:
            # From here to stop_trace every Tracer.phase in this process
            # also enters its `sym.*` annotation; the capture thread names
            # its own sleep so no reader mistakes it for idle host time.
            set_capture_active(True)
            with jax.profiler.TraceAnnotation("sym.capture"):
                time.sleep(max(0.0, float(duration_s)))
        finally:
            set_capture_active(False)
            jax.profiler.stop_trace()
        return path
    finally:
        with _capture_lock:
            _capture_busy = False
