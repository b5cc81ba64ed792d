"""What the engine host can say about the device and the compiler.

`capture_device_profile` is a full `jax.profiler` trace (HLO timelines,
HBM) for a bounded window, triggered by the HostOp.PROFILE pipe op
(provider wire op, SIGUSR1, or the SLO burn-rate breach hook alongside
the flight recorder) and dumped as a linkable TensorBoard/Perfetto
artifact. While it runs, every `Tracer.phase` in the process also enters
its `sym.*` TraceAnnotation (utils/trace.py), so the capture's host plane
carries the scheduler-loop phases on the device rows' clock — the
benchmark's `--trace 1` reduces that capture to `device_idle`,
`idle_in.*`, `top_op_share` and `breakdown`.

`CompileWatch` counts what JAX traces, lowers and compiles in the engine
host, and what the persistent cache gave or lacked, from `jax.monitoring`
events — the stats op's `compile` block (`compile_share`,
`lowerings_in_window`) — hands out the growth of its counts since a mark
(the warm-up record books it to a program: engine/engine.py `_warm`) and
names the events that overlap a span (the scheduler's stall record).
`gc_seconds` is the wall the garbage collector has held the interpreter,
from one `gc.callbacks` pair.
"""

from __future__ import annotations

import gc
import os
import threading
import time
from collections import deque
from typing import Any

from symmetry_tpu.utils.trace import set_capture_active


# --------------------------------------------------- lowerings and compiles

class CompileWatch:
    """Counts what JAX traces, lowers and compiles in this process, from
    the `jax.monitoring` events JAX itself records — the operator's answer
    to "which step recompiled", and the benchmark's count of lowerings
    inside a window. Cumulative since `register()`; `mark_ready()` keeps
    the counts as they stood when warm-up ended, so growth past them is
    work the serving loop paid for. A listener body is an add and an
    append under a lock.

    `backend_s` is the wall of "compile or fetch": on a cache hit it is
    mostly `retrieval_s` (reading and loading the executable), on a miss
    the compiler's. `cache_misses` counts executables compiled AND written
    to the persistent cache — 0 on a warm start, and with the cache off."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
        "/jax/core/compile/jaxpr_to_mlir_module_duration":
            ("lowerings", "lower_s"),
        "/jax/core/compile/backend_compile_duration":
            ("backend_compiles", "backend_s"),
    }
    RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
    COUNTED = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}
    RECENT = 32

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: dict[str, float] = {
            key: 0 for pair in self.EVENTS.values() for key in pair}
        self._counts.update({key: 0 for key in self.COUNTED.values()})
        self._counts["retrieval_s"] = 0
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
        if event == self.RETRIEVAL:  # one a cache hit: seconds only
            with self._lock:
                self._counts["retrieval_s"] += duration
            return
        keys = self.EVENTS.get(event)
        if keys is None:
            return
        with self._lock:
            self._counts[keys[0]] += 1
            self._counts[keys[1]] += duration
            self._recent.append((time.monotonic(), keys[0],
                                 str(kwargs.get("fun_name", "")), duration))

    def _on_event(self, event: str, **kwargs: Any) -> None:
        key = self.COUNTED.get(event)
        if key is not None:
            with self._lock:
                self._counts[key] += 1

    def mark(self) -> dict[str, float]:
        """The counts as they stand: hand it back to `since`."""
        with self._lock:
            return dict(self._counts)

    def since(self, mark: dict[str, float]) -> dict[str, float]:
        """How far each count has grown since `mark`. JAX compiles on the
        calling thread, so between a mark and its reading on one thread
        the growth is that thread's own work."""
        with self._lock:
            return {key: value - mark[key]
                    for key, value in self._counts.items()}

    def _snapshot(self) -> dict[str, float]:
        out = dict(self._counts)
        out["host_s"] = out["trace_s"] + out["lower_s"] + out["backend_s"]
        return out

    def mark_ready(self) -> None:
        with self._lock:
            self._at_ready = self._snapshot()

    @property
    def lowerings(self) -> int:
        return int(self._counts["lowerings"])

    def overlapping(self, t0: float, t1: float) -> list[list]:
        """The recent events that ran inside [t0, t1] (monotonic), as
        [kind, function, seconds]: which step recompiled in that span."""
        with self._lock:
            return [[kind, name, round(s, 6)]
                    for t, kind, name, s in self._recent
                    if t - s <= t1 and t >= t0]

    def stats(self) -> dict[str, Any]:
        with self._lock:
            out: dict[str, Any] = self._snapshot()
            out["at_ready"] = self._at_ready
            out["recent"] = [[round(t, 4), kind, name, round(s, 6)]
                             for t, kind, name, s in self._recent]
        return out


# ------------------------------------------------------ the collector

# [seconds collecting since gc_watch(), start stamp of the collection in
# progress]. A collection runs on whichever thread tripped the threshold and
# holds the GIL throughout, so its wall is every thread's.
_gc = [0.0, 0.0]


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc[1] = time.perf_counter()
    else:
        _gc[0] += time.perf_counter() - _gc[1]


def gc_watch() -> None:
    """Register the callback pair, once per process."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_seconds() -> float:
    return _gc[0]


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
        # The Python tracer stays off: it hooks every Python call of every
        # thread for the window (a lowering costs 45-87 ms under it, ~10
        # untraced: PERF.md), and nothing reads its events — the `sym.*`
        # annotations below are TraceMe events of the host tracer.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(path, profiler_options=options)
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
