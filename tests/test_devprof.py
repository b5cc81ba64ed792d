"""The on-demand device profile (utils/devprof.py
capture_device_profile): it writes a real trace directory and refuses a
concurrent capture. `CompileWatch`: what the persistent cache gave and
lacked, and the growth of its counts since a mark."""

import os
import time

import pytest


class TestCaptureDeviceProfile:
    def test_capture_writes_artifacts_and_single_flights(self, tmp_path):
        import threading

        from symmetry_tpu.utils.devprof import capture_device_profile

        path = capture_device_profile(str(tmp_path), duration_s=0.05)
        assert os.path.isdir(path)
        # Concurrent capture refused while one holds the window.
        hold = threading.Thread(target=capture_device_profile,
                                args=(str(tmp_path),),
                                kwargs={"duration_s": 0.5})
        hold.start()
        time.sleep(0.15)
        with pytest.raises(RuntimeError, match="already running"):
            capture_device_profile(str(tmp_path), duration_s=0.05)
        hold.join()


class TestCompileWatch:
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def test_counts_a_miss_and_a_retrieval_from_injected_events(self):
        from symmetry_tpu.utils.devprof import CompileWatch

        watch = CompileWatch()
        watch._on_event(self.MISS)
        watch._on_event(self.MISS)
        watch._on_event(self.HIT)
        watch._on_duration(self.RETRIEVAL, 0.25)
        watch._on_duration(self.BACKEND, 1.5, fun_name="prefill")
        watch._on_event("/jax/compilation_cache/compile_requests_use_cache")
        watch._on_duration("/jax/some/other_duration", 9.0)
        stats = watch.stats()
        assert stats["cache_misses"] == 2 and stats["cache_hits"] == 1
        assert stats["retrieval_s"] == 0.25
        assert stats["backend_compiles"] == 1 and stats["backend_s"] == 1.5
        # a retrieval is seconds of a backend compile event, not an event
        # of its own in `recent`
        assert [r[1:3] for r in stats["recent"]] == [
            ["backend_compiles", "prefill"]]
        assert stats["host_s"] == 1.5

    def test_hands_out_growth_since_a_mark(self):
        from symmetry_tpu.utils.devprof import CompileWatch

        watch = CompileWatch()
        watch._on_event(self.MISS)
        watch._on_duration(self.BACKEND, 2.0)
        mark = watch.mark()
        assert all(v == 0 for v in watch.since(mark).values())
        watch._on_event(self.HIT)
        watch._on_duration(self.RETRIEVAL, 0.125)
        watch._on_duration(self.BACKEND, 0.25)
        grown = watch.since(mark)
        assert grown["cache_hits"] == 1 and grown["cache_misses"] == 0
        assert grown["retrieval_s"] == 0.125 and grown["backend_s"] == 0.25
        assert grown["backend_compiles"] == 1
        # the mark is a copy: the counts moved on, it did not
        assert mark["cache_misses"] == 1 and mark["backend_s"] == 2.0
        assert watch.since(watch.mark())["backend_s"] == 0

    def test_listens_to_jax_itself(self, tmp_path):
        """A real compile into a fresh cache directory is a miss, the
        same program from another function object (so nothing in memory
        serves it) a hit with retrieval seconds."""
        import jax
        import jax.numpy as jnp

        from symmetry_tpu.utils.devprof import CompileWatch

        watch = CompileWatch()
        watch.register()
        old = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            from jax.experimental.compilation_cache import (
                compilation_cache as cc)

            cc.reset_cache()

            def make():
                def program(x):
                    return jnp.sin(x) * 3.0 + jnp.arange(7.0)
                return program

            mark = watch.mark()
            jax.jit(make())(jnp.ones((7,))).block_until_ready()
            first = watch.since(mark)
            mark = watch.mark()
            jax.jit(make())(jnp.ones((7,))).block_until_ready()
            second = watch.since(mark)
        finally:
            jax.config.update("jax_compilation_cache_dir", old)
            cc.reset_cache()
            watch.unregister()
        assert first["cache_misses"] >= 1 and first["backend_s"] > 0
        assert second["cache_misses"] == 0 and second["cache_hits"] >= 1
        assert second["retrieval_s"] > 0
