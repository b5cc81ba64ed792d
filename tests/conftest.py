"""Test configuration.

Tests never require a TPU: JAX is pinned to the CPU backend by name, with 8
virtual devices so sharding/mesh tests exercise real multi-device compilation
paths (SURVEY §4 build implication), and Pallas kernels run interpreted
(ops/interpret.py). The environment is set before jax is imported anywhere,
and it OVERRIDES the outer one: a builder's shell may point JAX at a chip.
"""

import os
import time

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

from symmetry_tpu.utils.compile_cache import (  # noqa: E402 — imports no jax
    MIN_COMPILE_TIME_S, cache_dir, enable_compile_cache)

# Persistent compilation cache: every test that builds an engine re-traces
# the same programs; caching compiled executables across tests AND runs is
# the difference between an affordable suite and a >10-minute one. The env
# vars carry it to SUBPROCESSES that never call enable_compile_cache (graft
# dryrun, multihost workers); engine hosts resolve the same directory
# themselves.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                      str(MIN_COMPILE_TIME_S))

import jax  # noqa: E402  (import after env pinning, by design)

enable_compile_cache()

# Tests run models in float32 and compare against f32 references; the default
# matmul precision truncates f32 operands to bf16 passes, which swamps the
# tolerances. Production serving uses bf16 params, where this is a no-op.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture
def loop_ratio():
    """`loop_ratio(site, n, bare=<an empty call>)`: wall time of `n` calls
    of `site` over that of `n` calls of `bare`, each the best of five loops
    run turn about in this process. The disabled-mode guards bound THIS,
    never a number of seconds: a loaded machine slows both loops, the code
    under test only the first."""
    def loop(fn, n):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return time.perf_counter() - t0

    def ratio(site, n, bare=lambda: None):
        runs = [(loop(site, n), loop(bare, n)) for _ in range(5)]
        return min(s for s, _ in runs) / min(b for _, b in runs)

    return ratio
