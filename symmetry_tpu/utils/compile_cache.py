"""Persistent XLA compilation cache for every process that builds an engine.

The serving warmup compiles the full (prefill-batch × bucket) grid plus
the decode program, which is most of a cold start. JAX's persistent
compilation cache keys entries by HLO + compile options + backend, so one
directory is safe across configs and backends: a different mesh, dtype,
bucket grid or platform simply misses and fills its own entries.

Where the cache lives is decided in ONE place, `cache_dir`:

  - `JAX_COMPILATION_CACHE_DIR` set → that directory. JAX reads the
    variable itself at import, so no directory is set in code — whoever
    placed the cache from outside (a CI runner, the chip tool) keeps it.
  - unset → `<checkout>/.jax_cache` (git-ignored), or the directory a
    string `tpu.compile_cache` names. The path is part of what makes a
    cache findable again, so it is fixed: nothing under `~`, `/tmp`, a
    pid or a timestamp.

The engine host, the in-process backend, the bench, the smokes and
tests/conftest.py all resolve through here.
"""

from __future__ import annotations

import os
from typing import Any

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

# Every program is persisted, however fast it compiled. A time floor
# (JAX's default is 1 s) makes what gets cached depend on how fast THIS
# run happened to compile it: at a 0.3 s floor the first warm start on
# the chip still added 6 entries (PR 21), and the small-bucket insert
# programs it skips add up across a restart.
MIN_COMPILE_TIME_S = 0.0


def cache_dir(setting: Any = True) -> str | None:
    """The directory the cache uses for a `tpu.compile_cache` value
    (True → default, str → that directory, False → None). Imports no
    JAX, so a process that must stay off the chip can still look at it."""
    if setting is False:
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if isinstance(setting, str):
        return os.path.expanduser(setting)
    return DEFAULT_CACHE_DIR


def enable_compile_cache(tpu_cfg: Any = None) -> str | None:
    """Turn the persistent cache on for this process; returns the
    directory in use, or None when disabled (`tpu.compile_cache: false`)
    or when the directory cannot be created. Call before the first jit
    compile."""
    setting = True if tpu_cfg is None else getattr(tpu_cfg, "compile_cache",
                                                   True)
    directory = cache_dir(setting)
    if directory is None:
        return None
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_TIME_S)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return directory  # JAX already holds it; set no directory in code
    try:
        os.makedirs(directory, exist_ok=True)
    except OSError as exc:
        from symmetry_tpu.utils.logging import logger

        logger.warning(f"compile cache directory {directory} is not "
                       f"writable, compiling cold: {exc}")
        return None
    jax.config.update("jax_compilation_cache_dir", directory)
    return directory
