"""Shared timing helpers for the tools/ benchmarks.

sync() forces completion by pulling one element to the host: a fetch
cannot return before the value exists, whatever a backend does with
jax.block_until_ready. On the attached v5e the two fences agree — one
mistral-7b decode block takes 198.4 ms under block_until_ready and 199.0 ms
under sync(), against 0.5 ms for the enqueue alone (tools/chip_kernels.py
on one TPU v5 lite chip, PR 21; PERF.md Bring-up).
"""

from __future__ import annotations

import time

import jax
import numpy as np


def sync(x) -> None:
    """Force completion of x's computation by fetching one element."""
    leaf = jax.tree.leaves(x)[0]
    np.asarray(jax.device_get(leaf[(0,) * leaf.ndim]))


def timeit(fn, *args, n: int = 20, warmup: int = 3) -> float:
    """Mean wall ms per call of fn(*args), warmup excluded, sync()-fenced."""
    for _ in range(warmup):
        out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / n * 1e3
