"""Isolate the decode matmul's HBM efficiency on the real chip.

The decode step is weight-read-bound; profile_decode measured the trunk's
effective weight bandwidth at ~480 GB/s — well under v5e's ~819 GB/s. This
benchmarks ONE weight matmul shape in isolation, looping inside a single
jit (scan) so per-dispatch host overhead amortizes away and the weight
(sized past VMEM) must be re-streamed from HBM every iteration.

Variants:
  bf16      x[bf16] @ W[bf16]
  int8      x[bf16] @ W[int8] via ops/quant.qmatmul (mixed dot_general)
  int8-deq  x[bf16] @ dequant(W) materialized per call (the anti-pattern)
  w8a8      per-row-quantized x[int8] @ W[int8], s32 accumulate

Run: python tools/microbench_matmul.py
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bench_util import sync, timeit  # noqa: E402


def main():
    B, E, H = 128, 4096, 4 * 14336  # W sized ~235 MB int8: past VMEM
    ITERS = 20

    x = jnp.ones((B, E), jnp.bfloat16)
    wf = jax.random.normal(jax.random.key(0), (E, H), jnp.float32)
    w_bf16 = wf.astype(jnp.bfloat16)
    from symmetry_tpu.ops.quant import quantize

    w_q = quantize(wf)
    del wf

    def loop(body):
        """ITERS dependent matmuls in ONE jit; each re-reads W from HBM."""
        def run(x, w):
            def step(carry, _):
                y = body(carry, w)
                # feed a slice of y back so iterations can't be collapsed
                return carry + y[:, :E].astype(carry.dtype) * 1e-6, ()
            out, _ = jax.lax.scan(step, x, None, length=ITERS)
            return out
        return jax.jit(run)

    def report(name, ms, nbytes):
        gbs = nbytes * ITERS / (ms / 1e3) / 1e9
        print(f"{name:10s} {ms:8.2f} ms/loop  {gbs:7.1f} GB/s effective",
              flush=True)

    # bf16 reference
    f = loop(lambda x, w: x @ w)
    report("bf16", timeit(f, x, w_bf16), 2 * E * H)

    # int8 mixed dot (the serving path)
    from symmetry_tpu.ops.quant import qmatmul

    f = loop(qmatmul)
    report("int8", timeit(f, x, w_q), E * H)

    # int8 dequant-materialize (anti-pattern control)
    def deq(x, w):
        return x @ (w.q.astype(jnp.bfloat16) * w.scale.astype(jnp.bfloat16))

    f = loop(deq)
    report("int8-deq", timeit(f, x, w_q), E * H)

    # w8a8: dynamic per-row activation quant, s8 x s8 -> s32 MXU
    def w8a8(x, w):
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
        xs = jnp.maximum(amax, 1e-8) / 127.0
        xq = jnp.clip(jnp.round(x.astype(jnp.float32) / xs),
                      -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(
            xq, w.q, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return (y.astype(jnp.float32) * xs * w.scale).astype(x.dtype)

    f = loop(w8a8)
    report("w8a8", timeit(f, x, w_q), E * H)

    # int8 with bf16 accumulate hint
    def int8_bf16(x, w):
        y = jax.lax.dot_general(
            x, w.q, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.bfloat16)
        return y * w.scale.astype(jnp.bfloat16)

    f = loop(int8_bf16)
    report("int8-bf16", timeit(f, x, w_q), E * H)

    # int8 TRANSPOSED layout: W stored [out, in], contract on dim 1 of both
    wt = jnp.asarray(np.asarray(w_q.q).T)  # [H, E] int8, materialized
    sc = w_q.scale

    def int8_t(x, wt):
        y = jax.lax.dot_general(
            x, wt, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (y * sc).astype(x.dtype)

    f = loop(int8_t)
    report("int8-T", timeit(f, x, wt), E * H)

    # upcast whole W first with one convert op, then bf16 matmul
    def upcast_first(x, w):
        wb = jax.lax.convert_element_type(w.q, jnp.bfloat16)
        return (x @ wb) * sc.astype(jnp.bfloat16)

    f = loop(upcast_first)
    report("int8-up", timeit(f, x, w_q), E * H)


if __name__ == "__main__":
    main()
