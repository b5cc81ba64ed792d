"""Piecewise timing of the decode step on the real chip.

Times each stage of the serving decode step in isolation (trunk, attention,
LM head, sampling, cache scatter) to locate the gap between the measured
step time and the HBM-bandwidth floor. Not part of the test suite; run
manually: `python tools/profile_decode.py [--preset llama3-8b ...]`.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bench_util import sync, timeit  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="llama3-8b")
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--max-seq", type=int, default=640)
    ap.add_argument("--kv-quant", default="int8", choices=("none", "int8"),
                    help="A/B the cache dtype: if the int8 cache read were "
                         "upcast-materialized by XLA, int8 would not beat "
                         "bf16 here")
    ap.add_argument("--trunk-only", action="store_true")
    ap.add_argument("--force-einsum", action="store_true",
                    help="time the XLA attention path instead of the "
                         "Pallas decode kernel the program routes to "
                         "(forced here: the served path has no switch)")
    ap.add_argument("--occupancy", type=int, default=None,
                    help="per-slot cache occupancy for the trunk timing "
                         "(default: near capacity)")
    args = ap.parse_args()

    if args.force_einsum:
        from symmetry_tpu.ops import decode_attention as _da
        _da.geometry = lambda *a, **k: None

    from symmetry_tpu.models.llama import (
        forward_hidden, init_cache, init_params, logits_from_hidden, preset)
    from symmetry_tpu.ops.attention import gqa_attention
    from symmetry_tpu.ops.sampling import sample_tokens

    cfg = preset(args.preset)
    B, T = args.slots, args.max_seq
    kvq = args.kv_quant == "int8"
    n_warm, n_timed = 3, 20
    params = init_params(cfg, jax.random.key(0), jnp.bfloat16, quantize=True)
    cache = init_cache(cfg, B, T, jnp.bfloat16, quantized=kvq)
    # Start far enough from capacity that every warmup+timed step writes in
    # bounds — out-of-bounds scatters are silently dropped under jit, which
    # would make the tail iterations measure different work.
    occ = (args.occupancy if args.occupancy is not None
           else T - (n_warm + n_timed + 1))
    occ = min(occ, T - (n_warm + n_timed + 1))
    cache = cache._replace(lengths=jnp.full((B,), occ, jnp.int32))
    tok = jnp.ones((B, 1), jnp.int32)

    # Full trunk (all layers incl. attention + cache writes)
    trunk = jax.jit(lambda p, t, c: forward_hidden(p, cfg, t, c),
                donate_argnums=(2,))
    for _ in range(n_warm):
        h, cache = trunk(params, tok, cache)
    sync(h)
    t0 = time.perf_counter()
    for _ in range(n_timed):
        h, cache = trunk(params, tok, cache)
    sync(h)
    ms_trunk = (time.perf_counter() - t0) / n_timed * 1e3

    L = cfg.num_layers
    print(f"trunk (all {L} layers):   {ms_trunk:8.2f} ms  "
          f"(B={B} T={T} occ={occ} kv={'int8' if kvq else 'bf16'}"
          f"{' einsum' if args.force_einsum else ''})", flush=True)
    if args.trunk_only:
        return

    # LM head
    h = jnp.ones((B, 1, cfg.hidden_size), jnp.bfloat16)
    head = jax.jit(lambda p, h: logits_from_hidden(p, cfg, h))
    ms_head = timeit(head, params, h)
    print(f"lm head:                  {ms_head:8.2f} ms", flush=True)

    # Sampling
    logits = jnp.ones((B, cfg.vocab_size), jnp.float32)
    keys = jax.random.split(jax.random.key(0), B)
    temp = jnp.full((B,), 0.7, jnp.float32)
    top_p = jnp.ones((B,), jnp.float32)
    top_k = jnp.zeros((B,), jnp.int32)
    samp = jax.jit(sample_tokens)
    ms_samp = timeit(samp, logits, keys, temp, top_p, top_k)
    del logits, keys
    print(f"sampling:                 {ms_samp:8.2f} ms", flush=True)
    print(f"sum trunk+head+sample:    {ms_trunk + ms_head + ms_samp:8.2f} ms",
          flush=True)

    # Attention alone, one layer, einsum path (what the trunk uses at
    # T<4096). Positions/lengths passed as ARGUMENTS — closed-over device
    # arrays would be baked into the jaxpr as constants (host round-trip +
    # a device copy at trace time).
    D, nq, nkv = cfg.dim_per_head, cfg.num_heads, cfg.num_kv_heads
    q = jnp.ones((B, 1, nq, D), jnp.bfloat16)
    pos = jnp.full((B, 1), T - 8, jnp.int32)
    kl = jnp.full((B,), T - 7, jnp.int32)
    attn = jax.jit(lambda q, k, v, ks, vs, pos, kl: gqa_attention(
        q, k, v, pos, kl, k_scale=ks, v_scale=vs))
    try:
        ms_attn1 = timeit(attn, q, cache.k[0], cache.v[0], cache.k_scale[0],
                          cache.v_scale[0], pos, kl)
        print(f"attention x1 (einsum):    {ms_attn1:8.2f} ms  "
              f"(x{L} = {ms_attn1*L:.1f})", flush=True)
    except Exception as exc:  # noqa: BLE001 — keep profiling other stages
        print(f"attention x1 (einsum):    failed: {exc}", flush=True)

    # Cache scatter write, one layer-equivalent (k payload .at[].set).
    # The donated buffer must be REBOUND each call (k = f(k, ...)) — reusing
    # the stale python ref would hand the jit a deleted buffer.
    kq = jnp.ones((B, 1, nkv, D), jnp.int8)
    lidx = jnp.zeros((B, 1), jnp.int32)
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]

    def scatter(k, kq, pos):
        return k.at[lidx, bidx, pos].set(kq)

    try:
        f = jax.jit(scatter, donate_argnums=(0,))
        k = cache.k
        for _ in range(n_warm):
            k = f(k, kq, pos)
        sync(k)
        t0 = time.perf_counter()
        for _ in range(n_timed):
            k = f(k, kq, pos)
        sync(k)
        ms_scat1 = (time.perf_counter() - t0) / n_timed * 1e3
        cache = cache._replace(k=k)
        print(f"cache scatter x1 (k):     {ms_scat1:8.2f} ms  "
              f"(x{2*L} = {ms_scat1*2*L:.1f})", flush=True)
    except Exception as exc:  # noqa: BLE001
        print(f"cache scatter x1 (k):     failed: {exc}", flush=True)

    # Pallas ragged decode kernel at this capacity (block and slot tile by
    # its own geometry)
    from symmetry_tpu.ops import decode_attention as da
    q3 = jnp.ones((B, nq, D), jnp.bfloat16)
    pal = jax.jit(lambda q3, k, v, ks, vs, kl: da.decode_attention(
        q3, k, v, jnp.int32(0), kl, k_scale=ks, v_scale=vs))
    try:
        ms_pallas1 = timeit(pal, q3, cache.k, cache.v,
                            cache.k_scale, cache.v_scale, kl)
        print(f"attention x1 (pallas):    {ms_pallas1:8.2f} ms  "
              f"(x{L} = {ms_pallas1*L:.1f})", flush=True)
    except Exception as exc:  # noqa: BLE001
        print(f"attention x1 (pallas):    failed: {exc}", flush=True)

    # bandwidth sanity: weight bytes + kv bytes
    wb = sum(np.prod(x.shape) * x.dtype.itemsize
             for x in jax.tree.leaves(params))
    kvb = (2 * L * B * T * nkv * D * 1
           + 2 * L * B * nkv * T * 4)
    print(f"weight bytes: {wb/1e9:.2f} GB  kv bytes: {kvb/1e9:.2f} GB")


if __name__ == "__main__":
    main()
