"""Compile every routed Pallas kernel ON THE CHIP and check it against the
reference its test file uses — at mistral-7b's real geometry, which the CPU
suite (interpret mode, tiny shapes) cannot reach: VMEM limits, (8, 128)
trailing block dims on int8 payloads, and the int8 tile floors are checked
by the Mosaic compiler only.

  flash_prefill      S 128 and 2048; D 128, 32 Q / 8 KV heads, bf16
                     vs tests/test_ops.py naive_attention
  decode_attention   capacity 4096 and 8192, bf16 and int8 KV with
                     [L, B, K, T] f32 scale planes
                     vs ops/attention.py gqa_attention
  w8a16_matmul       M 8 and 128 × the trunk's K×N (wq wk/wv wo wg/wu wd,
                     LM head), through pack_quantized + w8a16_apply
                     vs tests/test_qmm.py _reference_qmatmul

  dsa_select         keye-vl-2.0-30b-a3b's indexer (16 heads of 64, topk
                     2,048): a prompt of 6,144 and of 14,848 tokens and a
                     decode step of 64 slots x 16,384 with 25 live of
                     ~8.6k; EXACT equality with the `jnp` form (`select`
                     over `index_scores`) on integer-valued inputs, where
                     every score is exact and ties are plentiful; then both
                     forms timed on normal draws (`--only select` runs
                     these rows alone)

  decode64           lfm2-8b-a1b's cell: 6 attention layers x 128 slots x
                     640, 8 int8 KV heads of 64 held in pairs
                     ([6, 128, 640, 4, 128]: models/llama.py kv_row), 32
                     query heads, the lengths of the cell's traffic (mean
                     ~200 live positions); the kernel against
                     gqa_attention on the chip, then a STEP's six layers
                     timed three ways — the kernel, the XLA path over the
                     pairs (what a cache the kernel has no form for falls
                     back to) and the XLA path over `[6, 128, 640, 8, 64]`
                     as written (PR 42's program: the padded leaves)
                     (`--only decode64` runs these rows alone)

  gqa16              nemotron-3-nano-30b-a3b's attention blocks: 32 query
                     heads on TWO K/V heads of 128 (16 queries a K/V head)
                     — the flash kernel at a bucket of 256 and the decode
                     kernel over 64 slots x 640 in bf16 and int8 — against
                     the plain attention (`--only gqa16`)

  append             a decode step of the homogeneous trunk (PR 66) at the
                     dense cells' 128 slots x 640, mistral-7b's 8 and
                     qwen2-7b's 4 int8 KV heads: the kernel over the rows
                     BELOW each slot's position with the position's own
                     row as an operand (`own`) against gqa_attention over
                     a cache that holds the row; and `append_step` — a scatter a
                     K/V leaf, ops/scale_append.py for the planes — EXACT
                     against `write_kv` called a layer, a slot at the
                     capacity (dropped) and a slot at position 0 among
                     them (`--only append`)

Then the one timing question later PRs lean on: does
`jax.block_until_ready` on this chip wait for completion? One decode block
of chip_smoke.py's engine is timed under it, under the fetch fence of
tools/_bench_util.sync, and with no fence at all (the enqueue).

References run in float32 at highest matmul precision; tolerances are set
from the dtype the kernel returns (bf16: 2 ulp). Writes
chiprun_out/chip_kernels.json; exits non-zero if any kernel failed to
compile or disagreed. Needs a TPU: `python tools/chip_kernels.py`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _bench_util import sync, timeit  # noqa: E402
from symmetry_tpu.ops.attention import gqa_attention  # noqa: E402
from symmetry_tpu.ops.decode_attention import decode_attention  # noqa: E402
from symmetry_tpu.ops.flash import flash_prefill  # noqa: E402
from symmetry_tpu.ops.interpret import interpret_mode  # noqa: E402
from symmetry_tpu.ops.qmm import w8a16_apply  # noqa: E402
from symmetry_tpu.ops.quant import (  # noqa: E402
    PackedQuantizedTensor, pack_quantized, quantize, quantize_kv)
from tests.test_ops import naive_attention  # noqa: E402
from tests.test_qmm import _reference_qmatmul  # noqa: E402

D, NQ, NKV, E, F, VOCAB = 128, 32, 8, 4096, 14336, 32768
BF16_TOL = 2 * 2.0 ** -8   # two bf16 ulps, relative; absolute on O(1) values


def check(name: str, fn, want: np.ndarray, tol: float) -> dict:
    """Compile + run `fn`, compare. (main() refuses to start off a TPU, so
    interpret_mode() is False in every call a chip run makes.)"""
    row: dict = {"kernel": name}
    t0 = time.perf_counter()
    try:
        got = np.asarray(fn(), np.float32)
    except Exception as exc:  # noqa: BLE001 — the refusal IS the finding
        row.update(ok=False, error=f"{type(exc).__name__}: {exc}"[:2000])
        return row
    row["compile_run_s"] = round(time.perf_counter() - t0, 2)
    err = np.abs(got - want)
    bound = tol * (1.0 + np.abs(want))
    row.update(ok=bool(np.isfinite(got).all() and (err <= bound).all()),
               max_abs_err=float(err.max()), tol=tol,
               worst_over_bound=float((err / bound).max()))
    return row


def flash_cases(lengths=(128, 2048), nkv: int = NKV) -> list[dict]:
    rows = []
    tag = "" if nkv == NKV else f" {NQ // nkv} queries a KV head"
    for S in lengths:
        # B 2 keeps naive_attention's per-row numpy loops affordable.
        B = 2
        rng = np.random.default_rng(S)
        q = rng.normal(size=(B, S, NQ, D)).astype(np.float32)
        k = rng.normal(size=(B, S, nkv, D)).astype(np.float32)
        v = rng.normal(size=(B, S, nkv, D)).astype(np.float32)
        seq_lens = np.array([S, S // 2 + 3], np.int32)
        to = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
        # The reference sees the same bf16-rounded inputs, in float32. It
        # checks a sample of query rows: the full S² loop at 2048 is hours.
        rows_q = np.unique(np.concatenate(
            [np.arange(0, S, max(1, S // 16)), [S - 1]]))
        qs, ks, vs = (np.asarray(to(a), np.float32) for a in (q, k, v))
        q_pos = np.broadcast_to(rows_q.astype(np.int32), (B, len(rows_q)))
        want = naive_attention(qs[:, rows_q], ks, vs, q_pos, seq_lens)
        valid = rows_q[None, :] < seq_lens[:, None]          # [B, rows]

        def run(q=q, k=k, v=v, seq_lens=seq_lens, rows_q=rows_q,
                valid=valid):
            out = flash_prefill(to(q), to(k), to(v), jnp.asarray(seq_lens),
                                interpret=interpret_mode())
            got = np.asarray(out, np.float32)[:, rows_q]
            return np.where(valid[..., None, None], got, 0.0)

        rows.append(check(f"flash_prefill S={S} bf16{tag}", run,
                          np.where(valid[..., None, None], want, 0.0),
                          BF16_TOL))
    return rows


def decode_cases(capacities=(4096, 8192), nkv: int = NKV,
                 slots: int = 8) -> list[dict]:
    rows = []
    tag = "" if nkv == NKV else (f" {NQ // nkv} queries a KV head, "
                                 f"{slots} slots")
    for T in capacities:
        L, B, layer = 2, slots, 1
        ks = jax.random.split(jax.random.key(T), 3)
        q = jax.random.normal(ks[0], (B, NQ, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (L, B, T, nkv, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (L, B, T, nkv, D), jnp.bfloat16)
        edge = [T - 3, 5, T // 2, 1, 513, 1024, T, 700]
        lengths = jnp.asarray(
            [min(n, T) for n in edge][:B] + np.random.default_rng(T).integers(
                1, T + 1, max(0, B - len(edge))).tolist(), jnp.int32)

        def ref(kl, vl, ksc=None, vsc=None):
            with jax.default_matmul_precision("highest"):
                out = gqa_attention(
                    q.astype(jnp.float32)[:, None], kl, vl,
                    (lengths - 1)[:, None], lengths,
                    k_scale=ksc, v_scale=vsc)
            return np.asarray(out[:, 0], np.float32)

        rows.append(check(
            f"decode_attention T={T} bf16{tag}",
            lambda: decode_attention(q, k, v, jnp.int32(layer), lengths,
                                     interpret=interpret_mode()),
            ref(k[layer].astype(jnp.float32), v[layer].astype(jnp.float32)),
            BF16_TOL))
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        ksc, vsc = jnp.moveaxis(ksc, -1, -2), jnp.moveaxis(vsc, -1, -2)
        rows.append(check(
            f"decode_attention T={T} int8 KV{tag}",
            lambda: decode_attention(q, kq, vq, jnp.int32(layer), lengths,
                                     k_scale=ksc, v_scale=vsc,
                                     interpret=interpret_mode()),
            ref(kq[layer], vq[layer], ksc[layer], vsc[layer]), BF16_TOL))
    return rows


def append_cases(layers=4, slots=128, capacity=640) -> list[dict]:
    """`append`: the per-step cache append and the attention that goes
    with it, at the dense cells' cache."""
    from symmetry_tpu.models.llama import KVCache, append_step, write_kv

    rows = []
    L, B, T = layers, slots, capacity
    for nkv, nq in ((8, 32), (4, 28)):
        ks = jax.random.split(jax.random.key(nkv), 5)
        q = jax.random.normal(ks[0], (B, nq, D), jnp.bfloat16)
        kq, ksc = quantize_kv(jax.random.normal(ks[1], (L, B, T, nkv, D)))
        vq, vsc = quantize_kv(jax.random.normal(ks[2], (L, B, T, nkv, D)))
        ksc, vsc = jnp.moveaxis(ksc, -1, -2), jnp.moveaxis(vsc, -1, -2)
        # each slot's position: ragged, with the first row, the last row
        # and a slot already at its capacity (its write is dropped)
        pos = jnp.asarray(np.random.default_rng(nkv).integers(
            1, T, B), jnp.int32).at[:3].set(jnp.asarray([0, T - 1, T]))
        new_k, new_v = (jax.random.normal(key, (L, B, 1, nkv, D),
                                          jnp.bfloat16) for key in ks[3:])
        cache = KVCache(k=kq, v=vq, lengths=pos, k_scale=ksc, v_scale=vsc)

        def by_layer(cache):
            for l in range(L):
                cache = write_kv(cache, jnp.int32(l), pos[:, None],
                                 new_k[l], new_v[l], by_head=False)
            return cache

        def by_step(cache):
            own = [quantize_kv(x[:, :, 0]) for x in (new_k, new_v)]
            return append_step(cache, pos, (own[0][0], own[1][0],
                                            own[0][1], own[1][1]))

        want = jax.jit(by_layer)(cache)
        row = {"kernel": f"append_step {nkv} KV heads == write_kv a layer"}
        try:
            got = jax.jit(by_step)(cache)
            row["ok"] = all(bool(jnp.array_equal(getattr(got, f),
                                                 getattr(want, f)))
                            for f in ("k", "v", "k_scale", "v_scale"))
        except Exception as exc:  # noqa: BLE001 — the refusal IS the finding
            row.update(ok=False, error=f"{type(exc).__name__}: {exc}"[:2000])
        rows.append(row)

        # attention of layer 1 over the cache WITH the row (the reference,
        # and what every step ran before PR 66) against the kernel over the
        # rows below it + the own row as an operand
        layer, live = 1, np.asarray(pos) < T
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(gqa_attention(
                q.astype(jnp.float32)[:, None], want.k[layer], want.v[layer],
                pos[:, None], pos + 1, k_scale=want.k_scale[layer],
                v_scale=want.v_scale[layer])[:, 0], np.float32)

        def with_own():
            own_k, own_ks = quantize_kv(new_k[layer, :, 0])
            own_v, own_vs = quantize_kv(new_v[layer, :, 0])
            # the planes are handed through the call, aliased: donated in
            # (copies: `want` is read below) and results, as in the trunk
            return jax.jit(lambda ks_, vs_: decode_attention(
                q, kq, vq, jnp.int32(layer), pos, ks_, vs_,
                own=(own_k, own_v, own_ks, own_vs),
                interpret=interpret_mode()), donate_argnums=(0, 1))(
                    ksc + 0, vsc + 0)[0][live]

        rows.append(check(f"decode_attention + own row, {nkv} KV heads",
                          with_own, ref[live], BF16_TOL))
    return rows


def matmul_cases() -> list[dict]:
    rows = []
    shapes = {"wq/wo": (E, NQ * D), "wk/wv": (E, NKV * D), "wg/wu": (E, F),
              "wd": (F, E), "lm_head": (E, VOCAB)}
    for name, (K, N) in shapes.items():
        kw = jax.random.key(K + N)
        qt = quantize(jax.random.normal(kw, (K, N), jnp.float32) * 0.05)
        pt = pack_quantized(qt)
        if not isinstance(pt, PackedQuantizedTensor):
            rows.append({"kernel": f"w8a16 {name} {K}x{N}", "ok": False,
                         "error": "pack_quantized left the leaf flat"})
            continue
        for M in (8, 128):
            x = jax.random.normal(jax.random.key(M), (M, K), jnp.bfloat16)
            want = _reference_qmatmul(np.asarray(x, np.float32), qt)
            rows.append(check(
                f"w8a16 {name} M={M} {K}x{N} tiles{pt.q.shape[-2:]}",
                lambda x=x, pt=pt: w8a16_apply(x, pt.q, pt.scale),
                want, BF16_TOL))
    return rows


def select_cases(prompts=(6144, 14848), slots=64, capacity=16384,
                 live=25, topk=2048, repeats=30) -> list[dict]:
    """`dsa_select` (ops/sparse_attention.py) against the `jnp` form it
    replaced, at keye-vl-2.0-30b-a3b's indexer: equal sets, and the
    milliseconds of each — a prompt's tiles (the `jnp` form scores all S
    columns whichever tile, so its last tile x the tiles is its prompt) and
    one layer's decode step."""
    from symmetry_tpu.ops import sparse_attention as sa

    H, Di, tile, layers = 16, 64, sa.QUERY_TILE, 2

    def draw(key, shape, whole):
        return (jax.random.randint(key, shape, -3, 4) if whole
                else jax.random.normal(key, shape)).astype(jnp.bfloat16)

    def ms(fn, *args):
        # calls back to back and ONE fence (a fence a call would time the
        # dispatch and the fetch: ~1.8 ms here, more than a decode step's
        # selection)
        return round(timeit(fn, *args, n=repeats), 3)

    def equal(name, pairs):
        pairs = [(np.asarray(g), np.asarray(w)) for g, w in pairs]
        wrong = sum(int((g != w).sum()) for g, w in pairs)
        return {"kernel": name, "ok": wrong == 0, "mismatched": wrong,
                "selected": sum(int(w.sum()) for _, w in pairs)}

    rows = []
    for S in prompts:
        lens = jnp.asarray([S - 77], jnp.int32)

        @jax.jit
        def kernel(qi, ki, w, lens=lens):
            return sa.prefill_keep(qi, ki, w, lens, topk)[0]

        @jax.jit
        def jnp_tile(qi, ki, w, t0, lens=lens):
            part = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, t0, tile, 1)
            return sa._masks(part(qi), ki, part(w),
                             (t0 + jnp.arange(tile, dtype=jnp.int32))[None],
                             lens, topk)[0]

        def inputs(whole, S=S):
            k = jax.random.split(jax.random.key(S + whole), 3)
            return (draw(k[0], (1, S, H, Di), whole),
                    draw(k[1], (1, S, Di), whole),
                    draw(k[2], (1, S, H), whole))

        try:
            args = inputs(True)
            got = np.asarray(kernel(*args)) != 0
            # the tile under topk (no pass), the first over it, one
            # mid-prompt, the last (its end padded)
            row = equal(
                f"dsa_select prefill S={S} (sets, whole-number inputs)",
                [(got[:, t0:t0 + tile], jnp_tile(*args, jnp.int32(t0)))
                 for t0 in (topk - tile, topk, S // 2 // tile * tile,
                            S - tile)])
            args = inputs(False)
            t_kernel = ms(kernel, *args)
            t_tile = ms(jnp_tile, *args, jnp.int32(S - tile))
            row.update(kernel_prompt_ms=t_kernel,
                       kernel_tile_ms=round(t_kernel * tile / S, 3),
                       jnp_tile_ms=t_tile,
                       jnp_prompt_ms=round(t_tile * S / tile, 3))
        except Exception as exc:  # noqa: BLE001 — the refusal IS the finding
            row = {"kernel": f"dsa_select prefill S={S}", "ok": False,
                   "error": f"{type(exc).__name__}: {exc}"[:2000]}
        rows.append(row)

    # decode: the cell's cache, 25 live slots scattered over 64, lengths as
    # the traffic's (4,096-14,336 and what was generated since)
    rng = np.random.default_rng(41)
    lengths = np.zeros(slots, np.int32)
    lengths[rng.choice(slots, live, replace=False)] = rng.integers(
        capacity // 4, capacity * 7 // 8 + 1, live) + rng.integers(
            0, capacity // 32, live)
    lengths[np.flatnonzero(lengths)[:2]] = (topk - 5, capacity)
    kv = jnp.asarray(lengths)
    pos = jnp.maximum(kv - 1, 0)[:, None]

    @jax.jit
    def kernel(qi, idx, w, layer):
        return sa.cache_keep(qi, idx, w, pos, kv, topk, layer=layer)[0]

    @jax.jit
    def jnp_form(qi, idx, w, layer):
        return sa._masks(qi, jax.lax.dynamic_index_in_dim(
            idx, layer, 0, keepdims=False), w, pos, kv, topk)[0]

    try:
        for whole in (True, False):
            k = jax.random.split(jax.random.key(7 + whole), 3)
            args = (draw(k[0], (slots, 1, H, Di), whole),
                    draw(k[1], (layers, slots, capacity, Di), whole),
                    draw(k[2], (slots, 1, H), whole), jnp.int32(1))
            if whole:
                row = equal(
                    f"dsa_select decode {slots} x {capacity}, {live} live "
                    f"of mean {int(lengths[lengths > 0].mean())} (sets, "
                    f"whole-number inputs)",
                    [(kernel(*args), jnp_form(*args))])
            else:
                row.update(kernel_ms=ms(kernel, *args),
                           jnp_ms=ms(jnp_form, *args))
    except Exception as exc:  # noqa: BLE001
        row = {"kernel": "dsa_select decode", "ok": False,
               "error": f"{type(exc).__name__}: {exc}"[:2000]}
    rows.append(row)
    return rows


def decode64_cases(layers=6, slots=128, capacity=640, repeats=20
                   ) -> list[dict]:
    """The decode kernel at a head of 64 (ops/decode_attention.py, the
    pair form) at lfm2-8b-a1b's cell: right on the chip, and what one
    decode step's six attention layers cost through it and through the
    XLA path it replaced."""
    nq, K, d = 32, 8, 64
    ks = jax.random.split(jax.random.key(64), 3)
    q = jax.random.normal(ks[0], (slots, nq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (layers, slots, capacity, K, d),
                          jnp.bfloat16)
    v = jax.random.normal(ks[2], (layers, slots, capacity, K, d),
                          jnp.bfloat16)
    kq, ksc = quantize_kv(k)
    vq, vsc = quantize_kv(v)
    ksc, vsc = jnp.moveaxis(ksc, -1, -2), jnp.moveaxis(vsc, -1, -2)
    del k, v
    # batch-closed: prompts of 51-179 tokens, 128-448 asked, a stream seen
    # at a uniform point of its output; two lanes at the edges
    rng = np.random.default_rng(64)
    lengths = rng.integers(51, 180, slots) + (
        rng.random(slots) * rng.integers(128, 449, slots)).astype(np.int64)
    lengths[:2] = (1, capacity)
    lengths = jnp.asarray(np.minimum(lengths, capacity), jnp.int32)

    def pairs(x):  # as the cache holds heads of 64
        return x.reshape(layers, slots, capacity, K // 2, 2 * d)

    def xla(q, kc, vc, ksc, vsc, layer):
        def at(x):
            return jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
        return gqa_attention(
            q[:, None], at(kc).reshape(slots, capacity, K, d),
            at(vc).reshape(slots, capacity, K, d), (lengths - 1)[:, None],
            lengths, k_scale=at(ksc), v_scale=at(vsc))[:, 0]

    def step(one_layer):
        # a decode step's attention: every layer once, each fed the one
        # before it so that none can be dropped or overlapped
        @jax.jit
        def run(q, kc, vc, ksc, vsc):
            def body(layer, q):
                return one_layer(q, kc, vc, ksc, vsc, layer)
            return jax.lax.fori_loop(0, layers, body, q)
        return run

    kernel = step(lambda q, kc, vc, ksc, vsc, layer: decode_attention(
        q, kc, vc, layer, lengths, ksc, vsc, interpret=interpret_mode()))
    want = np.asarray(xla(q.astype(jnp.float32), kq, vq, ksc, vsc,
                          jnp.int32(3)), np.float32)
    row = check(
        f"decode_attention {slots} x {capacity}, {K} int8 KV heads of {d} "
        f"in pairs, mean length {int(np.asarray(lengths).mean())}",
        lambda: decode_attention(q, pairs(kq), pairs(vq), jnp.int32(3),
                                 lengths, ksc, vsc,
                                 interpret=interpret_mode()),
        want, BF16_TOL)
    def lies(x):  # how the device holds an array, and in how many bytes
        try:
            return {"layout": str(x.format.layout),
                    "bytes": int(x.on_device_size_in_bytes())}
        except Exception as exc:  # noqa: BLE001 — a report, not a check
            return {"error": f"{type(exc).__name__}: {exc}"[:200]}

    row["as_written"] = {"shape": list(kq.shape), **lies(kq)}
    row["in_pairs"] = {"shape": list(pairs(kq).shape), **lies(pairs(kq))}
    if row["ok"]:
        try:
            # calls back to back under ONE fence (a fence a call costs
            # more than the layers it would time)
            for name, fn, kc, vc in (
                    ("kernel_step_ms", kernel, pairs(kq), pairs(vq)),
                    ("xla_pairs_step_ms", step(xla), pairs(kq), pairs(vq)),
                    ("xla_as_written_step_ms", step(xla), kq, vq)):
                row[name] = round(timeit(fn, q, kc, vc, ksc, vsc,
                                         n=repeats), 3)
                del kc, vc
            row["layers_a_step"] = layers
        except Exception as exc:  # noqa: BLE001
            row.update(ok=False,
                       error=f"{type(exc).__name__}: {exc}"[:2000])
    return [row]


def fence_timing() -> dict:
    """One decode block of chip_smoke.py's engine (mistral-7b int8+kv8,
    8 slots × 4096, block 16), 10 blocks per fence."""
    import chip_smoke
    from symmetry_tpu.engine.engine import InferenceEngine
    from symmetry_tpu.provider.config import TpuConfig

    tpu = TpuConfig.from_dict(
        chip_smoke.provider_config("mistral-7b", 1)["tpu"])
    engine = InferenceEngine.from_tpu_config(tpu)
    for _ in range(3):                       # compile + settle
        sync(engine.decode_steps_dispatch())

    def timed(fence) -> list[float]:
        out = []
        for _ in range(10):
            t0 = time.perf_counter()
            toks = engine.decode_steps_dispatch()
            fence(toks)
            out.append(time.perf_counter() - t0)
            sync(toks)                       # drain before the next sample
        return sorted(out)

    res = {name: timed(fence) for name, fence in (
        ("enqueue_only", lambda t: None),
        ("block_until_ready", jax.block_until_ready),
        ("fetch_fence", sync))}
    report = {f"{k}_ms": {"p50": round(1e3 * v[len(v) // 2], 3),
                          "min": round(1e3 * v[0], 3),
                          "max": round(1e3 * v[-1], 3)}
              for k, v in res.items()}
    bur = res["block_until_ready"][len(res["block_until_ready"]) // 2]
    fetch = res["fetch_fence"][len(res["fetch_fence"]) // 2]
    report["block_until_ready_over_fetch"] = round(bur / fetch, 4)
    report["decode_step_ms_p50"] = round(1e3 * fetch / engine.decode_block,
                                         3)
    report["attention"] = engine.attention_paths()
    return report


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=["select", "decode64", "gqa16",
                                       "append"],
                    help="these rows alone, no fence timing")
    only = ap.parse_args().only
    if interpret_mode() or jax.default_backend() != "tpu":
        print(f"chip_kernels needs a TPU; JAX gave "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    # gqa16: 32 query heads on TWO K/V heads at a 64 x 640 cache (PR 61)
    rows = (flash_cases((256,), nkv=2) + decode_cases((640,), nkv=2,
                                                      slots=64)
            if only == "gqa16" else append_cases() if only == "append" else
            (select_cases() if only != "decode64" else [])
            + (decode64_cases() if only != "select" else []))
    if only is None:
        rows = (flash_cases() + decode_cases() + append_cases()
                + matmul_cases() + rows)
    for r in rows:
        print(json.dumps(r))
    out = {"device": device, "jax": jax.__version__, "kernels": rows}
    if only is None:
        out["fence"] = fence_timing()
        print(json.dumps(out["fence"]))
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           f"chip_kernels{'.' + only if only else ''}.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    failed = [r["kernel"] for r in rows if not r["ok"]]
    print(json.dumps({"ok": not failed, "failed": failed, "device": device}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
