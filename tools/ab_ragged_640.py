"""Decode attention at the dense cells' shape, kernel against the XLA path.

For each of mistral-7b and qwen2-7b (int8 weights + int8 KV, 128 slots x
640, the benchmark's serving shape): first the kernel's result against
`gqa_attention` on one layer of random cache with lengths {0, 1, 127, 128,
129, 640} mixed in the batch, then the decode trunk (`forward_hidden`, one
position a slot) timed with every slot at 64 / 173 / 320 / 620 of 640 and
at the closed cells' mix (107 live slots spread over 32..600, 21 empty) —
once through ops/decode_attention.py as `_layer` routes it, once with the
route forced to "xla" (the staged per-layer slice the kernel replaced).
The forcing lives here, not in the program: the served path has no switch.

A preset that generates by diffusion over blocks (sdar-30b-a3b-chat) is
measured at what ITS forwards over the cache carry: a block of query
positions a slot from a block boundary (lengths rounded down to one), the
kernel against `gqa_attention(block_len=)`; `--tiles` also times the kernel
at other WAYS / MAX_TILE_LANES (set here, for this process alone).

`--kernel` times the kernel ALONE instead of the trunks, at the int8 shapes
whose cells it weighs most in — mistral-7b's and qwen2-7b's 128 x 640 (8
and 4 KV heads) and smallthinker-21b-a3b's two leaves, 64 rings of 4,096
rows all full and 64 full rows of 11,776 at the cell's lengths (5k-10k,
~7.6k a slot), 4 KV heads under 28 query heads: ms a call (a call a layer,
back to back under one fence), us an item (a slot's block of BLOCK_ROWS
rows: 128 KB of K and of V) and GB/s of live rows. (PR 59 also read them
with the scale planes' layout product replaced by ones, wrong numbers: an
item 15% cheaper, 0.527 -> 0.449 us at mistral's shape; PERF.md section 6.)
`--kernel --own` times each shape a second time as a decode step of the
homogeneous trunk calls it (PR 66): the position's own row an operand, a
slot's softmax started from it.

Needs a TPU: `python tools/ab_ragged_640.py [--tiles] [preset ...]`, or
`python tools/ab_ragged_640.py --kernel [--own]`. Writes
chiprun_out/ab_ragged_640.json.
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np
from _bench_util import sync
from symmetry_tpu.models import llama
from symmetry_tpu.ops import decode_attention as da
from symmetry_tpu.ops.attention import gqa_attention
from symmetry_tpu.ops.interpret import interpret_mode

B, T = 128, 640
OCCUPANCIES = (64, 173, 320, 620)
TILES = (da.WAYS, da.MAX_TILE_LANES)


def cell_mix() -> np.ndarray:
    """The closed cells' steady state: 107 of 128 slots live, lengths
    spread over prompt 32..160 + output 0..448, the rest empty."""
    rng = np.random.default_rng(0)
    lengths = np.zeros(B, np.int32)
    live = rng.permutation(B)[:107]
    lengths[live] = rng.integers(32, 600, size=live.size)
    return lengths


def kernel_shapes() -> dict:
    """label -> (capacity, KV heads, query heads, layers, calls, lengths):
    the cache a kernel-only row reads, `calls` calls a timed program."""
    rng = np.random.default_rng(0)
    return {
        "mistral-7b 128 x 640, cell mix": (T, 8, 32, 4, 8, cell_mix()),
        "mistral-7b 128 x 640, at 620": (T, 8, 32, 4, 8,
                                         np.full(B, 620, np.int32)),
        "qwen2-7b 128 x 640, at 620": (T, 4, 28, 4, 8,
                                       np.full(B, 620, np.int32)),
        "smallthinker ring 64 x 4,096, full": (
            4096, 4, 28, 9, 9, np.full(64, 4096, np.int32)),
        "smallthinker full 64 x 11,776, 5k-10k": (
            11776, 4, 28, 3, 6, rng.integers(5120, 10240, 64, np.int32)),
    }


def kernel_rows() -> dict:
    """The kernel alone over a random int8 cache, a call a layer."""
    out_ = {}
    for label, (cap, K, nq, L, calls, lengths) in kernel_shapes().items():
        slots, D = lengths.size, 128
        ks = jax.random.split(jax.random.key(2), 5)
        q = jax.random.normal(ks[0], (slots, nq, D), jnp.bfloat16)
        k, v = (jax.random.randint(key, (L, slots, cap, K, D), -127, 128,
                                   jnp.int8) for key in ks[1:3])
        ksc, vsc = (jax.random.uniform(key, (L, slots, K, cap), jnp.float32,
                                       0.005, 0.02) for key in ks[3:])
        block_t = da.geometry(slots, cap, K)[1]
        items = int(np.maximum(-(-lengths // block_t), 1).sum())
        live = int(lengths.sum()) * K * (2 * D + 8)   # K, V and two scales
        row = (k[0, :, 0], v[0, :, 0], ksc[0, :, :, 0], vsc[0, :, :, 0])
        n_ = jnp.asarray(lengths, jnp.int32)
        for own in (False, True) if "--own" in sys.argv else (False,):
            # (with its own row the call hands the scale planes through,
            # aliased: they are donated in and come back, as in the trunk)
            @functools.partial(jax.jit, donate_argnums=(3, 4))
            def step(q, k, v, ksc, vsc, n_):
                total = 0   # a q a call: identical calls would be one call
                for c in range(calls):
                    got = da.decode_attention(
                        q * (1 + c), k, v, jnp.int32(c % L), n_, ksc, vsc,
                        interpret=interpret_mode(),
                        **({"own": row} if own else {}))
                    if own:
                        got, ksc, vsc = got
                    total = total + got
                return total, ksc, vsc

            for i in range(3 + 20):   # 3 to warm up, 20 timed
                if i == 3:
                    sync(out)
                    t0 = time.perf_counter()
                out, ksc, vsc = step(q, k, v, ksc, vsc, n_)
            sync(out)
            ms = (time.perf_counter() - t0) / 20 * 1e3 / calls
            name = label + (", own row" if own else "")
            out_[name] = {"ms_a_call": round(ms, 4), "items": items,
                          "us_an_item": round(ms * 1e3 / items, 4),
                          "live_gb_s": round(live / ms / 1e6, 1)}
            print(f"kernel {name}: {ms:.4f} ms a call, {items} items, "
                  f"{ms * 1e3 / items:.4f} us an item, "
                  f"{live / ms / 1e6:.1f} GB/s of live rows", flush=True)
        del q, k, v, ksc, vsc
    return out_


def block_of(cfg) -> int:
    """Query positions a slot in a forward over the cache."""
    diffusion = getattr(cfg, "diffusion", None)
    return 1 if diffusion is None else diffusion.block


def parity(cfg) -> dict:
    """Worst |kernel - gqa_attention| on one layer, lengths mixed."""
    K, nq, D = cfg.num_kv_heads, cfg.num_heads, cfg.dim_per_head
    S = block_of(cfg)
    ks = jax.random.split(jax.random.key(1), 5)
    q = jax.random.normal(ks[0], (B, S, nq, D), jnp.bfloat16)
    k = jax.random.randint(ks[1], (2, B, T, K, D), -127, 128, jnp.int8)
    v = jax.random.randint(ks[2], (2, B, T, K, D), -127, 128, jnp.int8)
    ksc = jax.random.uniform(ks[3], (2, B, K, T), jnp.float32, 0.005, 0.02)
    vsc = jax.random.uniform(ks[4], (2, B, K, T), jnp.float32, 0.005, 0.02)
    lengths = np.resize([0, 1, 127, 128, 129, 640], B)
    if S > 1:   # the end of a block that starts on a block boundary
        lengths = np.where(lengths > 0, np.maximum(lengths // S * S, S), 0)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = da.decode_attention(q[:, 0] if S == 1 else q, k, v, jnp.int32(1),
                              lengths, ksc, vsc, window=cfg.sliding_window,
                              interpret=interpret_mode())
    got = got.reshape(q.shape)
    want = gqa_attention(q, k[1], v[1],
                         jnp.maximum(lengths - S, 0)[:, None]
                         + jnp.arange(S)[None], lengths,
                         sliding_window=cfg.sliding_window,
                         k_scale=ksc[1], v_scale=vsc[1],
                         **({"block_len": S} if S > 1 else {}))
    live = np.asarray(lengths) > 0
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    return {"max_abs_err": float(err[live].max()),
            "finite": bool(np.isfinite(np.asarray(got, np.float32)).all()),
            "ref_scale": float(np.abs(np.asarray(want, np.float32)[live]).max())}


def make_trunk(cfg, params, use_kernel: bool):
    """The decode trunk, compiled once per route; lengths are an argument,
    so every occupancy runs the same executable."""
    real = da.geometry
    if not use_kernel:
        da.geometry = lambda *a, **k: None
    try:
        def step(p, t, c, n_):
            # every call decodes at the SAME lengths: the occupancy under
            # test does not drift over the timed iterations
            return llama.forward_hidden(p, cfg, t, c._replace(lengths=n_))

        trunk = jax.jit(step, donate_argnums=(2,))
        cache = llama.init_cache(cfg, B, T, jnp.bfloat16, quantized=True)
        tok = jnp.ones((B, block_of(cfg)), jnp.int32)
        h, cache = trunk(params, tok, cache, jnp.zeros((B,), jnp.int32))
        sync(h)
    finally:
        da.geometry = real

    def timed(lengths, n: int = 20) -> float:
        nonlocal cache
        lengths = jnp.asarray(lengths, jnp.int32)
        for _ in range(3):
            h, cache = trunk(params, tok, cache, lengths)
        sync(h)
        t0 = time.perf_counter()
        for _ in range(n):
            h, cache = trunk(params, tok, cache, lengths)
        sync(h)
        return (time.perf_counter() - t0) / n * 1e3

    return timed


def save(report: dict) -> None:
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ab_ragged_640.json", "w") as fh:
        json.dump(report, fh, indent=1)


def main() -> None:
    report = {"device": jax.devices()[0].device_kind, "shape": [B, T]}
    names = [a for a in sys.argv[1:] if not a.startswith("--")]
    if "--kernel" in sys.argv:
        report["kernel"] = kernel_rows()
        save(report)
        return
    for name in names or ("mistral-7b", "qwen2-7b"):
        cfg = llama.preset(name)
        S = block_of(cfg)
        row = {"geometry": da.geometry(B, T, cfg.num_kv_heads, queries=S),
               "queries": S, "parity": parity(cfg), "trunk_ms": {}}
        print(name, "parity", row["parity"], flush=True)
        params = llama.init_params(cfg, jax.random.key(0), jnp.bfloat16,
                                   quantize=True)
        cases = {str(o): np.full(B, o // S * S, np.int32)
                 for o in OCCUPANCIES}
        cases["cell-mix"] = cell_mix() // S * S
        # (route, WAYS, MAX_TILE_LANES): the served constants, then others
        routes = [("xla",) + TILES, ("kernel",) + TILES]
        if "--tiles" in sys.argv:
            routes += [(f"kernel ways {w} lanes {n}", w, n)
                       for w, n in ((2, 128), (1, 128), (4, 256), (2, 256))]
        for route, da.WAYS, da.MAX_TILE_LANES in routes:
            jax.clear_caches()   # the constants are read when traced
            # one cache on the chip at a time
            timed = make_trunk(cfg, params, route != "xla")
            for label, lengths in cases.items():
                row["trunk_ms"].setdefault(label, {})[route] = round(
                    timed(lengths), 2)
            del timed
        da.WAYS, da.MAX_TILE_LANES = TILES
        for label, ms in row["trunk_ms"].items():
            print(f"{name} {label:>8} of 640: " + "  ".join(
                f"{route} {t:6.2f} ms" for route, t in ms.items())
                + f"  ({ms['xla'] - ms['kernel']:+.2f})", flush=True)
        report[name] = row
        del params
        save(report)


if __name__ == "__main__":
    main()
