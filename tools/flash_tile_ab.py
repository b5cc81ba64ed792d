"""The flash prefill at the latent-attention cell's shape, tile against tile.

One prompt of 32 heads, keys of 192 and values of 128 (kanana-2-30b-a3b
expanded), at each of `--buckets` with `--fill` of the bucket a real prompt:
`ops/flash.py flash_prefill` in its 128 x 128 tiles, then
`flash_prefill_wide` at each of `--blocks`; milliseconds a call (one layer of
one dispatch), the share of the chip's bf16 peak by the ACTIVE causal pairs
(`benchmarks/lib/mla_bytes.py`'s count), and the worst |wide - 128| over the
prompt's rows.

`--kv-heads 4 --heads 28 --dk 128 --window 4096` is the window / full
attention cell's shape instead (smallthinker-21b-a3b: 28 query heads over 4
KV heads of 128; `--window 0` its full layers, `--window 4096` its window
layers, the active pairs then the window's).

Needs a TPU: `python tools/flash_tile_ab.py`. Writes
chiprun_out/flash_tile_ab.json (`--out` another name).
"""
import argparse
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
from symmetry_tpu.ops import flash
from symmetry_tpu.ops.interpret import interpret_mode

H, D, DV = 32, 192, 128
PEAK = 197e12  # v5e bf16


def timed(fn, *args, reps: int) -> tuple[float, jax.Array]:
    sync(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / reps, out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", default="9344,6912")
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--fill", type=float, default=0.95)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--heads", type=int, default=H)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="KV heads (default: one a query head)")
    ap.add_argument("--dk", type=int, default=D)
    ap.add_argument("--dv", type=int, default=DV)
    ap.add_argument("--window", type=int, default=0,
                    help="a sliding window of this many keys (0: none)")
    ap.add_argument("--out", default="flash_tile_ab.json")
    args = ap.parse_args()
    window = args.window or None
    kv_heads = args.kv_heads or args.heads
    interp = interpret_mode()
    rows = []
    for S in (int(b) for b in args.buckets.split(",")):
        n = int(S * args.fill)
        ks = jax.random.split(jax.random.key(S), 3)
        q, k, v = (jax.random.normal(kk, (1, S, h, d), jnp.bfloat16)
                   for kk, h, d in zip(ks, (args.heads, kv_heads, kv_heads),
                                       (args.dk, args.dk, args.dv)))
        lens = jnp.asarray([n], jnp.int32)
        pairs = n * (n + 1) / 2 if window is None or n <= window else (
            window * (window + 1) / 2 + (n - window) * window)
        flops = 2 * args.heads * (args.dk + args.dv) * pairs
        wide_kw = {} if window is None else {"window": window}
        base_s, base = timed(
            lambda q, k, v, n: flash.flash_prefill(q, k, v, n, window=window,
                                                   interpret=interp),
            q, k, v, lens, reps=args.reps)
        row = {"bucket": S, "prompt": n, "tile_128_ms": 1e3 * base_s,
               "tile_128_peak_share": flops / base_s / PEAK, "wide": {}}
        for block in (int(b) for b in args.blocks.split(",")):
            try:
                s, out = timed(
                    lambda q, k, v, n, block=block: flash.flash_prefill_wide(
                        q, k, v, n, block=block, interpret=interp,
                        **wide_kw),
                    q, k, v, lens, reps=args.reps)
            except Exception as exc:  # noqa: BLE001 — a tile Mosaic refuses
                row["wide"][block] = {"error": repr(exc)[:300]}
                continue
            diff = np.abs(np.asarray(out[0, :n], np.float32)
                          - np.asarray(base[0, :n], np.float32)).max()
            row["wide"][block] = {"ms": 1e3 * s,
                                  "peak_share": flops / s / PEAK,
                                  "worst_diff": float(diff)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out), "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
