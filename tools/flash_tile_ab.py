"""The flash prefill at the latent-attention cell's shape, tile against tile.

One prompt of 32 heads, keys of 192 and values of 128 (kanana-2-30b-a3b
expanded), at each of `--buckets` with `--fill` of the bucket a real prompt:
`ops/flash.py flash_prefill` in its 128 x 128 tiles, then
`flash_prefill_wide` at each of `--blocks`; milliseconds a call (one layer of
one dispatch), the share of the chip's bf16 peak by the ACTIVE causal pairs
(`benchmarks/lib/mla_bytes.py`'s count), and the worst |wide - 128| over the
prompt's rows.

Needs a TPU: `python tools/flash_tile_ab.py`. Writes
chiprun_out/flash_tile_ab.json.
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
    args = ap.parse_args()
    interp = interpret_mode()
    rows = []
    for S in (int(b) for b in args.buckets.split(",")):
        n = int(S * args.fill)
        ks = jax.random.split(jax.random.key(S), 3)
        q, k, v = (jax.random.normal(kk, (1, S, args.heads, d), jnp.bfloat16)
                   for kk, d in zip(ks, (D, D, DV)))
        lens = jnp.asarray([n], jnp.int32)
        flops = 2 * args.heads * (D + DV) * n * (n + 1) / 2
        base_s, base = timed(
            lambda q, k, v, n: flash.flash_prefill(q, k, v, n,
                                                   interpret=interp),
            q, k, v, lens, reps=args.reps)
        row = {"bucket": S, "prompt": n, "tile_128_ms": 1e3 * base_s,
               "tile_128_peak_share": flops / base_s / PEAK, "wide": {}}
        for block in (int(b) for b in args.blocks.split(",")):
            try:
                s, out = timed(
                    lambda q, k, v, n, block=block: flash.flash_prefill_wide(
                        q, k, v, n, block=block, interpret=interp),
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
    with open("chiprun_out/flash_tile_ab.json", "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
