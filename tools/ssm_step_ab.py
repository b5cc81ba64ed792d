"""The two forms of the Mamba-2 decode step timed against each other on one
chip, a layer's state at a time: the jnp recurrence (`models/mamba2.py
recurrence`, which XLA compiles as two passes over the state — a reduction
for `S C`, then the in-place update) against the Pallas kernel
(`ops/ssm_step.py`: one pass) at each head tile tried.

The state is granite-4.0-h-small's cell: 128 slots x 128 heads x 64 x 128
float32 = 537 MB a layer, `--layers` of them as one donated stack that a
`lax.scan` steps layer by layer, as the model's trunk does (the layer is the
kernel's scalar-prefetch argument; the jnp form slices and `.at[j].set`s).
Prints one JSON line: ms a layer and GB/s of state for each form, the floor
(the state read once and written once at 819 GB/s), and the kernel's error
against the jnp form on the same inputs — the reading
`ops/ssm_step.py TILE_BYTES` is set from.

    python tools/ssm_step_ab.py                     # on the chip
    JAX_PLATFORMS=cpu python tools/ssm_step_ab.py --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--shape", default="128,128,64,128",
                    help="slots,heads,d_head,d_state")
    ap.add_argument("--tiles", default="16,32,64,128")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from _bench_util import timeit
    from symmetry_tpu.models.mamba2 import recurrence
    from symmetry_tpu.ops.interpret import interpret_mode
    from symmetry_tpu.ops.ssm_step import ssm_step

    B, H, P, N = (int(v) for v in args.shape.split(","))
    L, tiles = args.layers, [int(t) for t in args.tiles.split(",")]
    if args.tiny:
        (B, H, P, N), L, tiles = (2, 8, 16, 16), 2, [2, 8]
    keys = jax.random.split(jax.random.key(34), 6)
    xs = (jax.random.uniform(keys[0], (L, B, H), jnp.float32, 0.2, 0.999),
          jax.random.normal(keys[1], (L, B, H, P), jnp.float32),
          jax.random.normal(keys[2], (L, B, N), jnp.float32),
          jax.random.normal(keys[3], (L, B, N), jnp.float32),
          jax.random.normal(keys[4], (L, B, H, P), jnp.float32))

    def fresh():
        return jax.random.normal(keys[5], (L, B, H, P, N), jnp.float32)

    def trunk(form):
        def run(stack, xs):
            def body(stack, layer):
                j, inputs = layer
                y, stack = form(stack, j, *inputs)
                return stack, y
            return jax.lax.scan(body, stack,
                                (jnp.arange(L, dtype=jnp.int32), xs))
        return jax.jit(run, donate_argnums=(0,))

    def xla(stack, j, *inputs):
        y, new = recurrence(
            jax.lax.dynamic_index_in_dim(stack, j, 0, keepdims=False),
            *inputs)
        return y, stack.at[j].set(new)

    def kernel(tile):
        return lambda stack, j, *inputs: ssm_step(
            stack, j, *inputs, tile=tile, interpret=interpret_mode())

    layer_bytes = 2 * B * H * P * N * 4
    want_state, want_y = (np.asarray(v) for v in trunk(xla)(fresh(), xs))
    out = {"device": jax.devices()[0].device_kind, "layers": L,
           "state": {"slots": B, "heads": H, "d_head": P, "d_state": N,
                     "bytes_a_layer": layer_bytes // 2},
           "floor_ms_per_layer": round(1e3 * layer_bytes / 819e9, 4),
           "forms": {}}
    for name, form in [("xla", xla)] + [(f"pallas tile {t}", kernel(t))
                                        for t in tiles if H % t == 0]:
        fn = trunk(form)
        row = {}
        if name != "xla":
            state, y = fn(fresh(), xs)
            row["y_rel_err"] = float(np.max(np.abs(np.asarray(y) - want_y))
                                     / np.max(np.abs(want_y)))
            row["state_rel_err"] = float(
                np.max(np.abs(np.asarray(state) - want_state))
                / np.max(np.abs(want_state)))
            del state, y
        holder = [fresh()]

        def call():
            holder[0], y = fn(holder[0], xs)
            return y

        ms = timeit(call, n=args.repeats) / L
        row["ms_per_layer"] = round(ms, 4)
        row["state_gb_s"] = round(layer_bytes / ms / 1e6, 1)
        out["forms"][name] = row
        del holder
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
