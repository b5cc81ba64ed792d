"""The forms of a recurrent layer's decode step timed against each other on
one chip, a layer's state at a time: the jnp recurrence (XLA compiles it as
two passes over the state — a reduction for the read-out, then the in-place
update), the Pallas kernel (`ops/ssm_step.py`: one pass) at each head tile
tried, and a bare copy of the state through the same pipeline (the kernel's
blocks and aliasing with nothing computed: the rate the chip gives a read and
a write at once, which is what the kernel can reach).

`--kind mamba` (the default) is granite-4.0-h-small's cell: 128 slots x 128
heads x 64 x 128 float32 = 537 MB a layer, `models/mamba2.py recurrence`
against `ssm_step`. `--kind gdn` is qwen3-next-80b-a3b's: 128 slots x 32
value heads x 128 x 128 float32 = 268 MB a layer, `models/gdn.py recurrence`
against `gdn_step`. `--layers` of them lie as one donated stack that a
`lax.scan` steps layer by layer, as the model's trunk does (the layer is the
kernel's scalar-prefetch argument; the jnp form slices and `.at[j].set`s).
Prints one JSON line: ms a layer and GB/s of state for each form, the floor
(the state read once and written once at 819 GB/s), and the kernel's error
against the jnp form on the same inputs — the reading
`ops/ssm_step.py TILE_BYTES` is set from.

`--groups G` (mamba): B and C are G rows a slot and head h reads row
h // (heads / G) — nemotron-3-nano-30b-a3b's cell is `--shape 64,64,64,128
--groups 8 --layers 4 --tiles 16,32,64`: 64 slots x 64 heads x 64 x 128
float32 = 134 MB a layer.

    python tools/ssm_step_ab.py [--kind gdn]            # on the chip
    JAX_PLATFORMS=cpu python tools/ssm_step_ab.py [--kind gdn] --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

KINDS = {
    # the cell's state (slots, heads, plane), its layers, the head tiles tried
    "mamba": ("128,128,64,128", 4, "16,32,64,128"),
    "gdn": ("128,32,128,128", 3, "8,16,32"),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kind", choices=list(KINDS), default="mamba")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--shape", help="slots,heads,d_head,d_state (mamba) or "
                                    "slots,value heads,d_key,d_value (gdn)")
    ap.add_argument("--tiles")
    ap.add_argument("--groups", type=int, default=1,
                    help="groups of B and C a slot (mamba)")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from _bench_util import timeit
    from symmetry_tpu.models import gdn, mamba2
    from symmetry_tpu.ops import ssm_step as op
    from symmetry_tpu.ops.interpret import interpret_mode

    shape, layers, tiles = KINDS[args.kind]
    B, H, P, N = (int(v) for v in (args.shape or shape).split(","))
    L = args.layers or layers
    tiles = [int(t) for t in (args.tiles or tiles).split(",")]
    if args.tiny:
        (B, H, P, N), L, tiles = (2, 8, 16, 16), 2, [2, 8]
    keys = jax.random.split(jax.random.key(34), 6)
    decay = jax.random.uniform(keys[0], (L, B, H), jnp.float32, 0.2, 0.999)
    G = args.groups
    if args.tiny and G > 1:
        tiles = [t for t in tiles if t % (H // G) == 0 or (H // G) % t == 0]
    if args.kind == "mamba":
        recurrence, step = mamba2.recurrence, op.ssm_step
        rows = (L, B, N) if G == 1 else (L, B, G, N)
        xs = (decay,
              jax.random.normal(keys[1], (L, B, H, P), jnp.float32),
              jax.random.normal(keys[2], rows, jnp.float32),
              jax.random.normal(keys[3], rows, jnp.float32),
              jax.random.normal(keys[4], (L, B, H, P), jnp.float32))
    else:
        recurrence, step = gdn.recurrence, op.gdn_step

        def unit(key):      # l2-normed, as the layer's q and k are
            x = jax.random.normal(key, (L, B, H, P), jnp.float32)
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        xs = (decay,
              jax.random.uniform(keys[1], (L, B, H), jnp.float32),
              unit(keys[2]) * P ** -0.5, unit(keys[3]),
              jax.random.normal(keys[4], (L, B, H, N), jnp.float32))

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
        return lambda stack, j, *inputs: step(
            stack, j, *inputs, tile=tile, interpret=interpret_mode())

    def copy(tile):
        """The kernel's pipeline — the same blocks of the same stack, in
        and out — with nothing computed between."""
        def body(layer_ref, s_ref, y_ref, s_out_ref):
            y_ref[...] = jnp.zeros_like(y_ref)
            s_out_ref[...] = s_ref[...]

        def form(stack, j, *inputs):
            return op.over_stack(
                body, "bare_copy", stack, op.address(j), tile, [], (),
                pl.BlockSpec((1, 8, 128), lambda i, t, lay: (i, 0, 0)),
                jax.ShapeDtypeStruct((B, 8, 128), jnp.float32),
                interpret=interpret_mode())
        return form

    layer_bytes = 2 * B * H * P * N * 4
    want_state, want_y = (np.asarray(v) for v in trunk(xla)(fresh(), xs))
    out = {"device": jax.devices()[0].device_kind, "kind": args.kind,
           "layers": L,
           "state": {"slots": B, "heads": H, "plane": [P, N], "groups": G,
                     "bytes_a_layer": layer_bytes // 2},
           "floor_ms_per_layer": round(1e3 * layer_bytes / 819e9, 4),
           "forms": {}}
    tiles = [t for t in tiles if H % t == 0]
    for name, form in ([("xla", xla)]
                       + [(f"pallas tile {t}", kernel(t)) for t in tiles]
                       + [(f"bare copy tile {t}", copy(t)) for t in tiles]):
        fn = trunk(form)
        row = {}
        if name.startswith("pallas"):
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
