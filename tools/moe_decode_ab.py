"""The two forms of the expert FFN (`models/moe.py`) timed against each
other on one chip's share of a layer.

At decode (64 slots, one token each) every expert is hit and both forms
stream the same weight bytes — is the dense mixture (every expert computes
every token; 4x the FLOPs) faster than the routed form (sort +
`lax.ragged_dot`)? From how many tokens a dispatch does routing pay?

One chip holds what one chip of `mesh {model: 4}` holds of mixtral-8x7b: all
8 experts at a quarter of the FFN width ([8, 4096, 3584] int8 x 3), `--layers`
layers of them scanned like the model's trunk, random weights and tokens.
Prints one JSON line: ms a layer for each form at each token count — the
reading `models/moe.py ROUTED_MIN_TOKENS` is set from.

    python tools/moe_decode_ab.py            # on the chip
    python tools/moe_decode_ab.py --shape 72,10,4096,768 --layers 2 \
        --tokens 128,256,512,1024,2048       # granite-4.0-h-small's layer
    JAX_PLATFORMS=cpu python tools/moe_decode_ab.py --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--tokens", default="64,128,256,2048")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--shape", default="8,2,4096,3584",
                    help="experts,top_k,hidden,ffn_width held here; "
                         "granite-4.0-h-small whole is 72,10,4096,768")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from symmetry_tpu.models.moe import (
        _dense_mixture, _routed_ffn, moe_route)
    from symmetry_tpu.ops.quant import make_leaf

    X, k, D, F = (int(v) for v in args.shape.split(","))
    if args.tiny:
        D, F = 64, 32
    L = args.layers
    keys = jax.random.split(jax.random.key(0), 4)
    wg = make_leaf(keys[0], (L, X, D, F), D ** -0.5, jnp.bfloat16, True)
    wu = make_leaf(keys[1], (L, X, D, F), D ** -0.5, jnp.bfloat16, True)
    wd = make_leaf(keys[2], (L, X, F, D), F ** -0.5, jnp.bfloat16, True)
    router = make_leaf(keys[3], (L, D, X), D ** -0.5, jnp.bfloat16)

    def dense_mixture(x, router, wg, wu, wd):
        y, _ = _dense_mixture(x, jnp.ones((x.shape[0],), bool), router, wg,
                              wu, wd, k)
        return y

    def routed(x, router, wg, wu, wd):
        y, _ = _routed_ffn(x, jnp.ones((x.shape[0],), bool), router, wg,
                           wu, wd, k)
        return y

    def trunk(form):
        def run(x, layers):  # the weights are arguments, never constants
            def body(h, lp):
                return h + form(h, *lp).astype(h.dtype), None
            return jax.lax.scan(body, x, layers)[0]
        return jax.jit(run)

    layers = (router, wg, wu, wd)

    out = {"device": jax.devices()[0].device_kind, "layers": L,
           "shape": {"experts": X, "top_k": k, "hidden": D, "ffn_slice": F},
           "ms_per_layer": {}}
    for T in [int(t) for t in args.tokens.split(",")]:
        x = jax.random.normal(jax.random.key(T), (T, D), jnp.bfloat16)
        row = {}
        for name, form in (("routed", routed), ("dense_mixture",
                                                 dense_mixture)):
            fn = trunk(form)
            fn(x, layers).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                y = fn(x, layers)
            y.block_until_ready()
            row[name] = round(
                1e3 * (time.perf_counter() - t0) / args.repeats / L, 4)
        row["route"] = moe_route(T, X, k)
        out["ms_per_layer"][str(T)] = row
    weight_bytes = 3 * X * D * F
    out["weight_stream_floor_ms_per_layer"] = round(
        1e3 * weight_bytes / 819e9, 4) if not args.tiny else None
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
