"""The forms of the expert FFN (`models/moe.py`) timed against each other
on one chip's share of a layer: the routed form over `lax.ragged_dot`, the
routed form over the Pallas grouped matmul (`ops/gmm.py`; int8 stacks on
one device) and the dense mixture.

At decode (64 slots, one token each) every expert is hit and every form
streams the same weight bytes — is the dense mixture (every expert computes
every token; 4x the FLOPs) faster than the routed form? From how many
tokens a dispatch does routing pay?

One chip holds what one chip of `mesh {model: 4}` holds of mixtral-8x7b: all
8 experts at a quarter of the FFN width ([8, 4096, 3584] int8 x 3), `--layers`
layers of them scanned like the model's trunk, random weights and tokens.
Prints one JSON line: ms a layer for each form at each token count, the
weight stream's floor (every expert read once at 819 GB/s) and each form's
share of it (the kernel reads the HIT experts alone, so it can read over 1
where few tokens hit few experts) — the reading `models/moe.py
ROUTED_MIN_TOKENS` and `ROUTED_FROM` are set from. `--row-tiles` times the
kernel at other row tiles than `ops/gmm.py geometry` chooses. `--calls`
times a gated layer's gate + up products as kernel calls ALONE (`calls`: two
calls and the product between them, the pair call that serves them since
PR 64, and — the price of a visit by removal — the two calls against ONE
call over a [D, 2F] concatenation of the two stacks).

    python tools/moe_decode_ab.py            # on the chip
    python tools/moe_decode_ab.py --shape 72,10,4096,768 --layers 2 \
        --tokens 128,256,512,1024,2048       # granite-4.0-h-small's layer
    python tools/moe_decode_ab.py --shape 512,10,2048,512 --layers 2 \
        --row-tiles 32,64,256                # qwen3-next-80b-a3b's
    python tools/moe_decode_ab.py --shape 128,6,2688,1856 --held 32 \
        --ungated relu2 --layers 2 \
        --tokens 16,32,64,128,256,512,1024,2048,8192
                                             # nemotron-3-nano-30b-a3b's:
                                             # 32 of 128 held, two matrices
    JAX_PLATFORMS=cpu python tools/moe_decode_ab.py --tiny

`--held N` holds the first N of the experts routed over (a chip's share: the
stacks carry N experts, the router its full width, pairs on the others are
dropped); `--ungated ACT` times two-matrix experts, ACT(x W_up) W_down.
"""

from __future__ import annotations

import argparse
import contextlib
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
    ap.add_argument("--row-tiles", default="",
                    help="also time the kernel at these row tiles")
    ap.add_argument("--shape", default="8,2,4096,3584",
                    help="experts,top_k,hidden,ffn_width held here; "
                         "granite-4.0-h-small whole is 72,10,4096,768")
    ap.add_argument("--held", type=int, default=0,
                    help="experts held of those routed over (0: all)")
    ap.add_argument("--calls", action="store_true",
                    help="also time the gate + up kernel calls alone")
    ap.add_argument("--ungated", default="",
                    help="two-matrix experts under this activation "
                         "(e.g. relu2); default: gated, silu")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from symmetry_tpu.models.moe import (
        _dense_mixture, _routed_ffn, grouped_matmul_form, moe_route)
    from symmetry_tpu.ops import gmm
    from symmetry_tpu.ops.interpret import interpret_mode
    from symmetry_tpu.ops.quant import make_leaf

    X, k, D, F = (int(v) for v in args.shape.split(","))
    if args.tiny:
        D, F = 64, 32
    L = args.layers
    held = args.held or X       # the stacks carry the held experts alone
    routing = {}
    if args.held:
        routing["held"] = (0, held)
    if args.ungated:
        routing["act"] = args.ungated
    routing = routing or None
    keys = jax.random.split(jax.random.key(0), 4)
    wg = (None if args.ungated else
          make_leaf(keys[0], (L, held, D, F), D ** -0.5, jnp.bfloat16, True))
    wu = make_leaf(keys[1], (L, held, D, F), D ** -0.5, jnp.bfloat16, True)
    wd = make_leaf(keys[2], (L, held, F, D), F ** -0.5, jnp.bfloat16, True)
    router = make_leaf(keys[3], (L, D, X), D ** -0.5, jnp.bfloat16)
    names = ("wu", "wd") if args.ungated else ("wg", "wu", "wd")

    def valid(x):
        return jnp.ones((x.shape[0],), bool)

    def leaves(layer):      # (router, wg or None, wu, wd) of one layer
        router, *rest = layer[1:]
        return (router, *((None,) if args.ungated else ()), *rest)

    def dense_mixture(x, layer, stacks):
        return _dense_mixture(x, valid(x), *leaves(layer), k, routing)[0]

    def routed(x, layer, stacks):
        return _routed_ffn(x, valid(x), *leaves(layer), k, None, routing)[0]

    def routed_kernel(x, layer, stacks):
        # as models/hybrid.py calls it: the stacks whole, the layer's index
        return _routed_ffn(x, valid(x), *leaves(layer), k,
                           (stacks, layer[0]), routing)[0]

    def trunk(form):
        def run(x, layers):  # the weights are arguments, never constants
            stacks = dict(zip(names, layers[2:]))

            def body(h, layer):
                return h + form(h, layer, stacks).astype(h.dtype), None
            return jax.lax.scan(body, x, layers)[0]
        return jax.jit(run)

    def timed(fn, *operands):      # ms a layer, compiled before the clock
        fn(*operands).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            y = fn(*operands)
        y.block_until_ready()
        return round(1e3 * (time.perf_counter() - t0) / args.repeats / L, 4)

    def ms_a_layer(form, x):
        return timed(trunk(form), x, layers)

    def calls(T):
        """ms a layer of gate + up over T tokens routed uniformly, the
        kernel calls alone; each form scanned over the layers, the stacks
        its arguments."""
        sizes = jnp.bincount(jax.lax.top_k(jax.random.normal(
            jax.random.key(T), (T, held)), k)[1].reshape(-1), length=held)
        rows = jax.random.normal(jax.random.key(T + 1), (T * k, D),
                                 jnp.bfloat16)
        both = jax.tree.map(lambda g, u: jnp.concatenate([g, u], -1), wg, wu)
        act, interpret = jax.nn.silu, interpret_mode()

        def call(q, scale, i, **kw):
            return gmm.grouped_matmul(rows, q, scale, sizes, i,
                                      interpret=interpret, **kw)

        def whole(out):     # read every element, keep one number
            return jnp.sum(out.astype(jnp.float32))

        forms = {
            "two_calls+product": ((wg, wu), lambda g, u, i: whole(
                (act(call(*g, i)) * call(*u, i)).astype(rows.dtype))),
            "pair_call": ((wg, wu), lambda g, u, i: whole(
                call((g.q, u.q), (g.scale, u.scale), i, act=act))),
            # the calls' own time: eight rows of each result are read
            "two_calls": ((wg, wu), lambda g, u, i: whole(
                call(*g, i)[:8] + call(*u, i)[:8])),
            "one_call_over_[D,2F]": ((both,), lambda b, i: whole(
                call(*b, i)[:8]))}
        out = {"hit_experts": int((sizes > 0).sum())}
        for name, (stacks, form) in forms.items():
            fn = jax.jit(lambda *a, form=form: jax.lax.scan(
                lambda acc, i: (acc + form(*a, i), None), jnp.float32(0),
                jnp.arange(L, dtype=jnp.int32))[0])
            out[name] = timed(fn, *stacks)
        return out

    layers = (jnp.arange(L, dtype=jnp.int32), router,
              *(() if args.ungated else (wg,)), wu, wd)
    forms = {"routed": routed, "routed_kernel": routed_kernel,
             "dense_mixture": dense_mixture}
    weight_bytes = len(names) * held * D * F
    floor = None if args.tiny else round(1e3 * weight_bytes / 819e9, 4)

    out = {"device": jax.devices()[0].device_kind, "layers": L,
           "shape": {"experts": X, "top_k": k, "hidden": D, "ffn_slice": F,
                     "held": held, "matrices": len(names)},
           "weight_stream_floor_ms_per_layer": floor, "ms_per_layer": {}}
    for T in [int(t) for t in args.tokens.split(",")]:
        x = jax.random.normal(jax.random.key(T), (T, D), jnp.bfloat16)
        row = {name: ms_a_layer(form, x) for name, form in forms.items()}
        # the three forms are one mathematics: each against the routed
        # form over `ragged_dot`, as a share of its largest value
        want = trunk(routed)(x, layers).astype(jnp.float32)
        row["max_rel_diff"] = {
            name: round(float(jnp.max(jnp.abs(
                trunk(form)(x, layers).astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want))), 5)
            for name, form in forms.items() if name != "routed"}
        row["grouped_matmul"] = grouped_matmul_form(wu, T * k)
        for tile in [int(t) for t in args.row_tiles.split(",") if t]:
            with _row_tile(gmm, tile):
                row[f"routed_kernel@{tile}"] = ms_a_layer(routed_kernel, x)
        if floor:
            row["floor_share"] = {name: round(floor / row[name], 3)
                                  for name in forms}
        if args.calls and not args.ungated:
            row["calls"] = calls(T)
        row["route"] = moe_route(T, X, k, args.held or None)
        out["ms_per_layer"][str(T)] = row
    print(json.dumps(out), flush=True)
    return 0


@contextlib.contextmanager
def _row_tile(gmm, tile: int):
    """`ops/gmm.py` with another row tile, for the sweep alone (the
    kernel's own is a constant no caller chooses; a traced kernel keeps
    the tile it was traced with, hence the cleared caches)."""
    import jax

    before = gmm.ROW_TILE
    gmm.ROW_TILE = tile
    jax.clear_caches()
    try:
        yield
    finally:
        gmm.ROW_TILE = before
        jax.clear_caches()


if __name__ == "__main__":
    sys.exit(main())
