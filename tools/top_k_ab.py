"""The forms of the sampler's top-64 window timed against each other on one
chip, at the shapes the cells' programs hand `ops/sampling.py _top_k`: the
served form (`served`: `_top_k` itself, whatever `top_k_route` names for the
vocabulary) against the forms it was chosen from — the selection by stages at
each list of group widths tried (`128` alone is the two-stage form of PR 26:
one sort of 64 x 128 = 8,192 candidates a row; `128,32` keeps 64 sub-groups
of 32 of those and ranks 2,048), each with the stages' rows ranked as the
logits lie (`[R, S, n]`), with what a stage ranks reshaped to `[R * S, n]`
(`+rows`), with the logits themselves reshaped (`+flat`), and with a kept
position mapped back through the chosen groups by comparison instead of an
element gather (`+pick`). `--single` adds one `lax.top_k` over the vocabulary.

Each form is a jitted function of float32 logits and a temperature a row
(the division the sampler makes before the selection), run `--repeats` times
inside a profiler capture of its own; its time is the device's, read from
the capture's program line (`benchmarks/lib/xplane.py reduce_profile`), and
the longest ops of the capture stand beside it (`--ops`). Every form's
values and indices are checked equal to `lax.top_k`'s on the same input
first. Prints one JSON line a shape — the reading `ops/sampling.py`'s widths
and forms are set from (PERF.md §6, PR 55).

    python tools/top_k_ab.py                       # on the chip, ~8 min
    python tools/top_k_ab.py --mesh-model 4 --shapes 64,32000   # four chips
    JAX_PLATFORMS=cpu python tools/top_k_ab.py --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

# sdar's block of four first, then every other cell's [slots, vocabulary]
SHAPES = ("128,4,151936;128,152064;128,151936;128,32768;128,65536;"
          "128,100352;64,151936;64,128256")
FORMS = "128;128,16;128,32;128,32,8"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=SHAPES,
                    help="';'-separated logits shapes, each R[,S],V")
    ap.add_argument("--forms", default=FORMS,
                    help="';'-separated lists of group widths")
    ap.add_argument("--single", action="store_true",
                    help="also time one lax.top_k over the vocabulary")
    ap.add_argument("--mesh-model", type=int, default=0,
                    help="shard the vocabulary over this many chips")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--ops", type=int, default=6)
    ap.add_argument("--out", help="also append each line to this file")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData

    from lib.xplane import find_xplane, reduce_profile
    from symmetry_tpu.ops import sampling

    cap = sampling.SAMPLING_TOP_CAP
    if args.tiny:
        args.shapes, args.repeats = "8,4,20000;8,20000", 2
    sharding = None
    if args.mesh_model:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from symmetry_tpu.parallel.mesh import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(data=1, model=args.mesh_model))
        sharding = NamedSharding(mesh, P(None, "model"))

    def staged(widths, how):
        """`sampling._grouped_top_k` with the forms that lost still in it:
        `rows` / `pick` as the module has them, or neither."""
        rows = sampling._rows if "rows" in how else (lambda a: a)

        def select(x):
            lead = x.shape[:-1]
            if "flat" in how:
                x = x.reshape(-1, x.shape[-1])
            kept, stages = x, []
            for width in widths:
                *at, n = kept.shape
                groups = -(-n // width)
                grouped = jnp.pad(
                    kept, [(0, 0)] * len(at) + [(0, groups * width - n)],
                    constant_values=-jnp.inf).reshape(*at, groups, width)
                _, chosen = jax.lax.top_k(rows(grouped.max(-1)), cap)
                chosen = jnp.sort(chosen, axis=-1).reshape(*at, cap)
                kept = jnp.take_along_axis(
                    grouped, chosen[..., None], axis=-2).reshape(
                        *at, cap * width)
                stages.append((chosen, width))
            values, pos = jax.lax.top_k(rows(kept), cap)
            values = values.reshape(*lead, cap)
            pos = pos.reshape(*kept.shape[:-1], cap)
            for chosen, width in reversed(stages):
                if "pick" in how:
                    hit = (pos // width)[..., :, None] == jnp.arange(cap)
                    group = jnp.sum(
                        jnp.where(hit, chosen[..., None, :], 0), axis=-1)
                else:
                    group = jnp.take_along_axis(chosen, pos // width, axis=-1)
                pos = group * width + pos % width
            return values, pos.reshape(*lead, cap)
        return select

    def scaled(logits, temperature):  # as _masked_top_logits divides
        return logits / temperature[
            (slice(None),) + (None,) * (logits.ndim - 1)]

    def timed(name, select, logits, temperature):
        def fn(logits, temperature):
            return select(scaled(logits, temperature))
        fn.__name__ = name
        jitted = jax.jit(fn)
        got = jax.block_until_ready(jitted(logits, temperature))
        trace = tempfile.mkdtemp(prefix="top_k_ab.")
        try:
            with jax.profiler.trace(trace):
                t0 = time.perf_counter()
                for _ in range(args.repeats):
                    out = jitted(logits, temperature)
                jax.block_until_ready(out)
                wall = (time.perf_counter() - t0) / args.repeats * 1e3
            red = reduce_profile(ProfileData.from_file(find_xplane(trace)))
        finally:
            shutil.rmtree(trace, ignore_errors=True)
        runs = [v for n, v in red["programs"].items() if name in n]
        ms = (sum(v[0] for v in runs) / max(sum(v[1] for v in runs), 1)
              * 1e3 if runs else None)  # None: no device plane (the CPU)
        ops = [[n, round(s / args.repeats * 1e3, 4)]
               for n, s in red["ops"][:args.ops]]
        return got, ms, wall, ops

    for shape in args.shapes.split(";"):
        dims = tuple(int(d) for d in shape.split(","))
        rng = np.random.default_rng(dims[-1])
        logits = jnp.asarray(rng.normal(size=dims) * 3,
                             jnp.bfloat16).astype(jnp.float32)
        if sharding is not None:
            logits = jax.device_put(logits, sharding)
        temperature = jnp.full((dims[0],), 0.7, jnp.float32)
        want = jax.lax.top_k(scaled(logits, temperature), cap)
        line = {"shape": list(dims), "device": jax.devices()[0].device_kind,
                "mesh_model": args.mesh_model, "ms": {}, "wall_ms": {},
                "ops": {}, "equal": True}
        forms = [("served", lambda x: sampling._top_k(x, cap))]
        if args.single:
            forms.append(("single", lambda x: jax.lax.top_k(x, cap)))
        hows = (("", "rows", "rows+pick", "flat") if len(dims) > 2
                else ("", "pick"))
        for widths in args.forms.split(";"):
            w = tuple(int(v) for v in widths.split(","))
            forms += [(widths + ("+" + how if how else ""), staged(w, how))
                      for how in hows]
        for label, select in forms:
            name = "topk_" + "".join(c if c.isalnum() else "_"
                                     for c in label)
            got, ms, wall, ops = timed(name, select, logits, temperature)
            equal = all(bool(jnp.array_equal(g, w_))
                        for g, w_ in zip(got, want))
            line["equal"] &= equal
            line["ms"][label] = ms and round(ms, 4)
            line["wall_ms"][label] = round(wall, 4)
            line["ops"][label] = ops
            if not equal:
                line.setdefault("unequal", []).append(label)
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
