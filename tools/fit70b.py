"""70B int8 fit plan: per-device byte table + reduced-geometry dryrun.

Two halves (PR 19's acceptance row):

1. `--table`: jax.eval_shape the llama3-70b int8 param tree and the KV
   cache under a (dcn_data x ici_model) mesh and the default megatron
   rules, and fold each abstract leaf down to PER-DEVICE bytes. No
   weight is ever materialized — the table is pure shape arithmetic, so
   it runs in milliseconds on any host and answers "does 70B int8 fit
   a v5e-16 (2 hosts x 8 chips, 16 GB HBM each)?" before anyone rents
   the slice. Each int8 matmul leaf also gets a packability verdict at
   the given TP degree (TPU tile floors against the PER-SHARD dims), so
   the table doubles as the fused-dequant coverage plan: which leaves
   ride the packed kernel and which degrade to the mixed dot.

2. `--dryrun`: boot the REAL fused engine at 70B geometry — hidden
   8192, 64 q heads / 8 KV heads, intermediate 28672 — on a virtual
   8-device CPU mesh (TP=8), reduced to 1 layer and an 8192 vocab so
   Pallas interpret mode finishes in tool time (interpret unrolls the
   tile grid into the compiled program; 80 layers x 128k vocab would
   run for hours computing nothing extra — the per-layer programs are
   identical). Greedy decode must produce tokens and the packed-leaf
   count must be positive.

Default (no flags) runs both and writes MULTICHIP_r06.json (git-ignored:
an output, which nothing reads).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

HBM_PER_DEVICE = {"v5e": 16e9, "v5p": 95e9, "v4": 32e9}

_DRYRUN_SNIPPET = r"""
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_default_matmul_precision", "highest")
import dataclasses
from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
from symmetry_tpu.engine.tokenizer import ByteTokenizer
from symmetry_tpu.models import init_params, param_logical_axes, preset
from symmetry_tpu.models.llama import quantize_params
from symmetry_tpu.parallel import MeshSpec, build_mesh, shardings_for

cfg = dataclasses.replace(preset("llama3-70b"), num_layers=1,
                          vocab_size=8192)
mesh = build_mesh(MeshSpec(data=1, model=8))
params = init_params(cfg, jax.random.key(0), jnp.bfloat16)
params = jax.device_put(params, shardings_for(param_logical_axes(cfg), mesh))
params = quantize_params(params)
eng = InferenceEngine(cfg, params, ByteTokenizer(), mesh=mesh,
                      max_slots=2, max_seq_len=64, prefill_buckets=(16,),
                      cache_dtype=jnp.bfloat16, fused_dequant=True)
from symmetry_tpu.ops.quant import PackedQuantizedTensor
packed = sum(isinstance(l, PackedQuantizedTensor)
             for l in jax.tree.leaves(
                 eng.params,
                 is_leaf=lambda x: isinstance(x, PackedQuantizedTensor)))
assert packed > 0, "fused engine packed no leaves at 70B geometry"
first = eng.prefill_and_insert(0, list(b"fit plan"), SamplingParams())
toks = [int(first)]
for _ in range(2):
    toks.append(int(eng.decode_steps()[0][0]))
assert all(0 <= t < cfg.vocab_size for t in toks), toks
print("FIT70B_DRYRUN_OK packed=%d toks=%s" % (packed, toks))
"""


def per_device_table(dcn_data: int, ici_model: int) -> dict:
    """Abstract per-device byte table — eval_shape only, zero FLOPs."""
    import jax
    import jax.numpy as jnp

    from symmetry_tpu.models import preset
    from symmetry_tpu.models.llama import (
        QUANT_KEYS, cache_logical_axes, init_cache, init_params,
        param_logical_axes, quantized_logical_axes,
    )
    from symmetry_tpu.ops.qmm import (
        _TPU_MIN_BK, _TPU_MIN_BN, W8A16_BLOCK_K, W8A16_BLOCK_N,
        pick_w8a16_block,
    )
    from symmetry_tpu.ops.quant import QuantizedTensor
    from symmetry_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec

    cfg = preset("llama3-70b")
    axis_sizes = {"data": dcn_data, "model": ici_model}

    # Abstract trees: int8 param tree (QUANT_KEYS leaves quantize to
    # QuantizedTensor{q:int8, scale:f32}) and its logical-axes mirror.
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), jnp.bfloat16,
                            quantize=True))
    axes = quantized_logical_axes(param_logical_axes(cfg))

    def shard_parts(logical) -> int:
        parts = 1
        for mesh_ax in logical_to_spec(logical, DEFAULT_RULES):
            if mesh_ax is not None:
                parts *= axis_sizes.get(mesh_ax, 1)
        return parts

    def leaf_bytes(leaf) -> int:
        return math.prod(leaf.shape) * leaf.dtype.itemsize

    rows = []

    def walk(node, anode, prefix):
        if isinstance(node, dict):
            for name in node:
                walk(node[name], anode[name], prefix + (name,))
            return
        path = "/".join(prefix)
        if isinstance(node, QuantizedTensor):
            parts = shard_parts(anode.q)
            total = leaf_bytes(node.q) + leaf_bytes(node.scale)
            per_dev = (leaf_bytes(node.q) // shard_parts(anode.q)
                       + leaf_bytes(node.scale) // shard_parts(anode.scale))
            # Packability at this TP: per-shard last-two dims against
            # the TPU tile floors — the same gate pack_params applies.
            *_, K, N = node.q.shape
            k_parts = shard_parts((anode.q[-2],))
            n_parts = shard_parts((anode.q[-1],))
            if K % k_parts or N % n_parts:
                verdict = "mixed_dot:shard_indivisible"
            else:
                bk = pick_w8a16_block(K // k_parts, W8A16_BLOCK_K,
                                      floor=_TPU_MIN_BK)
                bn = pick_w8a16_block(N // n_parts, W8A16_BLOCK_N,
                                      floor=_TPU_MIN_BN)
                verdict = (f"packed:bk={bk},bn={bn}"
                           if bk and bn else "mixed_dot:shard_untileable")
        else:
            parts = shard_parts(anode)
            total = leaf_bytes(node)
            per_dev = total // parts
            verdict = "dense"
        rows.append({"leaf": path, "shape": list(getattr(
            node, "q", node).shape), "bytes_total": total,
            "bytes_per_device": per_dev, "shard_parts": parts,
            "layout": verdict})

    walk(params, axes, ())

    # KV cache at the serving shape the fit question is asked for:
    # 8 slots x 8192 positions, int8 KV (tpu.kv_quant) — batch on the
    # dcn data axis, kv_heads on the ici model axis.
    slots, capacity = 8, 8192
    kv = jax.eval_shape(lambda: init_cache(cfg, slots, capacity,
                                           jnp.bfloat16, quantized=True))
    kv_axes = cache_logical_axes(quantized=True)
    kv_rows = []
    for field in kv._fields:
        leaf, logical = getattr(kv, field), getattr(kv_axes, field)
        if leaf is None:
            continue
        parts = shard_parts(logical)
        kv_rows.append({"leaf": f"kv/{field}",
                        "shape": list(leaf.shape),
                        "bytes_total": leaf_bytes(leaf),
                        "bytes_per_device": leaf_bytes(leaf) // parts,
                        "shard_parts": parts, "layout": "dense"})

    param_dev = sum(r["bytes_per_device"] for r in rows)
    kv_dev = sum(r["bytes_per_device"] for r in kv_rows)
    packed_dev = sum(r["bytes_per_device"] for r in rows
                     if r["layout"].startswith("packed"))
    return {
        "model": "llama3-70b",
        "mesh": {"dcn_data": dcn_data, "ici_model": ici_model,
                 "n_devices": dcn_data * ici_model},
        "kv_shape": {"slots": slots, "capacity": capacity,
                     "kv_quant": "int8"},
        "params_bytes_per_device": param_dev,
        "kv_bytes_per_device": kv_dev,
        "total_bytes_per_device": param_dev + kv_dev,
        "packed_bytes_per_device": packed_dev,
        "fits": {name: param_dev + kv_dev < hbm
                 for name, hbm in HBM_PER_DEVICE.items()},
        "leaves": rows + kv_rows,
    }


def run_dryrun(timeout: int = 1800) -> dict:
    """Reduced-layer 70B-geometry fused TP=8 dryrun in a subprocess
    pinned to a virtual 8-device CPU mesh (self-contained: works on a
    host whose ambient backend is a single TPU chip — same contract as
    __graft_entry__.dryrun_multichip)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
           and not k.startswith("TPU")
           and not k.startswith("PJRT")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    try:
        proc = subprocess.run([sys.executable, "-c", _DRYRUN_SNIPPET],
                              env=env, capture_output=True, text=True,
                              timeout=timeout)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = -1
        out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) \
            else (e.stdout or "")
        err = f"timeout after {timeout}s"
    return {"rc": rc, "ok": rc == 0 and "FIT70B_DRYRUN_OK" in out,
            "stdout_tail": out[-500:], "stderr_tail": err[-500:]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--table", action="store_true",
                    help="byte table only (skip the dryrun)")
    ap.add_argument("--dryrun", action="store_true",
                    help="dryrun only (skip the byte table)")
    ap.add_argument("--dcn-data", type=int, default=2)
    ap.add_argument("--ici-model", type=int, default=8)
    ap.add_argument("--out", default=None,
                    help="write the combined JSON here "
                         "(default MULTICHIP_r06.json at the repo root)")
    args = ap.parse_args()
    both = not (args.table or args.dryrun)

    result: dict = {"round": "r06"}
    if args.table or both:
        result["fit_table"] = per_device_table(args.dcn_data,
                                               args.ici_model)
        t = result["fit_table"]
        gb = 1 / 1e9
        print(f"[fit70b] params {t['params_bytes_per_device'] * gb:.2f} "
              f"GB/dev + kv {t['kv_bytes_per_device'] * gb:.2f} GB/dev "
              f"= {t['total_bytes_per_device'] * gb:.2f} GB/dev on "
              f"{t['mesh']['n_devices']} devices "
              f"(fits v5e-16GB: {t['fits']['v5e']})")
    if args.dryrun or both:
        print("[fit70b] dryrun: 1-layer 70B geometry, fused TP=8, "
              "8 virtual CPU devices ...", flush=True)
        result["dryrun"] = run_dryrun()
        print(f"[fit70b] dryrun ok={result['dryrun']['ok']} "
              f"rc={result['dryrun']['rc']}")
        if not result["dryrun"]["ok"]:
            print(result["dryrun"]["stderr_tail"], file=sys.stderr)
    result["ok"] = all(result[k]["ok"] if k == "dryrun"
                       else result[k]["fits"]["v5e"]
                       for k in ("fit_table", "dryrun") if k in result)

    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "MULTICHIP_r06.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[fit70b] wrote {out}")
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
