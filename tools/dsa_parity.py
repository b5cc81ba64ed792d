"""Selection, attention and logits parity of a model with learned sparse
attention on the chip: `keye-vl-2.0-30b-a3b` as its cell serves it — every
width as published, the four layers of the cut, int8 weights, int8 K/V,
bfloat16 index keys, a 64 x 16,384 cache, one prompt through EACH of the
cell's four prefill buckets — against
`benchmarks/reference/sparse_moe_decoder.py` fed the SAME weights dequantised,
in float32 with every product at `highest`, one layer's weights at a time
(2.4 GB; the float32 model is 13 GB) and a tile of queries at a time (a
11k-token prompt's [heads, S, S] scores are 16 GB).

    python tools/dsa_parity.py --out chiprun_out/dsa_parity.json  # the chip
    JAX_PLATFORMS=cpu python tools/dsa_parity.py --preset tiny-dsa \\
        --lens 40,57,100 --buckets 64,128 --decode 6 --slots 4 \\
        --capacity 128 --query-tile 32
    python tools/dsa_parity.py --verdict chiprun_out/dsa_parity.json

Each of `--lens` prompts (from the cell's range, over 2 x topk) is
right-padded to the smallest of `--buckets` that holds it and prefilled from
empty through the trunk the served prefill program runs (`prefill_flash`: the
indexer, the threshold selection, the `dsa_flash` kernel), its row copied
into a lane of the `--slots` x `--capacity` cache as the engine's insert
copies it, then `--decode` single-token steps run teacher-forced through the
K/V and index caches over all the lanes (the decode kernel under each lane's
selection). Logits are compared at every `--stride`-th prompt position, the
prompt's last 64 and every decode step (a head row a position: 151,936
float32). The program's OWN selected sets and its attention's outputs are
tapped where they are made (`ops/sparse_attention.py prefill_keep` /
`cache_keep` / `flash_sparse`, `ops/decode_attention.py decode_attention`,
through `jax.debug.callback`), so what is compared is what ran.

Four comparisons, each per (layer, query):

(a) SELECTION AGREEMENT — the share of the program's selected positions that
    the reference, free-running in float32, also selects. Not 100%: the
    program scores from bfloat16 activations through int8 projections, and
    the 2,048th and 2,049th of up to 11k scores lie closer than that
    rounding. `agree_min` / `agree_median`, over the queries that select
    (more candidates than topk); `agree0_min` over layer 0's alone, where
    nothing upstream differs yet.
(b0) LAYER 0'S ATTENTION, THE SELECTION GIVEN — the heads' outputs (before
    `wo`) of the first layer, where no routing decision lies upstream (its
    input is the embedding row on both sides): per selecting query the
    relative error |program - reference| / |reference| with the reference
    attending over the program's own set. This is the clean reading of the
    two masked kernels at the real shapes — a mask a block off, a scale
    plane misread or a wrong row errs by the output itself (1.0) — and the
    one a LOWER PRECISION fails: the same reference with its softmax in
    bfloat16 (scores, exponentials, their sum and the probabilities each
    rounded: `softmax_bf16`, the nearest precision below the kernels'
    float32 softmax) is compared the same way and has to come out NOT ok —
    by `attn0_prefill_median` alone (the logits hide it: below).
(b) LOGITS WITH THE SELECTION GIVEN — the reference attending over the
    program's own sets in every layer: what is left is arithmetic
    (bfloat16 activations, int8 K/V) and ROUTING: a token whose 8th and 9th
    of 128 router logits lie within `eps` at any layer is left out (its
    share reported), and one flip upstream still moves every later layer's
    input. `given_median` / `given_atol`, in units of the logit scale;
    `given_clear_atol` over the tokens whose every margin is over
    `eps_clear` (no flip of their own: the arithmetic alone).
(c) FREE-RUNNING LOGITS — the reference with its own sets: (b)'s error plus
    what the sets' disagreement moves. `free_median` / `free_atol`.

The limits and their reasons are LIMITS below; PERF.md (PR 40) has the
readings they were set from. Prints one JSON line (and writes it to `--out`,
with the per-position errors, margins and agreements beside it as
`<out>.npz`); exits 0 only when the stated configuration is ok AND the
lower-precision control is not. `--verdict FILE` re-applies LIMITS to a
result line written earlier (no JAX, no chip). Touches JAX otherwise: never
beside a live engine host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

# The verdict's limits (agreement as a share; attn0 as a relative error of a
# query's output; logit errors as a share of the logit scale, max |reference
# logit|, 5.08). Each from the chip's readings of PR 40 (seed 40, prompts of
# 4,300 / 6,200 / 8,300 / 11,300 through buckets 6,144 / 8,192 / 11,264 /
# 14,848 + 8 steps: 87,760 selecting queries, 2,155 logit rows — quoted
# first — and seed 41, one prompt of 14,300, the top of the cell's range:
# 49,040 queries, 962 rows — "at 14,300"; PERF.md section 6), with its
# reason:
LIMITS = dict(
    # a token within eps of a tie between its 8th and 9th of 128 router
    # logits at any layer may route otherwise on the two sides: left out
    # (18.7%, 20.3% read), at most max_excluded may be
    eps=0.002, max_excluded=0.5,
    # (a) LAYER 0 (no difference upstream: what is left is the index
    # projections' bfloat16): min over its 21,940 queries read 0.994, median
    # 0.999 at every bucket (at 14,300: 0.9937 / 0.998 over 12,260); a
    # selection by blocks, a shorter topk or an approximate top-k reads far
    # under
    agree0_min=0.98,
    # (a) every layer: median 0.918, min 0.170 — from layer 1 on the
    # free-running reference's hidden states have parted from the program's
    # (router flips upstream), and the index scores with them: by layer the
    # median reads 0.999 / 0.92 / 0.85 / 0.86, falling with the prompt's
    # length (more candidates near the 2,048th score: median 0.860, min
    # 0.165 at 14,300); the min is the extreme of 88k queries, so its limit
    # is wide
    agree_median=0.75, agree_min=0.10,
    # (b0) layer 0's heads' outputs, per selecting query: the prompts' read
    # median 0.00426, worst 0.00471 of 21,908 (bfloat16 q, k, v and
    # probabilities); against the reference with a BFLOAT16 SOFTMAX median
    # 0.00631, its 1st percentile 0.00585 — the limit is the two medians'
    # geometric mean (22% of room under, 21% over) and the control has to
    # come out NOT ok by it (at 14,300: 0.00423 / 0.00470 of 12,252 against
    # the control's 0.00623). The steps' (int8 K/V rows) read median 0.0086,
    # worst 0.0093 of 32; a kernel's fault errs by the output itself, 1.0
    attn0_prefill_median=0.0052, attn0_decode_median=0.012, attn0_max=0.02,
    # (b) kept tokens' median read 0.0128, worst 0.308 — the worst is
    # ROUTING: it falls 0.308 / 0.235 / 0.164 / 0.017 as the margin asked
    # goes 0.005 / 0.01 / 0.02 / 0.05, the median stays 0.012 (one flip
    # among 8 of 128 experts, an extreme value: the room is wide, as
    # qwen3-next's 0.40 over 0.26 is); the 45 tokens whose every margin is
    # over eps_clear read worst 0.0169: the arithmetic alone (at 14,300:
    # median 0.0132, worst 0.336, the 7 clear tokens 0.0151)
    given_median=0.02, given_atol=0.45, eps_clear=0.05, given_clear_atol=0.03,
    # (c) (b) plus what the sets' disagreement from layer 1 on moves, so it
    # grows with the prompt as (a) falls: median 0.098, worst 0.382; at
    # 14,300 median 0.151, worst 0.427 — which the 0.15 first set from seed
    # 40 alone refused by a hundredth
    free_median=0.22, free_atol=0.6)


def verdict(r: dict, limits: dict) -> dict:
    """The checks of a result's readings `r` under `limits`: `checks` of
    the stated configuration (all must hold), `lower_checks` of the
    lower-precision control (one must NOT)."""
    a0, low = r["attn0"], r["attn0_lower"]
    checks = {
        "set_sizes": r["set_sizes"],
        "excluded": r["excluded_share"] <= limits["max_excluded"],
        "agree0_min": (r["selection_agreement_layer0"]["min"]
                       >= limits["agree0_min"]),
        "agree_min": r["selection_agreement"]["min"] >= limits["agree_min"],
        "agree_median": (r["selection_agreement"]["median"]
                         >= limits["agree_median"]),
        "attn0_prefill_median": (a0["prefill"]["median"]
                                 <= limits["attn0_prefill_median"]),
        "attn0_decode_median": (a0["decode"]["median"]
                                <= limits["attn0_decode_median"]),
        "attn0_max": max(a0["prefill"]["worst"], a0["decode"]["worst"])
        <= limits["attn0_max"],
        "given_median": r["given"]["all"]["median"] <= limits["given_median"],
        "given_atol": r["given"]["all"]["worst"] <= limits["given_atol"],
        "given_clear_atol": (r["given_clear"]["worst"]
                             <= limits["given_clear_atol"]),
        "free_median": r["free"]["all"]["median"] <= limits["free_median"],
        "free_atol": r["free"]["all"]["worst"] <= limits["free_atol"]}
    lower = {"attn0_prefill_median": (low["prefill"]["median"]
                                      <= limits["attn0_prefill_median"])}
    return {"ok": all(checks.values()), "checks": checks,
            "lower_ok": all(lower.values()), "lower_checks": lower}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preset", default="keye-vl-2.0-30b-a3b")
    ap.add_argument("--lens", default="4300,6200,8300,11300",
                    help="prompt lengths, one row each")
    ap.add_argument("--buckets", default="6144,8192,11264,14848",
                    help="the served prefill buckets; a prompt takes the "
                         "smallest that holds it")
    ap.add_argument("--decode", type=int, default=8)
    ap.add_argument("--stride", type=int, default=16)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--capacity", type=int, default=16384)
    ap.add_argument("--query-tile", type=int, default=512,
                    help="queries the reference scores and attends at a time")
    ap.add_argument("--seed", type=int, default=40)
    for name, value in LIMITS.items():
        ap.add_argument("--" + name.replace("_", "-"), type=float,
                        default=value)
    ap.add_argument("--out", default=None)
    ap.add_argument("--verdict", default=None, metavar="FILE",
                    help="re-apply the limits to a result written earlier")
    args = ap.parse_args()
    limits = {k: getattr(args, k) for k in LIMITS}

    if args.verdict:
        result = json.load(open(args.verdict))
        if "selection_agreement_layer0" not in result:
            # (a line written before the tool split layer 0 off: its .npz
            # holds every query's agreement, layer-major)
            import numpy as np

            agree = np.load(os.path.splitext(args.verdict)[0]
                            + ".npz")["agreement"]
            first = agree[:sum(ln + result["decode_steps"] - result["topk"]
                               for ln in result["prompt_lens"])]
            result["selection_agreement_layer0"] = {
                "queries": int(first.size), "min": float(first.min()),
                "median": float(np.median(first))}
        result.update(verdict(result, limits), limits=limits)
        print(json.dumps(result), flush=True)
        return 0 if result["ok"] and not result["lower_ok"] else 1

    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import sparse_moe_decoder as ref
    from symmetry_tpu.models import llama
    from symmetry_tpu.ops import decode_attention as da
    from symmetry_tpu.ops import sparse_attention as sa
    from symmetry_tpu.ops.quant import QuantizedTensor

    t0 = time.monotonic()
    cfg = llama.preset(args.preset)
    topk, L = cfg.sparse.topk, cfg.num_layers
    dtype = jnp.bfloat16
    params = llama.init_params(cfg, jax.random.key(args.seed), dtype,
                               quantize=True)

    def is_q(a):
        return isinstance(a, QuantizedTensor)

    lens = [int(x) for x in args.lens.split(",")]
    buckets = sorted(int(x) for x in args.buckets.split(","))
    bucket_of = [next(b for b in buckets if b >= ln) for ln in lens]
    n, d = len(lens), args.decode
    tokens = np.asarray(jax.random.randint(
        jax.random.key(args.seed + 1), (n, max(lens) + d), 0,
        cfg.vocab_size))
    seqs = [tokens[b, :lens[b] + d] for b in range(n)]

    # -- the program, its selections and its attention's outputs tapped
    # where they are made (ordered: a layer's before the next one's)
    keeps: list = []
    heads: list = []

    def tap_keep(fn):
        def wrapped(*a, **kw):
            keep, counts = fn(*a, **kw)
            jax.debug.callback(lambda x: keeps.append(np.asarray(x)), keep,
                               ordered=True)
            return keep, counts
        return wrapped

    def tap_out(fn):
        def wrapped(*a, **kw):
            out = fn(*a, **kw)
            jax.debug.callback(lambda x: heads.append(np.asarray(x)), out,
                               ordered=True)
            return out
        return wrapped

    sa.prefill_keep, sa.cache_keep = (tap_keep(sa.prefill_keep),
                                      tap_keep(sa.cache_keep))
    sa.flash_sparse = tap_out(sa.flash_sparse)
    da.decode_attention = tap_out(da.decode_attention)
    llama.gqa_attention = tap_out(llama.gqa_attention)   # (the XLA decode)

    def prefill(params, toks, seq_lens, cache, at):
        h, cache = llama.forward_hidden(params, cfg, toks, cache, seq_lens,
                                        prefill_flash=True)
        return llama.logits_from_hidden(params, cfg, h[:, at]), cache

    def insert(big, row, slot, length):
        def place(b, s):
            return None if b is None else jax.lax.dynamic_update_slice(
                b, s.astype(b.dtype), (0, slot) + (0,) * (b.ndim - 2))
        return big._replace(
            k=place(big.k, row.k), v=place(big.v, row.v),
            k_scale=place(big.k_scale, row.k_scale),
            v_scale=place(big.v_scale, row.v_scale),
            idx=place(big.idx, row.idx),
            lengths=big.lengths.at[slot].set(length))

    def step(params, tok, cache):
        h, cache = llama.forward_hidden(params, cfg, tok, cache)
        return llama.logits_from_hidden(params, cfg, h), cache

    prefill = jax.jit(prefill, donate_argnums=(3,))
    insert = jax.jit(insert, donate_argnums=(0,))
    step = jax.jit(step, donate_argnums=(2,))
    cache = llama.init_cache(cfg, args.slots, args.capacity, dtype,
                             quantized=True)
    # the prompt positions whose logits are compared, per row
    at = [np.unique(np.concatenate([
        np.arange(0, ln, args.stride), np.arange(max(0, ln - 64), ln)]))
        for ln in lens]
    first, prefill_keeps, heads0 = [], [], []
    for b in range(n):
        prompt = np.zeros((1, bucket_of[b]), np.int32)
        prompt[0, :lens[b]] = seqs[b][:lens[b]]
        scratch = llama.init_cache(cfg, 1, bucket_of[b], dtype,
                                   quantized=True)
        logits, scratch = prefill(params, jnp.asarray(prompt),
                                  jnp.asarray([lens[b]], jnp.int32), scratch,
                                  jnp.asarray(at[b]))
        first.append(np.asarray(logits[0], np.float32))
        jax.effects_barrier()
        prefill_keeps.append([k[0, :lens[b], :lens[b]] != 0
                              for k in keeps[-L:]])
        # layer 0's heads' outputs [S, n_q * D] of the prompt's own rows
        heads0.append(np.asarray(heads[-L], np.float32)[0, :lens[b]]
                      .reshape(lens[b], -1))
        del keeps[:], heads[:]
        cache = insert(cache, scratch, b, lens[b])
        del scratch
    steps = []
    for i in range(d):
        tok = np.zeros((args.slots, 1), np.int32)
        for b in range(n):
            tok[b, 0] = seqs[b][lens[b] + i]
        logits, cache = step(params, jnp.asarray(tok), cache)
        steps.append(np.asarray(logits[:n, 0], np.float32))
    jax.effects_barrier()
    del cache
    got = [np.concatenate([first[b], np.stack([s[b] for s in steps])])
           for b in range(n)]
    # the program's sets as [layer][S, S] per row: the prefill's rows, then
    # one row a decode step (tapped per layer, steps in order); layer 0's
    # heads' outputs the same way
    selection = []
    for b in range(n):
        s_len = lens[b] + d
        per_layer = []
        for layer in range(L):
            keep = np.zeros((s_len, s_len), bool)
            keep[:lens[b], :lens[b]] = prefill_keeps[b][layer]
            for i in range(d):
                keep[lens[b] + i] = keeps[i * L + layer][b, 0, :s_len]
            per_layer.append(keep)
        selection.append(per_layer)
        heads0[b] = np.concatenate([heads0[b]] + [
            np.asarray(heads[i * L], np.float32)[b].reshape(1, -1)
            for i in range(d)])
    del keeps[:], heads[:], prefill_keeps
    sizes_ok = all(
        (sel.sum(axis=1) == np.minimum(np.arange(sel.shape[0]) + 1, topk)
         ).all() for row in selection for sel in row)
    t_program = time.monotonic() - t0

    # -- the reference: the same weights, dequantised, float32 (every
    # product at `highest`: the reference sets it), a layer at a time;
    # once free-running, once given the sets. On the device the program
    # ran on: a host's cores reach 36 GFLOP/s through its [heads, tile, S]
    # einsums (an hour over these four prompts), the chip takes 16 minutes
    def to_f32(a):
        if is_q(a):
            return a.q.astype(jnp.float32) * jnp.expand_dims(a.scale, -2)
        return a.astype(jnp.float32)

    def one_layer(j):
        return {"layers": jax.tree.map(
            lambda a: to_f32(QuantizedTensor(a.q[j:j + 1], a.scale[j:j + 1])
                             if is_q(a) else a[j:j + 1]),
            params["layers"], is_leaf=is_q)}

    def row_error(got_rows, want_rows):
        want_rows = np.asarray(want_rows)
        return (np.linalg.norm(got_rows - want_rows, axis=1)
                / np.linalg.norm(want_rows, axis=1))

    model = llama.hf_config_sparse(cfg)
    tile = args.query_tile
    top = {"embed": to_f32(params["embed"]),
           "final_norm": to_f32(params["final_norm"]),
           "lm_head": to_f32(params["lm_head"])}
    agreement: list[np.ndarray] = []
    attn0, attn0_lower = [], []
    pos = [jnp.broadcast_to(jnp.arange(len(s)), (3, len(s)))
           for s in seqs]
    free = [ref.embed(top, jnp.asarray(s)) for s in seqs]
    given = list(free)
    margins = [[] for _ in range(n)]
    for layer in range(L):
        one = one_layer(layer)
        for b in range(n):
            mine = selection[b][layer]
            if layer == 0:
                # the control: the reference's softmax in bfloat16
                _, det = ref.run_layers(
                    one, model, given[b], pos[b], layers=[0],
                    selection={0: mine}, query_tile=tile,
                    softmax=ref.softmax_bf16)
                attn0_lower.append(row_error(heads0[b], det[0]["attn"]))
            free[b], det = ref.run_layers(
                one, model, free[b], pos[b], layers=[layer],
                query_tile=tile)
            theirs = np.asarray(det[0]["keep"])
            rows = np.arange(len(seqs[b])) >= topk    # rows that select
            agreement.append((mine & theirs)[rows].sum(axis=1)
                             / mine[rows].sum(axis=1))
            margins[b].append(np.asarray(det[0]["margin"]))
            given[b], det = ref.run_layers(
                one, model, given[b], pos[b], layers=[layer],
                selection={layer: mine}, query_tile=tile)
            margins[b].append(np.asarray(det[0]["margin"]))
            if layer == 0:
                attn0.append(row_error(heads0[b], det[0]["attn"]))
            del det, theirs
        del one
    # (the positions compared: the sampled prompt ones, every step)
    at = [np.concatenate([a, ln + np.arange(d)])
          for a, ln in zip(at, lens)]
    want_free = [np.asarray(ref.head(top, model, h[a]))
                 for h, a in zip(free, at)]
    want_given = [np.asarray(ref.head(top, model, h[a]))
                  for h, a in zip(given, at)]
    margins = np.concatenate([np.min(np.stack(m), axis=0)[a]
                              for m, a in zip(margins, at)])
    agreement0 = np.concatenate(agreement[:n])
    agreement = np.concatenate(agreement)
    scale = max(float(np.abs(w).max()) for w in want_free)
    kept = margins >= args.eps

    def errors(want):
        return np.concatenate([np.abs(g - w).max(axis=-1)
                               for g, w in zip(got, want)]) / scale

    e_given, e_free = errors(want_given), errors(want_free)
    is_decode = np.concatenate([a >= ln for a, ln in zip(at, lens)])

    def stats(e, mask=None):
        e = e if mask is None else e[mask]
        return ({"n": int(e.size), "median": float(np.median(e)),
                 "p99": float(np.quantile(e, 0.99)),
                 "worst": float(e.max())} if e.size else
                {"n": 0, "median": 0.0, "p99": 0.0, "worst": 0.0})

    def by_kind(rows):
        """Layer 0's per-query errors of every row, split: the prompt's
        selecting queries (at or past topk), its dense ones, the steps."""
        sel = np.concatenate([r[topk:ln] for r, ln in zip(rows, lens)])
        dense = np.concatenate([r[:min(topk, ln)]
                                for r, ln in zip(rows, lens)])
        dec = np.concatenate([r[ln:] for r, ln in zip(rows, lens)])
        return {"prefill": stats(sel), "prefill_dense": stats(dense),
                "decode": stats(dec)}

    by_margin = {}
    for eps in (0.0, 0.002, 0.005, 0.01, 0.02, 0.05):
        ok = margins >= eps
        by_margin[str(eps)] = {"share": float(ok.mean()),
                               "given": stats(e_given, ok)}
    dev = jax.devices()[0]
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "preset": args.preset, "layers": L, "topk": topk,
        "prompt_lens": lens, "buckets": bucket_of, "decode_steps": d,
        "slots": args.slots, "capacity": args.capacity,
        "tokens_compared": int(e_free.size), "logit_scale": scale,
        "units": "share of logit_scale", "eps": args.eps,
        "set_sizes": bool(sizes_ok),
        "excluded_share": float(1 - kept.mean()),
        "selection_agreement": {
            "queries": int(agreement.size),
            **({"min": float(agreement.min()),
                "p01": float(np.quantile(agreement, 0.01)),
                "p10": float(np.quantile(agreement, 0.10)),
                "median": float(np.median(agreement)),
                "mean": float(agreement.mean()),
                "share_identical": float((agreement == 1).mean())}
               if agreement.size else {"min": 1.0, "median": 1.0})},
        "selection_agreement_layer0": {
            "queries": int(agreement0.size),
            "min": float(agreement0.min()) if agreement0.size else 1.0,
            "median": (float(np.median(agreement0)) if agreement0.size
                       else 1.0)},
        "attn0": by_kind(attn0), "attn0_lower": by_kind(attn0_lower),
        "given": {"all": stats(e_given, kept),
                  "prefill": stats(e_given, kept & ~is_decode),
                  "decode": stats(e_given, kept & is_decode)},
        "free": {"all": stats(e_free, kept),
                 "prefill": stats(e_free, kept & ~is_decode),
                 "decode": stats(e_free, kept & is_decode)},
        "given_clear": stats(e_given, margins >= args.eps_clear),
        "by_margin": by_margin, "limits": limits,
        "program_s": round(t_program, 1),
        "total_s": round(time.monotonic() - t0, 1)}
    result.update(verdict(result, limits))
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
        # per position, for "which positions are off": the compared
        # positions of every row in order, their errors and router margins;
        # layer 0's per-query errors of every row
        np.savez(os.path.splitext(args.out)[0] + ".npz",
                 position=np.concatenate(at), e_given=e_given, e_free=e_free,
                 margin=margins, is_decode=is_decode, agreement=agreement,
                 attn0=np.concatenate(attn0),
                 attn0_lower=np.concatenate(attn0_lower))
    print(line, flush=True)
    return 0 if result["ok"] and not result["lower_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
