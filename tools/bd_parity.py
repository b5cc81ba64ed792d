"""Attention and logits parity of a model that generates by diffusion over
blocks, on the chip: `sdar-30b-a3b-chat` as its cell serves it — every width as
published, the twelve layers of the cut, int8 weights, int8 K/V, a 128 x 640
cache, the engine's OWN admission and decode programs (`bd_prefill`,
`bd_decode_block`: 4 blocks a dispatch, 2 denoise forwards and a commit a
block) — against
`benchmarks/reference/block_diffusion_moe_decoder.py` fed the SAME weights
dequantised, in float32 with every product at `highest`, one layer's weights
at a time (2.4 GB; the float32 model is 30 GB).

    python tools/bd_parity.py --out chiprun_out/bd_parity.json     # the chip
    JAX_PLATFORMS=cpu python tools/bd_parity.py --preset tiny-bd \\
        --lens 20,33,50,63 --buckets 32,64 --slots 8 --capacity 128
    python tools/bd_parity.py --verdict chiprun_out/bd_parity.*.json

One prompt through EACH of `--buckets`, with `len % block` = 0, 1, 2, 3 among
them, is admitted (a dispatch each: the whole blocks prefilled through the
flash kernel under the block mask, the opening block denoised and committed,
the row copied into a lane), then `--dispatches` decode dispatches of
`--decode-block` tokens generate four blocks a lane through the cache. The
program's own choices are tapped where they are made, through `jax.debug.callback`: the tokens of every forward over a block
and its final hidden states (`forward_hidden`), the logits, candidates and
confidences (`diffusion_candidates`), the known plane and what was taken
(`diffusion_unmask`), every layer's attention output over the cache
(`decode_attention` with the block as its q tile where the cache has a
geometry, `gqa_attention` elsewhere: the admission's scratch, a tiny head)
and the prefill's (`flash_prefill`). Each tapped forward is
TEACHER-FORCED into the reference: its full forward over [the lane's committed
context || the block as it stood] under the block mask.

Comparisons (greedy lanes, so a candidate is the argmax):

(a0) LAYER 0'S ATTENTION — the heads' outputs (before `wo`) of the first
     layer, where no routing decision lies upstream: per query the relative
     error |program - reference| / |reference|. `attn0_prefill_*`: the flash
     kernel under the block mask, the prompt's own bfloat16 K/V.
     `attn0_block_*`: a block's four queries over the int8 cache (its own rows
     written and read back as int8), against the reference with K/V rounded
     to int8 the same way. A mask a position off, a scale plane misread or a
     block not committed errs by the output itself.
(b)  LOGITS — the masked positions of every denoise forward (the program's
     head) and all four positions of every commit forward (its hidden states
     through the same head), in units of the logit scale. A position whose 8th
     and 9th router logits lie within `eps` at any layer of its own sequence
     is left out (its share reported): the two sides may route it otherwise.
(c)  CHOICES — given the program's own logits, the reference's rule
     (`unmask`) takes the positions the program took: exact.

Controls, each of which has to come out NOT ok by the limit CONTROLS names:
the reference with its softmax in bfloat16 (`softmax_bf16`, the nearest
precision below the kernels' float32 softmax) by `attn0_prefill_median`; the
reference under the PLAIN CAUSAL mask, and the reference whose context lacks
the block committed last (a SKIPPED COMMIT), each by `attn0_block_median` at
layer 0 AND, carried through all the layers and the head for the forwards of
`--control-lanes`, by `logit_median` (`causal_mask_logits`,
`skipped_commit_logits`); and the reference with its K/V rounded to INT4 a
(position, head) — the nearest precision below the int8 rows the program
keeps — by `logit_median` (`kv_int4_logits`).

Prints one JSON line (and writes it to `--out`); exits 0 only when the stated
configuration is ok AND every control is not. `--verdict FILE [FILE ...]`
re-applies LIMITS to lines written earlier, one a seed (no JAX): beside each
limit the LARGEST reading of the stated configuration and the SMALLEST of its
controls over the files. Touches JAX otherwise: never beside a live engine
host.
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

# The verdict's limits, each set between two readings on the chip (PR 47, the
# review round: seeds 47-50, prompts of 60 / 101 / 202 / 303 through buckets
# 64 / 128 / 256 / 512 — the left-overs 0 / 1 / 2 / 3 — and four blocks a
# lane in one dispatch of 16; PERF.md section 6): the LARGEST the stated
# configuration read over its seeds and the SMALLEST a control read. Relative
# errors of a query's output; logit errors as a share of the logit scale
# (max |reference logit|, 4.5-5.0).
LIMITS = dict(
    # a position within eps of a tie between its 8th and 9th of 128 router
    # logits at any layer may route otherwise on the two sides: left out
    # (26-33% of 189 rows a seed: twelve layers of 128-way routing), at most
    # max_excluded may be
    eps=0.002, max_excluded=0.5,
    # (a0) the flash kernel under the block mask, bfloat16 q / k / v and
    # probabilities: medians 0.00446-0.00456, worst 0.00503-0.00530 of 660
    # queries a seed; against the reference with a BFLOAT16 SOFTMAX medians
    # 0.00581-0.00594. The two ranges do not overlap and lie 1.27x apart, which
    # is all a bfloat16 probability is worth against a float32 one; the limit
    # is the geometric mean of the largest stated and the smallest control
    # median (13% of room each side). A mask a position off errs by the output
    # itself
    attn0_prefill_median=0.00515, attn0_prefill_max=0.02,
    # (a0) a block's queries over the int8 cache against the reference with
    # int8-rounded K/V (bfloat16 inputs round a few int8 steps otherwise):
    # medians 0.00746-0.00769, worst 0.0082-0.0086 of 240 queries a seed (20
    # contexts from 60 to 316 positions); the reference under the plain causal
    # mask reads 0.062-0.077, with the block committed last left out
    # 0.136-0.149 — each has to come out NOT ok by the median's limit, 1.6x
    # over the largest reading and 5x under the smallest control
    attn0_block_median=0.012, attn0_block_max=0.04,
    # (b) logits in units of the logit scale: bfloat16 activations through 12
    # layers of int8 matmuls and 128-way routing (a flip upstream moves every
    # later layer's input): kept rows read medians 0.0386-0.0445, worst
    # 0.083-0.106 of 126-139 a seed. Held against three controls carried
    # through all the layers for the forwards of lanes 0-1 (where the stated
    # reference reads 0.035-0.046): a skipped commit 0.095-0.116, int4 K/V
    # 0.196-0.224, the plain causal mask 0.473-0.522. The median's limit is the
    # geometric mean of the largest stated median and the smallest control's
    # (0.0445 and 0.0947: 1.46x of room each side). The worst row's limit
    # guards single rows (a wrong head row errs by the scale itself): 1.9x over
    # the largest worst row read, under the int4 (0.24) and causal (0.55)
    # controls' worst rows and NOT under every skipped commit's (0.14-0.21),
    # which its median catches
    logit_median=0.065, logit_max=0.2,
)


# which limit each control has to break
CONTROLS = {"softmax_bf16": "attn0_prefill_median",
            "causal_mask": "attn0_block_median",
            "skipped_commit": "attn0_block_median",
            "causal_mask_logits": "logit_median",
            "skipped_commit_logits": "logit_median",
            "kv_int4_logits": "logit_median"}
# the stated configuration's readings, each beside its limit
READINGS = {"attn0_prefill_median": ("attn0_prefill", "median"),
            "attn0_prefill_max": ("attn0_prefill", "max"),
            "attn0_block_median": ("attn0_block", "median"),
            "attn0_block_max": ("attn0_block", "max"),
            "logit_median": ("logits", "median"),
            "logit_max": ("logits", "max"),
            "max_excluded": ("logits", "excluded")}


def verdict(r: dict, limits: dict) -> dict:
    ok = (all(r[part][key] <= limits[limit]
              for limit, (part, key) in READINGS.items())
          and r["choices"]["differ"] == 0)
    controls = {name: r["controls"][name]["median"] <= limits[limit]
                for name, limit in CONTROLS.items()
                if name in r["controls"]}
    return {"ok": bool(ok), "controls_ok": {k: bool(v)
                                            for k, v in controls.items()}}


def across(results: list[dict], limits: dict) -> dict:
    """Several seeds' lines against LIMITS: per limit the largest reading of
    the stated configuration and the smallest median of each control held to
    it; ok only when every seed is and no control of any seed is."""
    table = {}
    for limit, (part, key) in READINGS.items():
        row = {"limit": limits[limit],
               "largest": max(r[part][key] for r in results)}
        for name, held in CONTROLS.items():
            got = [r["controls"][name]["median"] for r in results
                   if name in r["controls"]]
            if held == limit and got:
                row.setdefault("controls_smallest", {})[name] = min(got)
        table[limit] = row
    each = [verdict(r, limits) for r in results]
    return {"seeds": [r["seed"] for r in results], "table": table,
            "ok": all(v["ok"] for v in each),
            "controls_ok": {name: any(v["controls_ok"].get(name, False)
                                      for v in each) for name in CONTROLS},
            "choices_differ": sum(r["choices"]["differ"] for r in results),
            "limits": limits}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preset", default="sdar-30b-a3b-chat")
    ap.add_argument("--lens", default="60,101,202,303")
    ap.add_argument("--buckets", default="64,128,256,512")
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--capacity", type=int, default=640)
    ap.add_argument("--decode-block", type=int, default=16)
    ap.add_argument("--dispatches", type=int, default=1)
    ap.add_argument("--control-lanes", default="0,1",
                    help="the lanes whose forwards the logit controls "
                         "carry through all the layers")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=47)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--out", default=None)
    ap.add_argument("--verdict", nargs="+", default=None)
    args = ap.parse_args()
    if args.verdict:
        result = across([json.load(open(f)) for f in args.verdict], LIMITS)
        print(json.dumps(result), flush=True)
        return 0 if result["ok"] and not any(
            result["controls_ok"].values()) else 1

    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import block_diffusion_moe_decoder as ref
    from symmetry_tpu.engine import engine as eng_mod
    from symmetry_tpu.engine.tokenizer import get_tokenizer
    from symmetry_tpu.models import llama
    from symmetry_tpu.ops import decode_attention, flash
    from symmetry_tpu.ops.quant import QuantizedTensor

    t0 = time.monotonic()
    cfg = llama.preset(args.preset)
    model = llama.hf_config_diffusion(cfg)
    block, L = cfg.diffusion.block, cfg.num_layers
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[args.dtype]
    quantized = dtype == jnp.bfloat16
    params = llama.init_params(cfg, jax.random.key(args.seed), dtype,
                               quantize=quantized)
    lens = [int(x) for x in args.lens.split(",")]
    buckets = tuple(sorted(int(x) for x in args.buckets.split(",")))
    n = len(lens)
    assert sorted(ln % block for ln in lens) == list(range(block)), lens
    prompts = [np.asarray(jax.random.randint(
        jax.random.key(args.seed + 1 + b), (ln,), 0, 256)).tolist()
        for b, ln in enumerate(lens)]

    # -- the program, tapped where its choices are made (ordered)
    taps: list[tuple] = []

    def record(kind):
        def put(*arrays):
            taps.append((kind, *(np.asarray(a) for a in arrays)))
        return put

    def rows_of(x):
        return x[:min(n, x.shape[0])]

    orig_forward = eng_mod.forward_hidden

    def forward_hidden(p, c, tokens, cache, *a, **kw):
        h, out = orig_forward(p, c, tokens, cache, *a, **kw)
        if tokens.shape[1] == block:
            jax.debug.callback(record("forward"), rows_of(tokens),
                               rows_of(cache.lengths), rows_of(h),
                               ordered=True)
        return h, out

    orig_candidates = eng_mod.diffusion_candidates

    def candidates(logits, *a, **kw):
        cand, conf = orig_candidates(logits, *a, **kw)
        jax.debug.callback(record("candidates"), rows_of(logits),
                           rows_of(cand), rows_of(conf), ordered=True)
        return cand, conf

    orig_unmask = eng_mod.diffusion_unmask

    def unmask(conf, known, n_static, final, threshold=None):
        take = orig_unmask(conf, known, n_static, final, threshold)
        jax.debug.callback(record("unmask"), rows_of(known), rows_of(take),
                           n_static, final, ordered=True)
        return take

    orig_gqa, orig_flash = llama.gqa_attention, flash.flash_prefill
    orig_decode = decode_attention.decode_attention

    def gqa(*a, **kw):
        out = orig_gqa(*a, **kw)
        jax.debug.callback(record("gqa"), rows_of(out), ordered=True)
        return out

    def decode(*a, **kw):  # a block's forwards where the cache has tiles
        out = orig_decode(*a, **kw)
        jax.debug.callback(record("gqa"), rows_of(out), ordered=True)
        return out

    def flash_prefill(*a, **kw):
        out = orig_flash(*a, **kw)
        jax.debug.callback(record("flash"), out, ordered=True)
        return out

    eng_mod.forward_hidden = forward_hidden
    eng_mod.diffusion_candidates = candidates
    eng_mod.diffusion_unmask = unmask
    llama.gqa_attention, flash.flash_prefill = gqa, flash_prefill
    decode_attention.decode_attention = decode

    engine = eng_mod.InferenceEngine(
        cfg, params, get_tokenizer(None, vocab_size=cfg.vocab_size),
        max_slots=args.slots, max_seq_len=args.capacity,
        prefill_buckets=buckets, cache_dtype=dtype,
        decode_block=args.decode_block, kv_quant=quantized,
        prefill_chunk=None, diffusion_steps=args.steps)
    assert {engine.bucket_for(ln) for ln in lens} == set(buckets), (
        "a prompt through each bucket", lens, buckets)
    greedy = eng_mod.SamplingParams()
    admitted = []
    for b in range(n):
        np.asarray(engine.prefill_and_insert_many_dispatch(
            [(b, prompts[b], greedy)]))
        jax.effects_barrier()
        admitted.append(list(taps))
        del taps[:]
    for _ in range(args.dispatches):
        np.asarray(engine.decode_steps())
    jax.effects_barrier()
    decoded = list(taps)
    del taps[:]
    head_of = jax.jit(lambda h: llama.logits_from_hidden(params, cfg, h))
    engine.state = None
    engine._prefill_scratch.clear()
    t_program = time.monotonic() - t0

    # -- the tapped forwards, a record each: lane, committed context, the
    # block as it stood, what the program read and chose
    forwards = []      # dicts
    flash0 = []        # layer 0's prefill attention, per lane [P', n_q * d]
    context = [p[:len(p) // block * block] for p in prompts]

    def walk(events, lanes, row_of):
        """Group one dispatch's taps into forwards (events of a forward: L
        x gqa, forward, then candidates + unmask for a denoise forward)."""
        gqas, cur = [], None
        per_block = 0
        for ev in events:
            kind = ev[0]
            if kind == "flash":
                if len(gqas) == 0 and not flash0_pending:
                    flash0_pending.append(ev[1])
                continue
            if kind == "gqa":
                gqas.append(ev[1])
                continue
            if kind == "forward":
                _, tokens, lengths, h = ev
                cur = []
                for lane in lanes:
                    r = row_of(lane)
                    cur.append({
                        "lane": lane, "context": int(lengths[r]),
                        "block": tokens[r].tolist(), "h": h[r],
                        "attn0": np.asarray(gqas[0][r], np.float32).reshape(
                            block, -1)})
                gqas = []
                per_block += 1
                if per_block == args.steps + 1:     # the commit forward
                    for f in cur:
                        f["commit"] = True
                        assert f["context"] == len(context[f["lane"]]), f
                        context[f["lane"]] = context[f["lane"]] + f["block"]
                    per_block = 0
                forwards.extend(cur)
            elif kind == "candidates":
                _, logits, cand, conf = ev
                for f in cur:
                    r = row_of(f["lane"])
                    f.update(logits=np.asarray(logits[r], np.float32),
                             cand=cand[r], conf=conf[r])
            elif kind == "unmask":
                _, known, take, n_static, final = ev
                for f in cur:
                    r = row_of(f["lane"])
                    f.update(known=known[r].copy(), take=take[r].copy(),
                             n_static=int(n_static), final=bool(final))

    for b in range(n):
        flash0_pending: list = []
        walk(admitted[b], [b], lambda lane: 0)
        whole = lens[b] // block * block
        flash0.append(np.asarray(flash0_pending[0], np.float32)[0, :whole]
                      .reshape(whole, -1) if flash0_pending else None)
    flash0_pending = [None]
    walk(decoded, list(range(n)), lambda lane: lane)
    for f in forwards:      # the context a forward saw, from its length
        lane = f["lane"]
        f["tokens"] = (prompts[lane][:lens[lane] // block * block]
                       + [t for g in forwards if g["lane"] == lane
                          and g.get("commit") and g["context"] < f["context"]
                          for t in g["block"]] + f["block"])
        assert len(f["tokens"]) == f["context"] + block, (
            lane, len(f["tokens"]), f["context"])

    # -- the reference: every tapped forward's full sequence under the block
    # mask, and layer 0 of the prompts' whole blocks; K/V rounded to int8 a
    # (position, head) as the cache holds them
    def rounded_to(top_level):
        def rounded(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True),
                                1e-8) / top_level
            return jnp.clip(jnp.round(x / scale), -top_level,
                            top_level) * scale
        return lambda k, v: (rounded(k), rounded(v))

    int8_rows = rounded_to(127.0) if quantized else (lambda k, v: (k, v))
    int4_rows = rounded_to(7.0)         # the precision control's K/V

    def skipped_commit_mask(s):
        """The block mask of `s` positions whose last block does not see
        the block before it: what a commit that never happened leaves."""
        m = np.asarray(ref.block_mask(s, block)).copy()
        m[-block:, max(0, s - 2 * block):s - block] = False
        return jnp.asarray(m)

    def f32(leaf):
        if isinstance(leaf, QuantizedTensor):
            return leaf.q.astype(jnp.float32) * leaf.scale[..., None, :]
        return leaf.astype(jnp.float32)

    def layer_params(i):
        """Layer i's leaves in float32, no layer axis: the only float32 copy
        of them there is (2.4 GB beside the 8.4 GB of int8)."""
        return {name: f32(jax.tree.map(lambda a: a[i], leaf))
                for name, leaf in params["layers"].items()}

    # (the embedding stays as it lies: `ref.embed` lifts the rows it takes)
    top = {"embed": params["embed"],
           "final_norm": params["final_norm"].astype(jnp.float32)}
    seqs = [f["tokens"] for f in forwards]
    masks = [ref.block_mask(len(s), block) for s in seqs]
    hs = [ref.embed(top, np.asarray(s)) for s in seqs]
    margins = [np.full((len(s),), np.inf) for s in seqs]
    ref_attn0 = p0 = None
    controls = {}
    # the logit controls, over the forwards of `--control-lanes`: the same
    # sequences under the two wrong masks (carried in the stated call: a
    # mask a sequence) and with int4 K/V (a call of its own a layer)
    lanes_x = {int(x) for x in args.control_lanes.split(",") if x}
    sub = [j for j, f in enumerate(forwards) if f["lane"] in lanes_x
           and len(seqs[j]) >= 2 * block]
    n_f = len(forwards)
    masks_x = ([ref.causal_mask(len(seqs[j])) for j in sub]
               + [skipped_commit_mask(len(seqs[j])) for j in sub])
    hs_x = [hs[j] for j in sub] * 2
    hs_4 = [hs[j] for j in sub]
    for i in range(L):
        p = layer_params(i)
        if i == 0:
            # layer 0 of the prompts' whole blocks (the flash kernel's
            # rows: bfloat16 K/V, nothing rounded to int8), stated and with
            # a bfloat16 softmax; the block rows under the two wrong masks
            x0 = [ref.rms_norm(ref.embed(top, np.asarray(
                prompts[b][:lens[b] // block * block])),
                p["attn_norm"], model["rms_norm_eps"]) for b in range(n)]
            p0 = p
            with jax.default_matmul_precision("highest"):
                pre = [np.asarray(ref.attention(
                    x, p0, model, ref.block_mask(x.shape[0], block)))
                    for x in x0]
                pre_bf16 = [np.asarray(ref.attention(
                    x, p0, model, ref.block_mask(x.shape[0], block),
                    softmax=ref.softmax_bf16)) for x in x0]
                wrong = {"causal_mask": [], "skipped_commit": []}
                for f, h0 in zip(forwards, hs):
                    x = ref.rms_norm(h0, p["attn_norm"],
                                     model["rms_norm_eps"])
                    s = len(f["tokens"])
                    for name, m in (("causal_mask", ref.causal_mask(s)),
                                    ("skipped_commit",
                                     skipped_commit_mask(s))):
                        if name == "skipped_commit" and s < 2 * block:
                            continue
                        wrong[name].append((f, np.asarray(ref.attention(
                            x, p0, model, m, kv_round=int8_rows))[-block:]))
        out, detail = ref.layer_forward(p, model, hs + hs_x, masks + masks_x,
                                        routed=True, kv_round=int8_rows)
        hs, hs_x = out[:n_f], out[n_f:]
        if sub:
            hs_4, _ = ref.layer_forward(p, model, hs_4,
                                        [masks[j] for j in sub], routed=True,
                                        kv_round=int4_rows)
        if i == 0:
            ref_attn0 = [np.asarray(a)[-block:]
                         for a in detail["attn"][:n_f]]
        margins = [np.minimum(m, np.asarray(d))
                   for m, d in zip(margins, detail["margin"][:n_f])]
        del p, out, detail
        p0 = None
    head_p = {**top, "lm_head": f32(params["lm_head"])}

    def block_logits(h):
        return np.asarray(ref.head(head_p, model, h[-block:]))

    want = [block_logits(h) for h in hs]
    want_x = {"causal_mask_logits": hs_x[:len(sub)],
              "skipped_commit_logits": hs_x[len(sub):],
              "kv_int4_logits": hs_4}
    want_x = {name: [block_logits(h) for h in rows]
              for name, rows in want_x.items()}
    t_reference = time.monotonic() - t0 - t_program

    # -- the comparisons
    def rel(got, ref_rows):
        return (np.linalg.norm(got - ref_rows, axis=-1)
                / np.maximum(np.linalg.norm(ref_rows, axis=-1), 1e-30))

    def summary(errors):
        errors = np.concatenate([np.ravel(e) for e in errors])
        return {"n": int(errors.size), "median": float(np.median(errors)),
                "p99": float(np.percentile(errors, 99)),
                "max": float(errors.max())}

    attn0_prefill = summary([rel(flash0[b], pre[b]) for b in range(n)
                             if flash0[b] is not None and len(pre[b])])
    controls["softmax_bf16"] = summary(
        [rel(flash0[b], pre_bf16[b]) for b in range(n)
         if flash0[b] is not None and len(pre_bf16[b])])
    attn0_block = summary([rel(f["attn0"], a)
                           for f, a in zip(forwards, ref_attn0)])
    for name, rows in wrong.items():
        controls[name] = summary([rel(f["attn0"], a) for f, a in rows])
    scale = max(float(np.abs(w).max()) for w in want)
    errs, kept, seen, agree = [], 0, 0, []
    differ = 0
    compared = []       # per forward: the program's rows and which are kept
    for f, w, m in zip(forwards, want, margins):
        if f.get("commit"):
            got = np.asarray(head_of(jnp.asarray(f["h"])[None])[0],
                             np.float32)
            rows = np.arange(block)
        else:
            got = f["logits"]
            rows = np.flatnonzero(~f["known"])
            # (c) the reference's rule over the program's own confidences
            take = ref.unmask(f["conf"], f["known"], f["n_static"], None,
                              f["final"])
            differ += int((take != f["take"]).any())
            agree.append(got[rows].argmax(-1) == w[rows].argmax(-1))
        # a block position's own sequence decides its routing
        clear = m[-block:][rows] > LIMITS["eps"]
        seen += len(rows)
        kept += int(clear.sum())
        errs.append(np.abs(got[rows] - w[rows]).max(-1)[clear] / scale)
        compared.append((got, rows, clear))
    logits = summary(errs)
    # the same rows of the program against each logit control, and against
    # the stated reference over the same forwards (`logits_of_controls`)
    if sub:
        def over_sub(rows_of_want):
            return summary([
                np.abs(compared[j][0][compared[j][1]]
                       - w[compared[j][1]]).max(-1)[compared[j][2]] / scale
                for j, w in zip(sub, rows_of_want)])
        for name, rows_of_want in want_x.items():
            controls[name] = over_sub(rows_of_want)
        controls_base = over_sub([want[j] for j in sub])
    logits.update(excluded=1.0 - kept / max(1, seen), rows=seen,
                  logit_scale=scale,
                  argmax_agree=float(np.mean(np.concatenate(agree))))
    result = {
        "preset": args.preset, "dtype": args.dtype, "seed": args.seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "prompt_lens": lens, "buckets": list(buckets), "block": block,
        "steps": args.steps, "slots": args.slots, "capacity": args.capacity,
        "forwards": len(forwards),
        "commit_forwards": sum(1 for f in forwards if f.get("commit")),
        "contexts": sorted({f["context"] for f in forwards}),
        "attn0_prefill": attn0_prefill, "attn0_block": attn0_block,
        "logits": logits, "choices": {"forwards": len(agree),
                                      "differ": differ},
        "controls": controls,
        "logits_of_controls": controls_base if sub else None,
        "control_lanes": sorted(lanes_x),
        "seconds": {"program": round(t_program, 1),
                    "reference": round(t_reference, 1)},
    }
    result.update(verdict(result, LIMITS), limits=LIMITS)
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    return 0 if result["ok"] and not any(
        result["controls_ok"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
