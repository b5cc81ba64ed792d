"""Full-width, full-depth logits parity of a hybrid model on the chip, by
preset: `granite-4.0-h-small` (the default; the text below, up to "qwen3-next",
is its) or `--preset qwen3-next-80b-a3b`.

`granite-4.0-h-small` as the cell serves it — every width as published, the
ten layers of the cut, int8 weights, int8 KV, the recurrent state in float32
— against `benchmarks/reference/hybrid_decoder.py` fed the SAME weights
dequantised, in float32 on the host's CPU, one layer's weights at a time
(3.2 GB; the whole float32 model is 38 GB).

    python tools/hybrid_parity.py                         # on the chip
    JAX_PLATFORMS=cpu python tools/hybrid_parity.py --preset tiny-hybrid \\
        --prompts 3 --min-len 20 --max-len 60 --bucket 64 --decode 8 \\
        --capacity 96                                     # CPU rehearsal

A seeded sample of `--prompts` prompts of unequal lengths (log-uniform in
[--min-len, --max-len]: the cell's 51-179 with the template), right-padded to
`--bucket` and prefilled from empty through the trunk the served prefill
program runs (`prefill_flash`: flash attention, the chunked mamba form, each
row's state taken at its own length), then `--decode` single-token steps
through the K/V cache, the state and the conv tail, teacher-forced. Logits
at every valid prompt position and every step against the reference's full
forward over the whole sequence.

Router near-ties: a token whose REFERENCE margin between its k-th and
(k+1)-th router logit is under `--eps` at any layer may route otherwise on
the two sides — with 72 logits and the 10th against the 11th far more often
than mixtral's 2nd against 3rd of 8. Such tokens are left out and their share
is reported (at most `--max-excluded` may be).

Tolerances, in units of the logit scale (max |reference logit|, 1.67 here),
each set from the chip's readings (PERF.md, PR 33; seed 33, 4 prompts of
179 / 104 / 159 / 70 tokens, 64 steps, 768 tokens compared):

- `--eps 0.002`: 27.6% of the tokens sit within it of a tie at some layer
  (55.7% within 0.005) and are left out; at most `--max-excluded` 0.5 may be.
  With random weights a flip between the 10th and the 11th of 72 experts
  moves a logit by at most 1.9% of the scale (the worst over ALL tokens), so
  the margin matters little here; it is kept for trained weights.
- `--atol 0.03`: kept tokens' worst error read 0.0185 (bfloat16 activations,
  the int8 K/V, the bfloat16 conv tail); a wrong scale, layout or multiplier
  errs by the scale itself.
- `--median 0.009`: their median read 0.0061.
- `--state-rtol 0.0057`: the recurrent state itself — layer 0's state of
  every row after its last step (no routing decision upstream of it, so a
  clean reading) against the reference's, relative Frobenius error: float32
  state read 0.0044-0.0052 (what bfloat16 activations put into x, B and dt),
  `--state-dtype bfloat16` 0.0062-0.0074. That second reading is the nearest
  precision below the one the configuration states and has to come out NOT
  ok — by this limit alone: through the logits a bfloat16 state hides under
  the bfloat16 activations (worst 0.0185, median 0.0059 there too).

`--preset qwen3-next-80b-a3b` (PR 35): the four layers of the cut (Gated
DeltaNet x 3, gated attention), 512 experts, against
`benchmarks/reference/gdn_moe_decoder.py`, the same procedure; the prefill
crosses four chunks of 64 and routes its experts (1,024 tokens a dispatch).
Its limits, from the chip's readings (PERF.md, PR 35; seed 33, the same four
prompts, 64 steps, 768 tokens; logit scale 5.19; two draws of the weights —
the first tree's and the final tree's order of init keys — "first / final"):

- `--state-rtol 0.0058`: layer 0's MATRIX state of every row after its last
  step, relative Frobenius error: float32 state read 0.00377-0.00381 /
  0.00377-0.00382, `--state-dtype bfloat16` 0.00877-0.00928 / 0.00883-0.00922
  — NOT ok, by this limit alone (the logits read the same either way: worst
  0.2316 and median 0.0556 against 0.2378 and 0.0546 on the first draw,
  0.2620 and 0.0529 against 0.2620 and 0.0541 on the final one). The limit
  is the two readings' geometric mean: 52% of room below, 34% above.
- `--eps 0.002`: 17.8% of the tokens sit within it of a tie at some layer
  and are left out (38% within 0.005, 60% within 0.01).
- `--atol 0.40`, `--median 0.075`: kept tokens' worst error read 0.2316 /
  0.2620 (one token's flip: an extreme value, so the room is wide), their
  median 0.0556 / 0.0529 (the sharper of the two limits) — ten times
  granite's, and it is the ROUTING, not the mathematics: the 10th and 11th largest of 512 router logits lie 0.04
  apart on average, bfloat16 activations put ~0.01 of noise on a logit, so
  at every layer about a fifth of the tokens pick another 10th expert on
  the two sides; a flip moves the hidden state by a twentieth, and the
  recurrent state and the attention layer carry it to every later token,
  whatever that token's own margin (tokens with every margin over 0.02
  still read a median of 0.040). `--dtype float32` (a diagnostic: float32
  activations, embedding and cache, matmuls at `highest`, the same int8
  weights, kernels and programs otherwise) reads median **0.00044**, decode
  worst 0.0026, layer 0's state 0.000042-0.000047, and 0.0016 over the
  tokens with every margin over 0.05; its worst kept token (0.095, in the
  prefill) is one flip. A wrong scale, layout, gate or norm errs by the
  scale itself (1.0) on every token.

`--preset lfm2-8b-a1b` (PR 42): ALL 24 layers (18 gated short convolutions,
six GQA layers of 64-wide heads, two dense layers, 22 x 32 experts top 4 by
sigmoid scores + a selection bias), against
`benchmarks/reference/sconv_moe_decoder.py`, the same procedure; the "state"
compared is layer 0's TAIL (z at each row's last two positions, bfloat16).
`--controls a,b,...` then runs the PROGRAM again with one part wrong — the
reference computed once — and each has to come out NOT ok: `coarse-experts`
(the expert stacks' int8 payload rounded to 7 bits: one step coarser),
`bf16-router` (router logits accumulated in bfloat16 where they are float32),
and the falsifications of tests/test_short_conv.py that need no other tree
of weights: `no-bias` (the selection bias left out), `biased-gates` (gates
from the biased scores), `softmax` (for the sigmoid), `taps-reversed`. (The
fifth, the dense FFN at layer 2, needs a [3, ...] dense stack and a [21, ...]
expert stack: another model's weights, not a control of this one's.) Its
limits and their readings are in LIMITS' comment and PERF.md, PR 42.

Prints one JSON line (and writes it to `--out`); exits 0 only when the
verdict holds (and every control fails it). Touches JAX: never beside a live engine host.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))


# The limits of the verdict, by preset (the docstring has each one's reason).
LIMITS = {
    None: dict(eps=0.002, atol=0.03, median=0.009, max_excluded=0.5,
               state_rtol=0.0057),
    "qwen3-next-80b-a3b": dict(eps=0.002, atol=0.40, median=0.075,
                               max_excluded=0.5, state_rtol=0.0058),
    # lfm2-8b-a1b, by the activations' dtype (PERF.md, PR 42; seed 33, the
    # same four prompts, 64 steps, 768 rows, logit scale 5.05). Served
    # precision: median 0.190 / worst 0.440, and it is the ROUTING — 66.9%
    # of the tokens lie within `eps` of a tie between their 4th and 5th
    # biased SCORE (sigmoid scores span a quarter of the logits' spread) at
    # one of 22 layers, hence `max_excluded` 0.8 — so `median` 0.23 and
    # `atol` 0.60 hold the falsifications out (gates from biased scores
    # 0.272, no bias 0.738, softmax 0.745, taps reversed 1.10) and cannot
    # see a precision step (7-bit experts 0.205, a bfloat16 router 0.193);
    # layer 0's tail read 0.00278-0.00279 (bfloat16's rounding of z).
    "lfm2-8b-a1b": dict(eps=0.002, atol=0.60, median=0.23,
                        max_excluded=0.8, state_rtol=0.004),
    # The same programs and int8 weights under float32 activations,
    # embedding and cache: median 0.0000146 (the prefill's worst 0.0000172,
    # the tail 1.1e-6; decode's int8 K/V rows flip ties: worst 0.31). This
    # is the reading that sees a precision step: router logits rounded to
    # bfloat16 read 0.0245 (1,700x), 7-bit expert payloads 0.151 (10,000x),
    # each NOT ok by `median` 0.0006 alone — the two readings' geometric
    # mean, 41x of room either way.
    "lfm2-8b-a1b:float32": dict(eps=0.002, atol=0.60, median=0.0006,
                                max_excluded=0.8, state_rtol=1e-5),
}
CONTROLS = ("coarse-experts", "bf16-router", "no-bias", "biased-gates",
            "softmax", "taps-reversed")


def run_control(name: str, cfg, params, run_program):
    """The program run again with one part of the lfm2_moe model wrong (the
    docstring's `--controls`) -> (logits, layer 0's tails)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from symmetry_tpu.models import moe
    from symmetry_tpu.ops.quant import QuantizedTensor

    lay = params["layers"]

    def with_stack(stack, **leaves):
        return dict(params, layers=dict(lay, **{
            stack: dict(lay[stack], **leaves)}))

    if name == "coarse-experts":
        # 7 bits of the 8: every payload an even number; the buffer is
        # donated (the caller's `params` are spent after this control)
        coarse = jax.jit(lambda q: (q // 2) * 2, donate_argnums=0)
        for k in ("wg", "wu", "wd"):
            w = lay["ffn"][k]
            lay["ffn"][k] = QuantizedTensor(coarse(w.q), w.scale)
        return run_program(cfg, params)
    if name == "no-bias":
        return run_program(cfg, with_stack("ffn", expert_bias=jnp.zeros_like(
            lay["ffn"]["expert_bias"])))
    if name == "taps-reversed":
        return run_program(cfg, with_stack(
            "sconv", conv_w=lay["sconv"]["conv_w"][:, ::-1]))
    if name == "softmax":
        return run_program(dataclasses.replace(cfg, router_score="softmax"),
                           params)
    true_route = moe.route_top_k

    def biased_gates(x, router, k, *, score, bias, scale, eps):
        scores = jax.nn.sigmoid(jnp.dot(
            x, router, preferred_element_type=jnp.float32))
        top, idx = jax.lax.top_k(scores + bias, k)
        return (top / (jnp.sum(top, -1, keepdims=True) + eps) * scale,
                idx.astype(jnp.int32))

    def bf16_router(x, router, k, *, score, bias, scale, eps):
        # an EXPLICIT rounding of the logits to bfloat16's 8 bits: a dot
        # with a bfloat16 result is not one — XLA keeps the float32
        # accumulator through the fusion (`xla_allow_excess_precision`) and
        # the control read the stated run's digits (my chip run, PR 42)
        scores = jax.nn.sigmoid(jax.lax.reduce_precision(jnp.dot(
            x, router, preferred_element_type=jnp.float32), 8, 7))
        _, idx = jax.lax.top_k(scores + bias, k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
        return (top / (jnp.sum(top, -1, keepdims=True) + eps) * scale,
                idx.astype(jnp.int32))

    moe.route_top_k = {"biased-gates": biased_gates,
                       "bf16-router": bf16_router}[name]
    try:
        # (another config object, so that the jit traces again)
        return run_program(dataclasses.replace(cfg), params)
    finally:
        moe.route_top_k = true_route


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preset", default="granite-4.0-h-small")
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--min-len", type=int, default=51)
    ap.add_argument("--max-len", type=int, default=179)
    ap.add_argument("--bucket", type=int, default=256)
    ap.add_argument("--decode", type=int, default=64)
    ap.add_argument("--capacity", type=int, default=640)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--state-dtype", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"),
                    help="activations, embedding and cache dtype; float32 "
                         "(with matmuls at `highest`) is a DIAGNOSTIC, not "
                         "the served path: it says how much of the error "
                         "is bfloat16 rounding and the routing it flips")
    for name in LIMITS[None]:       # each preset's own, unless given
        ap.add_argument("--" + name.replace("_", "-"), type=float,
                        default=None)
    ap.add_argument("--controls", default="",
                    help=f"comma-separated, of {CONTROLS}: run the program "
                         f"again with that part wrong; each must fail")
    ap.add_argument("--reference-cache", default=None,
                    help="an .npz the reference's logits, margins and "
                         "layer-0 states are kept in (and read from, when "
                         "it holds this preset, seed and shape)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from symmetry_tpu.models import hybrid, llama
    from symmetry_tpu.ops.quant import QuantizedTensor

    t0 = time.monotonic()
    cfg = llama.preset(args.preset)
    conv_kind = cfg.recurrent_kind == "conv"
    if conv_kind:
        from reference import sconv_moe_decoder as ref
    elif cfg.recurrent_kind == "linear_attention":
        from reference import gdn_moe_decoder as ref
    else:
        from reference import hybrid_decoder as ref
    limits = LIMITS.get(f"{args.preset}:{args.dtype}",
                        LIMITS.get(args.preset, LIMITS[None]))
    for name, value in limits.items():
        if getattr(args, name) is None:
            setattr(args, name, value)
    controls = [c for c in args.controls.split(",") if c]
    if controls and not conv_kind or set(controls) - set(CONTROLS):
        ap.error(f"--controls takes {CONTROLS}, for an lfm2_moe preset")
    dtype = jnp.dtype(args.dtype)
    if dtype == jnp.float32:
        jax.config.update("jax_default_matmul_precision", "highest")
    params = llama.init_params(cfg, jax.random.key(args.seed), dtype,
                               quantize=True)
    n, d = args.prompts, args.decode
    rng = np.random.default_rng(args.seed)
    lens = np.exp(rng.uniform(math.log(args.min_len), math.log(args.max_len),
                              n)).astype(np.int32)
    lens[0] = args.max_len          # the longest the cell sends, always
    tokens = np.asarray(jax.random.randint(
        jax.random.key(args.seed + 1), (n, args.bucket + d), 0,
        cfg.vocab_size))
    # row b's sequence: its lens[b] prompt tokens, then d decode tokens
    seqs = [np.concatenate([tokens[b, :lens[b]],
                            tokens[b, args.bucket:args.bucket + d]])
            for b in range(n)]
    prompt = np.zeros((n, args.bucket), np.int32)
    for b in range(n):
        prompt[b, :lens[b]] = seqs[b][:lens[b]]

    def run_program(cfg, params):
        """The program's logits at every compared position, and layer 0's
        state (a conv model's: its tail) of every row at the end."""
        cache = llama.init_cache(cfg, n, args.capacity, dtype,
                                 quantized=True)
        if cache.ssm is not None:
            cache = cache._replace(ssm=cache.ssm.astype(args.state_dtype))

        def prefill(params, toks, seq_lens, cache):
            h, cache = llama.forward_hidden(params, cfg, toks, cache,
                                            seq_lens, prefill_flash=True)
            return llama.logits_from_hidden(params, cfg, h), cache

        def step(params, tok, cache):
            h, cache = llama.forward_hidden(params, cfg, tok, cache)
            return llama.logits_from_hidden(params, cfg, h), cache

        first, cache = jax.jit(prefill, donate_argnums=(3,))(
            params, jnp.asarray(prompt), jnp.asarray(lens), cache)
        first = np.asarray(first, np.float32)
        step = jax.jit(step, donate_argnums=(2,))
        steps = []
        for i in range(d):
            tok = np.stack([seqs[b][lens[b] + i] for b in range(n)])[:, None]
            logits, cache = step(params, jnp.asarray(tok), cache)
            steps.append(np.asarray(logits[:, 0], np.float32))
        got = [np.concatenate([first[b, :lens[b]],
                               np.stack([s[b] for s in steps])])
               for b in range(n)]
        if conv_kind:       # [K-1, B, E] -> a row's [K-1, E]
            state0 = np.moveaxis(np.asarray(
                cache.conv[0].astype(jnp.float32)), 1, 0)
        else:
            state0 = np.asarray(cache.ssm[0].astype(jnp.float32))
        return got, state0

    got, ssm0 = run_program(cfg, params)
    state_errors: list[float] = []
    t_program = time.monotonic() - t0

    # (the reference is most of the tool's time at 24 layers: kept beside the
    # result, keyed by what decides it, so a second call can skip it)
    ref_key = json.dumps([args.preset, args.seed, n, d, args.bucket,
                          args.min_len, args.max_len, args.dtype])
    cached = None
    if args.reference_cache and os.path.exists(args.reference_cache):
        with np.load(args.reference_cache, allow_pickle=False) as z:
            if str(z["key"]) == ref_key:
                cached = {k: z[k] for k in z.files}
    if cached is not None:
        want = [cached[f"want{b}"] for b in range(n)]
        margins = [[cached[f"margins{b}"]] for b in range(n)]
        want_state0 = list(cached["state0"]) if "state0" in cached else []
        state_errors = [
            float(np.linalg.norm(ssm0[b].astype(np.float32) - w0)
                  / np.linalg.norm(w0)) for b, w0 in enumerate(want_state0)]
    else:
        # The reference: the same weights, dequantised, float32, on the host,
        # a layer at a time.
        cpu = jax.devices("cpu")[0]

        def to_host(a):
            if isinstance(a, QuantizedTensor):
                return jax.device_put(
                    np.asarray(a.q).astype(np.float32)
                    * np.expand_dims(np.asarray(a.scale), -2), cpu)
            return jax.device_put(np.asarray(a.astype(jnp.float32)), cpu)

        def is_q(a):
            return isinstance(a, QuantizedTensor)

        def one_layer(stack, j):
            """Layer j of a stack as a stack of one, float32, on the host."""
            return jax.tree.map(
                lambda a: to_host(QuantizedTensor(a.q[j:j + 1], a.scale[j:j + 1])
                                  if is_q(a) else a[j:j + 1]),
                stack, is_leaf=is_q)

        model = hybrid.hf_config(cfg)
        top = {"embed": to_host(params["embed"]),
               "final_norm": to_host(params["final_norm"])}
        if "lm_head" in params:
            top["lm_head"] = to_host(params["lm_head"])
        with jax.default_device(cpu):
            hs = [ref.embed(top, model, jax.device_put(s, cpu)) for s in seqs]
            margins = [[] for _ in range(n)]
            want_state0: list = []
            for i, kind in enumerate(cfg.layer_types):
                stack = hybrid.KIND_STACK[kind]
                j = hybrid.stack_index(cfg, i)
                ffn, at = (("dense", i) if cfg.ffn_kind(i) == "dense"
                           else ("ffn", i - cfg.num_dense_layers))
                one = {"layers": {
                    stack: one_layer(params["layers"][stack], j),
                    ffn: one_layer(params["layers"][ffn], at)}}
                layer_model = dict(model, layer_types=[kind],
                                   num_dense_layers=int(ffn == "dense"))
                for b in range(n):
                    states = ([] if i == 0 and kind == cfg.recurrent_kind
                              else None)
                    hs[b], m = ref.run_layers(
                        one, layer_model, hs[b], layers=[0],
                        **{"tails" if conv_kind else "states": states})
                    margins[b].append(np.asarray(m[0]))
                    if states:
                        # layer 0 sees the embeddings alone: no routing decision
                        # upstream, so its state is a clean reading
                        mine = ssm0[b].astype(np.float32)
                        want0 = np.asarray(states[0])
                        want_state0.append(want0)
                        state_errors.append(float(
                            np.linalg.norm(mine - want0)
                            / np.linalg.norm(want0)))
                del one
            want = [np.asarray(ref.head(top, model, h)) for h in hs]
        if args.reference_cache:
            os.makedirs(os.path.dirname(os.path.abspath(
                args.reference_cache)), exist_ok=True)
            np.savez(args.reference_cache, key=ref_key,
                     **{f"want{b}": w for b, w in enumerate(want)},
                     **{f"margins{b}": np.min(np.stack(m), axis=0)
                        for b, m in enumerate(margins)},
                     **({"state0": np.stack(want_state0)}
                        if want_state0 else {}))
    margins = np.concatenate([np.min(np.stack(m), axis=0) for m in margins])
    scale = max(float(np.abs(w).max()) for w in want)
    is_decode = np.concatenate([
        np.arange(len(s)) >= ln for s, ln in zip(seqs, lens)])
    kept = margins >= args.eps
    excluded = float(1 - kept.mean())

    def verdict(got, state_errors):
        """The limits applied to one run of the program."""
        errors = np.concatenate([np.abs(g - w).max(axis=-1)
                                 for g, w in zip(got, want)]) / scale

        def worst(mask):
            return float(errors[mask].max()) if mask.any() else None

        by_margin = {}
        for eps in (0.0, 0.005, 0.01, 0.02, 0.05, 0.1):
            ok = margins >= eps
            by_margin[str(eps)] = {
                "excluded_share": float(1 - ok.mean()),
                "worst": float(errors[ok].max()) if ok.any() else None,
                "median": float(np.median(errors[ok])) if ok.any() else None}
        failed_by = [name for name, bad in (
            ("atol", not kept.any() or worst(kept) > args.atol),
            ("median", not kept.any()
             or float(np.median(errors[kept])) > args.median),
            ("max_excluded", excluded > args.max_excluded),
            ("state_rtol", bool(state_errors)
             and max(state_errors) > args.state_rtol)) if bad]
        return {
            "ok": not failed_by, "failed_by": failed_by,
            "worst_error": worst(kept),
            "worst_error_prefill": worst(kept & ~is_decode),
            "worst_error_decode": worst(kept & is_decode),
            "median_error": float(np.median(errors[kept])) if kept.any()
            else None,
            "median_error_decode": float(np.median(errors[kept & is_decode]))
            if (kept & is_decode).any() else None,
            "p99_error_all_tokens": float(np.quantile(errors, 0.99)),
            "state_error_layer0": state_errors,
            "by_margin": by_margin}

    main_verdict = verdict(got, state_errors)
    t_total = time.monotonic() - t0
    dev = jax.devices()[0]
    result = {
        **main_verdict,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "preset": args.preset, "layers": cfg.num_layers,
        "attention": llama.attention_paths(
            cfg, args.capacity, None, batch=n, kv_bytes=1),
        "state_dtype": args.state_dtype, "dtype": args.dtype, "prompts": n,
        "prompt_lens": lens.tolist(), "bucket": args.bucket,
        "decode_steps": d, "tokens_compared": int(margins.size),
        "logit_scale": scale, "units": "share of logit_scale",
        "eps": args.eps, "atol": args.atol, "median_tol": args.median,
        "excluded_share": excluded, "state_rtol": args.state_rtol,
        "program_s": round(t_program, 1), "total_s": round(t_total, 1)}
    def emit():
        line = json.dumps(result)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return line

    if controls:
        emit()              # the stated reading is kept whatever follows
        result["controls"] = {}
        # (`coarse-experts` rewrites the expert stacks where they lie — a
        # second copy does not fit the chip — so it runs last)
        for name in sorted(controls, key=lambda c: c == "coarse-experts"):
            try:
                wrong, state = run_control(name, cfg, params, run_program)
            except Exception as exc:  # noqa: BLE001 — say which, go on
                result["controls"][name] = {"ok": None,
                                            "error": repr(exc)[:300]}
                continue
            errs = [float(np.linalg.norm(state[b] - w0) / np.linalg.norm(w0))
                    for b, w0 in enumerate(want_state0)]
            v = verdict(wrong, errs)
            result["controls"][name] = {
                k: v[k] for k in ("ok", "failed_by", "worst_error",
                                  "median_error", "state_error_layer0")}
            emit()
        result["controls_all_fail"] = all(
            v["ok"] is False for v in result["controls"].values())
        result["ok"] = result["ok"] and result["controls_all_fail"]
        result["total_s"] = round(time.monotonic() - t0, 1)
    line = emit()
    print(line, flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
