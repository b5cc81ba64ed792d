"""Logits (and state) parity of a model whose blocks are one sub-layer each
on the chip: `nemotron-3-nano-30b-a3b` as its cell serves it — every width
as published, all 52 blocks, 32 of the 128 routed experts held, int8
weights, the int8 K/V cache and the float32 recurrent state at the cell's
capacity, the engine's OWN `prefill` at the cell's buckets, its `insert` and
`decode_block` — against `benchmarks/reference/nemotron_h_decoder.py` fed
the SAME weights dequantised, in float32 with every product at `highest`,
one block's weights at a time.

    python tools/nh_parity.py --seeds 1,2 --out chiprun_out/nh_parity.json
    JAX_PLATFORMS=cpu python tools/nh_parity.py --preset tiny-nh --seeds 1 \\
        --lens 21,27,9 --buckets 16,32 --capacity 96 --decode-block 4 \\
        --steps 24 --dtype float32
    python tools/nh_parity.py --verdict chiprun_out/nh_parity.json

The FIRST TWO of `--lens` are admitted together — two rows of the largest
bucket, 512 tokens: the dispatch the cell's band ROUTES, through the
grouped-matmul kernel at the experts' ragged tiles — and every further one
alone; then `--steps` greedy decode steps run over the lanes
through the K/V cache, the grouped recurrence kernel and the conv tails. The
reference then makes ONE full pass over [prompt || the program's greedy
tokens] a lane, which teacher-forces it through the same positions, and the
program's logit rows at the prompt's last position and at every decode step
are compared with its rows, in units of the logit scale (max |reference
logit| of the row); the program's recurrent state after the last step is
compared with the reference's after its last token (`state_err`: the largest
relative Frobenius error over the mamba blocks and lanes).

Router near-ties. Top 6 of 128 sigmoid scores in 23 expert blocks: nearly
every row has a 6th-against-7th pair somewhere that bfloat16 decides the
other way (a FREE pass — the reference routing by its own scores — kept 14
of 963 rows, my chip run, PR 61), and one flip moves a row by the logit
scale. So the JUDGED pass is FORCED: each expert block of the reference
computes with the experts the program's tap shows at every position (its
gates are still the reference's own scores of them), which holds the two
sides on one path and leaves the arithmetic to compare; where the
reference's own scores would have chosen another set is COUNTED, a block
and a position (`flip_share`, judged too: a wrong router flips many). The
free pass is run as well and reported (`free`: `logit_median_all`,
`excluded_share`).

Controls, each the REFERENCE wrong on purpose against the same taps (on the
first `--control-lanes` lanes), each of which has to come out NOT ok by the
reading CONTROLS names on every seed: `one-group` (every head reads group
0's B and C), `norm-all` (the gated norm over all 4,096 channels), `gated`
(a SwiGLU in the relu2 expert's place), `renormalised` (gates renormalised
over the held experts), `rotary` (a rotary embedding on the attention
blocks); `state-bf16` (the state rounded to bfloat16 every position) is
run and REPORTED, not judged: see REPORTED below.

Prints one JSON line a seed and a verdict line (written to `--out`); exits 0
only when every seed is ok AND every control is not. `--verdict FILE`
re-applies LIMITS to a written file without JAX. Touches JAX otherwise:
never beside a live engine host.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
# The verdict's limits, each between two readings on the chip (my chip runs,
# PR 61; PERF.md section 6): the LARGEST the stated configuration read over
# its seeds and the SMALLEST a control read. Logit errors as a share of the
# logit scale. No reading depends on a limit: `--verdict FILE` judges a
# written file again.
LIMITS = dict(
    # FORCED passes, seeds 1 and 2 (lanes of 140 + 230 admitted together —
    # 512 tokens through the grouped-matmul kernel — and 50 alone; 320
    # decode steps; 963 rows a seed, all of them judged): medians 0.0218 /
    # 0.0218 (0.0215-0.0221 a lane), worst rows 0.0314 / 0.0332. The
    # nearest control is the rotary on the six attention blocks: 0.0724 /
    # 0.0730 and 0.1068 / 0.1247 (the other four read 0.49-1.14 and
    # 0.62-1.50). Each limit is the geometric mean of the largest stated and
    # the smallest control reading
    logit_median=0.040, logit_max=0.060,
    # a block and a position where the reference's own scores choose another
    # six of 128 than the program did: 0.0796 / 0.0904 (the worst block
    # 0.162 / 0.168); under the rotary 0.262 / 0.270, under the others
    # 0.87-0.98
    flip_share=0.154,
    # the recurrent state after the last step against the reference's, the
    # worst of 23 blocks x 3 lanes, by norm: 0.0342 / 0.0305 (medians 0.022:
    # the state's inputs are bfloat16 activations); under the rotary 0.1009
    # / 0.0938, under the others 0.70-1.64
    state_err=0.057,
)
READINGS = ("logit_median", "logit_max", "flip_share", "state_err")
# control -> the reading that has to fail
CONTROLS = {"one-group": "logit_median", "norm-all": "logit_median",
            "gated": "logit_median", "renormalised": "logit_median",
            "rotary": "logit_median"}
# run and REPORTED, not judged: at the served precision the state's error is
# its INPUTS' (bfloat16 activations: 0.0136 of the state's norm over nine
# blocks) and rounding the state itself to bfloat16 every position adds
# nothing a reading shows (0.0135; my chip runs, PR 61) — the float32 state
# is held by the CPU tests, where it reads 2e-6 against 5e-3
REPORTED = ("state-bf16",)


def verdict(readings: dict) -> dict:
    failed = [k for k in READINGS
              if k in readings and not readings[k] <= LIMITS[k]]
    return {"ok": not failed, "failed": failed}


def judge(lines: list[dict]) -> dict:
    out = {"seeds": [], "ok": True, "limits": {}}
    for line in lines:
        v = verdict(line["stated"])
        controls = {}
        for name, reading in CONTROLS.items():
            if name in line.get("controls", {}):
                controls[name] = {
                    "reading": reading,
                    "value": line["controls"][name][reading],
                    "not_ok": reading in verdict(
                        line["controls"][name])["failed"]}
        ok = v["ok"] and all(c["not_ok"] for c in controls.values())
        out["seeds"].append({"seed": line["seed"], "stated": v,
                             "controls": controls, "ok": ok})
        out["ok"] &= ok
    for key in READINGS:
        stated = [line["stated"][key] for line in lines]
        ctl = [line["controls"][n][key] for line in lines
               for n, r in CONTROLS.items()
               if r == key and n in line.get("controls", {})]
        out["limits"][key] = {
            "limit": LIMITS[key],
            "largest_stated": max(stated),
            "smallest_control": min(ctl) if ctl else None}
    return out


TAPS: dict = {"route": [], "logits": []}
_JITS: dict = {}


def reference_block(model: dict):
    """The reference's one block, jitted ONCE a process, letter and set of
    controls: (h, the block's params as a stack of one) -> (h, the experts
    selected or None, the state after the last position or None)."""
    import jax

    from reference import nemotron_h_decoder as ref

    def block(h, p1, letter, controls, forced):
        selected, states = [], []
        h, _ = ref.run_blocks(
            p1, dict(model, hybrid_override_pattern=letter), h, blocks=[0],
            states=states, selected=selected, controls=controls,
            forced=None if forced is None else [forced])
        return (h, selected[0] if selected else None,
                states[0] if states else None)

    key = json.dumps(model, sort_keys=True)
    if key not in _JITS:
        _JITS[key] = jax.jit(block, static_argnums=(2, 3))
    return _JITS[key]


def tapped(fn, kind: str, pick):
    import jax
    import numpy as np

    def put(*arrays):
        TAPS[kind].append(tuple(np.asarray(a) for a in arrays))

    def wrapped(*args, **kw):
        out = fn(*args, **kw)
        jax.debug.callback(put, *pick(args, out), ordered=True)
        return out
    return wrapped


def run_seed(args, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import nemotron_h_decoder as ref
    from symmetry_tpu.engine import engine as eng_mod
    from symmetry_tpu.engine.tokenizer import get_tokenizer
    from symmetry_tpu.models import hybrid, llama, moe
    from symmetry_tpu.ops.quant import QuantizedTensor, dequantize

    gc.collect()  # the seed before this one's engine and weights
    cfg = llama.preset(args.preset)
    if args.blocks:     # the first N published blocks alone (a diagnostic)
        import dataclasses

        kinds, ffns = llama.pair_blocks(llama.blocks_of(cfg)[:args.blocks])
        cfg = dataclasses.replace(cfg, num_layers=args.blocks,
                                  layer_types=kinds, ffn_layout=ffns)
    model = hybrid.hf_config(cfg)
    pattern = model["hybrid_override_pattern"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[args.dtype]
    lens = [int(n) for n in args.lens.split(",")]
    buckets = tuple(int(b) for b in args.buckets.split(","))
    K = args.decode_block
    blocks = -(-args.steps // K)
    steps = blocks * K

    def taken():
        got = {k: list(v) for k, v in TAPS.items()}
        for v in TAPS.values():
            v.clear()
        return got

    patches = [
        (moe, "route_top_k", tapped(
            moe.route_top_k, "route", lambda a, out: (out[1],))),
        (eng_mod, "logits_from_hidden", tapped(
            eng_mod.logits_from_hidden, "logits", lambda a, out: (out,))),
    ]
    originals = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    for mod, name, fn in patches:
        setattr(mod, name, fn)
    try:
        t0 = time.monotonic()
        quantized = args.dtype == "bfloat16"
        params = llama.init_params(cfg, jax.random.key(seed), dtype,
                                   quantize=quantized)
        engine = eng_mod.InferenceEngine(
            cfg, params, get_tokenizer(None, vocab_size=cfg.vocab_size),
            max_slots=max(4, len(lens)), max_seq_len=args.capacity,
            prefill_buckets=buckets, decode_block=K, prefill_chunk=None,
            cache_dtype=dtype, kv_quant=quantized)
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, 256, n).tolist() for n in lens]
        greedy = eng_mod.SamplingParams()
        # the first two together (two rows of their bucket), the rest alone
        groups = [list(range(min(2, len(lens))))] + [
            [lane] for lane in range(2, len(lens))]
        firsts, where, prefill_taps = {}, {}, []
        taken()
        for g, lanes in enumerate(groups):
            toks = engine.prefill_and_insert_many(
                [(lane, prompts[lane], greedy) for lane in lanes])
            jax.effects_barrier()
            prefill_taps.append(taken())
            bucket = max(engine.bucket_for(lens[lane]) for lane in lanes)
            for row, lane in enumerate(lanes):
                firsts[lane] = int(toks[row])
                where[lane] = (g, row, bucket)
        toks = np.concatenate([np.asarray(engine.decode_steps())
                               for _ in range(blocks)])  # [steps, slots]
        jax.effects_barrier()
        decode_taps = taken()
        engine.collect_expert_pairs()
        final_state = np.asarray(engine.state.cache.ssm)   # [Lm, B, H, P, N]
        program_s = time.monotonic() - t0
        reports = {"ssm": engine.ssm_report(), "moe": engine.moe_report(),
                   "attention": engine.attention_paths(),
                   "counts": engine.moe_counts()}
        reports["moe"] = {k: reports["moe"][k] for k in (
            "route", "grouped_matmul", "held", "expert_form")}
        reports["counts"].pop("expert_pairs")
    finally:
        for mod, name, fn in originals:
            setattr(mod, name, fn)

    Lx = pattern.count("E")
    assert [len(decode_taps[k]) for k in ("route", "logits")] == [
        Lx * steps, steps], {k: len(v) for k, v in decode_taps.items()}

    def f32(leaf):
        return (dequantize(leaf) if isinstance(leaf, QuantizedTensor)
                else jnp.asarray(leaf, jnp.float32))

    block_fn = reference_block(model)

    def block_params(i):
        name, j = ref.STACK[pattern[i]], ref.stack_index(pattern, i)
        return {"layers": {name: {
            k: f32(jax.tree.map(lambda a: a[j:j + 1], v))
            for k, v in engine.params["layers"][name].items()}}}

    def routed(lane: int, x: int) -> np.ndarray:
        """The program's experts at expert block x for EVERY position of
        the lane's sequence: the prompt's from its prefill, then a row a
        decode step — [n + steps, k]."""
        n = lens[lane]
        g, row, bucket = where[lane]
        at = row * bucket
        return np.concatenate(
            [prefill_taps[g]["route"][x][0][at:at + n]]
            + [decode_taps["route"][s * Lx + x][0][lane][None]
               for s in range(steps)])

    seqs = [prompts[lane] + [firsts[lane]] + toks[:steps - 1, lane].tolist()
            for lane in range(len(lens))]
    top = {"final_norm": f32(engine.params["final_norm"]),
           "lm_head": f32(engine.params["lm_head"])}
    embed = {"embed": f32(engine.params["embed"])}

    def full_pass(controls: tuple, lanes: list[int], force: bool = True):
        rows, kept, by_lane, state_errs, flips = [], [], [], [], []
        hs = {lane: ref.embed(embed, model, jnp.asarray(seqs[lane]))
              for lane in lanes}
        picked = {lane: [] for lane in lanes}
        for i, letter in enumerate(pattern):
            p1 = block_params(i)
            x = pattern[:i].count("E")
            for lane in lanes:
                n = lens[lane]
                theirs = routed(lane, x) if letter == "E" else None
                hs[lane], experts, state = block_fn(
                    hs[lane], p1, letter, controls,
                    jnp.asarray(theirs) if force and letter == "E" else None)
                if letter == "E":
                    picked[lane].append(
                        (np.sort(np.asarray(experts), axis=-1)
                         == np.sort(theirs, axis=-1)).all(-1))
                    flips.append(1.0 - picked[lane][-1][n - 1:].mean())
                if letter == "M":
                    want = np.asarray(state)
                    got = final_state[ref.stack_index(pattern, i), lane]
                    state_errs.append(float(
                        np.linalg.norm(got - want) / np.linalg.norm(want)))
            del p1
        for lane in lanes:
            n = lens[lane]
            g, row, _ = where[lane]
            logits = np.asarray(ref.head(top, model,
                                         hs[lane][n - 1:n + steps]))
            got = np.concatenate(
                [np.asarray(prefill_taps[g]["logits"][0][0],
                            np.float32)[row, :1]]
                + [np.asarray(decode_taps["logits"][s][0],
                              np.float32)[lane, :1] for s in range(steps)])
            err = (np.abs(got - logits).max(axis=-1)
                   / np.abs(logits).max(axis=-1))
            same = np.ones(steps + 1, bool)
            for x in range(Lx):
                same &= picked[lane][x][n - 1:n + steps]
            rows += err.tolist()
            kept += same.tolist()
            use = err if force or not same.any() else err[same]
            by_lane.append({"len": n, "kept": int(same.sum()),
                            "median": float(np.median(use)),
                            "max": float(np.max(use)),
                            "prefill_row": float(err[0])})
        rows, kept = np.asarray(rows), np.asarray(kept)
        # forced: every row is on the program's path; free: the rows whose
        # routing agreed at every block, where there are any
        use = rows if force or not kept.any() else rows[kept]
        return {"logit_median": float(np.median(use)),
                "logit_max": float(np.max(use)),
                "logit_median_all": float(np.median(rows)),
                "logit_max_all": float(np.max(rows)),
                "excluded_share": float(1.0 - kept.mean()),
                # a block and a position the reference's own scores choose
                # another set at: the mean over blocks, and the worst block
                "flip_share": float(np.mean(flips)),
                "flip_share_worst_block": float(np.max(flips)),
                "state_err": max(state_errs),
                "state_err_median": float(np.median(state_errs)),
                "rows": int(rows.size), "lanes": by_lane}

    t1 = time.monotonic()
    everyone = list(range(len(lens)))
    stated = full_pass((), everyone)
    free = full_pass((), everyone, force=False)
    controls = {name: full_pass((name,), everyone[:args.control_lanes])
                for name in (args.controls.split(",") if args.controls
                             else ())}
    return {"seed": seed, "preset": args.preset, "lens": lens,
            "buckets": list(buckets), "decode_block": K, "steps": steps,
            **reports, "stated": stated, "free": free, "controls": controls,
            "program_s": round(program_s, 1),
            "reference_s": round(time.monotonic() - t1, 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="nemotron-3-nano-30b-a3b")
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--lens", default="140,230,50")
    ap.add_argument("--buckets", default="64,128,256")
    ap.add_argument("--capacity", type=int, default=640)
    ap.add_argument("--decode-block", type=int, default=16)
    ap.add_argument("--steps", type=int, default=320)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--controls",
                    default=",".join((*CONTROLS, *REPORTED)))
    ap.add_argument("--control-lanes", type=int, default=1)
    ap.add_argument("--blocks", type=int, default=0,
                    help="only the first N published blocks (a diagnostic: "
                         "9 of them fit the chip in float32)")
    ap.add_argument("--out")
    ap.add_argument("--verdict")
    args = ap.parse_args()
    if args.verdict:
        with open(args.verdict) as fh:
            lines = [json.loads(line) for line in fh if '"stated"' in line]
    else:
        lines = []
        for seed in args.seeds.split(","):
            lines.append(run_seed(args, int(seed)))
            print(json.dumps(lines[-1]), flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as fh:
                    fh.write(json.dumps(lines[-1]) + "\n")
    result = judge(lines)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
