"""Plain reference of a sparse-expert GQA decoder that GENERATES BY DIFFUSION
OVER BLOCKS (SDAR-30B-A3B-Chat: the Qwen3-MoE block — per-head q/k norms, 128
routed experts top 8 — under a block mask): the full forward pass and the
generation loop in straightforward `jax.numpy` / `numpy` and float32 — a Python
loop over layers and over experts; no kernels, no cache, no batching, no
quantisation.

The layer is `moe_decoder.py`'s with per-head norms; the one change is the
MASK. With block length B,

    M[i, j] = 1  iff  j // B <= i // B

— causal across blocks, bidirectional inside one. For a layer with input h:

    n = rms_norm(h) * w_attn_norm
    q = rope(rms_norm_head(n wq) * q_norm), k = rope(rms_norm_head(n wk)
        * k_norm), v = n wv             (n_q / n_kv heads of d, theta as given)
    h' = h + softmax_M(q k^T / sqrt(d)) v @ wo
    y  = rms_norm(h') * w_mlp_norm; router logits y router (float32), top k,
         gates = softmax over the selected logits (the softmax over all,
         renormalised: `norm_topk_prob`)
    h'' = h' + sum_e g_e (silu(y wg_e) * (y wu_e)) wd_e
    logits = rms_norm(h) * w_final_norm @ lm_head

and the logit row at position i predicts the token AT position i (mask-predict:
no shift).

GENERATION (`generate`). The prompt's whole blocks, P' = B (P // B) tokens, are
context; its r = P - P' left-over tokens open the first generated block as
KNOWN positions, the rest of the block is the mask token. A block is denoised:
a forward over [context || the block as it stands] under M; every still-masked
position takes a candidate (here the argmax: the reference generates greedily)
and a confidence, the probability softmax(logits)[candidate] over the whole
vocabulary; `low_confidence_static` makes known the n_i masked positions of
highest confidence at the block's i-th forward (n_i = B // steps, the first
B % steps forwards one more; all that are left at the last forward or where
fewer are left; a tie goes to the lower position), `low_confidence_dynamic`
every masked position whose confidence exceeds the threshold when those are at
least n_i (or all that are left), else the static choice. When no mask is left
the block joins the context (in a program with a cache: one more forward writes
its K/V), its new tokens are emitted in position order and the next block
starts all-masked. A stop token inside a block ends the stream before it; a
budget ends it at the budget.

Departures from the published script (JetLM/SDAR `generate.py`), each on
purpose:
- known positions are a plane of their own, not `token == mask_id`: a candidate
  that equals the mask token's id is a token like any other and its position
  is decided (comparing ids would stall the block);
- the confidence is read BEFORE the top-k / top-p cut (the script reads it
  after, where a greedy lane's every candidate has probability 1 and the
  choice of position falls to the tie rule);
- a block whose positions are all known skips its remaining denoise forwards
  (the script does too); the served program runs them and changes nothing by
  them;
- the script emits whole blocks and trims at the end; here a stream stops at
  its stop token or budget.

Weights arrive in the program's layout ([in, out] matrices on a leading layer
axis; quantised leaves dequantised by the caller). `layer_forward` is one
layer over many sequences, so a caller can hold one layer's float32 weights at
a time and advance every sequence it compares by it. On a TPU
a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set; every entry point sets it.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from moe_decoder import rms_norm, rope, sparse_moe_block  # noqa: E402
from sparse_moe_decoder import routed_moe_block, softmax_bf16  # noqa: E402,F401


def block_mask(s: int, block: int):
    """[S, S] bool: query i sees key j iff j's block is not after i's."""
    blk = jnp.arange(s) // block
    return blk[None, :] <= blk[:, None]


def causal_mask(s: int):
    pos = jnp.arange(s)
    return pos[None, :] <= pos[:, None]


def attention(x, p, model, mask, softmax=jax.nn.softmax, kv_round=None):
    """x [S, E] (normed), mask [S, S] bool -> the heads' outputs [S, n_q * d]
    (before `wo`). `kv_round(k, v)`, where given, rounds the K/V rows as a
    program's cache holds them (a parity tool's stated precision)."""
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n_q
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    s = x.shape[0]
    pos = jnp.arange(s)
    q = rms_norm((x @ p["wq"]).reshape(s, n_q, d), p["q_norm"], eps)
    k = rms_norm((x @ p["wk"]).reshape(s, n_kv, d), p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(s, n_kv, d)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    if kv_round is not None:
        k, v = kv_round(k, v)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(float(d))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    attn = jnp.einsum("hst,thd->shd", softmax(scores, -1), v)
    return attn.reshape(s, n_q * d)


def layer_forward(p: dict, model: dict, hs: list, masks: list,
                  softmax=jax.nn.softmax, routed: bool = False,
                  kv_round=None):
    """ONE layer over a list of sequences `hs` ([S_i, E] each) under their
    `masks`: attention a sequence, the experts over all their rows together
    (a token's experts depend on no other token) -> (hs, {"attn": [the heads'
    outputs [S_i, n_q * d]], "margin": [the gap between each token's k-th and
    (k+1)-th router logit, [S_i]]}). `p`: the layer's leaves, no layer axis.
    `routed`: each expert on the rows that chose it alone
    (`sparse_moe_decoder.routed_moe_block`: the same result, a sixteenth of
    the products at 128 experts top 8)."""
    experts = routed_moe_block if routed else sparse_moe_block
    cuts = np.cumsum([x.shape[0] for x in hs])[:-1].tolist()
    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        attn = [attention(rms_norm(x, p["attn_norm"], eps), p, model, m,
                          softmax, kv_round) for x, m in zip(hs, masks)]
        hs = [x + a @ p["wo"] for x, a in zip(hs, attn)]
        y, margin = experts(
            rms_norm(jnp.concatenate(hs), p["mlp_norm"], eps), p["router"],
            p["wg"], p["wu"], p["wd"], model["num_experts_per_tok"])
        hs = [x + part for x, part in zip(hs, jnp.split(y, cuts))]
    return hs, {"attn": attn, "margin": jnp.split(margin, cuts)}


def run_layers(params: dict, model: dict, h, mask, layers=None,
               softmax=jax.nn.softmax, routed: bool = False, kv_round=None):
    """h [S, E] through `layers` (default: all) under `mask` -> (h, per-layer
    dicts of `attn` [S, n_q * d] and `margin` [S]). `params["layers"]` holds
    the leaves of exactly the layers asked for, in order, on a leading layer
    axis. A caller that holds one layer's float32 weights at a time, or
    advances many sequences by it, calls `layer_forward` itself."""
    layers = range(model["num_hidden_layers"]) if layers is None else layers
    details = []
    for n, _ in enumerate(layers):
        p = {name: leaf[n] for name, leaf in params["layers"].items()}
        (h,), d = layer_forward(p, model, [h], [mask], softmax, routed,
                                kv_round)
        details.append({"attn": d["attn"][0], "margin": d["margin"][0]})
    return h, details


def embed(params: dict, tokens):
    return params["embed"][jnp.asarray(tokens)].astype(jnp.float32)


def head(params: dict, model: dict, h):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, params["final_norm"],
                        model["rms_norm_eps"]) @ params["lm_head"]


def reference_logits(params: dict, model: dict, tokens, *,
                     with_details: bool = False, causal: bool = False):
    """Logits [S, vocab] (float32) of one sequence `tokens` [S] under the
    block mask of `model["block_length"]` (`causal`: the plain causal mask
    instead — what the model is NOT, for a control). Row i predicts the token
    at position i. `params`: float32 arrays in the program's layout —
    moe_decoder.py's plus layers.{q_norm, k_norm} [L, d]. `model`: the
    published config.json keys plus `block_length`."""
    s = len(tokens)
    mask = causal_mask(s) if causal else block_mask(s, model["block_length"])
    h, details = run_layers(params, model, embed(params, tokens), mask)
    logits = head(params, model, h)
    return (logits, details) if with_details else logits


def transfer_schedule(block: int, steps: int) -> list[int]:
    """Masked positions the static rule makes known at each of a block's
    `steps` forwards (the published `get_num_transfer_tokens`)."""
    assert 1 <= steps <= block, (block, steps)
    return [block // steps + (i < block % steps) for i in range(steps)]


def unmask(confidence, known, n: int, threshold=None, final: bool = False):
    """Which masked positions of ONE block become known -> bool [B].
    `confidence` [B] float, `known` [B] bool, `n` this forward's static
    count; see the module docstring for the two rules."""
    confidence = np.asarray(confidence, np.float64)
    known = np.asarray(known, bool)
    masked = np.flatnonzero(~known)
    take = np.zeros(known.shape, bool)
    if final or len(masked) <= n:
        take[masked] = True
        return take
    if threshold is not None:
        high = [i for i in masked if confidence[i] > threshold]
        if len(high) >= n:
            take[high] = True
            return take
    # highest confidence first, a tie to the lower position
    order = sorted(masked, key=lambda i: (-confidence[i], i))
    take[order[:n]] = True
    return take


def generate(params: dict, model: dict, prompt, max_new: int, *, steps=None,
             threshold=None, stop_ids=(), logits_fn=None):
    """Greedy generation by diffusion over blocks -> (tokens, trace).

    `tokens`: what a stream receives, at most `max_new`, cut before a stop
    token. `trace`: one dict a forward — `context` (committed length),
    `block` (the block as it stood, ids), `known` (bool [B]) and, for a
    denoise forward, `logits` [B, V], `candidates`, `confidence`, `take`;
    `commit: True` marks the forward that writes a finished block's K/V
    (no logits are read from it). `logits_fn(tokens) -> [S, V]` replaces the
    forward (default: `reference_logits`)."""
    block, mask_id = model["block_length"], model["mask_token_id"]
    steps = block if steps is None else steps
    schedule = transfer_schedule(block, steps)
    if logits_fn is None:
        def logits_fn(seq):
            return reference_logits(params, model, np.asarray(seq))
    prompt = [int(t) for t in prompt]
    whole = len(prompt) // block * block
    context, left_over = prompt[:whole], prompt[whole:]
    cur = left_over + [mask_id] * (block - len(left_over))
    known = np.array([True] * len(left_over)
                     + [False] * (block - len(left_over)))
    out: list[int] = []
    trace: list[dict] = []
    stop_ids = set(int(t) for t in stop_ids)
    while len(out) < max_new:
        given = known.copy()
        for i in range(steps):
            if known.all():
                break
            logits = np.asarray(logits_fn(context + cur))[-block:]
            cand = logits.argmax(-1)
            z = logits.astype(np.float64)
            z = z - z.max(-1, keepdims=True)
            conf = (np.exp(z) / np.exp(z).sum(-1, keepdims=True))[
                np.arange(block), cand]
            take = unmask(conf, known, schedule[i], threshold,
                          final=i == steps - 1)
            trace.append({"context": len(context), "block": list(cur),
                          "known": known.copy(), "logits": logits,
                          "candidates": cand, "confidence": conf,
                          "take": take})
            cur = [int(cand[j]) if take[j] else cur[j] for j in range(block)]
            known = known | take
        trace.append({"context": len(context), "block": list(cur),
                      "known": known.copy(), "commit": True})
        context = context + cur
        for j in range(block):
            if given[j]:
                continue
            if cur[j] in stop_ids or len(out) >= max_new:
                return out, trace
            out.append(cur[j])
        cur, known = [mask_id] * block, np.zeros(block, bool)
    return out, trace
