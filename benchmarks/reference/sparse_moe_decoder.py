"""Plain reference of a sparse-expert GQA decoder with LEARNED SPARSE
ATTENTION (Keye-VL-2.0-30B-A3B's language model: a DeepSeek-Sparse-Attention
lightning indexer under GQA, a rotary of three position components, per-head
q/k norms, 128 routed experts): the full forward pass in straightforward
`jax.numpy` and float32 — a Python loop over layers and over experts, the
selection by `jax.lax.top_k`; no kernels, no cache, no batching, no
quantisation, no threshold.

For a layer with input h_t (position t), x_t = rms_norm(h_t) * w_attn_norm:

    q_t = x_t wq (n_q heads of d), k_t = x_t wk, v_t = x_t wv (n_kv heads);
    q, k: rms_norm per head over its d channels (* q_norm / k_norm);
    q, k: multimodal rotary — rotate-half pairs i in 0 .. d/2, frequency
          theta^(-2i/d), pair i turned by position component c(i):
          temporal for the first mrope_section[0] pairs, height for the
          next, width for the last. Equal components are the plain rotary.
    indexer (sa_config): qI_t = x_t wqi (index heads of d_I), kI_t = x_t wki
          (ONE key of d_I for all of them), w_t = x_t wwi (a weight a head);
          qI, kI take the plain rotary over their d_I channels by the
          temporal component; the score of s <= t is
              I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]);
    selection: S_t = the min(t + 1, topk) positions s <= t of the largest
          I[t, s], ties toward the lower position (`jax.lax.top_k`'s order);
          one set for all query heads;
    o[t, a] = sum_{s in S_t} softmax_{s in S_t}(q[t, a] . k[s, a // g]
          / sqrt(d)) v[s, a // g];   h'_t = h_t + o_t wo;
    y_t = rms_norm(h'_t) * w_mlp_norm; router logits y_t router (float32),
          top k experts, gates = softmax over the selected logits (the
          softmax over all, renormalised: `norm_topk_prob`);
          h''_t = h'_t + sum_e g_e (silu(y_t wg_e) * (y_t wu_e)) wd_e.
    logits = rms_norm(h) * w_final_norm @ lm_head.

`selection`, where given, replaces S_t: `selection[layer]` is a [S, S] bool
array (query, position) to attend over — so a caller can ask "are the logits
right GIVEN the program's own sets", apart from whether two roundings of the
index scores picked the same near-tied positions. `with_details` also
returns, per layer, the sets this reference chose and the heads' outputs.

The expert block, the norm and the plain rotary are `moe_decoder.py`'s, in
this directory. Weights arrive in the program's layout ([in, out] matrices
on a leading layer axis; quantised leaves dequantised by the caller).
`run_layers` takes a range of layers, so a caller can hold one layer's
float32 weights at a time, and a `query_tile`, so a 14k-token prompt's
[heads, S, S] scores (28 GB) are never alive whole: a query's row of the
index scores, its set and its attention depend on no other query.

On a TPU a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set; every entry point sets it.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from moe_decoder import rms_norm, rope, sparse_moe_block  # noqa: E402


def mrope(x, positions, theta, section):
    """x [S, H, D], positions [3, S]; pair i of the D / 2 rotate-half pairs
    is turned by the component its section names."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    of_pair = jnp.concatenate([jnp.full((n,), c, jnp.int32)
                               for c, n in enumerate(section)])
    assert of_pair.shape[0] == d // 2, (section, d)
    ang = positions.astype(jnp.float32).T[:, of_pair] * inv_freq   # [S, D/2]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def index_scores(x, p, model, t_pos, rows=None):
    """x [S, E] (normed) -> I [R, S] float32 (not yet causal) of the
    queries in `rows` (a slice; default all S)."""
    sa = model["sa_config"]
    n_i, d_i = sa["indexer_num_heads"], sa["indexer_head_dim"]
    s = x.shape[0]
    rows = slice(0, s) if rows is None else rows
    qi = rope((x[rows] @ p["wqi"]).reshape(-1, n_i, d_i), t_pos[rows],
              model["rope_theta"])
    ki = rope((x @ p["wki"]).reshape(s, 1, d_i), t_pos,
              model["rope_theta"])[:, 0]
    w = x[rows] @ p["wwi"]                                         # [R, n_i]
    return jnp.einsum("tj,tjs->ts", w,
                      jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki)))


def select(scores, topk: int, first: int = 0):
    """[R, S] index scores of queries first .. first + R - 1 -> [R, S]
    bool: query t's min(t + 1, topk) best positions s <= t by
    `jax.lax.top_k` (ties toward the lower position)."""
    r, s = scores.shape
    causal = jnp.arange(s)[None, :] <= (first + jnp.arange(r))[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, s))
    chosen = jnp.zeros((r, s), bool).at[jnp.arange(r)[:, None], idx].set(True)
    return chosen & causal      # a row of fewer candidates took -inf ones


def softmax_bf16(scores, axis=-1):
    """The softmax of `attention` in the NEAREST PRECISION BELOW float32:
    scores, exponentials, their sum and the probabilities each rounded to
    bfloat16 — what a kernel's softmax must NOT be (tools/dsa_parity.py's
    control)."""
    s = scores.astype(jnp.bfloat16)
    e = jnp.exp(s - jnp.max(s, axis, keepdims=True))
    return (e / jnp.sum(e, axis, keepdims=True).astype(jnp.bfloat16)
            ).astype(jnp.float32)


def attention(x, p, model, positions, keep, rows=None,
              softmax=jax.nn.softmax):
    """x [S, E] (normed), keep [R, S] bool -> the heads' outputs [R, n_q * d]
    (before `wo`) of the queries in `rows` (a slice; default all S)."""
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n_q
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    section = model["rope_scaling"]["mrope_section"]
    s = x.shape[0]
    rows = slice(0, s) if rows is None else rows
    q = rms_norm((x[rows] @ p["wq"]).reshape(-1, n_q, d), p["q_norm"], eps)
    k = rms_norm((x @ p["wk"]).reshape(s, n_kv, d), p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(s, n_kv, d)
    q = mrope(q, positions[:, rows], theta, section)
    k = mrope(k, positions, theta, section)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(float(d))
    scores = jnp.where(keep[None], scores, -jnp.inf)
    attn = jnp.einsum("hst,thd->shd", softmax(scores, -1), v)
    return attn.reshape(-1, n_q * d)


def routed_moe_block(x, router, wg, wu, wd, k, pad_to: int = 256):
    """`moe_decoder.sparse_moe_block`'s result, each expert evaluated on the
    rows that chose it alone (128 experts over 14k tokens: a sixteenth of
    the products): x [S, E] -> (y [S, E], margin [S]). An expert's rows are
    padded to a multiple of `pad_to` with row 0 at gate 0 (which adds
    nothing), so that a device compiles a few shapes and not one an
    expert."""
    import numpy as np

    logits = x @ router                                            # [S, X]
    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
    margin = ranked[:, k - 1] - ranked[:, k]
    top_vals, top_idx = jax.lax.top_k(logits, k)
    gates = np.asarray(jax.nn.softmax(top_vals, axis=-1))          # [S, k]
    top_idx = np.asarray(top_idx)
    y = jnp.zeros_like(x)
    for e in range(router.shape[-1]):
        chose = (top_idx == e)
        rows = np.nonzero(chose.any(axis=-1))[0]
        if rows.size == 0:
            continue
        g = (gates * chose).sum(axis=-1)[rows]
        pad = -rows.size % pad_to
        rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
        g = jnp.asarray(np.concatenate([g, np.zeros(pad, g.dtype)]))
        xe = x[rows]
        out = (jax.nn.silu(xe @ wg[e]) * (xe @ wu[e])) @ wd[e]
        y = y.at[rows].add(g[:, None] * out)
    return y, margin


def run_layers(params: dict, model: dict, h, positions, layers=None,
               selection=None, query_tile=None, softmax=jax.nn.softmax):
    """h [S, E] through `layers` (default: all) -> (h, per-layer dicts of
    `keep` [S, S] bool, `attn` [S, n_q * d] (the heads' outputs before
    `wo`), `margin` [S]). `params["layers"]` holds the leaves of exactly
    the layers asked for, in order. `query_tile`: the indexer, the
    selection and the attention a tile of queries at a time (each query's
    row is its own: the same result, a [heads, tile, S] score array alive
    in place of [heads, S, S]); experts then by `routed_moe_block`."""
    layers = range(model["num_hidden_layers"]) if layers is None else layers
    topk = model["sa_config"]["topk"]
    s = h.shape[0]
    tiles = [slice(t, min(t + (query_tile or s), s))
             for t in range(0, s, query_tile or s)]
    experts = sparse_moe_block if query_tile is None else routed_moe_block
    details = []
    with jax.default_matmul_precision("highest"):
        for n, i in enumerate(layers):
            p = {name: leaf[n] for name, leaf in params["layers"].items()}
            x = rms_norm(h, p["attn_norm"], model["rms_norm_eps"])
            keep, attn = [], []
            for rows in tiles:
                keep.append(
                    select(index_scores(x, p, model, positions[0], rows),
                           topk, rows.start) if selection is None
                    else jnp.asarray(selection[i][rows]))
                attn.append(attention(x, p, model, positions, keep[-1],
                                      rows, softmax))
            keep, attn = jnp.concatenate(keep), jnp.concatenate(attn)
            h = h + attn @ p["wo"]
            x = rms_norm(h, p["mlp_norm"], model["rms_norm_eps"])
            y, margin = experts(
                x, p["router"], p["wg"], p["wu"], p["wd"],
                model["num_experts_per_tok"])
            h = h + y
            details.append({"keep": keep, "attn": attn, "margin": margin})
    return h, details


def embed(params: dict, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def head(params: dict, model: dict, h):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, params["final_norm"],
                        model["rms_norm_eps"]) @ params["lm_head"]


def reference_logits(params: dict, model: dict, tokens, *, positions=None,
                     selection=None, with_details: bool = False):
    """Logits [S, vocab] (float32) of one sequence `tokens` [S].
    `positions` [3, S]: the rotary's components (default: a text token's,
    all three the position in the sequence). `selection`: see the module.
    `params`: float32 arrays in the program's layout — moe_decoder.py's
    plus layers.{q_norm, k_norm} [L, d], layers.wqi [L, E, n_I * d_I],
    layers.wki [L, E, d_I], layers.wwi [L, E, n_I]. `model`: the published
    config.json keys."""
    s = tokens.shape[0]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (3, s))
    h, details = run_layers(params, model, embed(params, tokens),
                            jnp.asarray(positions), selection=selection)
    logits = head(params, model, h)
    return (logits, details) if with_details else logits
