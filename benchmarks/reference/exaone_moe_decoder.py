"""Plain reference of the decoder with WINDOW (rotary) and FULL (NoPE)
attention layers under q/k norms, a leading dense layer, sigmoid-routed
experts beside a shared one, and a multi-token-prediction module behind the
trunk (k-exaone-236b-a23b, HF `exaone_moe`): the full forward pass in
straightforward `jax.numpy` and float32 — a loop over layers and over
experts, every position's attention over the whole sequence under the
layer's own mask; no cache, no ring, no kernels, no batching, no
quantisation, no drafting. Imports nothing from the program.

`norm(x, w) = x * rsqrt(mean x^2 + eps) * w` (a plain weight), eps
`rms_norm_eps`.

    h = embed[tokens]
    for each layer l:
        h = h + attention_l(norm(h, w_norm))
        n = norm(h, w_ffn_norm)
        h = h + ffn_l(n)
    hidden = norm(h, w_final_norm);  logits = hidden @ lm_head      (untied)

attention_l (H = `num_attention_heads`, K = `num_key_value_heads`, D =
`head_dim`; query head i reads KV head i // (H / K)):

    q, k, v = a @ wq, a @ wk, a @ wv             no bias
    q, k    = norm(q, w_q_norm), norm(k, w_k_norm)   per head, over its D
    `layer_types[l]` "sliding_attention": q and k turned by the rotary at
        `rope_parameters.rope_theta`, the whole head, by halves (HF's
        `rotate_half`), and key s visible to query t iff
        t - `sliding_window` < s <= t; "full_attention": NO positional
        embedding, and iff s <= t
    out = concat_heads(softmax(q k^T / sqrt(D)) v) @ wo

ffn_l, `mlp_layer_types[l]` "dense": (silu(n @ wg) * (n @ wu)) @ wd at
`intermediate_size`. "sparse" (deepseek_v3's router at one group):

    s    = sigmoid(n @ router)                   [S, X] float32, X =
                                                 `experts_routed_over`
    idx  = top-k(s + e_score_correction_bias)    ties toward the lower index
    g    = `routed_scaling_factor` * s[idx] / (sum s[idx] + 1e-20)
    y    = sum_{e in idx, e HELD} g_e * expert_e(n)  +  shared(n)

each expert and the shared expert a gated silu FFN of width
`moe_intermediate_size`. `experts_held` [first, count] (a benchmark
configuration's share of a deployment; no published config has it): the
routed experts whose weights `params` holds — `num_experts` of them, stack
index e - first; a selected expert that is not held contributes nothing and
the gates are NOT renormalised over the held ones. Without the key all
`num_experts` are held.

The multi-token-prediction module (DeepSeek-V3's form, arXiv:2412.19437
s2.2; one module), at position t with the NEXT token x_{t+1}:

    x'  = [norm(hidden_t, w_hnorm) ; norm(embed[x_{t+1}], w_enorm)] @ in_proj
    x'  = x' + attention(norm(x', w_norm))       full NoPE, over the module's
                                                 OWN sequence x'_0 .. x'_t
    x'  = x' + ffn(norm(x', w_ffn_norm))         sparse, as above
    logits'_t = norm(x', w_mtp_final_norm) @ lm_head        for x_{t+2}

`hidden_t` is the trunk's hidden state as the head reads it, AFTER the final
norm.

What the published config does not say, each listed under `assumed` in
benchmarks/configs/k-exaone-236b-a23b.json (the checkpoint's modeling code
is not in the sandbox): pre-norm placement; q/k norms and rotary on window
layers only (the EXAONE-4 family's hybrid attention); the selection bias;
the module's projection order ([hidden ; embedding]), the hidden state it is
handed (after the final norm) and that its FFN is a sparse layer with the
same held share.

Weights arrive in the program's layout (`[in, out]` matrices stacked on a
leading axis: `attn` [n_full, ...] the full layers in order, `swa`
[n_window, ...] the window layers, `dense` [n_dense, ...], `ffn`
[n_sparse, ...], `mtp` {hnorm, enorm, in_proj, attn, ffn, final_norm} with
a leading axis of one; quantised leaves dequantised by the caller).

`wrong` names ONE departure a comparison must be able to tell:
"no_qk_norm", "rope_full" (ropes a full layer), "window_short" (one key
fewer), "no_bias" (selects without the bias), "renormed_held" (gates
renormalised over the held experts), "mtp_swapped" ([embedding ; hidden]),
"mtp_prenorm_hidden" (hands the module the hidden state before the final
norm).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_halves(x, theta: float):
    """x [S, heads, D]: frequency i turns the channels (i, i + D / 2)."""
    s, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def attention(x, p, model, *, windowed: bool, wrong: str | None = None,
              tile: int | None = None):
    """x [S, E] (already normed) -> [S, E]; a window layer ropes, a full
    one does not. `tile` computes the scores a tile of queries at a time
    (the same numbers; a long prompt's [H, S, S] would not fit)."""
    n_h, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n_h
    eps = model["rms_norm_eps"]
    s = x.shape[0]
    q = (x @ p["wq"]).reshape(s, n_h, d)
    k = (x @ p["wk"]).reshape(s, n_kv, d)
    v = (x @ p["wv"]).reshape(s, n_kv, d)
    if wrong != "no_qk_norm":
        q, k = norm(q, p["q_norm"], eps), norm(k, p["k_norm"], eps)
    if windowed or wrong == "rope_full":
        theta = float(model["rope_parameters"]["rope_theta"])
        q, k = rope_halves(q, theta), rope_halves(k, theta)
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if windowed:
        span = model["sliding_window"] - (wrong == "window_short")
        mask &= pos[None, :] > pos[:, None] - span
    group = n_h // n_kv

    def rows(lo, hi):
        qg = q[lo:hi].reshape(hi - lo, n_kv, group, d)
        scores = jnp.einsum("skgd,tkd->kgst", qg, k) * d ** -0.5
        scores = jnp.where(mask[None, None, lo:hi], scores, -jnp.inf)
        a = jax.nn.softmax(scores, -1)
        return jnp.einsum("kgst,tkd->skgd", a, v).reshape(hi - lo, n_h * d)

    step = tile or s
    out = jnp.concatenate([rows(lo, min(lo + step, s))
                           for lo in range(0, s, step)], axis=0)
    return out @ p["wo"]


def gated(n, wg, wu, wd):
    return (jax.nn.silu(n @ wg) * (n @ wu)) @ wd


def held_of(model: dict) -> tuple[int, int]:
    return tuple(model.get("experts_held") or (0, model["num_experts"]))


def route(n, p, model, wrong: str | None = None):
    """n [S, E] -> (gates [S, k], experts [S, k], margin [S]): the gap
    between the k-th and (k+1)-th biased score."""
    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid((n @ p["router"]).astype(jnp.float32))
    biased = scores if wrong == "no_bias" else scores + p["expert_bias"]
    ranked, top_idx = jax.lax.top_k(biased, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_idx = top_idx[:, :k]
    top = jnp.take_along_axis(scores, top_idx, axis=-1)
    if wrong == "renormed_held":
        first, count = held_of(model)
        mine = (top_idx >= first) & (top_idx < first + count)
        total = jnp.sum(jnp.where(mine, top, 0.0), -1, keepdims=True)
    else:
        total = jnp.sum(top, axis=-1, keepdims=True)
    gates = model["routed_scaling_factor"] * top / (total + 1e-20)
    return gates, top_idx, margin


def routed(n, p, model, wrong: str | None = None):
    """The HELD experts' part of the routed sum: (y [S, E], margin [S])."""
    gates, top_idx, margin = route(n, p, model, wrong)
    first, _ = held_of(model)

    def one(out, expert):
        e, wg, wu, wd = expert
        g = jnp.sum(jnp.where(top_idx == e, gates, 0.0), axis=-1)
        return out + g[:, None] * gated(n, wg, wu, wd), None

    # (a loop over the experts as a scan: unrolled bodies at `highest` take
    # the chip's compiler minutes a program)
    out, _ = jax.lax.scan(
        one, jnp.zeros_like(n),
        (first + jnp.arange(p["wg"].shape[0]), p["wg"], p["wu"], p["wd"]))
    return out, margin


def shared(n, p):
    return gated(n, p["sg"], p["su"], p["sd"])


def sparse_ffn(n, p, model, wrong: str | None = None):
    y, margin = routed(n, p, model, wrong)
    return y + shared(n, p), margin


def stack_of(model: dict, i: int) -> tuple[str, int]:
    """Layer i's attention stack and its index there: the full layers lie
    in `attn`, the window layers in `swa`, each in layer order."""
    types = model["layer_types"]
    return ("swa" if types[i] == "sliding_attention" else "attn",
            sum(t == types[i] for t in types[:i]))


def ffn_of(model: dict, i: int) -> tuple[str, int]:
    kinds = model["mlp_layer_types"]
    return ("dense" if kinds[i] == "dense" else "ffn",
            sum(t == kinds[i] for t in kinds[:i]))


def at(stack: dict, j: int) -> dict:
    """Entry j of every leaf's leading axis (the module's stack nests)."""
    return {k: at(v, j) if isinstance(v, dict) else v[j]
            for k, v in stack.items()}


def layer_forward(params: dict, model: dict, h, i: int = 0,
                  wrong: str | None = None, **attention_kw):
    """Hidden states [S, E] through layer i of the stacks in `params`.
    Returns (h, router margin [S]; inf for a dense layer)."""
    eps = model["rms_norm_eps"]
    lay = params["layers"]
    with jax.default_matmul_precision("highest"):
        name, j = stack_of(model, i)
        p = at(lay[name], j)
        h = h + attention(norm(h, p["norm"], eps), p, model,
                          windowed=name == "swa", wrong=wrong,
                          **attention_kw)
        name, j = ffn_of(model, i)
        p = at(lay[name], j)
        n = norm(h, p["norm"], eps)
        if name == "dense":
            return h + gated(n, p["wg"], p["wu"], p["wd"]), jnp.full(
                h.shape[:1], jnp.inf)
        y, margin = sparse_ffn(n, p, model, wrong)
        return h + y, margin


def trunk_hidden(params: dict, model: dict, tokens, *,
                 wrong: str | None = None, **attention_kw):
    """(the residual stream after the last layer [S, E], margins
    [sparse layers, S])."""
    h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    margins = []
    for i in range(model["num_hidden_layers"]):
        h, margin = layer_forward(params, model, h, i, wrong=wrong,
                                  **attention_kw)
        margins.append(margin)
    return h, jnp.stack(margins)


def head(params: dict, hidden):
    with jax.default_matmul_precision("highest"):
        return hidden @ params["lm_head"]


def mtp_hidden(params: dict, model: dict, hidden, stream, next_tokens, *,
               wrong: str | None = None, **attention_kw):
    """The module over positions 0 .. n - 1: `hidden` [n, E] the trunk's
    normed hidden states there (`stream` the un-normed ones, for the
    control), `next_tokens` [n] the token after each. Returns the module's
    normed hidden state [n, E] (`head` makes the logits)."""
    eps = model["rms_norm_eps"]
    mp = at(params["mtp"], 0)
    with jax.default_matmul_precision("highest"):
        e = params["embed"][jnp.asarray(next_tokens)].astype(jnp.float32)
        a = norm(stream if wrong == "mtp_prenorm_hidden" else hidden,
                 mp["hnorm"], eps)
        b = norm(e, mp["enorm"], eps)
        x = jnp.concatenate([b, a] if wrong == "mtp_swapped" else [a, b],
                            axis=-1) @ mp["in_proj"]
        p = mp["attn"]
        x = x + attention(norm(x, p["norm"], eps), p, model, windowed=False,
                          wrong=wrong if wrong == "no_qk_norm" else None,
                          **attention_kw)
        p = mp["ffn"]
        y, _ = sparse_ffn(norm(x, p["norm"], eps), p, model, wrong)
        return norm(x + y, mp["final_norm"], eps)


def reference_logits(params: dict, model: dict, tokens, *,
                     with_margins: bool = False, wrong: str | None = None,
                     **attention_kw):
    """(trunk logits [S, vocab], module logits [S - 1, vocab]) in float32
    of one sequence `tokens` [S]: trunk row t scores token t + 1; module
    row t, made from the trunk's hidden state at t and token t + 1, scores
    token t + 2 (None for a model without a module). With `with_margins`,
    also the router margins [layers, S].

    `params`: float32 arrays — embed [V, E], final_norm [E], lm_head [E, V],
    layers.attn / layers.swa {norm [n, E], wq [n, E, H D], wk, wv [n, E,
    K D], wo [n, H D, E], q_norm, k_norm [n, D]}, layers.dense {norm, wg, wu
    [n, E, F], wd}, layers.ffn {norm [n, E], router [n, E, X], expert_bias
    [n, X], wg, wu [n, Xheld, E, F], wd, sg, su [n, E, F], sd}, mtp (the
    module's, leading axis 1). `model`: the published config.json keys.
    """
    tokens = jnp.asarray(tokens)
    stream, margins = trunk_hidden(params, model, tokens, wrong=wrong,
                                   **attention_kw)
    hidden = norm(stream, params["final_norm"], model["rms_norm_eps"])
    logits = head(params, hidden)
    draft = None
    if model.get("num_nextn_predict_layers") and tokens.shape[0] > 1:
        draft = head(params, mtp_hidden(
            params, model, hidden[:-1], stream[:-1], tokens[1:], wrong=wrong,
            **attention_kw))
    return (logits, draft, margins) if with_margins else (logits, draft)
