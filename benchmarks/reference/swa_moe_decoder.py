"""Plain reference of the decoder with WINDOW and FULL attention layers in
one model, a router on the layer's input and ReGLU experts
(smallthinker-21b-a3b, PowerInfer `SmallThinker*`): the full forward pass in
straightforward `jax.numpy` and float32 — a loop over layers and over
experts, every position's attention over the whole sequence under the
layer's own mask; no cache, no ring, no kernels, no batching, no
quantisation. Imports nothing from the program.

`norm(x, w) = x * rsqrt(mean x^2 + eps) * w` (a plain weight), eps
`rms_norm_eps`.

    h = embed[tokens]
    for each layer l (x = h as it ENTERS the layer):
        r      = x @ router                      [S, X] float32, from x itself:
                                                 BEFORE the input norm
        idx    = top-k(r);  g = softmax(r[idx])  (`moe_primary_router_apply_
                                                 softmax`; `norm_topk_prob`
                                                 is then the identity)
        h      = x + attention_l(norm(x, w_norm))
        n      = norm(h, w_ffn_norm)
        h      = h + sum_{e in idx} g_e * (relu(n @ wg[e]) * (n @ wu[e])) @ wd[e]
    logits = norm(h, w_final_norm) @ lm_head                        (untied)

attention_l (H = `num_attention_heads`, K = `num_key_value_heads`, D =
`head_dim`; query head i reads KV head i // (H / K)):

    q, k, v = a @ wq, a @ wk, a @ wv             no bias, no per-head norm
    `rope_layout[l]` 1: q and k turned by the rotary at `rope_theta`, the
        whole head, by halves (frequency i turns channels i and i + D / 2,
        HF's `rotate_half`); 0: no positional embedding at all
    `sliding_window_layout[l]` 1: key s visible to query t iff
        t - `sliding_window_size` < s <= t (the window's keys, the query's
        own among them); 0: iff s <= t
    out = concat_heads(softmax(q k^T / sqrt(D)) v) @ wo

Departures from the published code, each listed under `assumed` in
benchmarks/configs/smallthinker-21b-a3b.json (the checkpoint's
`modeling_smallthinker.py` is not in the sandbox):
  - the router's input is the un-normed layer input (`SmallThinkerDecoder
    Layer.forward` hands `hidden_states` to the router before
    `input_layernorm`; llama.cpp `llm_build_smallthinker`: `ffn_gate_inp`
    times `inpL`) — the "router placed before attention" of the catalog;
  - the window is `kv_pos > q_pos - sliding_window_size`: 4,096 keys, the
    query's own among them;
  - an expert is ONE dense ReGLU FFN: the catalog speaks of primary and
    secondary experts and a sparsity predictor, the 21B `config` has
    primary keys only;
  - `attention_mask`, dropout and `past_key_values` have no place in a full
    causal pass over one sequence.

Weights arrive in the program's layout (`[in, out]` matrices stacked on a
leading axis: `attn` [n_full, ...] the full layers in order, `swa`
[n_window, ...] the window layers in order, `ffn` [L, ...]; quantised leaves
dequantised by the caller), so the same seeded weights can be fed to both
sides.

Router near-ties: `with_margins=True` also returns, per layer and token, the
gap between the k-th and (k+1)-th router logit.

`layer_forward(params, model, h, i)` takes given hidden states through ONE
layer whose weights `params` holds as stacks of one — `model`'s two layouts
then have one entry, that layer's — so that a caller can hold one layer's
float32 weights at a time; `taps`, where a dict is given, receives the
attention's output before `wo` (`attn`: [S, H, D]), the keys a cache would
hold (`k`: [S, K, D], roped where the layer ropes) and the selected experts
(`experts`: [S, k]). `wrong` names ONE departure a comparison must be able
to tell (tools/swa_parity.py's controls, the tests'): "rope_full" ropes a
layer whose layout says not to, "window_short" sees one key fewer,
"router_normed" feeds the router the normed FFN input, "silu" swaps the
activation. `reference_logits` is the whole pass.
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


def attention(x, p, model, *, roped: bool, windowed: bool, taps=None,
              softmax_dtype=jnp.float32, wrong: str | None = None,
              tile: int | None = None):
    """x [S, E] (already normed) -> [S, E]. `tile` computes the scores a
    tile of queries at a time (the same numbers; a long prompt's [H, S, S]
    would not fit)."""
    n_h, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n_h
    s = x.shape[0]
    q = (x @ p["wq"]).reshape(s, n_h, d)
    k = (x @ p["wk"]).reshape(s, n_kv, d)
    v = (x @ p["wv"]).reshape(s, n_kv, d)
    if roped or wrong == "rope_full":
        q = rope_halves(q, model["rope_theta"])
        k = rope_halves(k, model["rope_theta"])
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if windowed:
        span = model["sliding_window_size"] - (wrong == "window_short")
        mask &= pos[None, :] > pos[:, None] - span
    group = n_h // n_kv

    def rows(lo, hi):
        qg = q[lo:hi].reshape(hi - lo, n_kv, group, d)
        scores = jnp.einsum("skgd,tkd->kgst", qg, k) * d ** -0.5
        scores = jnp.where(mask[None, None, lo:hi], scores, -jnp.inf)
        a = jax.nn.softmax(scores.astype(softmax_dtype), -1)
        return jnp.einsum("kgst,tkd->skgd", a.astype(jnp.float32),
                          v).reshape(hi - lo, n_h, d)

    step = tile or s
    out = jnp.concatenate([rows(lo, min(lo + step, s))
                           for lo in range(0, s, step)], axis=0)
    if taps is not None:
        taps["attn"], taps["k"] = out, k
    return out.reshape(s, n_h * d) @ p["wo"]


def route(r_in, p, model, experts=None):
    """r_in [S, E] -> (gates [S, k], experts [S, k], margin [S]). `experts`
    [S, k], where given, are taken as the selection (a comparison feeding
    another side's choice) and gated by the softmax over their own logits;
    the margin is still the router's own."""
    k = model["moe_num_active_primary_experts"]
    logits = r_in @ p["router"]
    ranked, top_idx = jax.lax.top_k(logits, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_idx = top_idx[:, :k] if experts is None else experts
    top = jnp.take_along_axis(logits, top_idx, axis=-1)
    return jax.nn.softmax(top, axis=-1), top_idx, margin


def moe(n, r_in, p, model, taps=None, experts=None, wrong=None):
    """n [S, E] the normed FFN input, r_in [S, E] the router's input ->
    (moe(n) [S, E], router margin [S]): every expert in turn over every
    row, weighted by its gate (zero where it was not selected)."""
    gates, top_idx, margin = route(n if wrong == "router_normed" else r_in,
                                   p, model, experts)
    if taps is not None:
        taps["experts"] = top_idx
    act = jax.nn.silu if wrong == "silu" else jax.nn.relu

    def one(out, expert):
        e, wg, wu, wd = expert
        g = jnp.sum(jnp.where(top_idx == e, gates, 0.0), axis=-1)
        return out + g[:, None] * ((act(n @ wg) * (n @ wu)) @ wd), None

    # (a loop over the experts as a scan: unrolled bodies at `highest` take
    # the chip's compiler minutes a program)
    out, _ = jax.lax.scan(
        one, jnp.zeros_like(n),
        (jnp.arange(p["router"].shape[-1]), p["wg"], p["wu"], p["wd"]))
    return out, margin


def stack_of(model: dict, i: int) -> tuple[str, int]:
    """Layer i's stack and its index there: the full layers lie in `attn`,
    the window layers in `swa`, each in layer order."""
    layout = model["sliding_window_layout"]
    kind = int(bool(layout[i]))
    return ("swa" if kind else "attn",
            sum(int(bool(w)) == kind for w in layout[:i]))


def layer_forward(params: dict, model: dict, h, i: int = 0, taps=None,
                  wrong: str | None = None, experts=None, **attention_kw):
    """Hidden states [S, E] through layer i of the stacks in `params`.
    Returns (h, margin [S])."""
    eps = model["rms_norm_eps"]
    lay = params["layers"]
    name, j = stack_of(model, i)
    with jax.default_matmul_precision("highest"):
        x = h
        p = {k: v[j] for k, v in lay[name].items()}
        h = x + attention(
            norm(x, p["norm"], eps), p, model,
            roped=bool(model["rope_layout"][i]),
            windowed=bool(model["sliding_window_layout"][i]), taps=taps,
            wrong=wrong, **attention_kw)
        p = {k: v[i] for k, v in lay["ffn"].items()}
        y, margin = moe(norm(h, p["norm"], eps), x, p, model, taps, experts,
                        wrong)
        return h + y, margin


def embed(params: dict, model: dict, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def head(params: dict, model: dict, h):
    with jax.default_matmul_precision("highest"):
        return (norm(h, params["final_norm"], model["rms_norm_eps"])
                @ params["lm_head"])


def reference_logits(params: dict, model: dict, tokens, *,
                     with_margins: bool = False, wrong: str | None = None,
                     **attention_kw):
    """Logits [S, vocab] (float32) of one sequence `tokens` [S]; with
    `with_margins`, also the router margins [layers, S].

    `params`: float32 arrays — embed [V, E], final_norm [E], lm_head [E, V],
    layers.attn / layers.swa {norm [n, E], wq [n, E, H D], wk, wv [n, E,
    K D], wo [n, H D, E]}, layers.ffn {norm [L, E], router [L, E, X], wg, wu
    [L, X, E, F], wd [L, X, F, E]}. `model`: the published config.json keys.
    """
    h = embed(params, model, jnp.asarray(tokens))
    margins = []
    for i in range(model["num_hidden_layers"]):
        h, margin = layer_forward(params, model, h, i, wrong=wrong,
                                  **attention_kw)
        margins.append(margin)
    logits = head(params, model, h)
    return (logits, jnp.stack(margins)) if with_margins else logits
