"""Plain reference of the latent-attention (MLA) decoder with a leading dense
layer, sigmoid-routed experts and shared experts (kanana-2-30b-a3b, HF
`DeepseekV3*` with `q_lora_rank` null): the full forward pass in
straightforward `jax.numpy` and float32 — a loop over layers and over
experts, the EXPANDED attention for every position; no cache, no absorption
of the up-projection, no kernels, no batching, no quantisation. Imports
nothing from the program.

`norm(x, w) = x * rsqrt(mean x^2 + eps) * w` (a plain weight), eps
`rms_norm_eps` — `kv_a_layernorm`'s too.

    h = embed[tokens]
    for each layer i:
        h = h + attention(norm(h, w_norm))
        y = norm(h, w_ffn_norm)
        h = h + (dense(y) if i < first_k_dense_replace else moe(y))
    logits = norm(h, w_final_norm) @ lm_head                        (untied)

attention (H = `num_attention_heads`, nope = `qk_nope_head_dim`, rope =
`qk_rope_head_dim`, v = `v_head_dim`, R = `kv_lora_rank`):

    q        = x @ wq                          [S, H, nope + rope]
    c | k_r  = x @ wkva                        [S, R] | [S, rope]
    c_n      = norm(c, kv_norm)
    k_nope|v = c_n @ wkvb                      [S, H, nope] | [S, H, v]
    q_pe, k_r: rotary over the rope channels at `rope_theta`, frequency i
               turning the PAIR (2i, 2i + 1) (`rope_interleave`); k_r is ONE
               key, shared by all heads
    a        = softmax_causal((q_nope . k_nope + q_pe . k_r)
                              * (nope + rope) ** -0.5)
    out      = concat_heads(a v) @ wo          [S, H * v] -> [S, E]

dense(y) = (silu(y @ wg) * (y @ wu)) @ wd at width `intermediate_size`.

moe(y): s = sigmoid(y @ router) in float32; the `num_experts_per_tok`
experts of the largest s + expert_bias (HF `e_score_correction_bias`; ties
toward the lower index; `n_group` 1 and `topk_group` 1, so the group limit
is the identity); gates = the UNBIASED s of the selected, divided by their
sum + 1e-20 (`norm_topk_prob`), times `routed_scaling_factor`; expert e:
(silu(y @ wg[e]) * (y @ wu[e])) @ wd[e] at width `moe_intermediate_size`;
the gated sum, plus shared(y) = (silu(y @ sg) * (y @ su)) @ sd at width
`n_shared_experts` x `moe_intermediate_size`, ungated.

Departures from HF's `DeepseekV3`:
  - the rotary turns the pairs (2i, 2i + 1) IN PLACE; HF first reorders the
    rope channels to evens | odds and rotates by halves. The two differ by
    one fixed permutation applied to q_pe and k_r alike, so every score
    q_pe . k_r — the only place either enters — is the same number;
  - no `mscale` on the softmax scale (`rope_scaling` null: HF applies it
    only under yarn);
  - `attention_mask`, dropout and `past_key_values` have no place in a full
    causal pass over one sequence.
Assumed where the catalog's row has no key (benchmarks/configs/
kanana-2-30b-a3b.json lists each): the softmax scale, `kv_a_layernorm`'s eps,
the gate denominator's 1e-20.

Weights arrive in the program's layout (`[in, out]` matrices stacked on a
leading axis: `attn` [L, ...], `dense` [Ld, ...], `ffn` [L - Ld, ...];
quantised leaves dequantised by the caller), so the same seeded weights can
be fed to both sides.

Router near-ties: `with_margins=True` also returns, per layer and token, the
gap between the k-th and (k+1)-th biased score (infinite at a dense layer).

`layer_forward(params, model, h, i)` takes given hidden states through ONE
layer whose weights `params` holds as stacks of one (`model` then says
whether that layer is dense: `first_k_dense_replace` 1 or 0), so that a
caller can hold one layer's float32 weights at a time; `taps`, where a dict
is given, receives the attention's output before `wo` (`attn`: [S, H, v]),
the rows a cache would hold (`latent`: [S, R + rope]) and, of an expert
layer, the selected experts (`experts`: [S, k]).
`reference_logits` is the whole pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_pairs(x, theta: float, positions=None):
    """x [S, heads, D]: frequency i turns the pair (2i, 2i + 1), in place."""
    s, _, d = x.shape
    pos = (jnp.arange(s, dtype=jnp.float32) if positions is None
           else jnp.asarray(positions, jnp.float32))
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * inv_freq[None, :]                  # [S, D / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _int8_rows(x):
    """Each row rounded to int8 at its own scale (max |x| / 127)."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    return jnp.round(x / jnp.maximum(scale, 1e-30)) * scale


def rope_halves(x, theta: float):
    """The WRONG rotary for this family (a control): frequency i turns the
    channels (i, i + D / 2) as they lie."""
    s, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def attention(x, p, model, taps=None, *, softmax_dtype=jnp.float32,
              wrong: str | None = None, tile: int | None = None):
    """x [S, E] (already normed) -> [S, E], the expanded form. `tile`
    computes the scores a tile of queries at a time (the same numbers; a
    long prompt's [H, S, S] would not fit). `wrong` names ONE departure a
    comparison must be able to tell (tools/mla_parity.py's controls):
    "no_kv_norm" skips `kv_a_layernorm`, "rope_halves" rotates by halves,
    "latent_int8" rounds the cached row (c_n | roped k_r) to int8."""
    n_h = model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    v_dim, rank = model["v_head_dim"], model["kv_lora_rank"]
    theta, eps = model["rope_theta"], model["rms_norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    turn = rope_halves if wrong == "rope_halves" else rope_pairs
    q = (x @ p["wq"]).reshape(s, n_h, nope + rope)
    ckr = x @ p["wkva"]
    c_n = ckr[:, :rank] if wrong == "no_kv_norm" else norm(
        ckr[:, :rank], p["kv_norm"], eps)
    q_pe = turn(q[..., nope:], theta)
    k_r = turn(ckr[:, None, rank:], theta)                   # [S, 1, rope]
    if wrong == "latent_int8":
        row = _int8_rows(jnp.concatenate([c_n, k_r[:, 0]], axis=-1))
        c_n, k_r = row[:, :rank], row[:, None, rank:]
    kv = (c_n @ p["wkvb"]).reshape(s, n_h, nope + v_dim)

    def rows(lo, hi):
        scores = (jnp.einsum("shd,thd->hst", q[lo:hi, :, :nope],
                             kv[..., :nope])
                  + jnp.einsum("shd,td->hst", q_pe[lo:hi], k_r[:, 0]))
        scores = scores * (nope + rope) ** -0.5
        scores = jnp.where(causal[None, lo:hi], scores, -jnp.inf)
        a = jax.nn.softmax(scores.astype(softmax_dtype), -1)
        return jnp.einsum("hst,thd->shd", a.astype(jnp.float32),
                          kv[..., nope:])

    step = tile or s
    out = jnp.concatenate([rows(lo, min(lo + step, s))
                           for lo in range(0, s, step)], axis=0)
    if taps is not None:
        taps["attn"] = out
        taps["latent"] = jnp.concatenate([c_n, k_r[:, 0]], axis=-1)
    return out.reshape(s, n_h * v_dim) @ p["wo"]


def dense_ffn(y, p):
    return (jax.nn.silu(y @ p["wg"]) * (y @ p["wu"])) @ p["wd"]


def route(y, p, model, experts=None):
    """y [S, E] -> (gates [S, k], experts [S, k], margin [S]). `experts`
    [S, k], where given, are taken as the selection (a comparison feeding
    another side's choice: tools/mla_parity.py) and gated by their own
    unbiased scores; the margin is still the router's own."""
    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(y @ p["router"])
    ranked, top_idx = jax.lax.top_k(scores + p["expert_bias"], k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_idx = top_idx[:, :k] if experts is None else experts
    top = jnp.take_along_axis(scores, top_idx, axis=-1)     # unbiased
    gates = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return gates * model.get("routed_scaling_factor", 1.0), top_idx, margin


def moe(y, p, model, taps=None, experts=None):
    """y [S, E] -> (moe(y) + shared(y) [S, E], router margin [S]): every
    expert in turn over every row, weighted by its gate (zero where it was
    not selected). `taps` receives the selection (`experts`: [S, k])."""
    gates, top_idx, margin = route(y, p, model, experts)
    if taps is not None:
        taps["experts"] = top_idx

    def one(out, expert):
        e, wg, wu, wd = expert
        g = jnp.sum(jnp.where(top_idx == e, gates, 0.0), axis=-1)
        return out + g[:, None] * ((jax.nn.silu(y @ wg) * (y @ wu)) @ wd), None

    # (a loop over the experts as a scan: 128 unrolled bodies at `highest`
    # take the chip's compiler ten minutes a program)
    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (jnp.arange(p["router"].shape[-1]), p["wg"], p["wu"], p["wd"]))
    if model.get("n_shared_experts"):
        out = out + (jax.nn.silu(y @ p["sg"]) * (y @ p["su"])) @ p["sd"]
    return out, margin


def layer_forward(params: dict, model: dict, h, i: int = 0, taps=None,
                  **attention_kw):
    """Hidden states [S, E] through layer i of the stacks in `params`
    (dense where i < `first_k_dense_replace`). Returns (h, margin [S])."""
    n_dense = model.get("first_k_dense_replace", 0)
    eps = model["rms_norm_eps"]
    lay = params["layers"]
    with jax.default_matmul_precision("highest"):
        p = {k: v[i] for k, v in lay["attn"].items()}
        h = h + attention(norm(h, p["norm"], eps), p, model, taps,
                          **attention_kw)
        if i < n_dense:
            p = {k: v[i] for k, v in lay["dense"].items()}
            return (h + dense_ffn(norm(h, p["norm"], eps), p),
                    jnp.full((h.shape[0],), jnp.inf))
        p = {k: v[i - n_dense] for k, v in lay["ffn"].items()}
        y, margin = moe(norm(h, p["norm"], eps), p, model, taps)
        return h + y, margin


def embed(params: dict, model: dict, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def head(params: dict, model: dict, h):
    with jax.default_matmul_precision("highest"):
        return (norm(h, params["final_norm"], model["rms_norm_eps"])
                @ params["lm_head"])


def reference_logits(params: dict, model: dict, tokens, *,
                     with_margins: bool = False):
    """Logits [S, vocab] (float32) of one sequence `tokens` [S]; with
    `with_margins`, also the router margins [layers, S].

    `params`: float32 arrays — embed [V, E], final_norm [E], lm_head [E, V],
    layers.attn {norm, wq [L, E, H (nope + rope)], wkva [L, E, R + rope],
    kv_norm [L, R], wkvb [L, R, H (nope + v)], wo [L, H v, E]}, layers.dense
    {norm, wg, wu [Ld, E, Fd], wd}, layers.ffn {norm, router [Lx, E, X],
    expert_bias [Lx, X], wg, wu [Lx, X, E, F], wd [Lx, X, F, E], sg, su
    [Lx, E, Fs], sd [Lx, Fs, E]}. `model`: the published config.json keys.
    """
    h = embed(params, model, jnp.asarray(tokens))
    margins = []
    for i in range(model["num_hidden_layers"]):
        h, margin = layer_forward(params, model, h, i)
        margins.append(margin)
    logits = head(params, model, h)
    return (logits, jnp.stack(margins)) if with_margins else logits
