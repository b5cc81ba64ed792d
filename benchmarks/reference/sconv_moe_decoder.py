"""Plain reference of the gated-short-convolution / GQA decoder with two
leading dense layers and sigmoid-routed experts (LFM2-8B-A1B, HF `Lfm2Moe*`):
the full forward pass in straightforward `jax.numpy` and float32 — a Python
loop over layers and experts, the convolution as written; no cache, no
kernels, no batching, no quantisation. Imports nothing from the program.

`norm(x, w) = x * rsqrt(mean x^2 + eps) * w` (a plain weight), eps `norm_eps`.

    h = embed[tokens]
    for each layer i:
        h = h + mixer_i(norm(h, w_norm))
        y = norm(h, w_ffn_norm)
        h = h + (dense(y) if i < num_dense_layers else moe(y))
    logits = norm(h, w_final_norm) @ embed^T                       (tied)

mixer `conv` (gated short convolution; E channels, K = `conv_L_cache` taps,
no bias, NO activation):

    [B | C | x] = u @ in_proj                  three chunks of E, in that order
    z_t = B_t * x_t
    c_t = sum_j conv_w[j] * z_{t-K+1+j}        (zeros before position 0: the
                                               LAST tap meets the current
                                               position, a cross-correlation)
    out = (C_t * c_t) @ out_proj

mixer `full_attention`: q in `num_attention_heads` heads of D, k and v in
`num_key_value_heads`; q and k pass a plain RMSNorm over the head's D
channels BEFORE the rotary; rotate-half rotary over all D channels at
`rope_theta`; causal softmax(q k^T / sqrt(D)) v, KV head a // group for
query head a; `out = o @ wo`.

dense(y) = (silu(y @ wg) * (y @ wu)) @ wd at width `intermediate_size`.

moe(y): logits = y @ router in float32; scores = sigmoid(logits); the
`num_experts_per_tok` experts of the largest scores + expert_bias (ties
toward the lower index); gates = the UNBIASED scores of the selected,
divided by their sum + 1e-6 (`norm_topk_prob`), times
`routed_scaling_factor`; expert e: (silu(y @ wg[e]) * (y @ wu[e])) @ wd[e];
the gated sum. No shared expert.

Departures from the published description: none. Assumed (the catalog's
row has no key for them; benchmarks/configs/lfm2-8b-a1b.json lists each):
heads of hidden / heads channels, the per-head q/k norms, the tied head.
Weights arrive in the program's layout (`[in, out]` matrices stacked per
kind on a leading axis: `sconv` [Lc, ...], `attn` [La, ...], `dense` [Ld,
...], `ffn` [L - Ld, ...]; the convolution as [taps, channels]; quantised
leaves dequantised by the caller), so the same seeded weights can be fed to
both sides.

Router near-ties: `with_margins=True` also returns, per layer and token, the
gap between the k-th and (k+1)-th biased score (infinite at a dense layer).

`run_layers(params, model, h, layers=[...])` takes given hidden states
through some of the layers, so that a caller can hold one layer's float32
weights at a time (`embed`, then a layer at a time, then `head`): `params`
then holds stacks of ONE layer and `model` that layer's kind alone
(`layer_types` of one entry, `num_dense_layers` 1 or 0);
`reference_logits` is the whole pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta: float):
    """x [S, heads, D]: rotate-half rotary over all D channels."""
    s, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [S, 1, D]
    half = d // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def attention(x, p, model):
    """x [S, E] (already normed) -> [S, E]."""
    n_q = model["num_attention_heads"]
    n_kv = model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n_q
    eps = model["norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    q = (x @ p["wq"]).reshape(s, n_q, d)
    k = (x @ p["wk"]).reshape(s, n_kv, d)
    v = (x @ p["wv"]).reshape(s, n_kv, d)
    q = _rope(norm(q, p["q_norm"], eps), model["rope_theta"])
    k = _rope(norm(k, p["k_norm"], eps), model["rope_theta"])
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(float(d))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, n_q * d) @ p["wo"]


def short_conv(u, p, model, tails=None):
    """u [S, E] (already normed) -> [S, E]; z at the last K - 1 positions,
    [K-1, E], is appended to `tails` where a list is given."""
    taps = model["conv_L_cache"]
    s = u.shape[0]
    b, c, x = jnp.split(u @ p["in_proj"], 3, axis=-1)
    z = b * x
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, z.shape[1]), z.dtype), z], axis=0)
    conv = sum(p["conv_w"][j] * padded[j:j + s] for j in range(taps))
    if tails is not None:
        tails.append(padded[s:])
    return (c * conv) @ p["out_proj"]


def dense_ffn(y, p):
    return (jax.nn.silu(y @ p["wg"]) * (y @ p["wu"])) @ p["wd"]


def route(y, p, model):
    """y [S, E] -> (gates [S, k], experts [S, k], margin [S])."""
    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(y @ p["router"])
    biased = scores + p["expert_bias"] if model.get("use_expert_bias") \
        else scores
    ranked, top_idx = jax.lax.top_k(biased, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_idx = top_idx[:, :k]
    top = jnp.take_along_axis(scores, top_idx, axis=-1)     # unbiased
    gates = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6)
    return gates * model.get("routed_scaling_factor", 1.0), top_idx, margin


def moe(y, p, model):
    """y [S, E] -> (moe(y) [S, E], router margin [S])."""
    gates, top_idx, margin = route(y, p, model)
    out = jnp.zeros_like(y)
    for e in range(p["router"].shape[-1]):
        g = jnp.sum(jnp.where(top_idx == e, gates, 0.0), axis=-1)
        out = out + g[:, None] * (
            (jax.nn.silu(y @ p["wg"][e]) * (y @ p["wu"][e])) @ p["wd"][e])
    return out, margin


def stack_index(kinds, i: int) -> int:
    """Layer i's index in the stack of its own kind."""
    return sum(t == kinds[i] for t in kinds[:i])


def run_layers(params: dict, model: dict, h, layers=None, tails=None):
    """Hidden states through `layers` (default: all). Returns (h, margins
    [len(layers), S]); each conv layer's final tail is appended to `tails`
    where a list is given."""
    kinds = list(model["layer_types"])
    n_dense = model.get("num_dense_layers", 0)
    eps = model["norm_eps"]
    lay = params["layers"]
    margins = []
    with jax.default_matmul_precision("highest"):
        for i in (range(len(kinds)) if layers is None else layers):
            kind = "attn" if kinds[i] == "full_attention" else "sconv"
            p = {k: v[stack_index(kinds, i)] for k, v in lay[kind].items()}
            x = norm(h, p["norm"], eps)
            h = h + (attention(x, p, model) if kind == "attn"
                     else short_conv(x, p, model, tails))
            if i < n_dense:
                p = {k: v[i] for k, v in lay["dense"].items()}
                h = h + dense_ffn(norm(h, p["norm"], eps), p)
                margins.append(jnp.full((h.shape[0],), jnp.inf))
            else:
                p = {k: v[i - n_dense] for k, v in lay["ffn"].items()}
                y, margin = moe(norm(h, p["norm"], eps), p, model)
                h = h + y
                margins.append(margin)
    return h, jnp.stack(margins)


def embed(params: dict, model: dict, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def head(params: dict, model: dict, h):
    with jax.default_matmul_precision("highest"):
        return (norm(h, params["final_norm"], model["norm_eps"])
                @ params["embed"].T)


def reference_logits(params: dict, model: dict, tokens, *,
                     with_margins: bool = False):
    """Logits [S, vocab] (float32) of one sequence `tokens` [S]; with
    `with_margins`, also the router margins [layers, S].

    `params`: float32 arrays — embed [V, E], final_norm [E], layers.sconv
    {norm, in_proj [Lc, E, 3E], conv_w [Lc, taps, E], out_proj}, layers.attn
    {norm, wq, wk, wv, wo, q_norm, k_norm [La, D]}, layers.dense {norm, wg,
    wu [Ld, E, Fd], wd}, layers.ffn {norm, router [Lx, E, X], expert_bias
    [Lx, X], wg, wu [Lx, X, E, F], wd [Lx, X, F, E]}. `model`: the published
    config.json keys."""
    h, margins = run_layers(params, model, embed(params, model, tokens))
    logits = head(params, model, h)
    return (logits, margins) if with_margins else logits
