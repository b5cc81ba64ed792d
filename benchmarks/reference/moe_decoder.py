"""Plain reference of the sparse-expert GQA decoder (Mixtral-8x7B-v0.1): the
full forward pass in straightforward `jax.numpy` and float32 — a Python loop
over layers and over experts; no sorting, no grouping, no kernels, no cache,
no batching, no quantisation.

Follows the published description (HF `modeling_mixtral`: `MixtralAttention`,
`MixtralSparseMoeBlock`, `MixtralBlockSparseTop2MLP`):

    h = embed[tokens]
    for each layer:
        x = rms_norm(h) * w_attn_norm
        q, k, v = x @ wq, x @ wk, x @ wv
        q, k = rope(q), rope(k)           # rotate-half pairing, theta from config
        a = softmax(causal(q k^T / sqrt(d))) v        # GQA: each KV head serves
        h = h + a @ wo                                # n_q / n_kv query heads
        x = rms_norm(h) * w_mlp_norm
        r = x @ router                                # [S, experts], float32
        top = the k largest of r per token; g = softmax(r[top])   # over the k
        h = h + sum_{e in top} g_e * (silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
    logits = rms_norm(h) * w_final_norm @ lm_head

HF takes the softmax over all experts, keeps the top k and divides by their
sum; a softmax over the k selected logits is the same numbers. Sliding-window
attention is not here: the published config has `sliding_window: null`.

Departures: none from the mathematics. Weights arrive in the program's layout
(`[in, out]` matrices stacked on a leading layer axis, expert matrices on a
second expert axis; quantised leaves are dequantised by the caller before
they get here), so that the same seeded weights can be fed to both sides.

Router near-ties. With random weights the k-th and (k+1)-th router logits of
a token sometimes sit within rounding of each other; two correct
implementations then pick different experts — a discrete jump that is no
error. `reference_logits(..., with_margins=True)` also returns, per layer
and token, the reference's gap between those two logits, so that a
comparison can leave out tokens whose choice was a coin toss, and say how
many it left out.

On a TPU a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set; `reference_logits` sets it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x [S, H, D], positions [S]; rotate-half pairing (HF convention)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq        # [S, D/2]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def sparse_moe_block(x, router, wg, wu, wd, k):
    """x [S, E] -> (y [S, E], margin [S]). `margin` is the gap between the
    k-th and the (k+1)-th largest router logit of each token."""
    n_experts = router.shape[-1]
    logits = x @ router                                            # [S, X]
    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
    margin = ranked[:, k - 1] - ranked[:, k]
    top_vals, top_idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_vals, axis=-1)                      # [S, k]
    y = jnp.zeros_like(x)
    for e in range(n_experts):
        # This expert's gate per token: 0 where it was not selected.
        g = jnp.sum(jnp.where(top_idx == e, gates, 0.0), axis=-1)  # [S]
        out = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
        y = y + g[:, None] * out
    return y, margin


def reference_logits(params: dict, model: dict, tokens, *,
                     with_margins: bool = False):
    """Logits [S, vocab] (float32) of one sequence `tokens` [S]; with
    `with_margins`, also the router margins [layers, S].

    `params`: float32 arrays in the program's layout — embed [V, E],
    layers.{attn_norm, mlp_norm} [L, E], layers.{wq, wk, wv, wo} [L, in,
    out], layers.router [L, E, X], layers.{wg, wu} [L, X, E, F], layers.wd
    [L, X, F, E], final_norm [E], lm_head [E, V]. `model`: the published
    config.json keys."""
    n_q = model["num_attention_heads"]
    n_kv = model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n_q
    eps = model["rms_norm_eps"]
    theta = model["rope_theta"]
    top_k = model["num_experts_per_tok"]
    lay = params["layers"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]                          # [S, S]
    margins = []
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens].astype(jnp.float32)
        for i in range(model["num_hidden_layers"]):
            x = rms_norm(h, lay["attn_norm"][i], eps)
            q, k, v = x @ lay["wq"][i], x @ lay["wk"][i], x @ lay["wv"][i]
            q = rope(q.reshape(s, n_q, d), pos, theta)
            k = rope(k.reshape(s, n_kv, d), pos, theta)
            v = v.reshape(s, n_kv, d)
            k = jnp.repeat(k, n_q // n_kv, axis=1)
            v = jnp.repeat(v, n_q // n_kv, axis=1)
            scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(float(d))
            scores = jnp.where(causal[None], scores, -jnp.inf)
            attn = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
            h = h + attn.reshape(s, n_q * d) @ lay["wo"][i]
            x = rms_norm(h, lay["mlp_norm"][i], eps)
            y, margin = sparse_moe_block(
                x, lay["router"][i], lay["wg"][i], lay["wu"][i],
                lay["wd"][i], top_k)
            h = h + y
            margins.append(margin)
        h = rms_norm(h, params["final_norm"], eps)
        logits = h @ params["lm_head"]
    return (logits, jnp.stack(margins)) if with_margins else logits
