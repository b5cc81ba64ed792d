"""Plain reference of the dense GQA decoder family the benchmark's
configurations belong to (Mistral-7B-v0.3, Qwen2-7B): the full forward pass
in straightforward `jax.numpy` and float32 — no kernels, no cache, no
batching tricks, no quantisation.

Follows the published descriptions (HF `modeling_mistral` / `modeling_qwen2`):

    h = embed[tokens]
    for each layer:
        x = rms_norm(h) * w_attn_norm
        q, k, v = x @ wq (+ bq), x @ wk (+ bk), x @ wv (+ bv)   # qwen2: biases
        q, k = rope(q), rope(k)           # rotate-half pairing, theta from config
        a = softmax(causal(q k^T / sqrt(d))) v                  # GQA: each KV
        h = h + a @ wo                                          # head serves
        x = rms_norm(h) * w_mlp_norm                            # n_q / n_kv
        h = h + (silu(x @ wg) * (x @ wu)) @ wd                  # query heads
    logits = rms_norm(h) * w_final_norm @ lm_head

Departures: none from the mathematics. Weights arrive in the program's
layout (`[in, out]` matrices stacked on a leading layer axis; quantised
leaves are dequantised by the caller before they get here), so that the same
seeded weights can be fed to both sides.

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


def reference_logits(params: dict, model: dict, tokens) -> jnp.ndarray:
    """Logits [S, vocab] (float32) of one sequence `tokens` [S].

    `params`: float32 arrays in the program's layout — embed [V, E],
    layers.{attn_norm, mlp_norm} [L, E], layers.{wq, wk, wv, wo, wg, wu, wd}
    [L, in, out], optional layers.{bq, bk, bv} [L, out], final_norm [E],
    lm_head [E, V]. `model`: the published config.json keys."""
    n_q = model["num_attention_heads"]
    n_kv = model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n_q
    eps = model["rms_norm_eps"]
    theta = model["rope_theta"]
    lay = params["layers"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]                          # [S, S]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens].astype(jnp.float32)
        for i in range(model["num_hidden_layers"]):
            x = rms_norm(h, lay["attn_norm"][i], eps)
            q, k, v = x @ lay["wq"][i], x @ lay["wk"][i], x @ lay["wv"][i]
            if "bq" in lay:
                q, k, v = q + lay["bq"][i], k + lay["bk"][i], v + lay["bv"][i]
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
            h = h + (jax.nn.silu(x @ lay["wg"][i]) * (x @ lay["wu"][i])
                     ) @ lay["wd"][i]
        h = rms_norm(h, params["final_norm"], eps)
        return h @ params["lm_head"]
