"""Plain reference of the Gated DeltaNet / gated-attention decoder with routed
experts and a gated shared expert (Qwen3-Next-80B-A3B, HF `Qwen3Next*`): the
full forward pass in straightforward `jax.numpy` and float32 — a Python loop
over layers and experts, the delta rule as a `lax.scan` over time, the
convolution as written; no chunks, no cache, no kernels, no batching, no
quantisation. Imports nothing from the program.

Every RMSNorm but the Gated DeltaNet's output norm is zero-centred:
`norm(x, w) = x * rsqrt(mean x^2 + eps) * (1 + w)`. Layer i is
`full_attention` when `(i + 1) % full_attention_interval == 0`, else
`linear_attention`.

    h = embed[tokens]
    for each layer i:
        h = h + mixer_i(norm(h, w_norm))
        x = norm(h, w_ffn_norm)
        h = h + moe(x) + sigmoid(x . w_sgate) * shared(x)
    logits = norm(h, w_final_norm) @ lm_head                     (untied)

mixer `linear_attention` (Gated DeltaNet; Hk key heads of Dk, Hv value heads
of Dv, K taps; key head g serves value heads g * Hv/Hk .. ):

    [q | k | v | z] = u @ in_proj         widths Hk*Dk | Hk*Dk | Hv*Dv | Hv*Dv
    [b | a]         = u @ in_ba           widths Hv | Hv
    [q | k | v]_t = silu(sum_j conv_w[j] * [q | k | v]_{t-K+1+j})   (zeros before 0)
    q, k: per head x / sqrt(sum x^2 + 1e-6);   q = q / sqrt(Dk)
    beta_t = sigmoid(b_t);   g_t = -exp(A_log) * softplus(a_t + dt_bias)
    S   = exp(g_t) * S_{t-1}                                     S in R^{Dk x Dv}
    d_t = beta_t * (v_t - S^T k_t)
    S_t = S + k_t (outer) d_t
    o_t = S_t^T q_t
    o = w_gate_norm * o * rsqrt(mean(o^2 over the head's Dv) + eps) * silu(z)
    out = o @ out_proj

mixer `full_attention`: per head the two halves of x @ wq are the query and
an output gate; q and k pass a zero-centred RMSNorm over the head's D
channels; the first `partial_rotary_factor * D` channels of q and k rotate
(rotate-half pairing inside that prefix, base `rope_theta`), the rest pass;
causal softmax(q k^T / sqrt(D)) v; `out = (a * sigmoid(gate)) @ wo`.

moe(x): softmax over ALL router logits in float32, the k largest,
renormalised to sum 1 (`norm_topk_prob`); expert e:
(silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]; the gated sum. shared(x): the same
form at `shared_expert_intermediate_size`.

Departures: the published model's multi-token-prediction module is left out
(the served forward pass does not use it; the config has no key for it).
Nothing else. Weights arrive in the program's layout (`[in, out]` matrices
stacked per kind on a leading axis: `gdn` [Ll, ...], `attn` [La, ...], `ffn`
[L, ...]; HF's per-key-head fused `in_proj_qkvz` / `in_proj_ba` split as
above; the convolution as [taps, channels]; quantised leaves dequantised by
the caller), so the same seeded weights can be fed to both sides.

Router near-ties: `with_margins=True` also returns, per layer and token, the
gap between the k-th and (k+1)-th router logit.

`run_layers(params, model, h, layers=[...])` takes given hidden states
through some of the layers, so that a caller can hold one layer's float32
weights at a time (`embed`, then a layer at a time, then `head`);
`reference_logits` is the whole pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def norm(x, w, eps):
    """Zero-centred RMSNorm over the last axis."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def layer_kinds(model: dict) -> list[str]:
    if model.get("layer_types"):
        return list(model["layer_types"])
    every = model["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(model["num_hidden_layers"])]


def _rope(x, rot: int, theta: float):
    """x [S, heads, D]: rotate channels 0..rot-1 by position, pass the rest."""
    s = x.shape[0]
    inv_freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]      # [S, 1, rot]
    head, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    turned = jnp.concatenate([-head[..., half:], head[..., :half]], axis=-1)
    return jnp.concatenate(
        [head * jnp.cos(ang) + turned * jnp.sin(ang), rest], axis=-1)


def attention(x, p, model):
    """x [S, E] (already normed) -> [S, E]."""
    n_q = model["num_attention_heads"]
    n_kv = model["num_key_value_heads"]
    d = model["head_dim"]
    eps = model["rms_norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    qg = (x @ p["wq"]).reshape(s, n_q, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(s, n_q * d)
    k = (x @ p["wk"]).reshape(s, n_kv, d)
    v = (x @ p["wv"]).reshape(s, n_kv, d)
    rot = int(d * model.get("partial_rotary_factor", 1.0))
    q = _rope(norm(q, p["q_norm"], eps), rot, model["rope_theta"])
    k = _rope(norm(k, p["k_norm"], eps), rot, model["rope_theta"])
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(float(d))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    return (out.reshape(s, n_q * d) * jax.nn.sigmoid(gate)) @ p["wo"]


def gated_deltanet(u, p, model, states=None):
    """u [S, E] (already normed) -> [S, E]; the state after the last token,
    [Hv, Dk, Dv], is appended to `states` where a list is given."""
    hk, dk = model["linear_num_key_heads"], model["linear_key_head_dim"]
    hv, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    taps = model["linear_conv_kernel_dim"]
    s = u.shape[0]
    qkvz = u @ p["in_proj"]
    qkv, z = qkvz[:, :2 * hk * dk + hv * dv], qkvz[:, 2 * hk * dk + hv * dv:]
    ba = u @ p["in_ba"]
    beta = jax.nn.sigmoid(ba[:, :hv])                               # [S, Hv]
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, qkv.shape[1]), qkv.dtype), qkv], axis=0)
    qkv = jax.nn.silu(sum(p["conv_w"][j] * padded[j:j + s]
                          for j in range(taps)))
    q = qkv[:, :hk * dk].reshape(s, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(s, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(q / jnp.sqrt(float(dk)), hv // hk, axis=1)       # [S,Hv,Dk]
    k = jnp.repeat(k, hv // hk, axis=1)

    def step(state, xs):
        q_t, k_t, v_t, beta_t, g_t = xs
        state = jnp.exp(g_t)[:, None, None] * state                 # decay
        read = jnp.einsum("hkv,hk->hv", state, k_t)                 # S^T k
        d_t = beta_t[:, None] * (v_t - read)
        state = state + k_t[:, :, None] * d_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)          # S_t^T q

    last, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), jnp.float32),
                           (q, k, v, beta, g))
    if states is not None:
        states.append(last)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + model["rms_norm_eps"]) * p["gate_norm"]
    o = o.reshape(s, hv * dv) * jax.nn.silu(z)
    return o @ p["out_proj"]


def moe_and_shared(x, p, model):
    """x [S, E] -> (moe(x) + gate * shared(x) [S, E], router margin [S])."""
    k = model["num_experts_per_tok"]
    logits = x @ p["router"]
    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
    margin = ranked[:, k - 1] - ranked[:, k]
    probs = jax.nn.softmax(logits, axis=-1)                 # over ALL experts
    top_vals, top_idx = jax.lax.top_k(probs, k)
    gates = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    y = ((jax.nn.silu(x @ p["sg"]) * (x @ p["su"])) @ p["sd"]
         * jax.nn.sigmoid(x @ p["sgate"]))
    for e in range(logits.shape[-1]):
        g = jnp.sum(jnp.where(top_idx == e, gates, 0.0), axis=-1)
        y = y + g[:, None] * (
            (jax.nn.silu(x @ p["wg"][e]) * (x @ p["wu"][e])) @ p["wd"][e])
    return y, margin


def stack_index(kinds, i: int) -> int:
    """Layer i's index in the stack of its own kind."""
    return sum(t == kinds[i] for t in kinds[:i])


def run_layers(params: dict, model: dict, h, layers=None, states=None):
    """Hidden states through `layers` (default: all). Returns (h, margins
    [len(layers), S]); each Gated DeltaNet layer's final state is appended
    to `states` where a list is given."""
    kinds = layer_kinds(model)
    eps = model["rms_norm_eps"]
    lay = params["layers"]
    margins = []
    with jax.default_matmul_precision("highest"):
        for i in (range(len(kinds)) if layers is None else layers):
            kind = "attn" if kinds[i] == "full_attention" else "gdn"
            p = {k: v[stack_index(kinds, i)] for k, v in lay[kind].items()}
            x = norm(h, p["norm"], eps)
            h = h + (attention(x, p, model) if kind == "attn"
                     else gated_deltanet(x, p, model, states))
            p = {k: v[i] for k, v in lay["ffn"].items()}
            y, margin = moe_and_shared(norm(h, p["norm"], eps), p, model)
            h = h + y
            margins.append(margin)
    return h, jnp.stack(margins)


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

    `params`: float32 arrays — embed [V, E], lm_head [E, V], final_norm [E],
    layers.gdn {norm, in_proj, in_ba, conv_w [Ll, taps, C], dt_bias, A_log,
    gate_norm [Ll, Dv], out_proj}, layers.attn {norm, wq [La, E, 2 * Hq * D],
    wk, wv, wo, q_norm, k_norm [La, D]}, layers.ffn {norm, router, wg, wu
    [L, X, E, F], wd [L, X, F, E], sg, su [L, E, Fs], sd [L, Fs, E], sgate
    [L, E, 1]}. `model`: the published config.json keys."""
    h, margins = run_layers(params, model, embed(params, model, tokens))
    logits = head(params, model, h)
    return (logits, margins) if with_margins else logits
