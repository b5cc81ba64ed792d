"""Plain reference of the hybrid Mamba-2 / attention decoder with routed and
shared experts (granite-4.0-h-small, HF `GraniteMoeHybrid*`): the full forward
pass in straightforward `jax.numpy` and float32 — a Python loop over layers
and experts, the recurrence as a `lax.scan` over time, the convolution as
written; no chunks, no cache, no kernels, no batching, no quantisation.
Imports nothing from the program.

    h = embed[tokens] * embedding_multiplier
    for each layer i, of kind layer_types[i], with r = residual_multiplier:
        h = h + r * mixer_i(rms_norm(h) * w_norm)
        x = rms_norm(h) * w_ffn_norm
        h = h + r * (moe(x) + shared(x))
    logits = (rms_norm(h) * w_final_norm) @ embed^T / logits_scaling

mixer `attention`: GQA, no bias, NO positional embedding
(`position_embedding_type: "nope"`), causal, scores scaled by
`attention_multiplier` (1/128 for granite, not 1/sqrt(128)).

mixer `mamba` (Mamba-2; H heads of P channels, one group, state N, K taps):

    [z | xBC | dt] = u @ in_proj          widths H*P | H*P + 2N | H
    xBC_t = silu(sum_j conv_w[j] * xBC_{t-K+1+j} + conv_b)    (zeros before 0)
    [x | B | C] = xBC                     widths H*P | N | N;  x as [H, P]
    D_t = softplus(dt_t + dt_bias)        per head
    a_t = exp(-D_t * exp(A_log))
    S_t = a_t * S_{t-1} + D_t * x_t (outer) B_t               S in R^{H x P x N}
    y_t = S_t C_t + D * x_t
    y = y * silu(z)                       the gate FIRST,
    y = y * rsqrt(mean(y^2 over all H*P channels) + eps) * w_gate_norm
    out = y @ out_proj

moe(x): router logits x @ W_r in float32, the k largest, softmax over the k
selected (HF takes the softmax over the selected logits here too);
expert e: (silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]; the gated sum.
shared(x): the same gated form at `shared_intermediate_size`, every token,
weight 1.

Departures: none from the mathematics. Weights arrive in the program's layout
(`[in, out]` matrices stacked per kind on a leading axis: `mamba` [Lm, ...],
`attn` [La, ...], `ffn` [L, ...]; HF's fused `input_linear` is the pair
(wg, wu); the convolution as [taps, channels]; quantised leaves dequantised
by the caller), so the same seeded weights can be fed to both sides.
`n_groups` other than 1 is not written here (the published config has 1).

Router near-ties: as `moe_decoder.py` — `with_margins=True` also returns,
per layer and token, the gap between the k-th and (k+1)-th router logit.

`run_layers(params, model, h, layers=[...])` takes given hidden states
through some of the layers, so that a caller can hold one layer's float32
weights at a time (`embed`, then a layer at a time, then `head`);
`reference_logits` is the whole pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def attention(x, p, model):
    """x [S, E] (already normed) -> [S, E]."""
    n_q = model["num_attention_heads"]
    n_kv = model["num_key_value_heads"]
    d = model.get("head_dim") or model["hidden_size"] // n_q
    s = x.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    q = (x @ p["wq"]).reshape(s, n_q, d)
    k = (x @ p["wk"]).reshape(s, n_kv, d)
    v = (x @ p["wv"]).reshape(s, n_kv, d)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * model["attention_multiplier"]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, n_q * d) @ p["wo"]


def mamba(u, p, model, states=None):
    """u [S, E] (already normed) -> [S, E]; the state after the last token,
    [H, P, N], is appended to `states` where a list is given."""
    n_heads, d_head = model["mamba_n_heads"], model["mamba_d_head"]
    n_state, taps = model["mamba_d_state"], model["mamba_d_conv"]
    assert model.get("mamba_n_groups", 1) == 1
    d_inner = n_heads * d_head
    s = u.shape[0]
    zxbcdt = u @ p["in_proj"]
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * n_state]
    dt = zxbcdt[:, 2 * d_inner + 2 * n_state:]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    conv = p["conv_b"] + sum(p["conv_w"][j] * padded[j:j + s]
                             for j in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(s, n_heads, d_head)
    b = xbc[:, d_inner:d_inner + n_state]
    c = xbc[:, d_inner + n_state:]
    delta = jax.nn.softplus(dt + p["dt_bias"])                      # [S, H]
    a = jnp.exp(-delta * jnp.exp(p["A_log"]))                       # [S, H]

    def step(state, xs):
        x_t, b_t, c_t, a_t, d_t = xs
        state = (a_t[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, state @ c_t + p["D"][:, None] * x_t

    last, y = jax.lax.scan(
        step, jnp.zeros((n_heads, d_head, n_state), jnp.float32),
        (x, b, c, a, delta))
    if states is not None:
        states.append(last)
    y = y.reshape(s, d_inner) * jax.nn.silu(z)
    y = rms_norm(y, p["gate_norm"], model["rms_norm_eps"])
    return y @ p["out_proj"]


def moe_and_shared(x, p, model):
    """x [S, E] -> (moe(x) + shared(x) [S, E], router margin [S])."""
    k = model["num_experts_per_tok"]
    logits = x @ p["router"]
    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
    margin = ranked[:, k - 1] - ranked[:, k]
    top_vals, top_idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_vals, axis=-1)
    y = (jax.nn.silu(x @ p["sg"]) * (x @ p["su"])) @ p["sd"]
    for e in range(logits.shape[-1]):
        g = jnp.sum(jnp.where(top_idx == e, gates, 0.0), axis=-1)
        y = y + g[:, None] * (
            (jax.nn.silu(x @ p["wg"][e]) * (x @ p["wu"][e])) @ p["wd"][e])
    return y, margin


def stack_index(layer_types, i: int) -> int:
    """Layer i's index in the stack of its own kind."""
    return sum(t == layer_types[i] for t in layer_types[:i])


def run_layers(params: dict, model: dict, h, layers=None, states=None):
    """Hidden states through `layers` (default: all). Returns (h, margins
    [len(layers), S]); each mamba layer's final state is appended to
    `states` where a list is given."""
    kinds = list(model["layer_types"])
    r = model["residual_multiplier"]
    eps = model["rms_norm_eps"]
    lay = params["layers"]
    margins = []
    with jax.default_matmul_precision("highest"):
        for i in (range(len(kinds)) if layers is None else layers):
            kind = "attn" if kinds[i] == "attention" else "mamba"
            p = {k: v[stack_index(kinds, i)] for k, v in lay[kind].items()}
            x = rms_norm(h, p["norm"], eps)
            h = h + r * (attention(x, p, model) if kind == "attn"
                         else mamba(x, p, model, states))
            p = {k: v[i] for k, v in lay["ffn"].items()}
            y, margin = moe_and_shared(rms_norm(h, p["norm"], eps), p, model)
            h = h + r * y
            margins.append(margin)
    return h, jnp.stack(margins)


def embed(params: dict, model: dict, tokens):
    return (params["embed"][tokens].astype(jnp.float32)
            * model["embedding_multiplier"])


def head(params: dict, model: dict, h):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(h, params["final_norm"], model["rms_norm_eps"])
        return h @ params["embed"].T / model["logits_scaling"]


def reference_logits(params: dict, model: dict, tokens, *,
                     with_margins: bool = False):
    """Logits [S, vocab] (float32) of one sequence `tokens` [S]; with
    `with_margins`, also the router margins [layers, S].

    `params`: float32 arrays — embed [V, E], final_norm [E], layers.mamba
    {norm, in_proj, conv_w [Lm, taps, C], conv_b, dt_bias, A_log, D,
    gate_norm, out_proj}, layers.attn {norm, wq, wk, wv, wo}, layers.ffn
    {norm, router, wg, wu [L, X, E, F], wd [L, X, F, E], sg, su [L, E, Fs],
    sd [L, Fs, E]}. `model`: the published config.json keys."""
    h, margins = run_layers(params, model, embed(params, model, tokens))
    logits = head(params, model, h)
    return (logits, margins) if with_margins else logits
