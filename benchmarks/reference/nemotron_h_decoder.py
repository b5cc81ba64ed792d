"""Plain reference of the nemotron_h decoder (NVIDIA-Nemotron-3-Nano-30B-A3B,
HF `NemotronH*`): blocks of ONE sub-layer each — a grouped Mamba-2 mixer, a
GQA attention layer without positional embedding, or routed + shared ungated
relu2 experts — the full forward pass in straightforward `jax.numpy` and
float32: a Python loop over the published blocks and over the experts, the
recurrence as a `lax.scan` over time, the convolution as written; no chunks,
no cache, no kernels, no batching, no quantisation. Imports nothing from the
program.

    h = embed[tokens]
    for block i of hybrid_override_pattern:
        h = h + f_i(rms_norm(h; w_i, eps))      f_i ONE of M, E, * below
    logits = rms_norm(h; w_f, eps) @ W_head     (untied head)

No multipliers; the residual stream is in the compute dtype (float32 here).

`M`, Mamba-2 with G = n_groups groups, H = mamba_num_heads heads of P =
mamba_head_dim channels, state N = ssm_state_size, K = conv_kernel taps:

    [z | xBC | dt] = u @ in_proj     widths H*P | H*P + 2*G*N | H, no bias
                                     (H*P, NOT expand x hidden_size)
    xBC_t = silu(sum_j conv_w[j] * xBC_{t-K+1+j} + conv_b)   (zeros before 0)
    [x | B | C] = xBC                x as [H, P];  B, C as [G, N]
    g(h) = h // (H / G)              the group head h reads
    D_t = softplus(dt_t + dt_bias)   per head, UNCLAMPED (no time_step_limit
                                     in the config)
    a_t = exp(-D_t * exp(A_log))
    S_t[h] = a_t[h] S_{t-1}[h] + D_t[h] x_t[h] (outer) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
    y = y * silu(z)                  the gate FIRST,
    y = y * rsqrt(mean(y^2 over EACH GROUP's H*P/G channels) + eps) * w
                                     (HF `MambaRMSNormGated` with group_size
                                     = intermediate_size // n_groups)
    out = y @ out_proj

`E`, experts: s = sigmoid(x @ W_r) in float32 over ALL the experts routed
over; the k experts of the largest s + e_score_correction_bias (n_group 1,
topk_group 1: no group limit; ties toward the lower index);
g = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor;
expert e: relu(x @ W_up[e])^2 @ W_down[e] — two matrices, NO gate matrix
(`mlp_hidden_act: relu2`); y = sum_e g_e expert_e(x) + shared(x), the shared
expert the same ungated form at `moe_shared_expert_intermediate_size`.

`*`, attention: q = x W_q (heads x head_dim), k, v (kv heads x head_dim), no
bias, no q/k norm, NO rotary (the Nemotron-H report: no positional
embeddings; `rope_theta` and `partial_rotary_factor` are carried in the
config and unused); causal softmax at 1/sqrt(head_dim); W_o back to hidden.

A chip's share of the experts: `model["experts_held"]` = [first, count] with
`model["experts_routed_over"]` the router's width says that the expert
leaves hold experts first .. first + count - 1 alone. The router, the
selection and the gates are over ALL the experts routed over; the held
experts' terms of the sum are computed and the absent experts' terms LEFT
OUT — gates not renormalised over the held ones; a token none of whose
experts is held gets the shared expert alone — exactly as the program does,
and that partial result goes on to the next block. Without the two keys
every expert is held.

Departures from the published code: none from the mathematics. Weights
arrive in the program's layout ([in, out] matrices stacked per kind on a
leading axis: `mamba` [23, ...], `attn` [6, ...], `ffn` [23, ...] at the
published pattern; the convolution as [taps, channels]; quantised leaves
dequantised by the caller), so the same seeded weights feed both sides; the
published blocks are walked one at a time and each takes the next layer of
its kind's stack. `controls` (tools/nh_parity.py's falsifications) is a set
of names, each of which makes ONE line above wrong on purpose: "one-group"
(every head reads group 0's B and C), "norm-all" (the gated norm over all
H*P channels), "gated" (a SwiGLU in the expert's place, its gate matrix the
up matrix: silu(x W_up) * (x W_up)), "renormalised" (gates renormalised
over the held experts), "rotary" (a rotary embedding at `rope_theta` on q
and k), "state-bf16" (the recurrent state rounded to bfloat16 after every
position: the nearest precision below the float32 the configuration
states).

Router near-ties: `with_margins=True` also returns, per expert block and
token, the gap between the k-th and (k+1)-th biased score; `forced` hands
an expert block the experts to compute with (tools/nh_parity.py feeds the
program's own choices, and counts where this pass would have chosen
otherwise).

`run_blocks(params, model, h, blocks=[...])` takes given hidden states
through some of the blocks, so that a caller can hold one block's float32
weights at a time (`embed`, then a block at a time, then `head`);
`reference_logits` is the whole pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STACK = {"M": "mamba", "*": "attn", "E": "ffn"}


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _eps(model):
    return model.get("norm_eps", model.get("layer_norm_epsilon", 1e-5))


def _rotate(x, theta):
    """A plain rotary embedding (half-split layout) on x [S, heads, d]: the
    "rotary" control alone calls it."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, model, controls=()):
    """x [S, E] (already normed) -> [S, E]."""
    n_q = model["num_attention_heads"]
    n_kv = model["num_key_value_heads"]
    d = model["head_dim"]
    s = x.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    q = (x @ p["wq"]).reshape(s, n_q, d)
    k = (x @ p["wk"]).reshape(s, n_kv, d)
    v = (x @ p["wv"]).reshape(s, n_kv, d)
    if "rotary" in controls:
        q, k = (_rotate(t, float(model["rope_theta"])) for t in (q, k))
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, n_q * d) @ p["wo"]


def mamba(u, p, model, states=None, controls=()):
    """u [S, E] (already normed) -> [S, E]; the state after the last token,
    [H, P, N], is appended to `states` where a list is given."""
    n_heads, d_head = model["mamba_num_heads"], model["mamba_head_dim"]
    n_state, taps = model["ssm_state_size"], model["conv_kernel"]
    groups = model["n_groups"]
    d_inner, gn = n_heads * d_head, groups * n_state
    s = u.shape[0]
    zxbcdt = u @ p["in_proj"]
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * gn]
    dt = zxbcdt[:, 2 * d_inner + 2 * gn:]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    conv = p["conv_b"] + sum(p["conv_w"][j] * padded[j:j + s]
                             for j in range(taps))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(s, n_heads, d_head)
    b = xbc[:, d_inner:d_inner + gn].reshape(s, groups, n_state)
    c = xbc[:, d_inner + gn:].reshape(s, groups, n_state)
    # the group each head reads: g(h) = h // (H / G)
    of_head = jnp.arange(n_heads) // (n_heads // groups)
    if "one-group" in controls:
        of_head = jnp.zeros_like(of_head)
    b, c = b[:, of_head], c[:, of_head]                             # [S, H, N]
    delta = jax.nn.softplus(dt + p["dt_bias"])                      # [S, H]
    a = jnp.exp(-delta * jnp.exp(p["A_log"]))                       # [S, H]

    def step(state, xs):
        x_t, b_t, c_t, a_t, d_t = xs
        state = (a_t[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if "state-bf16" in controls:
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, (jnp.einsum("hpn,hn->hp", state, c_t)
                       + p["D"][:, None] * x_t)

    last, y = jax.lax.scan(
        step, jnp.zeros((n_heads, d_head, n_state), jnp.float32),
        (x, b, c, a, delta))
    if states is not None:
        states.append(last)
    y = y.reshape(s, d_inner) * jax.nn.silu(z)
    over = 1 if "norm-all" in controls else groups
    y = y.reshape(s, over, d_inner // over)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + _eps(model))
    return (y.reshape(s, d_inner) * p["gate_norm"]) @ p["out_proj"]


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def held_of(model) -> tuple[int, int]:
    """(first, count) of the experts whose weights the leaves hold."""
    held = model.get("experts_held")
    return (0, model["n_routed_experts"]) if held is None else tuple(held)


def experts_and_shared(x, p, model, controls=(), forced=None):
    """x [S, E] -> (sum of the HELD experts' gated terms + shared(x) [S, E],
    router margin [S], the experts THIS pass's scores select [S, k]).
    `forced` [S, k]: the experts to compute with instead (a caller's — the
    program's own choice at each position, so that a near-tie that rounding
    decides the other way does not send the two down different paths; the
    gates are still this pass's scores of them)."""
    k = model["num_experts_per_tok"]
    first, count = held_of(model)
    scores = jax.nn.sigmoid(x @ p["router"])            # all routed over
    biased = scores + p["expert_bias"]
    ranked = jnp.sort(biased, axis=-1)[:, ::-1]
    margin = ranked[:, k - 1] - ranked[:, k]
    _, own = jax.lax.top_k(biased, k)
    top_idx = own if forced is None else forced
    top = jnp.take_along_axis(scores, top_idx, axis=-1)
    if "renormalised" in controls:
        mine = (top_idx >= first) & (top_idx < first + count)
        top = jnp.where(mine, top, 0.0)
    gates = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) \
        * model["routed_scaling_factor"]
    act = relu2
    if "gated" in controls:
        def act(u):
            return jax.nn.silu(u) * u
    y = act(x @ p["su"]) @ p["sd"]
    for e in range(count):      # leaf e is expert first + e; the rest absent
        g = jnp.sum(jnp.where(top_idx == first + e, gates, 0.0), axis=-1)
        y = y + g[:, None] * (act(x @ p["wu"][e]) @ p["wd"][e])
    return y, margin, own


def stack_index(pattern: str, i: int) -> int:
    """Block i's index in the stack of its own kind."""
    return pattern[:i].count(pattern[i])


def block_params(params: dict, pattern: str, i: int) -> dict:
    return {k: v[stack_index(pattern, i)]
            for k, v in params["layers"][STACK[pattern[i]]].items()}


def run_blocks(params: dict, model: dict, h, blocks=None, states=None,
               controls=(), selected=None, forced=None):
    """Hidden states through `blocks` (default: all). Returns (h, margins
    [expert blocks among them, S]); each mamba block's final state is
    appended to `states`, each expert block's own selection [S, k] to
    `selected`, where a list is given; `forced`: one [S, k] an expert block
    visited, in order — the experts it computes with."""
    forced = list(forced or ())
    pattern = model["hybrid_override_pattern"]
    eps = _eps(model)
    margins = []
    with jax.default_matmul_precision("highest"):
        for i in (range(len(pattern)) if blocks is None else blocks):
            p = block_params(params, pattern, i)
            x = rms_norm(h, p["norm"], eps)
            if pattern[i] == "M":
                h = h + mamba(x, p, model, states, controls)
            elif pattern[i] == "*":
                h = h + attention(x, p, model, controls)
            else:
                y, margin, sel = experts_and_shared(
                    x, p, model, controls, forced.pop(0) if forced else None)
                h = h + y
                margins.append(margin)
                if selected is not None:
                    selected.append(sel)
    return h, (jnp.stack(margins) if margins else jnp.zeros((0, h.shape[0])))


def embed(params: dict, model: dict, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def head(params: dict, model: dict, h):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, params["final_norm"], _eps(model)) \
            @ params["lm_head"]


def reference_logits(params: dict, model: dict, tokens, *,
                     with_margins: bool = False, controls=()):
    """Logits [S, vocab] (float32) of one sequence `tokens` [S]; with
    `with_margins`, also the router margins [expert blocks, S].

    `params`: float32 arrays — embed [V, E], lm_head [E, V], final_norm
    [E], layers.mamba {norm, in_proj, conv_w [Lm, taps, C], conv_b, dt_bias,
    A_log, D, gate_norm, out_proj}, layers.attn {norm, wq, wk, wv, wo},
    layers.ffn {norm, router [Lx, E, routed over], expert_bias, wu [Lx,
    held, E, F], wd [Lx, held, F, E], su [Lx, E, Fs], sd [Lx, Fs, E]}.
    `model`: the published config.json keys (+ `experts_routed_over`,
    `experts_held` for a share)."""
    h, margins = run_blocks(params, model, embed(params, model, tokens),
                            controls=controls)
    logits = head(params, model, h)
    return (logits, margins) if with_margins else logits
