"""Gated DeltaNet mixer (qwen3_next `linear_attention` layers): one step for
decode, a chunked form for prefill, both reading and writing a per-slot
MATRIX state.

The layer (HF `Qwen3NextGatedDeltaNet`; Hk key heads of Dk channels, Hv value
heads of Dv, each key head serving Hv / Hk value heads, K taps):

    [q | k | v | z] = u @ in_proj         widths Hk*Dk | Hk*Dk | Hv*Dv | Hv*Dv
    [b | a]         = u @ in_ba           widths Hv | Hv
    [q | k | v] = silu(causal depthwise conv over K taps, no bias)
    q, k: per head x / sqrt(sum x^2 + 1e-6);  q = q / sqrt(Dk)
    beta_t = sigmoid(b_t);  g_t = -exp(A_log) * softplus(a_t + dt_bias)
    S   = exp(g_t) * S_{t-1}                        S in R^{Dk x Dv} a value head
    d_t = beta_t * (v_t - S^T k_t)                  the delta: read, then correct
    S_t = S + k_t (outer) d_t
    o_t = S_t^T q_t
    o = gate_norm * o * rsqrt(mean over the head's Dv channels of o^2 + eps)
        * silu(z)                                   plain weight, norm first
    out = o @ out_proj

HF stores `in_proj_qkvz` and `in_proj_ba` fused per key-head group; ours are
split as above (models/hybrid.py converts both ways).

What a slot keeps between calls (models/llama.py KVCache, the leaves the
Mamba-2 layers use in granite): `ssm` [B, Hv, Dk, Dv] float32 — S after the
slot's last valid token — and `conv` [K-1, B, C] — its last K-1 inputs to
the convolution. Neither is indexed by position.

Two forms, one mathematics:

- `step_at` (S == 1): the recurrence with both read-outs taken from the OLD
  state — `S^T k = a (S_{t-1}^T k)` and `S_t^T q = a (S_{t-1}^T q) +
  (k . q) d_t` — so one copy of a tile of the state serves both read-outs
  and the update `a S + k (outer) d`. Between the heads and the gate it is
  ONE Pallas kernel over the whole [n_layers, B, Hv, Dk, Dv] stack
  (ops/ssm_step.py `gdn_step`: each head's [Dk, Dv] tile read once, reduced
  for k and q in VMEM, updated where it lies; the layer is DMA addressing)
  wherever that kernel has a geometry for the state (`step_form`);
  `recurrence` is the same algebra in jnp — what the tests hold the kernel
  to, and what a state the kernel has no geometry for falls back to (XLA
  makes it two fusions a layer, three crossings of the state: PERF.md,
  PR 35 / PR 45).
- `chunked` (S > 1; prefill): per chunk of `linear_chunk_size` positions,
  with G the running sum of g inside the chunk, the deltas solve a unit
  lower-triangular system
      (I + A) D = beta * (V - e^G K S_0),  A[t, s] = beta_t e^{G_t - G_s}
      (k_t . k_s) for s < t
  (forward substitution, `solve_triangular`: no power series, whose terms
  cancel catastrophically when keys repeat), the outputs are the incoming
  state's decayed read-out plus a masked [Q, Q] product with D, and the
  state moves a chunk at a time. It starts from a given state and conv
  tail, and a row stops at its own `seq_len`: positions at or past it get
  beta = 0 and g = 0, so d = 0 and the state after the bucket IS the state
  after the row's last valid token; the new conv tail is the row's last
  K-1 VALID inputs. State, decays, l2-norms and the small einsums are
  float32 at `highest` precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from symmetry_tpu.ops import ssm_step
from symmetry_tpu.ops.interpret import interpret_mode
from symmetry_tpu.ops.quant import qmatmul

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def sizes(config) -> dict:
    hk, dk = config.linear_num_key_heads, config.linear_key_head_dim
    hv, dv = config.linear_num_value_heads, config.linear_value_head_dim
    return {"Hk": hk, "Dk": dk, "Hv": hv, "Dv": dv,
            "K": config.linear_conv_kernel_dim, "key": hk * dk,
            "inner": hv * dv, "conv": 2 * hk * dk + hv * dv,
            "proj": 2 * hk * dk + 2 * hv * dv}


def step_form(config, itemsize: int = 4) -> dict:
    """Which form the single-position recurrence takes for this config's
    state (what `step_at` routes by and the engine reports): "pallas"
    with its `head_tile` ("pallas-interpret": the same kernel on the CPU
    backend), or the jnp recurrence where the kernel has no geometry."""
    z = sizes(config)
    return ssm_step.step_form(
        z["Hv"], z["Dk"], z["Dv"], itemsize, interpret=interpret_mode(),
        otherwise="step (jnp), read-outs from the old state")


def _beta_decay(u: jnp.ndarray, lp: dict, z: dict):
    """u [..., E] -> (beta, g) [..., Hv] float32: the write strength and the
    log of the state's decay."""
    ba = jnp.dot(u, lp["in_ba"], preferred_element_type=jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :z["Hv"]])
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., z["Hv"]:] + lp["dt_bias"].astype(jnp.float32))
    return beta, g


def _heads(qkv: jnp.ndarray, z: dict):
    """Convolved [..., C] float32 -> q, k [..., Hv, Dk] (l2-normed, q scaled,
    each key head repeated for the value heads it serves), v [..., Hv, Dv]."""
    lead = qkv.shape[:-1]
    q = qkv[..., :z["key"]].reshape(lead + (z["Hk"], z["Dk"]))
    k = qkv[..., z["key"]:2 * z["key"]].reshape(lead + (z["Hk"], z["Dk"]))
    v = qkv[..., 2 * z["key"]:].reshape(lead + (z["Hv"], z["Dv"]))

    def l2(x):
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)

    rep = z["Hv"] // z["Hk"]
    q = jnp.repeat(l2(q) * z["Dk"] ** -0.5, rep, axis=-2)
    k = jnp.repeat(l2(k), rep, axis=-2)
    return q, k, v


def _gate_out(o: jnp.ndarray, gate: jnp.ndarray, lp: dict, eps: float,
              dtype) -> jnp.ndarray:
    """o [..., Hv, Dv] float32, gate [..., Hv * Dv] -> the layer's output."""
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + eps) * lp["gate_norm"].astype(jnp.float32)
    o = o.reshape(gate.shape) * jax.nn.silu(gate.astype(jnp.float32))
    return qmatmul(o.astype(dtype), lp["out_proj"])


def recurrence(state, a, beta, q, k, v):
    """One position of the delta rule in jnp: state [B, H, Dk, Dv], a / beta
    [B, H], q / k [B, H, Dk], v [B, H, Dv], float32 -> (o [B, H, Dv],
    state)."""
    # both read-outs from the OLD state, in one pass over it
    kq = jnp.stack([k, q], axis=-1)                             # [B,H,Dk,2]
    read = jnp.einsum("bhkv,bhkj->bhjv", state, kq, precision=HIGHEST)
    d = beta[..., None] * (v - a[..., None] * read[:, :, 0])
    o = (a[..., None] * read[:, :, 1]
         + jnp.sum(k * q, axis=-1, keepdims=True) * d)
    return o, a[..., None, None] * state + k[..., :, None] * d[..., None, :]


def step_at(u: jnp.ndarray, lp: dict, state: jnp.ndarray, layer,
            conv: jnp.ndarray, config
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One token a slot, the state where it lies: u [B, E], state the WHOLE
    stack [n_layers, B, Hv, Dk, Dv] of which layer `layer` (traced) steps,
    conv [K-1, B, C] that layer's tail -> (out [B, E], the stack, conv)."""
    z = sizes(config)
    qkvz = qmatmul(u, lp["in_proj"])
    qkv, gate = qkvz[:, :z["conv"]], qkvz[:, z["conv"]:]
    beta, g = _beta_decay(u, lp, z)
    window = jnp.concatenate(
        [conv.astype(jnp.float32), qkv[None].astype(jnp.float32)], axis=0)
    q, k, v = _heads(jax.nn.silu(jnp.sum(
        window * lp["conv_w"].astype(jnp.float32)[:, None, :], axis=0)), z)
    if "head_tile" in step_form(config, state.dtype.itemsize):
        o, state = ssm_step.gdn_step(state, layer, jnp.exp(g), beta, q, k, v,
                                     interpret=interpret_mode())
    else:
        o, new = recurrence(
            jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False
                                         ).astype(jnp.float32),
            jnp.exp(g), beta, q, k, v)
        state = state.at[layer].set(new.astype(state.dtype))
    out = _gate_out(o, gate, lp, config.rms_eps, u.dtype)
    return out, state, window[1:].astype(conv.dtype)


def _chunk(q, k, v, beta, g, state):
    """One chunk. q / k [B, Q, H, Dk], v [B, Q, H, Dv], beta / g [B, Q, H],
    state [B, H, Dk, Dv] -> (o [B, Q, H, Dv], state)."""
    Q = q.shape[1]
    cum = jnp.cumsum(g, axis=1)                                 # inclusive
    cum_h = jnp.moveaxis(cum, 1, 2)                             # [B, H, Q]
    seg = cum_h[..., :, None] - cum_h[..., None, :]             # [B, H, t, s]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # the mask goes on the exponent: above the diagonal seg > 0 can overflow
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    into = jnp.exp(cum)[..., None]                  # the chunk's start -> t
    # (I + A) D = beta (V - e^G K S_0), A strictly lower
    a_mat = (jnp.moveaxis(beta, 1, 2)[..., None] * decay
             * jnp.einsum("bthk,bshk->bhts", k, k, precision=HIGHEST))
    rhs = beta[..., None] * (v - into * jnp.einsum(
        "bhkv,bthk->bthv", state, k, precision=HIGHEST))
    d = solve_triangular(a_mat, jnp.moveaxis(rhs, 1, 2), lower=True,
                         unit_diagonal=True)                    # [B,H,Q,Dv]
    # o_t = e^{G_t} S_0^T q_t + sum_{s<=t} e^{G_t - G_s} (q_t . k_s) d_s
    qk = jnp.einsum("bthk,bshk->bhts", q, k, precision=HIGHEST)
    o = (into * jnp.einsum("bhkv,bthk->bthv", state, q, precision=HIGHEST)
         + jnp.einsum("bhts,bhsv->bthv", qk * decay, d, precision=HIGHEST))
    # the state at the chunk's end
    to_end = jnp.exp(cum[:, -1:, :] - cum)                      # [B, Q, H]
    state = (jnp.exp(cum[:, -1, :])[..., None, None] * state
             + jnp.einsum("bshk,bhsv->bhkv", to_end[..., None] * k, d,
                          precision=HIGHEST))
    return o, state


def chunked(u: jnp.ndarray, lp: dict, state: jnp.ndarray, conv: jnp.ndarray,
            seq_lens: jnp.ndarray, config
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A run of positions a row: u [B, S, E], starting from state [B, Hv,
    Dk, Dv] and conv [K-1, B, C]; row b has seq_lens[b] valid positions ->
    (out [B, S, E], state, conv) with the state and tail as they stand
    after each row's LAST VALID position."""
    z = sizes(config)
    B, S, _ = u.shape
    K = z["K"]
    qkvz = qmatmul(u, lp["in_proj"])
    qkv, gate = qkvz[..., :z["conv"]], qkvz[..., z["conv"]:]
    beta, g = _beta_decay(u, lp, z)                             # [B, S, Hv]
    padded = jnp.concatenate(
        [jnp.moveaxis(conv, 0, 1).astype(jnp.float32),
         qkv.astype(jnp.float32)], axis=1)
    w = lp["conv_w"].astype(jnp.float32)
    conv_out = sum(w[j] * padded[:, j:j + S] for j in range(K))
    # the row's last K-1 valid inputs: padded[seq_len .. seq_len + K-2]
    tail_at = seq_lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    tail = jnp.take_along_axis(padded, tail_at[..., None], axis=1)
    q, k, v = _heads(jax.nn.silu(conv_out), z)
    valid = (jnp.arange(S, dtype=jnp.int32)[None, :]
             < seq_lens[:, None])[..., None]
    beta = jnp.where(valid, beta, 0.0)          # no write past the row's end
    g = jnp.where(valid, g, 0.0)                # and no decay

    Q = min(config.linear_chunk_size, S)
    s0 = state.astype(jnp.float32)
    if S == Q:
        o, s0 = _chunk(q, k, v, beta, g, s0)
    else:
        pad = -S % Q
        parts = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 for t in (q, k, v, beta, g)]                   # beta 0: inert
        parts = [jnp.moveaxis(
            t.reshape((B, (S + pad) // Q, Q) + t.shape[2:]), 1, 0)
            for t in parts]

        def body(s0, xs):
            o, s0 = _chunk(*xs, s0)
            return s0, o

        s0, o = jax.lax.scan(body, s0, tuple(parts))
        o = jnp.moveaxis(o, 0, 1).reshape(B, S + pad, z["Hv"],
                                          z["Dv"])[:, :S]
    out = _gate_out(o, gate, lp, config.rms_eps, u.dtype)
    return (out, s0.astype(state.dtype),
            jnp.moveaxis(tail, 1, 0).astype(conv.dtype))
