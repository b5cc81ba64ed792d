"""Mamba-2 mixer (state-space duality layer): one step for decode, a chunked
form for prefill, both reading and writing a per-slot recurrent state.

The layer (HF `GraniteMoeHybridMambaLayer` / `Mamba2Mixer`; G groups of B
and C — 1 for granitemoehybrid, `n_groups` for nemotron_h — head h reading
group g(h) = h // (H / G)):

    [z | xBC | dt] = u @ in_proj           widths H*P | H*P + 2GN | H
    xBC = silu(causal depthwise conv over K taps, with bias)
    [x | B | C] = xBC                      x as [H, P], B and C as [G, N]
    D_t = softplus(dt + dt_bias);  a_t = exp(-D_t * exp(A_log))     per head
    S_t[h] = a_t S_{t-1}[h] + D_t x_t[h] (outer) B_t[g(h)]          [H, P, N]
    y_t[h] = S_t[h] C_t[g(h)] + D x_t[h]
    y = rms_norm(y * silu(z)) * gate_norm  gate first; the mean square over
                                           EACH GROUP's H*P/G channels
    out = y @ out_proj

At one group every line below is the one-group form it was (granite's
lowered programs do not change); the grouped forms view the heads as
[G, H / G] and carry the group through every product.

What a slot keeps between calls (models/llama.py KVCache): `ssm` [B, H, P, N]
float32 — the state S after the slot's last valid token — and `conv`
[K-1, B, C] — its last K-1 inputs to the convolution (batch second-minor and
channels minor: no padded dim on the chip). Neither is indexed by position.

Two forms, one mathematics:

- `step` / `step_at` (S == 1): the recurrence as written, the output taken
  from the OLD state — `S_t C = a (S_{t-1} C) + D_t x (B . C)` — so one
  copy of a tile of the state serves both lines. Between the decay and the
  gate it is ONE Pallas kernel over the whole [n_mamba, B, H, P, N] stack
  (ops/ssm_step.py: each tile read once, updated where it lies, `S C`
  reduced in VMEM; the layer is DMA addressing) wherever that kernel has a
  geometry for the state (`step_form`); `recurrence` is the same two lines
  in jnp — what the tests hold the kernel to, and what a state the kernel
  has no geometry for falls back to (XLA makes it two passes over the
  state: PERF.md, PR 33 / PR 34).
- `chunked` (S > 1; prefill): per chunk of `chunk` positions the outputs are
  a masked [Q, Q] matrix product (the "dual" quadratic form) plus the
  incoming state's decayed read-out, and the state moves a chunk at a time.
  It starts from a given state and conv tail, and a row stops at its own
  `seq_len`: positions at or past it get D_t = 0 (a = 1, no input), so the
  state after the bucket IS the state after the row's last valid token, and
  the new conv tail is the row's last K-1 VALID inputs. Decays, products of
  decays and the state stay float32 and the small einsums run at `highest`
  precision: they are a few GFLOP a layer, and bf16 passes there would cost
  three digits of the state.

The projections are `ops/quant.py` mixed dots on int8 leaves; `in_proj`'s
accumulator is kept in float32 up to the split (dt feeds an exponential).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from symmetry_tpu.ops import ssm_step
from symmetry_tpu.ops.interpret import interpret_mode
from symmetry_tpu.ops.quant import QuantizedTensor, qmatmul

HIGHEST = jax.lax.Precision.HIGHEST


def sizes(config) -> dict:
    h, p, n = (config.mamba_n_heads, config.mamba_d_head,
               config.mamba_d_state)
    g = getattr(config, "mamba_n_groups", 1)
    return {"H": h, "P": p, "N": n, "K": config.mamba_d_conv, "G": g,
            "inner": h * p, "conv": h * p + 2 * g * n,
            "proj": 2 * h * p + 2 * g * n + h}


def _in_proj(u: jnp.ndarray, w) -> jnp.ndarray:
    """u @ in_proj with the float32 accumulator kept (no cast back)."""
    if isinstance(w, QuantizedTensor):
        y = jax.lax.dot_general(
            u, w.q, (((u.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return y * w.scale
    return jnp.dot(u, w, preferred_element_type=jnp.float32)


def _split(zxbcdt: jnp.ndarray, z: dict):
    inner, conv = z["inner"], z["conv"]
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv],
            zxbcdt[..., inner + conv:])


def _decay(dt: jnp.ndarray, lp: dict):
    """dt [..., H] raw -> (delta, log a), float32."""
    delta = jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32))
    return delta, -delta * jnp.exp(lp["A_log"].astype(jnp.float32))


def _gate_out(y: jnp.ndarray, gate: jnp.ndarray, lp: dict, eps: float,
              dtype, groups: int = 1) -> jnp.ndarray:
    """y, gate [..., inner] float32 -> the layer's output in `dtype`; the
    norm's mean square is over each of the `groups` groups' channels."""
    y = y * jax.nn.silu(gate)
    if groups == 1:
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                              + eps) * lp["gate_norm"].astype(jnp.float32)
    else:
        per = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
        per = per * jax.lax.rsqrt(
            jnp.mean(jnp.square(per), axis=-1, keepdims=True) + eps)
        y = per.reshape(y.shape) * lp["gate_norm"].astype(jnp.float32)
    return qmatmul(y.astype(dtype), lp["out_proj"])


def _split_bc(xbc: jnp.ndarray, z: dict):
    """The convolved [.., conv] -> (x [.., H, P], B, C): B and C [.., N]
    at one group, [.., G, N] at more."""
    inner, gn = z["inner"], z["G"] * z["N"]
    x = xbc[..., :inner].reshape(*xbc.shape[:-1], z["H"], z["P"])
    b, c = xbc[..., inner:inner + gn], xbc[..., inner + gn:]
    if z["G"] > 1:
        b = b.reshape(*b.shape[:-1], z["G"], z["N"])
        c = c.reshape(*c.shape[:-1], z["G"], z["N"])
    return x, b, c


def step_form(config, itemsize: int = 4) -> dict:
    """Which form the single-position recurrence takes for this config's
    state (what `step_at` routes by and the engine reports): "pallas"
    with its `head_tile` ("pallas-interpret": the same kernel on the CPU
    backend), or the jnp recurrence where the kernel has no geometry."""
    z = sizes(config)
    return ssm_step.step_form(
        z["H"], z["P"], z["N"], itemsize, interpret=interpret_mode(),
        otherwise="step (jnp), two passes over the state",
        **({"groups": z["G"]} if z["G"] > 1 else {}))


def recurrence(ssm, a, dx, b, c, skip):
    """One position of the recurrence in jnp: ssm [B, H, P, N], a [B, H],
    dx / skip [B, H, P], b / c [B, N] (or [B, G, N]: a group of H / G
    heads reads its own), float32 -> (y [B, H, P], ssm)."""
    if b.ndim == 3:
        B, H, P, N = ssm.shape
        G = b.shape[1]
        s = ssm.reshape(B, G, H // G, P, N)
        y = (a.reshape(B, G, -1)[..., None] * jnp.einsum(
            "bgrpn,bgn->bgrp", s, c, precision=HIGHEST)
             + dx.reshape(B, G, -1, P)
             * jnp.sum(b * c, axis=-1)[:, :, None, None]).reshape(B, H, P)
        new = (a.reshape(B, G, -1)[..., None, None] * s
               + dx.reshape(B, G, -1, P)[..., None]
               * b[:, :, None, None, :])
        return y + skip, new.reshape(ssm.shape).astype(ssm.dtype)
    # y_t = S_t C = a (S_{t-1} C) + (dt x) (B . C): reads the OLD state
    y = (a[..., None] * jnp.einsum("bhpn,bn->bhp", ssm, c,
                                   precision=HIGHEST)
         + dx * jnp.sum(b * c, axis=-1)[:, None, None] + skip)
    return y, (a[..., None, None] * ssm
               + dx[..., None] * b[:, None, None, :]).astype(ssm.dtype)


def step_at(u: jnp.ndarray, lp: dict, ssm: jnp.ndarray, layer,
            conv: jnp.ndarray, config
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One token a slot, the state where it lies: u [B, E], ssm the WHOLE
    stack [n_mamba, B, H, P, N] of which layer `layer` (traced) steps,
    conv [K-1, B, C] that layer's tail -> (out [B, E], the stack, conv)."""
    z = sizes(config)
    B = u.shape[0]
    gate, xbc, dt = _split(_in_proj(u, lp["in_proj"]), z)
    window = jnp.concatenate([conv.astype(jnp.float32), xbc[None]], axis=0)
    xbc = jax.nn.silu(
        jnp.sum(window * lp["conv_w"].astype(jnp.float32)[:, None, :],
                axis=0) + lp["conv_b"].astype(jnp.float32))
    x, b, c = _split_bc(xbc, z)                                 # x [B, H, P]
    delta, log_a = _decay(dt, lp)                               # [B, H]
    a = jnp.exp(log_a)
    dx = delta[..., None] * x                                   # [B, H, P]
    skip = lp["D"].astype(jnp.float32)[:, None] * x
    if "head_tile" in step_form(config, ssm.dtype.itemsize):
        y, ssm = ssm_step.ssm_step(ssm, layer, a, dx, b, c, skip,
                                   interpret=interpret_mode())
    else:
        y, new = recurrence(
            jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False),
            a, dx, b, c, skip)
        ssm = ssm.at[layer].set(new)
    out = _gate_out(y.reshape(B, z["inner"]), gate, lp, config.rms_eps,
                    u.dtype, z["G"])
    return out, ssm, window[1:].astype(conv.dtype)


def step(u: jnp.ndarray, lp: dict, ssm: jnp.ndarray, conv: jnp.ndarray,
         config) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`step_at` for one layer's state alone: u [B, E], ssm [B, H, P, N],
    conv [K-1, B, C] -> (out [B, E], ssm, conv)."""
    out, ssm, conv = step_at(u, lp, ssm[None], jnp.int32(0), conv, config)
    return out, ssm[0], conv


def _chunk(x, delta, log_a, b, c, state):
    """One chunk of the dual form. x [B, Q, H, P], delta / log_a [B, Q, H],
    b / c [B, Q, N], state [B, H, P, N] -> (y [B, Q, H, P], state)."""
    if b.ndim == 4:
        return _chunk_grouped(x, delta, log_a, b, c, state)
    Q = x.shape[1]
    cum = jnp.cumsum(log_a, axis=1)                             # inclusive
    dx = delta[..., None] * x                                   # [B, Q, H, P]
    # within the chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dx_s
    scores = jnp.einsum("btn,bsn->bts", c, b, precision=HIGHEST)
    cum_h = jnp.moveaxis(cum, 1, 2)                             # [B, H, Q]
    seg = cum_h[..., :, None] - cum_h[..., None, :]             # [B, H, t, s]
    # the mask goes on the exponent: above the diagonal seg > 0 can overflow
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg,
                              -jnp.inf))
    y = jnp.einsum("bhts,bshp->bthp", scores[:, None] * decay, dx,
                   precision=HIGHEST)
    # the incoming state, decayed to each position
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bhpn,btn->bthp", state, c, precision=HIGHEST)
    # the state at the chunk's end
    to_end = jnp.exp(cum[:, -1:, :] - cum)                      # [B, Q, H]
    state = (jnp.exp(cum[:, -1, :])[..., None, None] * state
             + jnp.einsum("bshp,bsn->bhpn", to_end[..., None] * dx, b,
                          precision=HIGHEST))
    return y, state


def _chunk_grouped(x, delta, log_a, b, c, state):
    """`_chunk` with b / c [B, Q, G, N]: the same three products with the
    heads viewed as [G, H / G] and the group carried through."""
    B, Q, H, P = x.shape
    G, N = b.shape[2:]
    cum = jnp.cumsum(log_a, axis=1)                             # inclusive
    dx = (delta[..., None] * x).reshape(B, Q, G, H // G, P)
    scores = jnp.einsum("btgn,bsgn->bgts", c, b, precision=HIGHEST)
    cum_h = jnp.moveaxis(cum, 1, 2)                             # [B, H, Q]
    seg = cum_h[..., :, None] - cum_h[..., None, :]             # [B, H, t, s]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg,
                              -jnp.inf)).reshape(B, G, H // G, Q, Q)
    y = jnp.einsum("bgrts,bsgrp->btgrp", scores[:, :, None] * decay, dx,
                   precision=HIGHEST)
    s = state.reshape(B, G, H // G, P, N)
    y = y + jnp.exp(cum).reshape(B, Q, G, -1)[..., None] * jnp.einsum(
        "bgrpn,btgn->btgrp", s, c, precision=HIGHEST)
    to_end = jnp.exp(cum[:, -1:, :] - cum).reshape(B, Q, G, -1)
    s = (jnp.exp(cum[:, -1, :]).reshape(B, G, -1)[..., None, None] * s
         + jnp.einsum("bsgrp,bsgn->bgrpn", to_end[..., None] * dx, b,
                      precision=HIGHEST))
    return y.reshape(B, Q, H, P), s.reshape(state.shape)


def chunked(u: jnp.ndarray, lp: dict, ssm: jnp.ndarray, conv: jnp.ndarray,
            seq_lens: jnp.ndarray, config
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A run of positions a row: u [B, S, E], starting from ssm [B, H, P, N]
    and conv [K-1, B, C]; row b has seq_lens[b] valid positions ->
    (out [B, S, E], ssm, conv) with the state and tail as they stand after
    each row's LAST VALID position."""
    z = sizes(config)
    B, S, _ = u.shape
    K = z["K"]
    gate, xbc, dt = _split(_in_proj(u, lp["in_proj"]), z)
    padded = jnp.concatenate(
        [jnp.moveaxis(conv, 0, 1).astype(jnp.float32), xbc], axis=1)
    w = lp["conv_w"].astype(jnp.float32)
    conv_out = lp["conv_b"].astype(jnp.float32) + sum(
        w[j] * padded[:, j:j + S] for j in range(K))
    # the row's last K-1 valid inputs: padded[seq_len .. seq_len + K-2]
    tail_at = seq_lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    tail = jnp.take_along_axis(padded, tail_at[..., None], axis=1)
    x, b, c = _split_bc(jax.nn.silu(conv_out), z)               # [B, S, H, P]
    delta, log_a = _decay(dt, lp)                               # [B, S, H]
    valid = (jnp.arange(S, dtype=jnp.int32)[None, :]
             < seq_lens[:, None])[..., None]
    delta = jnp.where(valid, delta, 0.0)                        # a = 1 there
    log_a = jnp.where(valid, log_a, 0.0)

    Q = min(config.mamba_chunk_size, S)
    state = ssm.astype(jnp.float32)
    if S == Q:
        y, state = _chunk(x, delta, log_a, b, c, state)
    else:
        pad = -S % Q
        parts = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 for t in (x, delta, log_a, b, c)]              # delta 0: inert
        parts = [jnp.moveaxis(
            t.reshape((B, (S + pad) // Q, Q) + t.shape[2:]), 1, 0)
            for t in parts]

        def body(state, xs):
            y, state = _chunk(*xs, state)
            return state, y

        state, y = jax.lax.scan(body, state, tuple(parts))
        y = jnp.moveaxis(y, 0, 1).reshape(B, S + pad, z["H"], z["P"])[:, :S]
    y = y + lp["D"].astype(jnp.float32)[:, None] * x
    out = _gate_out(y.reshape(B, S, z["inner"]), gate, lp, config.rms_eps,
                    u.dtype, z["G"])
    return (out, state.astype(ssm.dtype),
            jnp.moveaxis(tail, 1, 0).astype(conv.dtype))
