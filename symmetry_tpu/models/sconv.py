"""Gated short convolution (lfm2_moe `conv` layers): one step for decode, a
whole-prompt form for prefill. The mixer's only state is a TAIL: its last
K - 1 inputs to the convolution.

The layer (HF `Lfm2MoeShortConv`; E channels, K = `conv_L_cache` taps, no
bias anywhere):

    [B | C | x] = u @ in_proj               three chunks of E, in that order
    z_t = B_t * x_t
    c_t = sum_j w[j] * z_{t-K+1+j}          causal, depthwise; w[K-1] meets
                                            the current position, z before
                                            position 0 is 0
    out = (C_t * c_t) @ out_proj

No activation: the two elementwise gates are all of its non-linearity.

What a slot keeps between calls (models/llama.py KVCache): `conv`
[K-1, B, E] — z at its last K - 1 valid positions — in the cache's dtype.
There is no `ssm` leaf for this kind (None, as for a model without
recurrent layers). z is rounded to the tail's dtype BEFORE the convolution
reads it, in both forms, so a position convolves the same three values
whether its neighbours came from this call or from the cache; the products
and the tap sum are float32.

Two forms, one mathematics, the entry points of models/mamba2.py and
models/gdn.py (`state` is their per-slot state and is None here):

- `step_at` (S == 1): three elementwise products and the two tail reads a
  channel, in jnp between the two int8 dots (XLA fuses them; `step_form`
  says so).
- `chunked` (S > 1; prefill): the convolution over the whole bucket as K
  shifted products, from a given tail; a row stops at its own `seq_len`:
  the new tail is z at the row's last K - 1 VALID positions (what the tail
  it started from held, where the row is shorter), and since the
  convolution is causal no valid position reads a padded one.
"""

from __future__ import annotations

import jax.numpy as jnp

from symmetry_tpu.ops.quant import qmatmul


def sizes(config) -> dict:
    e = config.hidden_size
    return {"K": config.conv_L_cache, "conv": e, "proj": 3 * e}


def step_form(config) -> dict:
    """What `startup.ssm.decode` reports for this kind."""
    return {"form": "step (jnp)"}


def _gates(u: jnp.ndarray, lp: dict, tail_dtype):
    """u [..., E] -> (z [..., E] in the tail's dtype, C [..., E] float32)."""
    b, c, x = jnp.split(qmatmul(u, lp["in_proj"]).astype(jnp.float32), 3,
                        axis=-1)
    return (b * x).astype(tail_dtype), c


def step_at(u: jnp.ndarray, lp: dict, state, layer, conv: jnp.ndarray,
            config) -> tuple[jnp.ndarray, None, jnp.ndarray]:
    """One token a slot: u [B, E], conv [K-1, B, E] this layer's tail ->
    (out [B, E], None, the tail moved on by one position)."""
    z, c = _gates(u, lp, conv.dtype)
    window = jnp.concatenate([conv, z[None]], axis=0)           # [K, B, E]
    w = lp["conv_w"].astype(jnp.float32)                        # [K, E]
    y = c * jnp.sum(window.astype(jnp.float32) * w[:, None, :], axis=0)
    return qmatmul(y.astype(u.dtype), lp["out_proj"]), None, window[1:]


def chunked(u: jnp.ndarray, lp: dict, state, conv: jnp.ndarray,
            seq_lens: jnp.ndarray, config
            ) -> tuple[jnp.ndarray, None, jnp.ndarray]:
    """A run of positions a row: u [B, S, E], starting from the tail conv
    [K-1, B, E]; row b has seq_lens[b] valid positions -> (out [B, S, E],
    None, the tail as it stands after each row's LAST VALID position)."""
    K = config.conv_L_cache
    S = u.shape[1]
    z, c = _gates(u, lp, conv.dtype)
    padded = jnp.concatenate([jnp.moveaxis(conv, 0, 1), z], axis=1)
    w = lp["conv_w"].astype(jnp.float32)
    y = c * sum(w[j] * padded[:, j:j + S].astype(jnp.float32)
                for j in range(K))
    # z at the row's last K-1 valid positions: padded[seq_len .. + K-2]
    tail_at = seq_lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    tail = jnp.take_along_axis(padded, tail_at[..., None], axis=1)
    return (qmatmul(y.astype(u.dtype), lp["out_proj"]), None,
            jnp.moveaxis(tail, 1, 0))
