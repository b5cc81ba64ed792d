"""Rotary position embeddings (RoPE), HF-llama convention.

Uses the rotate-half layout (first half / second half pairing) so weights
loaded from HF llama/mistral checkpoints produce identical activations —
required because the north star loads HF safetensors directly (BASELINE.json).
Cos/sin are computed in float32 regardless of activation dtype; bf16 RoPE
phases drift noticeably past ~2k positions.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_cos_sin(
    positions: jnp.ndarray,  # [..., seq] int32 absolute positions
    head_dim: int,
    theta: float = 500000.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Return (cos, sin) of shape [..., seq, head_dim], float32."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )  # [head_dim/2]
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., seq, hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [..., seq, head_dim]
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(
    x: jnp.ndarray,          # [batch, seq, heads, head_dim]
    positions: jnp.ndarray,  # [batch, seq]
    theta: float = 500000.0,
    rotary_dim: int | None = None,
) -> jnp.ndarray:
    """Rotate q or k by absolute position; returns x's dtype. With
    `rotary_dim` under the head size (partial rotary: HF
    `partial_rotary_factor`) only the leading `rotary_dim` channels of each
    head rotate, as a head of that size would; the rest pass through."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rotary_dim], positions, theta),
             x[..., rotary_dim:]], axis=-1)
    cos, sin = rope_cos_sin(positions, x.shape[-1], theta)
    # Broadcast over the heads axis: [batch, seq, 1, head_dim].
    cos, sin = cos[..., None, :], sin[..., None, :]
    xf = x.astype(jnp.float32)
    out = xf * cos + _rotate_half(xf) * sin
    return out.astype(x.dtype)
