"""Rotary position embeddings (RoPE), HF-llama convention.

Uses the rotate-half layout (first half / second half pairing) so weights
loaded from HF llama/mistral checkpoints produce identical activations —
required because the engine loads HF safetensors directly (engine/weights.py).
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


def mrope_cos_sin(
    positions: jnp.ndarray,        # [3, batch, seq]: temporal, height, width
    head_dim: int,
    theta: float,
    section: tuple[int, ...],      # frequency pairs per component
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Multimodal rotary (HF `mrope_section`): frequency pair i of the
    head_dim / 2 is turned by the position component whose section holds
    it — the first `section[0]` pairs by the temporal one, the next by the
    height, the rest by the width. Three equal components give
    `rope_cos_sin`'s values exactly."""
    if sum(section) != head_dim // 2 or positions.shape[0] != len(section):
        raise ValueError(f"mrope_section {section} must split the "
                         f"{head_dim // 2} frequency pairs over the "
                         f"{positions.shape[0]} position components")
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    of_pair = jnp.repeat(jnp.arange(len(section)), jnp.asarray(section),
                         total_repeat_length=head_dim // 2)
    # [B, S, hd/2]: each pair's own component
    pos = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., of_pair]
    freqs = pos * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(
    x: jnp.ndarray,          # [batch, seq, heads, head_dim]
    positions: jnp.ndarray,  # [batch, seq], or [3, batch, seq] (mrope)
    theta: float = 500000.0,
    rotary_dim: int | None = None,
    mrope_section: tuple[int, ...] | None = None,
    interleaved: bool = False,
) -> jnp.ndarray:
    """Rotate q or k by absolute position; returns x's dtype. With
    `rotary_dim` under the head size (partial rotary: HF
    `partial_rotary_factor`) only the leading `rotary_dim` channels of each
    head rotate, as a head of that size would; the rest pass through.
    3-D `positions` are the components of a multimodal rotary
    (`mrope_cos_sin`, by `mrope_section`); 2-D ones are three equal
    components, which is the plain rotary below.
    `interleaved` (HF `rope_interleave`, the DeepseekV3 family): frequency i
    turns the channel PAIR (2i, 2i + 1), not (i, i + d / 2). As HF's
    `apply_rotary_pos_emb_interleave` does, the channels are first put in
    the order evens | odds and then rotated by halves, so the result is the
    pairwise rotation IN THAT ORDER: q and k take the same permutation and
    every q . k is the pairwise rotary's."""
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rotary_dim], positions, theta,
                        mrope_section=mrope_section),
             x[..., rotary_dim:]], axis=-1)
    if positions.ndim == 3:
        cos, sin = mrope_cos_sin(positions, x.shape[-1], theta,
                                 tuple(mrope_section))
    else:
        cos, sin = rope_cos_sin(positions, x.shape[-1], theta)
    # Broadcast over the heads axis: [batch, seq, 1, head_dim].
    cos, sin = cos[..., None, :], sin[..., None, :]
    xf = x.astype(jnp.float32)
    out = xf * cos + _rotate_half(xf) * sin
    return out.astype(x.dtype)
