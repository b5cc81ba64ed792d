"""Grouped-query attention over a static KV cache.

One attention routine serves every phase of serving:

  - prefill: q covers S new positions, cache already holds them (written
    before the call), mask is causal-by-absolute-position;
  - decode:  q covers 1 new position per slot, attends to everything the
    slot has written so far;
  - a block (generation by diffusion, `block_len`): q covers one block a
    slot or the prompt's whole blocks, each seeing its own block whole.

(Where ops/decode_attention.py has a geometry for the cache it takes the
decode case and a block of queries from a block boundary, and this routine
keeps what has a mask a position or a cache the kernel has no tiles for:
chunked continuations, speculative verify, the admission's opening block
over its scratch — models/llama.py attention_paths.)

Masking is driven entirely by absolute positions, so the same jitted
computation handles ragged per-slot lengths in a continuous batch — the
shapes stay static (slots × max_seq) and the MXU sees one big batched
matmul rather than per-request loops (SURVEY §2.3: continuous batching is
the core net-new engine component).

The einsum groups query heads onto their KV head ([B, K, G, S, D]) instead of
materializing repeated K/V — with 8 q-heads per KV head (llama3-8b) that is
an 8x saving of HBM traffic on the cache read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0**30  # large-but-finite: keeps softmax NaN-free for all-masked rows


def gqa_attention(
    q: jnp.ndarray,          # [B, S, n_q_heads, head_dim]
    k_cache: jnp.ndarray,    # [B, T, n_kv_heads, head_dim]  (T = cache capacity)
    v_cache: jnp.ndarray,    # [B, T, n_kv_heads, head_dim]
    q_positions: jnp.ndarray,  # [B, S] absolute position of each query token
    kv_length: jnp.ndarray,    # [B] number of valid cache entries per sample
    sliding_window: int | None = None,  # mistral-style local attention span
    k_scale: jnp.ndarray | None = None,  # [B, n_kv_heads, T] f32: int8 cache
    v_scale: jnp.ndarray | None = None,  # per-token-per-head dequant scales
    keep: jnp.ndarray | None = None,  # [B, S, T] bool: a learned selection
                                      # (ops/sparse_attention.py) to stay in
    block_len: int | None = None,  # block mask: causal ACROSS blocks only
    kv_positions: jnp.ndarray | None = None,  # [B, T] int32: the position
                                      # each cache row holds (a ring: -1
                                      # where none); None: row t holds t
) -> jnp.ndarray:
    """Returns [B, S, n_q_heads, head_dim] in q's dtype. Softmax in f32.

    With k_scale/v_scale set, k_cache/v_cache hold int8 payloads
    (ops/quant.py quantize_kv). Dequantization is folded into the existing
    contractions — k's scale multiplies the scores (k = q·s distributes over
    the dot product), v's scale multiplies the probabilities — so no bf16
    copy of the cache is ever materialized and the HBM read stays int8-wide.

    `block_len` (generation by diffusion over blocks) makes the mask causal
    across blocks of that many ABSOLUTE positions and bidirectional inside
    one: a query sees every written key up to the last position of its own
    block. (One block a slot with `kv_length` on the block's end is the same
    mask for all its rows, `pos < kv_length`: the decode kernel's.)

    `kv_positions` (a window layer's ring, models/llama.py ring_positions)
    masks by the position each row HOLDS instead of its index: causal and
    windowed against that, and live where it is not -1.
    """
    B, S, n_q, D = q.shape
    T, n_kv = k_cache.shape[1], k_cache.shape[2]
    group = n_q // n_kv
    scale = D ** -0.5
    # HIGHEST forces multi-pass bf16 matmuls; with an int8 operand the
    # upcast is exact, so default precision loses nothing.
    prec = None if k_scale is not None else jax.lax.Precision.HIGHEST

    qg = q.reshape(B, S, n_kv, group, D)
    # scores: [B, n_kv, group, S, T]. f32 accumulation: bf16 qk products drift
    # visibly at long T, and the MXU accumulates in f32 natively anyway.
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k_cache,
        precision=prec,
        preferred_element_type=jnp.float32,
    )
    if k_scale is not None:
        scores = scores * k_scale[:, :, None, None, :]
    scores = scores * scale

    kv_pos = jnp.arange(T, dtype=jnp.int32)
    q_last = q_positions if block_len is None else (
        q_positions // block_len * block_len + (block_len - 1))
    if kv_positions is not None:
        held = kv_positions[:, None, :]
        mask = (held >= 0) & (held <= q_last[..., None])  # [B, S, T]
        if sliding_window is not None:
            mask &= held > q_positions[..., None] - sliding_window
        sliding_window = None
    else:
        # key valid iff written (pos < kv_length) and causal (pos <= query
        # pos)
        mask = (kv_pos[None, None, :] <= q_last[..., None]) & (
            kv_pos[None, None, :] < kv_length[:, None, None]
        )  # [B, S, T]
    if sliding_window is not None:
        mask &= kv_pos[None, None, :] > q_positions[..., None] - sliding_window
    if keep is not None:
        mask &= keep
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)

    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    if v_scale is not None:
        # Fold v's dequant scale into the probabilities (per key position) —
        # masked positions contribute 0 regardless of their garbage scale.
        probs = probs * v_scale[:, :, None, None, :]
    probs = probs.astype(q.dtype)

    out = jnp.einsum("bkgst,btkd->bskgd", probs, v_cache,
                     precision=prec,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(B, S, n_q, D)
