"""Pallas flash attention for prefill (causal, GQA, ragged lengths).

Why: naive prefill attention materializes [heads, S, S] f32 scores — at the
2048 bucket that is ~0.5 GB per layer, and HBM traffic dominates. The flash
kernel streams K/V blocks through VMEM with the standard running-max /
running-sum rescaling, so score tiles never leave VMEM (online softmax).

Inputs arrive [B, S, H, D] (the model's layout) and are viewed [B, H, S, D]
for the kernel — TPU lowering needs the block's trailing dims to be the
tileable (S, D) pair. BlockSpec `None` dims pick the (batch, head)
coordinate per grid step and the GQA q→kv head mapping happens in the k/v
index_map (h // group), so repeated KV heads are never materialized.

Causality is block-skipped: the kv loop for query block `qi` runs only to
block qi, giving the ~2x FLOP saving of causal masking, with the partial
diagonal block masked by element positions. Ragged prompt lengths
(`seq_lens`, the padded-bucket contract of engine prefill) mask the same
way; fully-masked padded rows get a sum-guard instead of NaNs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30


def _flash_kernel(seqlen_ref, q_ref, k_ref, v_ref, o_ref, *, scale: float,
                  block_q: int, block_k: int, window: int | None,
                  block_len: int | None = None):
    qi = pl.program_id(2)
    seq_len = seqlen_ref[pl.program_id(0)]  # this batch row's true length

    q = q_ref[:].astype(jnp.float32) * scale  # [block_q, D]
    D = q.shape[-1]

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, D), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    # the last key a query sees: itself, or under a block mask (generation
    # by diffusion over blocks of `block_len`) the last position of its
    # own block — causal across blocks, bidirectional inside one
    q_last = q_pos if block_len is None else (
        q_pos // block_len * block_len + (block_len - 1))

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]  # [block_k, D]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        kv_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (kv_pos <= q_last) & (kv_pos < seq_len)
        if window is not None:
            # mistral-style local attention: key within `window` of query
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    # Causal block skip: query block qi only sees kv blocks 0..qi; with a
    # sliding window, also skip blocks wholly OLDER than the window (the
    # oldest key any query in this block can see is qi*block_q - window+1).
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (qi * block_q - window + 1) // block_k)
    # (a block mask ends a query tile's keys with the tile: flash_prefill
    # holds block_len to a divisor of block_q, and block_k == block_q)
    m, l, acc = jax.lax.fori_loop(lo, qi + 1, body, (m0, l0, acc0))
    # Padded rows (q_pos >= seq_len) are fully masked: l == 0. Guard the
    # division; their output is garbage by contract, but must not be NaN.
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "window", "interpret",
                              "block_len"))
def flash_prefill(
    q: jnp.ndarray,         # [B, S, H, D]
    k: jnp.ndarray,         # [B, S, K, D]
    v: jnp.ndarray,         # [B, S, K, D]
    seq_lens: jnp.ndarray,  # [B] int32 valid prompt lengths
    *,
    block_q: int = 128,
    block_k: int = 128,
    window: int | None = None,  # mistral-style sliding-window span
    interpret: bool = False,
    block_len: int | None = None,  # block mask: causal ACROSS blocks only
) -> jnp.ndarray:
    """Causal self-attention over a fresh (cache-empty) padded prompt.

    Returns [B, S, H, D] in q's dtype. Requires S % block == 0 (buckets are
    chosen that way); positions are 0..S-1 (prefill-from-empty contract of
    engine prefill, engine.py). `window` restricts attention to the last
    `window` keys (sliding-window models); blocks wholly outside the
    window are skipped, making long-prompt prefill O(S·window).
    """
    B, S, H, D = q.shape
    K = k.shape[2]
    group = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} not a multiple of blocks {block_q}/{block_k}")
    if block_len is not None and (block_q % block_len or block_q != block_k
                                  or window is not None):
        raise ValueError(
            f"block_len {block_len} must divide the query tile {block_q}, "
            f"with equal tiles ({block_k}) and no sliding window")
    scale = D ** -0.5

    # [B, S, H, D] -> [B, H, S, D]: trailing (S, D) dims are the TPU-tileable
    # pair; XLA fuses these transposes into the surrounding projections.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, S // block_q)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        window=window, **({} if block_len is None
                          else {"block_len": block_len}))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # seq_lens
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, block_q, D),
                             lambda b, h, qi, sl: (b, h, qi, 0)),
                pl.BlockSpec((None, None, S, D),
                             lambda b, h, qi, sl: (b, h // group, 0, 0)),
                pl.BlockSpec((None, None, S, D),
                             lambda b, h, qi, sl: (b, h // group, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, block_q, D),
                                   lambda b, h, qi, sl: (b, h, qi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(seq_lens, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def flash_prefill_tp(q, k, v, seq_lens, *, mesh, **kw) -> jnp.ndarray:
    """flash_prefill inside a GSPMD program sharded over `mesh`.

    XLA cannot partition a pallas_call: left bare under tensor parallelism
    it would gather every head onto every device first. One shard_map runs
    the kernel per shard instead — heads over `model` (a shard's query
    heads are exactly its KV heads' groups, so the h // group mapping holds
    shard-locally; needs kv_heads % model == 0) and prompts over `data`
    when they divide. No collective: the output stays head-sharded, where
    the row-parallel wo wants it."""
    from jax.sharding import PartitionSpec as P

    data = dict(mesh.shape).get("data", 1)
    b = "data" if data > 1 and q.shape[0] % data == 0 else None
    spec = P(b, None, "model", None)
    return jax.shard_map(
        lambda q, k, v, n: flash_prefill(q, k, v, n, **kw), mesh=mesh,
        in_specs=(spec, spec, spec, P(b)), out_specs=spec,
        check_vma=False)(q, k, v, seq_lens)
