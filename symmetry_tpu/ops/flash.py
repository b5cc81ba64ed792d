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

`flash_prefill_wide` is the same attention in tiles of 512 x 512 for long
prompts of many heads (latent attention expanded: 32 heads of 192 / 128 over
6-9k tokens), where the 128 x 128 walk is bound by the loop's own turns —
691k of them a 9,344-token prompt, each two matmuls too small to fill the
MXU's pipeline — and not by the FLOPs.
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
    D = v_ref.shape[-1]  # the value's width: the key's, or (latent
    # attention expanded: keys of 192, values of 128) its own

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
    v: jnp.ndarray,         # [B, S, K, Dv]: D, or a value width of its own
    seq_lens: jnp.ndarray,  # [B] int32 valid prompt lengths
    *,
    block_q: int = 128,
    block_k: int = 128,
    window: int | None = None,  # mistral-style sliding-window span
    interpret: bool = False,
    block_len: int | None = None,  # block mask: causal ACROSS blocks only
) -> jnp.ndarray:
    """Causal self-attention over a fresh (cache-empty) padded prompt.

    Returns [B, S, H, Dv] in q's dtype (scores scale by D ** -0.5, the
    key's width). Requires S % block == 0 (buckets are
    chosen that way); positions are 0..S-1 (prefill-from-empty contract of
    engine prefill, engine.py). `window` restricts attention to the last
    `window` keys (sliding-window models); blocks wholly outside the
    window are skipped, making long-prompt prefill O(S·window).
    """
    B, S, H, D = q.shape
    K, Dv = k.shape[2], v.shape[3]
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
                pl.BlockSpec((None, None, S, Dv),
                             lambda b, h, qi, sl: (b, h // group, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, block_q, Dv),
                                   lambda b, h, qi, sl: (b, h, qi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        interpret=interpret,
    )(seq_lens, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


WIDE_NAME = "flash_wide"
# The wide walk's tile, of 256 / 512 / 1024 on the chip at 32 heads of 192 /
# 128 (tools/flash_tile_ab.py; PERF.md, PR 54): a call over a 9,344 bucket
# 15.5 / 10.5 / 10.9 ms against 36.7 in 128 x 128, over 6,912 8.3 / 5.5 / 6.1
# against 20.4.
WIDE_TILE = 512


def wide_takes(window: int | None) -> bool:
    """Whether `flash_prefill_wide` walks a layer of this window: one whose
    edge is a tile's (a multiple of WIDE_TILE), or none."""
    return window is None or window % WIDE_TILE == 0


def _flash_wide_kernel(seqlen_ref, q_ref, k_ref, v_ref, o_ref, *,
                       scale: float, block: int, window_tiles: int = 0):
    """One tile of `block` queries of one head against the key tiles at or
    under its diagonal. Beside `_flash_kernel`: the operands go to the MXU
    as they are stored (bfloat16) and the scores scale in float32; only the
    diagonal tile is masked (a real query sees no key past itself, so none
    past the prompt's length either); a query tile that lies wholly in the
    bucket's padding — the last tiles, the longest walks — writes zeros.

    `window_tiles` w > 0 is a sliding window of w whole tiles (key s
    visible to query t iff t - w * block < s <= t): the walk starts at tile
    qi - w, and that tile alone is cut by the window's far edge — row r
    sees its columns past r, the diagonal's complement — the tiles between
    it and the diagonal are whole."""
    qi = pl.program_id(2)
    seq_len = seqlen_ref[pl.program_id(0)]
    Dv = v_ref.shape[-1]

    def tile(j, carry, diagonal=False, edge=False):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(j * block, block), :]
        v_blk = v_ref[pl.ds(j * block, block), :]
        s = jax.lax.dot_general(
            q_ref[:], k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if diagonal or edge:
            row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
            s = jnp.where(col <= row if diagonal else col > row, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    @pl.when(qi * block < seq_len)
    def _():
        carry = (jnp.full((block, 1), NEG_INF, jnp.float32),
                 jnp.zeros((block, 1), jnp.float32),
                 jnp.zeros((block, Dv), jnp.float32))
        if window_tiles:
            # (the edge tile's last row sees none of it: its running
            # maximum stays NEG_INF, its weights are exp(0) and its sum is
            # not 0 — until the next tile's correction exp(NEG_INF - m)
            # wipes them; the diagonal, where every row sees its own key,
            # always follows)
            lo = qi - window_tiles
            carry = jax.lax.cond(
                lo >= 0, lambda c: tile(lo, c, edge=True), lambda c: c,
                carry)
            carry = jax.lax.fori_loop(jnp.maximum(lo + 1, 0), qi, tile,
                                      carry)
        else:
            carry = jax.lax.fori_loop(0, qi, tile, carry)
        _, l, acc = tile(qi, carry, diagonal=True)
        o_ref[:] = (acc / l).astype(o_ref.dtype)  # l >= 1: the diagonal

    @pl.when(qi * block >= seq_len)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "window"))
def flash_prefill_wide(
    q: jnp.ndarray,         # [B, S, H, D]
    k: jnp.ndarray,         # [B, S, K, D]: K == H, or H a multiple of it
    v: jnp.ndarray,         # [B, S, K, Dv]
    seq_lens: jnp.ndarray,  # [B] int32 valid prompt lengths
    *,
    block: int = WIDE_TILE,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """`flash_prefill` (causal, from an empty cache) in tiles of `block` x
    `block`; S is padded up to a multiple of `block` here (a bucket is a
    multiple of 128) and the rows past a prompt's length come back as
    garbage or zeros, by the same contract. Returns [B, S, H, Dv].

    One key head a query head (latent attention expanded: the form PR 54
    built, whose program this leaves as it was), or grouped-query heads: a
    KV head's K and V stay in VMEM while its group's query heads walk them
    (the grid's head axis is the query's; consecutive heads of a group map
    to one block, which is fetched once). `window`: a sliding window of
    whole tiles (`wide_takes`); one no shorter than the padded prompt masks
    nothing and is dropped."""
    B, S, H, D = q.shape
    K, Dv = k.shape[2], v.shape[3]
    group = H // K
    block = min(block, S)
    if window is not None and window >= S + (-S % block):
        window = None
    if window is not None and window % block:
        raise ValueError(f"window {window} is no multiple of the tile "
                         f"{block}: flash_prefill takes it")
    pad = -S % block
    qt, kt, vt = (jnp.pad(a.transpose(0, 2, 1, 3),
                          ((0, 0), (0, 0), (0, pad), (0, 0)))
                  for a in (q, k, v))
    Sp = S + pad
    lanes = lambda d: -(-d // 128) * 128  # noqa: E731
    # K and V of one head stay whole in VMEM, double-buffered, beside the
    # tile's float32 scores and probabilities
    vmem = (4 * Sp * (lanes(D) + lanes(Dv)) + 6 * 4 * block * block
            + (8 << 20))
    kv_head = ((lambda b, h, qi, sl: (b, h, 0, 0)) if group == 1 else
               (lambda b, h, qi, sl: (b, h // group, 0, 0)))
    out = pl.pallas_call(
        functools.partial(
            _flash_wide_kernel, scale=D ** -0.5, block=block,
            **({} if window is None else {"window_tiles": window // block})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # seq_lens
            grid=(B, H, Sp // block),
            in_specs=[
                pl.BlockSpec((None, None, block, D),
                             lambda b, h, qi, sl: (b, h, qi, 0)),
                pl.BlockSpec((None, None, Sp, D), kv_head),
                pl.BlockSpec((None, None, Sp, Dv), kv_head),
            ],
            out_specs=pl.BlockSpec((None, None, block, Dv),
                                   lambda b, h, qi, sl: (b, h, qi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        name=WIDE_NAME,
        interpret=interpret,
    )(seq_lens, qt, kt, vt)
    return out[:, :, :S].transpose(0, 2, 1, 3)


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
