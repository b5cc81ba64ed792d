"""Attention over a LATENT cache (multi-head latent attention, the
DeepseekV2/V3 family): a cached position is ONE row `c_n | k_r` — the
normed low-rank latent (`rank` values) and the one roped key every head
shares (`rope` values), padded with zeros to whole lane tiles of 128 (576
values in 640 lanes: what the chip's layout holds anyway, declared so that
the kernel's copies are aligned) — and never a key and a value a head.
Queries carry zeros against the padding.

The ABSORBED form attends over those rows as they lie. With the
up-projection `W_kvb` split a head into `W_UK`, `W_UV` [rank -> nope / v],

    q_lat = q_nope W_UK^T                      [H, rank]
    s     = ([q_lat | q_pe] . [c_n | k_r]) * scale
    o_lat = softmax(s) c_n                     [H, rank]
    o     = o_lat W_UV                         [H, v]

so every head's key is the same row of rank + rope values and its value the
row's first `rank`: one read of the cache serves all H heads (the caller,
models/llama.py `_latent_attention`, makes `q_lat` and applies `W_UV`).

  - `absorbed_attention`: the `jnp` form, any number of query positions a
    slot, causal by absolute position against one layer's rows (a chunk, a
    verify block, the CPU tests' continuation; a cache the kernel has no
    block for).
  - `mla_decode`: the Pallas kernel of the decode step — one query position
    a slot. The FULL [L, B, T, lanes] cache is its operand, pinned to
    HBM, layer and slot are DMA addressing: a grid step is a slot, whose
    LIVE blocks of `block_t` rows (those under its length, at least one)
    are copied into VMEM double-buffered — block j + 1 in flight while j is
    computed — and each is read ONCE for all H heads: scores [H, block_t]
    on the MXU against the block as it lies, the online softmax in
    float32, the output product against the block's first `rank` lanes. An
    empty slot walks one masked block: its row is garbage by contract and
    finite by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
LANES = 128
DECODE_NAME = "mla_decode"      # the kernel's name in a device capture
BLOCKS = (1024, 512, 256, 128)  # rows a copy: the largest that divides T
MAX_BLOCK_BYTES = 2**21         # of one buffer (two of them in VMEM)


def geometry(capacity: int, width: int, itemsize: int = 2) -> int | None:
    """`block_t`, the rows one copy of `mla_decode` moves at this cache
    shape, or None where the kernel has no block: a capacity that is no
    multiple of 128 (the tiny test shapes; the caller keeps the `jnp` form
    and says so). The largest of BLOCKS that divides the capacity and whose
    buffer — rows of `width` padded to whole lane tiles — is at most
    MAX_BLOCK_BYTES: 512 at 11,776 x 576 bfloat16 (0.66 MB a block)."""
    padded = -(-width // LANES) * LANES * itemsize
    for block_t in BLOCKS:
        if capacity % block_t == 0 and block_t * padded <= MAX_BLOCK_BYTES:
            return block_t
    return None


def absorbed_attention(
    q: jnp.ndarray,          # [B, S, H, rank + rope]: q_lat | q_pe
    rows: jnp.ndarray,       # [B, T, rank + rope]: one layer's cache
    positions: jnp.ndarray,  # [B, S] absolute position of each query
    kv_length: jnp.ndarray,  # [B] valid rows (this call's included)
    scale: float,
    rank: int,
) -> jnp.ndarray:
    """[B, S, H, rank] in q's dtype: softmax in float32 over the rows at or
    below each query's position (and under the slot's length)."""
    T = rows.shape[1]
    s = jnp.einsum("bshw,btw->bhst", q, rows.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    t = jnp.arange(T, dtype=jnp.int32)[None, None, :]
    mask = (t <= positions[:, :, None]) & (t < kv_length[:, None, None])
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,btr->bshr", p.astype(q.dtype),
                      rows[..., :rank].astype(q.dtype),
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _decode_kernel(len_ref, layer_ref, q_ref, c_hbm, o_ref, buf, sem, *,
                   scale: float, block_t: int, n_t: int, rank: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[b]
    n = jnp.clip((length + block_t - 1) // block_t, 1, n_t)
    # named, not None: a process-wide default (the tests' "highest") must
    # not turn the bf16 products into f32 ones Mosaic refuses
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def copy(j, slot):
        at = pl.ds(pl.multiple_of(j * block_t, block_t), block_t)
        return pltpu.make_async_copy(c_hbm.at[layer, b, at], buf.at[slot],
                                     sem.at[slot])

    copy(0, 0).start()
    q = q_ref[...]                                        # [H, W]
    H = q.shape[0]

    def body(j, carry):
        m, l, acc = carry
        slot = j % 2

        @pl.when(j + 1 < n)
        def _():
            copy(j + 1, 1 - slot).start()

        copy(j, slot).wait()
        blk = buf[slot].astype(q.dtype)                   # [block_t, W]
        s = jax.lax.dot_general(
            q, blk, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale   # [H, block_t]
        pos = j * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_t), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(q.dtype), blk[:, :rank], (((1,), (0,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(
        0, n, body, (jnp.full((H, 1), NEG_INF, jnp.float32),
                     jnp.zeros((H, 1), jnp.float32),
                     jnp.zeros((H, rank), jnp.float32)))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def mla_decode(
    q: jnp.ndarray,          # [B, H, rank + rope]: q_lat | q_pe
    cache: jnp.ndarray,      # [L, B, T, rank + rope] FULL latent cache
    layer: jnp.ndarray,      # scalar int32
    kv_length: jnp.ndarray,  # [B] valid rows (the current token's included)
    *,
    scale: float,
    rank: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """[B, H, rank] in q's dtype: `absorbed_attention` for one query a slot
    at position kv_length - 1, reading each slot's live blocks alone."""
    L, B, T, W = cache.shape
    H = q.shape[1]
    block_t = geometry(T, W, cache.dtype.itemsize)
    if block_t is None:
        raise ValueError(f"no mla_decode block for a capacity of {T}")
    if not interpret:
        # left free, XLA may stage a small operand whole in its fast
        # memory around the call; the cache stays in HBM
        cache = pltpu.with_memory_space_constraint(cache, pltpu.HBM)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_t=block_t,
                          n_t=T // block_t, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # kv_length, layer
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, W),
                                   lambda b, lens, lay: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((None, H, rank),
                                   lambda b, lens, lay: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, block_t, W), cache.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        interpret=interpret,
        name=DECODE_NAME,
    )(kv_length.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, cache)
