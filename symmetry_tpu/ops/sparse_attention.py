"""Learned sparse attention (DeepSeek-Sparse-Attention's lightning indexer):
which cache entries a query reads is itself computed.

A layer with a `sparse` sub-config (models/llama.py SparseAttention) keeps,
beside K and V, one INDEX KEY of `index_head_dim` channels a position
(`KVCache.idx`). A query t scores every position s <= t with

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (j: index heads)

and attends over the `min(t + 1, topk)` positions of the largest score
(ties toward the lower position: `jax.lax.top_k`'s order), one set for all
of the layer's query heads. Softmax over that set equals softmax over every
s <= t with the scores outside it at -inf, so the attention kernels take the
set as a KEEP MASK — the lossless "masked" form; nothing here selects by
blocks, shortens `topk`, or reuses one query's set for another.

  - `index_scores`: the scores, float32, by two contractions.
  - `select`: the set as a mask. The k-th largest score of a row is found
    EXACTLY by bisection over the scores' bit patterns (32 counting passes;
    a float32's bits, sign-folded, order as the value does) — a threshold,
    not a sort: `lax.top_k(x, 2048)` over 16,384 candidates is one — and
    the ties at the threshold are kept from the lowest position up until
    the set is full.
  - `prefill_keep`: a whole prompt's masks [B, S, S], a tile of queries at
    a time (the [heads, queries, S] products of a tile are all that is
    ever alive).
  - `flash_sparse`: causal flash attention under that mask — one
    `pallas_call` named `dsa_flash`, the KV-block loop a grid axis (so a
    16,384-token prompt's K and V are never resident whole), a KV head's
    whole group of query heads a step (the mask block and the K/V blocks
    are read once for the group).
  - `counts` / `add_counts`: what `stats.engine.dsa` reports.

Decode (one query a slot) and the S > 1 continuation over a non-empty
cache take `select`'s mask into ops/decode_attention.py (`keep=`) and
ops/attention.py `gqa_attention(keep=)`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
NAME = "dsa_flash"
QUERY_TILE = 256      # queries scored and thresholded at a time in prefill
BLOCK_Q = 128         # flash_sparse: queries a step (x the group's heads)
BLOCK_K = 512         # flash_sparse: keys a step
# the counters a sparse model appends to `KVCache.expert_pairs`: queries,
# dense_queries, then candidates and selected each as (high, low) words of
# COUNT_BITS low bits — a 16,384-token prompt alone has 134 M candidates a
# layer, and the int32 vector is only zeroed once a decode block
N_COUNTS = 6
COUNT_BITS = 20


def index_scores(qi: jnp.ndarray,    # [B, S, H, Di] roped index queries
                 ki: jnp.ndarray,    # [B, T, Di] roped index keys
                 w: jnp.ndarray,     # [B, S, H] head weights
                 ) -> jnp.ndarray:
    """[B, S, T] float32: sum over heads of w * relu(qI . kI)."""
    s = jnp.einsum("bshd,btd->bsht", qi, ki.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bsht,bsh->bst", jax.nn.relu(s),
                      w.astype(jnp.float32))


def _order_keys(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 in the values' TOTAL order (-0.0 under +0.0, as
    `lax.top_k` ranks them)."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def select(scores: jnp.ndarray,     # [..., T] float32
           valid: jnp.ndarray,      # [..., T] bool: the candidates
           topk: int) -> jnp.ndarray:
    """[..., T] bool: per row the min(candidates, topk) candidates of the
    largest score, ties toward the lower position — the set
    `lax.top_k(where(valid, scores, -inf), topk)` names, without a sort."""
    keys = jnp.where(valid, _order_keys(scores), jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, thr)

    # the largest threshold that at least topk keys reach: the topk-th
    # largest key (0 where a row has fewer candidates: they all stay)
    thr = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))[..., None]
    above = valid & (keys > thr)
    ties = valid & (keys == thr)
    room = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                            <= room))


def counts(keep: jnp.ndarray,       # [B, S, T] bool
           valid: jnp.ndarray,      # [B, S, T] bool
           topk: int) -> jnp.ndarray:
    """[N_COUNTS] int32 of one call (under 2**31 candidates): queries,
    dense queries (candidates <= topk: the set is every position), then
    candidates and selected as (high, low) words — of the rows that have a
    candidate."""
    n_valid = jnp.sum(valid, axis=-1, dtype=jnp.int32)
    large = jnp.stack([jnp.sum(n_valid, dtype=jnp.int32),
                       jnp.sum(keep, dtype=jnp.int32)])
    return jnp.concatenate([
        jnp.stack([jnp.sum(n_valid > 0, dtype=jnp.int32),
                   jnp.sum((n_valid > 0) & (n_valid <= topk),
                           dtype=jnp.int32)]),
        jnp.stack([large >> COUNT_BITS, large % (1 << COUNT_BITS)],
                  axis=1).reshape(4)])


def add_counts(vector: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """`vector` (an `expert_pairs` whose last N_COUNTS entries are the
    sparse counters, or the counters alone) plus `new` counters, the low
    words' overflow carried into the high ones so no int32 wraps."""
    tail = vector[-N_COUNTS:] + new
    low = tail[3::2]
    words = jnp.stack([tail[2::2] + (low >> COUNT_BITS),
                       low % (1 << COUNT_BITS)], axis=1).reshape(4)
    return jnp.concatenate([vector[:-N_COUNTS], tail[:2], words])


def read_counts(tail) -> dict:
    """The counters of a handed-out vector's tail as exact Python ints."""
    q, dense, ch, cl, sh, sl = (int(x) for x in tail)
    return {"queries": q, "dense_queries": dense,
            "candidates": (ch << COUNT_BITS) + cl,
            "selected": (sh << COUNT_BITS) + sl}


def cache_keep(qi, ki_cache, w, positions, kv_valid, topk: int):
    """The masks of S queries against a slot's cached index keys:
    qi [B, S, H, Di], ki_cache [B, T, Di], positions [B, S] (each query's
    own), kv_valid [B] -> (keep [B, S, T] bool, counts)."""
    return _masks(qi, ki_cache, w, positions, kv_valid, topk)


def _masks(qi, ki_cache, w, positions, kv_valid, topk: int):
    T = ki_cache.shape[1]
    pos = jnp.arange(T, dtype=jnp.int32)
    # (a padded query, at or past the slot's length, has no candidate)
    valid = ((pos[None, None, :] <= positions[..., None])
             & (positions[..., None] < kv_valid[:, None, None]))
    keep = select(index_scores(qi, ki_cache, w), valid, topk)
    return keep, counts(keep, valid, topk)


def prefill_keep(qi, ki, w, seq_lens, topk: int, *, tile: int = QUERY_TILE):
    """A fresh prompt's masks, queries at positions 0..S-1 over this call's
    own index keys: qi [B, S, H, Di], ki [B, S, Di], w [B, S, H],
    seq_lens [B] -> (keep [B, S, S] int8, counts). A tile of queries at
    a time: a tile's [B, tile, H, S] products are the largest array alive."""
    B, S, H, Di = qi.shape
    tile = min(tile, S)
    if S % tile:
        raise ValueError(f"S={S} is no multiple of the query tile {tile}")

    def one(t0):
        # the tile's queries against the prompt as a cache of its length
        keep, n = _masks(
            jax.lax.dynamic_slice_in_dim(qi, t0, tile, 1), ki,
            jax.lax.dynamic_slice_in_dim(w, t0, tile, 1),
            jnp.broadcast_to(t0 + jnp.arange(tile, dtype=jnp.int32),
                             (B, tile)), seq_lens, topk)
        return keep.astype(jnp.int8), n

    keep, n = jax.lax.map(one, jnp.arange(0, S, tile, dtype=jnp.int32))
    total = jax.lax.fori_loop(
        0, n.shape[0], lambda i, acc: add_counts(acc, n[i]),
        jnp.zeros((N_COUNTS,), jnp.int32))
    return jnp.moveaxis(keep, 0, 1).reshape(B, S, S), total


def _flash_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_sc, l_sc, acc_sc,
                  *, scale: float, block_q: int, block_k: int):
    qi, kj = pl.program_id(2), pl.program_id(3)
    G, _, D = q_ref.shape
    rows = G * block_q
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    @pl.when(kj == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    # causal: a KV block wholly above the query block's diagonal is skipped
    # (its index map repeats the last needed block: no copy either)
    @pl.when(kj * block_k <= qi * block_q + block_q - 1)
    def _():
        q = q_ref[...].reshape(rows, D)
        s = jax.lax.dot_general(
            q, k_ref[...], (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale   # [rows, block_k]
        keep = jnp.broadcast_to((keep_ref[...] != 0)[None],
                                (G, block_q, block_k)).reshape(rows, block_k)
        s = jnp.where(keep, s, NEG_INF)
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_old - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        # a padded query keeps nothing: l counts its masked keys (exp(0)
        # each) — garbage by contract, finite by construction
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                      ).reshape(G, block_q, D).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def flash_sparse(
    q: jnp.ndarray,        # [B, S, H, D]
    k: jnp.ndarray,        # [B, S, K, D]
    v: jnp.ndarray,        # [B, S, K, D]
    keep: jnp.ndarray,     # [B, S, S] int8: query t attends key s
    *,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """Self-attention over a fresh prompt under a per-query keep-set (which
    carries causality and the prompt's length: `prefill_keep`). Returns
    [B, S, H, D] in q's dtype; S must be a multiple of both blocks."""
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    block_q, block_k = min(block_q, S), min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} not a multiple of blocks {block_q}/{block_k}")
    qt = q.reshape(B, S, K, G, D).transpose(0, 2, 3, 1, 4)  # [B, K, G, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    def last(qi):  # the last KV block a query block needs
        return (qi * block_q + block_q - 1) // block_k

    q_spec = pl.BlockSpec((None, None, G, block_q, D),
                          lambda b, h, qi, kj: (b, h, 0, qi, 0))
    kv_spec = pl.BlockSpec(
        (None, None, block_k, D),
        lambda b, h, qi, kj: (b, h, jnp.minimum(kj, last(qi)), 0))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=D ** -0.5, block_q=block_q,
                          block_k=block_k),
        grid=(B, K, S // block_q, S // block_k),
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((None, block_q, block_k),
                               lambda b, h, qi, kj:
                               (b, qi, jnp.minimum(kj, last(qi))))],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((G * block_q, 1), jnp.float32),
                        pltpu.VMEM((G * block_q, 1), jnp.float32),
                        pltpu.VMEM((G * block_q, D), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B, K, G, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 2**20),
        name=NAME,
        interpret=interpret,
    )(qt, kt, vt, keep)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)
