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
    the set is full. These two are the `jnp` form: the oracle of the
    tests, and what an S > 1 continuation over a cache runs through XLA.
  - `dsa_select`: both of them as ONE `pallas_call`. A tile's scores are
    made a key block at a time on the MXU and kept, as order keys, in a
    VMEM scratch that the counting passes and the tie rule read; only the
    mask leaves. What cannot be a candidate is neither scored nor counted:
    the key blocks above a tile's diagonal, a slot's keys past its length,
    and a tile (or slot) whose queries all have <= topk candidates runs no
    pass at all — its mask is its candidates.
  - `prefill_keep`: a whole prompt's masks [B, S, S], a tile of queries a
    grid step of that kernel.
  - `cache_keep`: the masks of queries against cached index keys — one
    position a slot through the kernel's decode form (a work list of the
    live (slot, group of keys) items, DMA'd from the cache where it lies,
    every slot's threshold found together), anything else through `select`.
  - `flash_sparse`: causal flash attention under that mask — one
    `pallas_call` named `dsa_flash`, the KV-block loop a grid axis (so a
    16,384-token prompt's K and V are never resident whole), a KV head's
    whole group of query heads a step (the mask block and the K/V blocks
    are read once for the group).
  - `counts` / `length_counts` / `add_counts`: what `stats.engine.dsa`
    reports (the kernel's callers count from the lengths alone: the rule
    keeps exactly min(candidates, topk) a row).

Decode (one query a slot) and the S > 1 continuation over a non-empty
cache take the mask into ops/decode_attention.py (`keep=`) and
ops/attention.py `gqa_attention(keep=)`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from symmetry_tpu.ops.interpret import interpret_mode

NEG_INF = -2.0**30
NAME = "dsa_flash"
SELECT_NAME = "dsa_select"
QUERY_TILE = 256      # queries scored and thresholded at a time in prefill
SELECT_BLOCK = 256    # dsa_select: keys scored a step, counted a chunk
ROW_GROUP = 64        # dsa_select: queries whose thresholds rise together
ROW_BLOCK = 32        # dsa_select: queries whose head sum stays in registers
DECODE_GROUP = 2048   # dsa_select at decode: a slot's keys copied and scored
                      # a step
DECODE_VMEM = 48 * 2**20   # ... of its 64 MiB for every slot's keys and mask
LANES = 128
INT_MIN = -2**31
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


def _counters(n_cand: jnp.ndarray,    # [B, S] int32: candidates a query
              n_kept: jnp.ndarray,    # [B, S] int32: of them selected
              topk: int) -> jnp.ndarray:
    """[N_COUNTS] int32 of one call: queries, dense queries (candidates <=
    topk: the set is every position), then candidates and selected as
    (high, low) words — of the rows that have a candidate. Summed a row of
    the batch first (each under 2**31 candidates), the rows' words then."""
    large = jnp.stack([jnp.sum(n_cand, axis=-1, dtype=jnp.int32),
                       jnp.sum(n_kept, axis=-1, dtype=jnp.int32)])  # [2, B]
    words = jnp.stack([jnp.sum(large >> COUNT_BITS, axis=1),
                       jnp.sum(large % (1 << COUNT_BITS), axis=1)], axis=1)
    return add_counts(jnp.zeros((N_COUNTS,), jnp.int32), jnp.concatenate([
        jnp.stack([jnp.sum(n_cand > 0, dtype=jnp.int32),
                   jnp.sum((n_cand > 0) & (n_cand <= topk),
                           dtype=jnp.int32)]),
        words.reshape(4)]))


def counts(keep: jnp.ndarray,       # [B, S, T] bool
           valid: jnp.ndarray,      # [B, S, T] bool
           topk: int) -> jnp.ndarray:
    """The counters of a mask, by a pass over it."""
    return _counters(jnp.sum(valid, axis=-1, dtype=jnp.int32),
                     jnp.sum(keep, axis=-1, dtype=jnp.int32), topk)


def length_counts(n_cand: jnp.ndarray,   # [B, S] int32: candidates a query
                  topk: int) -> jnp.ndarray:
    """`counts` from each query's number of candidates alone: the rule
    keeps exactly min(candidates, topk) a row, so the kernel's callers need
    no pass over the mask."""
    return _counters(n_cand, jnp.minimum(n_cand, topk), topk)


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


# ---------------------------------------------------------------- dsa_select
#
# The selection as ONE kernel: a tile's index scores are made a key block at
# a time on the MXU, turned into order keys and kept in a VMEM scratch; the
# bisection's 32 counting passes and the tie rule read that scratch; only
# the keep mask is written. Key blocks above a tile's diagonal (or past a
# slot's length) are neither scored nor counted, and rows none of which has
# more than `topk` candidates run no pass at all: their mask is `valid`.
# Keys are `_order_keys` with the top bit flipped, so that SIGNED compares
# order them and INT_MIN is "no candidate".

def _precision(dtype):
    """float32 operands multiply exactly (the tests' oracle does)."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _int_keys(x: jnp.ndarray) -> jnp.ndarray:
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _bisect(count_ge, passes, shape, topk: int):
    """`select`'s threshold over keys only `count_ge(cand)` sees (how many
    are >= cand, shaped `shape`): (the topk-th largest key — INT_MIN where
    there are fewer, or after 0 passes —, how many keys reach it beyond
    topk: the ties to drop, from the highest position down)."""
    def bit(i, carry):
        thr, n_ge = carry
        cand = thr | jnp.left_shift(jnp.int32(1), 31 - i)
        n = count_ge(cand ^ jnp.int32(INT_MIN))
        enough = n >= topk
        return jnp.where(enough, cand, thr), jnp.where(enough, n, n_ge)

    zero = jnp.zeros(shape, jnp.int32)
    thr, n_ge = jax.lax.fori_loop(0, passes, bit, (zero, zero))
    return thr ^ jnp.int32(INT_MIN), n_ge - topk


def _fit(size: int, want: int) -> int:
    return want if size % want == 0 else size


def _threshold_mask(keys_sc, o_ref, rows, height: int, n_chunks, passes,
                    valid, *, topk: int, block: int):
    """Rows `rows` (`height` of them) of the keys in VMEM -> their rows of
    the mask: the bisection's `passes` counting passes over the first
    `n_chunks` blocks of columns, then `valid(c0)` ([height, block] from
    column c0 on) under the threshold — the tie rule only where some row's
    ties overfill its set —, zeros past the last chunk. After 0 passes the
    mask is `valid`."""
    def cols(c):
        return pl.ds(pl.multiple_of(c * block, block), block)

    def count_ge(cand):
        cb = jnp.broadcast_to(cand, (height, block))
        return jnp.sum(jax.lax.fori_loop(
            0, n_chunks,
            lambda c, acc: acc + (keys_sc[rows, cols(c)] >= cb
                                  ).astype(jnp.int32),
            jnp.zeros((height, block), jnp.int32)), axis=1, keepdims=True)

    thr, excess = _bisect(count_ge, passes, (height, 1), topk)
    tb = jnp.broadcast_to(thr, (height, block))
    drop = jnp.max(excess) > 0    # some row's ties overfill its set

    @pl.when(jnp.logical_not(drop))
    def _():
        def put(c, _):
            o_ref[rows, cols(c)] = (
                valid(c * block) & (keys_sc[rows, cols(c)] >= tb)
            ).astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, n_chunks, put, 0)

    @pl.when(drop)
    def _():
        # ties at the threshold stay from the lowest position up: walk down
        # from the last block, a row's ties so far as the carry, the ties
        # from a column on inside a block by a 0/1 product
        later = (jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
                 >= jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
                 ).astype(jnp.bfloat16)
        over = excess.astype(jnp.float32)

        def put(j, run):
            c = n_chunks - 1 - j
            ks, ok = keys_sc[rows, cols(c)], valid(c * block)
            ties = (ok & (ks == tb)).astype(jnp.float32)
            behind = run + jnp.dot(   # (0 / 1 in bfloat16: exact)
                ties.astype(jnp.bfloat16), later,
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)
            o_ref[rows, cols(c)] = (ok & ((ks > tb) | (
                (ties > 0) & (behind > over)))).astype(o_ref.dtype)
            return run + jnp.sum(ties, axis=1, keepdims=True)

        jax.lax.fori_loop(0, n_chunks, put,
                          jnp.zeros((height, 1), jnp.float32))

    def clear(c, _):
        o_ref[rows, cols(c)] = jnp.zeros((height, block), o_ref.dtype)
        return 0

    jax.lax.fori_loop(n_chunks, o_ref.shape[1] // block, clear, 0)


def _select_kernel(n_ref, q_ref, kt_ref, w_ref, o_ref, keys_sc, s_sc, wb_sc,
                   *, topk: int, block: int, group: int, row_block: int):
    b, ti = pl.program_id(0), pl.program_id(1)
    H, tile, Di = q_ref.shape
    t0, n = ti * tile, n_ref[b]
    prec = _precision(q_ref.dtype)

    def valid(r0, rows, c0):   # [rows, block]: candidates of queries r0.. on
        t = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0) + (t0 + r0)
        s = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1) + c0
        return (s <= t) & (t < n)

    # -- the scores of the tile's causal extent, as keys, into VMEM (only
    # where a query has more candidates than it may keep)
    hi = jnp.minimum(t0 + tile, n)
    selecting = (hi > t0) & (hi > topk)

    @pl.when(selecting)
    def _():
        for h in range(H):
            wb_sc[h] = jnp.broadcast_to(w_ref[:, h:h + 1], wb_sc.shape[1:])

    def score(kb, _):
        c0 = pl.multiple_of(kb * block, block)
        s_sc[...] = jax.lax.dot_general(
            q_ref[...].reshape(H * tile, Di), kt_ref[:, pl.ds(c0, block)],
            (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)   # rows ordered (head, query)

        def rows(rb, _):
            r0 = pl.multiple_of(rb * row_block, row_block)
            at = pl.ds(r0, row_block)

            def head(h, acc):
                part = s_sc[pl.ds(pl.multiple_of(h * tile + r0, row_block),
                                  row_block), :]
                return acc + jnp.maximum(part, 0.0) * wb_sc[h, at, :]

            # (traced once, unrolled when lowered: a head's slab is its
            # own loads, and the engine's start pays for every traced op)
            acc = jax.lax.fori_loop(
                0, H, head, jnp.zeros((row_block, block), jnp.float32),
                unroll=True)
            keys_sc[at, pl.ds(c0, block)] = jnp.where(
                valid(r0, row_block, c0), _int_keys(acc), jnp.int32(INT_MIN))
            return 0

        jax.lax.fori_loop(0, tile // row_block, rows, 0)
        return 0

    jax.lax.fori_loop(0, jnp.where(selecting, pl.cdiv(hi, block), 0),
                      score, 0)

    # -- a row group at a time: the threshold, then the mask
    def rows_of(g, _):
        g0 = pl.multiple_of(g * group, group)
        hi_g = jnp.minimum(t0 + g0 + group, n)
        live = hi_g > t0 + g0
        _threshold_mask(
            keys_sc, o_ref, pl.ds(g0, group), group,
            jnp.where(live, pl.cdiv(hi_g, block), 0),
            jnp.where(live & (hi_g > topk), 32, 0),
            functools.partial(valid, g0, group), topk=topk, block=block)
        return 0

    jax.lax.fori_loop(0, tile // group, rows_of, 0)


@functools.partial(jax.jit, static_argnames=("topk", "tile", "interpret"))
def _select_prefill(qi, ki, w, seq_lens, *, topk: int, tile: int,
                    interpret: bool):
    B, S, H, Di = qi.shape
    block = min(SELECT_BLOCK, S)
    group, row_block = _fit(tile, ROW_GROUP), _fit(tile, ROW_BLOCK)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block=block,
                          group=group, row_block=row_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, S // tile),
            in_specs=[
                pl.BlockSpec((None, H, tile, Di),
                             lambda b, t, n: (b, 0, t, 0)),
                # a prompt's index keys stay resident across its tiles
                pl.BlockSpec((None, Di, S), lambda b, t, n: (b, 0, 0)),
                pl.BlockSpec((None, tile, H), lambda b, t, n: (b, t, 0))],
            out_specs=pl.BlockSpec((None, tile, S),
                                   lambda b, t, n: (b, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile, S), jnp.int32),
                pltpu.VMEM((H * tile, block), jnp.float32),
                pltpu.VMEM((H, tile, block), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 2**20),
        name=SELECT_NAME,
        interpret=interpret,
    )(seq_lens.astype(jnp.int32), jnp.swapaxes(qi, 1, 2),
      jnp.swapaxes(ki.astype(qi.dtype), 1, 2), w.astype(jnp.float32))


def prefill_keep(qi, ki, w, seq_lens, topk: int, *, tile: int = QUERY_TILE,
                 interpret: bool | None = None):
    """A fresh prompt's masks, queries at positions 0..S-1 over this call's
    own index keys: qi [B, S, H, Di], ki [B, S, Di], w [B, S, H],
    seq_lens [B] -> (keep [B, S, S] int8, counts). The `dsa_select` kernel,
    a tile of queries a grid step: neither the [tile, H, S] products nor
    the [tile, S] scores exist outside VMEM."""
    S = qi.shape[1]
    tile = min(tile, S)
    if S % tile:
        raise ValueError(f"S={S} is no multiple of the query tile {tile}")
    if S % min(SELECT_BLOCK, S):
        raise ValueError(f"S={S} is no multiple of the key block "
                         f"{SELECT_BLOCK}")
    keep = _select_prefill(
        qi, ki, w, seq_lens, topk=topk, tile=tile,
        interpret=interpret_mode() if interpret is None else interpret)
    t = jnp.arange(S, dtype=jnp.int32)[None, :]
    return keep, length_counts(
        jnp.where(t < seq_lens[:, None], t + 1, 0), topk)


def decode_group(capacity: int, slots: int, index_dim: int) -> int | None:
    """Cached index keys one step of `dsa_select`'s decode form copies and
    scores, or None where a cache of this shape has no such layout (the
    `jnp` form serves it): groups of whole lane tiles, every slot's keys
    (int32) and mask (int32, two buffers) in VMEM at once, and index keys
    under a lane tile wide — the cache XLA keeps position-minor, which the
    kernel's [channels, keys] blocks are cut from without a copy."""
    if 12 * slots * capacity > DECODE_VMEM or index_dim >= LANES:
        return None
    if capacity % DECODE_GROUP == 0:
        return DECODE_GROUP
    if capacity < DECODE_GROUP and capacity % LANES == 0:
        return capacity            # (a small cache: one group a slot)
    return None


def _select_decode_kernel(n_ref, layer_ref, work_ref, slot_ref, group_ref,
                          q_ref, w_ref, len_ref, idx_ref, o_ref, keys_sc,
                          buf, sem, *, topk: int, gk: int, block: int):
    B, T = o_ref.shape
    total, n_chunks = work_ref[0], pl.cdiv(work_ref[1], block)
    prec = _precision(q_ref.dtype)

    def copy(i, s):
        return pltpu.make_async_copy(
            idx_ref.at[layer_ref[0], slot_ref[i], :,
                       pl.ds(group_ref[i] * gk, gk)], buf.at[s], sem.at[s])

    # -- the keys of the work list's (slot, group) items into the slots'
    # rows; what no item writes is no candidate
    @pl.when(total > 0)
    def _():
        copy(0, 0).start()

        def blank(c, _):
            keys_sc[:, pl.ds(pl.multiple_of(c * block, block), block)] = (
                jnp.full((B, block), INT_MIN, jnp.int32))
            return 0

        jax.lax.fori_loop(0, n_chunks, blank, 0)

    def item(i, _):
        s = i % 2

        @pl.when(i + 1 < total)
        def _():
            copy(i + 1, 1 - s).start()

        copy(i, s).wait()
        b, c0 = slot_ref[i], pl.multiple_of(group_ref[i] * gk, gk)
        sc = jax.lax.dot_general(
            q_ref[b], buf[s], (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)           # [H, gk]
        one = jnp.sum(jnp.maximum(sc, 0.0) * w_ref[b], axis=0,
                      keepdims=True)                      # [1, gk]
        pos = c0 + jax.lax.broadcasted_iota(jnp.int32, (1, gk), 1)
        keys_sc[pl.ds(b, 1), pl.ds(c0, gk)] = jnp.where(
            pos < n_ref[b], _int_keys(one), jnp.int32(INT_MIN))
        return 0

    jax.lax.fori_loop(0, total, item, 0)

    # -- every slot's threshold together (a row a slot), then the mask
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, block), 1)
    lens = jnp.broadcast_to(len_ref[...], (B, block))
    _threshold_mask(keys_sc, o_ref, slice(None), B, n_chunks,
                    jnp.where(total > 0, 32, 0),
                    lambda c0: lane + c0 < lens, topk=topk, block=block)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select_decode(qi, idx, layer, w, n_cand, *, topk: int, interpret: bool):
    B, H, Di = qi.shape
    T = idx.shape[3]
    gk = decode_group(T, B, Di)
    block = _fit(gk, SELECT_BLOCK)
    # the work list: a (slot, group) item for every group of gk keys that
    # holds a candidate of a slot with more candidates than it may keep —
    # a shorter slot keeps them all unscored, a dead one has none
    per_slot = jnp.where(n_cand > topk, -(-n_cand // gk), 0)
    ends = jnp.cumsum(per_slot)
    items = jnp.arange(B * (T // gk), dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, items, side="right"),
                       B - 1).astype(jnp.int32)
    group = jnp.clip(items - (ends - per_slot)[slot], 0, T // gk - 1)
    out = pl.pallas_call(
        functools.partial(_select_decode_kernel, topk=topk, gk=gk,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((B, H, Di), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec((B, H, 1), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec((B, 1), lambda i, *_: (0, 0)),
                # the whole index cache stays where it lies (channel-
                # major: the caller's note): layer, slot and the live
                # groups are DMA addressing
                pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((B, T), lambda i, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((B, T), jnp.int32),
                pltpu.VMEM((2, Di, gk), idx.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, T), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2**20),
        name=SELECT_NAME,
        interpret=interpret,
    )(n_cand, jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.stack([ends[-1], jnp.max(n_cand)]).astype(jnp.int32), slot,
      group.astype(jnp.int32), qi.astype(idx.dtype),
      w.astype(jnp.float32)[..., None], n_cand[:, None], idx)
    return out[:, None, :] != 0


def cache_keep(qi, ki_cache, w, positions, kv_valid, topk: int, *,
               layer=None, interpret: bool | None = None):
    """The masks of S queries against a slot's cached index keys:
    qi [B, S, H, Di], ki_cache [B, T, Di], positions [B, S] (each query's
    own), kv_valid [B] -> (keep [B, S, T] bool, counts). With `layer`,
    `ki_cache` is the WHOLE index cache [L, B, T, Di]: a single position a
    slot (S == 1) then goes through `dsa_select`'s decode form, which reads
    of each slot only the groups of keys under its length — a dead slot
    none — and never a layer's slice of the cache as an array."""
    if layer is not None:
        _, slots, capacity, dim = ki_cache.shape
        if qi.shape[1] == 1 and decode_group(capacity, slots,
                                             dim) is not None:
            # (a padded query, at or past the slot's length, has no
            # candidate)
            n_cand = jnp.where(positions < kv_valid[:, None], positions + 1,
                               0).astype(jnp.int32)
            # (XLA lays such a cache out [L, B, Di, T] physically, as it
            # does a head-major K/V cache, ops/decode_attention.py: the
            # swap is a bitcast there — tests/test_chip_compile.py)
            keep = _select_decode(
                qi[:, 0], jnp.swapaxes(ki_cache, 2, 3), layer, w[:, 0],
                n_cand[:, 0], topk=topk,
                interpret=(interpret_mode() if interpret is None
                           else interpret))
            return keep, length_counts(n_cand, topk)
        ki_cache = jax.lax.dynamic_index_in_dim(ki_cache, layer, 0,
                                                keepdims=False)
    return _masks(qi, ki_cache, w, positions, kv_valid, topk)


def _masks(qi, ki_cache, w, positions, kv_valid, topk: int):
    T = ki_cache.shape[1]
    pos = jnp.arange(T, dtype=jnp.int32)
    # (a padded query, at or past the slot's length, has no candidate)
    valid = ((pos[None, None, :] <= positions[..., None])
             & (positions[..., None] < kv_valid[:, None, None]))
    keep = select(index_scores(qi, ki_cache, w), valid, topk)
    return keep, counts(keep, valid, topk)


def _flash_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_sc, l_sc, acc_sc,
                  *, scale: float, block_q: int, block_k: int):
    qi, kj = pl.program_id(2), pl.program_id(3)
    G, _, D = q_ref.shape
    rows = G * block_q
    prec = _precision(q_ref.dtype)

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
