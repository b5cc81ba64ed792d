"""Pallas ragged decode attention: the cache is read where it lies, and
each slot only up to (a block past) its own length.

The decode step is HBM-bound and the KV cache is its second-largest stream
(after the weights). The XLA path reads the FULL [T] capacity of every slot
— `dynamic_index_in_dim(cache.k, layer)` is staged as one whole
`[B, T, K, D]` slice per layer for K and again for V (a copy into the
compiler's fast memory that the score / output fusions then read), and
masking discards the dead positions' values but not their traffic. This
kernel is the attention of every one-chip decode program (and, a call a
shard, of a sharded trunk from TP_MIN_CAPACITY up): one query position a
slot, or the S positions of a block that a model generating by diffusion
denoises at once — S times the query rows of a slot against the same
live blocks. The contract of S > 1: every row of a slot sees the slot's
keys below `kv_length` and nothing else tells the rows apart (the block
mask with `kv_length` on the block's end: ops/attention.py gqa_attention
`block_len`). It is NOT a causal multi-position kernel: speculative
verify and a chunked continuation have a mask a row and keep
`gqa_attention`.

  - The FULL [L, B, T, K, D] cache stays in HBM (pinned there: left
    free, XLA stages small operands whole in its fast memory) and is
    seen as it lies there, so the view is a bitcast (`_lanes`): as a
    rule the K heads of a position are rows of one memory tile and a
    slot is one lane, [T * K, D]; 2 int8 heads (a shard of 8 over
    model: 4; nemotron-3-nano-30b-a3b's and qwen3-next-80b-a3b's own 2 on
    one chip) lie head-major, [L, B, K, T, D] physically, and every
    (slot, head) is a lane of its own, [T, D], with one KV head — as
    long as the program's WRITES index the head too (models/llama.py
    write_kv, kv_head_major): a scatter of [K, D] rows has XLA relay the
    whole leaf to a (4, 128) tile for it and back for this view, a layer
    (PERF.md, PR 62; the scale planes of 2 rows likewise: a decode step
    writes them by a select in the (2, 128) tiles this kernel copies
    blocks out of). The layer is a scalar-prefetch argument: layer
    and lane selection are DMA addressing, never a materialised slice.
  - A head of 64 is half a lane tile, and `s8[L, B, T, 8, 64]` has no
    dense layout with a position's heads in rows: compiled for a
    described v5e it is one (8, 128) tile a position with lanes 64-127
    empty, 1,024 bytes for 512, and Mosaic refuses a 64-wide slice of
    it; the attached chip's own default for the shape is position-MINOR
    (major to minor L, B, K, D, T: dense, but a position's 512 bytes lie
    a capacity apart). So a model with such heads declares its K/V
    leaves PAIR-FOLDED (models/llama.py kv_row: [L, B, T, K / 2, 128],
    heads 2p and 2p + 1 in the two halves of row p), and the kernel is
    its own K / 2 form at 128 lanes: a query row of head (pair p, half s)
    carries its 64 values in lanes 64 s ... and zeros in the other half,
    so the 128-lane contraction against row (t, p) is that head's score;
    the output product yields the head's result in its own half (the
    sibling's V weighted by this head's probabilities in the other,
    which the wrapper drops). 2x redundant MXU lanes, as the interleave
    already spends K-fold. The scale planes stay a row a HEAD.
  - Work is lists of (slot, block) items the kernel writes into SMEM at
    the top of each grid step: for each slot only the `block_t`-entry
    blocks under its length (and, with a sliding window, not below the
    window's floor) — per slot, so a short slot costs nothing for sharing
    a tile with a long one. The slots are dealt to WAYS lists (each to
    the shortest so far) and one loop walks the lists side by side: an
    item's chain of MXU and cross-lane latencies is long and serial, WAYS
    independent chains in one straight line of code overlap. Per way the
    copies of the next item (K, V and the two scale blocks) are in flight
    while this one is computed, slot boundaries included. An empty slot
    still walks one (fully masked) block, so every output row is written,
    none NaN.
  - The grid is (B / slot_tile,): q and the output move in tiles of
    `slot_tile` slots (bounded so the work lists fit SMEM at any
    capacity); everything ragged happens inside a grid step.
  - GQA without a head loop or a relayout: ALL query heads contract
    against the block's rows — (position, head) interleaved, as they lie
    — in one [nq, block_t * K] MXU product with the block as stationary
    operand; wrong-pair scores are masked to -inf BEFORE the online
    softmax, so they exp to exactly 0 and the output product needs no
    selection. K-fold redundant MXU and VPU work (bf16 operands, one
    pass), which measured far cheaper than pulling each head's rows out
    of the interleave (PERF.md, PR 29).
  - Query rows are ordered (position, group, head) inside the kernel, so
    rows r, r + K, ... share a KV head and a `slab` of max(K, 8) rows is
    a whole sublane tile with the same head pattern in every slab: S
    positions are S times the groups, and nothing in the kernel's body
    knows which of the two a row is.
  - int8 caches (ops/quant.py quantize_kv): payload is read at 1 byte and
    widened in VMEM to the query's dtype (exact). The [K, block_t] scale
    planes are position-minor; the MXU lays them out in the scores'
    (position, head) lane order — a product with a 0/1 `spread` matrix,
    exact because the f32 scales go in as three bf16 terms and every
    output has one non-zero addend. The planes of the WAYS items a loop
    step computes share ONE product a chunk, a row a head: what the
    layout costs is the rows that stream through the MXU and come back
    [rows] wide, far more than the loads of `spread` (PR 59, one v5e, us
    an item at mistral's 128 x 640 of 8 heads | smallthinker's full
    rings of 4: a product a way over rows repeated to whole slabs 0.527
    | 0.521; one product for the ways 0.502 | 0.499; each distinct row
    once 0.502 | 0.466; no layout at all, wrong numbers, 0.449 | 0.441;
    the copies alone 0.389 | 0.380). k scales multiply the scores, v
    scales the probabilities, the probabilities are cast to the query's
    dtype before the output product — `gqa_attention`'s algebra.
  - Online softmax in f32; running max / sum / accumulator are the loop
    carry of each way, reset at a slot's first item. A masked position
    contributes exp(-inf - m) = 0 exactly, so a slot's result depends on
    nothing but its own rows.

  - A decode step of the homogeneous trunk appends to the cache once,
    BEHIND the layer loop (models/llama.py append_step), so inside it the
    position's own K/V row is not in the cache: it comes as an operand
    (`own`), `kv_length` counts the cached rows alone, and the row is
    where each way's online softmax STARTS — a slot's first item takes
    (own score, 1, own V) for its carry where any other call takes
    (-2^30, 0, 0). Dequantised as the kernel would have read it back:
    payload widened (exact), the k scale on the score, the v scale on
    the probability, which is cast to the query's dtype. The score and
    the weighted V are the wrapper's (two small XLA fusions over whole
    tiles a call); the kernel loads them. (A merge in jnp BEHIND a call
    that also returned its log-sum-exps was a dozen small relayouts and
    fusions a layer, half of what the append saved: PERF.md, PR 66.)
Masking is by absolute position (kv_pos < kv_length), identical semantics
to ops/attention.py gqa_attention at decode (q position == length - 1)
and, with S positions a slot, under `block_len=S` with the block's last
position at length - 1.

A learned selection (ops/sparse_attention.py: `keep`, a 0/1 float32 plane
[B, K, T], the slot's set repeated a KV head) rides with the int8 cache's
scale planes as a third one: copied block by block, laid out in the scores'
lane order by the same `spread` product, and turned into -inf on the scores
of what the query did not select — the masked form: every live block is
still read. Interleaved int8 lanes alone (`keep_supported`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
WINDOW_NAME = "swa_decode"  # the kernel's calls of a window / full model
LANES = 128          # scale planes are position-minor: blocks are lane tiles
SUBLANES = 8
BLOCK_ROWS = 1024    # (position, head) rows of one item: 128 KB of int8
WAYS = 4             # independent lane lists walked side by side
NBUF = 2             # buffers a way: one item computed, the next in flight
MAX_TILE_LANES = 128  # q / output lanes resident in VMEM per grid step, a
                      # query position each: S positions a lane are S of them
MAX_TILE_ITEMS = 1024  # a work list's entries in SMEM: 32 KB for them all
MAX_ITEM_BYTES = 2**19  # of K (and of V), WAYS x NBUF buffers each: 8 MB of
                        # the 16 MB of VMEM a kernel may take


def _lanes(n_kv: int, kv_bytes: int) -> tuple[int, int] | None:
    """(heads, n_kv): how a slot's KV heads (those on this chip) lie in
    the cache — `heads` lanes of `n_kv` interleaved heads each — or None
    for a count the kernel has no view of. What XLA does with the
    [L, B, T, K, D] cache of a program decides it (tests/test_chip_compile
    .py holds it to that): 4, 8 or a multiple of 8 heads are row tiles of
    the array as written; 2 heads of int8 would fill a sixteenth of a
    tile and the chip's layout of that leaf is head-major ([L, B, K, T, D]
    physically) — which a program keeps under the head-indexed scatter
    alone (models/llama.py write_kv: the row-window scatter of 2 int8
    heads has XLA copy the whole leaf to an interleaved (4, 128) tile and
    back around every call of this kernel, so `write_kv` never takes it
    for such a leaf) — while 2 heads of bf16 or f32 get a 2-row tile and
    stay interleaved.
    Heads of 64 come here as the PAIRS the cache holds (`geometry`): a
    pair is a row of 128 lanes like any head of 128."""
    if n_kv == 2 and kv_bytes == 1:
        return 2, 1
    if n_kv in (1, 2, 4) or (n_kv and n_kv % SUBLANES == 0):
        return 1, n_kv
    return None


def geometry(batch: int, capacity: int, n_kv: int, head_dim: int = LANES,
             kv_bytes: int = 1, queries: int = 1) -> tuple[int, int] | None:
    """(slot_tile, block_t) the kernel compiles with at this cache shape
    (`n_kv`: the KV heads on the chip; `kv_bytes`: of a cache entry;
    `queries`: the positions a slot's q carries), or None where it has
    none: the one gate — the caller keeps the XLA path
    there and says so. By shape alone, at every capacity and on either
    backend (the CPU interprets it); the >= 4,096 floor this replaced
    priced the old one-slot-one-block grid (PERF.md §6, PR 29), not
    the idea.

    block_t: the positions that make BLOCK_ROWS rows of a lane — 128 at
    8 interleaved KV heads, 256 at 4, 1,024 for a head-major lane — so an
    item is the same 128 KB copy and the same [nq, 1024] scores whatever
    the model; never under a lane tile, never over the capacity. A slot
    is read at most one block past its length: the block is the
    granularity of what "live" means. A capacity that the block does not
    divide (640 = 2.5 x 256) ends in a block that starts early and masks
    what the one before it covered. A capacity that is no multiple of 128
    has no block (the scale planes are position-minor and a partial lane
    tile would be a masked copy), nor have heads so many or wide that a
    lane tile of positions overruns MAX_ITEM_BYTES (gemma-7b's 16 heads
    of 256 in bf16). A head of 64 (`head_dim`: the QUERY's) has the
    geometry of its pair-folded cache — K / 2 rows of 128 a position —
    where the pairs are interleaved rows of one lane (lfm2-8b-a1b's 8
    heads: block_t 256, qwen2-7b's items); an odd head count, a single
    pair or head-major pairs (4 int8 heads) have none, and no other head
    size that is no lane tile has (the tiny test configurations' 16).
    slot_tile: the largest divisor of the batch whose lanes (slots x
    head-major heads) x `queries` are at most MAX_TILE_LANES — the q and
    output tiles are that many lanes' rows, double-buffered, so a block of
    4 queries moves in tiles of 32 slots at the bytes one query moves 128
    in (sdar-30b-a3b-chat: 32 x 128 rows x 128 in bf16, 1 MB a tile, 4 MB
    of VMEM for q and the output) — and whose blocks fit a work list of
    MAX_TILE_ITEMS (128 slots at 640, 16 at 8,192)."""
    fold = 2 if 2 * head_dim == LANES else 1    # heads a cache row
    if n_kv % fold:
        return None
    lanes = _lanes(n_kv // fold, kv_bytes)
    head_dim *= fold
    if lanes is None or capacity % LANES or head_dim % LANES:
        return None
    heads, n_kv = lanes
    if fold > 1 and (heads > 1 or n_kv == 1):
        return None
    block_t = min(capacity, max(LANES, BLOCK_ROWS // n_kv // LANES * LANES))
    if block_t * n_kv * head_dim * kv_bytes > MAX_ITEM_BYTES:
        return None
    most = max(1, min(MAX_TILE_LANES // queries, MAX_TILE_ITEMS
                      // -(-capacity // block_t)) // heads)
    return next(t for t in range(min(batch, most), 0, -1)
                if batch % t == 0), block_t


def keep_supported(n_kv: int, kv_bytes: int, quantized: bool,
                   head_dim: int = LANES) -> bool:
    """Whether `decode_attention(keep=)` has a form for this cache: the
    selection travels as a third scale plane, which only an int8 cache of
    interleaved heads has (of whole lane tiles: no configuration selects
    over pair-folded heads of 64)."""
    lanes = _lanes(n_kv, kv_bytes)
    return (quantized and lanes is not None and lanes[1] > 1
            and head_dim % LANES == 0)


def _three_bf16(x):
    """f32 x as three bf16 terms whose f32 sum is x exactly."""
    a = x.astype(jnp.bfloat16)
    r = x - a.astype(jnp.float32)
    b = r.astype(jnp.bfloat16)
    c = (r - b.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([a, b, c], axis=0)


def _lay_out(x, spread):
    """f32 planes x [n, chunk], position-minor, in the scores' lane order:
    [n, chunk * n_kv], column j the plane's value at position j // n_kv.
    One MXU product against the 0/1 `spread` [chunk, chunk * n_kv]: exact,
    since x goes in as three bf16 terms and an output has one non-zero
    addend a term."""
    n = x.shape[0]
    e = jax.lax.dot_general(
        _three_bf16(x), spread, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)
    return e[:n] + e[n:2 * n] + e[2 * n:]


def _kernel(len_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
            scale: float, block_t: int, capacity: int, heads: int,
            n_kv: int, fold: int, slab: int, ways: int, quantized: bool,
            window: int | None, compute_dtype, masked: bool,
            sliced: bool, with_own: bool):
    if quantized:
        planes_hbm, rest = rest[:2 + masked], rest[2 + masked:]
    if with_own:
        s0_ref, v0_ref, rest = rest[0], rest[1], rest[2:]
    if with_own and quantized:   # the planes again, aliased: never touched
        rest = rest[:1] + rest[3:]
    if quantized:
        o_ref, kbuf, vbuf, scbuf, *spread, islot, iblk, sem = rest
    else:
        o_ref, kbuf, vbuf, islot, iblk, sem = rest
    tile, nq, D = q_ref.shape
    base = pl.program_id(0) * tile
    layer = layer_ref[0]
    rows = block_t * n_kv               # a block's (position, head) rows
    chunk = min(block_t, LANES)         # positions one spread product lays
    n_t = -(-capacity // block_t)       # out
    # the last block of a capacity the block does not divide starts early
    align = block_t if capacity % block_t == 0 else LANES
    # named, not None: a process-wide default (the tests' "highest") must
    # not turn the bf16 products into f32 ones Mosaic refuses
    prec = (jax.lax.Precision.HIGHEST if compute_dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    # The work lists, one per way: (lane, block) items, lane-major, never
    # empty for a lane; each lane goes to the way with the fewest items.
    # A lane is a slot, or one head-major head of a slot.
    def list_slot(i, counts):
        length = len_ref[(base + i) // heads]
        hi = jnp.clip((length + block_t - 1) // block_t, 1, n_t)
        # decode q position == length - 1: the window's floor is
        # length - window, and blocks wholly under it are never read
        lo = 0 if window is None else jnp.minimum(
            jnp.maximum(length - window, 0) // block_t, hi - 1)
        w, least = jnp.int32(0), counts[0]
        for c in range(1, ways):
            fewer = counts[c] < least
            w = jnp.where(fewer, c, w)
            least = jnp.where(fewer, counts[c], least)

        def put(j, n):
            islot[w, n] = i
            iblk[w, n] = j
            return n + 1

        n = jax.lax.fori_loop(lo, hi, put, least)
        return tuple(jnp.where(w == c, n, counts[c]) for c in range(ways))

    counts = jax.lax.fori_loop(0, tile, list_slot,
                               tuple(jnp.int32(0) for _ in range(ways)))

    def block_start(blk):
        return pl.multiple_of(
            jnp.minimum(blk * block_t, capacity - block_t), align)

    def copies(w, item, buf):
        b = base + islot[w, item]
        t0 = block_start(iblk[w, item])
        at = pl.ds(pl.multiple_of(t0 * n_kv, align * n_kv), rows)
        out = [pltpu.make_async_copy(k_hbm.at[layer, b, at],
                                     kbuf.at[w, buf], sem.at[0, w, buf]),
               pltpu.make_async_copy(v_hbm.at[layer, b, at],
                                     vbuf.at[w, buf], sem.at[1, w, buf])]
        if quantized:
            for p, plane in enumerate(planes_hbm):
                # the keep plane (p == 2) is one layer's: no layer axis;
                # scale planes of one layer are the caller's slice of it
                at_t = (b // heads, slice(None), pl.ds(t0, block_t))
                out.append(pltpu.make_async_copy(
                    plane.at[at_t if p == 2 else
                             (0 if sliced else layer,) + at_t],
                    scbuf.at[w, buf, p], sem.at[2 + p, w, buf]))
        return out

    for w in range(ways):
        for d in range(NBUF - 1):
            @pl.when(d < counts[w])
            def _(w=w, d=d):
                for c in copies(w, d, d):
                    c.start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    t_of_lane = lane // n_kv
    row = jax.lax.broadcasted_iota(jnp.int32, (nq, 1), 0)
    # own-head: query row r (head r % K) against lane j (head j % K); of
    # pair-folded heads row r is head r % 2K, in the lanes of pair r % 2K // 2
    own = row % n_kv if fold == 1 else row % (n_kv * fold) // fold
    bias = jnp.where(own == (lane % n_kv), 0.0, NEG_INF).astype(jnp.float32)
    if quantized and n_kv > 1:
        # spread[t, j] = 1 where lane j holds position t: one MXU product
        # lays a [slab, chunk] scale plane out in the scores' lane order.
        spread, = spread
        spread[...] = (
            jax.lax.broadcasted_iota(jnp.int32, spread.shape, 0)
            == jax.lax.broadcasted_iota(jnp.int32, spread.shape, 1) // n_kv
        ).astype(jnp.bfloat16)

    def per_slab(x, planes):
        # x [nq, rows] times planes [slab, rows], the same for every slab
        # (one row for a head-major lane: its own head's)
        if planes.shape[0] == 1:
            return x * planes
        return (x.reshape(nq // slab, slab, rows) * planes[None]
                ).reshape(nq, rows)

    def body(step, carry):
        # Control first — each way's copies started and awaited — so that
        # the ways' arithmetic below is one straight line the scheduler
        # can interleave: the chains are independent.
        meta = []
        buf = step % NBUF
        for w in range(ways):
            live = step < counts[w]
            item = jnp.minimum(step, counts[w] - 1)
            i = islot[w, item]
            first = (item == 0) | (islot[w, jnp.maximum(item - 1, 0)] != i)

            @pl.when(step + NBUF - 1 < counts[w])
            def _(w=w):
                for c in copies(w, step + NBUF - 1, (step + NBUF - 1) % NBUF):
                    c.start()

            @pl.when(live)
            def _(w=w, item=item, buf=buf):
                for c in copies(w, item, buf):
                    c.wait()

            meta.append((live, i, iblk[w, item], first))

        if quantized and n_kv > 1:
            # The scale planes of ALL the ways' items through one product a
            # chunk, a row a head (2K rows where the heads lie in pairs):
            # what the layout costs is the rows that stream through the
            # MXU and come back as [rows]-wide results, so each distinct
            # row goes in once — [ways * (2 + masked) * n_h, chunk].
            n_h = n_kv * fold
            laid = jnp.concatenate([
                _lay_out(jnp.concatenate(
                    [scbuf[w, buf, p, :, c:c + chunk]
                     for w in range(ways) for p in range(2 + masked)],
                    axis=0), spread[...])
                for c in range(0, block_t, chunk)], axis=1)

        out = []
        for w in range(ways):
            live, i, blk, first = meta[w]
            m_in, l_in, acc_in = carry[w]
            length = len_ref[(base + i) // heads]
            start = (NEG_INF, 0.0, 0.0)
            if with_own:
                # the position's own row, which the cache does not hold
                # yet, is where the slot's softmax starts: its score (the
                # wrapper's: every lane of a query row's holds it, and the
                # MAX over them is how a column leaves a tile in the
                # carry's layout — a lane slice, or a block one lane wide,
                # is a relayout an item: +19% of the kernel, PERF.md PR
                # 66), a weight of exp(0), its V a slab of rows (row j the
                # lane's head j % n_h)
                start = (jnp.max(s0_ref[i], axis=-1, keepdims=True), 1.0,
                         jnp.broadcast_to(v0_ref[i][None],
                                          (nq // slab, slab, D)
                                          ).reshape(nq, D))
            m_old = jnp.where(first, start[0], m_in)
            l_old = jnp.where(first, start[1], l_in)
            acc = jnp.where(first, start[2], acc_in)

            s = jax.lax.dot_general(
                q_ref[i], kbuf[w, buf].astype(compute_dtype),
                dimension_numbers=(((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32)       # [nq, rows]
            if quantized and n_kv == 1:
                # the lane's own row of the [heads, block_t] planes
                head = (base + i) % heads
                k_plane, v_plane = (
                    functools.reduce(
                        lambda row, h: jnp.where(
                            head == h, scbuf[w, buf, p, h:h + 1], row),
                        range(1, heads), scbuf[w, buf, p, 0:1])
                    for p in range(2))
                s = per_slab(s, k_plane)
            elif quantized:
                # K < 8 heads fill a slab by repeating their rows
                k_plane, v_plane, *kept = (
                    jnp.concatenate(
                        [laid[r * n_h:(r + 1) * n_h]] * (slab // n_h), axis=0)
                    for r in range(w * (2 + masked), (w + 1) * (2 + masked)))
                s = per_slab(s, k_plane)
            s = s * scale + bias
            if masked:
                # 0 on a selected position, -inf (as the own-head bias's)
                # on one the query left out
                s = (s.reshape(nq // slab, slab, rows)
                     + ((kept[0] - 1.0) * -NEG_INF)[None]
                     ).reshape(nq, rows)
            pos = t_of_lane + block_start(blk)
            keep = (pos < length) & (pos >= blk * block_t)
            if window is not None:
                keep &= pos >= length - window
            s = jnp.where(keep, s, NEG_INF)

            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)                        # 0 where masked
            corr = jnp.exp(m_old - m_new)
            l_new = l_old * corr + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = per_slab(p, v_plane)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(compute_dtype), vbuf[w, buf].astype(compute_dtype),
                dimension_numbers=(((1,), (0,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32)       # [nq, D]
            # A way whose list has run out keeps its last slot's result
            # (what its buffers hold then is stale, and dropped here).
            out.append((jnp.where(live, m_new, m_in),
                        jnp.where(live, l_new, l_in),
                        jnp.where(live, acc, acc_in)))

        # Every item stores its slot's running result and the slot's last
        # item wins: no branch after the arithmetic. A slot with nothing
        # valid sums exp(0) over a masked block — garbage by contract,
        # finite by construction.
        for w in range(ways):
            _, l_new, acc = out[w]
            o_ref[meta[w][1]] = (acc / l_new).astype(o_ref.dtype)
        return tuple(out)

    steps = counts[0]
    for c in range(1, ways):
        steps = jnp.maximum(steps, counts[c])
    jax.lax.fori_loop(
        0, steps, body,
        tuple((jnp.full((nq, 1), NEG_INF, jnp.float32),
               jnp.ones((nq, 1), jnp.float32),
               jnp.zeros((nq, D), jnp.float32)) for _ in range(ways)))


@functools.partial(jax.jit, static_argnames=("window", "interpret", "name"))
def decode_attention(
    q: jnp.ndarray,           # [B, n_q_heads, D] (single decode position),
                              # or [B, S, n_q_heads, D]: S that share keys
    k_cache: jnp.ndarray,     # [L, B, T, K, D] FULL cache (bf16/f32 or int8);
    v_cache: jnp.ndarray,     # heads of 64 pair-folded: [L, B, T, K / 2, 128]
    layer: jnp.ndarray,       # scalar int32: which layer's cache to read
    kv_length: jnp.ndarray,   # [B] int32: the cache rows of each slot the
                              # queries see — WITH the current token where
                              # the caller wrote its row (every S > 1, the
                              # hybrid trunk), WITHOUT it where the row
                              # comes as `own`
    k_scale: jnp.ndarray | None = None,  # [L, B, K, T] f32 (int8 caches;
    v_scale: jnp.ndarray | None = None,  # position minor — tile-friendly),
                                         # or [1, B, K, T]: `layer`'s own
    keep: jnp.ndarray | None = None,     # [B, T] bool: the positions each
                                         # slot's query selected
    own: tuple | None = None,  # the position's OWN row, not in the cache yet:
                               # (k, v, k_scale, v_scale): [B, K, D] as the
    *,                         # cache will hold them, [B, K] (None: no int8)
    window: int | None = None,  # sliding-window span (mistral); bounds the
                                # per-slot block range below AND above
    interpret: bool = False,
    name: str | None = None,  # the call's name in a device trace (a model
                              # with window and full layers: WINDOW_NAME)
) -> jnp.ndarray:
    """Returns q's shape in q's dtype. Every one of a slot's S positions
    attends to the slot's keys below `kv_length`, its own block's among
    them (the caller wrote them): there is no mask a position, so no
    `window` and no `keep` with S > 1.

    With `own` (one query a slot, no selection) the query attends to the
    `kv_length` cached rows AND the row handed in, as if the cache held it
    at position `kv_length` (a `window` counts it): the same result as
    the call over a cache that holds the row but for the order of one
    float32 sum a query row; a slot with no cached row returns the own
    row's V. Heads of 64 come pair-folded as the cache holds them, their
    scales a HEAD. Over an int8 cache such a call returns (that, k_scale,
    v_scale): the planes ALIASED through the call, untouched. Its caller
    writes no cache in its layer loop (the append is behind it), so the
    planes would be constants of the loop, and XLA stages a loop-invariant
    operand that fits its fast memory whole around every call, the pin to
    HBM notwithstanding — qwen2-7b's 37 MB `k_scale` a LAYER, 1 GB a step
    beside the 8 GB it must read (PERF.md, PR 66). An output the
    loop carries is no constant; the leaves (1.2 GB) fit nowhere and stay
    operands alone. The planes keep their pin to HBM as OUTPUTS, so on the
    chip they have to flow through — donated in (or a loop's carry) and
    results of the program, as the trunk has them: a program that feeds
    them as plain arguments or drops them aborts the v5e compiler's memory
    assignment (tools/chip_kernels.py, tools/ab_ragged_640.py donate).

    A window layer's RING (models/llama.py KVCache.kw: position p at row p
    mod T, T the window) is this kernel with `kv_length = min(length, T)`
    and no `window`: the keys were roped when written and a softmax does
    not care in which row a key lies, so items start at row 0 as ever."""
    L, B, T, K, D = k_cache.shape     # K x D: a cache row as it lies
    queries = 1 if q.ndim == 3 else q.shape[1]
    nq, head_dim = q.shape[-2:]
    fold = D // head_dim              # heads a lane row: 2 for heads of 64
    group = nq // (K * fold)
    kv_bytes = k_cache.dtype.itemsize
    tiles = geometry(B, T, K * fold, head_dim, kv_bytes, queries)
    if tiles is None:
        raise ValueError(f"no decode-attention geometry for a {B} x {T} "
                         f"cache of {K * fold} KV heads of {head_dim}")
    if q.ndim == 4 and (window is not None or keep is not None
                        or own is not None):
        raise ValueError("a window, a selection or a row of its own is a "
                         "mask a position: a block of queries shares one "
                         "key set")
    if own is not None and keep is not None:
        raise ValueError("the own row rides no selection")
    slot_tile, block_t = tiles
    heads, n_kv = _lanes(K, kv_bytes)
    lanes, tile = B * heads, slot_tile * heads
    slab = max(n_kv * fold, SUBLANES)
    quantized = k_scale is not None
    masked = keep is not None
    if masked and not keep_supported(K, kv_bytes, quantized, head_dim):
        raise ValueError("a selection rides with the scale planes of "
                         "interleaved int8 lanes alone")
    compute_dtype = (q.dtype if quantized
                     else jnp.promote_types(q.dtype, k_cache.dtype))
    n_t = -(-T // block_t)
    rows = block_t * n_kv
    ways = min(WAYS, tile)

    # A lane's query rows ordered (position, group, head) and padded to
    # whole slabs: row r of the kernel's q and output belongs to the
    # lane's head r % n_kv.
    nql = queries * group * n_kv * fold
    nqp = -(-nql // slab) * slab
    if q.ndim == 3:
        qk = jnp.swapaxes(q.reshape(lanes, n_kv * fold, group, head_dim),
                          1, 2)
    else:
        by_lane = (B, heads, queries, group, n_kv * fold, head_dim)
        qk = jnp.transpose(q.reshape(B, queries, heads, n_kv * fold, group,
                                     head_dim), (0, 2, 1, 4, 3, 5))
    if fold > 1:
        # head 2p + s of a pair: its values in half s of the lane row,
        # zeros in the sibling's
        half = jax.lax.broadcasted_iota(jnp.int32, (1, 1, fold, 1), 2)
        qk = qk.reshape(lanes, nql // fold, fold, head_dim)
        qk = jnp.concatenate([jnp.where(half == s, qk, 0)
                              for s in range(fold)], axis=-1)
    qk = jnp.pad(qk.reshape(lanes, nql, D).astype(compute_dtype),
                 ((0, 0), (0, nqp - nql), (0, 0)))
    if heads > 1:  # head-major, as such a leaf lies under head-indexed
        # writes (models/llama.py kv_head_major): a bitcast
        k_cache, v_cache = (jnp.swapaxes(x, 2, 3) for x in (k_cache, v_cache))

    tile_spec = pl.BlockSpec((tile, nqp, D),
                             lambda i, lens, lay: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    args = [kv_length.astype(jnp.int32),
            jnp.reshape(layer, (1,)).astype(jnp.int32), qk,
            k_cache.reshape(L, lanes, T * n_kv, D),
            v_cache.reshape(L, lanes, T * n_kv, D)]
    in_specs = [tile_spec, hbm, hbm]
    scratch = [pltpu.VMEM((ways, NBUF, rows, D), k_cache.dtype),
               pltpu.VMEM((ways, NBUF, rows, D), v_cache.dtype)]
    n_sem = 2
    if quantized:
        chunk = min(block_t, LANES)
        args += [k_scale, v_scale]
        in_specs += [hbm, hbm]
        if masked:
            args += [jnp.broadcast_to(
                keep.astype(jnp.float32)[:, None, :], (B, K, T))]
            in_specs += [hbm]
        scratch += [pltpu.VMEM((ways, NBUF, 2 + masked, K * fold, block_t),
                               jnp.float32)]
        if n_kv > 1:
            scratch += [pltpu.VMEM((chunk, chunk * n_kv), jnp.bfloat16)]
        n_sem += 2 + masked
    scratch += [pltpu.SMEM((ways, tile * n_t), jnp.int32),
                pltpu.SMEM((ways, tile * n_t), jnp.int32),
                pltpu.SemaphoreType.DMA((n_sem, ways, NBUF))]
    start = []
    if own is not None:
        # The own row's score and weighted V, in the kernel's row order
        # (row r of a lane the head r % n_h) and its algebra: the payload
        # widened to the products' dtype, products summed in float32, the
        # k scale on the score, the v scale — cast to the products' dtype
        # as a probability is — on V. Made here: over whole (slab, 128)
        # tiles XLA needs two small fusions a call, where the kernel would
        # reduce over lanes an item.
        n_h = n_kv * fold
        f32 = jnp.float32

        def a_slab(x):  # [lanes, n_h, ...] -> a slab of rows, head j % n_h
            return jnp.tile(x.astype(f32), (1, slab // n_h)
                            + (1,) * (x.ndim - 2))

        def payload(x):  # a row a HEAD: a pair's row for both its heads
            x = x.astype(compute_dtype).reshape(lanes, n_kv, D)
            return a_slab(jnp.repeat(x, fold, axis=1) if fold > 1 else x)

        s0 = jnp.sum(qk.astype(f32).reshape(lanes, nqp // slab, slab, D)
                     * payload(own[0])[:, None], axis=-1)
        v0 = payload(own[1])
        if quantized:
            s0 = s0 * a_slab(own[2].reshape(lanes, n_h))[:, None]
            v0 = a_slab(own[3].reshape(lanes, n_h)).astype(
                compute_dtype).astype(f32)[..., None] * v0
        start = [jnp.broadcast_to(
            (s0 * head_dim ** -0.5).reshape(lanes, nqp, 1),
            (lanes, nqp, LANES)), v0]
        if window is not None:
            window -= 1   # of the cached rows: the own row takes a place
    # Left free, XLA's memory-space assignment stages a small enough
    # operand WHOLE in its fast memory around every call (qwen2-7b's 9 MB
    # scale arrays: three 9 MB copies a layer, for blocks the kernel
    # copies itself). The cache stays in HBM. (The interpreter has no
    # memory spaces.)
    if not interpret:
        args[3:] = [pltpu.with_memory_space_constraint(x, pltpu.HBM)
                    for x in args[3:]]
    for x in start:   # a lane's start moves with its q, in tiles
        args += [x]
        in_specs += [pl.BlockSpec((tile, *x.shape[1:]),
                                  lambda i, lens, lay: (i, 0, 0))]
    out_specs = tile_spec
    out_shape = jax.ShapeDtypeStruct((lanes, nqp, D), q.dtype)
    through = {}
    if own is not None and quantized:
        # (operands 5 and 6, counted with the two prefetched scalars; an
        # aliased operand's pin is its output's)
        through = {5: 1, 6: 2}
        out_specs = [tile_spec, hbm, hbm]
        out_shape = [out_shape] + [
            (jax.ShapeDtypeStruct if interpret else pltpu.HBM)(
                x.shape, x.dtype) for x in (k_scale, v_scale)]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=head_dim ** -0.5, block_t=block_t,
                          capacity=T, heads=heads, n_kv=n_kv, fold=fold,
                          slab=slab, ways=ways,
                          quantized=quantized, window=window,
                          compute_dtype=compute_dtype, masked=masked,
                          sliced=quantized and k_scale.shape[0] != L,
                          with_own=own is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # kv_length, layer
            grid=(B // slot_tile,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=through,
        interpret=interpret,
        **({} if name is None else {"name": name}),
    )(*args)
    if through:
        out, *planes = out
    out = out[:, :nql]
    if fold > 1:  # a head's result is in its own half of the row
        out = out.reshape(lanes, nql // fold, fold, D)
        out = sum(jnp.where(half == s,
                            out[..., s * head_dim:(s + 1) * head_dim], 0)
                  for s in range(fold))
    if q.ndim == 3:
        out = out.reshape(lanes, group, n_kv * fold, head_dim)
        out = jnp.swapaxes(out, 1, 2).reshape(B, nq, head_dim)
        return (out, *planes) if through else out
    return jnp.transpose(out.reshape(by_lane),
                         (0, 2, 1, 4, 3, 5)).reshape(q.shape)


# A sharded trunk takes the kernel only from here up: the gate the old
# grid had, kept for the mesh alone until the per-shard kernel has a
# four-chip A/B under it (models/llama.py attention_paths).
TP_MIN_CAPACITY = 4096


def decode_attention_tp(q, k_cache, v_cache, layer, kv_length,
                        k_scale=None, v_scale=None, *, mesh,
                        **kw) -> jnp.ndarray:
    """decode_attention inside a GSPMD program sharded over `mesh`.

    XLA cannot partition a pallas_call: left bare under tensor parallelism
    it would gather the whole KV cache into every call. One shard_map runs
    the kernel per shard instead — KV heads over `model` (the cache's own
    sharding, parallel/sharding.py; a shard's query heads are exactly its
    KV heads' groups; needs kv_heads % model == 0) and slots over `data`
    when they divide. No collective: the output stays head-sharded."""
    from jax.sharding import PartitionSpec as P

    data = dict(mesh.shape).get("data", 1)
    b = "data" if data > 1 and q.shape[0] % data == 0 else None
    kv = P(None, b, None, "model", None)
    scale = P(None, b, "model", None)
    quantized = k_scale is not None
    return jax.shard_map(
        lambda q, k, v, lay, n, *sc: decode_attention(q, k, v, lay, n, *sc,
                                                      **kw),
        mesh=mesh,
        in_specs=(P(b, "model", None), kv, kv, P(), P(b))
        + ((scale, scale) if quantized else ()),
        out_specs=P(b, "model", None), check_vma=False,
    )(q, k_cache, v_cache, layer, kv_length,
      *((k_scale, v_scale) if quantized else ()))
