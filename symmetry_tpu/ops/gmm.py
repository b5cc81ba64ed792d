"""Pallas grouped matmul with an int8 operand: the routed expert FFN's
three matmuls (models/moe.py `_routed_ffn`) on one device.

    out[r, :] = (rows[r, :] @ q[layer, e(r)]) * scale[layer, e(r), :]

(down, and an ungated expert's up), or for a gated expert's gate and up

    out[r, :] = act(gate's product) * up's product      in `rows.dtype`

`rows [R, A]` lie sorted by expert, so expert e's rows are the contiguous
group `offsets[e] .. offsets[e + 1]`; `q [L, X, A, F]` is the int8 stack of
every layer's experts and `scale [L, X, F]` its per-(expert, column) scales.
The algebra is `lax.ragged_dot`'s followed by the scale gathered per row,
letter for letter: the int8 tile is widened to the activation dtype in VMEM
(exact: |q| <= 127), products accumulate in float32, the scale multiplies
the accumulator. No pair is dropped and there is no capacity.

What the kernel is for is what it does NOT read and does not compute:

  - The WHOLE stack is the operand, left where it lies in HBM, with the
    layer a scalar-prefetch argument (ops/ssm_step.py's lesson: a per-layer
    slice around a kernel is a copy of the layer — 0.5 GB a matmul at 512
    experts). Which expert's tile is copied in is DMA addressing, read from
    the walk below: an expert with no row is never read, and an expert's
    tile is read once however many row tiles its group touches (the block
    index does not change between them, so the pipeline skips the copy).
  - The grid's second axis walks VISITS: (group, row tile) for every row
    tile a non-empty group touches, groups ascending — at most
    `tiles + min(X, R) - 1` of them, a static bound; the walk's tail past
    the true count repeats the last visit's addresses (no copy) and skips
    the body. A visit multiplies its row tile by its expert's [A, tn] tile
    and keeps only the rows of that group (a select on the accumulator):
    a row outside the group costs no MXU tile of its own, it rides in the
    tile of its neighbours. Consecutive visits of one row tile find its
    output block still in VMEM; it goes back to HBM when the walk leaves it.
  - The first axis tiles F where an expert's [A, F] is too large for VMEM
    (`COLUMN_TILE_BYTES`); at the served shapes an expert is one tile.

A gated expert's first two matmuls are ONE call (PR 64): `q` and `scale`
a (gate, up) pair of stacks of one shape, `act` the activation. The walk,
the row tile and the maps are the same; a visit copies the expert's gate
tile AND its up tile, multiplies the row tile it holds by each into a
float32 accumulator, scales each, and writes `act(g) * u` — formed in
float32, rounded once, to the activations' dtype: the float32 ops and
their order are those of two calls and the fusion between them
(tests/test_gmm.py holds the two to the last bit), with half the visits,
and neither [R, F] float32 product goes to HBM and back. The column tile
is `geometry`'s, per leaf; the VMEM the call asks for is what doubles
(granite's [4096, 768] pair 51 MB, lfm2's [2048, 1792] 60 of a v5e's 128).

`megablox` (jax.experimental.pallas.ops.tpu) is the structure; it refuses an
int8 operand and takes one layer. Rows beyond `sum(group_sizes)` are left
unwritten: `_routed_ffn`'s groups cover every row.

Costs (a v5e; PERF.md, PR 36; models/moe.py has the layers' tables). One
matmul of 5,120 rows over 512 experts of [2048, 512] (504 hit): 0.89 ms at
a row tile of 64, 0.94 at 32, 1.02 at 128, against 0.66 for the hit
experts' bytes at 819 GB/s — a visit costs about what the DMA of its 1 MB
tile costs (1.28 us) while the MXU streams 64 rows or fewer behind it (the
chip multiplies ~120 rows in the time it copies the weights they meet), and
a larger tile only adds rows of other groups to every visit. Over 72
experts of [4096, 768] the same rows take 0.58 / 0.56 ms at 64 / 128 (the
bytes: 0.28): ~70 rows a group are MXU work either way. So the row tile is
64 whatever the shape. Tried and left out: the widened tile kept in a
scratch for the group's next row tile (+4% to +13%: widening where it is
multiplied is the cheaper form), narrower column tiles (+7% to +60%), the
contraction cut in chunks (no change), a third buffer for the expert tile
(the grid pipeline has two).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROW_TILE = 64                   # rows a visit multiplies (module docstring)
COLUMN_TILE_BYTES = 4 * 2**20   # of one expert's int8 a grid step copies in
NAME = "moe_gmm"                # the op's name in a device trace


def geometry(n_rows: int, width: int, columns: int, itemsize: int = 2, *,
             interpret: bool = False) -> tuple[int, int] | None:
    """(row tile, column tile) for rows [R, A] against experts of [A, F],
    or None where the kernel has none: Mosaic wants A and the column tile
    whole lanes (any shape interprets). The row tile is `ROW_TILE`, or all
    the rows rounded up to the activation dtype's sublane tile where there
    are fewer; the column tile the widest divisor of F, in lanes, whose
    int8 [A, tn] is at most `COLUMN_TILE_BYTES`."""
    sublanes = 32 // itemsize
    tm = min(ROW_TILE, -(-n_rows // sublanes) * sublanes)
    if interpret:
        return tm, columns
    if width % LANES or columns % LANES:
        return None
    most = COLUMN_TILE_BYTES // width // LANES
    tn = next((t for t in range(min(columns // LANES, most), 0, -1)
               if (columns // LANES) % t == 0), None)
    return None if tn is None else (tm, tn * LANES)


def visits(group_sizes: jnp.ndarray, n_rows: int, tm: int):
    """The walk: (offsets [X + 1], group [V], row tile [V], count [1]),
    int32. Visit v multiplies row tile `tile[v]` by expert `group[v]`;
    v >= count repeats the last visit."""
    X = group_sizes.shape[0]
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    first = (ends - group_sizes) // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    through = jnp.cumsum(tiles)             # visits of groups 0 .. g
    count = through[-1]
    n_visits = -(-n_rows // tm) + min(X, n_rows) - 1
    v = jnp.minimum(jnp.arange(n_visits, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(through, v, side="right", method="compare_all"),
        X - 1).astype(jnp.int32)
    tile = first[group] + v - (through[group] - tiles[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile.astype(jnp.int32), count.reshape(1)


def _kernel(layer_ref, offsets_ref, group_ref, tile_ref, count_ref,
            rows_ref, *refs, act=None):
    """`refs`: (q, scale, out) or, gated, (gate q, gate scale, up q, up
    scale, out)."""
    del layer_ref                                   # addressing only
    *leaves, out_ref = refs
    v = pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _():
        tm = rows_ref.shape[0]
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        rows = rows_ref[...]

        def product(q_ref, scale_ref):
            # The tile is widened where it is multiplied (module
            # docstring). One MXU pass is exact for bfloat16 operands and
            # all Mosaic takes for them: said here, a process whose default
            # precision is "highest" (the CPU tests) is not refused the
            # kernel.
            acc = jax.lax.dot_general(
                rows, q_ref[...].astype(rows.dtype),
                (((1,), (0,)), ((), ())),
                precision=(jax.lax.Precision.DEFAULT
                           if rows.dtype == jnp.bfloat16 else None),
                preferred_element_type=jnp.float32)
            return acc * scale_ref[...]

        y = product(*leaves[:2])
        if len(leaves) == 4:
            # the float32 product first, the rounding after it: the order
            # of `act(g) * u` followed by the cast between two calls
            y = (act(y) * product(*leaves[2:])).astype(out_ref.dtype)
        # rows of the tile's other groups keep what their visit wrote (or
        # will write: what the block holds until then is never read as a
        # number)
        out_ref[...] = jnp.where(mine, y, out_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "interpret", "act"))
def grouped_matmul(
    rows: jnp.ndarray,          # [R, A] activations, sorted by expert
    q,                          # [L, X, A, F] int8, the FULL stack —
    scale,                      # [L, X, F] f32 — or (gate, up) of each
    group_sizes: jnp.ndarray,   # [X] int32, summing to R
    layer: jnp.ndarray,         # scalar int32: which layer's experts
    *,
    act=None,                   # the gated pair's activation
    row_tile: int | None = None,    # None: `geometry`'s
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [R, F] float32; for a (gate, up) pair of stacks
    `act(gate product) * up product`, formed in float32 and rounded once,
    in `rows.dtype`."""
    qs, scales = (q, scale) if isinstance(q, tuple) else ((q,), (scale,))
    R, A = rows.shape
    _, X, _, F = qs[0].shape
    tiling = geometry(R, A, F, rows.dtype.itemsize, interpret=interpret)
    if tiling is None:
        raise ValueError(f"no grouped-matmul geometry for rows [{R}, {A}] "
                         f"against experts of [{A}, {F}]")
    tm, tn = tiling
    if row_tile is not None:
        tm = row_tile
    walk = visits(group_sizes, R, tm)
    n_visits = walk[1].shape[0]
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    # the layer's scales alone (1 MB at most), a row an expert: the stack
    # of them reshaped whole would be a relayout of every layer's a call
    scales = [jax.lax.dynamic_index_in_dim(
        s.astype(jnp.float32), layer[0], 0, keepdims=False
    ).reshape(X, 1, F) for s in scales]

    def at_tile(n, v, lay, offsets, group, tile, count):
        return tile[v], 0

    def at_expert(n, v, lay, offsets, group, tile, count):
        return lay[0], group[v], 0, n

    def at_scale(n, v, lay, offsets, group, tile, count):
        return group[v], 0, n

    def at_out(n, v, lay, offsets, group, tile, count):
        return tile[v], n

    out_dtype = rows.dtype if len(qs) == 2 else jnp.float32
    # rows and out double-buffered, each leaf's int8 tile too, and its
    # widened copy and float32 product
    block = (2 * tm * A * rows.dtype.itemsize
             + 2 * tm * tn * jnp.dtype(out_dtype).itemsize
             + len(qs) * (A * tn * (2 + rows.dtype.itemsize) + tm * tn * 4))
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,  # layer, offsets, group, tile, count
            grid=(F // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, A), at_tile),
                      *[pl.BlockSpec((None, None, A, tn), at_expert),
                        pl.BlockSpec((None, 1, tn), at_scale)] * len(qs)],
            out_specs=pl.BlockSpec((tm, tn), at_out),
        ),
        out_shape=jax.ShapeDtypeStruct((R, F), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 * 2**20, 2 * block)),
        name=NAME,
        interpret=interpret,
    )(layer, *walk, rows, *[a for pair in zip(qs, scales) for a in pair])
