"""Pallas decode steps of the recurrent layers (Mamba-2: `ssm_step`; Gated
DeltaNet: `gdn_step`): one pass over the recurrent state.

The single-position recurrence of a mamba layer (models/mamba2.py step),
float32 throughout:

    y[b,h,p]   = a[b,h] * sum_n S[b,h,p,n] * C[b,n]
                 + dx[b,h,p] * (B[b,.] . C[b,.]) + skip[b,h,p]
    S[b,h,p,n] = a[b,h] * S[b,h,p,n] + dx[b,h,p] * B[b,n]

The state is nearly all of the bytes (4 MB a slot a layer at granite's
128 x 64 x 128 against 100 KB of everything else), and the step is bound by
reading and writing it. XLA compiles the two lines as two fusions — a
reduction that reads the layer's state for `S C`, then an elementwise pass
that reads it again and writes it — so the state crosses HBM three times a
layer. Here each tile of `S` is copied into VMEM once, both lines are
computed from that copy, and the new tile goes back to where it came from:

  - The WHOLE stack [L, B, H, P, N] is the kernel's operand, aliased input
    to output, with the layer a scalar-prefetch argument: layer, slot and
    head tile are DMA addressing (ops/decode_attention.py's lesson: a
    per-layer slice around a kernel is a 537 MB copy each way). Blocks the
    grid does not visit are untouched: only layer `layer` changes.
  - The grid is (B, H / head_tile): one step moves `head_tile` heads of one
    slot, [head_tile, P, N], in and out through Pallas's double buffers.
    Every slot steps, idle lanes included: the work is the state of all
    slots read once and written once (benchmarks/lib/hybrid_bytes.py).
  - N lies on lanes, so B and C are lane vectors (a stride-0 sublane
    broadcast), the decay of a head is a scalar (read from SMEM, a free
    splat) and what is per (h, p) — dx, the reduced `S C` — is a COLUMN.
    Those come in as [B, P, H] (P on sublanes, heads on lanes, padded to
    a lane tile): head h's dx is lane h of eight vregs, broadcast along
    lanes for the update, and the lane reduction's result is selected
    into lane h of the output. A group of heads is brought to lanes 0..
    by one dynamic lane rotation, so every slice inside the unrolled
    group is static.
  - All arithmetic is float32 on the VPU, the reduction over N on the XLU:
    no product takes a bf16 pass. The MXU stays idle — its f32-exact forms
    (three-term splits) cost more passes than the DMA leaves time for.
  - What it costs (tools/ssm_step_ab.py on a v5e, ms a layer of 537 MB;
    PERF.md, PR 34): a bare copy through the same pipeline 1.67 at any
    tile — the chip's rate for a read and a write at once, 640 GB/s — and
    the kernel 1.68 at 64 heads a step; with the decay as a third lane
    broadcast it was 1.79 (the XLU, not the DMA, set the pace), at 16
    heads a step 1.90 (1,024 grid steps a layer).

`gdn_step` is the same pass for the gated delta rule of a Gated DeltaNet
layer (models/gdn.py recurrence), whose state is a [Dk, Dv] MATRIX a value
head (qwen3-next: 128 slots x 32 heads x 128 x 128, 268 MB a layer, which
XLA's two fusions a layer cross three times):

    r_k = S^T k,  r_q = S^T q          both read-outs of the OLD state
    d   = beta * (v - a * r_k)
    o   = a * r_q + (k . q) * d
    S   = a * S + k (outer) d

  - It shares the stack's addressing (`over_stack`: the whole [L, B, H, Dk,
    Dv] stack aliased through the call, the layer a scalar-prefetch
    argument, grid (B, H / head_tile)) and `head_tile`'s byte budget: 32
    heads x 64 KB, a whole slot a grid step at qwen3-next's shape.
  - Here the update DEPENDS on a reduction over the whole tile (`d` needs
    `r_k`): a head's 16 vregs are reduced first and updated second, from
    the same VMEM copy. Dv lies on lanes, so v, d, o are lane rows and the
    reductions over Dk are sublane-direction adds on the VPU; k and q are
    COLUMNS, [B, H / tile, Dk, tile] with a head a lane (two lane broadcasts
    a vreg of state, and no lane reduction); a, beta and k . q are scalars
    from SMEM. Every slice is static: the heads of a tile are unrolled.
  - What it costs (tools/ssm_step_ab.py --kind gdn on a v5e, ms a layer of
    268 MB; PERF.md, PR 45): XLA's form 1.233, a bare copy through the same
    pipeline 0.841 (638 GB/s), the kernel 0.884 at 16 or 32 heads a step
    (608 GB/s, 5% over the copy) and 0.911 at 8. The columns loaded once a
    grid step, or a key head's two broadcasts shared by the two value heads
    it serves (half the lane broadcasts), read the same within the noise of
    two calls (0.871 / 0.868 beside 0.862–0.873): the XLU does not set the
    pace here.

`head_tile` is the one shape gate: None where a kernel has no geometry (the
caller keeps the jnp recurrence there and says so: `step_form`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
GROUP = 16             # heads unrolled between two lane rotations
TILE_BYTES = 2**21     # of state a grid step moves each way
NAME = "ssm_step"      # the ops' names in a device trace
GDN_NAME = "gdn_step"


def head_tile(n_heads: int, d_head: int, d_state: int, itemsize: int = 4,
              *, interpret: bool = False, groups: int = 1) -> int | None:
    """Heads of one slot a grid step moves at this state shape, or None
    where the kernel has none: a [P, N] plane of a head has to be whole
    (8, 128) tiles for Mosaic (any shape interprets). The largest divisor
    of the head count whose tile is at most TILE_BYTES — and, where the
    heads read `groups` groups of B and C, is whole groups or lies inside
    one (at one group every divisor does)."""
    if not interpret and (d_state % LANES or d_head % SUBLANES):
        return None
    most = max(1, TILE_BYTES // (d_head * d_state * itemsize))
    per = n_heads // groups
    return next(t for t in range(min(n_heads, most), 0, -1)
                if n_heads % t == 0 and (t % per == 0 or per % t == 0))


def step_form(n_heads: int, d_head: int, d_state: int, itemsize: int, *,
              interpret: bool, otherwise: str, groups: int = 1) -> dict:
    """What a recurrent kind's `step_form` reports and its `step_at` routes
    by: "pallas" with the `head_tile` ("pallas-interpret": the same kernel
    on the CPU backend) and, where there is more than one, the `groups` of
    B and C the heads read, or `otherwise` — the kind's name for its jnp
    recurrence — where the kernel has no geometry for the state."""
    tile = head_tile(n_heads, d_head, d_state, itemsize, interpret=interpret,
                     groups=groups)
    if tile is None:
        return {"form": otherwise}
    return {"form": "pallas-interpret" if interpret else "pallas",
            "head_tile": tile, **({"groups": groups} if groups > 1 else {})}


def _kernel(layer_ref, b_ref, c_ref, dx_ref, skip_ref, a_ref, s_ref,
            y_ref, s_out_ref, *, group: int, per: int | None = None):
    """`per` (None: one group of B and C, every head reads row 0): heads a
    group of B and C serves — head h reads row h // per of the [G, N]
    blocks. The unrolled `group` of heads is whole groups or inside one
    (`ssm_step`), so the row of head h0 + j is h0 // per + j // per, the
    second term static."""
    del layer_ref                                   # addressing only
    tile, P, N = s_ref.shape[2:]
    lanes = dx_ref.shape[-1]
    first = pl.program_id(1) * tile                 # this step's first head
    whole = (b_ref[0], c_ref[0]) if per is None else None   # [1, N] each
    dx_all = dx_ref[0]                              # [P, lanes]
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, lanes), 1)

    def heads(g, sc):
        h0 = first + g * group
        # bring heads h0 .. h0 + group - 1 to lanes 0 .. group - 1
        dx_g = pltpu.roll(dx_all, (lanes - h0) % lanes, 1)
        b_row, c_row = whole or (None, None)
        for j in range(group):
            at = g * group + j
            a = a_ref[0, 0, h0 + j]
            if per is not None and j % per == 0:    # a new group's rows
                row = pl.ds(h0 // per + j // per, 1)
                b_row, c_row = b_ref[0, row, :], c_ref[0, row, :]
            s = s_ref[0, 0, at].astype(jnp.float32)             # [P, N]
            read = jnp.sum(s * c_row, axis=-1, keepdims=True)   # [P, 1]
            s_out_ref[0, 0, at] = (
                a * s + dx_g[:, j:j + 1] * b_row).astype(s_out_ref.dtype)
            sc = jnp.where(lane == h0 + j, a * read, sc)
        return sc

    sc = jax.lax.fori_loop(0, tile // group, heads,
                           jnp.zeros((P, lanes), jnp.float32))
    mine = (lane >= first) & (lane < first + tile)
    # the output block stays in VMEM across a slot's head tiles: each
    # writes its own lanes (what the others' lanes hold until then is
    # never read as a number)
    y_ref[0] = jnp.where(mine, sc + skip_ref[0], y_ref[0])


def _tile(stack, tile, interpret: bool, what: str) -> int:
    """The head tile a call takes: the caller's, or `head_tile`'s."""
    H, P, N = stack.shape[2:]
    if tile is None:
        tile = head_tile(H, P, N, stack.dtype.itemsize, interpret=interpret)
    if tile is None or H % tile:
        raise ValueError(f"no {what} geometry for a state of {H} heads "
                         f"of {P} x {N} (tile {tile})")
    return tile


def address(layer) -> jnp.ndarray:
    """The layer as the scalar-prefetch operand."""
    return jnp.reshape(layer, (1,)).astype(jnp.int32)


def over_stack(kernel, name: str, stack, layer, tile: int, in_specs,
               operands, out_spec, out_shape, *, interpret: bool):
    """`kernel` over layer `layer` of the WHOLE stack [L, B, H, P, N], a
    [tile, P, N] block of one slot a grid step, aliased input to output:
    the stack is the last operand and the last result, the layer (`address`
    of it) the one scalar-prefetch argument of every index map. Returns
    (the kernel's other result, the stack)."""
    B, H, P, N = stack.shape[1:]
    state = pl.BlockSpec((1, 1, tile, P, N),
                         lambda i, t, lay: (lay[0], i, t, 0, 0))
    block = 2 * tile * P * N * 4    # a tile in and a tile out, as float32
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # layer
            grid=(B, H // tile),
            in_specs=[*in_specs, state],
            out_specs=[out_spec, state],
        ),
        out_shape=[out_shape, jax.ShapeDtypeStruct(stack.shape, stack.dtype)],
        # the stack, counted with `layer`
        input_output_aliases={len(in_specs) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # double buffers of the tile each way, and room for the rest
            vmem_limit_bytes=max(32 * 2**20, 3 * block)),
        name=name,
        interpret=interpret,
    )(layer, *operands, stack)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def ssm_step(
    ssm: jnp.ndarray,       # [L, B, H, P, N] the FULL stack
    layer: jnp.ndarray,     # scalar int32: which layer's state steps
    a: jnp.ndarray,         # [B, H] f32 decay of this position
    dx: jnp.ndarray,        # [B, H, P] f32 dt * x
    b: jnp.ndarray,         # [B, N] f32, or [B, G, N]: a row a group
    c: jnp.ndarray,         # [B, N] f32, or [B, G, N]
    skip: jnp.ndarray,      # [B, H, P] f32 D * x
    *,
    tile: int | None = None,    # heads a grid step (None: `head_tile`)
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [B, H, P] f32, the stack with layer `layer` stepped).
    With b and c [B, G, N], head h reads row h // (H / G) of each."""
    if b.ndim == 3:
        return _ssm_step_grouped(ssm, layer, a, dx, b, c, skip, tile,
                                 interpret)
    L, B, H, P, N = ssm.shape
    tile = _tile(ssm, tile, interpret, "ssm-step")
    group = next(g for g in range(min(GROUP, tile), 0, -1) if tile % g == 0)
    lanes = -(-H // LANES) * LANES

    def columns(v):     # [B, H, P] -> [B, P, lanes]: heads on lanes
        return jnp.pad(jnp.swapaxes(v.astype(jnp.float32), 1, 2),
                       ((0, 0), (0, 0), (0, lanes - H)))

    skip = skip + dx * jnp.sum(b * c, axis=-1)[:, None, None]
    row = pl.BlockSpec((1, 1, N), lambda i, t, lay: (i, 0, 0))
    col = pl.BlockSpec((1, P, lanes), lambda i, t, lay: (i, 0, 0))
    decay = pl.BlockSpec((1, 1, H), lambda i, t, lay: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    y, ssm = over_stack(
        functools.partial(_kernel, group=group), NAME, ssm, address(layer),
        tile, [row, row, col, col, decay],
        (b[:, None].astype(jnp.float32), c[:, None].astype(jnp.float32),
         columns(dx), columns(skip), a[:, None].astype(jnp.float32)),
        col, jax.ShapeDtypeStruct((B, P, lanes), jnp.float32),
        interpret=interpret)
    return jnp.swapaxes(y[:, :, :H], 1, 2), ssm


def _columns(v, lanes: int):
    """[B, H, P] -> [B, P, lanes] float32: heads on lanes."""
    return jnp.pad(jnp.swapaxes(v.astype(jnp.float32), 1, 2),
                   ((0, 0), (0, 0), (0, lanes - v.shape[1])))


def _ssm_step_grouped(ssm, layer, a, dx, b, c, skip, tile, interpret):
    """`ssm_step` with G rows of B and C a slot: the same pass and the same
    addressing, the [G, N] blocks of a slot resident beside its columns; a
    head tile and the unrolled group of heads are each whole groups of
    H / G heads or inside one (at 64 heads in 8 groups: a tile of 64, 16
    heads unrolled over two groups)."""
    L, B, H, P, N = ssm.shape
    G = b.shape[1]
    per = H // G
    if tile is None:
        tile = head_tile(H, P, N, ssm.dtype.itemsize, interpret=interpret,
                         groups=G)
    if tile is None or H % tile or (tile % per and per % tile):
        raise ValueError(f"no ssm-step geometry for a state of {H} heads "
                         f"of {P} x {N} in {G} groups (tile {tile})")
    group = next(g for g in range(min(GROUP, tile), 0, -1)
                 if tile % g == 0 and (g % per == 0 or per % g == 0))
    lanes = -(-H // LANES) * LANES

    bc = jnp.repeat(jnp.sum(b * c, axis=-1), per, axis=1)       # [B, H]
    skip = skip + dx * bc[:, :, None]
    rows = pl.BlockSpec((1, G, N), lambda i, t, lay: (i, 0, 0))
    col = pl.BlockSpec((1, P, lanes), lambda i, t, lay: (i, 0, 0))
    decay = pl.BlockSpec((1, 1, H), lambda i, t, lay: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    y, ssm = over_stack(
        functools.partial(_kernel, group=group, per=per), NAME, ssm,
        address(layer), tile, [rows, rows, col, col, decay],
        (b.astype(jnp.float32), c.astype(jnp.float32),
         _columns(dx, lanes), _columns(skip, lanes),
         a[:, None].astype(jnp.float32)),
        col, jax.ShapeDtypeStruct((B, P, lanes), jnp.float32),
        interpret=interpret)
    return jnp.swapaxes(y[:, :, :H], 1, 2), ssm


def _gdn_kernel(layer_ref, k_ref, q_ref, v_ref, w_ref, s_ref,
                o_ref, s_out_ref):
    del layer_ref                                   # addressing only
    tile = s_ref.shape[2]
    first = pl.program_id(1) * tile                 # this step's first head
    for j in range(tile):
        a, beta, kq = (w_ref[0, n, first + j] for n in range(3))
        s = s_ref[0, 0, j].astype(jnp.float32)                  # [Dk, Dv]
        k = k_ref[0, 0, :, j:j + 1]                             # [Dk, 1]
        # both read-outs of the OLD state, from the one copy of the tile
        read_k = jnp.sum(s * k, axis=0, keepdims=True)          # [1, Dv]
        read_q = jnp.sum(s * q_ref[0, 0, :, j:j + 1], axis=0, keepdims=True)
        d = beta * (v_ref[0, 0, j:j + 1, :] - a * read_k)
        o_ref[0, 0, j:j + 1, :] = a * read_q + kq * d
        s_out_ref[0, 0, j] = (a * s + k * d).astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def gdn_step(
    state: jnp.ndarray,     # [L, B, H, Dk, Dv] the FULL stack
    layer: jnp.ndarray,     # scalar int32: which layer's state steps
    a: jnp.ndarray,         # [B, H] f32 decay of this position
    beta: jnp.ndarray,      # [B, H] f32 write strength
    q: jnp.ndarray,         # [B, H, Dk] f32
    k: jnp.ndarray,         # [B, H, Dk] f32
    v: jnp.ndarray,         # [B, H, Dv] f32
    *,
    tile: int | None = None,    # heads a grid step (None: `head_tile`)
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One position of the gated delta rule (models/gdn.py recurrence):
    returns (o [B, H, Dv] f32, the stack with layer `layer` stepped)."""
    L, B, H, Dk, Dv = state.shape
    tile = _tile(state, tile, interpret, "gdn-step")
    tiles = H // tile

    def columns(x):     # [B, H, Dk] -> [B, tiles, Dk, tile]: a head a lane
        return jnp.swapaxes(
            x.astype(jnp.float32).reshape(B, tiles, tile, Dk), 2, 3)

    # what is one number a head: the decay, the write strength, k . q
    w = jnp.stack([a, beta, jnp.sum(k * q, axis=-1)],
                  axis=1).astype(jnp.float32)                   # [B, 3, H]
    col = pl.BlockSpec((1, 1, Dk, tile), lambda i, t, lay: (i, t, 0, 0))
    row = pl.BlockSpec((1, 1, tile, Dv), lambda i, t, lay: (i, t, 0, 0))
    scalars = pl.BlockSpec((1, 3, H), lambda i, t, lay: (i, 0, 0),
                           memory_space=pltpu.SMEM)
    o, state = over_stack(
        _gdn_kernel, GDN_NAME, state, address(layer), tile,
        [col, col, row, scalars],
        (columns(k), columns(q),
         v.astype(jnp.float32).reshape(B, tiles, tile, Dv), w),
        row, jax.ShapeDtypeStruct((B, tiles, tile, Dv), jnp.float32),
        interpret=interpret)
    return o.reshape(B, H, Dv), state
