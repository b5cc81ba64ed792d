"""Pallas per-step append of an int8 KV cache's scale planes.

A decode step of the homogeneous trunk writes the cache once, behind the
layer scan (models/llama.py append_step): every layer's row of slot b at the
slot's position. For the K/V payload that is one XLA scatter a leaf, its
window the [L, 1, 1, K, D] rows of a slot, in place as the leaf lies. The
scale planes [L, B, K, T] have no such scatter: positions are their MINOR
dim (ops/decode_attention.py copies [K, block] blocks out of them), a window
of [L, 1, K, 1] is one lane of L tiles, and the v5e compiler answers it — as
a scatter, a gather + scatter of the tile column, or a loop of
dynamic-update-slices alike — by relaying the whole plane layer-minor
around the write and back for the kernel (two copies of 84 MB a plane a
step at mistral-7b's cell). The forms it takes in place are a scatter of
L x B windows of [K] (~73 ns an update: 0.6 ms a step for two planes,
PERF.md PR 66) and a select over the whole plane (0.4 ms).

So the planes' append is this kernel, once a step for all of them: a grid
step a slot, whose block is the [L, K, 128] tile column that holds the
slot's position — found by the scalar-prefetched positions in the block's
index map, so the pipeline copies it in and back out — and whose body
selects the new scales into the one lane. The planes are aliased to the
outputs: every other block stays where it lies. The call sits behind the
layer scan, on the decode block's carry; nothing inside the layer loop is
aliased (the kernel PR 30 removed was, and cost a second cache there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
NAME = "scale_append"


def _kernel(pos_ref, *refs, n: int, capacity: int):
    cols, planes, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    L, K, _ = planes[0].shape
    p = pos_ref[pl.program_id(0)]
    # the lane of the block that is the position; past the capacity the
    # block is the last one and no lane is it: the write is dropped
    at = p - jnp.minimum(p // LANES, capacity // LANES - 1) * LANES
    hit = jax.lax.broadcasted_iota(jnp.int32, (K, LANES), 1) == at
    for col, plane, out in zip(cols, planes, outs):
        new = col[...]                                    # [K, L]
        for l in range(L):
            scale = new[:, l:l + 1]   # layer l's scales, a column a head
            out[l] = jnp.where(hit, scale, plane[l])


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_scales(planes: tuple, scales: tuple, positions: jnp.ndarray, *,
                  interpret: bool = False) -> tuple:
    """`planes` (each [L, B, K, T] float32, T a multiple of 128) with
    `scales` (each [L, B, K]) at [:, b, :, positions[b]]; a position at or
    past T is dropped. Donated planes are updated in place."""
    n = len(planes)
    L, B, K, T = planes[0].shape
    if T % LANES:
        raise ValueError(f"no tile column in a plane of {T} positions")

    def column(b, pos):
        return 0, b, 0, jnp.minimum(pos[b] // LANES, T // LANES - 1)

    plane_spec = pl.BlockSpec((L, None, K, LANES), column)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, capacity=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # positions
            grid=(B,),
            in_specs=[pl.BlockSpec((None, K, L), lambda b, pos: (b, 0, 0))
                      ] * n + [plane_spec] * n,
            out_specs=[plane_spec] * n,
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in planes],
        # (counted with the positions)
        input_output_aliases={1 + n + i: i for i in range(n)},
        interpret=interpret,
        name=NAME,
    )(positions.astype(jnp.int32),
      # a slot's scales with the layers in lanes: [B, K, L]
      *(jnp.transpose(s, (1, 2, 0)).astype(jnp.float32) for s in scales),
      *planes)
    return tuple(out)
