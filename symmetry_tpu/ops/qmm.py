"""W8A16 fused-dequant Pallas matmul (`tpu.fused_dequant`, off by default).

The regime (measured on a shared v5e before the benchmark existed, so no
rate of record): DECODE (M ≈ slot count, ~128 rows) is bandwidth-bound, and
the floor is the int8→bf16 CONVERT, not HBM — XLA's mixed dot
materializes a full bf16 copy of every int8 weight before each dot
(~480 GB/s effective vs the 740-860 a pure bf16 matmul streams).

`w8a16_matmul` keeps the weights int8 in HBM and dequantizes them TILE BY
TILE in VMEM — the pallas_call grid pipeline double-buffers each
weight-tile DMA against the previous tile's MXU work, so the convert
rides inside the DMA/matmul pipeline instead of materializing a full bf16
weight tensor per decode step. Activations stay bf16. Weights are
PRE-PACKED into the kernel's [K/bk, N/bn, bk, bn] tile layout at load
(ops/quant.py pack_quantized) so each grid step's DMA is one contiguous
read. Numerics are the mixed dot's exactly: int8 values are exact in
bf16, products accumulate in f32, the per-output-channel scale is applied
in the epilogue — `(x @ q_bf16) * scale`, cast to the activation dtype.

A W8A8 form (activations quantized per row, s8×s8 → s32 MXU tiles) was
measured and never routed: ~50% slower than the mixed dot in the decode
trunk (48.5 vs 32.1 ms) and no gain at prefill (165.3 vs 167.6 ms a
group), on that same shared chip; the kernel left in PR 30.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from symmetry_tpu.ops.interpret import interpret_mode

# Tile defaults: bn/bk are the DMA granularity AND the effective double-
# buffer depth lever (the pallas grid pipeline keeps the next (bk, bn)
# tile's DMA in flight behind the current tile's MXU work). 512×512 int8
# = 256 KiB per tile, two in flight, well inside VMEM next to the
# activation block and f32 accumulator. tools/chip_kernels.py: the shapes.
W8A16_BLOCK_K = 512
W8A16_BLOCK_N = 512
# Row-block cap: x [bm, bk] + acc [bm, bn] f32 + out [bm, bn] must fit
# VMEM beside the weight tiles. Decode (M = slots ≈ 128) and verify
# (M = slots × (1+k)) fit in one block; wide prefill shapes grid over M.
W8A16_BLOCK_M = 1024
# On-TPU floors: int8 native tiling is (32, 128) — narrower tiles pad in
# VMEM and starve the DMA. Interpret mode (CPU tests) accepts any
# divisor down to 8 so the tiny presets exercise the real kernel.
_TPU_MIN_BK = 32
_TPU_MIN_BN = 128


def pick_w8a16_block(dim: int, prefer: int, floor: int = 8) -> int | None:
    """Largest candidate ≤ prefer (and ≥ floor) that divides dim."""
    for cand in (1024, 512, 256, 128, 64, 32, 16, 8):
        if floor <= cand <= prefer and dim % cand == 0:
            return cand
    return None


def w8a16_supports(k: int, n: int, backend: str) -> bool:
    """Static pack-time gate: True when (k, n) tiles into a layout the
    fused kernel can stream efficiently on `backend`. Untileable leaves
    stay in the flat [K, N] layout and keep the XLA mixed dot."""
    if backend == "tpu":
        bk = pick_w8a16_block(k, W8A16_BLOCK_K, floor=_TPU_MIN_BK)
        bn = pick_w8a16_block(n, W8A16_BLOCK_N, floor=_TPU_MIN_BN)
    else:
        bk = pick_w8a16_block(k, W8A16_BLOCK_K)
        bn = pick_w8a16_block(n, W8A16_BLOCK_N)
    return bk is not None and bn is not None


def _w8a16_kernel(x_ref, w_ref, ws_ref, o_ref, acc_scr, *, n_k: int,
                  out_dtype, apply_scale: bool = True):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    x = x_ref[:]
    # The fused dequant: ONE (bk, bn) int8 tile, freshly DMA'd into VMEM
    # by the grid pipeline, converted to the activation dtype right here
    # — int8 values are exact in bf16, so this is the mixed dot's
    # arithmetic without its full-tensor bf16 materialization. The
    # per-output-channel scale waits for the epilogue (scaling commutes
    # with the K-sum).
    w = w_ref[0, 0].astype(x.dtype)
    acc_scr[:] += jax.lax.dot_general(
        x, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        acc = acc_scr[:]
        if apply_scale:
            acc = acc * ws_ref[:]
        o_ref[:] = acc.astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "apply_scale", "interpret"))
def w8a16_matmul(
    x: jnp.ndarray,        # [M, K] float (bf16/f32)
    w_tiles: jnp.ndarray,  # [K//bk, N//bn, bk, bn] int8 (pack_quantized)
    w_scale: jnp.ndarray,  # [N] f32 per-output-channel
    *,
    out_dtype=None,
    apply_scale: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """x @ dequant(w) with the weight streamed as pre-packed int8 tiles
    and dequantized in VMEM — semantically identical to ops/quant.qmatmul
    on the unpacked QuantizedTensor: (x @ q) accumulated f32, scaled per
    output channel, cast back to the activation dtype.

    apply_scale=False leaves the epilogue scale off (the f32 accumulator
    casts out raw) — the row-parallel sharded path sums the per-shard
    partials FIRST and scales after the reduce, matching the unfused
    GSPMD mixed dot's reduce-then-scale order exactly."""
    M, K = x.shape
    n_kt, n_nt, bk, bn = w_tiles.shape
    assert n_kt * bk == K, (w_tiles.shape, x.shape)
    N = n_nt * bn
    out_dtype = out_dtype or x.dtype
    bm = M if M <= W8A16_BLOCK_M else pick_w8a16_block(M, W8A16_BLOCK_M,
                                                       floor=64)
    if bm is None:
        raise ValueError(f"w8a16 row count {M} untileable past "
                         f"{W8A16_BLOCK_M}")
    ws = w_scale.astype(jnp.float32).reshape(1, N)

    return pl.pallas_call(
        functools.partial(_w8a16_kernel, n_k=n_kt, out_dtype=out_dtype,
                          apply_scale=apply_scale),
        grid=(M // bm, n_nt, n_kt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            # One contiguous packed tile per grid step: this DMA is the
            # weight stream, and the grid pipeline double-buffers it.
            pl.BlockSpec((1, 1, bk, bn), lambda m, n, k: (k, n, 0, 0)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_tiles, ws)


def w8a16_apply(x: jnp.ndarray, w_tiles: jnp.ndarray,
                w_scale: jnp.ndarray, *, out_dtype=None,
                apply_scale: bool = True) -> jnp.ndarray:
    """qmatmul's fused-path entry: any leading batch shape on `x`,
    flattened to rows for the kernel. Falls back to the mixed dot on an
    unpacked view for row counts the kernel can't tile (never an engine
    shape — engine row counts are slot/bucket products)."""
    *lead, K = x.shape
    M = 1
    for d in lead:
        M *= d
    n_kt, n_nt, bk, bn = w_tiles.shape
    N = n_nt * bn
    out_dtype = out_dtype or x.dtype
    if M > W8A16_BLOCK_M and pick_w8a16_block(M, W8A16_BLOCK_M,
                                              floor=64) is None:
        # Mixed dot on an unpacked view, honouring the same out_dtype /
        # apply_scale contract as the kernel path.
        q = jnp.swapaxes(w_tiles, -3, -2).reshape(K, N)
        y = jax.lax.dot_general(
            x, q,
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if apply_scale:
            y = y * w_scale
        return y.astype(out_dtype)
    out = w8a16_matmul(x.reshape(M, K), w_tiles, w_scale,
                       out_dtype=out_dtype, apply_scale=apply_scale,
                       interpret=interpret_mode())
    return out.reshape(*lead, N)


def w8a16_apply_sharded(x: jnp.ndarray, w) -> jnp.ndarray:
    """qmatmul's fused path for a mesh-sharded PackedQuantizedTensor
    (ops/quant.py — the leaf carries mesh + axis names as static aux):
    one shard_map whose body runs the SAME per-shard kernel on the local
    tiles. Column-parallel (n_axis set): every shard holds the full K
    and its N-slice — no collective, the output stays N-sharded, exactly
    where megatron TP wants wq/wk/wv/wg/wu/lm_head outputs. Row-parallel
    (k_axis set): each shard contracts its K-slice with the epilogue
    scale OFF, the f32 partials psum over the axis, and the per-output-
    channel scale applies after the reduce — the identical reduce-then-
    scale order the unfused GSPMD mixed dot lowers to, so fused and
    unfused mesh builds agree token for token.

    Specs are rebuilt from the leaf's static aux at trace time (ndim is
    all that varies — lax.scan strips the layers dim off the arrays but
    not the aux), which is what lets the same leaf serve every trunk
    program (prefill/chunk/decode/verify) with zero extra plumbing."""
    from jax.sharding import PartitionSpec as P

    mesh, k_ax, n_ax = w.mesh, w.k_axis, w.n_axis
    data = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
    # Keep activations batch-sharded through the kernel when they are
    # (trace-time static shapes); otherwise run full rows per shard.
    bspec = ("data" if data > 1 and x.ndim >= 2 and x.shape[0] % data == 0
             else None)
    lead = (None,) * (x.ndim - 2)
    x_spec = P(bspec, *lead, k_ax)
    q_spec = P(*(None,) * (w.q.ndim - 4), k_ax, n_ax, None, None)
    s_spec = P(*(None,) * (w.scale.ndim - 1), n_ax)
    o_spec = P(bspec, *lead, n_ax)

    def body(xl, ql, sl):
        if k_ax is None:
            return w8a16_apply(xl, ql, sl)
        part = w8a16_apply(xl, ql, sl, out_dtype=jnp.float32,
                           apply_scale=False)
        y = jax.lax.psum(part, k_ax)
        return (y * sl).astype(x.dtype)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(x_spec, q_spec, s_spec),
                         out_specs=o_spec, check_vma=False)(x, w.q, w.scale)
