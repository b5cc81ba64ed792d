"""Int8 weight quantization (every benchmark cell serves int8 weights).

Symmetric per-output-channel int8: for w [.., in, out], each output column
gets scale = max|column| / 127, q = round(w / scale). The matmul computes
(x @ q) * scale — exact w.r.t. per-column scaling, and the int8 weight
halves HBM traffic vs bf16, which is the decode bottleneck (weights are
re-read every step).

QuantizedTensor is a pytree, so quantized params stack under lax.scan,
shard with NamedShardings, and donate exactly like dense ones.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from symmetry_tpu.ops.interpret import interpret_mode


class QuantizedTensor(NamedTuple):
    q: jnp.ndarray      # int8, same shape as the dense weight
    scale: jnp.ndarray  # f32, weight shape minus the contraction dim


@jax.tree_util.register_pytree_node_class
class PackedQuantizedTensor:
    """Tile-packed int8 weight for the fused W8A16 dequant matmul
    (ops/qmm.py w8a16_matmul, `tpu.fused_dequant`): the flat [.., K, N]
    int8 payload re-laid-out as [.., K/bk, N/bn, bk, bn] so each kernel
    grid step DMAs ONE contiguous tile from HBM. Same pytree discipline
    as QuantizedTensor — stacks under lax.scan (the leading layers dim
    strips off both leaves together) and donates like a dense leaf. The
    scale stays the flat per-output-channel [.., N].

    Mesh-aware: `k_axis`/`n_axis` name the MESH axes the weight's
    contraction/output dims are sharded over (None = replicated), and
    `mesh` is the Mesh itself. They ride the treedef as static aux data
    — lax.scan strips the stacked layers dim off the arrays while the
    axis names survive untouched, so qmatmul can rebuild per-rank
    PartitionSpecs from ndim at trace time and route the leaf through
    its shard_map'd per-shard kernel (ops/qmm.py w8a16_apply_sharded).
    A leaf packed without a mesh (or with both axes None) keeps the
    plain single-device dispatch."""

    __slots__ = ("q", "scale", "k_axis", "n_axis", "mesh")

    def __init__(self, q, scale, *, k_axis: str | None = None,
                 n_axis: str | None = None, mesh=None):
        self.q = q          # int8 [.., K/bk, N/bn, bk, bn] tile layout
        self.scale = scale  # f32 [.., N] per-output-channel
        self.k_axis = k_axis
        self.n_axis = n_axis
        self.mesh = mesh

    def tree_flatten(self):
        return (self.q, self.scale), (self.k_axis, self.n_axis, self.mesh)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, k_axis=aux[0], n_axis=aux[1], mesh=aux[2])

    def __repr__(self):
        return (f"PackedQuantizedTensor(q={self.q!r}, scale={self.scale!r}, "
                f"k_axis={self.k_axis!r}, n_axis={self.n_axis!r})")


def quantize(w: jnp.ndarray, *, contract_axis: int = -2) -> QuantizedTensor:
    """Quantize a dense weight along its contraction (input) axis."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=contract_axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=jnp.squeeze(scale, axis=contract_axis))


def dequantize(qt: QuantizedTensor, dtype=jnp.float32,
               *, contract_axis: int = -2) -> jnp.ndarray:
    scale = jnp.expand_dims(qt.scale, contract_axis)
    return (qt.q.astype(jnp.float32) * scale).astype(dtype)


def qmatmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for dense arrays, QuantizedTensor, or PackedQuantizedTensor
    ([in, out] contraction).

    QuantizedTensor: a mixed-precision dot with the int8 operand passed
    directly — no `astype` on the weight, so XLA never materializes a
    bf16 copy as a SEPARATE op (for a 128k-vocab head that copy alone is
    >1 GB)... except it does anyway: on v5e the mixed dot's effective
    bandwidth (~480 GB/s) is the int8→bf16 convert's, not HBM's, because
    XLA converts the full weight ahead of the dot. Accumulates f32,
    applies the per-column scales, casts back to the activation dtype.

    PackedQuantizedTensor (`tpu.fused_dequant`): routes through the
    W8A16 Pallas kernel (ops/qmm.py w8a16_matmul) — int8 tiles stream
    from HBM double-buffered and dequantize in VMEM inside the
    DMA/matmul pipeline. Same arithmetic as the mixed dot (int8 exact in
    bf16, f32 accumulation, epilogue scale); the layout IS the routing,
    chosen once at weight load (engine/engine.py packs when the knob is
    on), so this hot-path dispatch stays a type check.

    Measured alternative, not routed: the native s8×s8 MXU kernel
    (ops/qmm.py) is ~50% slower in-trunk at decode-sized M and exactly
    NEUTRAL at prefill-sized M (165.3 vs 167.6 ms per coalesced prefill
    group on-chip, despite winning isolated matmul microbenchmarks —
    prefill is not matmul-bound). Since W8A8 would add activation-quant
    noise for zero measured gain, the mixed dot serves the default path.
    """
    if isinstance(w, PackedQuantizedTensor):
        from symmetry_tpu.ops.qmm import w8a16_apply, w8a16_apply_sharded

        if w.mesh is not None and (w.k_axis or w.n_axis):
            return w8a16_apply_sharded(x, w)
        return w8a16_apply(x, w.q, w.scale)
    if isinstance(w, QuantizedTensor):
        y = jax.lax.dot_general(
            x, w.q,
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (y * w.scale).astype(x.dtype)
    return x @ w


# One shared jitted quantizer: donating the dense original lets XLA reuse
# its buffer; both post-hoc tree quantization and quantized init go through
# this single definition.
quantize_jit = jax.jit(quantize, donate_argnums=(0,))


def quantize_tree(params: dict, keys: tuple[str, ...]) -> dict:
    """Quantize the named leaves of a params dict in place (donating the
    dense originals one at a time to bound peak memory)."""

    def visit(node):
        for name, child in list(node.items()):
            if isinstance(child, dict):
                visit(child)
            elif name in keys:
                node[name] = quantize_jit(child)

    visit(params)
    return params


# ---------------------------------------------------------------------------
# W8A16 tile packing (tpu.fused_dequant): performed ONCE at weight load so
# every decode-step weight DMA is contiguous. Packing is pure layout — the
# int8 payload bytes and the scales are untouched, so a packed tree is
# bit-equivalent to its flat original (unpack_quantized round-trips).


def _pack_body(q: jnp.ndarray, bk: int, bn: int) -> jnp.ndarray:
    *lead, K, N = q.shape
    q = q.reshape(*lead, K // bk, bk, N // bn, bn)
    return jnp.swapaxes(q, -3, -2)


@functools.partial(jax.jit, static_argnames=("bk", "bn"))
def _pack_leaf(q: jnp.ndarray, bk: int, bn: int) -> jnp.ndarray:
    """[.., K, N] int8 → [.., K/bk, N/bn, bk, bn]. The tile transpose is
    a real copy; pack_tree replaces each leaf as it goes, so the flat
    original is freed right after and peak HBM overhead stays one int8
    leaf (~0.5 GB for an 8B lm_head), paid once at load."""
    return _pack_body(q, bk, bn)


def packed_q_spec(ndim: int, k_axis: str | None, n_axis: str | None):
    """PartitionSpec for a packed q of `ndim` dims ([.., K/bk, N/bn, bk,
    bn]): the K-grid dim carries the contraction shard, the N-grid dim
    the output shard, tile dims never shard. Because the per-shard tile
    counts divide (pack_quantized picks bk/bn against PER-SHARD K/N),
    slicing the global packed array along the grid dims IS the pack of
    the flat local shard — shard-wise bit-identical layouts."""
    from jax.sharding import PartitionSpec as P

    return P(*(None,) * (ndim - 4), k_axis, n_axis, None, None)


def packed_scale_spec(ndim: int, n_axis: str | None):
    """PartitionSpec for a packed scale [.., N]: with the output channels."""
    from jax.sharding import PartitionSpec as P

    return P(*(None,) * (ndim - 1), n_axis)


def _axis_size(mesh, axis: str | None) -> int:
    if mesh is None or axis is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def _pack_quantized_report(
    qt: QuantizedTensor, *, bk: int | None = None, bn: int | None = None,
    k_axis: str | None = None, n_axis: str | None = None, mesh=None,
) -> tuple:
    """pack_quantized plus the degrade reason: returns (leaf, reason)
    where reason is None when the leaf packed, else one of
    "untileable" (single-device shape the kernel can't tile),
    "shard_indivisible" (mesh axis doesn't divide K/N at all), or
    "shard_untileable" (per-shard K/N loses tileability)."""
    from symmetry_tpu.ops import qmm

    # A mesh axis of size 1 shards nothing — treat as replicated so the
    # leaf keeps the cheaper single-device dispatch.
    k_axis = k_axis if _axis_size(mesh, k_axis) > 1 else None
    n_axis = n_axis if _axis_size(mesh, n_axis) > 1 else None
    if k_axis is None and n_axis is None:
        mesh = None
    k_parts = _axis_size(mesh, k_axis)
    n_parts = _axis_size(mesh, n_axis)

    *_, K, N = qt.q.shape
    if K % k_parts or N % n_parts:
        return qt, "shard_indivisible"
    K_loc, N_loc = K // k_parts, N // n_parts
    if bk is None and bn is None:
        # Blocks are chosen against the PER-SHARD dims so the tile grid
        # [K/bk, N/bn] divides evenly across the mesh axes — that is
        # what makes the sharded packed layout equal the per-shard pack.
        floor_k = 8 if interpret_mode() else qmm._TPU_MIN_BK
        floor_n = 8 if interpret_mode() else qmm._TPU_MIN_BN
        bk = qmm.pick_w8a16_block(K_loc, qmm.W8A16_BLOCK_K, floor=floor_k)
        bn = qmm.pick_w8a16_block(N_loc, qmm.W8A16_BLOCK_N, floor=floor_n)
        if bk is None or bn is None:
            return qt, ("shard_untileable" if mesh is not None
                        else "untileable")
    elif bk is None or bn is None:
        raise ValueError("pack_quantized tile override needs BOTH bk and "
                         "bn (a partial override would mix a default-"
                         "derived block with the explicit one)")
    elif K_loc % bk or N_loc % bn:
        # Explicit overrides (probe sweeps) fail loudly, not deep inside
        # the jitted reshape — the default path's fallback-to-flat is for
        # load-time packing only.
        raise ValueError(f"tiles ({bk}, {bn}) do not divide weight "
                         f"({K}, {N}) per-shard ({K_loc}, {N_loc})")
    if mesh is None:
        tiles = _pack_leaf(qt.q, bk, bn)
    else:
        # Repack WITH the output placement declared, so the tile copy
        # lands shard-local instead of gathering and re-scattering.
        from jax.sharding import NamedSharding

        spec = packed_q_spec(qt.q.ndim + 2, k_axis, n_axis)
        tiles = jax.jit(
            functools.partial(_pack_body, bk=bk, bn=bn),
            out_shardings=NamedSharding(mesh, spec))(qt.q)
    return PackedQuantizedTensor(q=tiles, scale=qt.scale, k_axis=k_axis,
                                 n_axis=n_axis, mesh=mesh), None


def pack_quantized(qt: QuantizedTensor, *, bk: int | None = None,
                   bn: int | None = None, k_axis: str | None = None,
                   n_axis: str | None = None, mesh=None):
    """Pack one QuantizedTensor into the fused kernel's tile layout, or
    return it unchanged when its shape doesn't tile on this backend (the
    leaf then keeps the XLA mixed dot — per-leaf fallback, no all-or-
    nothing). Explicit bk/bn override the kernel defaults (probe sweeps).

    With `mesh` + `k_axis`/`n_axis` (mesh axis names for the contraction
    and output dims), the pack happens AFTER the sharding decision: tile
    blocks are picked against the per-shard K/N, the repack jit declares
    the packed NamedSharding, and the leaf carries the axis names so
    qmatmul routes it through the shard_map'd per-shard kernel."""
    leaf, _ = _pack_quantized_report(qt, bk=bk, bn=bn, k_axis=k_axis,
                                     n_axis=n_axis, mesh=mesh)
    return leaf


def unpack_quantized(pt: PackedQuantizedTensor) -> QuantizedTensor:
    """Tile layout back to flat [.., K, N] (tests, re-export)."""
    *lead, n_kt, n_nt, bk, bn = pt.q.shape
    q = jnp.swapaxes(pt.q, -3, -2).reshape(*lead, n_kt * bk, n_nt * bn)
    return QuantizedTensor(q=q, scale=pt.scale)


def pack_tree(params: dict, keys: tuple[str, ...], *,
              axes: dict | None = None, mesh=None,
              report: list | None = None) -> dict:
    """Pack the named QuantizedTensor leaves of a params dict in place
    (mirrors quantize_tree). Only 2-D weights and [L, K, N] layer stacks
    pack — MoE expert stacks ([L, E, K, N]) and untileable shapes keep
    the flat layout and the mixed dot.

    `axes` maps leaf name -> (k_mesh_axis, n_mesh_axis) for mesh-aware
    packing (models/llama.py pack_params resolves it from the logical-
    axis tree + sharding rules); `report`, when given, collects
    (path, reason) for every int8 leaf that stayed flat so the caller
    can log and count the degrades instead of silently eating them."""

    def note(path, reason):
        if report is not None:
            report.append((path, reason))

    def visit(node, prefix):
        for name, child in list(node.items()):
            if isinstance(child, dict):
                visit(child, prefix + (name,))
            elif name in keys and isinstance(child, QuantizedTensor):
                path = "/".join(prefix + (name,))
                if child.q.ndim not in (2, 3):
                    # MoE expert stacks [L, E, K, N]: the kernel has no
                    # expert grid dim; the mixed dot serves them.
                    note(path, "expert_stack")
                    continue
                k_ax, n_ax = (axes or {}).get(name, (None, None))
                leaf, reason = _pack_quantized_report(
                    child, k_axis=k_ax, n_axis=n_ax, mesh=mesh)
                node[name] = leaf
                if reason is not None:
                    note(path, reason)

    visit(params, ())
    return params


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token-per-head symmetric int8 for KV cache entries.

    x [..., D] -> (q int8 [..., D], scale f32 [...]): one scale per leading
    index (token × kv-head), amax over the head_dim axis. At decode the
    cache read is the second-largest HBM stream after the weights; int8
    halves it, and the scale array is D× smaller than the payload.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("shape", "scale", "dtype", "quantized"))
def make_leaf(key, shape: tuple[int, ...], scale: float, dtype,
              quantized: bool = False):
    """Random-init one parameter leaf fully inside ONE compiled program:
    normal → scale → cast (→ quantize). Nothing full-precision survives the
    program, so peak memory per leaf is its fused temporaries — which is
    what makes 8B-scale quantized init fit on one chip."""
    w = (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    return quantize(w) if quantized else w


def leaf_is_sliced(shape: tuple[int, ...], dtype, sharding=None,
                   limit_bytes: int | None = None) -> bool:
    """Whether init builds this leaf a leading slice at a time
    (make_leaf_sliced) instead of in one piece (make_leaf): when one
    device's share of the leaf in `dtype` — the full-precision temporary a
    one-piece init holds beside its output — is over `limit_bytes`. The
    two forms draw DIFFERENT random values, so a leaf that fits keeps the
    one-piece form and its values (mistral-7b and qwen2-7b on one chip:
    3.76 / 3.80 GB against a quarter of 16.9 GB). `sharding` is the
    leaf's NamedSharding (of `q` for a quantized leaf) or None."""
    if limit_bytes is None:
        return False
    local = sharding.shard_shape(tuple(shape)) if sharding is not None \
        else shape
    return math.prod(local) * jnp.dtype(dtype).itemsize > limit_bytes


def default_leaf_limit() -> int | None:
    """A quarter of the first device's memory, or None where the backend
    reports none (the CPU): then nothing is sliced."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return limit // 4 if limit else None


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype",
                                             "quantized", "sharding"))
def make_leaf_sliced(key, shape: tuple[int, ...], scale: float, dtype,
                     quantized: bool = False, sharding=None):
    """make_leaf for a stacked leaf too large to hold in full precision:
    one slice of the leading (layers) axis at a time under `lax.map`, each
    from its own key, so the full-precision temporary is one slice — what
    lets mixtral-8x7b's [32, 8, 4096, 14336] expert stacks (7.5 GB a chip
    in bf16 under `model: 4`) be made beside 11.7 GB of int8 weights.
    `sharding` (NamedSharding, or a QuantizedTensor of them) is the
    stack's placement; each slice is constrained to it minus the leading
    axis, so the temporary is sharded like the output."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(k):
        w = (jax.random.normal(k, shape[1:], jnp.float32) * scale
             ).astype(dtype)
        out = quantize(w) if quantized else w
        if sharding is None:
            return out
        return jax.lax.with_sharding_constraint(
            out, jax.tree.map(
                lambda s: NamedSharding(s.mesh, P(*s.spec[1:])), sharding))

    return jax.lax.map(one, jax.random.split(key, shape[0]))
