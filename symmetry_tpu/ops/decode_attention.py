"""Pallas ragged decode attention: per-slot length-aware KV block skipping.

The decode step is HBM-bound and the KV cache is its second-largest stream
(after the weights). The XLA einsum path must read the FULL [T] cache
capacity for every slot — masking discards the values but not the traffic —
and slicing the read at the XLA level measured slower than the full read
(it defeats the int8-dequant/matmul fusion; see the round-2 bench log).
This kernel reads only the occupied prefix of each slot's cache:

  - grid = (batch, T/block_t), T innermost; the k/v BlockSpec index_map
    CLAMPS the block index at the slot's last occupied block, so Pallas's
    revisit rule (a block whose index equals the previous iteration's is
    not re-fetched) skips the DMA for every unoccupied tail block. A slot
    at length 600 of an 8192-capacity cache streams 2 × 512-entry blocks,
    not 16 — fully dynamic, zero recompiles, per-slot.
  - The FULL [L, B, T, K, D] cache (native layout — reshaping it outside
    would force a relaid-out copy) is the kernel operand and the layer is
    a scalar-prefetch arg consumed by the index_map: layer selection is
    pure block addressing, never a materialized slice.
  - GQA without a head loop: ALL query heads contract against ALL kv heads
    in ONE [nq, K*block_t] MXU matmul; wrong-pair scores are masked to
    -inf BEFORE the online softmax, so they exp to exactly 0 and the
    output matmul [nq, K*block_t] @ [K*block_t, D] needs no selection —
    the zeros kill every cross-head term. 8x redundant MXU FLOPs, but the
    step is bandwidth-bound and this removes the per-head scalar work
    that otherwise dominates small grids.
  - Online softmax (running max/sum) accumulates in VMEM scratch across
    the T grid dimension; output is written on the final T iteration.
  - int8 caches (ops/quant.py quantize_kv): payload is read at 1 byte and
    dequantized in VMEM — k scales multiply the scores, v scales the
    probabilities, exactly like the XLA fallback (ops/attention.py).

Masking is by absolute position (kv_pos < kv_length), identical semantics
to ops/attention.py gqa_attention at decode (q position == length - 1).

Regime: the kernel wins when capacity is large relative to typical
occupancy (long-context serving — at 32k capacity the full-read einsum is
unserveable); at small capacities the einsum's fusion wins. supports()
encodes the measured crossover.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
DEFAULT_BLOCK_T = 512
# Below this cache capacity the XLA full-read einsum path measured faster
# than the kernel (grid overhead > saved bandwidth at 1-2k capacities).
MIN_CAPACITY = 4096


def _kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, block_t: int,
            n_kv: int, group: int, quantized: bool,
            window: int | None = None,
            ks_ref=None, vs_ref=None):
    del layer_ref  # consumed by the index_maps
    b = pl.program_id(0)
    t = pl.program_id(1)
    length = len_ref[b]
    n_blocks = (length + block_t - 1) // block_t
    # Sliding window: keys below (length - window) are dead — blocks fully
    # below it are skipped (their DMA too, via the index_map clamp; for
    # t < first the fetched block belongs to `first` and must not be
    # processed under this t, hence the compute gate below).
    first = (jnp.maximum(length - window, 0) // block_t
             if window is not None else 0)
    nq, D = q_ref.shape
    KB = n_kv * block_t

    @pl.when(t == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when((t >= first) & (t < n_blocks))
    def _():
        q = q_ref[:].astype(jnp.float32) * scale          # [nq, D]
        # Dequant scales multiply the K/V blocks in 3-D BEFORE flattening
        # (same algebra as scaling scores/probs; Mosaic cannot shape-cast
        # a per-position scale vector onto the flattened score lanes).
        # Scale blocks arrive [K, block_t] (position-minor layout).
        kb = k_ref[:].astype(jnp.float32)                 # [block_t, K, D]
        if quantized:
            kb = kb * ks_ref[:].T[:, :, None]
        # [block_t, K, D] -> [block_t*K, D]: leading-dim merge, layout-free.
        # Flat row j holds (t_in_block = j // K, head = j % K).
        s = jax.lax.dot_general(
            q, kb.reshape(KB, D),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [nq, K*block_t]
        col = jax.lax.broadcasted_iota(jnp.int32, (nq, KB), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (nq, KB), 0)
        kv_pos = t * block_t + col // n_kv
        # own-head (query row h ↔ kv head h // group) AND in-length
        keep = ((col % n_kv) == (row // group)) & (kv_pos < length)
        if window is not None:
            # decode q position == length - 1: window floor is length - w
            keep &= kv_pos >= length - window
        s = jnp.where(keep, s, NEG_INF)

        m_old = m_scr[:, 0:1]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                            # 0 at masked cols
        corr = jnp.exp(m_old - m_new)
        l_scr[:, 0:1] = l_scr[:, 0:1] * corr + jnp.sum(p, -1, keepdims=True)
        m_scr[:, 0:1] = m_new
        vb = v_ref[:].astype(jnp.float32)                 # [block_t, K, D]
        if quantized:
            vb = vb * vs_ref[:].T[:, :, None]
        acc_scr[:, :D] = acc_scr[:, :D] * corr + jax.lax.dot_general(
            p, vb.reshape(KB, D),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        # Empty / fully-masked rows have l == 0: guard the divide (their
        # output is garbage by contract, but must not be NaN).
        o_ref[:] = (acc_scr[:, :D]
                    / jnp.maximum(l_scr[:, 0:1], 1e-30)).astype(o_ref.dtype)


def _quant_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, m_scr, l_scr, acc_scr, **kw):
    _kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, quantized=True,
            ks_ref=ks_ref, vs_ref=vs_ref, **kw)


def supports(config, cache_capacity: int, backend: str) -> bool:
    """Static gate for routing decode attention through the kernel.

    Long-context capacities only: below MIN_CAPACITY the XLA einsum path
    measured as fast or faster (round-3 re-measure with fetch-fenced
    timing: kernel 33.6 vs einsum 32.6 ms full-trunk at 640 — the step
    there is convert-throughput-bound, not KV-traffic-bound, so block
    skipping buys nothing). Sliding-window models route through the
    kernel too: the window bounds the block range per slot (mistral at
    8k capacity / 4k window reads half the blocks)."""
    D = config.dim_per_head
    return (D % 128 == 0
            and backend == "tpu"
            and cache_capacity >= MIN_CAPACITY
            # decode_attention auto-picks a block from (512, 256, 128, 64),
            # so any 64-multiple capacity tiles.
            and cache_capacity % 64 == 0)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "window", "interpret"))
def decode_attention(
    q: jnp.ndarray,           # [B, n_q_heads, D] (single decode position)
    k_cache: jnp.ndarray,     # [L, B, T, K, D] FULL cache (bf16/f32 or int8)
    v_cache: jnp.ndarray,
    layer: jnp.ndarray,       # scalar int32: which layer's cache to read
    kv_length: jnp.ndarray,   # [B] int32 valid entries (incl. current token)
    k_scale: jnp.ndarray | None = None,  # [L, B, K, T] f32 (int8 caches;
    v_scale: jnp.ndarray | None = None,  # position minor — tile-friendly)
    *,
    block_t: int = DEFAULT_BLOCK_T,
    window: int | None = None,  # sliding-window span (mistral); bounds the
                                # per-slot block range below AND above
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [B, n_q_heads, D] in q's dtype."""
    L, B, T, K, D = k_cache.shape
    nq = q.shape[1]
    group = nq // K
    block_t = min(block_t, T)
    if T % block_t:
        # Auto-pick the largest standard block that tiles the capacity
        # (e.g. 640 → 128); callers then never need capacity-aware sizing.
        for cand in (256, 128, 64):
            if cand < block_t and T % cand == 0:
                block_t = cand
                break
        else:
            raise ValueError(f"cache capacity {T} has no usable block size")
    n_t = T // block_t
    scale = D ** -0.5
    quantized = k_scale is not None

    layer_arr = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def clamp_t(b, t, len_ref, layer_ref):
        # Clamp into the live block range for this slot: above the last
        # occupied block, and (windowed models) below the first block the
        # window can still see. Out-of-range iterations repeat a boundary
        # index, so Pallas's revisit rule skips their DMAs; the kernel's
        # compute gate skips their math.
        last = jnp.maximum((len_ref[b] + block_t - 1) // block_t - 1, 0)
        t_eff = jnp.minimum(t, last)
        if window is not None:
            first = jnp.maximum(len_ref[b] - window, 0) // block_t
            t_eff = jnp.maximum(t_eff, first)
        return layer_ref[0], b, t_eff, 0, 0

    q_spec = pl.BlockSpec((None, nq, D), lambda b, t, lr, yr: (b, 0, 0))
    kv_spec = pl.BlockSpec((None, None, block_t, K, D), clamp_t)
    out_spec = pl.BlockSpec((None, nq, D), lambda b, t, lr, yr: (b, 0, 0))
    scratch = [
        pltpu.VMEM((nq, 128), jnp.float32),  # running max (col 0)
        pltpu.VMEM((nq, 128), jnp.float32),  # running denom (col 0)
        pltpu.VMEM((nq, max(D, 128)), jnp.float32),  # output accumulator
    ]
    common = dict(scale=scale, block_t=block_t, n_kv=K, group=group,
                  window=window)

    if quantized:
        def clamp_t_scale(b, t, len_ref, layer_ref):
            lay, bb, tt, _, _ = clamp_t(b, t, len_ref, layer_ref)
            return lay, bb, 0, tt

        sc_spec = pl.BlockSpec((None, None, K, block_t), clamp_t_scale)
        kernel = functools.partial(_quant_kernel, **common)
        in_specs = [q_spec, kv_spec, kv_spec, sc_spec, sc_spec]
        args = (kv_length, layer_arr, q, k_cache, v_cache, k_scale, v_scale)
    else:
        kernel = functools.partial(_kernel, quantized=False, **common)
        in_specs = [q_spec, kv_spec, kv_spec]
        args = (kv_length, layer_arr, q, k_cache, v_cache)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # kv_length, layer
            grid=(B, n_t),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, nq, D), q.dtype),
        interpret=interpret,
    )(*args)


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
