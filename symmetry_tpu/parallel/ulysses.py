"""Ulysses-style sequence parallelism: all-to-all head scatter.

The second long-context scheme of SURVEY §5.7 ("Ulysses-style head-scatter
as the alternative when head_count >= shard count"), complementing ring
attention (parallel/ring.py):

  ring:    K/V blocks rotate through every device (n ppermute steps);
           works for any head count, communication spread over the ring.
  ulysses: ONE all-to-all re-shards the data from sequence-sharded to
           head-sharded, every device runs plain full-sequence attention
           on its head subset, and a second all-to-all restores sequence
           sharding. Two collectives total, but requires
           num_kv_heads % shard_count == 0.

Correctness of the head split under GQA: heads are laid out k-major
(h = kv_head * group + g), so a contiguous split of the H axis into n
chunks is exactly a contiguous split of the KV-head axis — each device
gets (K/n) kv heads together with all their query heads, and the local
attention's h // group mapping is unchanged.

The local attention reuses ops/attention.py gqa_attention (absolute-
position causal masking, ragged seq_lens); on TPU the flash kernel could
drop in for the local step — the sharding transformation is the point of
this module and is attention-implementation-agnostic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from symmetry_tpu.ops.attention import gqa_attention


def _ulysses_shard_fn(q, k, v, seq_lens, *, axis: str):
    """Per-shard body under shard_map.

    Local shapes in: q [B, Sc, H, D], k/v [B, Sc, K, D] (sequence-sharded).
    """
    B, Sc, H, D = q.shape

    def seq_to_heads(x):
        # [B, Sc, heads, D] -> [B, Sc * n, heads / n, D]: split the head
        # axis across devices, gather the full sequence in exchange.
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    q_full = seq_to_heads(q)   # [B, S, H/n, D]
    k_full = seq_to_heads(k)   # [B, S, K/n, D]
    v_full = seq_to_heads(v)

    S = q_full.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    out = gqa_attention(q_full, k_full, v_full, positions, seq_lens)
    return heads_to_seq(out)   # [B, Sc, H, D]


def ulysses_attention(
    q: jnp.ndarray,         # [B, S, H, D], S sharded over `axis`
    k: jnp.ndarray,         # [B, S, K, D]
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,  # [B] valid lengths (replicated)
    mesh,
    axis: str = "context",
) -> jnp.ndarray:
    """Causal attention with sequence parallelism via head scatter.

    Returns [B, S, H, D], sequence-sharded like the inputs. Requires
    num_kv_heads (and so num_heads) divisible by the shard count and
    S divisible by it as well.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    B, S, H, D = q.shape
    K = k.shape[2]
    if S % n:
        raise ValueError(f"sequence {S} not divisible by shard count {n}")
    if K % n or H % n:
        raise ValueError(
            f"ulysses needs heads divisible by shards: H={H}, K={K}, n={n} "
            f"(use ring attention otherwise)")

    fn = functools.partial(_ulysses_shard_fn, axis=axis)
    spec = P(None, axis, None, None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=spec,
    )(q, k, v, seq_lens)
