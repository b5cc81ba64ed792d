"""Ring attention: causal attention with K/V sharded over the `context`
mesh axis (long-context prefill, SURVEY §5.7 — net-new vs the reference,
which had no attention code at all).

Each device holds a sequence shard of Q/K/V. K/V shards rotate around the
ring via `jax.lax.ppermute` (XLA lowers neighbor permutes to ICI
send/recv), and every device folds each visiting K/V block into its local
queries with the same online-softmax (running max / running sum) merge the
flash kernel uses — so the full [S, S] score matrix never exists anywhere
and sequence length scales with the number of devices in the ring.

Causality note: with Q block-sharded, later ring steps are partially or
fully masked for low-index devices (they hold early queries). The rotation
still runs all n steps — static schedule, no data-dependent control flow —
matching how production ring/blockwise implementations behave under jit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from symmetry_tpu.ops.attention import NEG_INF


def _partial_attention(q, k, v, q_pos, kv_pos, seq_lens, m, l, acc):
    """Fold one K/V block into the running (m, l, acc) online softmax.

    Grouped GQA shapes throughout: q [B, Sq, H, D]; k/v [B, Sk, K, D];
    q_pos [B, Sq]; kv_pos [Sk]; seq_lens [B];
    m/l [B, K, G, Sq, 1]; acc [B, K, G, Sq, D] (H = K * G).
    """
    B, Sq, H, D = q.shape
    K, Sk = k.shape[2], k.shape[1]
    group = H // K
    scale = D ** -0.5

    qg = q.reshape(B, Sq, K, group, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32) * scale  # [B,K,G,Sq,Sk]

    mask = (kv_pos[None, None, :] <= q_pos[:, :, None]) & (
        kv_pos[None, None, :] < seq_lens[:, None, None])        # [B,Sq,Sk]
    s = jnp.where(mask[:, None, None], s, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v.dtype), v,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr + pv
    return m_new, l_new, acc_new


def _ring_shard_fn(q, k, v, seq_lens, *, axis: str, shard_len: int,
                   n_shards: int):
    """Per-shard body under shard_map. q/k/v [B, Sc, H|K, D] local shards."""
    my = jax.lax.axis_index(axis)
    B, Sc, H, D = q.shape
    K = k.shape[2]
    group = H // K

    q_pos = my * shard_len + jnp.arange(Sc, dtype=jnp.int32)[None, :]
    q_pos = jnp.broadcast_to(q_pos, (B, Sc))

    m = jnp.full((B, K, group, Sc, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, K, group, Sc, 1), jnp.float32)
    acc = jnp.zeros((B, K, group, Sc, D), jnp.float32)

    k_cur, v_cur = k, v
    for step in range(n_shards):
        src = (my - step) % n_shards  # whose K/V block we hold this step
        kv_pos = src * shard_len + jnp.arange(Sc, dtype=jnp.int32)
        m, l, acc = _partial_attention(q, k_cur, v_cur, q_pos, kv_pos,
                                       seq_lens, m, l, acc)
        if step < n_shards - 1:
            perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)

    l = jnp.maximum(l, 1e-30)  # fully-masked padded rows
    out = (acc / l).astype(q.dtype)                 # [B, K, G, Sc, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sc, H, D)


def ring_attention(
    q: jnp.ndarray,         # [B, S, H, D], S sharded over `axis`
    k: jnp.ndarray,         # [B, S, K, D]
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,  # [B] valid lengths (replicated)
    mesh,
    axis: str = "context",
) -> jnp.ndarray:
    """Causal ring attention over the context mesh axis. Returns [B,S,H,D]."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    B, S, H, D = q.shape
    if S % n:
        raise ValueError(f"sequence {S} not divisible by ring size {n}")
    shard_len = S // n

    fn = functools.partial(_ring_shard_fn, axis=axis, shard_len=shard_len,
                           n_shards=n)
    spec = P(None, axis, None, None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=spec,
    )(q, k, v, seq_lens)
