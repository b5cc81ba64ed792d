"""Mixture-of-experts FFN (mixtral family): two lossless forms, chosen by
token count.

The block is HF `MixtralSparseMoeBlock`: router logits in float32, top-k
experts per token, softmax over the k selected logits, and

    y = sum_e gate_e * wd_e(silu(wg_e x) * wu_e x)      over the k selected.

Neither form drops a (token, expert) pair, and there is no capacity:

- ROUTED (`_routed_ffn`; programs of `ROUTED_MIN_TOKENS` tokens or more —
  the larger prefill dispatches). The T*k pairs are sorted by expert, so
  each expert's rows are one contiguous group; the three expert matmuls run
  over the groups as `jax.lax.ragged_dot` (XLA:TPU's native grouped matmul:
  T*k rows whatever the routing — static shapes, FLOPs of k experts and not
  of all of them); the rows are un-sorted by a gather and combined with the
  gates in float32.
- DENSE MIXTURE (`_dense_mixture`; decode and the smaller prefills). Every
  expert computes every token as one batched matmul and the gates, zero
  outside the top k, weight the combine: X/k times the FLOPs, the same
  weight bytes. A decode step of B slots x k pairs hits every expert anyway,
  so all expert weights stream from HBM either way.

Which form where is a measurement, not a taste (`tools/moe_decode_ab.py`;
PERF.md, PR 28; one chip's share of mixtral-8x7b under `model: 4`, ms a
layer, routed / dense): 64 tokens 1.35 / 0.49 (the weight stream's floor is
0.43), 256 tokens 2.85 / 1.04, 768 tokens 3.69 / 3.24, 1,024 tokens 4.00 /
3.93, 1,280 tokens 4.42 / 4.91, 2,048 tokens 5.98 / 7.93, 4,096 tokens 10.7
/ 15.8. The mixed int8 dot runs the dense form at ~90% of the MXU peak,
`ragged_dot` with an int8 operand reaches ~35%, so routing pays only once
it saves more than it wastes: they cross at about 1,050 tokens.
`moe_route(T, experts, k)` is that choice, from the shape alone (each routing
shape has its own measured crossing: 72 experts top 10 never route below
2,560 tokens; 512 experts top 10 route from 1,024);
`startup.moe` reports it per program. A config with
`shared_intermediate_size` adds a shared expert (`sg`, `su`, `sd`: one dense
gated FFN every token passes through) to the routed sum, weighted by
sigmoid(x . sgate) where the layer has that column (qwen3_next).

qwen3_next's router (HF `Qwen3NextSparseMoeBlock`, `norm_topk_prob` true)
takes the softmax over ALL the router logits, keeps the k largest and
renormalises them to sum 1. That IS `route_top_k`'s softmax over the k
selected logits — e^{l_i} / sum_{j in top} e^{l_j} either way: the full
softmax's denominator cancels, and the k largest probabilities are the k
largest logits — so the router needs no second form
(`tests/test_gdn.py` holds the two to each other).

int8 expert stacks stay int8 in HBM in both forms: the int8 payload is the
dot's operand, the per-(expert, column) scale is applied to the float32
accumulator (row by row in the routed form: each row knows its expert).

Sharding (`tp_mesh` with `model` > 1): every expert's FFN width is split
over `model` (all experts on every chip) and the FFN runs per shard inside
one `shard_map` — each chip routes identically (the router and the
activations are replicated over `model`), computes its slice of every
expert, combines, and one `psum` over `model` adds the partial [T, D] rows:
the dense-TP collective pattern, no all-to-all, no straggler when routing is
uneven. Without `tp_mesh`, or with an `expert` axis > 1, the same function
is traced bare and GSPMD partitions it from the shardings (exact; not
measured on a chip — PERF.md).

Counting: `moe_mlp` also returns the per-expert number of VALID pairs it
computed ([experts] int32; padded prompt positions are left out), which
`models/llama.py _layer` adds to `KVCache.expert_pairs` where the cache
carries that counter (the engine's does; `stats.engine.moe`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from symmetry_tpu.ops.quant import QuantizedTensor, qmatmul


# Programs of fewer tokens take the dense mixture (module docstring: the
# two forms tie at 1,024 tokens a dispatch and routing wins above) — at
# mixtral's 8 experts top 2, where it was measured.
ROUTED_MIN_TOKENS = 1024
# Other routing shapes, each from its own reading of tools/moe_decode_ab.py:
# (experts, k) -> the least tokens a dispatch at which routing pays. 72 top
# 10 at expert width 768 (granite-4.0-h-small; PERF.md, PR 33; ms a layer,
# routed / dense): 128 tokens 5.51 / 1.15, 512: 9.09 / 4.34, 1,024: 12.03 /
# 8.10, 2,048: 18.10 / 16.74 — `ragged_dot` over 72 groups of a width of 768
# reaches 11% of the MXU peak where the mixture's batched dot reaches 84%,
# so the 7.2x FLOPs are still the cheaper form up to the largest dispatch
# (2,048 tokens); the slopes cross near 2,600. (Compiled for a v5e the
# mixture's [X, T, D] float32 products are never held whole — 0.75 GB of
# temporaries for a 2,048-token prefill of the ten layers — so it needs no
# blocking at 72 experts.)
# 512 top 10 at expert width 512 (qwen3-next-80b-a3b; PERF.md, PR 35;
# `tools/moe_decode_ab.py --shape 512,10,2048,512`, ms a layer, routed /
# dense): 64 tokens 7.96 / 2.20, 128: 10.45 / 2.85 (the weight stream's
# floor is 1.97), 256: 14.76 / 4.93, 512: 15.21 / 9.92, 1,024: 16.51 /
# 20.11, 2,048: 19.42 / 49.84 — `ragged_dot` over 512 groups costs 8-15 ms
# before the first useful row and is nearly flat from 256 tokens on (a
# group of 5-40 rows fills a fraction of an MXU tile), the mixture's 51x
# FLOPs grow with every token (~82% of the MXU peak at 2,048): they cross
# between 512 and 1,024 tokens, and no dispatch lies between the two
# (batches double). Decode (128 tokens) is the mixture at 1.4x its weight
# floor.
ROUTED_FROM = {(72, 10): 2560, (512, 10): 1024}


def moe_route(n_tokens: int, experts: int = 8, k: int = 2) -> str:
    """The form a program of `n_tokens` tokens takes, from the shape alone:
    the measured crossing of this routing shape, or mixtral's where none
    was measured."""
    least = ROUTED_FROM.get((experts, k), ROUTED_MIN_TOKENS)
    return "routed" if n_tokens >= least else "dense-mixture"


def route_top_k(x: jnp.ndarray, router: jnp.ndarray, k: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[T, D] tokens -> (gates [T, k] float32, experts [T, k] int32).
    Logits accumulate and come out in float32; the softmax is over the k
    selected logits (mixtral: normalise AFTER selection)."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    top_vals, top_idx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(top_vals, axis=-1), top_idx.astype(jnp.int32)


def _grouped_matmul(rows: jnp.ndarray, w, group_sizes: jnp.ndarray,
                    row_expert: jnp.ndarray) -> jnp.ndarray:
    """rows [R, A] sorted by expert @ per-expert [X, A, F] -> [R, F] in
    float32. A QuantizedTensor keeps its int8 payload as the operand; its
    [X, F] scales are gathered per row onto the accumulator."""
    if isinstance(w, QuantizedTensor):
        y = jax.lax.ragged_dot(rows, w.q, group_sizes,
                               preferred_element_type=jnp.float32)
        return y * jnp.take(w.scale, row_expert, axis=0)
    return jax.lax.ragged_dot(rows, w, group_sizes,
                              preferred_element_type=jnp.float32)


def _routed_ffn(x, valid, router, wg, wu, wd, k: int):
    """x [T, D], valid [T] bool -> (y [T, D] float32, pairs [X] int32).
    Under shard_map this is one shard's program: wg/wu hold a slice of the
    FFN width, wd the matching rows, and y is that slice's partial sum."""
    T, _ = x.shape
    X = router.shape[-1]
    gates, experts = route_top_k(x, router, k)            # [T, k]
    flat_expert = experts.reshape(-1)                     # [T*k]
    # Stable sort: pairs of one expert keep token order, so the result
    # does not depend on how the sort breaks ties.
    order = jnp.argsort(flat_expert, stable=True)         # sorted -> pair
    row_expert = jnp.take(flat_expert, order)
    onehot = flat_expert[:, None] == jnp.arange(X, dtype=jnp.int32)
    group_sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    pairs = jnp.sum(onehot & jnp.repeat(valid, k)[:, None], axis=0,
                    dtype=jnp.int32)

    rows = jnp.take(x, order // k, axis=0)                # [T*k, D]
    h = (jax.nn.silu(_grouped_matmul(rows, wg, group_sizes, row_expert))
         * _grouped_matmul(rows, wu, group_sizes, row_expert))
    y = _grouped_matmul(h.astype(x.dtype), wd, group_sizes, row_expert)

    # Un-sort by a gather (the inverse permutation), then the gated sum
    # over each token's k rows in float32: no scatter-add, so the sum has
    # one order.
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype))
    y = jnp.take(y, inverse, axis=0).reshape(T, k, -1)
    return jnp.einsum("tkd,tk->td", y, gates), pairs


def _experts_dot(x: jnp.ndarray, w) -> jnp.ndarray:
    """[T, A] @ per-expert [X, A, F] -> [T, X, F], every expert at once."""
    if isinstance(w, QuantizedTensor):
        y = jax.lax.dot_general(x, w.q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return (y * w.scale).astype(x.dtype)
    return jnp.einsum("ta,xaf->txf", x, w)


def _dense_mixture(x, valid, router, wg, wu, wd, k: int):
    """Same contract as `_routed_ffn`; every expert computes every token."""
    X = router.shape[-1]
    gates, experts = route_top_k(x, router, k)            # [T, k]
    onehot = experts[..., None] == jnp.arange(X, dtype=jnp.int32)
    dense_gates = jnp.sum(jnp.where(onehot, gates[..., None], 0.0), axis=1)
    pairs = jnp.sum(onehot & valid[:, None, None], axis=(0, 1),
                    dtype=jnp.int32)
    h = jax.nn.silu(_experts_dot(x, wg)) * _experts_dot(x, wu)  # [T, X, F]
    if isinstance(wd, QuantizedTensor):
        y = jax.lax.dot_general(h, wd.q, (((2,), (1,)), ((1,), (0,))),
                                preferred_element_type=jnp.float32)
        y = y * wd.scale[:, None, :]                      # [X, T, D]
    else:
        y = jnp.einsum("txf,xfd->xtd", h, wd,
                       preferred_element_type=jnp.float32)
    return jnp.einsum("xtd,tx->td", y, dense_gates), pairs


def _expert_ffn(x, valid, router, wg, wu, wd, k: int):
    """x [T, D] -> (y [T, D] float32, valid pairs [X]) by the form this
    token count takes."""
    form = (_routed_ffn
            if moe_route(x.shape[0], router.shape[-1], k) == "routed"
            else _dense_mixture)
    return form(x, valid, router, wg, wu, wd, k)


def _model_shards(tp_mesh, ffn_width: int) -> int:
    """How many ways `model` splits the FFN width inside a shard_map, or 1
    when the expert FFN is traced bare (no mesh, an `expert` axis, or a
    width that does not divide)."""
    if tp_mesh is None:
        return 1
    shape = dict(tp_mesh.shape)
    n = shape.get("model", 1)
    if shape.get("expert", 1) > 1 or ffn_width % n:
        return 1
    return n


def moe_layout(tp_mesh, ffn_width: int) -> str:
    """One line for `startup.moe`: where the expert weights live."""
    n = _model_shards(tp_mesh, ffn_width)
    if n > 1:
        return (f"every expert's FFN width split {n} ways over `model` "
                f"({ffn_width // n} columns a chip), all experts on every "
                f"chip, one psum after the combine (shard_map)")
    if tp_mesh is None:
        return "one device holds every expert whole"
    return "GSPMD partitions the expert FFN from the parameter shardings"


def moe_mlp(x: jnp.ndarray, lp: dict, config, seq_lens=None,
            tp_mesh=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN: [B, S, D] -> ([B, S, D], valid pairs per expert [X]).
    `seq_lens` [B] says how many of each row's S positions are real."""
    B, S, D = x.shape
    k = config.num_experts_per_tok
    if seq_lens is None:
        valid = jnp.ones((B * S,), bool)
    else:
        valid = (jnp.arange(S, dtype=jnp.int32)[None, :]
                 < seq_lens[:, None]).reshape(B * S)
    xf = x.reshape(B * S, D)
    args = (xf, valid, lp["router"], lp["wg"], lp["wu"], lp["wd"])

    n = _model_shards(tp_mesh, config.intermediate_size)
    if n == 1:
        y, pairs = _expert_ffn(*args, k)
    else:
        from jax.sharding import PartitionSpec as P

        data = dict(tp_mesh.shape).get("data", 1)
        b = "data" if data > 1 and (B * S) % data == 0 else None

        def spec(w, q_spec, scale_spec):
            return (QuantizedTensor(q=q_spec, scale=scale_spec)
                    if isinstance(w, QuantizedTensor) else q_spec)

        col = spec(lp["wg"], P(None, None, "model"), P(None, "model"))
        row = spec(lp["wd"], P(None, "model", None), P())

        def shard(*a):
            y, pairs = _expert_ffn(*a, k)
            y = jax.lax.psum(y, "model")
            if b is not None:
                pairs = jax.lax.psum(pairs, b)
            return y, pairs

        y, pairs = jax.shard_map(
            shard, mesh=tp_mesh,
            in_specs=(P(b, None), P(b), P(), col, col, row),
            out_specs=(P(b, None), P()), check_vma=False)(*args)
    if "sg" in lp:
        # the shared expert: the same gated form, every token, weight 1 —
        # or, where the layer has a `sgate` column (qwen3_next's
        # `shared_expert_gate`), sigmoid(x . sgate) — added to the routed
        # sum in float32
        shared = qmatmul(jax.nn.silu(qmatmul(xf, lp["sg"]))
                         * qmatmul(xf, lp["su"]), lp["sd"])
        if "sgate" in lp:
            shared = shared * jax.nn.sigmoid(jnp.dot(
                xf, lp["sgate"], preferred_element_type=jnp.float32))
        y = y + shared
    return y.astype(x.dtype).reshape(B, S, D), pairs
