"""Bytes and operations of a sparse-expert GQA decoder with LEARNED SPARSE
ATTENTION (KeyeVL2 family: a lightning indexer with a key cache of its own
picks `topk` positions a query), computed from shapes alone: what one decode
step must move through HBM in the GATHER form — the index key of every live
position, then K, V and their scales of the SELECTED positions alone — and
the floating-point operations the ACTIVE mathematics needs to prefill a
prompt: k experts of `num_experts`, the indexer's scores over the causal
pairs, attention over `min(t + 1, topk)` keys a query. A program that reads
every live block and masks, or computes attention over every causal pair,
does more than this and so reads UNDER what its time would suggest; none can
read above 100% of a peak through these counts.

`model` is the model section of a benchmark configuration file (the published
config.json keys, cut as its `reduced` says); `serving` its `tpu` section.
One chip: nothing here is sharded.
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes


def _dims(model: dict) -> dict:
    sa = model["sa_config"]
    head = model["head_dim"]
    return {"h": model["hidden_size"], "head": head,
            "q": model["num_attention_heads"] * head,
            "kv": model["num_key_value_heads"] * head,
            "kv_heads": model["num_key_value_heads"],
            "f": model["moe_intermediate_size"],
            "experts": model["num_experts"],
            "k": model["num_experts_per_tok"],
            "vocab": model["vocab_size"],
            "layers": model["num_hidden_layers"],
            "topk": sa["topk"], "ih": sa["indexer_num_heads"],
            "id": sa["indexer_head_dim"]}


def mixer_weight_bytes(model: dict, serving: dict) -> int:
    """One layer's attention and indexer: wq, wk, wv, wo and the index
    query / key projections (quantised), the head-weight projection
    (activation dtype, like the router), the layer norm and the two
    per-head norms."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"]), (d["h"], d["ih"] * d["id"]), (d["h"], d["id"])))
        + d["h"] * d["ih"] * act + (d["h"] + 2 * d["head"]) * act)


def expert_weight_bytes(model: dict, serving: dict) -> int:
    """ONE routed expert's three matrices of one layer."""
    d = _dims(model)
    return (2 * _matrix_bytes(d["h"], d["f"], serving)
            + _matrix_bytes(d["f"], d["h"], serving))


def ffn_fixed_bytes(model: dict, serving: dict) -> int:
    """What every token reads of one layer's FFN: the router and the norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return d["h"] * d["experts"] * act + d["h"] * act


def index_bytes_per_token(model: dict, serving: dict) -> int:
    """The index keys of one cached position, every layer (activation
    dtype: the file's `assumed`)."""
    d = _dims(model)
    return d["layers"] * d["id"] * _dtype_bytes(serving["dtype"])


def kv_row_bytes(model: dict, serving: dict) -> int:
    """K and V of one position in ONE layer, with the int8 cache's f32
    scale per (position, head)."""
    d = _dims(model)
    if serving.get("kv_quantization") == "int8":
        return 2 * d["kv_heads"] * (d["head"] + 4)
    return 2 * d["kv"] * _dtype_bytes(serving["dtype"])


def cache_bytes_per_token(model: dict, serving: dict) -> int:
    """What one cached position holds: K, V, scales and index keys."""
    d = _dims(model)
    return (d["layers"] * kv_row_bytes(model, serving)
            + index_bytes_per_token(model, serving))


def head_bytes(model: dict, serving: dict) -> int:
    d = _dims(model)
    if model.get("tie_word_embeddings"):
        return d["h"] * d["vocab"] * _dtype_bytes(serving["dtype"])
    return _matrix_bytes(d["h"], d["vocab"], serving)


def decode_step_bytes(model: dict, serving: dict, lengths) -> float:
    """One decode step over ALL slots of the engine in the gather form:
    every layer's mixer weights, the experts the step's pairs hit (uniform
    routing), router and norms, the head; for each live slot of `lengths`
    (its positions) every position's index keys, `min(length, topk)` K/V
    rows and scales a layer, and one embedding row."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    slots = int(serving["max_batch_size"])
    hit = experts_hit(slots * d["k"], d["experts"])
    weights = (d["layers"] * (mixer_weight_bytes(model, serving)
                              + hit * expert_weight_bytes(model, serving)
                              + ffn_fixed_bytes(model, serving))
               + d["h"] * act + head_bytes(model, serving))
    lengths = list(lengths)
    rows = sum(min(n, d["topk"]) for n in lengths)
    return (weights
            + sum(lengths) * index_bytes_per_token(model, serving)
            + rows * d["layers"] * kv_row_bytes(model, serving)
            + len(lengths) * d["h"] * act)


def active_flops_per_token(model: dict) -> int:
    """Multiply-adds x 2 of one token through the trunk, the
    position-dependent parts (index scores, attention) and the head left
    out: per layer the four attention projections, the indexer's three,
    the router and k experts."""
    d = _dims(model)
    per_layer = (2 * d["h"] * d["q"] + 2 * 2 * d["h"] * d["kv"]
                 + 2 * d["q"] * d["h"]
                 + 2 * d["h"] * (d["ih"] * d["id"] + d["id"] + d["ih"])
                 + 2 * d["h"] * d["experts"]
                 + d["k"] * 3 * 2 * d["h"] * d["f"])
    return d["layers"] * per_layer


def causal_pairs(prompt_tokens: int) -> int:
    s = int(prompt_tokens)
    return s * (s + 1) // 2


def selected_pairs(prompt_tokens: int, topk: int) -> int:
    """Sum over the prompt's queries of min(t + 1, topk)."""
    s, k = int(prompt_tokens), int(topk)
    return causal_pairs(min(s, k)) + max(0, s - k) * k


def prefill_flops(model: dict, prompt_tokens: int) -> float:
    """One prompt prefilled from empty: every token's active operations,
    the indexer's scores over every causal pair (a dot of `id` channels and
    the weighted sum, each of `ih` heads: 2 x ih x (id + 1) a pair),
    attention over the SELECTED pairs (QK^T and PV: 2 x 2 x q_dim a pair),
    and one LM-head row."""
    d = _dims(model)
    s = int(prompt_tokens)
    index = d["layers"] * 2 * d["ih"] * (d["id"] + 1) * causal_pairs(s)
    attention = d["layers"] * 4 * d["q"] * selected_pairs(s, d["topk"])
    return (s * active_flops_per_token(model) + index + attention
            + 2 * d["h"] * d["vocab"])


def flash_flops(model: dict, bucket: int, rows: int = 1) -> float:
    """The `dsa_flash` kernel's own work for one call over `rows` prompts
    padded to `bucket`: QK^T and PV of every query head over the KV blocks
    at or under each query block's diagonal — what a masked kernel
    computes whatever was selected (block sizes as ops/sparse_attention.py
    compiles them: 128 queries x 512 keys)."""
    d = _dims(model)
    bq, bk = min(128, bucket), min(512, bucket)
    blocks = sum((qi * bq + bq - 1) // bk + 1 for qi in range(bucket // bq))
    return rows * 4 * d["q"] * blocks * bq * bk
