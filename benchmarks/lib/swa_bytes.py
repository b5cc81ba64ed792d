"""Bytes and operations of a decoder with WINDOW and FULL attention layers
in one model (PowerInfer `smallthinker`: `sliding_window_layout[l]` 1 is a
layer whose keys are the last `sliding_window_size` positions, 0 one that
sees every position), grouped-query heads, a softmax router on the layer's
input and ReGLU experts — computed from shapes and the program's counters
alone: what one decode step MUST move through HBM (every weight outside the
experts once, the experts the step's pairs hit, each live row of a full
layer and each ring row of a window layer once), what the decode-attention
kernel's calls of one step must move, and the floating-point operations the
ACTIVE mathematics needs to prefill a prompt. Each is a lower count of what
a program does: a cached position at its values and scales, the prefill at
the pairs a mask leaves (causal on a full layer, the window's on a window
layer — a kernel that computes a whole diagonal or edge tile does more) — so
no share built on them can read over 100% of a peak. The one term that is an
expectation and no bound is the experts a step hits: `experts_hit` of
uniformly routed pairs, as `lib/moe_bytes.py` prices it.

`model` is the model section of a benchmark configuration file (the published
config.json keys, cut as its `reduced` says); `serving` its `tpu` section.
One chip: nothing here is sharded.
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes


def _dims(model: dict) -> dict:
    heads = model["num_attention_heads"]
    head = model.get("head_dim") or model["hidden_size"] // heads
    layers = model["num_hidden_layers"]
    layout = [int(bool(w)) for w in model["sliding_window_layout"]][:layers]
    return {"h": model["hidden_size"], "heads": heads,
            "kv_heads": model["num_key_value_heads"], "d": head,
            "q": heads * head, "kv": model["num_key_value_heads"] * head,
            "f": model["moe_ffn_hidden_size"],
            "experts": model["moe_num_primary_experts"],
            "k": model["moe_num_active_primary_experts"],
            "vocab": model["vocab_size"], "layers": layers,
            "window": sum(layout), "full": layers - sum(layout),
            "span": model["sliding_window_size"]}


def attention_weight_bytes(model: dict, serving: dict) -> int:
    """One layer's attention, either kind: wq, wk, wv, wo (quantised) and
    the input norm."""
    d = _dims(model)
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"]))) + d["h"] * _dtype_bytes(serving["dtype"]))


def expert_weight_bytes(model: dict, serving: dict) -> int:
    """ONE expert's three matrices of one layer."""
    d = _dims(model)
    return (2 * _matrix_bytes(d["h"], d["f"], serving)
            + _matrix_bytes(d["f"], d["h"], serving))


def moe_fixed_bytes(model: dict, serving: dict) -> int:
    """What every token reads of one layer's FFN beside its experts: the
    router (activation dtype) and the norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return d["h"] * d["experts"] * act + d["h"] * act


def head_bytes(model: dict, serving: dict) -> int:
    d = _dims(model)
    if model.get("tie_word_embeddings"):
        return d["h"] * d["vocab"] * _dtype_bytes(serving["dtype"])
    return _matrix_bytes(d["h"], d["vocab"], serving)


def weight_bytes(model: dict, serving: dict) -> int:
    """The whole model as the chip holds it: every layer and every expert,
    the embedding in the activation dtype, the head, the final norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (d["layers"] * (attention_weight_bytes(model, serving)
                           + d["experts"] * expert_weight_bytes(model, serving)
                           + moe_fixed_bytes(model, serving))
            + d["vocab"] * d["h"] * act + head_bytes(model, serving)
            + d["h"] * act)


def kv_row_bytes(model: dict, serving: dict) -> int:
    """One cached position in ONE layer: K and V of every KV head — int8
    payloads with one float32 scale a head each, or the activation dtype."""
    d = _dims(model)
    if serving.get("kv_quantization") == "int8":
        return 2 * d["kv_heads"] * (d["d"] + 4)
    return 2 * d["kv"] * _dtype_bytes(serving["dtype"])


def cache_bytes(model: dict, serving: dict) -> dict:
    """What the served cache holds: a full layer's rows at `max_seq_len`, a
    window layer's ring of the window's rows, every slot; and what ONE
    capacity for every attention layer would have held."""
    d = _dims(model)
    row, slots = kv_row_bytes(model, serving), serving["max_batch_size"]
    full = d["full"] * serving["max_seq_len"] * row * slots
    ring = d["window"] * d["span"] * row * slots
    return {"full": full, "ring": ring, "total": full + ring,
            "uniform": d["layers"] * serving["max_seq_len"] * row * slots}


def cache_step_bytes(model: dict, serving: dict, full_rows: float,
                     ring_rows: float) -> tuple[float, float]:
    """(full, ring) bytes of cache rows one decode step must read:
    `full_rows` live rows once a FULL layer, `ring_rows` ring rows once a
    WINDOW layer (the program's counters say how many of each a step read:
    a slot's length, and its length capped at the ring)."""
    d = _dims(model)
    row = kv_row_bytes(model, serving)
    return full_rows * d["full"] * row, ring_rows * d["window"] * row


def decode_step_bytes(model: dict, serving: dict, full_rows: float,
                      ring_rows: float, live_slots: float) -> float:
    """One decode step over ALL slots of the engine: every layer's
    attention weights, per layer the experts the live slots' pairs hit
    (uniform routing) and what every token reads beside them, the head and
    the final norm; each live cache row once a layer of its kind; one
    embedding row a live slot."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    hit = experts_hit(live_slots * d["k"], d["experts"])
    weights = (d["layers"] * (attention_weight_bytes(model, serving)
                              + hit * expert_weight_bytes(model, serving)
                              + moe_fixed_bytes(model, serving))
               + d["h"] * act + head_bytes(model, serving))
    return (weights + sum(cache_step_bytes(model, serving, full_rows,
                                           ring_rows))
            + live_slots * d["h"] * act)


def kernel_step_bytes(model: dict, serving: dict, full_rows: float,
                      ring_rows: float, slots: int) -> float:
    """What the decode-attention kernel's calls of ONE step (one a layer)
    must move: the cache rows of `cache_step_bytes`, and a call's queries
    in and outputs back (`slots` x heads x head_dim, twice)."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (sum(cache_step_bytes(model, serving, full_rows, ring_rows))
            + d["layers"] * 2 * slots * d["q"] * act)


def active_flops_per_token(model: dict) -> int:
    """Multiply-adds x 2 of one token through the trunk, attention's pairs
    and the head left out: per layer the four attention projections, the
    router and k experts."""
    d = _dims(model)
    attn = 2 * (d["h"] * d["q"] + 2 * d["h"] * d["kv"] + d["q"] * d["h"])
    moe = 2 * d["h"] * d["experts"] + d["k"] * 3 * 2 * d["h"] * d["f"]
    return d["layers"] * (attn + moe)


def causal_pairs(prompt_tokens: int) -> int:
    s = int(prompt_tokens)
    return s * (s + 1) // 2


def window_pairs(prompt_tokens: int, span: int) -> int:
    """(query, key) pairs a window of `span` leaves of a prompt: query t
    sees min(t + 1, span) keys."""
    s = int(prompt_tokens)
    if s <= span:
        return causal_pairs(s)
    return causal_pairs(span) + (s - span) * span


def layer_attention_flops(model: dict, prompt_tokens: int,
                          windowed: bool) -> float:
    """ONE layer's attention over a prompt (one call of the prefill
    kernel): every query head's scores and weighted values over the pairs
    its mask leaves."""
    d = _dims(model)
    pairs = (window_pairs(prompt_tokens, d["span"]) if windowed
             else causal_pairs(prompt_tokens))
    return 2.0 * d["heads"] * 2 * d["d"] * pairs


def attention_flops(model: dict, prompt_tokens: int) -> float:
    """Every layer's attention over a prompt: causal on the full layers,
    window-bounded on the window layers."""
    d = _dims(model)
    return (d["full"] * layer_attention_flops(model, prompt_tokens, False)
            + d["window"] * layer_attention_flops(model, prompt_tokens, True))


def prefill_flops(model: dict, prompt_tokens: int) -> float:
    """One prompt prefilled from empty: every token's active operations,
    the attention over the pairs each layer's mask leaves, one LM-head
    row."""
    d = _dims(model)
    return (int(prompt_tokens) * active_flops_per_token(model)
            + attention_flops(model, prompt_tokens)
            + 2 * d["h"] * d["vocab"])
