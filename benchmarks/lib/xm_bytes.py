"""Bytes and operations of the exaone_moe decoder (K-EXAONE: window layers
with rotary beside full NoPE layers under q/k norms, a leading dense layer,
sigmoid-routed experts of which this chip holds a SHARE beside a shared
expert, an eighth of the vocabulary, and a multi-token-prediction module
that drafts one token a step), computed from shapes and the program's
counters alone: what one decode step MUST move through HBM — every held
weight of the trunk once, the module's once, the head's slice once (however
many positions it scores), each live row of a full layer and of the
module's layer and each ring row of a window layer once — and the
floating-point operations the ACTIVE mathematics needs to prefill a prompt:
the pairs that fall on HELD experts alone (k x held / routed over a token),
one shared expert a token, the module's block over the prompt, the pairs a
layer's mask leaves. Each is a lower count of what the program does (a
verify reads a full leaf's live rows twice, a ring of 256 rows holds 128
live ones, the prefill kernels compute whole tiles), so no share built on
them can read over 100% of a peak. The one term that is an expectation and
no bound is the held experts a step hits: `experts_hit` of uniformly routed
pairs, as `lib/moe_bytes.py` prices it.

`model` is the model section of a benchmark configuration file (the published
config.json keys; `num_experts` the experts HELD here, `experts_routed_over`
the router's width); `serving` its `tpu` section. One chip: nothing here is
sharded, and nothing stands in for the absent chips.
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes


def _dims(model: dict) -> dict:
    heads = model["num_attention_heads"]
    head = model.get("head_dim") or model["hidden_size"] // heads
    layers = model["num_hidden_layers"]
    types = list(model["layer_types"])[:layers]
    ffns = list(model["mlp_layer_types"])[:layers]
    held = model["num_experts"]
    return {"h": model["hidden_size"], "heads": heads,
            "kv_heads": model["num_key_value_heads"], "d": head,
            "q": heads * head, "kv": model["num_key_value_heads"] * head,
            "f": model["moe_intermediate_size"],
            "fd": model["intermediate_size"],
            "held": held,
            "routed_over": model.get("experts_routed_over", held),
            "k": model["num_experts_per_tok"],
            "vocab": model["vocab_size"], "layers": layers,
            "window": types.count("sliding_attention"),
            "full": types.count("full_attention"),
            "dense": ffns.count("dense"), "sparse": ffns.count("sparse"),
            "span": model["sliding_window"],
            "mtp": int(model.get("num_nextn_predict_layers", 0))}


def attention_weight_bytes(model: dict, serving: dict) -> int:
    """One layer's attention, either kind (and the module's): wq, wk, wv,
    wo (quantised), the input norm and the two per-head norms."""
    d = _dims(model)
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"])))
        + (d["h"] + 2 * d["d"]) * _dtype_bytes(serving["dtype"]))


def gated_bytes(model: dict, serving: dict, width: int) -> int:
    """A gated FFN's three matrices at `width`: an expert, the shared
    expert, the dense layer."""
    d = _dims(model)
    return (2 * _matrix_bytes(d["h"], width, serving)
            + _matrix_bytes(width, d["h"], serving))


def sparse_fixed_bytes(model: dict, serving: dict) -> int:
    """What every token reads of one sparse layer beside its routed
    experts: the shared expert, the router (activation dtype) over ALL the
    experts routed over, the selection bias (float32) and the norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (gated_bytes(model, serving, d["f"])
            + d["h"] * d["routed_over"] * act + 4 * d["routed_over"]
            + d["h"] * act)


def head_bytes(model: dict, serving: dict) -> int:
    d = _dims(model)
    return _matrix_bytes(d["h"], d["vocab"], serving)


def module_fixed_bytes(model: dict, serving: dict) -> int:
    """The multi-token-prediction module outside its routed experts: the
    projection of [hidden ; embedding], its attention, its sparse layer's
    fixed part, its three norms."""
    d = _dims(model)
    if not d["mtp"]:
        return 0
    return (_matrix_bytes(2 * d["h"], d["h"], serving)
            + attention_weight_bytes(model, serving)
            + sparse_fixed_bytes(model, serving)
            + 3 * d["h"] * _dtype_bytes(serving["dtype"]))


def weight_bytes(model: dict, serving: dict) -> int:
    """The whole share as the chip holds it: every layer, every HELD expert,
    the module, the embedding slice in the activation dtype, the head's
    slice, the final norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    expert = gated_bytes(model, serving, d["f"])
    return (d["layers"] * attention_weight_bytes(model, serving)
            + d["dense"] * (gated_bytes(model, serving, d["fd"])
                            + d["h"] * act)
            + d["sparse"] * (sparse_fixed_bytes(model, serving)
                             + d["held"] * expert)
            + module_fixed_bytes(model, serving)
            + d["mtp"] * d["held"] * expert
            + d["vocab"] * d["h"] * act + head_bytes(model, serving)
            + d["h"] * act)


def held_pairs(model: dict, tokens: float) -> float:
    """Expected (token, expert) pairs of `tokens` tokens that fall on a HELD
    expert under uniform routing: k x held / routed over a token."""
    d = _dims(model)
    return tokens * d["k"] * d["held"] / d["routed_over"]


def kv_row_bytes(model: dict, serving: dict) -> int:
    """One cached position in ONE layer: K and V of every KV head — int8
    payloads with one float32 scale a head each, or the activation dtype."""
    d = _dims(model)
    if serving.get("kv_quantization") == "int8":
        return 2 * d["kv_heads"] * (d["d"] + 4)
    return 2 * d["kv"] * _dtype_bytes(serving["dtype"])


def ring_rows(model: dict, serving: dict) -> int:
    """The rows a served ring HOLDS: the window's, or, where drafts are
    verified, the window's and the drafted position's in whole lane tiles
    (models/residents.py ring_rows)."""
    d = _dims(model)
    if not serving.get("speculative"):
        return d["span"]
    return -(-(d["span"] + 1) // 128) * 128


def cache_bytes(model: dict, serving: dict) -> dict:
    """What the served cache holds: a full layer's rows (and the module's)
    at `max_seq_len`, a window layer's ring, every slot."""
    d = _dims(model)
    row, slots = kv_row_bytes(model, serving), serving["max_batch_size"]
    full = (d["full"] + d["mtp"]) * serving["max_seq_len"] * row * slots
    ring = d["window"] * ring_rows(model, serving) * row * slots
    return {"full": full, "ring": ring, "total": full + ring}


def cache_step_bytes(model: dict, serving: dict, full_rows: float,
                     ring_rows: float) -> tuple[float, float]:
    """(full, ring) bytes of cache rows one decode step must read:
    `full_rows` live rows once a FULL layer and once the module's layer,
    `ring_rows` live ring rows once a WINDOW layer (the program's counters:
    a slot's length, and its length capped at the window)."""
    d = _dims(model)
    row = kv_row_bytes(model, serving)
    return (full_rows * (d["full"] + d["mtp"]) * row,
            ring_rows * d["window"] * row)


def decode_step_bytes(model: dict, serving: dict, full_rows: float,
                      ring_rows: float, live_slots: float,
                      positions: float = 2.0) -> float:
    """One decode step over ALL slots of the engine: every layer's
    attention weights, the dense layer, per sparse layer (and the module's)
    the held experts the live slots' pairs hit (`positions` a slot: the
    pending token and the draft) and the fixed part, the module, the head
    ONCE and the final norm; each live cache row once a layer of its kind;
    `positions` embedding rows a live slot, and one more for the module."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    expert = gated_bytes(model, serving, d["f"])
    hit = experts_hit(held_pairs(model, live_slots * positions), d["held"])
    weights = (d["layers"] * attention_weight_bytes(model, serving)
               + d["dense"] * (gated_bytes(model, serving, d["fd"])
                               + d["h"] * act)
               + d["sparse"] * (sparse_fixed_bytes(model, serving)
                                + hit * expert)
               + module_fixed_bytes(model, serving)
               + d["mtp"] * hit * expert
               + d["h"] * act + head_bytes(model, serving))
    return (weights + sum(cache_step_bytes(model, serving, full_rows,
                                           ring_rows))
            + live_slots * (positions + d["mtp"]) * d["h"] * act)


def active_flops_per_token(model: dict) -> float:
    """Multiply-adds x 2 of one token through the trunk AND the module,
    attention's pairs and the head left out: per layer the four attention
    projections; the dense layer's FFN; per sparse layer the router over
    all experts, the shared expert and the pairs that fall on HELD experts;
    the module's projection, attention projections and sparse FFN."""
    d = _dims(model)
    attn = 2 * (d["h"] * d["q"] + 2 * d["h"] * d["kv"] + d["q"] * d["h"])
    gated = 3 * 2 * d["h"]
    sparse = (2 * d["h"] * d["routed_over"] + gated * d["f"]
              + held_pairs(model, 1) * gated * d["f"])
    module = d["mtp"] * (2 * 2 * d["h"] * d["h"] + attn + sparse)
    return (d["layers"] * attn + d["dense"] * gated * d["fd"]
            + d["sparse"] * sparse + module)


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


def attention_flops(model: dict, prompt_tokens: int) -> float:
    """Every layer's attention over a prompt: causal on the full layers and
    the module's, window-bounded on the window layers."""
    d = _dims(model)
    per_pair = 2.0 * d["heads"] * 2 * d["d"]
    return per_pair * ((d["full"] + d["mtp"]) * causal_pairs(prompt_tokens)
                       + d["window"] * window_pairs(prompt_tokens,
                                                    d["span"]))


def prefill_flops(model: dict, prompt_tokens: int) -> float:
    """One prompt prefilled from empty: every token's active operations
    (the module's among them), the attention over the pairs each layer's
    mask leaves, and the head's slice twice — the first token's row and
    the first draft's."""
    d = _dims(model)
    return (int(prompt_tokens) * active_flops_per_token(model)
            + attention_flops(model, prompt_tokens)
            + (1 + d["mtp"]) * 2 * d["h"] * d["vocab"])
