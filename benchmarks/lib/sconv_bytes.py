"""Bytes and operations of the gated-short-convolution / GQA decoder with
leading dense layers and sigmoid-routed experts (lfm2_moe family), computed
from shapes alone: what one decode step must move through HBM, and the
floating-point operations the ACTIVE mathematics needs to prefill a prompt —
k experts of `num_experts` a token in the expert layers, never all of them —
so a program that computes more than it must (the dense mixture's
experts / k times the FLOPs) cannot read above 100% of a peak through these
counts.

`model` is the model section of a benchmark configuration file (the published
config.json keys: `intermediate_size` is the leading dense layers' width,
`moe_intermediate_size` a routed expert's); `serving` its `tpu` section. One
chip: nothing here is sharded.

What a conv layer keeps per slot is not a row per position: the
`conv_L_cache - 1` last inputs of its convolution, `hidden_size` channels
each, in the activation dtype. A decode step reads AND writes the tail of
EVERY slot of the engine (idle lanes step too): each slot's tails count once
read and once written; the attention layers' K/V count once, for the live
tokens, whatever implements the attention.
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes


def _dims(model: dict) -> dict:
    h = model["hidden_size"]
    head = model.get("head_dim") or h // model["num_attention_heads"]
    kinds = list(model["layer_types"])
    dense = int(model.get("num_dense_layers", 0))
    return {"h": h, "head": head,
            "q": model["num_attention_heads"] * head,
            "kv": model["num_key_value_heads"] * head,
            "kv_heads": model["num_key_value_heads"],
            "fd": model["intermediate_size"],
            "f": model["moe_intermediate_size"],
            "experts": model["num_experts"],
            "k": model["num_experts_per_tok"],
            "vocab": model["vocab_size"], "layers": len(kinds),
            "conv": kinds.count("conv"),
            "attn": kinds.count("full_attention"),
            "dense": dense, "moe": len(kinds) - dense,
            "taps": model["conv_L_cache"],
            "bias": bool(model.get("use_expert_bias"))}


def conv_weight_bytes(model: dict, serving: dict) -> int:
    """One short-convolution layer: the B|C|x and the output projections
    (quantised), the taps and the layer norm (activation dtype)."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (_matrix_bytes(d["h"], 3 * d["h"], serving)
            + _matrix_bytes(d["h"], d["h"], serving)
            + d["taps"] * d["h"] * act + d["h"] * act)


def attention_weight_bytes(model: dict, serving: dict) -> int:
    """One attention layer: wq, wk, wv, wo, the layer norm and the two
    per-head norms."""
    d = _dims(model)
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"])))
        + (d["h"] + 2 * d["head"]) * _dtype_bytes(serving["dtype"]))


def dense_ffn_bytes(model: dict, serving: dict) -> int:
    """One leading dense layer's SwiGLU and its norm."""
    d = _dims(model)
    return (2 * _matrix_bytes(d["h"], d["fd"], serving)
            + _matrix_bytes(d["fd"], d["h"], serving)
            + d["h"] * _dtype_bytes(serving["dtype"]))


def expert_weight_bytes(model: dict, serving: dict) -> int:
    """ONE routed expert's three matrices of one layer."""
    d = _dims(model)
    return (2 * _matrix_bytes(d["h"], d["f"], serving)
            + _matrix_bytes(d["f"], d["h"], serving))


def moe_fixed_bytes(model: dict, serving: dict) -> int:
    """What every token reads of one expert layer beside its experts: the
    router (activation dtype), the float32 selection bias and the norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (d["h"] * d["experts"] * act + d["h"] * act
            + (4 * d["experts"] if d["bias"] else 0))


def state_bytes_per_slot(model: dict, serving: dict) -> int:
    """Bytes one slot holds in all the conv layers: their tails."""
    d = _dims(model)
    return (d["conv"] * (d["taps"] - 1) * d["h"]
            * _dtype_bytes(serving["dtype"]))


def kv_bytes_per_token(model: dict, serving: dict) -> int:
    """K and V of one live token in the ATTENTION layers, with the int8
    cache's f32 scale per (token, head)."""
    d = _dims(model)
    if serving.get("kv_quantization") == "int8":
        return d["attn"] * 2 * d["kv_heads"] * (d["head"] + 4)
    return d["attn"] * 2 * d["kv"] * _dtype_bytes(serving["dtype"])


def head_bytes(model: dict, serving: dict) -> int:
    """The LM head: the tied embedding in the activation dtype (the
    family's), or a quantised matrix of its own."""
    d = _dims(model)
    if model.get("tie_embedding", True):
        return d["h"] * d["vocab"] * _dtype_bytes(serving["dtype"])
    return _matrix_bytes(d["h"], d["vocab"], serving)


def weight_bytes(model: dict, serving: dict, hit: float | None = None
                 ) -> float:
    """Every weight a decode step multiplies by, `hit` experts a layer
    (default: all of them), the final norm and the head."""
    d = _dims(model)
    hit = d["experts"] if hit is None else hit
    return (d["conv"] * conv_weight_bytes(model, serving)
            + d["attn"] * attention_weight_bytes(model, serving)
            + d["dense"] * dense_ffn_bytes(model, serving)
            + d["moe"] * (hit * expert_weight_bytes(model, serving)
                          + moe_fixed_bytes(model, serving))
            + d["h"] * _dtype_bytes(serving["dtype"])
            + head_bytes(model, serving))


def step_bytes(model: dict, serving: dict, live_tokens: float,
               live_slots: float) -> float:
    """One decode step over ALL slots of the engine: every layer's weights
    with the experts the step's pairs hit (uniform routing: 512 pairs over
    32 hit every one), the head; the tails of every slot read once and
    written once; the live K/V; one embedding row per live slot."""
    d = _dims(model)
    slots = int(serving["max_batch_size"])
    hit = experts_hit(slots * d["k"], d["experts"])
    return (weight_bytes(model, serving, hit)
            + 2 * slots * state_bytes_per_slot(model, serving)
            + live_tokens * kv_bytes_per_token(model, serving)
            + live_slots * d["h"] * _dtype_bytes(serving["dtype"]))


def active_flops_per_token(model: dict) -> int:
    """Multiply-adds x 2 of one token through the trunk, attention's
    position-dependent part and the head left out: per conv layer the two
    projections, the taps and the two gates; per attention layer the four
    projections; per dense layer the SwiGLU; per expert layer the router and
    k experts."""
    d = _dims(model)
    conv = (2 * d["h"] * 3 * d["h"] + 2 * d["h"] * d["h"]
            + 2 * d["taps"] * d["h"] + 2 * d["h"])
    attn = 2 * d["h"] * d["q"] + 2 * 2 * d["h"] * d["kv"] \
        + 2 * d["q"] * d["h"]
    dense = 3 * 2 * d["h"] * d["fd"]
    moe = 2 * d["h"] * d["experts"] + d["k"] * 3 * 2 * d["h"] * d["f"]
    return (d["conv"] * conv + d["attn"] * attn + d["dense"] * dense
            + d["moe"] * moe)


def prefill_flops(model: dict, prompt_tokens: int) -> float:
    """One prompt prefilled from empty: every token's active operations,
    causal attention in the attention layers (QK^T and PV over the positions
    at or before each: 2 x 2 x q_dim x S(S+1)/2 a layer), and one LM-head
    row."""
    d = _dims(model)
    s = int(prompt_tokens)
    attention = d["attn"] * 4 * d["q"] * s * (s + 1) / 2
    return (s * active_flops_per_token(model) + attention
            + 2 * d["h"] * d["vocab"])
