"""Bytes and operations of the Gated DeltaNet / gated-attention decoder with
routed experts and a gated shared expert (qwen3_next family), computed from
shapes alone: what one decode step must move through HBM, and the
floating-point operations the ACTIVE mathematics needs to prefill a prompt —
k experts of `num_experts` and the shared expert a token, never all experts;
the delta rule counted as written (a scan over time), never the chunked
form's extra products (the triangular solve, the [Q, Q] score matrices) — so
a program that computes more than it must cannot read above 100% of a peak
through these counts.

`model` is the model section of a benchmark configuration file (the published
config.json keys, cut as its `reduced` says); `serving` its `tpu` section.
One chip: nothing here is sharded.

What a linear-attention layer keeps per slot is not a row per position: a
matrix state (`linear_num_value_heads` x `linear_key_head_dim` x
`linear_value_head_dim`, float32) and the convolution's last
`linear_conv_kernel_dim - 1` inputs. A decode step reads AND writes the state
of EVERY slot of the engine (idle lanes step too): each slot's state counts
once read and once written; the attention layers' K/V count once, for the
live tokens.
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes

STATE_BYTES = 4     # the recurrent state is float32 (the file's `assumed`)


def layer_kinds(model: dict) -> list[str]:
    if model.get("layer_types"):
        return list(model["layer_types"])
    every = model["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(model["num_hidden_layers"])]


def _dims(model: dict) -> dict:
    h, head = model["hidden_size"], model["head_dim"]
    kinds = layer_kinds(model)
    hk, dk = model["linear_num_key_heads"], model["linear_key_head_dim"]
    hv, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    return {"h": h, "head": head,
            "q": model["num_attention_heads"] * head,
            "kv": model["num_key_value_heads"] * head,
            "kv_heads": model["num_key_value_heads"],
            "f": model["moe_intermediate_size"],
            "fs": model["shared_expert_intermediate_size"],
            "experts": model["num_experts"],
            "k": model["num_experts_per_tok"],
            "vocab": model["vocab_size"], "layers": len(kinds),
            "linear": kinds.count("linear_attention"),
            "attn": kinds.count("full_attention"),
            "hv": hv, "dk": dk, "dv": dv,
            "taps": model["linear_conv_kernel_dim"],
            "inner": hv * dv, "conv": 2 * hk * dk + hv * dv,
            "proj": 2 * hk * dk + 2 * hv * dv}


def linear_weight_bytes(model: dict, serving: dict) -> int:
    """One Gated DeltaNet layer: the q|k|v|z and the output projections
    (quantised), the b|a projection and the convolution (activation dtype),
    the two norms, A_log and dt_bias."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (_matrix_bytes(d["h"], d["proj"], serving)
            + _matrix_bytes(d["inner"], d["h"], serving)
            + d["h"] * 2 * d["hv"] * act
            + d["taps"] * d["conv"] * act
            + (d["h"] + d["dv"]) * act + 2 * d["hv"] * 4)


def attention_weight_bytes(model: dict, serving: dict) -> int:
    """One gated-attention layer: wq (query and gate: twice the heads'
    width), wk, wv, wo, the layer norm and the two per-head norms."""
    d = _dims(model)
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], 2 * d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"])))
        + (d["h"] + 2 * d["head"]) * _dtype_bytes(serving["dtype"]))


def expert_weight_bytes(model: dict, serving: dict) -> int:
    """ONE routed expert's three matrices of one layer."""
    d = _dims(model)
    return (2 * _matrix_bytes(d["h"], d["f"], serving)
            + _matrix_bytes(d["f"], d["h"], serving))


def ffn_fixed_bytes(model: dict, serving: dict) -> int:
    """What every token reads of one layer's FFN: the shared expert, its
    gate column, the router and the norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (2 * _matrix_bytes(d["h"], d["fs"], serving)
            + _matrix_bytes(d["fs"], d["h"], serving)
            + d["h"] * d["experts"] * act + 2 * d["h"] * act)


def state_bytes_per_slot(model: dict, serving: dict) -> dict:
    """{"ssm", "conv"}: bytes one slot holds in all the linear layers."""
    d = _dims(model)
    return {"ssm": d["linear"] * d["hv"] * d["dk"] * d["dv"] * STATE_BYTES,
            "conv": d["linear"] * (d["taps"] - 1) * d["conv"]
            * _dtype_bytes(serving["dtype"])}


def kv_bytes_per_token(model: dict, serving: dict) -> int:
    """K and V of one live token in the ATTENTION layers, with the int8
    cache's f32 scale per (token, head)."""
    d = _dims(model)
    if serving.get("kv_quantization") == "int8":
        return d["attn"] * 2 * d["kv_heads"] * (d["head"] + 4)
    return d["attn"] * 2 * d["kv"] * _dtype_bytes(serving["dtype"])


def head_bytes(model: dict, serving: dict) -> int:
    """The LM head: a quantised matrix of its own (untied), or the tied
    embedding in the activation dtype."""
    d = _dims(model)
    if model.get("tie_word_embeddings"):
        return d["h"] * d["vocab"] * _dtype_bytes(serving["dtype"])
    return _matrix_bytes(d["h"], d["vocab"], serving)


def decode_step_bytes(model: dict, serving: dict, live_tokens: float,
                      live_slots: float) -> float:
    """One decode step over ALL slots of the engine: every layer's mixer
    weights, the experts the step's pairs hit (uniform routing), the shared
    expert, router and norms, the head; the state and conv tail of every
    slot read once and written once; the live K/V; one embedding row per
    live slot."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    slots = int(serving["max_batch_size"])
    hit = experts_hit(slots * d["k"], d["experts"])
    weights = (d["linear"] * linear_weight_bytes(model, serving)
               + d["attn"] * attention_weight_bytes(model, serving)
               + d["layers"] * (hit * expert_weight_bytes(model, serving)
                                + ffn_fixed_bytes(model, serving))
               + d["h"] * act + head_bytes(model, serving))
    per_slot = state_bytes_per_slot(model, serving)
    state = 2 * slots * (per_slot["ssm"] + per_slot["conv"])
    return (weights + state
            + live_tokens * kv_bytes_per_token(model, serving)
            + live_slots * d["h"] * act)


def active_flops_per_token(model: dict) -> int:
    """Multiply-adds x 2 of one token through the trunk, attention's
    position-dependent part and the head left out: per linear layer the
    three projections, the convolution and the delta rule as written (the
    decay 1, the read S^T k 2, the write k (x) d 2, the read-out S^T q 2: 7
    a state element); per attention layer the four projections (wq twice
    as wide: the gate); per layer the router, k experts, the shared expert
    and its gate column."""
    d = _dims(model)
    state_elems = d["hv"] * d["dk"] * d["dv"]
    linear = (2 * d["h"] * d["proj"] + 2 * d["h"] * 2 * d["hv"]
              + 2 * d["inner"] * d["h"] + 2 * d["taps"] * d["conv"]
              + 7 * state_elems)
    attn = (2 * d["h"] * 2 * d["q"] + 2 * 2 * d["h"] * d["kv"]
            + 2 * d["q"] * d["h"])
    ffn = (2 * d["h"] * d["experts"] + d["k"] * 3 * 2 * d["h"] * d["f"]
           + 3 * 2 * d["h"] * d["fs"] + 2 * d["h"])
    return d["linear"] * linear + d["attn"] * attn + d["layers"] * ffn


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
