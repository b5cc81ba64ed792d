"""Bytes and operations of the hybrid Mamba-2 / attention decoder with routed
and shared experts (granitemoehybrid family), computed from shapes alone:
what one decode step must move through HBM, and the floating-point operations
the ACTIVE mathematics needs to prefill a prompt — k experts of
`num_local_experts` and one shared expert a token, never all experts; the
recurrence counted as written, never the chunked form's quadratic products —
so a program that computes more than it must cannot read above 100% of a peak
through these counts.

`model` is the model section of a benchmark configuration file (the published
config.json keys, cut as its `reduced` says); `serving` its `tpu` section.
One chip: nothing here is sharded.

What a mamba layer keeps per slot is not a row per position: the state
(`mamba_n_heads` x `mamba_d_head` x `mamba_d_state`, float32) and the
convolution's last `mamba_d_conv - 1` inputs. A decode step reads AND writes
the state of EVERY slot of the engine (idle lanes step too), so it counts
twice; the attention layers' K/V count once, for the live tokens, as in the
dense family (`lib/step_bytes.py`).
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes

STATE_BYTES = 4     # the recurrent state is float32 (the file's `assumed`)


def _dims(model: dict) -> dict:
    h = model["hidden_size"]
    head = model.get("head_dim") or h // model["num_attention_heads"]
    kinds = list(model["layer_types"])
    heads, d_head = model["mamba_n_heads"], model["mamba_d_head"]
    state, groups = model["mamba_d_state"], model.get("mamba_n_groups", 1)
    inner = heads * d_head
    return {"h": h, "head": head,
            "q": model["num_attention_heads"] * head,
            "kv": model["num_key_value_heads"] * head,
            "kv_heads": model["num_key_value_heads"],
            "f": model["intermediate_size"],
            "fs": model.get("shared_intermediate_size", 0),
            "experts": model["num_local_experts"],
            "k": model["num_experts_per_tok"],
            "vocab": model["vocab_size"],
            "layers": len(kinds),
            "mamba": sum(t == "mamba" for t in kinds),
            "attn": sum(t == "attention" for t in kinds),
            "heads": heads, "d_head": d_head, "state": state,
            "taps": model["mamba_d_conv"], "inner": inner,
            "conv": inner + 2 * groups * state,
            "proj": 2 * inner + 2 * groups * state + heads}


def mamba_weight_bytes(model: dict, serving: dict) -> int:
    """One mamba layer: in_proj, out_proj, the convolution and its bias, the
    two norms, dt_bias / A_log / D."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (_matrix_bytes(d["h"], d["proj"], serving)
            + _matrix_bytes(d["inner"], d["h"], serving)
            + (d["taps"] + 1) * d["conv"] * act
            + (d["h"] + d["inner"]) * act + 3 * d["heads"] * 4)


def attention_weight_bytes(model: dict, serving: dict) -> int:
    """One attention layer: wq, wk, wv, wo and its norm."""
    d = _dims(model)
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"]))) + d["h"] * _dtype_bytes(serving["dtype"]))


def expert_weight_bytes(model: dict, serving: dict) -> int:
    """ONE routed expert's three matrices of one layer."""
    d = _dims(model)
    return (2 * _matrix_bytes(d["h"], d["f"], serving)
            + _matrix_bytes(d["f"], d["h"], serving))


def ffn_fixed_bytes(model: dict, serving: dict) -> int:
    """What every token reads of one layer's FFN: the shared expert, the
    router and the norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (2 * _matrix_bytes(d["h"], d["fs"], serving)
            + _matrix_bytes(d["fs"], d["h"], serving)
            + d["h"] * d["experts"] * act + d["h"] * act)


def state_bytes_per_slot(model: dict, serving: dict) -> dict:
    """{"ssm", "conv"}: bytes one slot holds in all the mamba layers."""
    d = _dims(model)
    return {"ssm": d["mamba"] * d["heads"] * d["d_head"] * d["state"]
            * STATE_BYTES,
            "conv": d["mamba"] * (d["taps"] - 1) * d["conv"]
            * _dtype_bytes(serving["dtype"])}


def kv_bytes_per_token(model: dict, serving: dict) -> int:
    """K and V of one live token in the ATTENTION layers, with the int8
    cache's f32 scale per (token, head)."""
    d = _dims(model)
    if serving.get("kv_quantization") == "int8":
        return d["attn"] * 2 * d["kv_heads"] * (d["head"] + 4)
    return d["attn"] * 2 * d["kv"] * _dtype_bytes(serving["dtype"])


def head_bytes(model: dict, serving: dict) -> int:
    """The LM head: the tied embedding in the activation dtype, or a
    quantised matrix of its own."""
    d = _dims(model)
    if model.get("tie_word_embeddings"):
        return d["h"] * d["vocab"] * _dtype_bytes(serving["dtype"])
    return _matrix_bytes(d["h"], d["vocab"], serving)


def decode_step_bytes(model: dict, serving: dict, live_tokens: float,
                      live_slots: float) -> float:
    """One decode step over ALL slots of the engine: every layer's mixer
    weights, the experts the step's pairs hit (uniform routing), the shared
    expert, router and norms, the head; the state of every slot read and
    written, its conv tails read and written; the live K/V; one embedding
    row per live slot."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    slots = int(serving["max_batch_size"])
    hit = experts_hit(slots * d["k"], d["experts"])
    weights = (d["mamba"] * mamba_weight_bytes(model, serving)
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
    position-dependent part and the head left out: per mamba layer the two
    projections, the convolution, the state update (a S + dt x (x) B: 3 a
    state element) and its read-out (2 a state element); per attention layer
    the four projections; per layer the router, k experts and the shared
    expert."""
    d = _dims(model)
    state_elems = d["heads"] * d["d_head"] * d["state"]
    mamba = (2 * d["h"] * d["proj"] + 2 * d["inner"] * d["h"]
             + 2 * d["taps"] * d["conv"] + 5 * state_elems)
    attn = (2 * d["h"] * d["q"] + 2 * 2 * d["h"] * d["kv"]
            + 2 * d["q"] * d["h"])
    ffn = (2 * d["h"] * d["experts"] + d["k"] * 3 * 2 * d["h"] * d["f"]
           + 3 * 2 * d["h"] * d["fs"])
    return d["mamba"] * mamba + d["attn"] * attn + d["layers"] * ffn


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
