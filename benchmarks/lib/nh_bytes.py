"""Bytes and operations of the nemotron_h decoder (NVIDIA-Nemotron-3-Nano:
blocks of one sub-layer each — grouped Mamba-2, GQA without rotary, ungated
relu2 experts of which this chip holds a SHARE), computed from shapes alone:
what one decode step must move through HBM, and the floating-point operations
the ACTIVE mathematics needs to prefill a prompt — the pairs that fall on
HELD experts alone (k x held / routed over a token, never all experts), one
shared expert a token, the recurrence counted as written, never the chunked
form's quadratic products — so a program that computes more than it must
cannot read above 100% of a peak through these counts.

`model` is the model section of a benchmark configuration file (the published
config.json keys; `n_routed_experts` the experts HELD here, `experts_routed_
over` the router's width); `serving` its `tpu` section. One chip: nothing here
is sharded, and nothing stands in for the absent chips.

An expert is TWO matrices (relu2(x W_up) W_down), the shared expert too.
What a mamba block keeps per slot is not a row per position: the state
(`mamba_num_heads` x `mamba_head_dim` x `ssm_state_size`, float32) and the
convolution's last `conv_kernel - 1` inputs (H*P + 2*G*N channels). A decode
step reads AND writes the state of EVERY slot of the engine (idle lanes step
too), so it counts twice; the attention blocks' K/V count once, for the live
tokens, as in the dense family (`lib/step_bytes.py`).
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes

STATE_BYTES = 4     # the recurrent state is float32 (the file's `assumed`)


def _dims(model: dict) -> dict:
    h = model["hidden_size"]
    pattern = model["hybrid_override_pattern"]
    heads, d_head = model["mamba_num_heads"], model["mamba_head_dim"]
    state, groups = model["ssm_state_size"], model["n_groups"]
    inner = heads * d_head
    held = model["n_routed_experts"]
    return {"h": h, "head": model["head_dim"],
            "q": model["num_attention_heads"] * model["head_dim"],
            "kv": model["num_key_value_heads"] * model["head_dim"],
            "kv_heads": model["num_key_value_heads"],
            "f": model["moe_intermediate_size"],
            "fs": model["moe_shared_expert_intermediate_size"],
            "held": held,
            "routed_over": model.get("experts_routed_over", held),
            "k": model["num_experts_per_tok"],
            "vocab": model["vocab_size"],
            "mamba": pattern.count("M"), "attn": pattern.count("*"),
            "moe": pattern.count("E"),
            "heads": heads, "d_head": d_head, "state": state,
            "groups": groups, "taps": model["conv_kernel"], "inner": inner,
            "conv": inner + 2 * groups * state,
            "proj": 2 * inner + 2 * groups * state + heads}


def held_pairs(model: dict, tokens: float) -> float:
    """Expected (token, expert) pairs of `tokens` tokens that fall on a HELD
    expert under uniform routing: k x held / routed over a token."""
    d = _dims(model)
    return tokens * d["k"] * d["held"] / d["routed_over"]


def mamba_weight_bytes(model: dict, serving: dict) -> int:
    """One mamba block: in_proj, out_proj, the convolution and its bias, the
    two norms, dt_bias / A_log / D."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (_matrix_bytes(d["h"], d["proj"], serving)
            + _matrix_bytes(d["inner"], d["h"], serving)
            + (d["taps"] + 1) * d["conv"] * act
            + (d["h"] + d["inner"]) * act + 3 * d["heads"] * 4)


def attention_weight_bytes(model: dict, serving: dict) -> int:
    """One attention block: wq, wk, wv, wo and its norm."""
    d = _dims(model)
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"]))) + d["h"] * _dtype_bytes(serving["dtype"]))


def expert_weight_bytes(model: dict, serving: dict) -> int:
    """ONE routed expert's two matrices of one block."""
    d = _dims(model)
    return (_matrix_bytes(d["h"], d["f"], serving)
            + _matrix_bytes(d["f"], d["h"], serving))


def ffn_fixed_bytes(model: dict, serving: dict) -> int:
    """What every token reads of one expert block: the shared expert's two
    matrices, the router (all it scores), its bias and the norm."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (_matrix_bytes(d["h"], d["fs"], serving)
            + _matrix_bytes(d["fs"], d["h"], serving)
            + d["h"] * d["routed_over"] * act + d["routed_over"] * 4
            + d["h"] * act)


def state_bytes_per_slot(model: dict, serving: dict) -> dict:
    """{"ssm", "conv"}: bytes one slot holds in all the mamba blocks."""
    d = _dims(model)
    return {"ssm": d["mamba"] * d["heads"] * d["d_head"] * d["state"]
            * STATE_BYTES,
            "conv": d["mamba"] * (d["taps"] - 1) * d["conv"]
            * _dtype_bytes(serving["dtype"])}


def state_layer_bytes(model: dict, serving: dict) -> int:
    """What ONE pass of the recurrence kernel must move: one block's state
    of EVERY slot, read once and written once (the decay, dt x, B and C it
    also reads are not counted)."""
    d = _dims(model)
    return (2 * d["heads"] * d["d_head"] * d["state"] * STATE_BYTES
            * int(serving["max_batch_size"]))


def kv_bytes_per_token(model: dict, serving: dict) -> int:
    """K and V of one live token in the ATTENTION blocks, with the int8
    cache's f32 scale per (token, head)."""
    d = _dims(model)
    if serving.get("kv_quantization") == "int8":
        return d["attn"] * 2 * d["kv_heads"] * (d["head"] + 4)
    return d["attn"] * 2 * d["kv"] * _dtype_bytes(serving["dtype"])


def head_bytes(model: dict, serving: dict) -> int:
    """The untied LM head, a quantised matrix of its own."""
    d = _dims(model)
    return _matrix_bytes(d["h"], d["vocab"], serving)


def experts_step_bytes(model: dict, serving: dict, tokens: float) -> float:
    """The routed experts' weights ONE expert block must read for `tokens`
    tokens: the held experts their pairs hit (uniform routing), two
    matrices each."""
    d = _dims(model)
    return (experts_hit(held_pairs(model, tokens), d["held"])
            * expert_weight_bytes(model, serving))


def decode_step_bytes(model: dict, serving: dict, live_tokens: float,
                      live_slots: float) -> float:
    """One decode step over ALL slots of the engine: every block's mixer
    weights, the HELD experts the step's pairs hit, the shared expert,
    router and norms, the head; the state of every slot read and written,
    its conv tails read and written; the live K/V; one embedding row per
    live slot."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    slots = int(serving["max_batch_size"])
    weights = (d["mamba"] * mamba_weight_bytes(model, serving)
               + d["attn"] * attention_weight_bytes(model, serving)
               + d["moe"] * (experts_step_bytes(model, serving, slots)
                             + ffn_fixed_bytes(model, serving))
               + d["h"] * act + head_bytes(model, serving))
    per_slot = state_bytes_per_slot(model, serving)
    state = 2 * slots * (per_slot["ssm"] + per_slot["conv"])
    return (weights + state
            + live_tokens * kv_bytes_per_token(model, serving)
            + live_slots * d["h"] * act)


def expert_flops_per_token(model: dict) -> float:
    """Multiply-adds x 2 of one token's HELD pairs through one expert block:
    k x held / routed over pairs, two matrices each."""
    d = _dims(model)
    return held_pairs(model, 1) * 2 * 2 * d["h"] * d["f"]


def active_flops_per_token(model: dict) -> float:
    """Multiply-adds x 2 of one token through the trunk, attention's
    position-dependent part and the head left out: per mamba block the two
    projections, the convolution, the state update (a S + dt x (x) B: 3 a
    state element) and its read-out (2 a state element); per attention block
    the four projections; per expert block the router over all it scores,
    the pairs on held experts and the shared expert, two matrices each."""
    d = _dims(model)
    state_elems = d["heads"] * d["d_head"] * d["state"]
    mamba = (2 * d["h"] * d["proj"] + 2 * d["inner"] * d["h"]
             + 2 * d["taps"] * d["conv"] + 5 * state_elems)
    attn = (2 * d["h"] * d["q"] + 2 * 2 * d["h"] * d["kv"]
            + 2 * d["q"] * d["h"])
    ffn = (2 * d["h"] * d["routed_over"] + expert_flops_per_token(model)
           + 2 * 2 * d["h"] * d["fs"])
    return d["mamba"] * mamba + d["attn"] * attn + d["moe"] * ffn


def prefill_flops(model: dict, prompt_tokens: int) -> float:
    """One prompt prefilled from empty: every token's active operations,
    causal attention in the attention blocks (QK^T and PV over the positions
    at or before each: 2 x 2 x q_dim x S(S+1)/2 a block), and one LM-head
    row."""
    d = _dims(model)
    s = int(prompt_tokens)
    attention = d["attn"] * 4 * d["q"] * s * (s + 1) / 2
    return (s * active_flops_per_token(model) + attention
            + 2 * d["h"] * d["vocab"])
