"""Bytes and operations of the sparse-expert decoder (Mixtral family),
computed from shapes alone: what one decode step must read from HBM per
chip, and the floating-point operations the ROUTED mathematics needs per
token — k experts of `num_local_experts`, never all of them, so a program
that computes every expert for every token cannot read above 100% of a peak
through these counts.

`model` is the model section of a benchmark configuration file (the published
config.json keys); `serving` its `tpu` section. Under `mesh {model: tp}` each
chip holds 1/tp of every matrix's sharded dimension (the experts' FFN width,
the heads, the KV heads, the vocabulary) and the whole of what is replicated
(the router, the norms).

The cache is the dense family's: `kv_bytes_per_token` is `lib/step_bytes.py`'s.

A decode step of B live slots routes B x k pairs; with B x k well above the
number of experts (128 pairs over 8 at 64 slots) every expert is hit, so the
step reads every expert's weights once, whatever the routing:
`experts_hit(pairs, experts)` is the expected number when routing is uniform,
and `decode_step_bytes` uses it.
"""

from __future__ import annotations

from lib.step_bytes import _dtype_bytes, kv_bytes_per_token  # noqa: F401


def _dims(model: dict) -> dict:
    h = model["hidden_size"]
    head = model.get("head_dim") or h // model["num_attention_heads"]
    return {"h": h, "head": head, "f": model["intermediate_size"],
            "q": model["num_attention_heads"] * head,
            "kv": model["num_key_value_heads"] * head,
            "layers": model["num_hidden_layers"],
            "vocab": model["vocab_size"],
            "experts": model["num_local_experts"],
            "k": model["num_experts_per_tok"]}


def _matrix_bytes(k: int, n: int, serving: dict) -> int:
    """One [k, n] matrix as it lies in HBM: int8 with an f32 scale per output
    column, or the activation dtype."""
    if serving.get("quantization") == "int8":
        return k * n + 4 * n
    return k * n * _dtype_bytes(serving["dtype"])


def attention_weight_bytes(model: dict, serving: dict) -> int:
    """wq, wk, wv, wo of one layer (sharded over `model`)."""
    d = _dims(model)
    return sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"])))


def expert_weight_bytes(model: dict, serving: dict) -> int:
    """ONE expert's three matrices of one layer (sharded over `model`)."""
    d = _dims(model)
    return (2 * _matrix_bytes(d["h"], d["f"], serving)
            + _matrix_bytes(d["f"], d["h"], serving))


def replicated_layer_bytes(model: dict, serving: dict) -> int:
    """What every chip holds whole of one layer: the router and two norms."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return d["h"] * d["experts"] * act + 2 * d["h"] * act


def weight_bytes(model: dict, serving: dict) -> int:
    """The whole model's weights and scales, all chips together (the
    embedding is left out: a step gathers one row per slot)."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    per_layer = (attention_weight_bytes(model, serving)
                 + d["experts"] * expert_weight_bytes(model, serving)
                 + replicated_layer_bytes(model, serving))
    return (d["layers"] * per_layer + d["h"] * act
            + _matrix_bytes(d["h"], d["vocab"], serving))


def experts_hit(pairs: float, experts: int) -> float:
    """Expected number of distinct experts that `pairs` uniformly routed
    (token, expert) pairs touch."""
    if pairs <= 0:
        return 0.0
    return experts * (1.0 - (1.0 - 1.0 / experts) ** pairs)


def decode_step_bytes(model: dict, serving: dict, live_tokens: float,
                      live_slots: float) -> float:
    """Per chip, one decode step over ALL slots of the engine (idle slots
    compute too): the sharded weights / tp — attention, the experts that the
    step's pairs hit, the LM head — the replicated router and norms, live
    KV / tp, and one embedding row per slot."""
    d = _dims(model)
    tp = int((serving.get("mesh") or {}).get("model", 1))
    act = _dtype_bytes(serving["dtype"])
    slots = int(serving["max_batch_size"])
    hit = experts_hit(slots * d["k"], d["experts"])
    sharded = (d["layers"] * (attention_weight_bytes(model, serving)
                              + hit * expert_weight_bytes(model, serving))
               + _matrix_bytes(d["h"], d["vocab"], serving))
    replicated = (d["layers"] * replicated_layer_bytes(model, serving)
                  + d["h"] * act)
    return (sharded / tp + replicated
            + live_tokens * kv_bytes_per_token(model, serving) / tp
            + live_slots * d["h"] * act)


def routed_matmul_flops_per_token(model: dict) -> int:
    """Multiply-adds x 2 of one token through the trunk's matrices with k
    experts: attention projections, the router, k x three expert matmuls.
    The LM head is not in it (a prefill projects one position a prompt)."""
    d = _dims(model)
    per_layer = (2 * d["h"] * d["q"] + 2 * 2 * d["h"] * d["kv"]
                 + 2 * d["q"] * d["h"] + 2 * d["h"] * d["experts"]
                 + d["k"] * 3 * 2 * d["h"] * d["f"])
    return d["layers"] * per_layer


def prefill_flops(model: dict, prompt_tokens: int) -> float:
    """One prompt prefilled from empty: the routed matmuls of every token,
    causal attention (QK^T and PV over the positions at or before each:
    2 x 2 x q_dim x S(S+1)/2 a layer), and one LM-head projection."""
    d = _dims(model)
    s = int(prompt_tokens)
    attention = d["layers"] * 4 * d["q"] * s * (s + 1) / 2
    return (s * routed_matmul_flops_per_token(model) + attention
            + 2 * d["h"] * d["vocab"])
