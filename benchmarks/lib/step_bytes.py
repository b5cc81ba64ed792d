"""Bytes one decode step must read from HBM, computed from shapes alone.

The least a step of this decoder family can read, per chip: every weight the
step multiplies by (the transformer's matrices, their quantisation scales, the
norms, the QKV biases, the LM head), plus the keys and values of the tokens
that are live in the cache and their scales. The embedding is a gather of one
row per slot and is counted as that. Activations are not counted: they are
small beside the weights and live in fast memory between fused operations.

`model` is the model section of a benchmark configuration file (the published
config.json keys); `serving` its serving section. Under tensor parallelism of
degree `tp` each chip holds 1/tp of every matrix and of the KV heads.
"""

from __future__ import annotations


def _dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[name]


def weight_bytes(model: dict, serving: dict) -> int:
    """Bytes of weights (and scales) one decode step streams, whole model."""
    h = model["hidden_size"]
    layers = model["num_hidden_layers"]
    head = model.get("head_dim") or h // model["num_attention_heads"]
    q_dim = model["num_attention_heads"] * head
    kv_dim = model["num_key_value_heads"] * head
    f = model["intermediate_size"]
    vocab = model["vocab_size"]
    act = _dtype_bytes(serving["dtype"])
    int8 = serving.get("quantization") == "int8"
    w = 1 if int8 else act
    # (contraction, output) of each matrix in a layer
    mats = [(h, q_dim), (h, kv_dim), (h, kv_dim), (q_dim, h),
            (h, f), (h, f), (f, h)]
    per_layer = sum(k * n * w for k, n in mats)
    if int8:
        per_layer += sum(n * 4 for _, n in mats)  # f32 scale per out channel
    per_layer += 2 * h * act                      # the two RMSNorm weights
    if model.get("attention_bias"):
        per_layer += (q_dim + 2 * kv_dim) * act
    total = layers * per_layer + h * act          # + final norm
    if not model.get("tie_word_embeddings"):
        total += h * vocab * w + (vocab * 4 if int8 else 0)
    else:
        total += h * vocab * act
    return total


def kv_bytes_per_token(model: dict, serving: dict) -> int:
    """Bytes of cache one live token occupies (K and V, all layers), with the
    per-(token, head) f32 scales of the int8 cache."""
    head = model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"])
    kv_heads = model["num_key_value_heads"]
    layers = model["num_hidden_layers"]
    if serving.get("kv_quantization") == "int8":
        return layers * 2 * kv_heads * (head * 1 + 4)
    return layers * 2 * kv_heads * head * _dtype_bytes(serving["dtype"])


def decode_step_bytes(model: dict, serving: dict, live_tokens: float,
                      live_slots: float) -> float:
    """Per chip: weights/tp + live KV/tp + one embedding row per live slot."""
    tp = int((serving.get("mesh") or {}).get("model", 1))
    act = _dtype_bytes(serving["dtype"])
    return (weight_bytes(model, serving) / tp
            + live_tokens * kv_bytes_per_token(model, serving) / tp
            + live_slots * model["hidden_size"] * act)
