"""Bytes and operations of a decoder with LATENT attention (HF `deepseek_v3`
without a query latent: one cached row of `kv_lora_rank + qk_rope_head_dim`
values a position and layer for every head), a leading dense layer,
sigmoid-routed experts and shared experts — computed from shapes and the
program's counters alone: what one decode step MUST move through HBM (every
weight outside the routed experts once, the experts the step's pairs hit,
each live latent row once), what one call of the decode kernel must move,
and the floating-point operations the ACTIVE mathematics needs to prefill a
prompt in the EXPANDED form. Each is a lower count of what a program does:
the row is priced at its 576 values (the chip holds it in 640 lanes), the
prefill at keys of 192 and values of 128 over the causal pairs (a kernel
that pads a width or computes a whole diagonal block does more), the
absorbed factors `W_UK` / `W_UV` at the bytes of the `kv_b_proj` they are
derived from — so no share built on them can read over 100% of a peak. The
one term that is an expectation and no bound is the experts a step hits:
`experts_hit` of uniformly routed pairs, as `lib/moe_bytes.py` and
`lib/dsa_bytes.py` price it.

`model` is the model section of a benchmark configuration file (the published
config.json keys, cut as its `reduced` says); `serving` its `tpu` section.
One chip: nothing here is sharded.
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes


def _dims(model: dict) -> dict:
    heads = model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    layers = model["num_hidden_layers"]
    dense = min(model.get("first_k_dense_replace", 0), layers)
    return {"h": model["hidden_size"], "heads": heads, "nope": nope,
            "rope": rope, "v": model["v_head_dim"],
            "rank": model["kv_lora_rank"],
            "row": model["kv_lora_rank"] + rope,
            "q": heads * (nope + rope),
            "kvb": heads * (nope + model["v_head_dim"]),
            "o": heads * model["v_head_dim"],
            "f": model["moe_intermediate_size"],
            "fd": model["intermediate_size"],
            "fs": model.get("n_shared_experts", 0)
            * model["moe_intermediate_size"],
            "experts": model["n_routed_experts"],
            "k": model["num_experts_per_tok"],
            "vocab": model["vocab_size"], "layers": layers,
            "dense": dense, "moe": layers - dense}


def attention_weight_bytes(model: dict, serving: dict) -> int:
    """One layer's attention: `q_proj`, `kv_a_proj_with_mqa`, `kv_b_proj`
    and `o_proj` (quantised), the layer norm and `kv_a_layernorm`. A decode
    step multiplies by `kv_b_proj`'s content once, as the absorbed factors."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["row"]), (d["rank"], d["kvb"]),
        (d["o"], d["h"]))) + (d["h"] + d["rank"]) * act)


def dense_ffn_bytes(model: dict, serving: dict) -> int:
    """A leading dense layer's FFN and its norm."""
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
    """What every token reads of one expert layer's FFN beside its routed
    experts: the router (activation dtype) and its float32 selection bias,
    the norm, and the shared experts' one FFN."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    shared = (2 * _matrix_bytes(d["h"], d["fs"], serving)
              + _matrix_bytes(d["fs"], d["h"], serving)) if d["fs"] else 0
    return d["h"] * d["experts"] * act + d["experts"] * 4 + d["h"] * act \
        + shared


def head_bytes(model: dict, serving: dict) -> int:
    d = _dims(model)
    if model.get("tie_word_embeddings"):
        return d["h"] * d["vocab"] * _dtype_bytes(serving["dtype"])
    return _matrix_bytes(d["h"], d["vocab"], serving)


def weight_bytes(model: dict, serving: dict) -> int:
    """The whole model as the chip holds it: every layer and every expert,
    the embedding in the activation dtype, the head, the final norm (the
    bfloat16 absorbed factors, a second form of `kv_b_proj`, left out)."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (d["layers"] * attention_weight_bytes(model, serving)
            + d["dense"] * dense_ffn_bytes(model, serving)
            + d["moe"] * (d["experts"] * expert_weight_bytes(model, serving)
                          + moe_fixed_bytes(model, serving))
            + d["vocab"] * d["h"] * act + head_bytes(model, serving)
            + d["h"] * act)


def latent_row_bytes(model: dict, serving: dict) -> int:
    """One cached position in ONE layer: its 576 VALUES (what must move; the
    chip holds them in 640 lanes)."""
    return _dims(model)["row"] * _dtype_bytes(serving["dtype"])


def cache_bytes_per_token(model: dict, serving: dict,
                          lanes: int = 128) -> int:
    """What one cached position HOLDS on the chip, every layer: the row in
    whole lane tiles."""
    d = _dims(model)
    return (d["layers"] * -(-d["row"] // lanes) * lanes
            * _dtype_bytes(serving["dtype"]))


def latent_step_bytes(model: dict, serving: dict,
                      live_positions: float) -> float:
    """The latent rows one decode step must read: each live position's row
    once a layer."""
    return (live_positions * _dims(model)["layers"]
            * latent_row_bytes(model, serving))


def decode_step_bytes(model: dict, serving: dict, live_positions: float,
                      live_slots: float) -> float:
    """One decode step over ALL slots of the engine: every layer's
    attention weights, the dense layer's FFN, per expert layer the experts
    the live slots' pairs hit (uniform routing) and what every token reads
    beside them, the head and the final norm; each live latent row once a
    layer; one embedding row a live slot."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    hit = experts_hit(live_slots * d["k"], d["experts"])
    weights = (d["layers"] * attention_weight_bytes(model, serving)
               + d["dense"] * dense_ffn_bytes(model, serving)
               + d["moe"] * (hit * expert_weight_bytes(model, serving)
                             + moe_fixed_bytes(model, serving))
               + d["h"] * act + head_bytes(model, serving))
    return (weights + latent_step_bytes(model, serving, live_positions)
            + live_slots * d["h"] * act)


def kernel_bytes(model: dict, serving: dict, live_positions: float,
                 slots: int) -> float:
    """What ONE call of the decode kernel (one layer of one step) must
    move: each live row once, the queries in and the latent-space outputs
    back (`slots` x heads x (row + rank))."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (live_positions * latent_row_bytes(model, serving)
            + slots * d["heads"] * (d["row"] + d["rank"]) * act)


def kernel_flops(model: dict, live_positions: float) -> float:
    """The same call's operations: every head's score against each live
    row (row values) and its weighted sum of the row's latent (rank)."""
    d = _dims(model)
    return 2.0 * live_positions * d["heads"] * (d["row"] + d["rank"])


def active_flops_per_token(model: dict) -> int:
    """Multiply-adds x 2 of one token through the trunk in the EXPANDED
    form, attention's pairs and the head left out: per layer the four
    attention projections (`kv_b_proj` applied to the token's own latent);
    the dense layers' FFN; per expert layer the router, k experts and the
    shared experts."""
    d = _dims(model)
    attn = 2 * (d["h"] * d["q"] + d["h"] * d["row"] + d["rank"] * d["kvb"]
                + d["o"] * d["h"])
    dense = 3 * 2 * d["h"] * d["fd"]
    moe = (2 * d["h"] * d["experts"] + d["k"] * 3 * 2 * d["h"] * d["f"]
           + 3 * 2 * d["h"] * d["fs"])
    return d["layers"] * attn + d["dense"] * dense + d["moe"] * moe


def causal_pairs(prompt_tokens: int) -> int:
    s = int(prompt_tokens)
    return s * (s + 1) // 2


def attention_flops(model: dict, prompt_tokens: int) -> float:
    """Expanded attention over a prompt's causal pairs: a score over
    nope + rope channels and a weighted sum over v, every head and layer."""
    d = _dims(model)
    return (2.0 * d["layers"] * d["heads"]
            * (d["nope"] + d["rope"] + d["v"]) * causal_pairs(prompt_tokens))


def flash_call_flops(model: dict, prompt_tokens: int) -> float:
    """ONE call of the prefill attention kernel (`flash_wide`: one layer of
    one dispatch) over a prompt of `prompt_tokens`: every head's scores and
    weighted values over the causal pairs alone — the rest of a diagonal
    tile, a width's padding (keys of 192 in 256 lanes) and the bucket's
    padding are time spent and no work counted."""
    d = _dims(model)
    return attention_flops(model, prompt_tokens) / d["layers"]


def prefill_flops(model: dict, prompt_tokens: int) -> float:
    """One prompt prefilled from empty: every token's active operations,
    the expanded attention over the causal pairs, one LM-head row."""
    d = _dims(model)
    return (int(prompt_tokens) * active_flops_per_token(model)
            + attention_flops(model, prompt_tokens)
            + 2 * d["h"] * d["vocab"])
