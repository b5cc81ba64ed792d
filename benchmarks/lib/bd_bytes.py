"""Bytes and operations of a sparse-expert GQA decoder that GENERATES BY
DIFFUSION OVER BLOCKS (SDAR family), computed from shapes alone: what ONE
decode forward — every slot's block of `block` positions through the trunk —
must move through HBM and compute, and the floating-point operations the
ACTIVE mathematics needs to admit a prompt (its whole blocks under the block
mask, then the opening block's forwards): k experts of `num_experts`,
attention over the keys a position sees. A forward is near both roofs (512
positions a forward at 128 slots: every expert's weights read once, 4,096
routed pairs computed), so both counts are kept. LIVE slots alone are
counted: an idle slot's block is work the program does and nobody needs. A
program that reads a padded cache, or computes masked pairs or idle slots,
does more than this and so reads UNDER what its time would suggest.

THE EXPERT TERM OF `forward_bytes` IS AN UPPER COUNT: it prices the experts
that UNIFORM routing of the live positions' pairs would hit (all 128 at 4,096
pairs). The program keeps no count of the distinct experts a layer's forward
hit (`stats.engine.moe.expert_pairs` sums pairs per expert over layers and
forwards), and this traffic routes far from evenly — a masked position enters
layer 0 as one embedding row, so a block's positions choose alike
(`moe_expert_imbalance.bd` ~3); `moe_gmm`, which reads the hit experts alone,
was timed UNDER the all-experts stream time (PERF.md section 7). So
`bd_decode_hbm_share` is a share of an upper count of the bytes — it reads at
or above the true share — and NO kernel-level roofline may be built on this
term: `moe_gmm`'s would read over 100%. A per-layer hit count (`pairs > 0`
summed in `models/moe.py moe_mlp`) would make it exact.

`model` is the model section of a benchmark configuration file (the published
config.json keys, cut as its `reduced` says); `serving` its `tpu` section;
`block` the block length (the file's `assumed`; the program reports it as
`startup.diffusion.block`). One chip: nothing here is sharded.
"""

from __future__ import annotations

from lib.moe_bytes import _matrix_bytes, experts_hit
from lib.step_bytes import _dtype_bytes


def _dims(model: dict) -> dict:
    head = model["head_dim"]
    return {"h": model["hidden_size"], "head": head,
            "q": model["num_attention_heads"] * head,
            "kv": model["num_key_value_heads"] * head,
            "kv_heads": model["num_key_value_heads"],
            "f": model["moe_intermediate_size"],
            "experts": model["num_experts"],
            "k": model["num_experts_per_tok"],
            "vocab": model["vocab_size"],
            "layers": model["num_hidden_layers"]}


def mixer_weight_bytes(model: dict, serving: dict) -> int:
    """One layer's attention: wq, wk, wv, wo (quantised), the layer norm and
    the two per-head norms."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    return (sum(_matrix_bytes(k, n, serving) for k, n in (
        (d["h"], d["q"]), (d["h"], d["kv"]), (d["h"], d["kv"]),
        (d["q"], d["h"]))) + (d["h"] + 2 * d["head"]) * act)


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


def kv_row_bytes(model: dict, serving: dict) -> int:
    """K and V of one position in ONE layer, with the int8 cache's f32
    scale per (position, head)."""
    d = _dims(model)
    if serving.get("kv_quantization") == "int8":
        return 2 * d["kv_heads"] * (d["head"] + 4)
    return 2 * d["kv"] * _dtype_bytes(serving["dtype"])


def cache_bytes_per_token(model: dict, serving: dict) -> int:
    return _dims(model)["layers"] * kv_row_bytes(model, serving)


def head_bytes(model: dict, serving: dict) -> int:
    d = _dims(model)
    if model.get("tie_word_embeddings"):
        return d["h"] * d["vocab"] * _dtype_bytes(serving["dtype"])
    return _matrix_bytes(d["h"], d["vocab"], serving)


def forward_bytes(model: dict, serving: dict, lengths, block: int,
                  head_share: float = 1.0) -> float:
    """ONE decode forward over the LIVE slots of `lengths` (each its
    committed positions), `block` positions a slot: every layer's mixer
    weights, the experts the live positions' pairs would hit under UNIFORM
    routing (an upper count: the module's docstring), router and norms; the
    head and the final norm times `head_share` (a denoise forward reads
    them, a commit forward does not: steps / (steps + 1) over a block's
    forwards); for each live slot every committed position's K/V rows and
    scales a layer, the block's own rows written and read back, and one
    embedding row a position. Idle slots' blocks are not counted."""
    d = _dims(model)
    act = _dtype_bytes(serving["dtype"])
    lengths = list(lengths)
    positions = len(lengths) * block
    hit = experts_hit(positions * d["k"], d["experts"])
    weights = (d["layers"] * (mixer_weight_bytes(model, serving)
                              + hit * expert_weight_bytes(model, serving)
                              + ffn_fixed_bytes(model, serving))
               + head_share * (d["h"] * act + head_bytes(model, serving)))
    row = d["layers"] * kv_row_bytes(model, serving)
    return (weights + sum(lengths) * row + 2 * positions * row
            + positions * d["h"] * act)


def active_flops_per_token(model: dict) -> int:
    """Multiply-adds x 2 of one token through the trunk, attention's scores
    and the head left out: per layer the four attention projections, the
    router and k experts."""
    d = _dims(model)
    per_layer = (2 * d["h"] * d["q"] + 2 * 2 * d["h"] * d["kv"]
                 + 2 * d["q"] * d["h"] + 2 * d["h"] * d["experts"]
                 + d["k"] * 3 * 2 * d["h"] * d["f"])
    return d["layers"] * per_layer


def head_flops_per_token(model: dict) -> int:
    d = _dims(model)
    return 2 * d["h"] * d["vocab"]


def forward_flops(model: dict, serving: dict, lengths, block: int,
                  head_share: float = 1.0) -> float:
    """ONE decode forward over the LIVE slots of `lengths`: every live
    position's active operations and `head_share` of a head row each;
    attention of each live slot's `block` queries over its committed
    positions and the block itself (QK^T and PV: 2 x 2 x q_dim a pair a
    layer). Idle slots' blocks are not counted."""
    d = _dims(model)
    lengths = list(lengths)
    positions = len(lengths) * block
    pairs = sum(block * (n + block) for n in lengths)
    return (positions * (active_flops_per_token(model)
                         + head_share * head_flops_per_token(model))
            + d["layers"] * 4 * d["q"] * pairs)


def block_pairs(tokens: int, block: int) -> int:
    """(query, key) pairs of `tokens` positions under the block mask: a
    position sees every position up to the end of its own block that
    exists."""
    s, b = int(tokens), int(block)
    return sum(min(s, (i // b + 1) * b) for i in range(s))


def prefill_flops(model: dict, prompt_tokens: int, block: int,
                  steps: int) -> float:
    """One prompt admitted: its whole blocks prefilled under the block mask
    (every token's active operations, attention over `block_pairs`; no head
    row: nothing is sampled from a prompt position), then the opening
    block's `steps` denoise forwards and its commit forward — `block`
    positions each, over the whole blocks and the block itself, a head row
    a position in the denoise forwards."""
    d = _dims(model)
    whole = int(prompt_tokens) // block * block
    attention = d["layers"] * 4 * d["q"]
    prefill = (whole * active_flops_per_token(model)
               + attention * block_pairs(whole, block))
    one = (block * active_flops_per_token(model)
           + attention * block * (whole + block))
    return (prefill + (steps + 1) * one
            + steps * block * head_flops_per_token(model))
