"""Mixture-of-experts FFN (mixtral family): two lossless forms, chosen by
token count.

The block is HF `MixtralSparseMoeBlock`: router logits in float32, top-k
experts per token, softmax over the k selected logits, and

    y = sum_e gate_e * wd_e(silu(wg_e x) * wu_e x)      over the k selected.

Neither form drops a (token, expert) pair, and there is no capacity:

- ROUTED (`_routed_ffn`; programs outside the band of token counts the
  mixture keeps at this routing shape: `moe_route`). The T*k pairs are
  sorted by expert, so each expert's rows are one contiguous group; the
  three expert matmuls run over the groups —
  T*k rows whatever the routing: static shapes, FLOPs of k experts and not
  of all of them — the rows are un-sorted by a gather and combined with the
  gates in float32. The grouped matmul (`_grouped_matmul`) is
  * the Pallas kernel `moe_gmm` (ops/gmm.py) for an int8 stack where the
    expert FFN is traced on one device: it reads each HIT expert's int8
    tile once, where it lies in the layers' stack, widens it in VMEM and
    multiplies only the row tiles the expert's group touches — two calls a
    gated layer: gate and up share one (a visit copies both tiles and
    writes act(g) * u), down is the other;
  * `jax.lax.ragged_dot` (XLA:TPU's native grouped matmul) everywhere else:
    under a mesh (each shard of mixtral-8x7b on `model: 4`), for bf16 /
    float32 stacks, for a shape the kernel cannot tile. It is also the
    kernel's reference in the tests.
- DENSE MIXTURE (`_dense_mixture`; programs inside the shape's band: under
  the crossing, and at 128 experts top 8 from 64 tokens up). Every
  expert computes every token as one batched matmul and the gates, zero
  outside the top k, weight the combine: X/k times the FLOPs, the same
  weight bytes. A decode step of B slots x k pairs hits every expert of a
  few dozen anyway, so all expert weights stream from HBM either way.

Which form where is a measurement, not a taste (`tools/moe_decode_ab.py`:
`ROUTED_MIN_TOKENS` and `ROUTED_FROM` below hold the readings).
`moe_route(T, experts, k)` is that choice, from the shape alone;
`startup.moe` reports it per program, and what the grouped matmul runs as.
A config with `shared_intermediate_size` adds a shared expert (`sg`, `su`,
`sd`: one dense gated FFN every token passes through) to the routed sum,
weighted by sigmoid(x . sgate) where the layer has that column (qwen3_next).

lfm2_moe's router (HF `Lfm2MoeSparseMoeBlock`) is another form:
`route_top_k(score="sigmoid")` — sigmoid scores, selection by score + the
layer's `expert_bias`, gates the selected's UNBIASED scores renormalised —
chosen by the config (`routing_of`), the softmax form the default; both
expert forms take their gates from it.

qwen3_next's router (HF `Qwen3NextSparseMoeBlock`, `norm_topk_prob` true)
takes the softmax over ALL the router logits, keeps the k largest and
renormalises them to sum 1. That IS `route_top_k`'s softmax over the k
selected logits — e^{l_i} / sum_{j in top} e^{l_j} either way: the full
softmax's denominator cancels, and the k largest probabilities are the k
largest logits — so the router needs no second form
(`tests/test_gdn.py` holds the two to each other).

int8 expert stacks stay int8 in HBM in both forms: the int8 payload is the
dot's operand, the per-(expert, column) scale is applied to the float32
accumulator (row by row in the routed form: each row knows its expert).

Sharding (`tp_mesh` with `model` > 1): every expert's FFN width is split
over `model` (all experts on every chip) and the FFN runs per shard inside
one `shard_map` — each chip routes identically (the router and the
activations are replicated over `model`), computes its slice of every
expert, combines, and one `psum` over `model` adds the partial [T, D] rows:
the dense-TP collective pattern, no all-to-all, no straggler when routing is
uneven. Without `tp_mesh`, or with an `expert` axis > 1, the same function
is traced bare and GSPMD partitions it from the shardings (exact; not
measured on a chip — PERF.md).

A chip's share of the experts (`config.experts_held` = (first, count);
nemotron_h's cell: 32 of 128, one chip of a four-chip host that divides each
layer's experts four ways): the router keeps its width and its top k, and
the expert leaves hold the `count` held experts alone. A pair whose expert
is not held is dropped BEFORE the routed form's sort — it is given the
group past the last, no group size counts it, and the grouped matmul never
visits its row — and masked out of the mixture's gates; the gates are those
of the routing over ALL experts, never renormalised over the held ones, so
the parts the shares compute add up to the uncut layer
(tests/test_nemotron_h.py); a token with no held expert gets the shared
expert alone. Nothing stands in for the absent chips or their exchange.
Every other model holds all it routes over and traces what it traced.

Ungated experts (`config.gated_ffn` False; nemotron_h's relu2): an expert —
and the shared one — is act(x W_up) W_down: the `wg` / `sg` leaves are
absent and both forms skip the gate product.

Counting: `moe_mlp` also returns the per-expert number of VALID pairs it
computed ([experts] int32; padded prompt positions are left out; a share
appends the number of held experts hit: `_count_pairs`), which
`models/llama.py _layer` adds to `KVCache.expert_pairs` where the cache
carries that counter (the engine's does; `stats.engine.moe`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from symmetry_tpu.ops import gmm
from symmetry_tpu.ops.interpret import interpret_mode
from symmetry_tpu.ops.quant import QuantizedTensor, qmatmul


# Under a mesh the grouped matmul is `lax.ragged_dot`, and programs of fewer
# tokens take the dense mixture — mixtral's 8 experts top 2, where it was
# measured (PERF.md, PR 28; one chip's share of mixtral-8x7b under
# `model: 4`, ms a layer, routed / dense): 64 tokens 1.35 / 0.49 (the weight
# stream's floor is 0.43), 256 tokens 2.85 / 1.04, 768 tokens 3.69 / 3.24,
# 1,024 tokens 4.00 / 3.93, 1,280 tokens 4.42 / 4.91, 2,048 tokens 5.98 /
# 7.93, 4,096 tokens 10.7 / 15.8. The mixed int8 dot runs the dense form at
# ~90% of the MXU peak, `ragged_dot` with an int8 operand reaches ~35%, so
# routing pays only once it saves more than it wastes: they cross at about
# 1,050 tokens. Any routing shape without a reading of its own takes this.
ROUTED_MIN_TOKENS = 1024
# The one-chip routing shapes, each from its own reading of
# tools/moe_decode_ab.py with the kernel under the routed form (PERF.md,
# PR 36; a v5e; ms a layer: routed over `ragged_dot` / routed over the
# kernel / dense mixture): (experts, k) -> the BAND of tokens a dispatch
# the dense mixture keeps, `(lo, hi)`: the mixture iff lo <= tokens < hi,
# routed on either side of it. `hi` is the crossing each table below ends
# in (the first size from which routing wins at every larger one); `lo` is
# 0 — the mixture all the way down — for every shape whose low end has no
# reading that says otherwise, and the first count from which the MIXTURE
# wins at every larger one up to `hi` where it has ((128, 8), PR 57: few
# tokens hit few experts, and the kernel reads the hit ones alone).
# 72 top 10 at expert width 768 (granite-4.0-h-small; `--shape
# 72,10,4096,768`; the weight stream's floor is 0.83): 16 tokens 4.03 /
# 0.89 / 0.96, 64: 4.75 / 1.06 / 0.94, 128: 5.51 / 1.17 / 1.14, 256: 7.67 /
# 1.38 / 2.14, 512: 9.09 / 1.87 / 4.34, 1,024: 12.03 / 3.35 / 8.10, 2,048:
# 18.10 / 5.76 / 15.98. At 64-128 tokens every expert is hit and the
# mixture streams them at 73-88% of the chip's rate; from 256 its 7.2x
# FLOPs cost more than the kernel's visits: they cross between 128 and 256
# tokens and no dispatch lies between (batches double), so decode (128
# slots) keeps the mixture (a tie within 2% there) and a prefill of 256
# tokens or more is routed. (At 1,024 tokens the kernels are 2.3 ms of the
# 3.35; the rest is the sort, the row gather and the combine.)
# 512 top 10 at expert width 512 (qwen3-next-80b-a3b; `--shape
# 512,10,2048,512`; floor 1.97 for all 512 experts): 16 tokens 5.83 / 0.66
# / 2.17, 32: 6.61 / 1.12 / 2.18, 64: 7.94 / 1.72 / 2.17, 128: 10.42 / 2.15
# / 2.81, 256: 14.73 / 2.44 / 4.91, 512: 15.19 / 2.73 / 9.90, 1,024: 16.48
# / 3.28 / 20.08, 2,048: 19.39 / 5.28 / 49.80. The kernel reads the HIT
# experts alone (~140 of 512 at 16 tokens, ~470 at 128) where the mixture
# reads all of them and computes 51x the FLOPs, so it wins at every size,
# decode's 128 tokens among them (by 24%): the crossing is under the
# smallest dispatch there is. (`ragged_dot` over 512 groups costs 6-15 ms
# before its first useful row.)
# 128 top 8 at expert width 768 (keye-vl-2.0-30b-a3b; `--shape
# 128,8,2048,768`; PERF.md, PR 40; floor 0.74 for all 128 experts): 32
# tokens 4.30 / 0.77 / 0.83, 64: 6.15 / 0.88 / 0.83, 128: 6.34 / 0.95 /
# 1.00, 256: 6.49 / 1.07 / 1.90, 512: 6.77 / 1.31 / 3.83, 2,048: 10.24 /
# 3.06 / 14.94, 8,192: 23.30 / 11.01 / 59.38. At 64 tokens (decode's 64
# slots: 512 pairs hit 126 of the 128 experts) the mixture's 16x FLOPs
# still hide behind the weight stream (89% of the floor) and it wins by
# 6%; from 128 the kernel does. They cross between 64 and 128 and no
# dispatch lies between: decode keeps the mixture, every prefill of the
# long-document cell (1,024 tokens and up) is routed.
# The same shape's LOW end (sdar-30b-a3b-chat's opening blocks: 1-16 rows x
# 4 positions; PERF.md, PR 57; three repeats within 0.5%): 4 tokens 2.32 /
# 0.24 / 0.82, 8: 2.67 / 0.37 / 0.82, 16: 3.32 / 0.59 / 0.82, 32: 4.28 /
# 0.77 / 0.82, 64: 6.20 / 0.88 / 0.82, 128: 6.33 / 0.95 / 0.99. The mixture
# streams all 128 experts whatever the tokens (0.82 ms: 90% of the floor);
# the kernel reads the hit ones alone — 32 pairs hit ~29, three calls of
# 0.066 ms at 84% of THEIR bytes' stream, and the sort, the gathers and the
# combine are 0.03 ms a layer beside them — so it wins by 3.5x at 4 tokens,
# 2.2x at 8, 29% at 16 and 6% at 32, and loses by 7% at 64: the mixture's
# band is [64, 128). Under routing that is not uniform the kernel reads
# fewer still (eight tokens that route alike, as an admission's pad rows
# and masked positions do: 0.10 ms a layer).
# 32 top 4 at expert width 1,792 (lfm2-8b-a1b; `--shape 32,4,2048,1792`;
# PERF.md, PR 42; floor 0.43 for all 32 experts): 16 tokens 1.41 / 0.47 /
# 0.49, 32: 1.73 / 0.52 / 0.49, 64: 2.25 / 0.55 / 0.49, 128: 3.23 / 0.60 /
# 0.58, 256: 3.35 / 0.68 / 1.00, 512: 3.71 / 0.87 / 2.12, 1,024: 4.43 /
# 1.21 / 4.22, 2,048: 5.87 / 1.88 / 8.06. From 32 tokens every expert is
# hit; the mixture's 8x FLOPs hide behind the weight stream up to 128
# tokens (74-88% of the floor) and it wins there by 3-10%; at 256 its
# matmuls are the longer pole. They cross between 128 and 256 and no
# dispatch lies between: decode (128 slots) keeps the mixture, a prefill
# dispatch of 256 tokens or more is routed.
# 128 top 6 at expert width 768 (kanana-2-30b-a3b; `--shape 128,6,2048,768`;
# PERF.md, PR 54; floor 0.74 for all 128 experts): 32 tokens 3.40 / 0.68 /
# 0.83, 64: 4.01 / 0.84 / 0.83, 128: 4.71 / 0.93 / 1.00, 256: 6.44 / 1.01 /
# 1.90, 512: 6.64 / 1.18 / 3.83, 2,048: 8.98 / 2.70 / 14.93, 8,192: 20.87 /
# 8.64 / 59.38. At 32 tokens 192 pairs hit ~100 of the 128 experts and the
# kernel, reading those alone, wins by 18%; at 64 (decode's 64 slots: 384
# pairs hit ~122) the two tie within 1.2%, the mixture's 21x FLOPs still
# behind the weight stream (89% of the floor); from 128 the kernel wins (7%,
# then 1.9x at 256). One threshold cannot say "routed at 32, either at 64":
# the tie keeps the mixture, as granite's did, and the crossing is the
# first size from which routing wins at every larger one — decode keeps the
# mixture, every prefill of the report cell (6,912 tokens and up) is routed.
# 64 top 6 at expert width 768 (smallthinker-21b-a3b; `--shape
# 64,6,2560,768`; PERF.md, PR 58; floor 0.46 for all 64 experts): 16 tokens
# 2.19 / 0.49 / 0.53, 32: 2.40 / 0.56 / 0.53, 64: 2.60 / 0.59 / 0.54, 128:
# 3.01 / 0.633 / 0.633, 256: 4.10 / 0.73 / 1.10, 512: 4.35 / 0.92 / 2.25,
# 1,024: 5.32 / 1.29 / 4.65, 2,048: 7.82 / 2.38 / 8.98, 8,192: 21.16 /
# 10.96 / 35.79. At 16 tokens 96 pairs hit ~50 of the 64 experts and the
# kernel, reading those alone, wins by 7%; from 32 every expert is hit and
# the mixture's 10.7x FLOPs hide behind the weight stream (86% of the
# floor): it wins by 4% at 32 and by 10% at 64 (decode's 64 slots), ties at
# 128 (the tie keeps the mixture, as granite's and kanana's did) and loses
# by 1.5x at 256: the band is [32, 256) — decode keeps the mixture, every
# prefill of the draft cell (1,024 tokens and up) is routed.
# A SHARE of the experts routed over is keyed by what is HELD and measured,
# (held, k, routed over) — `moe_route(held=)`: with a share, "experts" alone
# would not say which. 32 held of 128, top 6, at expert width 1,856 stored
# as 1,920, TWO matrices (nemotron-3-nano-30b-a3b; `--shape 128,6,2688,1920
# --held 32 --ungated relu2`; PERF.md, PR 61; floor 0.40 for the 32 held
# experts' 330 MB): 16 tokens 3.82 / 0.31 / 0.52, 32: 5.21 / 0.44 / 0.52,
# 64: 7.06 / 0.55 / 0.52, 128: 7.53 / 0.59 / 0.55, 256: 8.42 / 0.67 / 0.96,
# 512: 8.79 / 0.82 / 1.94, 1,024: 9.92 / 1.21 / 4.48, 2,048: 12.84 / 2.46 /
# 7.72, 8,192: 30.67 / 11.76 / 30.79. A quarter of the pairs fall on held
# experts (24 of 96 at 16 tokens: ~17 of the 32 hit, and the kernel reads
# those alone; 96 of 384 at decode's 64 slots: ~30 hit), so the kernel wins
# under 64 tokens and from 256; between, the tool has the mixture ahead by
# 5-6% (0.52 against 0.55 at 64). IN THE TRUNK that reading does not hold:
# there XLA copies each layer's two [32, 2688, 1920] slices OUT of the
# layers' stack before the mixed dot (375 MB of temporaries in the decode
# program compiled for a v5e; the cell's first traced run read 46.5 ms a
# decode step, ~1.4 ms an expert layer, where the tool's scan of two layers
# read 0.52), and the kernel reads the stack where it lies. So no band: the
# share is routed at every size (as (512, 10) is), and the mixture's slice
# copy is the cell's first `perf_opt` question, not this entry's.
# 16 held of 128, top 8, at expert width 2,048, three matrices
# (k-exaone-236b-a23b; PR 65): NOT measured by the tool — the share takes
# nemotron's finding as it stands (in the trunk the mixture copies each
# layer's [16, 6144, 2048] slices out of the stack before its mixed dots,
# 0.6 GB a layer, and computes 16x the FLOPs of the 8 pairs a held expert
# sees a step; the kernel reads the stack where it lies), so no band: routed
# at every size. An A/B at this shape is an open question of PERF.md.
ROUTED_FROM = {(72, 10): (0, 256), (512, 10): (0, 1), (128, 8): (64, 128),
               (32, 4): (0, 256), (128, 6): (0, 128), (64, 6): (32, 256),
               (32, 6, 128): (0, 1), (16, 8, 128): (0, 1)}


def moe_route(n_tokens: int, experts: int = 8, k: int = 2,
              held: int | None = None) -> str:
    """The form a program of `n_tokens` tokens takes, from the shape alone:
    the dense mixture inside the measured band of this routing shape (or
    under mixtral's crossing where none was measured), routed outside it.
    `held`: the experts this chip holds where that is a share of the
    `experts` routed over — the key is then (held, k, experts): what is
    held and measured."""
    key = (experts, k) if held in (None, experts) else (held, k, experts)
    lo, hi = ROUTED_FROM.get(key, (0, ROUTED_MIN_TOKENS))
    return "dense-mixture" if lo <= n_tokens < hi else "routed"


def route_top_k(x: jnp.ndarray, router: jnp.ndarray, k: int, *,
                score: str = "softmax", bias: jnp.ndarray | None = None,
                scale: float = 1.0, eps: float = 1e-6
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[T, D] tokens -> (gates [T, k] float32, experts [T, k] int32).
    Logits accumulate and come out in float32; the softmax is over the k
    selected logits (mixtral: normalise AFTER selection).

    `score` "sigmoid" is lfm2_moe's router (HF `Lfm2MoeSparseMoeBlock`):
    scores sigmoid(logits); the k experts are those of the largest score +
    `bias` (the layer's `expert_bias` [X] float32; ties toward the lower
    index); the gates are the UNBIASED scores of the selected, divided by
    their sum + `eps` (`norm_topk_prob`), times `scale`. deepseek_v3's
    router (HF `DeepseekV3TopkRouter` at one group, `noaux_tc`) is this
    form with `bias` its `e_score_correction_bias`, `scale` its
    `routed_scaling_factor` and `eps` 1e-20."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_idx = jax.lax.top_k(
            scores if bias is None else scores + bias.astype(jnp.float32), k)
        top = jnp.take_along_axis(scores, top_idx, axis=-1)
        gates = top / (jnp.sum(top, axis=-1, keepdims=True) + eps)
        return gates * scale, top_idx.astype(jnp.int32)
    top_vals, top_idx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(top_vals, axis=-1), top_idx.astype(jnp.int32)


def routing_of(config, lp: dict, route_from=None) -> dict:
    """How this config and layer route and activate, for both expert forms:
    `route_top_k`'s keywords (lfm2_moe's and deepseek_v3's sigmoid router),
    `route_from` ([T, D]: the tensor the router reads where it is not the
    FFN's input — smallthinker's `router_input` "layer_input") and `act`
    (the gated activation where it is not silu: smallthinker's relu). Empty
    — the softmax router on the FFN's input under silu — for every other
    family."""
    out = {}
    if getattr(config, "router_score", "softmax") == "sigmoid":
        out = {"score": "sigmoid", "bias": lp.get("expert_bias"),
               "scale": config.routed_scaling_factor,
               "eps": config.router_norm_eps}
    if route_from is not None:
        out["route_from"] = route_from
    if config.hidden_act != "silu":
        out["act"] = config.hidden_act
    if getattr(config, "experts_held", None) is not None:
        out["held"] = tuple(config.experts_held)
    return out


ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
        "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True)}


def _route(x, router, k: int, routing) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`route_top_k` under `routing_of`'s answer: the router reads
    `route_from` where the layer gives one, else the FFN's input `x`."""
    kw = {name: v for name, v in (routing or {}).items()
          if name not in ("act", "held")}
    return route_top_k(kw.pop("route_from", x), router, k, **kw)


def _held(routing) -> tuple[int, int] | None:
    """(first, count) of the experts held where that is a share."""
    return (routing or {}).get("held")


def _act(routing):
    return ACTS[(routing or {}).get("act", "silu")]


def grouped_matmul_form(w, n_rows: int, one_device: bool = True) -> dict:
    """Which implementation the routed form's matmuls take against the
    expert leaf `w` ([.., X, A, F]) for `n_rows` rows (what
    `_grouped_matmul` routes by and `startup.moe` reports): the Pallas
    kernel (ops/gmm.py) with its row tile for an int8 stack on one device —
    "pallas-interpret" is the same kernel on the CPU backend — or
    `lax.ragged_dot` with the reason."""
    if not one_device:
        return {"form": "ragged_dot",
                "why": "the expert FFN is traced under a mesh"}
    if not isinstance(w, QuantizedTensor):
        return {"form": "ragged_dot", "why": "the expert stack is not int8"}
    A, F = w.q.shape[-2:]
    tiling = gmm.geometry(n_rows, A, F, interpret=interpret_mode())
    if tiling is None:
        return {"form": "ragged_dot",
                "why": f"no kernel geometry for experts of [{A}, {F}]"}
    return {"form": "pallas-interpret" if interpret_mode() else "pallas",
            "row_tile": tiling[0]}


def _grouped_matmul(rows: jnp.ndarray, w, group_sizes: jnp.ndarray,
                    row_expert: jnp.ndarray, at=None) -> jnp.ndarray:
    """rows [R, A] sorted by expert @ per-expert [X, A, F] -> [R, F] in
    float32. A QuantizedTensor keeps its int8 payload as the operand; its
    [X, F] scales go onto the accumulator, row by row. `at` = (the leaf's
    whole stack [L, X, A, F], this layer's index) where the expert FFN is
    traced on one device: the Pallas kernel addresses (layer, expert) in
    the stack as it lies (`w`, the layer's slice, is then not read)."""
    if at is not None and grouped_matmul_form(
            at[0], rows.shape[0])["form"] != "ragged_dot":
        stack, layer = at
        return gmm.grouped_matmul(rows, stack.q, stack.scale, group_sizes,
                                  layer, interpret=interpret_mode())
    if isinstance(w, QuantizedTensor):
        y = jax.lax.ragged_dot(rows, w.q, group_sizes,
                               preferred_element_type=jnp.float32)
        return y * jnp.take(w.scale, row_expert, axis=0)
    return jax.lax.ragged_dot(rows, w, group_sizes,
                              preferred_element_type=jnp.float32)


def _routed_ffn(x, valid, router, wg, wu, wd, k: int, at=None,
                routing=None):
    """x [T, D], valid [T] bool -> (y [T, D] float32, pairs [X] int32).
    Under shard_map this is one shard's program: wg/wu hold a slice of the
    FFN width, wd the matching rows, and y is that slice's partial sum.
    `at` = ({"wg", "wu", "wd"}: the whole stacks, this layer's index) on
    one device (`_grouped_matmul`), else None. `routing`: `route_top_k`'s
    keywords (`routing_of`)."""
    T, _ = x.shape
    X = router.shape[-1]
    gates, experts = _route(x, router, k, routing)        # [T, k]
    flat_expert = experts.reshape(-1)                     # [T*k]
    held = _held(routing)
    if held is not None:
        # a share: the held experts are groups 0 .. count - 1 of the local
        # stacks; a pair on an absent expert takes the group past the last,
        # sorts behind every held pair and is in no group size — dropped
        # here, before the sort — and its gate is zero in the combine
        first, X = held
        all_pairs = _count_pairs(experts, valid, router.shape[-1], held)
        mine = (experts >= first) & (experts < first + X)
        gates = jnp.where(mine, gates, 0.0)
        flat_expert = jnp.where(mine, experts - first, X).reshape(-1)
    # Stable sort: pairs of one expert keep token order, so the result
    # does not depend on how the sort breaks ties.
    order = jnp.argsort(flat_expert, stable=True)         # sorted -> pair
    row_expert = jnp.take(flat_expert, order)
    onehot = flat_expert[:, None] == jnp.arange(X, dtype=jnp.int32)
    group_sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    if held is None:
        pairs = jnp.sum(onehot & jnp.repeat(valid, k)[:, None], axis=0,
                        dtype=jnp.int32)
    else:   # counted over all the router scores; the scale gather in range
        pairs, row_expert = all_pairs, jnp.minimum(row_expert, X - 1)

    def grouped(rows, w, name):
        return _grouped_matmul(rows, w, group_sizes, row_expert,
                               at and (at[0][name], at[1]))

    rows = jnp.take(x, order // k, axis=0)                # [T*k, D]
    act = _act(routing)
    if wg is None:          # ungated: act(x W_up) W_down
        h = act(grouped(rows, wu, "wu"))
    elif at is not None and grouped_matmul_form(
            at[0]["wg"], T * k)["form"] != "ragged_dot":
        # gate and up (one shape, so one form) in ONE kernel call: a visit
        # copies the expert's gate tile AND its up tile, multiplies the
        # row tile it holds by both and writes act(g) * u once, formed in
        # float32 and rounded to x.dtype — half the visits of two calls,
        # and neither [T*k, F] float32 product goes to HBM and back
        gate, up = at[0]["wg"], at[0]["wu"]
        h = gmm.grouped_matmul(
            rows, (gate.q, up.q), (gate.scale, up.scale), group_sizes,
            at[1], act=act, interpret=interpret_mode())
    else:
        h = act(grouped(rows, wg, "wg")) * grouped(rows, wu, "wu")
    y = grouped(h.astype(x.dtype), wd, "wd")
    if held is not None:
        # rows past the held pairs were never written (ops/gmm.py): what
        # they hold is no number, and 0 x that is none either
        y = jnp.where((jnp.arange(T * k, dtype=jnp.int32)
                       < jnp.sum(group_sizes))[:, None], y, 0.0)

    # Un-sort by a gather (the inverse permutation), then the gated sum
    # over each token's k rows in float32: no scatter-add, so the sum has
    # one order.
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype))
    if at is None:
        y = jnp.take(y, inverse, axis=0).reshape(T, k, -1)
        return jnp.einsum("tkd,tk->td", y, gates), pairs
    # On one device the same sum a gather at a time: a token's j-th row
    # times its gate, added in the order j = 0 .. k - 1, in a loop XLA
    # cannot flatten — so the un-sorted [T*k, D] float32 copy and its
    # [T, k, D] relayout (k on sublanes: 10 pads to 16) never exist beside
    # the kernel's output: a 1,024-token prefill of granite-4.0-h-small
    # holds 521 MiB of temporaries for it where that form held 746 (the
    # mixture: 282), beside a chip 85% full whose largest free block is
    # 1.3 GB (PERF.md, PR 36). (The sharded trunk keeps the form above:
    # its lowered programs are held byte-identical in this PR.)
    slot = inverse.reshape(T, k)

    def add(j, out):
        row = jnp.take(y, jax.lax.dynamic_index_in_dim(slot, j, 1, False),
                       axis=0)
        return out + jax.lax.dynamic_index_in_dim(gates, j, 1) * row

    return jax.lax.fori_loop(
        0, k, add, jnp.zeros((T, y.shape[-1]), jnp.float32)), pairs


def _count_pairs(experts, valid, n_experts: int, held) -> jnp.ndarray:
    """What a SHARE counts: the valid (token, expert) pairs per expert of
    the `n_experts` the router scores, then (models/llama.py HELD_COUNTS)
    how many of the held experts a valid pair fell on — the experts this
    forward has to read: experts [T, k], valid [T] -> [n_experts + 1]
    int32."""
    onehot = experts[..., None] == jnp.arange(n_experts, dtype=jnp.int32)
    pairs = jnp.sum(onehot & valid[:, None, None], axis=(0, 1),
                    dtype=jnp.int32)
    hits = jnp.sum(pairs[held[0]:held[0] + held[1]] > 0, dtype=jnp.int32)
    return jnp.concatenate([pairs, hits[None]])


def _experts_dot(x: jnp.ndarray, w) -> jnp.ndarray:
    """[T, A] @ per-expert [X, A, F] -> [T, X, F], every expert at once."""
    if isinstance(w, QuantizedTensor):
        y = jax.lax.dot_general(x, w.q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return (y * w.scale).astype(x.dtype)
    return jnp.einsum("ta,xaf->txf", x, w)


def _dense_mixture(x, valid, router, wg, wu, wd, k: int, routing=None):
    """Same contract as `_routed_ffn`; every expert computes every token."""
    X = router.shape[-1]
    gates, experts = _route(x, router, k, routing)        # [T, k]
    held = _held(routing)
    if held is None:
        onehot = experts[..., None] == jnp.arange(X, dtype=jnp.int32)
    else:   # a share: the gates of the held experts' columns alone
        onehot = experts[..., None] == held[0] + jnp.arange(
            held[1], dtype=jnp.int32)
    dense_gates = jnp.sum(jnp.where(onehot, gates[..., None], 0.0), axis=1)
    if held is None:
        pairs = jnp.sum(onehot & valid[:, None, None], axis=(0, 1),
                        dtype=jnp.int32)
    else:
        pairs = _count_pairs(experts, valid, X, held)
    if wg is None:          # ungated: act(x W_up) W_down
        h = _act(routing)(_experts_dot(x, wu))                    # [T, X, F]
    else:
        h = _act(routing)(_experts_dot(x, wg)) * _experts_dot(x, wu)
    if isinstance(wd, QuantizedTensor):
        y = jax.lax.dot_general(h, wd.q, (((2,), (1,)), ((1,), (0,))),
                                preferred_element_type=jnp.float32)
        y = y * wd.scale[:, None, :]                      # [X, T, D]
    else:
        y = jnp.einsum("txf,xfd->xtd", h, wd,
                       preferred_element_type=jnp.float32)
    return jnp.einsum("xtd,tx->td", y, dense_gates), pairs


def _expert_ffn(x, valid, router, wg, wu, wd, k: int, at=None,
                routing=None):
    """x [T, D] -> (y [T, D] float32, valid pairs [X]) by the form this
    token count takes; `at` and `routing` as `_routed_ffn`'s."""
    held = _held(routing)
    if moe_route(x.shape[0], router.shape[-1], k,
                 held and held[1]) == "routed":
        return _routed_ffn(x, valid, router, wg, wu, wd, k, at, routing)
    return _dense_mixture(x, valid, router, wg, wu, wd, k, routing)


def _model_shards(tp_mesh, ffn_width: int) -> int:
    """How many ways `model` splits the FFN width inside a shard_map, or 1
    when the expert FFN is traced bare (no mesh, an `expert` axis, or a
    width that does not divide)."""
    if tp_mesh is None:
        return 1
    shape = dict(tp_mesh.shape)
    n = shape.get("model", 1)
    if shape.get("expert", 1) > 1 or ffn_width % n:
        return 1
    return n


def moe_layout(tp_mesh, ffn_width: int) -> str:
    """One line for `startup.moe`: where the expert weights live."""
    n = _model_shards(tp_mesh, ffn_width)
    if n > 1:
        return (f"every expert's FFN width split {n} ways over `model` "
                f"({ffn_width // n} columns a chip), all experts on every "
                f"chip, one psum after the combine (shard_map)")
    if tp_mesh is None:
        return "one device holds every expert whole"
    return "GSPMD partitions the expert FFN from the parameter shardings"


EXPERT_LEAVES = ("wg", "wu", "wd")


def expert_leaves(lp: dict) -> tuple[str, ...]:
    """The expert leaves a layer (or a stack of layers) has: all three, or
    `wu` and `wd` alone where the experts are ungated."""
    return tuple(name for name in EXPERT_LEAVES if name in lp)


def whole_stacks(layers: dict, tp_mesh=None) -> dict | None:
    """`moe_mlp`'s `stack` leaves for a scan over `layers` (models/llama.py
    `run_layers`): the three expert leaves [L, X, A, F] as they lie, so that
    the routed form's kernel addresses (layer, expert) in them and no layer's
    experts are sliced out of the scan's operand first; None under a mesh,
    where the expert FFN is partitioned and reads the layer's slice."""
    if tp_mesh is not None:
        return None
    return {name: layers[name] for name in expert_leaves(layers)}


def moe_mlp(x: jnp.ndarray, lp: dict, config, seq_lens=None,
            tp_mesh=None, stack=None, route_from=None
            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN: [B, S, D] -> ([B, S, D], valid pairs per expert [X]).
    `seq_lens` [B] says how many of each row's S positions are real.
    `stack` = (the FFN layers' stacked leaves, this layer's index) where the
    caller holds its layers stacked (models/hybrid.py; models/llama.py
    `run_layers` on one device): the routed form's kernel then reads an
    expert where it lies in the stack, and `lp`'s slices of the three
    expert leaves are read by the mixture alone (and by `ragged_dot`, for
    a stack `grouped_matmul_form` gives no kernel). `route_from` [B, S, D]:
    the tensor the router reads where it is not `x` (`routing_of`)."""
    B, S, D = x.shape
    k = config.num_experts_per_tok
    if seq_lens is None:
        valid = jnp.ones((B * S,), bool)
    else:
        valid = (jnp.arange(S, dtype=jnp.int32)[None, :]
                 < seq_lens[:, None]).reshape(B * S)
    xf = x.reshape(B * S, D)
    args = (xf, valid, lp["router"], lp.get("wg"), lp["wu"], lp["wd"])

    n = _model_shards(tp_mesh, config.intermediate_size)
    routing = routing_of(config, lp, None if route_from is None
                         else route_from.reshape(B * S, D))
    if routing and tp_mesh is not None:
        raise ValueError("the sigmoid router, a router off the FFN's input "
                         "and an activation other than silu are traced on "
                         "one device only")
    if tp_mesh is None:
        # one device: the kernel's operand is a stack — the caller's, or
        # this layer alone as a stack of one (a reshape)
        stacks, layer = stack if stack is not None else (
            jax.tree.map(lambda a: a[None],
                         {name: lp[name] for name in expert_leaves(lp)}), 0)
        y, pairs = _expert_ffn(*args, k, (stacks, jnp.int32(layer)),
                               routing)
    elif n == 1:
        y, pairs = _expert_ffn(*args, k)
    else:
        from jax.sharding import PartitionSpec as P

        data = dict(tp_mesh.shape).get("data", 1)
        b = "data" if data > 1 and (B * S) % data == 0 else None

        def spec(w, q_spec, scale_spec):
            return (QuantizedTensor(q=q_spec, scale=scale_spec)
                    if isinstance(w, QuantizedTensor) else q_spec)

        col = spec(lp["wg"], P(None, None, "model"), P(None, "model"))
        row = spec(lp["wd"], P(None, "model", None), P())

        def shard(*a):
            y, pairs = _expert_ffn(*a, k)
            y = jax.lax.psum(y, "model")
            if b is not None:
                pairs = jax.lax.psum(pairs, b)
            return y, pairs

        y, pairs = jax.shard_map(
            shard, mesh=tp_mesh,
            in_specs=(P(b, None), P(b), P(), col, col, row),
            out_specs=(P(b, None), P()), check_vma=False)(*args)
    if "sg" in lp:
        # the shared expert: the same gated form, every token, weight 1 —
        # or, where the layer has a `sgate` column (qwen3_next's
        # `shared_expert_gate`), sigmoid(x . sgate) — added to the routed
        # sum in float32
        shared = qmatmul(jax.nn.silu(qmatmul(xf, lp["sg"]))
                         * qmatmul(xf, lp["su"]), lp["sd"])
        if "sgate" in lp:
            shared = shared * jax.nn.sigmoid(jnp.dot(
                xf, lp["sgate"], preferred_element_type=jnp.float32))
        y = y + shared
    elif "su" in lp:
        # the UNGATED shared expert: act(x W_up) W_down, every token,
        # weight 1 — computed on every chip alike, whatever experts it holds
        y = y + qmatmul(_act(routing)(qmatmul(xf, lp["su"])), lp["sd"])
    return y.astype(x.dtype).reshape(B, S, D), pairs
