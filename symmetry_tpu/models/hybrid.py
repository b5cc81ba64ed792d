"""Decoder whose layers are of two mixer kinds: recurrent layers with a
per-slot state — Mamba-2 (models/mamba2.py; granitemoehybrid family), Gated
DeltaNet (models/gdn.py; qwen3_next family, whose `layer_types` say
"linear_attention" / "full_attention") or a gated short convolution
(models/sconv.py; lfm2_moe family: "conv" / "full_attention") — among
attention layers (models/llama.py `_attention`), every one followed by the
routed + shared expert FFN (models/moe.py).

    h = embed[tokens] * embedding_multiplier
    for layer i:   h = h + r * mixer_i(rms_norm(h))
                   h = h + r * (moe(x) + shared(x)),  x = rms_norm(h)
    logits = rms_norm(h) @ embed^T / logits_scaling

A layer is composed from its (mixer kind, ffn kind) by `layer_types[i]`.
Parameters are stacked PER KIND — `layers.mamba` [n_mamba, ...],
`layers.attn` [n_attention, ...], `layers.ffn` [num_layers, ...] — and the
pattern decides the order: the forward pass is one `lax.scan` per RUN of one
kind (mamba x 5, attention, mamba x 4 at granite's first period), each
indexing its stacks at an offset (no `lax.cond` over both mixers).

The cache (models/llama.py KVCache) holds K/V for the ATTENTION layers only
(`k` [n_attention, B, T, K, D], attention layer j at index j of the stack)
and, beside them, `ssm` [n_mamba, B, H, P, N] float32 and `conv` [n_mamba,
d_conv - 1, B, C] for the mamba layers. A mamba layer's step reads and
writes its slice of `ssm` where it lies — the decode step hands the whole
stack and the layer's index to one kernel (ops/ssm_step.py), the chunked
form `.at[j].set`s the donated buffer — and the stack rides every scan's
carry. The expert FFN is handed the `ffn` stacks whole beside the layer's
index for the same reason: the routed form's kernel (ops/gmm.py) reads an
expert's int8 tile at (layer, expert) of the stack as it lies.

Which form a mamba layer takes follows the call's shape: one position a
slot is the recurrence step; more is the chunked form, from zeros when the
caller says the cache is empty (`prefill_flash`, the engine's prefill: the
scratch it reuses is dirty) and from the cache's state otherwise.

A qwen3_next model is the same trunk with the other recurrent kind: its
stack is `layers.gdn`, its state rides the same two cache leaves — `ssm`
[n_linear, B, Hv, Dk, Dv] float32, a MATRIX a value head, and `conv`
[n_linear, K - 1, B, 2 Hk Dk + Hv Dv] — its norms are zero-centred
(`norm_plus_one`), its head untied, its shared expert gated; the forms
follow the call's shape in the same way (models/gdn.py `step_at` — the
whole stack and the layer's index to the same file's other kernel,
`gdn_step` — and `chunked`).

An lfm2_moe model is the same trunk with a third recurrent kind, whose
state is a tail ALONE: its stack is `layers.sconv` (a gated short
convolution, models/sconv.py), the cache has no `ssm` leaf (None) and `conv`
[n_conv, K - 1, B, E] is all a slot keeps for those layers; its attention
layers norm q and k per head and rotate every channel; and its FFN is of TWO
kinds — the first `num_dense_layers` layers end in a dense SwiGLU (stack
`layers.dense` [n_dense, ...]), the rest in routed experts (stack
`layers.ffn` [num_layers - n_dense, ...], indexed by a layer's place among
the expert layers) chosen by a sigmoid router with a selection bias
(models/moe.py `route_top_k`). A run (`runs`) breaks where the mixer kind OR
the FFN kind changes, so one scan body still holds one kind of each.

A deepseek_v3 model (`config.latent`) is the same trunk with NO recurrent
kind: every layer is "latent_attention" (models/llama.py
`_latent_attention`; stack `layers.attn`), the cache is `k` alone — one row
of rank + rope values a position in whole lane tiles, [num_layers, B, T,
lanes], no `v`, no `ssm`, no `conv` — and the FFN kinds are lfm2_moe's (a
leading dense layer, then experts by the sigmoid router) with granite's
ungated shared expert beside the routed sum.

A smallthinker model (`config.window_kind`) is the same trunk with no
recurrent kind and TWO ATTENTION kinds: "full_attention" layers (stack
`layers.attn`; the cache's `k` / `v`, every position at the full capacity)
and "sliding_attention" layers (stack `layers.swa`; the cache's `kw` / `vw`,
a RING of exactly the window's rows a slot, position p at row p mod window:
models/llama.py `write_kv(ring_valid=)`, `ring_view`), a rotary choice a
layer (`rope_layout`: the published layout ropes the window layers and gives
the full ones no positional embedding), and a router that reads the
residual stream as it ENTERS the layer (`router_input` "layer_input":
`moe_mlp(route_from=)`) over ReGLU experts. A run breaks where the kind or
the rotary choice changes. A prefill from empty (`prefill_flash`) writes a
window layer's rows PLAIN, position p at row p of a scratch whose window
leaves are as long as the bucket (the engine's insert rolls the last
`window` rows into the ring); every other call treats the window leaf as a
ring of its capacity.

A nemotron_h model (`config.ffn_layout`) is granite's trunk — Mamba-2
among NoPE GQA layers, the cache's `ssm` / `conv` / `k` / `v` as above — whose
published blocks are ONE sub-layer each: `M` a mixer alone, `*` attention
alone, `E` experts alone, every one `h + f(norm(h))`. Two consecutive blocks
"mixer, then E" ARE this trunk's (mixer, FFN) layer, exactly, so the 52
published blocks run as 29 layers (models/llama.py `pair_blocks`) whose FFN
kind is the LAYER's (`ffn_kind(i)`: "moe", or "none" — the body returns after
the mixer — for the mamba block before an attention block); a run breaks
where the FFN kind changes, and `layers.ffn` stacks the layers that END in
experts alone, indexed by `ffn_index`. Its Mamba-2 has G groups of B and C
(models/mamba2.py; the decode kernel indexes them by the head group of the
tile it holds), its experts are ungated relu2 — `wu` and `wd`, no `wg`; the
shared expert `su` / `sd` — stored at `expert_columns` of their width, and
the chip may hold a SHARE of the experts its router scores (`experts_held`;
models/moe.py), its head untied, no multipliers.

One device only: there are no sharding rules for the state yet.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from symmetry_tpu.models import gdn, llama, mamba2, sconv
from symmetry_tpu.models.moe import moe_mlp
from symmetry_tpu.ops.norm import rms_norm
from symmetry_tpu.ops.quant import qmatmul

KIND_STACK = {"mamba": "mamba", "attention": "attn",
              "linear_attention": "gdn", "full_attention": "attn",
              "conv": "sconv", "latent_attention": "attn",
              "sliding_attention": "swa"}
RECURRENT = {"mamba": mamba2, "linear_attention": gdn, "conv": sconv}
# the scope a multi-token-prediction module's ops carry in a device trace
MTP_SCOPE = "mtp_module"


def stack_index(config, i: int) -> int:
    """Layer i's index in the stack (and the cache leaves) of its kind."""
    return config.layers_of(config.layer_types[i]).index(i)


def state_shapes(config, batch: int) -> tuple[tuple | None, tuple]:
    """The shapes of the cache's `ssm` and `conv` leaves: a stack over the
    recurrent layers of this model's kind. A short convolution's state is
    its tail alone: no `ssm` leaf (None)."""
    if config.recurrent_kind is None:  # latent attention alone: no state
        return None, None
    n = len(config.layers_of(config.recurrent_kind))
    if config.recurrent_kind == "conv":
        z = sconv.sizes(config)
        return None, (n, z["K"] - 1, batch, z["conv"])
    if config.recurrent_kind == "mamba":
        z = mamba2.sizes(config)
        return ((n, batch, z["H"], z["P"], z["N"]),
                (n, z["K"] - 1, batch, z["conv"]))
    z = gdn.sizes(config)
    return ((n, batch, z["Hv"], z["Dk"], z["Dv"]),
            (n, z["K"] - 1, batch, z["conv"]))


def state_bytes_per_slot(config, dtype=jnp.bfloat16) -> dict:
    """What one slot holds that is not a row per position."""
    ssm, conv = state_shapes(config, 1)
    return {"ssm": math.prod(ssm) * 4 if ssm else 0,
            "conv": (math.prod(conv) * jnp.dtype(dtype).itemsize
                     if conv else 0)}


def init_cache(config, batch: int, capacity: int, dtype=jnp.bfloat16, *,
               quantized: bool = False, count_experts: bool = False,
               ring: int | None = None) -> llama.KVCache:
    """`ring` (a window / full model alone): the rows of the window layers'
    leaves — the window for a served cache, None for a prefill scratch,
    whose window leaves are `capacity` long like its full ones."""
    # (a multi-token-prediction module's block keeps its rows in a layer of
    # its own BEHIND the trunk's full layers: `mtp_forward`)
    n_attn = (len(config.layers_of(config.attention_kind))
              + config.mtp_layers)
    ssm, conv = state_shapes(config, batch)
    shape = (n_attn, batch, capacity, *llama.kv_row(config))
    pairs = llama.init_expert_pairs(config) if count_experts else None
    if config.latent is not None:
        if quantized:
            raise ValueError("a latent cache row has no int8 form")
        # the row is all a position keeps
        return llama.KVCache(
            k=jnp.zeros(shape, dtype), v=None,
            lengths=jnp.zeros((batch,), jnp.int32), expert_pairs=pairs)
    scale_shape = (n_attn, batch, config.num_kv_heads, capacity)
    kv_dtype = jnp.int8 if quantized else dtype
    window = {}
    if config.window_kind is not None:
        # the window layers' leaves beside the full layers': a ring of the
        # window's rows — or, for a prefill scratch (`ring` None), as many
        # rows as the full leaves have, written plain
        n_win = len(config.layers_of(config.window_kind))
        rows = capacity if ring is None else ring
        wshape = (n_win, batch, rows, *llama.kv_row(config))
        wscale = (n_win, batch, config.num_kv_heads, rows)
        window = dict(
            kw=jnp.zeros(wshape, kv_dtype), vw=jnp.zeros(wshape, kv_dtype),
            kw_scale=jnp.zeros(wscale, jnp.float32) if quantized else None,
            vw_scale=jnp.zeros(wscale, jnp.float32) if quantized else None)
    return llama.KVCache(
        k=jnp.zeros(shape, kv_dtype),
        v=jnp.zeros(shape, kv_dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
        k_scale=jnp.zeros(scale_shape, jnp.float32) if quantized else None,
        v_scale=jnp.zeros(scale_shape, jnp.float32) if quantized else None,
        expert_pairs=pairs,
        ssm=jnp.zeros(ssm, jnp.float32) if ssm else None,
        conv=jnp.zeros(conv, dtype) if conv else None,
        **window,
    )


def init_params(config, key: jax.Array, dtype=jnp.bfloat16, *,
                quantize: bool = False, slice_above: int | None = None
                ) -> dict:
    """Random init, each matrix of order fan_in ** -0.5 (int8 leaves made
    int8 in one program each, the expert stacks a layer at a time where the
    full-precision temporary would not fit: ops/quant.py)."""
    from symmetry_tpu.ops.quant import (
        default_leaf_limit, leaf_is_sliced, make_leaf, make_leaf_sliced)

    c = config
    if slice_above is None:
        slice_above = default_leaf_limit()
    keys = iter(jax.random.split(key, 48 if c.mtp_layers else 24))

    def dense(shape, name=None, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        quantized = quantize and name in llama.QUANT_KEYS
        make = (make_leaf_sliced if leaf_is_sliced(shape, dtype, None,
                                                   slice_above)
                else make_leaf)
        return make(next(keys), shape, scale, dtype, quantized=quantized)

    if c.latent is not None:
        return llama.absorb_latent(_init_latent(c, keys, dense, dtype), c,
                                   dtype)
    if c.recurrent_kind is None and c.router_input == "ffn_input":
        return _init_exaone(c, keys, dense, dtype)
    if c.recurrent_kind is None:
        return _init_window(c, keys, dense, dtype)
    if c.recurrent_kind == "linear_attention":
        return _init_qwen3_next(c, keys, dense, dtype)
    if c.recurrent_kind == "conv":
        return _init_lfm2(c, keys, dense, dtype)
    E, F = c.hidden_size, c.intermediate_size
    # the expert layers, and the experts whose weights lie HERE (all the
    # router scores, or this chip's share of them)
    L, X, Fs = (len(c.layers_ending_in("moe")), c.experts_here,
                c.shared_intermediate_size)
    Lm, La = len(c.layers_of("mamba")), len(c.layers_of("attention"))
    z = mamba2.sizes(c)
    H = z["H"]
    # dt in [1e-3, 1e-1] log-uniform through the softplus, A in [1, 16]:
    # the published initialisation's ranges (decays from 0.2 to 0.999)
    dt = jnp.exp(jax.random.uniform(next(keys), (Lm, H), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    a = jax.random.uniform(next(keys), (Lm, H), jnp.float32, 1.0, 16.0)
    params = {
        "embed": dense((c.vocab_size, E), scale=0.02),
        "layers": {
            "mamba": {
                "norm": jnp.ones((Lm, E), dtype),
                "in_proj": dense((Lm, E, z["proj"]), "in_proj"),
                "conv_w": dense((Lm, z["K"], z["conv"]), scale=z["K"] ** -0.5),
                "conv_b": jnp.zeros((Lm, z["conv"]), dtype),
                "dt_bias": jnp.log(jnp.expm1(dt)),          # softplus^-1
                "A_log": jnp.log(a),
                "D": jnp.ones((Lm, H), jnp.float32),
                "gate_norm": jnp.ones((Lm, z["inner"]), dtype),
                "out_proj": dense((Lm, z["inner"], E), "out_proj"),
            },
            "attn": {
                "norm": jnp.ones((La, E), dtype),
                "wq": dense((La, E, c.q_dim), "wq"),
                "wk": dense((La, E, c.kv_dim), "wk"),
                "wv": dense((La, E, c.kv_dim), "wv"),
                "wo": dense((La, c.q_dim, E), "wo"),
            },
        },
        "final_norm": jnp.ones((E,), dtype),
    }
    ffn = {"norm": jnp.ones((L, E), dtype),
           "router": dense((L, E, c.num_experts))}
    if c.gated_ffn:     # granitemoehybrid: three matrices an expert
        ffn.update(wg=dense((L, X, E, F), "wg"), wu=dense((L, X, E, F), "wu"),
                   wd=dense((L, X, F, E), "wd"), sg=dense((L, E, Fs), "sg"),
                   su=dense((L, E, Fs), "su"), sd=dense((L, Fs, E), "sd"))
    else:
        # nemotron_h: two, stored at `expert_columns` of their width
        Fp = expert_columns(F)
        ffn.update(
            wu=_zero_past(dense((L, X, E, Fp), "wu"), F, -1),
            wd=_zero_past(dense((L, X, Fp, E), "wd", scale=F ** -0.5), F, -2),
            su=dense((L, E, Fs), "su"), sd=dense((L, Fs, E), "sd"))
    params["layers"]["ffn"] = ffn
    if c.router_bias:
        # (HF `e_score_correction_bias`, drawn as `_init_lfm2` draws it: at
        # the published initial zero no comparison could tell it left out)
        ffn["expert_bias"] = jax.random.uniform(
            next(keys), (L, c.num_experts), jnp.float32, -0.25, 0.25)
    if not c.tie_embeddings:
        params["lm_head"] = dense((E, c.vocab_size), "lm_head", scale=0.02)
    return params


LANES = 128


def expert_columns(width: int) -> int:
    """The width an ungated expert's two matrices are STORED at: the
    published width rounded up to whole lane tiles, the columns of `wu` and
    the rows of `wd` past it zero (relu2(0) = 0 meets a zero row: exact).
    nemotron_h's 1,856 is 14.5 tiles: left as it is, the chip lays an
    [.., 2688, 1856] int8 leaf out with 2,688 minor to save the padding, and
    hands a kernel that wants rows of 1,856 a 3.5 GB copy of the stack a
    call (compiled for a described v5e: tests/test_chip_compile.py) — so the
    layout pads, 1,920, and no width of the model changes: the config, the
    reference and the benchmark's byte counts are of 1,856."""
    return -(-width // LANES) * LANES


def _zero_past(leaf, n: int, axis: int):
    """`leaf` with everything from index `n` on along `axis` (-1 or -2)
    zeroed where it lies — an int8 leaf's payload; a zero times any scale
    is zero."""
    from symmetry_tpu.ops.quant import QuantizedTensor

    def zero(a):
        if a.shape[axis] == n:
            return a
        keep = jnp.arange(a.shape[axis]) < n
        keep = keep if axis == -1 else keep[:, None]
        return jax.jit(lambda a: jnp.where(keep, a, jnp.zeros((), a.dtype)),
                       donate_argnums=0)(a)

    if isinstance(leaf, QuantizedTensor):
        return QuantizedTensor(q=zero(leaf.q), scale=leaf.scale)
    return zero(leaf)


def _init_qwen3_next(c, keys, dense, dtype) -> dict:
    """`init_params` for a qwen3_next config: A in (0, 16] and dt in [1e-3,
    1e-1] as published, every zero-centred norm at its identity (0; the
    Gated DeltaNet's output norm is a plain weight: 1)."""
    z = gdn.sizes(c)
    L, E, F = c.num_layers, c.hidden_size, c.intermediate_size
    X, Fs, D = c.num_experts, c.shared_intermediate_size, c.dim_per_head
    Lm = len(c.layers_of("linear_attention"))
    La = len(c.layers_of("full_attention"))
    dt = jnp.exp(jax.random.uniform(next(keys), (Lm, z["Hv"]), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    a = jax.random.uniform(next(keys), (Lm, z["Hv"]), jnp.float32, 1e-3,
                           16.0)
    params = {
        "embed": dense((c.vocab_size, E), scale=0.02),
        "layers": {
            "gdn": {
                "norm": jnp.zeros((Lm, E), dtype),
                "in_proj": dense((Lm, E, z["proj"]), "in_proj"),
                "in_ba": dense((Lm, E, 2 * z["Hv"])),
                "conv_w": dense((Lm, z["K"], z["conv"]),
                                scale=z["K"] ** -0.5),
                "dt_bias": jnp.log(jnp.expm1(dt)),          # softplus^-1
                "A_log": jnp.log(a),
                "gate_norm": jnp.ones((Lm, z["Dv"]), dtype),
                "out_proj": dense((Lm, z["inner"], E), "out_proj"),
            },
            "attn": {
                "norm": jnp.zeros((La, E), dtype),
                "wq": dense((La, E, (2 if c.attn_output_gate else 1)
                             * c.q_dim), "wq"),
                "wk": dense((La, E, c.kv_dim), "wk"),
                "wv": dense((La, E, c.kv_dim), "wv"),
                "wo": dense((La, c.q_dim, E), "wo"),
                "q_norm": jnp.zeros((La, D), dtype),
                "k_norm": jnp.zeros((La, D), dtype),
            },
            "ffn": {
                "norm": jnp.zeros((L, E), dtype),
                "router": dense((L, E, X)),
                "wg": dense((L, X, E, F), "wg"),
                "wu": dense((L, X, E, F), "wu"),
                "wd": dense((L, X, F, E), "wd"),
                "sg": dense((L, E, Fs), "sg"),
                "su": dense((L, E, Fs), "su"),
                "sd": dense((L, Fs, E), "sd"),
                "sgate": dense((L, E, 1)),
            },
        },
        "final_norm": jnp.zeros((E,), dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense((E, c.vocab_size), "lm_head", scale=0.02)
    return params


def _init_lfm2(c, keys, dense, dtype) -> dict:
    """`init_params` for an lfm2_moe config. `expert_bias` is drawn uniform
    in [-0.25, 0.25]: the published initial value is zero, under which
    leaving the bias out of the selection changes nothing and no comparison
    could tell (benchmarks/configs/lfm2-8b-a1b.json `weights`)."""
    z = sconv.sizes(c)
    E, F, X, D = c.hidden_size, c.intermediate_size, c.num_experts, \
        c.dim_per_head
    Lc, La = len(c.layers_of("conv")), len(c.layers_of("full_attention"))
    Ld, Fd = c.num_dense_layers, c.dense_intermediate_size
    Lx = c.num_layers - Ld
    params = {
        "embed": dense((c.vocab_size, E), scale=0.02),
        "layers": {
            "sconv": {
                "norm": jnp.ones((Lc, E), dtype),
                "in_proj": dense((Lc, E, z["proj"]), "in_proj"),
                "conv_w": dense((Lc, z["K"], z["conv"]),
                                scale=z["K"] ** -0.5),
                "out_proj": dense((Lc, E, E), "out_proj"),
            },
            "attn": {
                "norm": jnp.ones((La, E), dtype),
                "wq": dense((La, E, c.q_dim), "wq"),
                "wk": dense((La, E, c.kv_dim), "wk"),
                "wv": dense((La, E, c.kv_dim), "wv"),
                "wo": dense((La, c.q_dim, E), "wo"),
                "q_norm": jnp.ones((La, D), dtype),
                "k_norm": jnp.ones((La, D), dtype),
            },
            "ffn": {
                "norm": jnp.ones((Lx, E), dtype),
                "router": dense((Lx, E, X)),
                "wg": dense((Lx, X, E, F), "wg"),
                "wu": dense((Lx, X, E, F), "wu"),
                "wd": dense((Lx, X, F, E), "wd"),
            },
        },
        "final_norm": jnp.ones((E,), dtype),
    }
    if Ld:
        params["layers"]["dense"] = {
            "norm": jnp.ones((Ld, E), dtype),
            "wg": dense((Ld, E, Fd), "wg"),
            "wu": dense((Ld, E, Fd), "wu"),
            "wd": dense((Ld, Fd, E), "wd"),
        }
    if c.router_bias:
        params["layers"]["ffn"]["expert_bias"] = jax.random.uniform(
            next(keys), (Lx, X), jnp.float32, -0.25, 0.25)
    if not c.tie_embeddings:
        params["lm_head"] = dense((E, c.vocab_size), "lm_head", scale=0.02)
    return params


def _init_latent(c, keys, dense, dtype) -> dict:
    """`init_params` for a deepseek_v3 config (`absorb_latent` adds the
    absorbed factors). `expert_bias` (HF `e_score_correction_bias`) is
    drawn uniform in [-0.25, 0.25] as `_init_lfm2` draws it: at the
    published initial zero no comparison could tell the bias left out."""
    la = c.latent
    E, F, X, H = c.hidden_size, c.intermediate_size, c.num_experts, \
        c.num_heads
    L, Ld, Fd, Fs = c.num_layers, c.num_dense_layers, \
        c.dense_intermediate_size, c.shared_intermediate_size
    Lx = L - Ld
    params = {
        "embed": dense((c.vocab_size, E), scale=0.02),
        "layers": {
            "attn": {
                "norm": jnp.ones((L, E), dtype),
                "wq": dense((L, E, H * (la.nope + la.rope)), "wq"),
                "wkva": dense((L, E, la.row), "wkva"),
                "kv_norm": jnp.ones((L, la.rank), dtype),
                "wkvb": dense((L, la.rank, H * (la.nope + la.v)), "wkvb"),
                "wo": dense((L, H * la.v, E), "wo"),
            },
            "ffn": {
                "norm": jnp.ones((Lx, E), dtype),
                "router": dense((Lx, E, X)),
                "wg": dense((Lx, X, E, F), "wg"),
                "wu": dense((Lx, X, E, F), "wu"),
                "wd": dense((Lx, X, F, E), "wd"),
            },
        },
        "final_norm": jnp.ones((E,), dtype),
    }
    if Fs:
        params["layers"]["ffn"].update(
            sg=dense((Lx, E, Fs), "sg"), su=dense((Lx, E, Fs), "su"),
            sd=dense((Lx, Fs, E), "sd"))
    if Ld:
        params["layers"]["dense"] = {
            "norm": jnp.ones((Ld, E), dtype),
            "wg": dense((Ld, E, Fd), "wg"),
            "wu": dense((Ld, E, Fd), "wu"),
            "wd": dense((Ld, Fd, E), "wd"),
        }
    if c.router_bias:
        params["layers"]["ffn"]["expert_bias"] = jax.random.uniform(
            next(keys), (Lx, X), jnp.float32, -0.25, 0.25)
    if not c.tie_embeddings:
        params["lm_head"] = dense((E, c.vocab_size), "lm_head", scale=0.02)
    return params


def _init_window(c, keys, dense, dtype) -> dict:
    """`init_params` for a smallthinker config: an attention stack a kind
    (`attn` the full layers, `swa` the window layers; the same leaves), one
    expert FFN a layer, an untied head."""
    E, F, X, L = c.hidden_size, c.intermediate_size, c.num_experts, \
        c.num_layers

    def attention(n):
        return {"norm": jnp.ones((n, E), dtype),
                "wq": dense((n, E, c.q_dim), "wq"),
                "wk": dense((n, E, c.kv_dim), "wk"),
                "wv": dense((n, E, c.kv_dim), "wv"),
                "wo": dense((n, c.q_dim, E), "wo")}

    params = {
        "embed": dense((c.vocab_size, E), scale=0.02),
        "layers": {
            **{KIND_STACK[kind]: attention(len(c.layers_of(kind)))
               for kind in c.attention_kinds},
            "ffn": {
                "norm": jnp.ones((L, E), dtype),
                "router": dense((L, E, X)),
                "wg": dense((L, X, E, F), "wg"),
                "wu": dense((L, X, E, F), "wu"),
                "wd": dense((L, X, F, E), "wd"),
            },
        },
        "final_norm": jnp.ones((E,), dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense((E, c.vocab_size), "lm_head", scale=0.02)
    return params


def _init_exaone(c, keys, dense, dtype) -> dict:
    """`init_params` for an exaone_moe config: an attention stack a kind
    with q/k norms (`attn` the full layers, `swa` the window layers), the
    leading dense layers, then experts (the HELD ones' weights alone) under
    deepseek_v3's router beside a shared expert, an untied head — and,
    under `mtp`, each multi-token-prediction module as a stack of its own
    (leading axis: the module): the two input norms, the projection of
    [hidden ; embedding] (`in_proj`, [2E, E]), one full-attention block
    with an expert FFN like the trunk's last, and its output norm.
    `expert_bias` drawn as `_init_latent` draws it."""
    E, F, X, D = c.hidden_size, c.intermediate_size, c.experts_here, \
        c.dim_per_head
    Ld, Fd, Fs = c.num_dense_layers, c.dense_intermediate_size, \
        c.shared_intermediate_size

    def attention(n):
        return {"norm": jnp.ones((n, E), dtype),
                "wq": dense((n, E, c.q_dim), "wq"),
                "wk": dense((n, E, c.kv_dim), "wk"),
                "wv": dense((n, E, c.kv_dim), "wv"),
                "wo": dense((n, c.q_dim, E), "wo"),
                "q_norm": jnp.ones((n, D), dtype),
                "k_norm": jnp.ones((n, D), dtype)}

    def experts(n):
        return {"norm": jnp.ones((n, E), dtype),
                "router": dense((n, E, c.num_experts)),
                "wg": dense((n, X, E, F), "wg"),
                "wu": dense((n, X, E, F), "wu"),
                "wd": dense((n, X, F, E), "wd"),
                "sg": dense((n, E, Fs), "sg"),
                "su": dense((n, E, Fs), "su"),
                "sd": dense((n, Fs, E), "sd"),
                "expert_bias": jax.random.uniform(
                    next(keys), (n, c.num_experts), jnp.float32, -0.25,
                    0.25)}

    params = {
        "embed": dense((c.vocab_size, E), scale=0.02),
        "layers": {
            **{KIND_STACK[kind]: attention(len(c.layers_of(kind)))
               for kind in c.attention_kinds},
            "dense": {"norm": jnp.ones((Ld, E), dtype),
                      "wg": dense((Ld, E, Fd), "wg"),
                      "wu": dense((Ld, E, Fd), "wu"),
                      "wd": dense((Ld, Fd, E), "wd")},
            "ffn": experts(c.num_layers - Ld),
        },
        "final_norm": jnp.ones((E,), dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense((E, c.vocab_size), "lm_head", scale=0.02)
    if c.mtp_layers:
        M = c.mtp_layers
        params["mtp"] = {
            "hnorm": jnp.ones((M, E), dtype),
            "enorm": jnp.ones((M, E), dtype),
            "in_proj": dense((M, 2 * E, E), "in_proj"),
            "attn": attention(M), "ffn": experts(M),
            "final_norm": jnp.ones((M, E), dtype)}
    return params


def mtp_forward(params: dict, config, h: jnp.ndarray, tokens: jnp.ndarray,
                cache: llama.KVCache, seq_lens: jnp.ndarray | None = None,
                *, prefill_flash: bool = False
                ) -> tuple[jnp.ndarray, llama.KVCache]:
    """The multi-token-prediction module (DeepSeek-V3's form, arXiv:
    2412.19437 s2.2) over S positions a slot from `cache.lengths`: `h`
    [B, S, E] the trunk's hidden state there AS THE HEAD READS IT (after the
    final norm), `tokens` [B, S] the token AFTER each position. x' =
    in_proj [norm(h) ; norm(embed(token))], one pre-norm block — full NoPE
    attention over the module's own rows, which lie in the `k` / `v`
    leaves' layer behind the trunk's full layers and share the slot's
    length, then the expert FFN — and the module's own norm: what
    `logits_from_hidden` turns into logits for the token after `tokens`.
    Returns (that hidden state [B, S, E], the cache with the rows written,
    the module's expert pairs counted and `lengths` advanced)."""
    c = config
    B, S = tokens.shape
    if seq_lens is None:
        seq_lens = jnp.full((B,), S, jnp.int32)
    positions = (cache.lengths[:, None]
                 + jnp.arange(S, dtype=jnp.int32)[None, :])
    kv_valid = cache.lengths + seq_lens
    mp = _at(params["mtp"], 0)
    layer = len(c.layers_of(c.attention_kind))

    def norm(x, w):
        return rms_norm(x, llama._norm_w(w, c), c.rms_eps)

    with jax.named_scope(MTP_SCOPE):
        e = jnp.take(params["embed"], tokens, axis=0)
        x = qmatmul(jnp.concatenate(
            [norm(h, mp["hnorm"]), norm(e, mp["enorm"])], axis=-1),
            mp["in_proj"])
        lp = mp["attn"]
        out, cache = llama._attention(
            norm(x, lp["norm"]), lp, cache, jnp.int32(layer), positions,
            kv_valid, seq_lens, c, prefill_flash and S > 1, rope=False)
        x = x + out
        lp = mp["ffn"]
        y, pairs = moe_mlp(norm(x, lp["norm"]), lp, c, seq_lens,
                           stack=(params["mtp"]["ffn"], 0))
        x = norm(x + y, mp["final_norm"])
    return x, llama.add_expert_pairs(cache, pairs, c)._replace(
        lengths=kv_valid)


def runs(config) -> list[tuple[str, int, int]]:
    """The pattern as runs of one mixer kind AND one FFN kind: (mixer kind,
    first layer, length). A run breaks where either changes (lfm2_moe's
    leading dense layers end at `num_dense_layers`) — or the rotary choice
    (`rope_layout`; it changes with the kind in smallthinker's published
    layout: 26 runs at its full depth, 6 at three periods) — so one scan
    body holds one kind of each; the run's FFN kind is
    `config.ffn_kind(first)`, its rotary choice `config.layer_rope(first)`."""
    out: list[tuple[str, int, int]] = []
    for i, kind in enumerate(config.layer_types):
        if (out and out[-1][0] == kind
                and config.ffn_kind(out[-1][1]) == config.ffn_kind(i)
                and config.layer_rope(out[-1][1]) == config.layer_rope(i)):
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, i, 1))
    return out


def _at(stack: dict, j) -> dict:
    """Layer j of a stack, j traced: the slice of the leading axis that
    `lax.scan` takes of its xs, with an offset."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
        stack)


def forward_hidden(params: dict, config, tokens: jnp.ndarray,
                   cache: llama.KVCache, seq_lens: jnp.ndarray | None = None,
                   *, prefill_flash: bool = False
                   ) -> tuple[jnp.ndarray, llama.KVCache]:
    """models/llama.py forward_hidden for a config with `layer_types`: one
    `lax.scan` per run of one mixer kind, the cache (K/V, state, tails) on
    the carry and every stack indexed where it lies. (Unrolled over the
    period, the compiler overlapped the layers' temporaries: 6.8 GB of
    workspace for a prefill whose layers need 1.2 GB one at a time —
    PERF.md, PR 33.)"""
    c = config
    B, S = tokens.shape
    if seq_lens is None:
        seq_lens = jnp.full((B,), S, jnp.int32)
    positions = (cache.lengths[:, None]
                 + jnp.arange(S, dtype=jnp.int32)[None, :])
    kv_valid = cache.lengths + seq_lens
    layers = params["layers"]
    for kind in filter(None, (c.recurrent_kind, *c.attention_kinds)):
        n = jax.tree.leaves(layers[KIND_STACK[kind]])[0].shape[0]
        if n != len(c.layers_of(kind)):
            raise ValueError(f"params carry {n} {kind} layers but "
                             f"layer_types has {len(c.layers_of(kind))}")
    h = jnp.take(params["embed"], tokens, axis=0)
    h = h * jnp.asarray(c.embedding_multiplier, h.dtype)
    r = jnp.asarray(c.residual_multiplier, h.dtype)

    recurrent = RECURRENT.get(c.recurrent_kind)

    def norm(h, w):
        return rms_norm(h, llama._norm_w(w, c), c.rms_eps)

    def mixer(kind, x, lp, cache, j, rope):
        if kind == c.window_kind:
            # the ring leaves in the places of k / v; plain rows in a
            # prefill's scratch, whose insert makes the ring
            out, view = llama._attention(
                x, lp, llama.ring_view(cache), j, positions, kv_valid,
                seq_lens, c, prefill_flash and S > 1, rope=rope,
                window=c.sliding_window, ring=not prefill_flash)
            return out, llama.ring_restore(cache, view)
        if kind == c.attention_kind:
            return llama._attention(x, lp, cache, j, positions, kv_valid,
                                    seq_lens, c, prefill_flash and S > 1,
                                    rope=rope)
        conv = _at(cache.conv, j)
        if S == 1:  # the stack as it lies: layer j is the step's address
            out, ssm, conv = recurrent.step_at(x[:, 0], lp, cache.ssm, j,
                                               conv, c)
            return out[:, None], cache._replace(
                ssm=ssm, conv=cache.conv.at[j].set(conv))
        if cache.ssm is None:  # a tail is the kind's whole state
            if prefill_flash:
                conv = jnp.zeros_like(conv)
            out, _, conv = recurrent.chunked(x, lp, None, conv, seq_lens, c)
            return out, cache._replace(conv=cache.conv.at[j].set(conv))
        ssm = _at(cache.ssm, j)
        if prefill_flash:  # from empty, whatever the buffer holds
            ssm, conv = jnp.zeros_like(ssm), jnp.zeros_like(conv)
        out, ssm, conv = recurrent.chunked(x, lp, ssm, conv, seq_lens, c)
        return out, cache._replace(ssm=cache.ssm.at[j].set(ssm),
                                   conv=cache.conv.at[j].set(conv))

    def dense_ffn(x, lp):
        return qmatmul(llama._act(qmatmul(x, lp["wg"]), c)
                       * qmatmul(x, lp["wu"]), lp["wd"])

    for kind, first, length in runs(c):
        j0 = stack_index(c, first)
        # a run is of one FFN kind (`runs`): the run's first layer's, and
        # its index in that kind's stack
        ffn, f0 = c.ffn_kind(first), c.ffn_index(first)

        def body(carry, step, kind=kind, first=first, j0=j0, ffn=ffn,
                 f0=f0):
            h, cache = carry
            entered = h  # what a "layer_input" router reads
            lp = _at(layers[KIND_STACK[kind]], j0 + step)
            out, cache = mixer(kind, norm(h, lp["norm"]), lp, cache,
                               j0 + step, c.layer_rope(first))
            h = h + r * out
            if ffn == "none":  # the block is the mixer alone
                return (h, cache), None
            if ffn == "dense":
                lp = _at(layers["dense"], f0 + step)
                return (h + r * dense_ffn(norm(h, lp["norm"]), lp),
                        cache), None
            # the layer's index among the expert layers
            lp = _at(layers["ffn"], f0 + step)
            y, pairs = moe_mlp(
                norm(h, lp["norm"]), lp, c, seq_lens,
                stack=(layers["ffn"], f0 + step),
                **({"route_from": entered}
                   if c.router_input == "layer_input" else {}))
            h = h + r * y
            return (h, llama.add_expert_pairs(cache, pairs, c)), None

        (h, cache), _ = jax.lax.scan(
            body, (h, cache), jnp.arange(length, dtype=jnp.int32))
    h = norm(h, params["final_norm"])
    if c.latent is not None and S == 1 and cache.expert_pairs is not None:
        cache = cache._replace(expert_pairs=llama.count_latent(
            cache.expert_pairs, cache.lengths, kv_valid, c))
    if (c.window_kind is not None and not prefill_flash
            and cache.expert_pairs is not None):
        # a decode step, or a verify of a drafted position or more (no
        # other forward over a served ring: chunks are refused) — the rows
        # its LAST position reads, each counted once
        cache = cache._replace(expert_pairs=llama.count_window(
            cache.expert_pairs, cache.lengths, kv_valid,
            min(cache.kw.shape[2], c.sliding_window), c))
    return h, cache._replace(lengths=kv_valid)


# ---------------------------------------------------------------------------
# HF `granitemoehybrid` checkpoint names (engine/weights.py does the file IO).
# HF linears are [out, in]: transposed to ours. Ours splits what HF fuses:
# `input_linear` ([.., 2F, E]: gate rows then up rows) is the pair (wg, wu),
# for the routed experts ([X, 2F, E], all experts in ONE tensor — there is no
# per-expert name as in mixtral) and the shared one; `conv1d.weight`
# [C, 1, K] is ours [K, C].

HF_MIXER = {
    "mamba": {"input_layernorm.weight": "norm",
              "mamba.in_proj.weight": "in_proj",
              "mamba.conv1d.weight": "conv_w", "mamba.conv1d.bias": "conv_b",
              "mamba.dt_bias": "dt_bias", "mamba.A_log": "A_log",
              "mamba.D": "D", "mamba.norm.weight": "gate_norm",
              "mamba.out_proj.weight": "out_proj"},
    "attention": {"input_layernorm.weight": "norm",
                  "self_attn.q_proj.weight": "wq",
                  "self_attn.k_proj.weight": "wk",
                  "self_attn.v_proj.weight": "wv",
                  "self_attn.o_proj.weight": "wo"},
}
HF_FFN = {"post_attention_layernorm.weight": ("norm",),
          "block_sparse_moe.router.layer.weight": ("router",),
          "block_sparse_moe.input_linear.weight": ("wg", "wu"),
          "block_sparse_moe.output_linear.weight": ("wd",),
          "shared_mlp.input_linear.weight": ("sg", "su"),
          "shared_mlp.output_linear.weight": ("sd",)}
HF_TOP = {"model.embed_tokens.weight": "embed",
          "model.norm.weight": "final_norm"}


def hf_config(config) -> dict:
    """The config as its published `config.json` keys (what
    `models/llama.py config_from_hf` reads back, and what the plain
    reference — `benchmarks/reference/hybrid_decoder.py`, or
    `gdn_moe_decoder.py` for a qwen3_next config — is given)."""
    c = config
    if c.latent is not None:
        return llama.hf_config_latent(c)
    if c.recurrent_kind is None and c.router_input == "ffn_input":
        return llama.hf_config_exaone(c)
    if c.recurrent_kind is None:
        return llama.hf_config_window(c)
    if c.ffn_layout is not None:
        return llama.hf_config_nemotron_h(c)
    if c.recurrent_kind == "conv":
        return {
            "architectures": ["Lfm2MoeForCausalLM"],
            "model_type": "lfm2_moe",
            "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
            "num_hidden_layers": c.num_layers,
            "layer_types": list(c.layer_types),
            "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads,
            "head_dim": c.dim_per_head,
            "intermediate_size": c.dense_intermediate_size,
            "moe_intermediate_size": c.intermediate_size,
            "num_dense_layers": c.num_dense_layers,
            "num_experts": c.num_experts,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": True, "use_expert_bias": c.router_bias,
            "routed_scaling_factor": c.routed_scaling_factor,
            "conv_L_cache": c.conv_L_cache, "conv_bias": False,
            "rope_theta": c.rope_theta, "norm_eps": c.rms_eps,
            "tie_embedding": c.tie_embeddings,
            "max_position_embeddings": c.max_position,
        }
    if c.recurrent_kind == "linear_attention":
        return {
            "architectures": ["Qwen3NextForCausalLM"],
            "model_type": "qwen3_next",
            "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
            "num_hidden_layers": c.num_layers,
            "layer_types": list(c.layer_types),
            "full_attention_interval":
                c.layer_types.index("full_attention") + 1,
            "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads,
            "head_dim": c.dim_per_head,
            "moe_intermediate_size": c.intermediate_size,
            "shared_expert_intermediate_size": c.shared_intermediate_size,
            "num_experts": c.num_experts,
            "num_experts_per_tok": c.num_experts_per_tok,
            "norm_topk_prob": True, "decoder_sparse_step": 1,
            "mlp_only_layers": [],
            "linear_num_key_heads": c.linear_num_key_heads,
            "linear_key_head_dim": c.linear_key_head_dim,
            "linear_num_value_heads": c.linear_num_value_heads,
            "linear_value_head_dim": c.linear_value_head_dim,
            "linear_conv_kernel_dim": c.linear_conv_kernel_dim,
            "partial_rotary_factor": c.partial_rotary_factor,
            "rope_theta": c.rope_theta, "rms_norm_eps": c.rms_eps,
            "tie_word_embeddings": c.tie_embeddings,
            "max_position_embeddings": c.max_position,
        }
    return {
        "architectures": ["GraniteMoeHybridForCausalLM"],
        "model_type": "granitemoehybrid",
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_layers,
        "layer_types": list(c.layer_types),
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads, "head_dim": c.dim_per_head,
        "intermediate_size": c.intermediate_size,
        "shared_intermediate_size": c.shared_intermediate_size,
        "num_local_experts": c.num_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "mamba_n_heads": c.mamba_n_heads, "mamba_d_head": c.mamba_d_head,
        "mamba_d_state": c.mamba_d_state, "mamba_d_conv": c.mamba_d_conv,
        "mamba_n_groups": c.mamba_n_groups,
        "mamba_chunk_size": c.mamba_chunk_size,
        "embedding_multiplier": c.embedding_multiplier,
        "residual_multiplier": c.residual_multiplier,
        "attention_multiplier": c.attention_multiplier,
        "logits_scaling": c.logits_scaling,
        "position_embedding_type": "rope" if c.rope else "nope",
        "rope_theta": c.rope_theta, "rms_norm_eps": c.rms_eps,
        "tie_word_embeddings": c.tie_embeddings,
        "max_position_embeddings": c.max_position,
    }


def _no_name_map(config) -> None:
    """A nemotron_h or exaone_moe checkpoint's tensor names are not mapped:
    no checkpoint of either family has been in the repository to hold a map
    to (the served weights are random, from the seed)."""
    if config.ffn_layout is not None:
        raise ValueError(
            "a checkpoint of a model whose blocks are one sub-layer each "
            "(ffn_layout; HF nemotron_h) has no tensor-name map yet")
    if config.recurrent_kind is None and config.latent is None and (
            config.router_input == "ffn_input"):
        raise ValueError(
            "a checkpoint of a window / full model under deepseek_v3's "
            "router (HF exaone_moe) has no tensor-name map yet")


def _from_hf(ours: str, arr):
    """One HF tensor -> ours (a tuple for a fused pair)."""
    import numpy as np

    if ours == "conv_w":
        return np.ascontiguousarray(arr[:, 0, :].T)             # [K, C]
    if ours in ("wg", "sg"):                                    # fused pair
        half = arr.shape[-2] // 2
        return (np.swapaxes(arr[..., :half, :], -1, -2),
                np.swapaxes(arr[..., half:, :], -1, -2))
    if arr.ndim >= 2:
        return np.swapaxes(arr, -1, -2)
    return arr


def convert_hf_state_dict(tensors: dict, config) -> dict:
    """A full in-memory HF granitemoehybrid (or qwen3_next) state dict ->
    our pytree (numpy). Raises KeyError naming the first tensor that is
    missing and ValueError for one that maps nowhere."""
    import numpy as np

    if config.latent is not None:
        return _deepseek_from_hf(tensors, config)
    if config.recurrent_kind is None:
        return _smallthinker_from_hf(tensors, config)
    if config.recurrent_kind == "linear_attention":
        return _qwen3_next_from_hf(tensors, config)
    if config.recurrent_kind == "conv":
        return _lfm2_from_hf(tensors, config)
    _no_name_map(config)
    known = set(HF_TOP)
    stacks: dict = {"mamba": {}, "attn": {}, "ffn": {}}
    for i, kind in enumerate(config.layer_types):
        prefix = f"model.layers.{i}."
        for hf, ours in HF_MIXER[kind].items():
            known.add(prefix + hf)
            stacks[KIND_STACK[kind]].setdefault(ours, []).append(
                _from_hf(ours, tensors[prefix + hf]))
        for hf, names in HF_FFN.items():
            known.add(prefix + hf)
            parts = _from_hf(names[0], tensors[prefix + hf])
            for name, part in zip(names, parts if len(names) > 1
                                  else (parts,)):
                stacks["ffn"].setdefault(name, []).append(part)
    unmapped = sorted(set(tensors) - known - {"lm_head.weight"})
    if unmapped:
        raise ValueError(f"unmapped HF tensors: {unmapped[:4]}")
    return {"embed": tensors["model.embed_tokens.weight"],
            "final_norm": tensors["model.norm.weight"],
            "layers": {stack: {k: np.stack(v) for k, v in leaves.items()}
                       for stack, leaves in stacks.items()}}


def to_hf_state_dict(params: dict, config) -> dict:
    """The inverse of `convert_hf_state_dict` (numpy, float32)."""
    import numpy as np

    if config.latent is not None:
        return _deepseek_to_hf(params, config)
    if config.recurrent_kind is None:
        return _smallthinker_to_hf(params, config)
    if config.recurrent_kind == "linear_attention":
        return _qwen3_next_to_hf(params, config)
    if config.recurrent_kind == "conv":
        return _lfm2_to_hf(params, config)
    _no_name_map(config)

    def arr(a):
        return np.asarray(a, np.float32)

    lay = params["layers"]
    out = {"model.embed_tokens.weight": arr(params["embed"]),
           "model.norm.weight": arr(params["final_norm"])}
    for i, kind in enumerate(config.layer_types):
        prefix, j = f"model.layers.{i}.", stack_index(config, i)
        for hf, ours in HF_MIXER[kind].items():
            a = arr(lay[KIND_STACK[kind]][ours][j])
            if ours == "conv_w":
                a = a.T[:, None, :]
            elif a.ndim >= 2:
                a = np.swapaxes(a, -1, -2)
            out[prefix + hf] = a
        for hf, names in HF_FFN.items():
            parts = [np.swapaxes(arr(lay["ffn"][n][i]), -1, -2)
                     if lay["ffn"][n][i].ndim >= 2 else arr(lay["ffn"][n][i])
                     for n in names]
            out[prefix + hf] = np.concatenate(parts, axis=-2) \
                if len(parts) > 1 else parts[0]
    return out


# ---------------------------------------------------------------------------
# HF `qwen3_next` checkpoint names. HF fuses the Gated DeltaNet's projections
# PER KEY-HEAD GROUP: the rows of `in_proj_qkvz` are, for key head g,
# (q_g [Dk] | k_g [Dk] | v of g's value heads [r Dv] | z of them [r Dv]) with
# r = Hv / Hk, and those of `in_proj_ba` (b of g's value heads [r] | a [r]);
# ours are split by role, (q | k | v | z) and (b | a), every head in order.
# The routed experts are named one by one (`mlp.experts.{e}.gate_proj`, ...).

QWEN_MIXER = {
    "linear_attention": {
        "input_layernorm.weight": "norm",
        "linear_attn.in_proj_qkvz.weight": "in_proj",
        "linear_attn.in_proj_ba.weight": "in_ba",
        "linear_attn.conv1d.weight": "conv_w",
        "linear_attn.dt_bias": "dt_bias", "linear_attn.A_log": "A_log",
        "linear_attn.norm.weight": "gate_norm",
        "linear_attn.out_proj.weight": "out_proj"},
    "full_attention": {
        "input_layernorm.weight": "norm",
        "self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
        "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo",
        "self_attn.q_norm.weight": "q_norm",
        "self_attn.k_norm.weight": "k_norm"},
}
QWEN_FFN = {"post_attention_layernorm.weight": "norm",
            "mlp.gate.weight": "router",
            "mlp.shared_expert.gate_proj.weight": "sg",
            "mlp.shared_expert.up_proj.weight": "su",
            "mlp.shared_expert.down_proj.weight": "sd",
            "mlp.shared_expert_gate.weight": "sgate"}
QWEN_EXPERT = {"gate_proj": "wg", "up_proj": "wu", "down_proj": "wd"}
QWEN_TOP = {"model.embed_tokens.weight": "embed",
            "model.norm.weight": "final_norm", "lm_head.weight": "lm_head"}


def _group_widths(config) -> tuple[list[int], list[int]]:
    """Per key-head group, the widths HF fuses in `in_proj_qkvz` and in
    `in_proj_ba`."""
    z = gdn.sizes(config)
    r = z["Hv"] // z["Hk"]
    return [z["Dk"], z["Dk"], r * z["Dv"], r * z["Dv"]], [r, r]


def _ungroup(arr, widths: list[int], n_groups: int):
    """HF [n_groups * sum(widths), E] rows fused per group -> ours [E,
    sum over roles], every role's groups in order."""
    import numpy as np

    per = arr.reshape(n_groups, sum(widths), arr.shape[-1])
    cuts = np.cumsum([0] + widths)
    return np.concatenate(
        [per[:, a:b].reshape(-1, arr.shape[-1])
         for a, b in zip(cuts[:-1], cuts[1:])], axis=0).T


def _regroup(arr, widths: list[int], n_groups: int):
    """The inverse of `_ungroup`: ours [E, ...] -> HF's fused rows."""
    import numpy as np

    rows = arr.T
    cuts = np.cumsum([0] + [w * n_groups for w in widths])
    roles = [rows[a:b].reshape(n_groups, w, rows.shape[-1])
             for a, b, w in zip(cuts[:-1], cuts[1:], widths)]
    return np.concatenate(roles, axis=1).reshape(-1, rows.shape[-1])


def _qwen3_next_from_hf(tensors: dict, config) -> dict:
    import numpy as np

    qkvz, ba = _group_widths(config)
    n_groups = config.linear_num_key_heads
    known = set(QWEN_TOP)
    stacks: dict = {"gdn": {}, "attn": {}, "ffn": {}}

    def ours_of(name, a):
        if name == "in_proj":
            return _ungroup(a, qkvz, n_groups)
        if name == "in_ba":
            return _ungroup(a, ba, n_groups)
        if name == "conv_w":
            return np.ascontiguousarray(a[:, 0, :].T)           # [K, C]
        return np.swapaxes(a, -1, -2) if a.ndim >= 2 else a

    for i, kind in enumerate(config.layer_types):
        prefix = f"model.layers.{i}."
        for hf, name in QWEN_MIXER[kind].items():
            known.add(prefix + hf)
            stacks[KIND_STACK[kind]].setdefault(name, []).append(
                ours_of(name, tensors[prefix + hf]))
        for hf, name in QWEN_FFN.items():
            known.add(prefix + hf)
            stacks["ffn"].setdefault(name, []).append(
                ours_of(name, tensors[prefix + hf]))
        for hf, name in QWEN_EXPERT.items():
            names = [f"{prefix}mlp.experts.{e}.{hf}.weight"
                     for e in range(config.num_experts)]
            known.update(names)
            stacks["ffn"].setdefault(name, []).append(
                np.stack([tensors[n].T for n in names]))
    unmapped = sorted(set(tensors) - known)
    if unmapped:
        raise ValueError(f"unmapped HF tensors: {unmapped[:4]}")
    out = {"embed": tensors["model.embed_tokens.weight"],
           "final_norm": tensors["model.norm.weight"],
           "layers": {stack: {k: np.stack(v) for k, v in leaves.items()}
                      for stack, leaves in stacks.items()}}
    if not config.tie_embeddings:
        out["lm_head"] = tensors["lm_head.weight"].T
    return out


def _qwen3_next_to_hf(params: dict, config) -> dict:
    import numpy as np

    def arr(a):
        return np.asarray(a, np.float32)

    qkvz, ba = _group_widths(config)
    n_groups = config.linear_num_key_heads
    lay = params["layers"]
    out = {"model.embed_tokens.weight": arr(params["embed"]),
           "model.norm.weight": arr(params["final_norm"])}
    if not config.tie_embeddings:
        out["lm_head.weight"] = arr(params["lm_head"]).T

    def hf_of(name, a):
        if name == "in_proj":
            return _regroup(a, qkvz, n_groups)
        if name == "in_ba":
            return _regroup(a, ba, n_groups)
        if name == "conv_w":
            return a.T[:, None, :]
        return np.swapaxes(a, -1, -2) if a.ndim >= 2 else a

    for i, kind in enumerate(config.layer_types):
        prefix, j = f"model.layers.{i}.", stack_index(config, i)
        for hf, name in QWEN_MIXER[kind].items():
            out[prefix + hf] = hf_of(name, arr(lay[KIND_STACK[kind]][name][j]))
        for hf, name in QWEN_FFN.items():
            out[prefix + hf] = hf_of(name, arr(lay["ffn"][name][i]))
        for hf, name in QWEN_EXPERT.items():
            for e in range(config.num_experts):
                out[f"{prefix}mlp.experts.{e}.{hf}.weight"] = arr(
                    lay["ffn"][name][i][e]).T
    return out


# ---------------------------------------------------------------------------
# HF `lfm2_moe` checkpoint names. A layer's two norms are `operator_norm` and
# `ffn_norm`; the short convolution is `conv.in_proj` ([3E, E]: the rows of
# B, then C, then x), `conv.conv` ([E, 1, K], a cross-correlation: tap K-1
# meets the current position; ours [K, E]) and `conv.out_proj`; attention's
# output projection is `out_proj` and its per-head norms `q_layernorm` /
# `k_layernorm`; `feed_forward` is w1 / w3 / w2 (gate, up, down) in a dense
# layer and `gate` (the router), `expert_bias` and `experts.{e}.w1|w3|w2` in
# an expert layer; the final norm is `embedding_norm`, the head is tied.

LFM2_MIXER = {
    "conv": {"operator_norm.weight": "norm",
             "conv.in_proj.weight": "in_proj",
             "conv.conv.weight": "conv_w",
             "conv.out_proj.weight": "out_proj"},
    "full_attention": {
        "operator_norm.weight": "norm",
        "self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
        "self_attn.v_proj.weight": "wv", "self_attn.out_proj.weight": "wo",
        "self_attn.q_layernorm.weight": "q_norm",
        "self_attn.k_layernorm.weight": "k_norm"},
}
LFM2_FFN = {"dense": {"ffn_norm.weight": "norm",
                      "feed_forward.w1.weight": "wg",
                      "feed_forward.w3.weight": "wu",
                      "feed_forward.w2.weight": "wd"},
            "moe": {"ffn_norm.weight": "norm",
                    "feed_forward.gate.weight": "router",
                    "feed_forward.expert_bias": "expert_bias"}}
LFM2_EXPERT = {"w1": "wg", "w3": "wu", "w2": "wd"}
LFM2_TOP = {"model.embed_tokens.weight": "embed",
            "model.embedding_norm.weight": "final_norm"}
FFN_STACK = {"dense": "dense", "moe": "ffn"}


def _lfm2_ffn_names(config) -> dict:
    names = dict(LFM2_FFN["moe"])
    if not config.router_bias:
        del names["feed_forward.expert_bias"]
    return {"dense": LFM2_FFN["dense"], "moe": names}


def _lfm2_from_hf(tensors: dict, config) -> dict:
    import numpy as np

    def ours_of(name, a):
        if name == "conv_w":
            return np.ascontiguousarray(a[:, 0, :].T)           # [K, E]
        return np.swapaxes(a, -1, -2) if a.ndim >= 2 else a

    ffn_names = _lfm2_ffn_names(config)
    known = set(LFM2_TOP)
    stacks: dict = {"sconv": {}, "attn": {}, "dense": {}, "ffn": {}}
    for i, kind in enumerate(config.layer_types):
        prefix = f"model.layers.{i}."
        for hf, name in LFM2_MIXER[kind].items():
            known.add(prefix + hf)
            stacks[KIND_STACK[kind]].setdefault(name, []).append(
                ours_of(name, tensors[prefix + hf]))
        ffn = config.ffn_kind(i)
        for hf, name in ffn_names[ffn].items():
            known.add(prefix + hf)
            stacks[FFN_STACK[ffn]].setdefault(name, []).append(
                ours_of(name, tensors[prefix + hf]))
        if ffn == "moe":
            for hf, name in LFM2_EXPERT.items():
                names = [f"{prefix}feed_forward.experts.{e}.{hf}.weight"
                         for e in range(config.num_experts)]
                known.update(names)
                stacks["ffn"].setdefault(name, []).append(
                    np.stack([tensors[n].T for n in names]))
    unmapped = sorted(set(tensors) - known - {"lm_head.weight"})
    if unmapped:
        raise ValueError(f"unmapped HF tensors: {unmapped[:4]}")
    return {"embed": tensors["model.embed_tokens.weight"],
            "final_norm": tensors["model.embedding_norm.weight"],
            "layers": {stack: {k: np.stack(v) for k, v in leaves.items()}
                       for stack, leaves in stacks.items() if leaves}}


def _lfm2_to_hf(params: dict, config) -> dict:
    import numpy as np

    def arr(a):
        return np.asarray(a, np.float32)

    def hf_of(name, a):
        if name == "conv_w":
            return a.T[:, None, :]
        return np.swapaxes(a, -1, -2) if a.ndim >= 2 else a

    ffn_names = _lfm2_ffn_names(config)
    lay = params["layers"]
    out = {"model.embed_tokens.weight": arr(params["embed"]),
           "model.embedding_norm.weight": arr(params["final_norm"])}
    for i, kind in enumerate(config.layer_types):
        prefix, j = f"model.layers.{i}.", stack_index(config, i)
        for hf, name in LFM2_MIXER[kind].items():
            out[prefix + hf] = hf_of(name, arr(lay[KIND_STACK[kind]][name][j]))
        ffn = config.ffn_kind(i)
        at = i if ffn == "dense" else i - config.num_dense_layers
        for hf, name in ffn_names[ffn].items():
            out[prefix + hf] = hf_of(name, arr(lay[FFN_STACK[ffn]][name][at]))
        if ffn == "moe":
            for hf, name in LFM2_EXPERT.items():
                for e in range(config.num_experts):
                    out[f"{prefix}feed_forward.experts.{e}.{hf}.weight"] = \
                        arr(lay["ffn"][name][at][e]).T
    return out


# ---------------------------------------------------------------------------
# HF `deepseek_v3` checkpoint names (no query latent). Attention is `q_proj`,
# `kv_a_proj_with_mqa` ([rank + rope, E]: the latent's rows, then the shared
# rotary key's), `kv_a_layernorm`, `kv_b_proj` ([H (nope + v), rank]: a head's
# nope rows, then its v rows) and `o_proj`; the rope channels keep HF's
# INTERLEAVED order in both — the forward pass reorders them as HF's does
# (ops/rope.py `interleaved`). A dense layer's `mlp` is gate / up / down_proj;
# an expert layer's is `gate.weight` (the router), `gate.
# e_score_correction_bias`, `experts.{e}.*` and `shared_experts.*`. The
# absorbed factors are ours alone: derived on the way in, left out on the way
# back.

DEEPSEEK_ATTN = {"input_layernorm.weight": "norm",
                 "self_attn.q_proj.weight": "wq",
                 "self_attn.kv_a_proj_with_mqa.weight": "wkva",
                 "self_attn.kv_a_layernorm.weight": "kv_norm",
                 "self_attn.kv_b_proj.weight": "wkvb",
                 "self_attn.o_proj.weight": "wo"}
DEEPSEEK_FFN = {"dense": {"post_attention_layernorm.weight": "norm",
                          "mlp.gate_proj.weight": "wg",
                          "mlp.up_proj.weight": "wu",
                          "mlp.down_proj.weight": "wd"},
                "moe": {"post_attention_layernorm.weight": "norm",
                        "mlp.gate.weight": "router",
                        "mlp.gate.e_score_correction_bias": "expert_bias",
                        "mlp.shared_experts.gate_proj.weight": "sg",
                        "mlp.shared_experts.up_proj.weight": "su",
                        "mlp.shared_experts.down_proj.weight": "sd"}}
DEEPSEEK_TOP = {"model.embed_tokens.weight": "embed",
                "model.norm.weight": "final_norm",
                "lm_head.weight": "lm_head"}


def _deepseek_ffn_names(config) -> dict:
    names = dict(DEEPSEEK_FFN["moe"])
    if not config.shared_intermediate_size:
        names = {hf: n for hf, n in names.items()
                 if n not in ("sg", "su", "sd")}
    return {"dense": DEEPSEEK_FFN["dense"], "moe": names}


def _deepseek_from_hf(tensors: dict, config) -> dict:
    import numpy as np

    def ours_of(a):
        return np.swapaxes(a, -1, -2) if a.ndim >= 2 else a

    ffn_names = _deepseek_ffn_names(config)
    known = set(DEEPSEEK_TOP)
    stacks: dict = {"attn": {}, "dense": {}, "ffn": {}}
    for i in range(config.num_layers):
        prefix = f"model.layers.{i}."
        for hf, name in DEEPSEEK_ATTN.items():
            known.add(prefix + hf)
            stacks["attn"].setdefault(name, []).append(
                ours_of(tensors[prefix + hf]))
        ffn = config.ffn_kind(i)
        for hf, name in ffn_names[ffn].items():
            known.add(prefix + hf)
            stacks[FFN_STACK[ffn]].setdefault(name, []).append(
                ours_of(tensors[prefix + hf]))
        if ffn == "moe":
            for hf, name in QWEN_EXPERT.items():
                names = [f"{prefix}mlp.experts.{e}.{hf}.weight"
                         for e in range(config.num_experts)]
                known.update(names)
                stacks["ffn"].setdefault(name, []).append(
                    np.stack([tensors[n].T for n in names]))
    unmapped = sorted(set(tensors) - known)
    if unmapped:
        raise ValueError(f"unmapped HF tensors: {unmapped[:4]}")
    out = {"embed": tensors["model.embed_tokens.weight"],
           "final_norm": tensors["model.norm.weight"],
           "layers": {stack: {k: np.stack(v) for k, v in leaves.items()}
                      for stack, leaves in stacks.items() if leaves}}
    if not config.tie_embeddings:
        out["lm_head"] = tensors["lm_head.weight"].T
    return out


def _deepseek_to_hf(params: dict, config) -> dict:
    import numpy as np

    def arr(a):
        a = np.asarray(a, np.float32)
        return np.swapaxes(a, -1, -2) if a.ndim >= 2 else a

    ffn_names = _deepseek_ffn_names(config)
    lay = params["layers"]
    out = {"model.embed_tokens.weight": np.asarray(params["embed"],
                                                   np.float32),
           "model.norm.weight": arr(params["final_norm"])}
    if not config.tie_embeddings:
        out["lm_head.weight"] = arr(params["lm_head"])
    for i in range(config.num_layers):
        prefix = f"model.layers.{i}."
        for hf, name in DEEPSEEK_ATTN.items():
            out[prefix + hf] = arr(lay["attn"][name][i])
        ffn = config.ffn_kind(i)
        at = i if ffn == "dense" else i - config.num_dense_layers
        for hf, name in ffn_names[ffn].items():
            out[prefix + hf] = arr(lay[FFN_STACK[ffn]][name][at])
        if ffn == "moe":
            for hf, name in QWEN_EXPERT.items():
                for e in range(config.num_experts):
                    out[f"{prefix}mlp.experts.{e}.{hf}.weight"] = arr(
                        lay["ffn"][name][at][e])
    return out


# ---------------------------------------------------------------------------
# HF `smallthinker` checkpoint names (PowerInfer's modeling_smallthinker.py;
# the checkpoint is not in the sandbox). A layer of either attention kind has
# the same names — `self_attn.{q,k,v,o}_proj`, `input_layernorm`,
# `post_attention_layernorm` — and its kind decides the stack (`attn` /
# `swa`); the expert block is `block_sparse_moe.primary_router` (it reads the
# layer's input) and `block_sparse_moe.experts.{e}.{gate,up,down}`.

SMALLTHINKER_ATTN = {"input_layernorm.weight": "norm",
                     "self_attn.q_proj.weight": "wq",
                     "self_attn.k_proj.weight": "wk",
                     "self_attn.v_proj.weight": "wv",
                     "self_attn.o_proj.weight": "wo"}
SMALLTHINKER_FFN = {"post_attention_layernorm.weight": "norm",
                    "block_sparse_moe.primary_router.weight": "router"}
SMALLTHINKER_EXPERT = {"gate": "wg", "up": "wu", "down": "wd"}


def _smallthinker_from_hf(tensors: dict, config) -> dict:
    import numpy as np

    def ours_of(a):
        return np.swapaxes(a, -1, -2) if a.ndim >= 2 else a

    known = set(QWEN_TOP)
    stacks: dict = {"attn": {}, "swa": {}, "ffn": {}}
    for i, kind in enumerate(config.layer_types):
        prefix = f"model.layers.{i}."
        for hf, name in SMALLTHINKER_ATTN.items():
            known.add(prefix + hf)
            stacks[KIND_STACK[kind]].setdefault(name, []).append(
                ours_of(tensors[prefix + hf]))
        for hf, name in SMALLTHINKER_FFN.items():
            known.add(prefix + hf)
            stacks["ffn"].setdefault(name, []).append(
                ours_of(tensors[prefix + hf]))
        for hf, name in SMALLTHINKER_EXPERT.items():
            names = [f"{prefix}block_sparse_moe.experts.{e}.{hf}.weight"
                     for e in range(config.num_experts)]
            known.update(names)
            stacks["ffn"].setdefault(name, []).append(
                np.stack([tensors[n].T for n in names]))
    unmapped = sorted(set(tensors) - known)
    if unmapped:
        raise ValueError(f"unmapped HF tensors: {unmapped[:4]}")
    out = {"embed": tensors["model.embed_tokens.weight"],
           "final_norm": tensors["model.norm.weight"],
           "layers": {stack: {k: np.stack(v) for k, v in leaves.items()}
                      for stack, leaves in stacks.items() if leaves}}
    if not config.tie_embeddings:
        out["lm_head"] = tensors["lm_head.weight"].T
    return out


def _smallthinker_to_hf(params: dict, config) -> dict:
    import numpy as np

    def arr(a):
        a = np.asarray(a, np.float32)
        return np.swapaxes(a, -1, -2) if a.ndim >= 2 else a

    lay = params["layers"]
    out = {"model.embed_tokens.weight": np.asarray(params["embed"],
                                                   np.float32),
           "model.norm.weight": arr(params["final_norm"])}
    if not config.tie_embeddings:
        out["lm_head.weight"] = arr(params["lm_head"])
    for i, kind in enumerate(config.layer_types):
        prefix, j = f"model.layers.{i}.", stack_index(config, i)
        for hf, name in SMALLTHINKER_ATTN.items():
            out[prefix + hf] = arr(lay[KIND_STACK[kind]][name][j])
        for hf, name in SMALLTHINKER_FFN.items():
            out[prefix + hf] = arr(lay["ffn"][name][i])
        for hf, name in SMALLTHINKER_EXPERT.items():
            for e in range(config.num_experts):
                out[f"{prefix}block_sparse_moe.experts.{e}.{hf}.weight"] = \
                    arr(lay["ffn"][name][i][e])
    return out
