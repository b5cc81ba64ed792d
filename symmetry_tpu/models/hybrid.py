"""Decoder whose layers are of two mixer kinds (granitemoehybrid family):
Mamba-2 layers with a per-slot recurrent state (models/mamba2.py) among
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
carry.

Which form a mamba layer takes follows the call's shape: one position a
slot is the recurrence step; more is the chunked form, from zeros when the
caller says the cache is empty (`prefill_flash`, the engine's prefill: the
scratch it reuses is dirty) and from the cache's state otherwise.

One device only: there are no sharding rules for the state yet.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from symmetry_tpu.models import llama, mamba2
from symmetry_tpu.models.moe import moe_mlp
from symmetry_tpu.ops.norm import rms_norm

KIND_STACK = {"mamba": "mamba", "attention": "attn"}


def stack_index(config, i: int) -> int:
    """Layer i's index in the stack (and the cache leaves) of its kind."""
    return config.layers_of(config.layer_types[i]).index(i)


def state_bytes_per_slot(config, dtype=jnp.bfloat16) -> dict:
    """What one slot holds that is not a row per position."""
    z = mamba2.sizes(config)
    n = len(config.layers_of("mamba"))
    return {"ssm": n * z["H"] * z["P"] * z["N"] * 4,
            "conv": n * (z["K"] - 1) * z["conv"] * jnp.dtype(dtype).itemsize}


def init_cache(config, batch: int, capacity: int, dtype=jnp.bfloat16, *,
               quantized: bool = False, count_experts: bool = False
               ) -> llama.KVCache:
    z = mamba2.sizes(config)
    n_attn = len(config.layers_of("attention"))
    n_mamba = len(config.layers_of("mamba"))
    shape = (n_attn, batch, capacity, config.num_kv_heads,
             config.dim_per_head)
    scale_shape = (n_attn, batch, config.num_kv_heads, capacity)
    return llama.KVCache(
        k=jnp.zeros(shape, jnp.int8 if quantized else dtype),
        v=jnp.zeros(shape, jnp.int8 if quantized else dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
        k_scale=jnp.zeros(scale_shape, jnp.float32) if quantized else None,
        v_scale=jnp.zeros(scale_shape, jnp.float32) if quantized else None,
        expert_pairs=(jnp.zeros((config.num_experts,), jnp.int32)
                      if count_experts else None),
        ssm=jnp.zeros((n_mamba, batch, z["H"], z["P"], z["N"]), jnp.float32),
        conv=jnp.zeros((n_mamba, z["K"] - 1, batch, z["conv"]), dtype),
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
    z = mamba2.sizes(c)
    if slice_above is None:
        slice_above = default_leaf_limit()
    keys = iter(jax.random.split(key, 24))

    def dense(shape, name=None, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        quantized = quantize and name in llama.QUANT_KEYS
        make = (make_leaf_sliced if leaf_is_sliced(shape, dtype, None,
                                                   slice_above)
                else make_leaf)
        return make(next(keys), shape, scale, dtype, quantized=quantized)

    L, E, F = c.num_layers, c.hidden_size, c.intermediate_size
    X, Fs = c.num_experts, c.shared_intermediate_size
    Lm, La = len(c.layers_of("mamba")), len(c.layers_of("attention"))
    H = z["H"]
    # dt in [1e-3, 1e-1] log-uniform through the softplus, A in [1, 16]:
    # the published initialisation's ranges (decays from 0.2 to 0.999)
    dt = jnp.exp(jax.random.uniform(next(keys), (Lm, H), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    a = jax.random.uniform(next(keys), (Lm, H), jnp.float32, 1.0, 16.0)
    return {
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
            "ffn": {
                "norm": jnp.ones((L, E), dtype),
                "router": dense((L, E, X)),
                "wg": dense((L, X, E, F), "wg"),
                "wu": dense((L, X, E, F), "wu"),
                "wd": dense((L, X, F, E), "wd"),
                "sg": dense((L, E, Fs), "sg"),
                "su": dense((L, E, Fs), "su"),
                "sd": dense((L, Fs, E), "sd"),
            },
        },
        "final_norm": jnp.ones((E,), dtype),
    }


def state_refusals(*, mesh: bool = False, role: str = "unified",
                   prefix_cache: bool = False, speculative: bool = False,
                   prefill_chunk: int | None = None) -> list[str]:
    """Why a model with recurrent layers (a per-slot state beside the K/V
    rows) cannot be served under these settings: one sentence a setting,
    empty when it can. The engine raises the first as an EngineError;
    provider/config.py asks the same of a preset before anything is built
    and raises it as a ConfigError."""
    why = []
    if prefix_cache:
        why.append(
            "tpu.prefix_cache_mb: a cached prefix holds K/V rows and no "
            "recurrent state, so a hit would resume the mamba layers from "
            "nothing — leave it unset for a model with recurrent layers")
    if speculative:
        why.append(
            "tpu.speculative: a rejected draft is rolled back by lengths "
            "alone, and the recurrent state has already advanced past it — "
            "leave it unset for a model with recurrent layers")
    if prefill_chunk is not None:
        why.append(
            f"tpu.prefill_chunk {prefill_chunk}: the chunk programs are "
            f"not shown to carry the recurrent state from chunk to chunk — "
            f"set prefill_chunk: null for a model with recurrent layers "
            f"(prompts prefill whole, up to the largest bucket)")
    if role != "unified":
        why.append(
            f"tpu.role {role!r}: the KV handoff frame has no place for the "
            f"recurrent state — a model with recurrent layers serves "
            f"unified")
    if mesh:
        why.append(
            "tpu.mesh: the recurrent state has no sharding rules yet — a "
            "model with recurrent layers runs on one device")
    return why


def runs(config) -> list[tuple[str, int, int]]:
    """The pattern as runs of one mixer kind: (kind, first layer, length)."""
    out: list[tuple[str, int, int]] = []
    for i, kind in enumerate(config.layer_types):
        if out and out[-1][0] == kind:
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
    for kind, stack in KIND_STACK.items():
        n = jax.tree.leaves(layers[stack])[0].shape[0]
        if n != len(c.layers_of(kind)):
            raise ValueError(f"params carry {n} {kind} layers but "
                             f"layer_types has {len(c.layers_of(kind))}")
    h = jnp.take(params["embed"], tokens, axis=0)
    h = h * jnp.asarray(c.embedding_multiplier, h.dtype)
    r = jnp.asarray(c.residual_multiplier, h.dtype)

    def mixer(kind, x, lp, cache, j):
        if kind == "attention":
            return llama._attention(x, lp, cache, j, positions, kv_valid,
                                    seq_lens, c, prefill_flash and S > 1)
        conv = _at(cache.conv, j)
        if S == 1:  # the stack as it lies: layer j is the step's address
            out, ssm, conv = mamba2.step_at(x[:, 0], lp, cache.ssm, j,
                                            conv, c)
            return out[:, None], cache._replace(
                ssm=ssm, conv=cache.conv.at[j].set(conv))
        ssm = _at(cache.ssm, j)
        if prefill_flash:  # from empty, whatever the buffer holds
            ssm, conv = jnp.zeros_like(ssm), jnp.zeros_like(conv)
        out, ssm, conv = mamba2.chunked(x, lp, ssm, conv, seq_lens, c)
        return out, cache._replace(ssm=cache.ssm.at[j].set(ssm),
                                   conv=cache.conv.at[j].set(conv))

    for kind, first, length in runs(c):
        j0 = stack_index(c, first)

        def body(carry, step, kind=kind, first=first, j0=j0):
            h, cache = carry
            lp = _at(layers[KIND_STACK[kind]], j0 + step)
            out, cache = mixer(kind, rms_norm(h, lp["norm"], c.rms_eps), lp,
                               cache, j0 + step)
            h = h + r * out
            lp = _at(layers["ffn"], first + step)
            y, pairs = moe_mlp(rms_norm(h, lp["norm"], c.rms_eps), lp, c,
                               seq_lens)
            h = h + r * y
            if cache.expert_pairs is not None:
                cache = cache._replace(
                    expert_pairs=cache.expert_pairs + pairs)
            return (h, cache), None

        (h, cache), _ = jax.lax.scan(
            body, (h, cache), jnp.arange(length, dtype=jnp.int32))
    h = rms_norm(h, params["final_norm"], c.rms_eps)
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
    reference `benchmarks/reference/hybrid_decoder.py` is given)."""
    c = config
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
        "mamba_n_groups": 1, "mamba_chunk_size": c.mamba_chunk_size,
        "embedding_multiplier": c.embedding_multiplier,
        "residual_multiplier": c.residual_multiplier,
        "attention_multiplier": c.attention_multiplier,
        "logits_scaling": c.logits_scaling,
        "position_embedding_type": "rope" if c.rope else "nope",
        "rope_theta": c.rope_theta, "rms_norm_eps": c.rms_eps,
        "tie_word_embeddings": c.tie_embeddings,
        "max_position_embeddings": c.max_position,
    }


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
    """A full in-memory HF granitemoehybrid state dict -> our pytree
    (numpy). Raises KeyError naming the first tensor that is missing and
    ValueError for one that maps nowhere."""
    import numpy as np

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
