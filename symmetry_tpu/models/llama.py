"""Llama-family decoder (llama 3.x, mistral, any HF-llama-shaped LM).

Design (TPU-first, not a port — the reference has no model code to port):

  - Params are a plain pytree; `forward` is a pure function of
    (params, tokens, positions, cache). Everything jits.
  - All decoder layers are STACKED along a leading `layers` dim and executed
    with `lax.scan`: compile time is O(1) in depth (llama3-70b is 80 layers;
    unrolled tracing would take minutes and bloat the executable).
  - Projection weights stay fused 2-D ([embed, heads*head_dim]) so each layer
    is a handful of large matmuls the MXU tiles well, with logical axes
    mapped to the mesh by parallel/sharding.py (megatron-style TP by
    default — XLA derives the per-layer collectives from the shardings).
  - One forward serves prefill AND decode: masking is by absolute position
    (ops/attention.py), cache writes are scatters at per-sample positions,
    so a continuous batch of ragged requests runs at static shape.

HF weight compatibility (the engine loads HF safetensors directly):
tensor layout/naming map in `HF_LAYER_MAP` + `convert_hf_params`
(engine/weights.py does the streaming file IO).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from symmetry_tpu.ops.attention import gqa_attention
from symmetry_tpu.ops.interpret import interpret_mode
from symmetry_tpu.ops.norm import rms_norm
from symmetry_tpu.ops.quant import (
    QuantizedTensor, qmatmul, quantize_kv, quantize_tree)
from symmetry_tpu.ops.rope import apply_rope


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    head_dim: int | None = None          # defaults to hidden//heads
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    sliding_window: int | None = None    # mistral-v0.1 style local attention
    attention_bias: bool = False         # qwen2-style QKV projection biases
    max_position: int = 8192
    # gemma family: gelu-tanh GeGLU, RMSNorm scale stored as (weight - 1),
    # and embeddings multiplied by sqrt(hidden) at lookup
    hidden_act: str = "silu"             # "silu" | "gelu_tanh"
    norm_plus_one: bool = False
    scale_embed: bool = False

    @property
    def dim_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.dim_per_head

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.dim_per_head


@dataclass(frozen=True)
class SparseAttention:
    """Learned sparse attention (HF `sa_config`, DeepSeek-Sparse-Attention's
    lightning indexer; ops/sparse_attention.py): `index_heads` index query
    heads of `index_head_dim` score every cached position's ONE shared index
    key, and a query attends over its `topk` best positions."""

    topk: int
    index_heads: int
    index_head_dim: int


@dataclass(frozen=True)
class MoEConfig(ModelConfig):
    """Mixture-of-experts variant (mixtral family): the MLP becomes
    num_experts parallel FFNs with top-k routing (models/moe.py)."""

    num_experts: int = 8
    num_experts_per_tok: int = 2
    # width of a shared expert every token passes through beside its
    # routed ones (0: none); the routed experts' width is intermediate_size
    shared_intermediate_size: int = 0
    # KeyeVL2 family, each at "absent" for every other model: per-head
    # RMSNorm on q and k (the Qwen3-MoE block), the frequency pairs each
    # component of a multimodal rotary turns (ops/rope.py), and the sparse
    # attention's sizes (a third cache leaf, `KVCache.idx`, goes with them)
    qk_norm: bool = False
    mrope_section: tuple[int, ...] | None = None
    sparse: SparseAttention | None = None
    # whose tensor names the expert block has in an HF checkpoint
    # (`hf_moe_names`): "mixtral" (`block_sparse_moe`) or "qwen3_moe"
    # (`mlp.gate`, `mlp.experts`)
    hf_block: str = "mixtral"
    # random init draws a stacked leaf at fan_in ** -0.5 (models/hybrid.py's
    # scale). False keeps the older presets' layers ** -0.5 — the leading
    # axis of a stacked leaf — whose cells' weights may not move; at 4
    # layers that is 0.5 an entry: router logits of deviation 23, so a
    # softmax that is one expert, and expert outputs 100x the attention's
    # (PERF.md PR 40: bfloat16 rounding alone then flips one token in a
    # hundred to an unrelated logit row)
    init_fan_in: bool = False


@dataclass(frozen=True)
class HybridConfig(MoEConfig):
    """Layers of two mixer kinds in one model (granitemoehybrid, qwen3_next
    and lfm2_moe families): `layer_types[i]` is "mamba" (a Mamba-2 mixer
    with a per-slot recurrent state, models/mamba2.py) or "attention" (GQA,
    here without rotary embedding) — or, qwen3_next's pair,
    "linear_attention" (Gated DeltaNet, models/gdn.py) or "full_attention"
    — or, lfm2_moe's, "conv" (a gated short convolution, models/sconv.py) or
    "full_attention" — each followed by the routed (+ shared) expert FFN,
    lfm2_moe's first `num_dense_layers` by a dense SwiGLU instead. The forward pass, parameters and cache
    are models/hybrid.py's; every entry point of this module hands a config
    with `layer_types` over to it."""

    layer_types: tuple[str, ...] = ()
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None   # None: 1 / sqrt(head_dim)
    logits_scaling: float = 1.0
    rope: bool = True                 # False: position_embedding_type nope
    # qwen3_next family, every field at "absent" for any other model:
    # `layer_types[i]` is "linear_attention" (a Gated DeltaNet mixer with a
    # per-slot MATRIX state, models/gdn.py) or "full_attention" (GQA whose
    # q_proj also emits an output gate, q and k normed per head, only the
    # leading `partial_rotary_factor` of each head rotated), HF's names.
    linear_num_key_heads: int = 0
    linear_key_head_dim: int = 0
    linear_num_value_heads: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_chunk_size: int = 64       # the published kernels' chunk
    partial_rotary_factor: float = 1.0
    qk_norm: bool = False             # per-head RMSNorm on q and k
    attn_output_gate: bool = False    # wq is [E, 2 * q_dim]: (q | gate) a head
    shared_expert_gate: bool = False  # shared(x) * sigmoid(x . w_sg)
    # lfm2_moe family, every field at "absent" for any other model:
    # `layer_types[i]` is "conv" (a gated short convolution of `conv_L_cache`
    # taps whose only state is its last taps - 1 inputs, models/sconv.py) or
    # "full_attention" (GQA, q and k normed per head, full rotary, no gate);
    # the first `num_dense_layers` layers end in a dense SwiGLU of
    # `dense_intermediate_size` (stack `layers.dense`), the rest in experts
    # chosen by `router_score` "sigmoid": scores sigmoid(logits), selection
    # by score + `expert_bias` (`router_bias`), gates the selected's unbiased
    # scores renormalised and times `routed_scaling_factor` (models/moe.py).
    conv_L_cache: int = 0
    num_dense_layers: int = 0
    dense_intermediate_size: int = 0
    router_score: str = "softmax"     # "softmax" | "sigmoid"
    router_bias: bool = False
    routed_scaling_factor: float = 1.0

    def __post_init__(self):
        kinds = set(self.layer_types)
        if (len(self.layer_types) != self.num_layers
                or not (kinds <= {"mamba", "attention"}
                        or kinds <= {"linear_attention", "full_attention"}
                        or kinds <= {"conv", "full_attention"})):
            raise ValueError(
                f"layer_types must name {self.num_layers} layers, each "
                f"'mamba' or 'attention', or each 'linear_attention' or "
                f"'full_attention', or each 'conv' or 'full_attention'; "
                f"got {self.layer_types!r}")
        if ("conv" in kinds) != bool(self.conv_L_cache):
            raise ValueError(
                f"'conv' layers and conv_L_cache go together; got "
                f"conv_L_cache {self.conv_L_cache} with {self.layer_types!r}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score must be 'softmax' or 'sigmoid'; "
                             f"got {self.router_score!r}")
        if bool(self.num_dense_layers) != bool(self.dense_intermediate_size):
            raise ValueError("num_dense_layers and dense_intermediate_size "
                             "go together")

    def layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def recurrent_kind(self) -> str:
        """The name `layer_types` gives this model's recurrent layers."""
        if self.conv_L_cache:
            return "conv"
        return ("linear_attention" if "linear_attention" in self.layer_types
                or "full_attention" in self.layer_types else "mamba")

    @property
    def attention_kind(self) -> str:
        return "attention" if self.recurrent_kind == "mamba" \
            else "full_attention"

    def ffn_kind(self, i: int) -> str:
        """What layer i ends in: "dense" (the leading SwiGLU layers) or
        "moe"."""
        return "dense" if i < self.num_dense_layers else "moe"


# Named presets; sizes from the public HF configs of each model family.
PRESETS: dict[str, ModelConfig] = {
    # test-scale models (CPU-fast, exercised by the suite)
    "tiny": ModelConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=128, rope_theta=10000.0,
        max_position=512,
    ),
    "tiny-mha": ModelConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, intermediate_size=128, rope_theta=10000.0,
        max_position=512,
    ),
    # the first rounds' production targets (no benchmark cell serves them)
    "llama3-8b": ModelConfig(
        vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=8, intermediate_size=14336, rope_theta=500000.0,
    ),
    "llama3-70b": ModelConfig(
        vocab_size=128256, hidden_size=8192, num_layers=80, num_heads=64,
        num_kv_heads=8, intermediate_size=28672, rope_theta=500000.0,
    ),
    "llama3.2-1b": ModelConfig(
        vocab_size=128256, hidden_size=2048, num_layers=16, num_heads=32,
        num_kv_heads=8, intermediate_size=8192, rope_theta=500000.0,
        tie_embeddings=True,
    ),
    "mistral-7b": ModelConfig(
        vocab_size=32768, hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=8, intermediate_size=14336, rope_theta=1000000.0,
    ),
    "tiny-moe": MoEConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=128, rope_theta=10000.0,
        max_position=512, num_experts=4, num_experts_per_tok=2,
    ),
    # mixtral's routing shape (8 experts, top 2) with heads that divide a
    # `model: 4` mesh: the four-device rehearsals of the sharded path
    "tiny-moe8": MoEConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, intermediate_size=128, rope_theta=10000.0,
        max_position=512, num_experts=8, num_experts_per_tok=2,
    ),
    # the CPU's copy of Keye-VL-2.0's mechanisms: a lightning indexer whose
    # topk is SHORTER than the test prompts, GQA with q/k norms, a rotary of
    # three position components, 8 experts top 2, an untied head
    "tiny-dsa": MoEConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=32, head_dim=16,
        rope_theta=10000.0, rms_eps=1e-6, max_position=512,
        num_experts=8, num_experts_per_tok=2, qk_norm=True,
        mrope_section=(2, 3, 3),
        sparse=SparseAttention(topk=16, index_heads=4, index_head_dim=8),
        hf_block="qwen3_moe", init_fan_in=True,
    ),
    # Keye-VL-2.0-30B-A3B's language model CUT IN DEPTH to 4 of its 48
    # identical layers at every published width, with all 128 experts and
    # the whole vocabulary: stage 1 of 12 of a pipeline, what one 16 GB
    # chip holds in int8 beside a 64 x 16,384 cache (benchmarks/configs/
    # keye-vl-2.0-30b-a3b.json has the cut). `intermediate_size` is the
    # routed expert's width (`moe_intermediate_size`); the published dense
    # width 6144 serves no layer. The vision tower is left out: the served
    # path feeds three equal position components.
    "keye-vl-2.0-30b-a3b": MoEConfig(
        vocab_size=151936, hidden_size=2048, num_layers=4, num_heads=32,
        num_kv_heads=4, intermediate_size=768, head_dim=128,
        rope_theta=10000000.0, rms_eps=1e-6, max_position=262144,
        num_experts=128, num_experts_per_tok=8, qk_norm=True,
        mrope_section=(16, 24, 24),
        sparse=SparseAttention(topk=2048, index_heads=16,
                               index_head_dim=64),
        hf_block="qwen3_moe", init_fan_in=True,
    ),
    "mixtral-8x7b": MoEConfig(
        vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=8, intermediate_size=14336, rope_theta=1000000.0,
        num_experts=8, num_experts_per_tok=2,
    ),
    # both mixer kinds, 8 routed experts top 3 and a shared one, every
    # multiplier off 1, a chunk shorter than the test prompts: the CPU's
    # copy of granite-4.0-h-small's mechanisms
    "tiny-hybrid": HybridConfig(
        vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, intermediate_size=32, head_dim=16,
        rope_theta=10000.0, max_position=512, tie_embeddings=True,
        num_experts=8, num_experts_per_tok=3, shared_intermediate_size=48,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_chunk_size=16, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=1 / 16,
        logits_scaling=4.0, rope=False,
    ),
    # granite-4.0-h-small (32B-A9B) CUT IN DEPTH to the first period of its
    # layer pattern — layers 0-9 of 40: mamba x 5, attention, mamba x 4 —
    # at every published width, with all 72 experts and the whole
    # vocabulary: stage 1 of 4 of a pipeline, what one 16 GB chip holds in
    # int8 (benchmarks/configs/granite-4.0-h-small.json has the cut).
    # rope_theta is the published key; no layer uses it (rope=False).
    "granite-4.0-h-small": HybridConfig(
        vocab_size=100352, hidden_size=4096, num_layers=10, num_heads=32,
        num_kv_heads=8, intermediate_size=768, head_dim=128,
        rope_theta=10000.0, rms_eps=1e-5, tie_embeddings=True,
        max_position=131072,
        num_experts=72, num_experts_per_tok=10,
        shared_intermediate_size=1536,
        layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
        mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
        mamba_d_conv=4, mamba_chunk_size=256, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.0078125,
        logits_scaling=16.0, rope=False,
    ),
    # the CPU's copy of qwen3-next's mechanisms: the 3:1 pattern, 2 q/k
    # heads serving 4 value heads, a chunk shorter than the test prompts,
    # a quarter of each attention head rotated, 16 experts top 4 and a
    # gated shared one, zero-centred norms, an untied head
    "tiny-gdn": HybridConfig(
        vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, intermediate_size=32, head_dim=16,
        rope_theta=10000.0, rms_eps=1e-6, max_position=512,
        norm_plus_one=True, num_experts=16, num_experts_per_tok=4,
        shared_intermediate_size=32,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_num_key_heads=2, linear_key_head_dim=16,
        linear_num_value_heads=4, linear_value_head_dim=16,
        linear_chunk_size=16, partial_rotary_factor=0.25, qk_norm=True,
        attn_output_gate=True, shared_expert_gate=True,
    ),
    # Qwen3-Next-80B-A3B-Instruct CUT IN DEPTH to the first period of its
    # layer pattern — layers 0-3 of 48: Gated DeltaNet x 3, gated attention
    # — at every published width, with all 512 experts and the whole
    # vocabulary: stage 1 of 12 of a pipeline, what one 16 GB chip holds in
    # int8 (benchmarks/configs/qwen3-next-80b-a3b.json has the cut).
    # `intermediate_size` is the routed expert's width
    # (`moe_intermediate_size`); the published dense width 5120 serves no
    # layer (`mlp_only_layers` is empty).
    "qwen3-next-80b-a3b": HybridConfig(
        vocab_size=151936, hidden_size=2048, num_layers=4, num_heads=16,
        num_kv_heads=2, intermediate_size=512, head_dim=256,
        rope_theta=10000000.0, rms_eps=1e-6, max_position=262144,
        norm_plus_one=True, num_experts=512, num_experts_per_tok=10,
        shared_intermediate_size=512,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_num_key_heads=16, linear_key_head_dim=128,
        linear_num_value_heads=32, linear_value_head_dim=128,
        linear_conv_kernel_dim=4, linear_chunk_size=64,
        partial_rotary_factor=0.25, qk_norm=True, attn_output_gate=True,
        shared_expert_gate=True,
    ),
    # the CPU's copy of LFM2-MoE's mechanisms with its IRREGULAR pattern: two
    # leading dense layers, an attention layer after two conv layers and
    # after three, a sigmoid router with a selection bias over 8 experts top
    # 2, q/k norms, a tied head
    "tiny-sconv": HybridConfig(
        vocab_size=512, hidden_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, intermediate_size=32, head_dim=16,
        rope_theta=10000.0, rms_eps=1e-5, max_position=512,
        tie_embeddings=True, num_experts=8, num_experts_per_tok=2,
        qk_norm=True,
        layer_types=("conv", "conv", "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv"),
        conv_L_cache=3, num_dense_layers=2, dense_intermediate_size=128,
        router_score="sigmoid", router_bias=True,
    ),
    # LFM2-8B-A1B WHOLE: all 24 layers (18 gated short convolutions, six GQA
    # layers at 2, 6, 10, 14, 18, 21), both leading dense layers and all 32
    # experts of the other 22, the whole vocabulary, every published width —
    # 8.5 GB in int8, half of one 16 GB chip (benchmarks/configs/
    # lfm2-8b-a1b.json). `intermediate_size` is the routed expert's width
    # (`moe_intermediate_size`), `dense_intermediate_size` the published
    # `intermediate_size` of layers 0 and 1. Heads of 64 = 2,048 / 32.
    "lfm2-8b-a1b": HybridConfig(
        vocab_size=65536, hidden_size=2048, num_layers=24, num_heads=32,
        num_kv_heads=8, intermediate_size=1792, head_dim=64,
        rope_theta=1000000.0, rms_eps=1e-5, max_position=128000,
        tie_embeddings=True, num_experts=32, num_experts_per_tok=4,
        qk_norm=True,
        layer_types=("conv", "conv", "full_attention")
        + ("conv", "conv", "conv", "full_attention") * 4
        + ("conv", "conv", "full_attention", "conv", "conv"),
        conv_L_cache=3, num_dense_layers=2, dense_intermediate_size=7168,
        router_score="sigmoid", router_bias=True, routed_scaling_factor=1.0,
    ),
    "gemma-7b": ModelConfig(
        vocab_size=256000, hidden_size=3072, num_layers=28, num_heads=16,
        num_kv_heads=16, intermediate_size=24576, head_dim=256,
        rope_theta=10000.0, rms_eps=1e-6, tie_embeddings=True,
        hidden_act="gelu_tanh", norm_plus_one=True, scale_embed=True,
    ),
    "gemma-2b": ModelConfig(
        vocab_size=256000, hidden_size=2048, num_layers=18, num_heads=8,
        num_kv_heads=1, intermediate_size=16384, head_dim=256,
        rope_theta=10000.0, rms_eps=1e-6, tie_embeddings=True,
        hidden_act="gelu_tanh", norm_plus_one=True, scale_embed=True,
    ),
    "tiny-gemma": ModelConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=128, head_dim=16,
        rope_theta=10000.0, max_position=512, tie_embeddings=True,
        hidden_act="gelu_tanh", norm_plus_one=True, scale_embed=True,
    ),
    "qwen2-7b": ModelConfig(
        vocab_size=152064, hidden_size=3584, num_layers=28, num_heads=28,
        num_kv_heads=4, intermediate_size=18944, rope_theta=1000000.0,
        rms_eps=1e-6, attention_bias=True,
    ),
    "tiny-qwen": ModelConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=128, rope_theta=10000.0,
        max_position=512, attention_bias=True,
    ),
}


def preset(name: str) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(PRESETS)}")
    return PRESETS[name]


def kv_row(config: ModelConfig) -> tuple[int, int]:
    """(rows, width) of one position's K (or V) as the cache holds it:
    (kv_heads, head_dim), but heads of 64 lie in PAIRS, (kv_heads / 2,
    128) with heads 2p and 2p + 1 in the two halves of row p. A 64-wide
    minor dimension is half a lane tile: compiled for a described v5e
    `s8[6, 128, 640, 8, 64]` is `{4,3,2,1,0:T(8,128)(4,1)}` — the 64
    padded to 128 lanes, 1,024 bytes a position for 512, bf16 / f32 alike
    — and the attached chip's default layout of the shape is position-
    minor (dense, a position's row strewn a capacity apart: PERF.md, PR
    43); either way XLA slices and relays a whole layer to attend over it
    and the decode kernel has no view of it (Mosaic refuses a 64-wide
    slice of the padded rows). Folded, the bytes are dense where they
    lie, a position's row contiguous; it is a reshape of the row, never
    of what a head holds. `write_kv` folds, the XLA attention unfolds,
    ops/decode_attention.py reads the pairs as they lie."""
    K, D = config.num_kv_heads, config.dim_per_head
    return (K // 2, 2 * D) if D == 64 and K % 2 == 0 else (K, D)


class KVCache(NamedTuple):
    """Static-shape KV cache: [layers, batch, capacity, kv_heads, head_dim]
    ([..., kv_heads / 2, 128] for heads of 64: `kv_row`).

    With quantized=True at init, k/v hold int8 payloads and k_scale/v_scale
    hold the per-(layer, slot, kv_head, position) f32 dequant scales
    (ops/quant.py quantize_kv) — [layers, batch, kv_heads, capacity].
    Position is the MINOR scale dim on purpose: with kv_heads (8) minor the
    arrays would tile-pad 16x in HBM the moment a Pallas kernel takes them
    as operands. The scale planes are head_dim× smaller than the payload,
    so the decode-step cache read drops to ~half of bf16.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    lengths: jnp.ndarray  # [batch] int32: valid entries per slot
    k_scale: jnp.ndarray | None = None
    v_scale: jnp.ndarray | None = None
    # MoE only, and only where the caller asked init_cache to count: valid
    # (token, expert) pairs computed per expert, summed over the layers of
    # every forward through this cache ([experts] int32; models/moe.py).
    expert_pairs: jnp.ndarray | None = None
    # Models with recurrent layers only (models/hybrid.py; None, so no
    # leaf, for every other): per (mamba layer, slot) the state after the
    # slot's last token, ssm [n_mamba, B, H, P, N] float32, and its last
    # d_conv - 1 convolution inputs, conv [n_mamba, d_conv - 1, B, C].
    # Neither is indexed by position; k / v then hold the ATTENTION layers
    # alone, in pattern order.
    ssm: jnp.ndarray | None = None
    conv: jnp.ndarray | None = None
    # Models with learned sparse attention only (config.sparse; None, so no
    # leaf, for every other): the indexer's roped key of each cached
    # position, idx [L, B, T, index_head_dim] in the compute dtype. Such a
    # model's `expert_pairs` ends in ops/sparse_attention.py's N_COUNTS
    # counters (queries, dense queries, candidates, selected): they are
    # zeroed, merged at an insert and handed out exactly where the expert
    # counts are (engine/engine.py: eight sites in five programs), so they
    # ride that vector instead of a leaf that would twin each site; the
    # engine alone splits them off (`collect_expert_pairs`).
    idx: jnp.ndarray | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_cache(
    config: ModelConfig, batch: int, capacity: int, dtype=jnp.bfloat16,
    *, quantized: bool = False, count_experts: bool = False,
) -> KVCache:
    if getattr(config, "layer_types", None):
        from symmetry_tpu.models import hybrid

        return hybrid.init_cache(config, batch, capacity, dtype,
                                 quantized=quantized,
                                 count_experts=count_experts)
    shape = (config.num_layers, batch, capacity, *kv_row(config))
    pairs = (jnp.zeros((config.num_experts,), jnp.int32)
             if count_experts else None)
    extra = {}
    sparse = getattr(config, "sparse", None)
    if sparse is not None:
        from symmetry_tpu.ops.sparse_attention import N_COUNTS

        extra["idx"] = jnp.zeros((config.num_layers, batch, capacity,
                                  sparse.index_head_dim), dtype)
        if count_experts:
            pairs = jnp.zeros((config.num_experts + N_COUNTS,), jnp.int32)
    if quantized:
        scale_shape = (config.num_layers, batch, config.num_kv_heads,
                       capacity)
        return KVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            lengths=jnp.zeros((batch,), jnp.int32),
            k_scale=jnp.zeros(scale_shape, jnp.float32),
            v_scale=jnp.zeros(scale_shape, jnp.float32),
            expert_pairs=pairs, **extra,
        )
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
        expert_pairs=pairs, **extra,
    )


def write_kv(cache: KVCache, layer: jnp.ndarray, positions: jnp.ndarray,
             k: jnp.ndarray, v: jnp.ndarray, *, by_head: bool,
             idx: jnp.ndarray | None = None) -> KVCache:
    """Scatter this call's K/V ([B, S, K, D], roped) straight into the full
    cache at (layer, slot, position) — an in-place row write on the layer
    scan's carry; a per-layer slice-out/slice-in would stream the whole
    layer slice through HBM. `positions` is [B, S]; a position at or past
    the capacity is dropped. Padded tail tokens write garbage past the
    slot's valid length — never read, overwritten later. A quantized cache
    takes the int8 payload plus the f32 scales (ops/quant.py quantize_kv).
    Heads of 64 are written as the cache holds them, in pairs (`kv_row`);
    their scales stay one a head.
    `idx` ([B, S, index_head_dim]; sparse attention) goes into `cache.idx`
    at the same rows.

    `by_head` (the trunk is sharded over a mesh): a cache sharded by KV
    head may hold 2 heads a chip, and XLA then keeps it in HBM as
    [L, B, K, T, D] (a second-minor dim of 2 would pad 2x). A scatter
    whose window is the [K, D] row needs K next to D, so the program
    copied the whole cache into the padded layout around every decode
    block (+4.3 GB a chip at 64 x 2048: it did not fit). Indexing the head
    too leaves a window of D alone, which either layout serves in place.
    """
    B, S = k.shape[:2]
    nkv = cache.k.shape[3]

    def rows(x):
        return x.reshape(B, S, *cache.k.shape[3:])

    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    l_idx = jnp.full((B, S), layer, jnp.int32)
    row = (l_idx, b_idx, positions)  # each write is one [K, D] row
    if idx is not None:
        cache = cache._replace(
            idx=cache.idx.at[row].set(idx.astype(cache.idx.dtype)))
    if by_head:
        row = tuple(i[..., None] for i in row) + (
            jnp.arange(nkv, dtype=jnp.int32)[None, None, :],)
    if not cache.quantized:
        return cache._replace(
            k=cache.k.at[row].set(rows(k).astype(cache.k.dtype)),
            v=cache.v.at[row].set(rows(v).astype(cache.v.dtype)))
    kq, ks = quantize_kv(k)  # ks [B, S, K]
    vq, vs = quantize_kv(v)
    # Scale planes are [L, B, K, T] (position minor, see KVCache): the
    # mixed advanced/slice index puts the advanced dims (B, S) in front,
    # matching the [B, S, K] scale values.
    return cache._replace(
        k=cache.k.at[row].set(rows(kq)),
        v=cache.v.at[row].set(rows(vq)),
        k_scale=cache.k_scale.at[l_idx, b_idx, :, positions].set(ks),
        v_scale=cache.v_scale.at[l_idx, b_idx, :, positions].set(vs))


# ---------------------------------------------------------------------------
# Parameters


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                *, quantize: bool = False, shardings: dict | None = None,
                slice_above: int | None = None) -> dict:
    """Random init (scaled normal). Real serving loads HF weights instead.

    quantize=True materializes QUANT_KEYS leaves as int8 directly — the
    whole random-init→scale→quantize pipeline for a leaf runs as ONE
    compiled program (ops/quant.py make_leaf), so no full-precision copy of
    a leaf ever lands in HBM beyond that program's fused temporaries. That
    is what lets an 8B-parameter model initialize on a 16 GB chip.

    A leaf whose per-device share in `dtype` is over `slice_above` bytes
    (default: a quarter of the device's memory, ops/quant.py
    default_leaf_limit) is built a layer at a time instead
    (make_leaf_sliced) — mixtral-8x7b's expert stacks. `shardings` is the
    params' sharding tree when the caller jits this with out_shardings: it
    gives the per-device share and places each slice.
    """
    c = config
    if getattr(c, "layer_types", None):
        from symmetry_tpu.models import hybrid

        return hybrid.init_params(c, key, dtype, quantize=quantize,
                                  slice_above=slice_above)
    keys = iter(jax.random.split(key, 16))

    from symmetry_tpu.ops.quant import (
        default_leaf_limit, leaf_is_sliced, make_leaf, make_leaf_sliced)

    if slice_above is None:
        slice_above = default_leaf_limit()

    fan_axis = -2 if getattr(c, "init_fan_in", False) else 0

    def dense(k, shape, scale=None, name=None):
        scale = scale if scale is not None else shape[fan_axis] ** -0.5
        quantized = quantize and name in QUANT_KEYS
        # Only the stacked per-layer leaves have a layers axis to slice.
        where = (shardings or {}).get("layers", {}).get(name)
        q_where = where.q if isinstance(where, QuantizedTensor) else where
        if name in STACKED_KEYS and leaf_is_sliced(shape, dtype, q_where,
                                                   slice_above):
            return make_leaf_sliced(k, shape, scale, dtype,
                                    quantized=quantized, sharding=where)
        return make_leaf(k, shape, scale, dtype, quantized=quantized)

    L, E, F = c.num_layers, c.hidden_size, c.intermediate_size
    n_exp = getattr(c, "num_experts", 0)
    # MoE: FFN weights gain a leading experts dim; the router stays dense
    # (it is contracted per token, tiny, and its logits feed a top-k).
    ffn = (L, n_exp, E, F) if n_exp else (L, E, F)
    ffn_d = (L, n_exp, F, E) if n_exp else (L, F, E)
    params = {
        "embed": dense(next(keys), (c.vocab_size, E), scale=0.02),
        "layers": {
            "attn_norm": jnp.ones((L, E), dtype),
            "mlp_norm": jnp.ones((L, E), dtype),
            "wq": dense(next(keys), (L, E, c.q_dim), name="wq"),
            "wk": dense(next(keys), (L, E, c.kv_dim), name="wk"),
            "wv": dense(next(keys), (L, E, c.kv_dim), name="wv"),
            "wo": dense(next(keys), (L, c.q_dim, E), name="wo"),
            "wg": dense(next(keys), ffn, name="wg"),
            "wu": dense(next(keys), ffn, name="wu"),
            "wd": dense(next(keys), ffn_d, name="wd"),
        },
        "final_norm": jnp.ones((E,), dtype),
    }
    if n_exp:
        params["layers"]["router"] = dense(next(keys), (L, E, n_exp))
    if c.attention_bias:
        # qwen2: biases on q/k/v projections only (not o/mlp)
        params["layers"]["bq"] = jnp.zeros((L, c.q_dim), dtype)
        params["layers"]["bk"] = jnp.zeros((L, c.kv_dim), dtype)
        params["layers"]["bv"] = jnp.zeros((L, c.kv_dim), dtype)
    if not c.tie_embeddings:
        params["lm_head"] = dense(next(keys), (E, c.vocab_size), scale=0.02,
                                  name="lm_head")
    # (drawn after every key above, so no other model's weights move)
    if getattr(c, "qk_norm", False):
        params["layers"]["q_norm"] = jnp.ones((L, c.dim_per_head), dtype)
        params["layers"]["k_norm"] = jnp.ones((L, c.dim_per_head), dtype)
    sparse = getattr(c, "sparse", None)
    if sparse is not None:
        # the lightning indexer: index queries, the one shared index key,
        # and the per-head weights (bf16 like the router: 16 columns that
        # feed a selection)
        params["layers"]["wqi"] = dense(
            next(keys), (L, E, sparse.index_heads * sparse.index_head_dim),
            name="wqi")
        params["layers"]["wki"] = dense(
            next(keys), (L, E, sparse.index_head_dim), name="wki")
        params["layers"]["wwi"] = dense(next(keys),
                                        (L, E, sparse.index_heads))
    return params


def param_logical_axes(config: ModelConfig) -> dict:
    """Pytree of logical-axis tuples, same structure as init_params output."""
    if getattr(config, "layer_types", None):
        raise NotImplementedError(
            "a model with recurrent layers has no sharding rules yet: it "
            "runs on one device")
    moe = bool(getattr(config, "num_experts", 0))
    ffn = (("layers", "experts", "embed", "mlp") if moe
           else ("layers", "embed", "mlp"))
    ffn_d = (("layers", "experts", "mlp", "embed") if moe
             else ("layers", "mlp", "embed"))
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "mlp_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "wg": ffn,
            "wu": ffn,
            "wd": ffn_d,
        },
        "final_norm": ("embed",),
    }
    if moe:
        axes["layers"]["router"] = ("layers", "embed", None)
    if config.attention_bias:
        axes["layers"]["bq"] = ("layers", "heads")
        axes["layers"]["bk"] = ("layers", "kv_heads")
        axes["layers"]["bv"] = ("layers", "kv_heads")
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if getattr(config, "qk_norm", False):
        axes["layers"]["q_norm"] = ("layers", None)
        axes["layers"]["k_norm"] = ("layers", None)
    if getattr(config, "sparse", None) is not None:
        for name in ("wqi", "wki", "wwi"):
            axes["layers"][name] = ("layers", "embed", None)
    return axes


def cache_logical_axes(*, quantized: bool = False,
                       count_experts: bool = False) -> KVCache:
    kv = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
    sc = ("layers", "batch", "kv_heads", "cache_seq") if quantized else None
    return KVCache(k=kv, v=kv, lengths=("batch",), k_scale=sc, v_scale=sc,
                   expert_pairs=(None,) if count_experts else None)


# ---------------------------------------------------------------------------
# Forward


def attention_paths(config: ModelConfig, capacity: int, tp_mesh=None, *,
                    batch: int, kv_bytes: int) -> dict:
    """Which attention implementation the prefill-from-empty and the
    single-position decode programs take at this cache shape (`batch`
    slots of `capacity` entries of `kv_bytes`): "pallas" (ops/flash.py,
    ops/decode_attention.py), "pallas-interpret" (the same kernels on the
    CPU backend) or "xla" (ops/attention.py gqa_attention). `_layer`
    routes by this and the engine reports it, so a kernel giving way to
    the XLA path is visible rather than quiet. One route per observable
    case, decided by shapes and the mesh alone:

      - decode takes the kernel wherever it has a geometry for the cache
        as it lies on the chip (ops/decode_attention.py geometry: the one
        shape gate), and the reply names the `decode_slot_tile` and
        `decode_block_t` it compiles with;
      - a GSPMD mesh: the kernels run per shard with KV heads over
        `model`, which needs the heads to divide — otherwise that build
        keeps the XLA path — and decode takes the kernel only from
        TP_MIN_CAPACITY up: below it a sharded trunk stays on the XLA
        path (2 KV heads a chip lie head-major there and no slice is
        staged: PERF.md PR 28) until a four-chip A/B says otherwise;
      - learned sparse attention (`config.sparse`): the decode kernel
        takes a selection only beside the scale planes of an int8 cache of
        interleaved heads (ops/decode_attention.py keep_supported); any
        other cache decodes on the XLA path;
      - a head of 64 (lfm2-8b-a1b) lies in the cache in pairs (`kv_row`)
        and takes the kernel's pair form where the pairs are interleaved
        rows; any other head size that is no lane tile (the tiny test
        configurations' 16) keeps the XLA path and the reply says why
        (`decode_why`)."""
    from symmetry_tpu.ops import decode_attention as da

    kernel = "pallas-interpret" if interpret_mode() else "pallas"
    model = 1 if tp_mesh is None else dict(tp_mesh.shape).get("model", 1)
    if config.num_kv_heads % model:
        return {"prefill": "xla", "decode": "xla"}
    data = 1 if tp_mesh is None else dict(tp_mesh.shape).get("data", 1)
    tiles = da.geometry(batch // data if batch % data == 0 else batch,
                        capacity, config.num_kv_heads // model,
                        config.dim_per_head, kv_bytes)
    if tiles is None and config.dim_per_head % da.LANES:
        # the reason rides along where the SHAPE is what the kernel lacks
        # (the served configurations have heads of 64, 128 or 256); the
        # flash kernel lowers at any [block, D] (tests/test_chip_compile.py)
        why = (f"ops/decode_attention.py has no geometry for a head of "
               f"{config.dim_per_head}: no lane tile of {da.LANES}")
        if 2 * config.dim_per_head == da.LANES:
            why += (f", and {config.num_kv_heads // model} KV heads a chip "
                    f"are no interleaved pairs at this cache")
        return {"prefill": kernel, "decode": "xla", "decode_why": why}
    if tiles is None or (tp_mesh is not None
                         and capacity < da.TP_MIN_CAPACITY):
        return {"prefill": kernel, "decode": "xla"}
    if getattr(config, "sparse", None) is not None and not da.keep_supported(
            config.num_kv_heads, kv_bytes, kv_bytes == 1,
            config.dim_per_head):
        return {"prefill": kernel, "decode": "xla"}
    return {"prefill": kernel, "decode": kernel,
            "decode_slot_tile": tiles[0], "decode_block_t": tiles[1]}


def sparse_forms(paths: dict) -> dict:
    """What each program's attention is under a learned selection, from
    `attention_paths`' routes — every one the lossless MASKED form (each
    live block is read, what the query left out is set to -inf); the
    gather form (read the selected rows alone) is not built."""
    def form(route, kernel):
        return f"masked ({kernel if route != 'xla' else 'xla'})"

    return {"prefill": form(paths["prefill"], "dsa_flash kernel"),
            "decode": form(paths["decode"], "decode kernel")}


def sparse_select(paths: dict, capacity: int, slots: int,
                  index_dim: int) -> dict:
    """What makes each program's SELECTION (index scores, threshold, tie
    rule, mask): the `dsa_select` kernel — over a prompt's own index keys
    on the flash route, over each slot's live cached ones at decode where
    a cache of `slots` x `capacity` keys of `index_dim` has the kernel's
    layout — or the `jnp` form through XLA (`_attention`'s routing, asked
    again)."""
    from symmetry_tpu.ops.sparse_attention import SELECT_NAME, decode_group

    def by(kernel):
        return f"{SELECT_NAME} kernel" if kernel else "xla"

    return {"prefill": by(paths["prefill"] != "xla"),
            "decode": by(decode_group(capacity, slots,
                                      index_dim) is not None)}


def sparse_refusals(*, mesh: bool = False, role: str = "unified",
                    prefix_cache: bool = False, speculative: bool = False,
                    prefill_chunk: int | None = None) -> list[str]:
    """Why a model with learned sparse attention (an index key a cached
    position beside its K/V row) cannot be served under these settings:
    one sentence a setting, empty when it can. The engine raises the first
    as an EngineError; provider/config.py asks the same of a preset before
    anything is built and raises it as a ConfigError."""
    why = []
    if prefix_cache:
        why.append(
            "tpu.prefix_cache_mb: the block pool knows one entry shape (K, "
            "V and their scales) and would hand a hit back without its "
            "index keys — leave it unset for a model with sparse attention")
    if speculative:
        why.append(
            "tpu.speculative: the verify program over a selection is not "
            "shown to pick what the single-position steps pick — leave it "
            "unset for a model with sparse attention")
    if prefill_chunk is not None:
        why.append(
            f"tpu.prefill_chunk {prefill_chunk}: a chunk over a non-empty "
            f"cache selects on the XLA path alone ([B, S, T] scores and the "
            f"threshold's passes through HBM), which no chip run has driven "
            f"at long contexts — set prefill_chunk: null for a model with "
            f"sparse attention (prompts prefill whole, up to the largest "
            f"bucket)")
    if role != "unified":
        why.append(
            f"tpu.role {role!r}: the KV handoff frame has no place for the "
            f"index keys — a model with sparse attention serves unified")
    if mesh:
        why.append(
            "tpu.mesh: the index cache and the selection have no sharding "
            "rules yet — a model with sparse attention runs on one device")
    return why


def _attention(
    x: jnp.ndarray,             # [B, S, E], already normed
    lp: dict,                   # one layer's params (leading L dim stripped)
    cache: KVCache,             # FULL [L, B, T, K, D] cache (lengths unused)
    layer: jnp.ndarray,         # scalar int32 index into the cache's layers
    positions: jnp.ndarray,     # [B, S]
    kv_valid: jnp.ndarray,      # [B] cache length AFTER this call's writes
    seq_lens: jnp.ndarray,      # [B] valid tokens in this call's input
    config: ModelConfig,
    prefill_flash: bool,        # static: flash self-attention (fresh cache)
    ring_mesh=None,             # static: Mesh => sequence-parallel prefill
    sp_mode: str = "ring",      # static: "ring" | "ulysses" (SURVEY §5.7)
    tp_mesh=None,               # static: Mesh the trunk is GSPMD-sharded over
    rope_positions=None,        # [3, B, S] multimodal rotary components
) -> tuple[jnp.ndarray, KVCache]:
    """The attention mixer: projections, the cache write, attention by the
    routed path, the output projection -> ([B, S, E], cache)."""
    B, S, E = x.shape
    D, nq, nkv = config.dim_per_head, config.num_heads, config.num_kv_heads

    q = qmatmul(x, lp["wq"])
    k = qmatmul(x, lp["wk"])
    v = qmatmul(x, lp["wv"])
    if config.attention_bias:  # qwen2 family
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    gate = None
    if getattr(config, "attn_output_gate", False):
        # qwen3_next: q_proj emits, per head, the query then an output gate
        q = q.reshape(B, S, nq, 2 * D)
        q, gate = q[..., :D], q[..., D:].reshape(B, S, nq * D)
    q = q.reshape(B, S, nq, D)
    k = k.reshape(B, S, nkv, D)
    v = v.reshape(B, S, nkv, D)
    if getattr(config, "qk_norm", False):  # per head, over its D channels
        q = rms_norm(q, _norm_w(lp["q_norm"], config), config.rms_eps)
        k = rms_norm(k, _norm_w(lp["k_norm"], config), config.rms_eps)
    if getattr(config, "rope", True) and rope_positions is not None:
        # a multimodal rotary's three components (equal ones, the served
        # text path, are `positions`: the branch below)
        section = config.mrope_section
        q = apply_rope(q, rope_positions, config.rope_theta,
                       mrope_section=section)
        k = apply_rope(k, rope_positions, config.rope_theta,
                       mrope_section=section)
    elif getattr(config, "rope", True):
        rot = int(D * getattr(config, "partial_rotary_factor", 1.0))
        q = apply_rope(q, positions, config.rope_theta, rot)
        k = apply_rope(k, positions, config.rope_theta, rot)
    scale = getattr(config, "attention_multiplier", None)
    if scale is not None:
        # every attention path scales scores by 1 / sqrt(D): fold the
        # ratio to the published multiplier into q
        q = q * jnp.asarray(scale * D ** 0.5, q.dtype)

    sparse = getattr(config, "sparse", None)
    if sparse is None:
        cache = write_kv(cache, layer, positions, k, v,
                         by_head=tp_mesh is not None)
    else:
        # the lightning indexer: index queries, this position's index key
        # (cached beside K and V) and the head weights, from the same
        # normed input; plain rotary by the temporal component
        from symmetry_tpu.ops import sparse_attention as sa

        if ring_mesh is not None or tp_mesh is not None:
            raise ValueError("a model with learned sparse attention runs "
                             "on one device: no ring_mesh, no tp_mesh")
        t_pos = positions if rope_positions is None else rope_positions[0]
        qi = apply_rope(
            qmatmul(x, lp["wqi"]).reshape(B, S, sparse.index_heads,
                                          sparse.index_head_dim),
            t_pos, config.rope_theta)
        ki = apply_rope(qmatmul(x, lp["wki"])[:, :, None, :], t_pos,
                        config.rope_theta)[:, :, 0]
        wi = qmatmul(x, lp["wwi"])
        cache = write_kv(cache, layer, positions, k, v, by_head=False,
                         idx=ki)

    if ring_mesh is not None:
        # Long-context prefill: sequence sharded over the `context` mesh
        # axis — K/V blocks rotating on ICI (parallel/ring.py), or one
        # all-to-all head scatter when heads divide the shard count
        # (parallel/ulysses.py).
        if sp_mode == "ulysses":
            from symmetry_tpu.parallel.ulysses import ulysses_attention

            attn = ulysses_attention(q, k, v, seq_lens, ring_mesh)
        else:
            from symmetry_tpu.parallel.ring import ring_attention

            attn = ring_attention(q, k, v, seq_lens, ring_mesh)
    else:
        paths = attention_paths(config, cache.k.shape[2], tp_mesh,
                                batch=cache.k.shape[1],
                                kv_bytes=cache.k.dtype.itemsize)

        def at_layer(arr):
            return jax.lax.dynamic_index_in_dim(arr, layer, 0,
                                                keepdims=False)

        def heads_at_layer(arr):  # pair-folded rows (kv_row) as [K, D]
            return at_layer(arr).reshape(*arr.shape[1:3], nkv, D)

        flash_route = prefill_flash and paths["prefill"] != "xla"
        keep = None
        if sparse is not None:
            # each query's own keep-set: over this call's index keys on
            # the flash route ([B, S, S] int8), over the cached ones
            # ([B, S, T] bool) on the two routes that read the cache —
            # the whole index cache and the layer, so that a decode step
            # reads each slot's live keys where they lie
            keep, n_sel = (
                sa.prefill_keep(qi, ki, wi, seq_lens, sparse.topk)
                if flash_route else
                sa.cache_keep(qi, cache.idx, wi, positions, kv_valid,
                              sparse.topk, layer=layer))
            if cache.expert_pairs is not None:
                cache = cache._replace(
                    expert_pairs=sa.add_counts(cache.expert_pairs, n_sel))
        if flash_route:
            # Prefill-from-empty: attention is over this call's own K/V —
            # the Pallas kernel streams K/V blocks through VMEM instead of
            # materializing [H, S, S] scores (ops/flash.py); the cache
            # slice is never read back. Sliding-window models restrict the
            # kernel's block range to the window.
            from symmetry_tpu.ops import flash

            kw = dict(window=config.sliding_window,
                      interpret=interpret_mode())
            if keep is not None:
                # (the keep-set carries causality and the prompt's length)
                attn = sa.flash_sparse(q, k, v, keep,
                                       interpret=interpret_mode())
            elif tp_mesh is None:
                attn = flash.flash_prefill(q, k, v, seq_lens, **kw)
            else:
                attn = flash.flash_prefill_tp(q, k, v, seq_lens,
                                              mesh=tp_mesh, **kw)
        elif S == 1 and paths["decode"] != "xla":
            # Single-position decode: the Pallas kernel reads only each
            # slot's occupied KV prefix; the full cache is its operand and
            # stays where it lies, layer and slot are DMA addressing
            # (ops/decode_attention.py).
            from symmetry_tpu.ops import decode_attention as da

            args = (q[:, 0], cache.k, cache.v, layer, kv_valid,
                    cache.k_scale if cache.quantized else None,
                    cache.v_scale if cache.quantized else None)
            if keep is not None:
                args += (keep[:, 0],)
            kw = dict(window=config.sliding_window,
                      interpret=interpret_mode())
            attn = (da.decode_attention(*args, **kw) if tp_mesh is None
                    else da.decode_attention_tp(*args, mesh=tp_mesh,
                                                **kw))[:, None]
        else:
            attn = gqa_attention(
                q, heads_at_layer(cache.k), heads_at_layer(cache.v),
                positions, kv_valid,
                sliding_window=config.sliding_window,
                k_scale=at_layer(cache.k_scale) if cache.quantized else None,
                v_scale=at_layer(cache.v_scale) if cache.quantized else None,
                keep=keep)
    attn = attn.reshape(B, S, nq * D)
    if gate is not None:
        attn = attn * jax.nn.sigmoid(gate)
    return qmatmul(attn, lp["wo"]), cache


def _layer(
    h: jnp.ndarray,             # [B, S, E]
    lp: dict,
    cache: KVCache,
    layer: jnp.ndarray,
    positions: jnp.ndarray,
    kv_valid: jnp.ndarray,
    seq_lens: jnp.ndarray,
    config: ModelConfig,
    prefill_flash: bool,
    ring_mesh=None,
    sp_mode: str = "ring",
    tp_mesh=None,
    rope_positions=None,
) -> tuple[jnp.ndarray, KVCache]:
    """One decoder layer of the homogeneous stack: attention, then the FFN
    (dense or routed experts); arguments as `_attention`'s."""
    x = rms_norm(h, _norm_w(lp["attn_norm"], config), config.rms_eps)
    attn, cache = _attention(x, lp, cache, layer, positions, kv_valid,
                             seq_lens, config, prefill_flash,
                             ring_mesh=ring_mesh, sp_mode=sp_mode,
                             tp_mesh=tp_mesh, rope_positions=rope_positions)
    h = h + attn

    x = rms_norm(h, _norm_w(lp["mlp_norm"], config), config.rms_eps)
    if "router" in lp:
        from symmetry_tpu.models.moe import moe_mlp

        y, pairs = moe_mlp(x, lp, config, seq_lens, tp_mesh)
        h = h + y
        if cache.expert_pairs is not None:
            # (a sparse-attention model's vector ends in its own counters)
            extra = cache.expert_pairs.shape[0] - pairs.shape[0]
            cache = cache._replace(expert_pairs=cache.expert_pairs + (
                jnp.pad(pairs, (0, extra)) if extra else pairs))
    else:
        h = h + qmatmul(_act(qmatmul(x, lp["wg"]), config)
                        * qmatmul(x, lp["wu"]), lp["wd"])
    return h, cache


def _act(x: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    """Gated-MLP activation: silu (llama/mistral/qwen) or tanh-approx gelu
    (gemma's GeGLU)."""
    if config.hidden_act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _norm_w(w: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    """Gemma stores RMSNorm scale as (weight - 1): the model applies
    (1 + w). The add runs in float32 — HF GemmaRMSNorm computes
    (1.0 + weight.float()), and doing it in a bf16 checkpoint dtype would
    round the multiplier at every one of the model's norm sites. rms_norm
    upcasts anyway, so this costs nothing."""
    if not config.norm_plus_one:
        return w
    return w.astype(jnp.float32) + 1.0


def forward_hidden(
    params: dict,
    config: ModelConfig,
    tokens: jnp.ndarray,      # [B, S] int32
    cache: KVCache,           # lengths[b] = tokens already in cache for slot b
    seq_lens: jnp.ndarray | None = None,  # [B] valid tokens in `tokens`; None = all S
    *,
    prefill_flash: bool = False,  # static: caller guarantees cache is empty
    ring_mesh=None,               # static: context-parallel prefill mesh
    sp_mode: str = "ring",        # static: "ring" | "ulysses"
    tp_mesh=None,                 # static: Mesh the arrays are sharded over
    rope_positions: jnp.ndarray | None = None,  # [3, B, S] (mrope models)
) -> tuple[jnp.ndarray, KVCache]:
    """Decoder trunk: returns (final-norm hidden states [B, S, E], cache).

    `rope_positions` are the three components (temporal, height, width) a
    model with `mrope_section` turns its rotary by; None means a text
    token's: all three equal to the position in the sequence, which is the
    plain rotary every other model runs. Cache rows, causality and lengths
    go by the position in the sequence either way.

    Split from the LM head so prefill can project only the last valid
    position — at 128k vocab the head matmul over a full padded bucket would
    dominate prefill cost.

    prefill_flash=True routes attention through the Pallas flash kernel.
    VALID ONLY when cache.lengths are all zero (engine prefill's case) —
    both fast paths attend to this call's own K/V, not the cache.
    ring_mesh additionally shards the sequence over the mesh's `context`
    axis; it requires prefill_flash's empty-cache contract and S divisible
    by the shard count. sp_mode picks the scheme: "ring" rotates K/V
    blocks (parallel/ring.py, any head count), "ulysses" head-scatters via
    one all-to-all (parallel/ulysses.py, needs kv_heads % shards == 0).
    Sliding-window models (mistral-v0.1) use the window-bounded flash
    kernel for prefill. The ring/ulysses schemes do not support windows:
    with ring_mesh set, a sliding-window model runs the (non-sequence-
    parallel) flash kernel instead — callers needing SP for windowed
    models must shard some other way.
    """
    if getattr(config, "layer_types", None):
        from symmetry_tpu.models import hybrid

        if ring_mesh is not None or tp_mesh is not None:
            raise ValueError("a model with recurrent layers runs on one "
                             "device: no ring_mesh, no tp_mesh")
        return hybrid.forward_hidden(params, config, tokens, cache, seq_lens,
                                     prefill_flash=prefill_flash)
    B, S = tokens.shape
    if seq_lens is None:
        seq_lens = jnp.full((B,), S, jnp.int32)
    positions = cache.lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    kv_valid = cache.lengths + seq_lens
    if ring_mesh is not None and not prefill_flash:
        # Ring q/kv positions start at 0 and ignore cached entries — only
        # the prefill-from-empty contract makes that correct. Fail loudly
        # rather than silently mis-attend on a continuation call.
        raise ValueError("ring_mesh requires prefill_flash=True "
                         "(prefill-from-empty contract)")
    use_ring = ring_mesh if (ring_mesh is not None and S > 1
                             and config.sliding_window is None) else None
    # Flash prefill handles sliding windows natively (window-bounded block
    # range); only the ring path still requires global attention.
    use_flash = prefill_flash and use_ring is None and S > 1

    n_stacked = jax.tree.leaves(params["layers"])[0].shape[0]
    if n_stacked != config.num_layers:
        # A config/checkpoint depth mismatch must fail loudly: the cache is
        # sized by config, and out-of-bounds scatter/gather on the extra
        # layers would be silently dropped/clamped instead of erroring.
        raise ValueError(f"params carry {n_stacked} stacked layers but "
                         f"config.num_layers = {config.num_layers}")
    h = jnp.take(params["embed"], tokens, axis=0)
    if config.scale_embed:
        # gemma: embeddings scaled by sqrt(hidden) at lookup, normalizer
        # cast to the activation dtype (HF modeling_gemma semantics)
        h = h * jnp.asarray(config.hidden_size ** 0.5, h.dtype)
    if rope_positions is not None and not getattr(config, "mrope_section",
                                                  None):
        raise ValueError("rope_positions are a multimodal rotary's: the "
                         "config has no mrope_section")
    h, new_cache = run_layers(params["layers"], h, cache, positions,
                              kv_valid, seq_lens, config,
                              use_flash=use_flash, use_ring=use_ring,
                              sp_mode=sp_mode, tp_mesh=tp_mesh,
                              rope_positions=rope_positions)
    h = rms_norm(h, _norm_w(params["final_norm"], config), config.rms_eps)
    return h, new_cache._replace(lengths=kv_valid)


def run_layers(
    layers_params: dict,
    h: jnp.ndarray,
    cache: KVCache,            # leading layer dim == layers_params' leading dim
    positions: jnp.ndarray,
    kv_valid: jnp.ndarray,
    seq_lens: jnp.ndarray,
    config: ModelConfig,
    *,
    use_flash: bool = False,
    use_ring=None,
    sp_mode: str = "ring",
    tp_mesh=None,
    rope_positions=None,
) -> tuple[jnp.ndarray, KVCache]:
    """Scan a stack of decoder layers over `h`; layer indices inside are
    local to the stack passed in, whose leading dim is the cache's."""

    def body(carry, xs):
        # The cache rides the CARRY, scatter-updated in place: scan xs/ys
        # would stream the full [L, B, T, K, D] arrays through HBM every
        # forward — at decode that re-writes ~0.5 GB per token.
        h, c = carry
        lp, l = xs
        h, c = _layer(h, lp, c, l, positions, kv_valid,
                      seq_lens, config, use_flash, ring_mesh=use_ring,
                      sp_mode=sp_mode, tp_mesh=tp_mesh,
                      rope_positions=rope_positions)
        return (h, c), None

    n_layers = jax.tree.leaves(layers_params)[0].shape[0]
    (h, new_cache), _ = jax.lax.scan(
        body, (h, cache),
        (layers_params, jnp.arange(n_layers, dtype=jnp.int32)))
    return h, new_cache


def logits_from_hidden(params: dict, config: ModelConfig,
                       h: jnp.ndarray) -> jnp.ndarray:
    """LM head: [B, S, E] hidden -> [B, S, vocab] float32 logits."""
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = qmatmul(h, head).astype(jnp.float32)
    scaling = getattr(config, "logits_scaling", 1.0)
    return logits if scaling == 1.0 else logits / scaling


# Weights eligible for int8 quantization (all the large matmuls; the
# embedding stays dense — it is gathered, not contracted).
# `in_proj` / `out_proj` are the recurrent mixer's (mamba's, the Gated
# DeltaNet's q|k|v|z projection or the short convolution's B|C|x), `sg` /
# `su` / `sd` the shared expert's (models/hybrid.py; lfm2_moe's leading
# dense layers are `wg` / `wu` / `wd` of the stack `layers.dense`).
# `wqi` / `wki` are the sparse attention's index projections.
QUANT_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "in_proj",
              "out_proj", "sg", "su", "sd", "wqi", "wki", "lm_head")
# Those of them stacked along a leading layers axis.
STACKED_KEYS = QUANT_KEYS[:-1]


def quantize_params(params: dict) -> dict:
    """In-place int8 quantization of all QUANT_KEYS leaves (ops/quant.py)."""
    return quantize_tree(params, QUANT_KEYS)


def pack_params(params: dict, *, config: ModelConfig | None = None,
                mesh=None, rules: dict | None = None,
                report: list | None = None) -> dict:
    """In-place tile-packing of quantized QUANT_KEYS leaves into the
    W8A16 fused-dequant kernel layout (`tpu.fused_dequant`; ops/quant.py
    pack_tree). Layout is routing: qmatmul sends PackedQuantizedTensor
    leaves through the Pallas kernel and leaves everything else on the
    mixed dot, so per-leaf tileability fallback is automatic.

    With `mesh` (+ `config`, required to resolve each leaf's logical
    axes), packing happens AFTER the sharding decision: every leaf's
    contraction/output mesh axes come from the SAME logical-axis tree +
    rules the dense/int8 placement used (packed_shard_axes), tile blocks
    are picked against the per-shard dims, and the leaf carries its axes
    so qmatmul routes it through the shard_map'd per-shard kernel.
    Leaves whose per-shard shape loses tileability stay flat on the
    mixed dot; pass `report` to collect the (path, reason) degrades."""
    from symmetry_tpu.ops.quant import pack_tree

    axes = None
    if mesh is not None:
        if config is None:
            raise ValueError("pack_params needs `config` to resolve "
                             "per-leaf shard axes when packing on a mesh")
        axes = packed_shard_axes(config, mesh, rules)
    return pack_tree(params, QUANT_KEYS, axes=axes, mesh=mesh,
                     report=report)


def packed_shard_axes(config: ModelConfig, mesh,
                      rules: dict | None = None) -> dict:
    """leaf name -> (k_mesh_axis, n_mesh_axis) for every QUANT_KEYS leaf,
    resolved from param_logical_axes + the sharding rules — the packed
    layout shards exactly the axes the flat int8 leaf already did
    (megatron TP: wq/wk/wv/wg/wu/lm_head column-parallel over the output
    dim, wo/wd row-parallel over the contraction dim). Mesh axes of size
    1 resolve to None (nothing to shard)."""
    from symmetry_tpu.parallel.sharding import DEFAULT_RULES

    rules = DEFAULT_RULES if rules is None else rules
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out: dict = {}

    def resolve(logical):
        ax = rules.get(logical) if logical is not None else None
        return ax if ax is not None and sizes.get(ax, 1) > 1 else None

    def visit(node):
        for name, child in node.items():
            if isinstance(child, dict):
                visit(child)
            elif name in QUANT_KEYS:
                out[name] = (resolve(child[-2]), resolve(child[-1]))

    visit(param_logical_axes(config))
    return out


def packed_logical_axes(axes: dict, params: dict) -> dict:
    """Map a dense logical-axes tree to one matching a (possibly packed)
    params tree, so parallel/sharding.shardings_for composes for packed
    trees exactly as it does for flat int8 ones. A packed q keeps the
    dense dims' names on its tile-GRID dims and replicates the tile dims
    — [.., K/bk, N/bn, bk, bn] gets dense axes + (None, None) — because
    pack_quantized picks blocks against the per-shard dims, so sharding
    the grid dims IS sharding the weight. The scale maps as in
    quantized_logical_axes. Aux (mesh + axis names) is copied from the
    params leaf so the two trees stay structurally identical (the aux
    rides the treedef)."""
    from symmetry_tpu.ops.quant import PackedQuantizedTensor

    def visit(node, pnode):
        out = {}
        for name, child in node.items():
            leaf = pnode.get(name) if isinstance(pnode, dict) else None
            if isinstance(child, dict):
                out[name] = visit(child, leaf if isinstance(leaf, dict)
                                  else {})
            elif isinstance(leaf, PackedQuantizedTensor):
                out[name] = PackedQuantizedTensor(
                    q=child + (None, None),
                    scale=child[:-2] + child[-1:],
                    k_axis=leaf.k_axis, n_axis=leaf.n_axis, mesh=leaf.mesh)
            elif name in QUANT_KEYS and isinstance(
                    leaf, QuantizedTensor):
                out[name] = QuantizedTensor(
                    q=child, scale=child[:-2] + child[-1:])
            else:
                out[name] = child
        return out

    return visit(axes, params)


def quantized_logical_axes(axes: dict) -> dict:
    """Map a dense logical-axes tree to its quantized counterpart: the int8
    payload keeps the dense axes; per-column scales drop the contraction
    (second-to-last) axis."""
    def visit(node):
        out = {}
        for name, child in node.items():
            if isinstance(child, dict):
                out[name] = visit(child)
            elif name in QUANT_KEYS:
                out[name] = QuantizedTensor(
                    q=child, scale=child[:-2] + child[-1:])
            else:
                out[name] = child
        return out

    return visit(axes)


def forward(
    params: dict,
    config: ModelConfig,
    tokens: jnp.ndarray,      # [B, S] int32
    cache: KVCache,
    seq_lens: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Run the decoder; returns (logits [B, S, vocab] f32, updated cache).

    Serves prefill (S = padded prompt length, cache.lengths typically 0) and
    decode (S = 1 per slot) with the same traced computation. Logits at
    padded positions are garbage by contract; callers index the last valid
    position.
    """
    h, cache = forward_hidden(params, config, tokens, cache, seq_lens)
    return logits_from_hidden(params, config, h), cache


# ---------------------------------------------------------------------------
# HF weight layout map (used by engine/weights.py; kept here because it is
# model knowledge). HF linear weights are [out, in] — transposed vs ours.

HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),  # [V,E] -> [E,V]
}
HF_LAYER_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    # qwen2: QKV projection biases (absent in llama/mistral checkpoints)
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    "self_attn.o_proj.weight": ("wo", True),
    "mlp.gate_proj.weight": ("wg", True),
    "mlp.up_proj.weight": ("wu", True),
    "mlp.down_proj.weight": ("wd", True),
    # KeyeVL2 (the Qwen3-MoE block): per-head q/k norms, and the lightning
    # indexer's three Linears (ASSUMED names, DeepSeek-V3.2's `indexer`
    # module: the checkpoint is not in the sandbox)
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    "self_attn.indexer.wq.weight": ("wqi", True),
    "self_attn.indexer.wk.weight": ("wki", True),
    "self_attn.indexer.weights_proj.weight": ("wwi", True),
}


def optional_layer_params(config: ModelConfig) -> set[str]:
    """HF_LAYER_MAP's leaves this config does NOT have."""
    absent = set()
    if not config.attention_bias:
        absent |= {"bq", "bk", "bv"}
    if not getattr(config, "qk_norm", False):
        absent |= {"q_norm", "k_norm"}
    if getattr(config, "sparse", None) is None:
        absent |= {"wqi", "wki", "wwi"}
    return absent
# Mixtral: the MLP block is `block_sparse_moe` — a router (`gate`) plus
# per-expert w1/w2/w3 Linears (w1=gate_proj, w2=down_proj, w3=up_proj).
# All are HF [out, in] → transposed; experts stack on our leading dim.
HF_MOE_ROUTER = "block_sparse_moe.gate.weight"            # → router (T)
HF_EXPERT_MAP = {"w1": "wg", "w3": "wu", "w2": "wd"}      # all transposed


# The Qwen3-MoE block (KeyeVL2): `mlp.gate` routes, `mlp.experts.<e>` hold
# gate_proj / up_proj / down_proj.
HF_QWEN_MOE_ROUTER = "mlp.gate.weight"
HF_QWEN_EXPERT_MAP = {"gate_proj": "wg", "up_proj": "wu", "down_proj": "wd"}


def hf_moe_names(config: ModelConfig | None) -> tuple[str, str, dict]:
    """(router tensor, experts module, expert Linear -> ours) under a
    layer, by the family the config is of."""
    if getattr(config, "hf_block", "mixtral") == "qwen3_moe":
        return HF_QWEN_MOE_ROUTER, "mlp.experts", HF_QWEN_EXPERT_MAP
    return HF_MOE_ROUTER, "block_sparse_moe.experts", HF_EXPERT_MAP


def hf_expert_name(layer: int, expert: int, ours: str,
                   config: ModelConfig | None = None) -> str:
    _, module, names = hf_moe_names(config)
    w = {v: k for k, v in names.items()}[ours]
    return f"model.layers.{layer}.{module}.{expert}.{w}.weight"


def hf_config_sparse(config: MoEConfig) -> dict:
    """A sparse-attention config as its published `config.json` keys (what
    `config_from_hf` reads back, and what the plain reference —
    `benchmarks/reference/sparse_moe_decoder.py` — is given)."""
    c = config
    return {
        "model_type": "KeyeVL2", "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads, "head_dim": c.dim_per_head,
        "moe_intermediate_size": c.intermediate_size,
        "num_experts": c.num_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "rope_theta": c.rope_theta,
        "rope_scaling": {"mrope_section": list(c.mrope_section)},
        "rms_norm_eps": c.rms_eps,
        "max_position_embeddings": c.max_position,
        "tie_word_embeddings": c.tie_embeddings,
        "attention_bias": c.attention_bias, "use_sliding_window": False,
        "sa_config": {"topk": c.sparse.topk,
                      "indexer_num_heads": c.sparse.index_heads,
                      "indexer_head_dim": c.sparse.index_head_dim,
                      "indexer_num_kv_heads": 1},
    }


def config_from_hf(hf: dict[str, Any]) -> ModelConfig:
    """Build a ModelConfig from an HF config.json dict (llama/mistral/
    qwen2/mixtral shapes; mixtral's num_local_experts selects MoEConfig)."""
    arch = (hf.get("architectures") or [""])[0]
    # Exact match: gemma-2/3 checkpoints (Gemma2ForCausalLM, ...) need
    # logit softcapping, post-layer norms, and alternating local
    # attention this decoder does not implement — loading them with
    # gemma-1 semantics would silently generate garbage.
    gemma = arch == "GemmaForCausalLM"
    if arch.startswith("Gemma") and not gemma:
        raise ValueError(
            f"unsupported architecture {arch!r}: only first-generation "
            f"GemmaForCausalLM semantics are implemented")
    # qwen2 configs carry a vestigial sliding_window alongside
    # use_sliding_window: false — honoring it would silently disable every
    # fast attention path (flash prefill, ring, the Pallas decode kernel).
    sliding = hf.get("sliding_window")
    if hf.get("use_sliding_window") is False:
        sliding = None
    if hf.get("model_type") == "qwen3_next":
        if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
            raise ValueError("qwen3_next with dense-MLP layers "
                             "(mlp_only_layers, decoder_sparse_step) is not "
                             "implemented")
        if not hf.get("norm_topk_prob", True):
            raise ValueError("qwen3_next with norm_topk_prob false is not "
                             "implemented")
        interval = hf.get("full_attention_interval", 4)
        types = tuple(hf.get("layer_types") or (
            "full_attention" if (i + 1) % interval == 0
            else "linear_attention"
            for i in range(hf["num_hidden_layers"])))
        return HybridConfig(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            # the routed expert's width; `intermediate_size` (the dense
            # width) serves no layer when every layer is sparse
            intermediate_size=hf["moe_intermediate_size"],
            head_dim=hf.get("head_dim"),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            max_position=hf.get("max_position_embeddings", 8192),
            norm_plus_one=True,
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            shared_intermediate_size=hf["shared_expert_intermediate_size"],
            layer_types=types,
            linear_num_key_heads=hf["linear_num_key_heads"],
            linear_key_head_dim=hf["linear_key_head_dim"],
            linear_num_value_heads=hf["linear_num_value_heads"],
            linear_value_head_dim=hf["linear_value_head_dim"],
            linear_conv_kernel_dim=hf.get("linear_conv_kernel_dim", 4),
            partial_rotary_factor=hf.get("partial_rotary_factor", 1.0),
            qk_norm=True, attn_output_gate=True, shared_expert_gate=True,
        )
    if hf.get("model_type") == "lfm2_moe":
        if hf.get("conv_bias"):
            raise ValueError("lfm2_moe with conv_bias is not implemented")
        if not hf.get("norm_topk_prob", True):
            raise ValueError("lfm2_moe with norm_topk_prob false is not "
                             "implemented")
        return HybridConfig(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            # the routed expert's width; `intermediate_size` is the leading
            # dense layers'
            intermediate_size=hf["moe_intermediate_size"],
            head_dim=hf.get("head_dim"),
            rope_theta=float(hf.get("rope_theta", 1000000.0)),
            rms_eps=hf.get("norm_eps", 1e-5),
            tie_embeddings=hf.get("tie_embedding",
                                  hf.get("tie_word_embeddings", True)),
            max_position=hf.get("max_position_embeddings", 128000),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            qk_norm=True, layer_types=tuple(hf["layer_types"]),
            conv_L_cache=hf["conv_L_cache"],
            num_dense_layers=hf.get("num_dense_layers", 0),
            dense_intermediate_size=(hf["intermediate_size"]
                                     if hf.get("num_dense_layers") else 0),
            router_score="sigmoid",
            router_bias=bool(hf.get("use_expert_bias", True)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        )
    if hf.get("model_type") == "KeyeVL2":
        # the LANGUAGE model of the family (the catalog row's keys): the
        # Qwen3-MoE block under a DeepSeek-Sparse-Attention indexer
        if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
            raise ValueError("KeyeVL2 with dense-MLP layers (mlp_only_layers,"
                             " decoder_sparse_step) is not implemented")
        if not hf.get("norm_topk_prob", True):
            raise ValueError("KeyeVL2 with norm_topk_prob false is not "
                             "implemented")
        sa = hf["sa_config"]
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("an indexer with more than one key head is not "
                             "implemented")
        return MoEConfig(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            intermediate_size=hf["moe_intermediate_size"],
            head_dim=hf.get("head_dim"),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            sliding_window=sliding,
            attention_bias=hf.get("attention_bias", False),
            max_position=hf.get("max_position_embeddings", 8192),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            qk_norm=True,
            mrope_section=tuple(
                (hf.get("rope_scaling") or {}).get("mrope_section") or ())
            or None,
            sparse=SparseAttention(topk=sa["topk"],
                                   index_heads=sa["indexer_num_heads"],
                                   index_head_dim=sa["indexer_head_dim"]),
            hf_block="qwen3_moe", init_fan_in=True,
        )
    if hf.get("model_type") == "granitemoehybrid":
        types = tuple(hf["layer_types"])
        if hf.get("mamba_n_groups", 1) != 1:
            raise ValueError("mamba_n_groups other than 1 is not implemented")
        return HybridConfig(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads",
                                hf["num_attention_heads"]),
            intermediate_size=hf["intermediate_size"],
            head_dim=hf.get("head_dim"),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", True),
            max_position=hf.get("max_position_embeddings", 8192),
            num_experts=hf["num_local_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            shared_intermediate_size=hf.get("shared_intermediate_size", 0),
            layer_types=types,
            mamba_n_heads=hf["mamba_n_heads"],
            mamba_d_head=hf["mamba_d_head"],
            mamba_d_state=hf["mamba_d_state"],
            mamba_d_conv=hf.get("mamba_d_conv", 4),
            mamba_chunk_size=hf.get("mamba_chunk_size", 256),
            embedding_multiplier=hf.get("embedding_multiplier", 1.0),
            residual_multiplier=hf.get("residual_multiplier", 1.0),
            attention_multiplier=hf.get("attention_multiplier"),
            logits_scaling=hf.get("logits_scaling", 1.0),
            rope=hf.get("position_embedding_type", "rope") != "nope",
        )
    if hf.get("num_local_experts"):
        return MoEConfig(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads",
                                hf["num_attention_heads"]),
            intermediate_size=hf["intermediate_size"],
            head_dim=hf.get("head_dim"),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            sliding_window=sliding,
            attention_bias=hf.get("attention_bias", "Qwen2" in arch),
            max_position=hf.get("max_position_embeddings", 8192),
            num_experts=hf["num_local_experts"],
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        )
    return ModelConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        intermediate_size=hf["intermediate_size"],
        head_dim=hf.get("head_dim"),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        # gemma ties embeddings BY DEFAULT, so its config.json often omits
        # the key entirely — defaulting it False would reject the checkpoint
        tie_embeddings=hf.get("tie_word_embeddings", gemma),
        sliding_window=sliding,
        # older qwen2 configs carry no attention_bias key; the architecture
        # implies it (HF modeling_qwen2 hardcodes bias=True on q/k/v).
        attention_bias=hf.get("attention_bias", "Qwen2" in arch),
        max_position=hf.get("max_position_embeddings", 8192),
        # gemma: GeGLU + (1+w) norms + scaled embeddings; hidden_activation
        # ("gelu_pytorch_tanh") appears in newer configs, older ones imply it
        hidden_act="gelu_tanh" if gemma else "silu",
        norm_plus_one=gemma,
        scale_embed=gemma,
    )
