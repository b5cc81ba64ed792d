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

import dataclasses
from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from symmetry_tpu.ops.attention import gqa_attention
from symmetry_tpu.ops.interpret import interpret_mode
from symmetry_tpu.ops.norm import rms_norm
from symmetry_tpu.ops.quant import (
    QuantizedTensor, dequantize, qmatmul, quantize_kv, quantize_tree)
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
    # local attention's span: a key s is visible to a query t iff
    # t - sliding_window < s <= t. For a model WITHOUT `layer_types` it is
    # model-wide (mistral-v0.1 style): every layer masks by it over the one
    # cache of full capacity (flash's block bounds, the decode kernel's
    # `window=`, gqa_attention's mask). For a HybridConfig whose
    # `layer_types` name "sliding_attention" layers it is THAT KIND's span
    # alone: those layers cache their K/V in a ring of exactly this many
    # rows (`KVCache.kw` / `vw`), the "full_attention" layers beside them
    # see every position
    sliding_window: int | None = None
    attention_bias: bool = False         # qwen2-style QKV projection biases
    max_position: int = 8192
    # gemma family: gelu-tanh GeGLU, RMSNorm scale stored as (weight - 1),
    # and embeddings multiplied by sqrt(hidden) at lookup
    hidden_act: str = "silu"    # "silu" | "gelu_tanh" | "relu" | "relu2"
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
class BlockDiffusion:
    """Generation by diffusion over blocks (the SDAR family): attention is
    causal ACROSS blocks of `block` positions and bidirectional inside one
    (`M[i, j] = 1 iff j // block <= i // block`), the logit row at a
    position predicts the token AT it, and a block is generated by
    denoising: its unknown positions enter as `mask_token_id` and are made
    known by confidence, a few a forward (ops/sampling.py
    diffusion_candidates / diffusion_unmask; engine/engine.py has the
    programs)."""

    block: int
    mask_token_id: int


@dataclass(frozen=True)
class LatentAttention:
    """Multi-head latent attention (HF `DeepseekV3`, `q_lora_rank` null):
    keys and values are up-projections of ONE normed latent of `rank` values
    a position, beside one rotary key of `rope` values that all heads share,
    so a cached position is a single row of `rank + rope` values and no K or
    V a head (`KVCache.k` [L, B, T, rank + rope], no `v` leaf). A head's
    query and key are `nope + rope` wide, its value `v` wide; attention runs
    ABSORBED over the cached rows and EXPANDED over a prompt's own
    (`_latent_attention`, ops/mla_attention.py)."""

    rank: int                 # kv_lora_rank
    rope: int                 # qk_rope_head_dim
    nope: int                 # qk_nope_head_dim
    v: int                    # v_head_dim
    rope_interleave: bool = True   # frequency i turns channels (2i, 2i + 1)

    @property
    def row(self) -> int:
        return self.rank + self.rope

    @property
    def lanes(self) -> int:
        """The row as the cache leaf declares it: padded with zeros to
        whole lane tiles of 128 (576 -> 640). The chip's tiled layout pads
        a 576-wide minor dimension to 640 lanes whatever is declared, and
        Mosaic refuses to slice such an array for the decode kernel's
        copies ("must be aligned to tiling (128), but is 576"): declared,
        the same bytes are a leaf the kernel can address."""
        return -(-self.row // 128) * 128


# The SDAR family's generation settings where a config.json does not carry
# them: the released chat models' block of four and their `<|MASK|>` id.
SDAR_BLOCK_LENGTH = 4
SDAR_MASK_TOKEN_ID = 151669


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
    # the SDAR family: the block mask and the mask token (None: a causal
    # decoder that yields one token a stream a forward, every other model)
    diffusion: BlockDiffusion | None = None
    # the DeepseekV3 family: the latent cache entry's sizes (None: K and V
    # a head, every other model); `head_dim` is then the published rotary
    # width and sizes no matrix
    latent: LatentAttention | None = None
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
    lfm2_moe's first `num_dense_layers` by a dense SwiGLU instead — or,
    nemotron_h's, "mamba" or "attention" again, each followed by what its
    `ffn_layout` entry says: experts, or nothing. The forward pass,
    parameters and cache are models/hybrid.py's; every entry point of this module hands a config
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
    # what the sigmoid router adds to the sum it divides the selected
    # scores by: lfm2_moe's 1e-6, deepseek_v3's 1e-20
    router_norm_eps: float = 1e-6
    # deepseek_v3 family: every layer "latent_attention" (`latent` has the
    # sizes; no recurrent kind, so no state a slot), the first
    # `num_dense_layers` (`first_k_dense_replace`) dense, the rest experts
    # by the sigmoid router plus an ungated shared expert
    # smallthinker family, both fields at "absent" for any other model:
    # every layer is attention, of TWO KINDS — "sliding_attention" (a window
    # of `sliding_window` positions, cached in a ring of that many rows:
    # `KVCache.kw` / `vw`) or "full_attention" (every position, cached in
    # `k` / `v`) — so there is no recurrent kind and no state a slot;
    # `rope_layout[i]` says whether layer i rotates q and k (1) or has no
    # positional embedding (0) — a rotary choice a LAYER, the published
    # layout's a kind (None: `rope` for every layer) — and `router_input`
    # "layer_input" feeds the router the residual stream as it ENTERS the
    # layer, before the input norm and attention ("ffn_input": the normed
    # tensor the experts read, every other model)
    rope_layout: tuple[int, ...] | None = None
    router_input: str = "ffn_input"   # "ffn_input" | "layer_input"
    # nemotron_h family, every field at "absent" for any other model: a
    # published block is ONE sub-layer — a mixer alone or an FFN alone, each
    # under its own norm and residual — so the FFN kind is a property of the
    # LAYER: `ffn_layout[i]` says what trunk layer i (the mixer
    # `layer_types[i]`) ends in, "moe", "dense" or "none" (None: the leading
    # `num_dense_layers` "dense", the rest "moe"), and `num_layers` may count
    # the published blocks (mixers + FFNs) instead of the trunk's layers.
    # `mamba_n_groups` groups of B and C a position (head h reads group
    # h // (heads / groups); the gated norm is over each group's channels).
    # `experts_held` = (first, count): the routed experts THIS chip holds of
    # the `num_experts` the router scores (None: all of them) — routing is
    # over all, the held experts' part of the sum is computed and the rest
    # left out, gates as routed (models/moe.py). `gated_ffn` False: an
    # expert (and the shared one) is act(x W_up) W_down, two matrices, no
    # gate matrix; `hidden_act` "relu2" is relu(x)^2.
    ffn_layout: tuple[str, ...] | None = None
    mamba_n_groups: int = 1
    experts_held: tuple[int, int] | None = None
    gated_ffn: bool = True
    # exaone_moe family (K-EXAONE): smallthinker's window / full pair under
    # lfm2_moe's q/k norms and leading dense layers and deepseek_v3's router
    # and shared expert, every one a field above — and `mtp_layers`
    # (`num_nextn_predict_layers`, 0 for every other model): multi-token-
    # prediction modules behind the trunk (models/hybrid.py mtp_forward),
    # each a full-attention NoPE block with a cache layer of its own in the
    # `k` / `v` leaves BEHIND the trunk's full layers, that drafts the
    # token after the next one from the trunk's hidden state and the next
    # token's embedding (engine/engine.py: the on-device drafter)
    mtp_layers: int = 0

    def __post_init__(self):
        kinds = set(self.layer_types)
        if self.ffn_layout is not None and (
                len(self.ffn_layout) != len(self.layer_types)
                or not set(self.ffn_layout) <= {"moe", "dense", "none"}):
            raise ValueError(
                f"ffn_layout must name 'moe', 'dense' or 'none' for each of "
                f"the {len(self.layer_types)} layers; got "
                f"{self.ffn_layout!r}")
        if (self.num_layers not in (len(self.layer_types), self.num_blocks)
                or not (kinds <= {"mamba", "attention"}
                        or kinds <= {"linear_attention", "full_attention"}
                        or kinds <= {"conv", "full_attention"}
                        or kinds <= {"sliding_attention", "full_attention"}
                        or kinds == {"latent_attention"})):
            raise ValueError(
                f"layer_types must name {self.num_layers} layers, each "
                f"'mamba' or 'attention', or each 'linear_attention' or "
                f"'full_attention', or each 'conv' or 'full_attention', or "
                f"each 'sliding_attention' or 'full_attention', or "
                f"each 'latent_attention'; got {self.layer_types!r}")
        if ("sliding_attention" in kinds) != (
                self.sliding_window is not None):
            raise ValueError(
                f"'sliding_attention' layers and sliding_window (their "
                f"span) go together; got sliding_window "
                f"{self.sliding_window} with {self.layer_types!r}")
        if self.rope_layout is not None and (
                len(self.rope_layout) != self.num_layers):
            raise ValueError(f"rope_layout must have {self.num_layers} "
                             f"entries; got {self.rope_layout!r}")
        if self.router_input not in ("ffn_input", "layer_input"):
            raise ValueError(f"router_input must be 'ffn_input' or "
                             f"'layer_input'; got {self.router_input!r}")
        if ("latent_attention" in kinds) != (self.latent is not None):
            raise ValueError("'latent_attention' layers and the `latent` "
                             "sizes go together")
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
        if self.mamba_n_groups < 1 or (
                self.mamba_n_heads % self.mamba_n_groups):
            raise ValueError(
                f"mamba_n_groups {self.mamba_n_groups} must divide "
                f"mamba_n_heads {self.mamba_n_heads}")
        if self.mtp_layers not in (0, 1) or (
                self.mtp_layers and "sliding_attention" not in kinds):
            raise ValueError(
                f"mtp_layers {self.mtp_layers}: one multi-token-prediction "
                f"module behind a window / full trunk is what is built")
        if self.experts_held is not None:
            first, count = self.experts_held
            if not (0 <= first and 0 < count
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held (first, count) = {self.experts_held!r} "
                    f"must lie inside the {self.num_experts} experts routed "
                    f"over")

    @property
    def num_blocks(self) -> int:
        """Mixers and FFNs counted singly — what a family whose published
        block is ONE sub-layer (`ffn_layout`) calls its number of layers;
        the trunk's layers for every other."""
        return len(self.layer_types) + (
            0 if self.ffn_layout is None
            else sum(k != "none" for k in self.ffn_layout))

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this chip holds."""
        return (self.num_experts if self.experts_held is None
                else self.experts_held[1])

    def layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def recurrent_kind(self) -> str | None:
        """The name `layer_types` gives this model's recurrent layers; None
        for a model of attention layers alone — latent attention, or the
        window / full pair (which always carries a `rope_layout`) — which
        has none."""
        if (self.latent is not None or self.rope_layout is not None
                or "sliding_attention" in self.layer_types):
            return None
        if self.conv_L_cache:
            return "conv"
        return ("linear_attention" if "linear_attention" in self.layer_types
                or "full_attention" in self.layer_types else "mamba")

    @property
    def attention_kind(self) -> str:
        """The kind whose layers ride the cache's `k` / `v` leaves at the
        full capacity."""
        if self.latent is not None:
            return "latent_attention"
        return "attention" if self.recurrent_kind == "mamba" \
            else "full_attention"

    @property
    def window_kind(self) -> str | None:
        """The SECOND attention kind of a window / full model, whose layers
        ride the ring leaves `kw` / `vw`; None for every other model."""
        return ("sliding_attention" if "sliding_attention" in self.layer_types
                else None)

    @property
    def attention_kinds(self) -> tuple[str, ...]:
        return tuple(filter(None, (self.attention_kind, self.window_kind)))

    def layer_rope(self, i: int) -> bool:
        """Whether layer i rotates q and k: its `rope_layout` entry, or the
        model-wide `rope`."""
        return bool(self.rope if self.rope_layout is None
                    else self.rope_layout[i])

    def ffn_kind(self, i: int) -> str:
        """What layer i ends in: its `ffn_layout` entry ("moe", "dense" or
        "none": the mixer alone), or, without a layout, "dense" for the
        leading SwiGLU layers and "moe" for the rest."""
        if self.ffn_layout is not None:
            return self.ffn_layout[i]
        return "dense" if i < self.num_dense_layers else "moe"

    def ffn_index(self, i: int) -> int:
        """Layer i's index in the stack of its FFN kind."""
        kind = self.ffn_kind(i)
        return sum(self.ffn_kind(j) == kind for j in range(i))

    def layers_ending_in(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i in range(len(self.layer_types))
                     if self.ffn_kind(i) == kind)


BLOCK_MIXERS = {"M": "mamba", "*": "attention"}


def pair_blocks(pattern: str) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """A nemotron_h `hybrid_override_pattern` (one letter a block: `M` a
    Mamba-2 mixer, `*` attention, `E` experts; each block `h + f(norm(h))`)
    as the trunk's (layer_types, ffn_layout): two consecutive pre-norm
    residual blocks "mixer, then E" ARE one (mixer, FFN) layer, exactly, and
    a mixer that no `E` follows is a layer that ends in "none". Refuses a
    pattern it cannot pair: an `E` with no mixer before it, and `-` (a
    dense MLP block, which no served model of the family has)."""
    kinds, ffns = [], []
    for at, letter in enumerate(pattern):
        if letter in BLOCK_MIXERS:
            kinds.append(BLOCK_MIXERS[letter])
            ffns.append("none")
        elif letter == "E" and ffns and ffns[-1] == "none" \
                and pattern[at - 1] in BLOCK_MIXERS:
            ffns[-1] = "moe"
        else:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: block {at} ({letter!r}"
                f") cannot be paired — an expert block needs a mixer block "
                f"before it, and only M, * and E blocks are implemented")
    return tuple(kinds), tuple(ffns)


def blocks_of(config: "HybridConfig") -> str:
    """The inverse of `pair_blocks`."""
    letters = {v: k for k, v in BLOCK_MIXERS.items()}
    return "".join(letters[kind] + ("E" if ffn == "moe" else "")
                   for kind, ffn in zip(config.layer_types,
                                        config.ffn_layout))


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
    # the CPU's copy of SDAR-MoE's mechanisms: blocks of four under the
    # block mask, GQA with q/k norms, 8 experts top 2, an untied head; the
    # mask token is the vocabulary's last id (outside the byte tokenizer's
    # 0..257)
    "tiny-bd": MoEConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=32, head_dim=16,
        rope_theta=10000.0, rms_eps=1e-6, max_position=512,
        num_experts=8, num_experts_per_tok=2, qk_norm=True,
        diffusion=BlockDiffusion(block=4, mask_token_id=511),
        hf_block="qwen3_moe", init_fan_in=True,
    ),
    # SDAR-30B-A3B-Chat CUT IN DEPTH to 12 of its 48 identical layers at
    # every published width, with all 128 experts and the whole vocabulary:
    # stage 1 of 4 of a pipeline over one four-chip host, what one 16 GB
    # chip holds in int8 beside a 128 x 640 cache (benchmarks/configs/
    # sdar-30b-a3b-chat.json has the cut). `intermediate_size` is the
    # routed expert's width (`moe_intermediate_size`); the published dense
    # width 6144 serves no layer. Block length and mask token are the
    # released chat models' generation settings (`assumed` in that file).
    "sdar-30b-a3b-chat": MoEConfig(
        vocab_size=151936, hidden_size=2048, num_layers=12, num_heads=32,
        num_kv_heads=4, intermediate_size=768, head_dim=128,
        rope_theta=1000000.0, rms_eps=1e-6, max_position=32768,
        num_experts=128, num_experts_per_tok=8, qk_norm=True,
        diffusion=BlockDiffusion(block=SDAR_BLOCK_LENGTH,
                                 mask_token_id=SDAR_MASK_TOKEN_ID),
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
    # the CPU's copy of Kanana-2's mechanisms: a latent cache row of 16 + 8
    # values for 4 heads (nope 16, value 12: unequal key and value widths),
    # a leading dense layer, a sigmoid router with a selection bias and a
    # gate scale over 8 experts top 2, an ungated shared expert, an untied
    # head
    "tiny-mla": HybridConfig(
        vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
        num_kv_heads=4, intermediate_size=32, head_dim=8,
        rope_theta=10000.0, rms_eps=1e-6, max_position=512,
        num_experts=8, num_experts_per_tok=2, shared_intermediate_size=64,
        latent=LatentAttention(rank=16, rope=8, nope=16, v=12),
        layer_types=("latent_attention",) * 3,
        num_dense_layers=1, dense_intermediate_size=128,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.448, router_norm_eps=1e-20,
    ),
    # kanana-2-30b-a3b-instruct-2601 (HF `deepseek_v3`, no query latent)
    # CUT IN DEPTH to 8 of its 48 layers — the leading dense layer and
    # seven of the 47 expert layers — at every published width, with all
    # 128 experts, both shared experts (one FFN of 2 x 768) and the whole
    # vocabulary: stage 1 of 6 of a pipeline, what one 16 GB chip holds in
    # int8 beside a 64 x 11,776 cache of 576-value latent rows
    # (benchmarks/configs/kanana-2-30b-a3b.json has the cut). `head_dim` is
    # the published key, the rotary width: it sizes no matrix (`latent` has
    # a head's widths), `intermediate_size` the routed expert's width,
    # `dense_intermediate_size` the published `intermediate_size` of layer 0.
    "kanana-2-30b-a3b": HybridConfig(
        vocab_size=128256, hidden_size=2048, num_layers=8, num_heads=32,
        num_kv_heads=32, intermediate_size=768, head_dim=64,
        rope_theta=1000000.0, rms_eps=1e-6, max_position=32768,
        num_experts=128, num_experts_per_tok=6,
        shared_intermediate_size=1536,
        latent=LatentAttention(rank=512, rope=64, nope=128, v=128),
        layer_types=("latent_attention",) * 8,
        num_dense_layers=1, dense_intermediate_size=6144,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.448, router_norm_eps=1e-20,
    ),
    # the CPU's copy of SmallThinker's mechanisms: two periods of (full,
    # window, window, window), a window of 8 — shorter than the test
    # prompts, so a ring wraps in a few steps — rope on the window layers
    # alone, the router on the layer's input, 8 ReGLU experts top 2, an
    # untied head
    "tiny-swa": HybridConfig(
        vocab_size=512, hidden_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, intermediate_size=32, head_dim=16,
        rope_theta=10000.0, rms_eps=1e-6, max_position=512,
        sliding_window=8, hidden_act="relu",
        num_experts=8, num_experts_per_tok=2,
        layer_types=("full_attention",) + ("sliding_attention",) * 3
        + ("full_attention",) + ("sliding_attention",) * 3,
        rope_layout=(0, 1, 1, 1) * 2, router_input="layer_input",
    ),
    # SmallThinker-21BA3B-Instruct CUT IN DEPTH to 12 of its 52 layers —
    # three whole periods of (full NoPE, window, window, window): 3 full and
    # 9 window layers, the published 1 : 3 — at every published width, with
    # all 64 experts and the whole vocabulary: one of the three 12-layer
    # stages of a (12, 12, 12, 16) pipeline over one four-chip host, what
    # one 16 GB chip holds in int8 beside a 64-slot cache of 11,776-row full
    # leaves and 4,096-row rings (benchmarks/configs/smallthinker-21b-a3b
    # .json has the cut). `intermediate_size` is the expert's width
    # (`moe_ffn_hidden_size`); there is no dense FFN and no shared expert.
    "smallthinker-21b-a3b": HybridConfig(
        vocab_size=151936, hidden_size=2560, num_layers=12, num_heads=28,
        num_kv_heads=4, intermediate_size=768, head_dim=128,
        rope_theta=1500000.0, rms_eps=1e-6, max_position=16384,
        sliding_window=4096, hidden_act="relu",
        num_experts=64, num_experts_per_tok=6,
        layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 3,
        rope_layout=(0, 1, 1, 1) * 3, router_input="layer_input",
    ),
    # the exaone_moe test size: two periods of (window, window, window,
    # full NoPE) with a window of 8, one leading dense layer, 8 experts top 2
    # by the sigmoid router of which this chip holds the first four, a shared
    # expert, q/k norms, one multi-token-prediction module — and a
    # vocabulary of 32, small enough that a random module's drafts land
    "tiny-xm": HybridConfig(
        vocab_size=32, hidden_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, intermediate_size=32, head_dim=16,
        rope_theta=10000.0, rms_eps=1e-5, max_position=512,
        sliding_window=8, qk_norm=True,
        num_experts=8, num_experts_per_tok=2, experts_held=(0, 4),
        shared_intermediate_size=32,
        num_dense_layers=1, dense_intermediate_size=96,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 2,
        rope_layout=(1, 1, 1, 0) * 2,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.5, router_norm_eps=1e-20, mtp_layers=1,
    ),
    # K-EXAONE-236B-A23B (HF `exaone_moe`) CUT IN DEPTH to 12 of its 48
    # layers — three whole periods of (window, window, window, full NoPE),
    # the leading dense layer and 11 sparse ones — plus its multi-token-
    # prediction module, at every published width. The router scores all
    # 128 experts; this chip HOLDS 16 of them and rows 0-19,199 of the
    # vocabulary — one chip's share of eight that divide each layer's
    # experts and the vocabulary eight ways and replicate the rest
    # (benchmarks/configs/k-exaone-236b-a23b.json has the cut).
    # `intermediate_size` is the routed expert's width
    # (`moe_intermediate_size`), `dense_intermediate_size` the published
    # `intermediate_size`.
    "k-exaone-236b-a23b": HybridConfig(
        vocab_size=19200, hidden_size=6144, num_layers=12, num_heads=64,
        num_kv_heads=8, intermediate_size=2048, head_dim=128,
        rope_theta=1000000.0, rms_eps=1e-5, max_position=262144,
        sliding_window=128, qk_norm=True,
        num_experts=128, num_experts_per_tok=8, experts_held=(0, 16),
        shared_intermediate_size=2048,
        num_dense_layers=1, dense_intermediate_size=18432,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 3,
        rope_layout=(1, 1, 1, 0) * 3,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.5, router_norm_eps=1e-20, mtp_layers=1,
    ),
    # the CPU's copy of Nemotron-H's mechanisms: blocks of one sub-layer
    # ("MEM*EMEM*EME": every pairing — a mamba block before experts, before
    # attention, attention before experts — as 7 trunk layers of 12 blocks),
    # 2 groups of 4 mamba heads, a chunk shorter than the test prompts, 4
    # query heads on ONE K/V head with no rotary, 8 ungated relu2 experts
    # top 2 by the sigmoid router of which this chip holds the SECOND four,
    # an ungated shared expert, an untied head
    "tiny-nh": HybridConfig(
        vocab_size=512, hidden_size=64, num_layers=12, num_heads=4,
        num_kv_heads=1, intermediate_size=24, head_dim=16,
        rope_theta=10000.0, rms_eps=1e-5, max_position=512,
        hidden_act="relu2", gated_ffn=False,
        num_experts=8, num_experts_per_tok=2, experts_held=(4, 4),
        shared_intermediate_size=40,
        layer_types=("mamba", "mamba", "attention", "mamba", "mamba",
                     "attention", "mamba"),
        ffn_layout=("moe", "none", "moe", "moe", "none", "moe", "moe"),
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_chunk_size=16, mamba_n_groups=2, rope=False,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.5, router_norm_eps=1e-20,
    ),
    # NVIDIA-Nemotron-3-Nano-30B-A3B (HF `nemotron_h`) at its WHOLE depth,
    # all 52 blocks — 23 Mamba-2 (8 groups), 6 GQA (32 Q on 2 K/V heads, no
    # rotary), 23 expert blocks — as 29 trunk layers: 23 (mixer, experts)
    # and 6 (mamba, none), every width as published, the whole vocabulary.
    # The router scores all 128 experts; this chip HOLDS 32 of them — one
    # chip's share of a four-chip host whose chips divide each block's
    # experts four ways and replicate the rest (benchmarks/configs/
    # nemotron-3-nano-30b-a3b.json has the cut). `intermediate_size` is the
    # routed expert's width (`moe_intermediate_size`); rope_theta is the
    # published key and no layer uses it (rope=False).
    "nemotron-3-nano-30b-a3b": HybridConfig(
        vocab_size=131072, hidden_size=2688, num_layers=52, num_heads=32,
        num_kv_heads=2, intermediate_size=1856, head_dim=128,
        rope_theta=10000.0, rms_eps=1e-5, max_position=262144,
        hidden_act="relu2", gated_ffn=False,
        num_experts=128, num_experts_per_tok=6, experts_held=(0, 32),
        shared_intermediate_size=3712,
        **dict(zip(("layer_types", "ffn_layout"), pair_blocks(
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"))),
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_d_conv=4, mamba_chunk_size=128, mamba_n_groups=8, rope=False,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.5, router_norm_eps=1e-20,
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


# tiny-xm at a vocabulary the byte tokenizer fits (ids 0-257): what the
# benchmark's CPU rehearsal serves (benchmarks/tests/test_run_xm_cpu.py);
# its drafts land as rarely as the real cell's
PRESETS["tiny-xm-bytes"] = dataclasses.replace(PRESETS["tiny-xm"],
                                               vocab_size=512)


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
    latent = getattr(config, "latent", None)
    if latent is not None:
        # ONE row a position, the latent and the shared rotary key (in
        # whole lane tiles: `LatentAttention.lanes`): no head axis, and no
        # `v` leaf beside it
        return (latent.lanes,)
    K, D = config.num_kv_heads, config.dim_per_head
    return (K // 2, 2 * D) if D == 64 and K % 2 == 0 else (K, D)


def kv_head_major(row: tuple[int, ...], kv_bytes: int) -> bool:
    """Whether a K/V leaf whose position holds `row` (`kv_row`: what follows
    [L, B, T] in the leaf's shape) entries of `kv_bytes` LIES HEAD-MAJOR on
    one device: 2 one-byte rows of whole lane tiles (nemotron-3-nano-30b-
    a3b's 2 int8 heads of 128, qwen3-next-80b-a3b's of 256). Such a pair
    fills a sixteenth of an (8, 128)(4, 1) tile, and the chip's own layout
    of `s8[L, B, T, 2, D]` — an argument, a donated state — is [L, B, 2, T,
    D] physically (`{4,2,3,1,0:T(8,128)(4,1)}`). A program keeps it there
    only while nothing asks for a position's two rows side by side: the
    scatter whose window is the [2, D] row does (`write_kv`), and XLA then
    relays the WHOLE leaf to a (4, 128) tile around the scatter and back to
    head-major around ops/decode_attention.py's view of it, K and V, once
    a layer (PERF.md PR 62: 36 copies of 63 MB in nemotron's decode step,
    a third of it). So this is what every site that would fix a layout
    asks — `write_kv` indexes the head too and writes a decode step's
    scales by a select (`_put_scales`: the planes of 2 rows are the same
    case), `attention_paths` names it — by shape alone; a wider or fuller
    row (4 heads, 2 of bfloat16) is tiled as written and interleaved."""
    return (len(row) == 2 and row[0] == 2 and kv_bytes == 1
            and row[1] % 128 == 0)


class KVCache(NamedTuple):
    """Static-shape KV cache: [layers, batch, capacity, kv_heads, head_dim]
    ([..., kv_heads / 2, 128] for heads of 64: `kv_row`). That is the
    LOGICAL shape of every model's leaves; where they lie in HBM is the
    compiler's: as a rule a position's heads are rows of one tile, but 2
    int8 heads lie head-major ([layers, batch, 2, capacity, head_dim]
    physically) and stay so while every write indexes the head
    (`kv_head_major`, `write_kv`).

    With quantized=True at init, k/v hold int8 payloads and k_scale/v_scale
    hold the per-(layer, slot, kv_head, position) f32 dequant scales
    (ops/quant.py quantize_kv) — [layers, batch, kv_heads, capacity].
    Position is the MINOR scale dim on purpose: with kv_heads (8) minor the
    arrays would tile-pad 16x in HBM the moment a Pallas kernel takes them
    as operands. The scale planes are head_dim× smaller than the payload,
    so the decode-step cache read drops to ~half of bf16.
    """

    k: jnp.ndarray
    # None for a latent-attention model (config.latent), whose `k` holds
    # the one row a position there is — [layers, batch, capacity, lanes]:
    # the normed latent, the shared rotary key, then zeros up to a whole
    # lane tile (576 values in 640 lanes) — in the compute dtype, never
    # quantized
    v: jnp.ndarray | None
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
    # Models with window AND full attention layers only (a HybridConfig
    # with "sliding_attention" layers; None, so no leaf, for every other):
    # the window layers' K/V, kw / vw [n_window, B, ring, K, D] with their
    # scale planes [n_window, B, K, ring] where int8 — the served cache's
    # `ring` is exactly the window, and position p lies at row p mod ring
    # (`write_kv(ring_valid=)`), so a ring holds a slot's last `ring`
    # positions and no other; a prefill scratch's is the bucket and holds
    # position p at row p (the engine's insert rolls the last `ring` rows
    # into place). `k` / `v` then hold the FULL attention layers alone, at
    # the full capacity. Such a model's `expert_pairs` ends in
    # WINDOW_COUNTS.
    kw: jnp.ndarray | None = None
    vw: jnp.ndarray | None = None
    kw_scale: jnp.ndarray | None = None
    vw_scale: jnp.ndarray | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def ring_view(cache: KVCache) -> KVCache:
    """The cache as a window layer's `_attention` sees it: the ring leaves
    in the places of `k` / `v` and their scales (a rebinding of the pytree,
    no copy)."""
    return cache._replace(k=cache.kw, v=cache.vw, k_scale=cache.kw_scale,
                          v_scale=cache.vw_scale, kw=None, vw=None,
                          kw_scale=None, vw_scale=None)


def ring_restore(cache: KVCache, view: KVCache) -> KVCache:
    """`ring_view`'s inverse: `view`'s leaves back into the ring's places
    of `cache` (whose full leaves a window layer never touches), with what
    else the layer updated."""
    return view._replace(k=cache.k, v=cache.v, k_scale=cache.k_scale,
                         v_scale=cache.v_scale, kw=view.k, vw=view.v,
                         kw_scale=view.k_scale, vw_scale=view.v_scale)


def init_expert_pairs(config) -> jnp.ndarray:
    """A zeroed `KVCache.expert_pairs`: a count an expert the router scores,
    then the words of whatever else the model counts there
    (models/residents.py tail_words: the ONE definition of the tail)."""
    from symmetry_tpu.models.residents import tail_words

    return jnp.zeros((config.num_experts + len(tail_words(config)),),
                     jnp.int32)


def init_cache(
    config: ModelConfig, batch: int, capacity: int, dtype=jnp.bfloat16,
    *, quantized: bool = False, count_experts: bool = False,
    ring: int | None = None,
) -> KVCache:
    """`ring`: the rows of a window / full model's window leaves (models/
    hybrid.py init_cache; no other model takes it)."""
    if getattr(config, "layer_types", None):
        from symmetry_tpu.models import hybrid

        return hybrid.init_cache(config, batch, capacity, dtype,
                                 quantized=quantized,
                                 count_experts=count_experts,
                                 **({} if ring is None else {"ring": ring}))
    shape = (config.num_layers, batch, capacity, *kv_row(config))
    pairs = init_expert_pairs(config) if count_experts else None
    extra = {}
    sparse = getattr(config, "sparse", None)
    if sparse is not None:
        extra["idx"] = jnp.zeros((config.num_layers, batch, capacity,
                                  sparse.index_head_dim), dtype)
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
             idx: jnp.ndarray | None = None,
             ring_valid: jnp.ndarray | None = None) -> KVCache:
    """Scatter this call's K/V ([B, S, K, D], roped) straight into the full
    cache at (layer, slot, position) — an in-place row write on the layer
    scan's carry; a per-layer slice-out/slice-in would stream the whole
    layer slice through HBM. Every forward of S > 1 positions writes here,
    and every decode step but the homogeneous one-chip trunk's through the
    kernel, which appends ONCE behind its layer scan (`append_step`,
    `attention_paths`: `kv_append`) — four scatters a layer (K, V and an
    int8 cache's two planes) cost a decode step ~16 us each whatever they
    move. `positions` is [B, S]; a position at or past
    the capacity is dropped. Padded tail tokens write garbage past the
    slot's valid length — never read, overwritten later. A quantized cache
    takes the int8 payload plus the f32 scales (ops/quant.py quantize_kv).
    Heads of 64 are written as the cache holds them, in pairs (`kv_row`);
    their scales stay one a head.
    `idx` ([B, S, index_head_dim]; sparse attention) goes into `cache.idx`
    at the same rows.
    `ring_valid` ([B]: each slot's length AFTER this call; a window layer's
    leaves, `ring_view`) makes the leaf a RING of its capacity M: position p
    is written at row p mod M, and only a slot's last M positions under its
    length are written at all — an older one would land on a newer one's
    row and a padded tail's garbage on a live row, so both are dropped (a
    scatter's order among equal rows is not defined).

    `by_head` (the trunk is sharded over a mesh) indexes the head too: a
    cache sharded by KV head may hold 2 int8 heads a chip, which lie in
    HBM as [L, B, K, T, D] (a second-minor dim of 2 would pad). A scatter
    whose window is the [K, D] row needs K next to D, so the program
    copied the whole cache into the padded layout around every decode
    block (+4.3 GB a chip at 64 x 2048: it did not fit). A window of D
    alone either layout serves in place. It is the SCATTER that keeps such
    a leaf head-major, not the mesh: on one device the same 2 int8 heads
    under the row-window scatter were relaid whole around every layer's
    write and read, so a leaf that `kv_head_major` names takes the
    head-indexed scatter whatever the caller says — and its scale planes,
    at a decode step (S = 1), a select in place of a scatter
    (`_put_scales`: 2-row planes have the same quarrel with a scatter).
    """
    B, S = k.shape[:2]
    nkv = cache.k.shape[3]
    if ring_valid is not None:
        M = cache.k.shape[2]
        live = ((positions >= ring_valid[:, None] - M)
                & (positions < ring_valid[:, None]))
        positions = jnp.where(live, positions % M, M)  # M: dropped

    def rows(x):
        return x.reshape(B, S, *cache.k.shape[3:])

    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    l_idx = jnp.full((B, S), layer, jnp.int32)
    row = (l_idx, b_idx, positions)  # each write is one [K, D] row
    if idx is not None:
        cache = cache._replace(
            idx=cache.idx.at[row].set(idx.astype(cache.idx.dtype)))
    head_major = kv_head_major(cache.k.shape[3:], cache.k.dtype.itemsize)
    if by_head or head_major:
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
    def put(plane, scales):
        if S == 1 and head_major:
            return _put_scales(plane, scales, layer, positions[:, 0])
        return plane.at[l_idx, b_idx, :, positions].set(scales)

    return cache._replace(
        k=cache.k.at[row].set(rows(kq)),
        v=cache.v.at[row].set(rows(vq)),
        k_scale=put(cache.k_scale, ks), v_scale=put(cache.v_scale, vs))


def _put_scales(plane: jnp.ndarray, scales: jnp.ndarray, layer,
                positions: jnp.ndarray) -> jnp.ndarray:
    """`plane` [L, B, K, T] with one position's `scales` [B, 1, K] a slot at
    [layer, b, :, positions[b]] (a position at or past T is dropped) — a
    decode step's write into the planes of a HEAD-MAJOR leaf
    (`kv_head_major`), as a select over the layer's slice updated in place.
    A scatter there costs the step a relayout of the whole plane a layer:
    planes of 2 rows lie in (2, 128) tiles, which is what
    ops/decode_attention.py copies blocks out of, and XLA's scatter takes
    no 2-row tile — it relaid `f32[6,64,2,640]` to a padded (8, 128) tile
    for each write and back for each kernel call, K and V, 0.19 ms a pass
    and 5.1 ms of nemotron's 26 ms step (PERF.md, PR 62). An elementwise
    select takes the tile as it lies."""
    T = plane.shape[3]
    at = jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=True)
    hit = (positions[None, :, None, None]
           == jnp.arange(T, dtype=positions.dtype)[None, None, None, :])
    new = jnp.where(hit, scales[None, :, 0, :, None].astype(plane.dtype), at)
    return jax.lax.dynamic_update_slice_in_dim(plane, new, layer, 0)


def append_step(cache: KVCache, positions: jnp.ndarray,
                rows: tuple) -> KVCache:
    """A decode step's ONE cache write (`run_layers`): every layer's row of
    slot b — `rows` as `_attention(own_row=True)` handed them out of the
    layer scan, stacked: K and V [L, B, *kv_row] in the cache's dtype, and
    of an int8 cache the scales [L, B, K] beside them — at position
    `positions[b]`, all layers at once: a scatter a K/V leaf, its window a
    slot's [L, 1, 1, *kv_row] rows, and one kernel call for the scale
    planes (ops/scale_append.py: no scatter writes a plane's [L, 1, K, 1]
    window where it lies), where `write_kv` a layer is four scatters a
    LAYER — each ~16 us on a v5e whatever it moves, 128 updates of 1 KB or
    of 8 floats alike: 1.7-1.8 ms of the dense cells' 22-24 ms step
    (PERF.md, PR 66). The same bytes at the same places as `write_kv` called
    a layer; a position at or past the capacity is dropped."""
    k, v, k_scale, v_scale = rows
    b = jnp.arange(positions.shape[0], dtype=jnp.int32)
    cache = cache._replace(k=cache.k.at[:, b, positions].set(k),
                           v=cache.v.at[:, b, positions].set(v))
    if not cache.quantized:
        return cache
    from symmetry_tpu.ops.scale_append import append_scales

    planes = append_scales((cache.k_scale, cache.v_scale),
                           (k_scale, v_scale), positions,
                           interpret=interpret_mode())
    return cache._replace(k_scale=planes[0], v_scale=planes[1])


# What a latent-attention model's `expert_pairs` ends in (as a sparse
# model's ends in its own counters: `KVCache.idx`): forwards of one position
# a slot over the cache, and the live rows those read (each live slot's
# length with the current position, once a forward — not a layer). The
# vector is handed out and zeroed a decode block (engine/engine.py), so no
# int32 wraps: a block of 16 steps over 64 x 11,776 rows counts 1.2e7.
LATENT_COUNTS = ("decode_steps", "live_positions")


def tail_add(vector: jnp.ndarray, config, words: tuple[str, ...],
             new: jnp.ndarray) -> jnp.ndarray:
    """`vector` (an `expert_pairs`) with `new` added to the words of the
    row that counts `words`, where the table says they lie
    (models/residents.py tail_at)."""
    from symmetry_tpu.models.residents import tail_at

    lo, hi = tail_at(config, words)
    # (a row that ends the vector lowers as it did while each model had one
    # row: tools/lowered_programs.py holds the older programs byte for byte)
    return jnp.concatenate([vector[:lo], vector[lo:hi] + new]
                           + ([vector[hi:]] if hi < vector.shape[0] else []))


def count_latent(vector: jnp.ndarray, lengths: jnp.ndarray,
                 kv_valid: jnp.ndarray, config) -> jnp.ndarray:
    """`vector` (an `expert_pairs` with LATENT_COUNTS in its tail) plus one
    decode forward's counts. A parked lane (length 0) is no live row."""
    new = jnp.stack([jnp.int32(1), jnp.sum(
        jnp.where(lengths > 0, kv_valid, 0), dtype=jnp.int32)])
    return tail_add(vector, config, LATENT_COUNTS, new)


# What a window / full model's `expert_pairs` ends in, as a latent model's
# ends in LATENT_COUNTS and by the same rule (handed out and zeroed a decode
# block, so no int32 wraps): forwards of one position a slot over the cache;
# the rows a FULL layer reads in them (each live slot's length with the
# current position) and the rows a WINDOW layer reads (the same, capped at
# the ring), once a forward — not a layer; and the steps whose write came
# back to row 0 of a full ring.
WINDOW_COUNTS = ("decode_steps", "full_rows", "ring_rows", "ring_wraps")
# What a model that holds a SHARE of its experts counts behind the per-expert
# pairs: (expert layer, held expert) hits — a held expert that some VALID
# pair of a forward fell on — which is what the routed form's kernel must
# read, an expert's matrices a hit (models/moe.py; `stats.engine.moe`).
HELD_COUNTS = ("expert_hits",)
# What a model with a multi-token-prediction module counts a decode step the
# module drafts for (engine/engine.py; `stats.engine.mtp`), live lanes
# alone: drafts scored, drafts accepted, tokens yielded, (slot, step) pairs.
MTP_COUNTS = ("drafted", "accepted", "emitted", "steps")


def count_window(vector: jnp.ndarray, lengths: jnp.ndarray,
                 kv_valid: jnp.ndarray, ring: int, config) -> jnp.ndarray:
    """`vector` (an `expert_pairs` with WINDOW_COUNTS in its tail) plus one
    decode forward's counts — of one position a slot, or of a verify's
    last one (`kv_valid`: the rows with it). A parked lane (length 0) is
    no live row."""
    live = lengths > 0

    def total(x):
        return jnp.sum(jnp.where(live, x, 0), dtype=jnp.int32)

    new = jnp.stack([
        jnp.int32(1), total(kv_valid), total(jnp.minimum(kv_valid, ring)),
        total(((kv_valid > ring) & ((kv_valid - 1) % ring == 0))
              .astype(jnp.int32))])
    return tail_add(vector, config, WINDOW_COUNTS, new)


def write_latent(cache: KVCache, layer: jnp.ndarray, positions: jnp.ndarray,
                 rows: jnp.ndarray) -> KVCache:
    """`write_kv` for a latent cache: this call's rows ([B, S, rank + rope],
    the normed latent | the roped shared key; padded here with zeros to the
    leaf's lanes) scattered into `cache.k` at (layer, slot, position), in
    place on the layer scan's carry."""
    B, S = rows.shape[:2]
    rows = jnp.pad(rows, ((0, 0), (0, 0),
                          (0, cache.k.shape[-1] - rows.shape[-1])))
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    l_idx = jnp.full((B, S), layer, jnp.int32)
    return cache._replace(k=cache.k.at[l_idx, b_idx, positions].set(
        rows.astype(cache.k.dtype)))


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
                    batch: int, kv_bytes: int, kind: str | None = None,
                    ring: int | None = None) -> dict:
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
      - ONE device and 2 int8 KV heads of whole lane tiles a position
        (`kv_head_major`): the leaves lie head-major, [L, B, 2, T, D]
        physically, in every program — `write_kv` indexes the head too,
        so nothing asks for the two rows side by side, and a decode step
        writes the 2-row scale planes by a select in the (2, 128) tiles
        the kernel reads — and the decode kernel's lane a (slot, head) is
        a bitcast of them; the reply says so (`kv_layout`), there alone;
      - learned sparse attention (`config.sparse`): the decode kernel
        takes a selection only beside the scale planes of an int8 cache of
        interleaved heads (ops/decode_attention.py keep_supported); any
        other cache decodes on the XLA path;
      - a head of 64 (lfm2-8b-a1b) lies in the cache in pairs (`kv_row`)
        and takes the kernel's pair form where the pairs are interleaved
        rows; any other head size that is no lane tile (the tiny test
        configurations' 16) keeps the XLA path and the reply says why
        (`decode_why`);
      - generation by diffusion over blocks (`config.diffusion`): every
        forward over the cache carries a block of queries a slot, from a
        block boundary (`cache.lengths`: the engine's contract), so under
        the block mask all of a slot's rows see the same keys — the
        kernel's mask — and the forward takes the kernel with the block
        as its q tile (`decode_queries`) wherever it has a geometry for
        that; a cache it has none for (the admission program's scratch of
        bucket + block positions is no multiple of 128, a tiny head is no
        lane tile) and a sliding window (a mask a position) keep the XLA
        path, the served cache's with its `decode_why`;
      - a MODEL-WIDE window (`sliding_window` on a config without
        `layer_types`): one cache of full capacity, the kernels masked by
        the window (flash skips the blocks wholly older than it, the
        decode kernel takes `window=`);
      - a window A KIND (a HybridConfig with "sliding_attention" layers):
        the answer is a kind's — `kind` "full" (the `k` / `v` leaves at
        `capacity`, no window) or "window" (the ring leaves, `capacity`
        their rows) — and, asked without one, both side by side
        (`_window_paths`: `startup.attention`). A window layer's decode is
        the kernel over its ring with `kv_length = min(length, ring)` and
        NO `window=`: keys are roped when written and a softmax does not
        care in which row a key lies, so a ring of exactly the window's
        rows needs no start offset; a leaf of any other size (a uniform
        cache in a test) keeps the XLA path, which masks by each row's
        own position."""
    from symmetry_tpu.ops import decode_attention as da

    kernel = "pallas-interpret" if interpret_mode() else "pallas"
    latent = getattr(config, "latent", None)
    if latent is not None:
        return _latent_paths(latent, capacity, kernel, kv_bytes)
    if getattr(config, "window_kind", None) is not None and kind is None:
        return _window_paths(config, capacity, tp_mesh, batch=batch,
                             kv_bytes=kv_bytes, ring=ring)
    model = 1 if tp_mesh is None else dict(tp_mesh.shape).get("model", 1)
    if config.num_kv_heads % model:
        return {"prefill": "xla", "decode": "xla"}
    diffusion = getattr(config, "diffusion", None)
    # no program of a model that generates by diffusion has one query a
    # slot: a decode forward carries a block of them over the cache
    queries = 1 if diffusion is None else diffusion.block
    data = 1 if tp_mesh is None else dict(tp_mesh.shape).get("data", 1)
    tiles = da.geometry(batch // data if batch % data == 0 else batch,
                        capacity, config.num_kv_heads // model,
                        config.dim_per_head, kv_bytes, queries)
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
    if queries > 1 and (tiles is None or tp_mesh is not None
                        or config.sliding_window is not None):
        lacks = (f"has no geometry for a {batch} x {capacity} cache"
                 if tiles is None else
                 "takes on one device and without a sliding window alone")
        return {"prefill": kernel, "decode": "xla", "decode_why": (
            f"a decode forward carries {queries} positions a slot under "
            f"the block mask, a q tile that ops/decode_attention.py "
            f"{lacks}: the forward takes gqa_attention over the cache")}
    if tiles is None or (tp_mesh is not None
                         and capacity < da.TP_MIN_CAPACITY):
        return {"prefill": kernel, "decode": "xla"}
    if getattr(config, "sparse", None) is not None and not da.keep_supported(
            config.num_kv_heads, kv_bytes, kv_bytes == 1,
            config.dim_per_head):
        return {"prefill": kernel, "decode": "xla"}
    if kind == "window" and capacity != config.sliding_window:
        return {"prefill": kernel, "decode": "xla", "decode_why": (
            f"a window layer's leaf of {capacity} rows is no ring of the "
            f"window's {config.sliding_window}: its keys need a start "
            f"offset that ops/decode_attention.py's items lack, so the "
            f"step masks by each row's own position in gqa_attention")}
    head_major = tp_mesh is None and kv_head_major(kv_row(config), kv_bytes)
    return {"prefill": kernel, "decode": kernel,
            **({"decode_queries": queries} if queries > 1 else {}),
            "decode_slot_tile": tiles[0], "decode_block_t": tiles[1],
            # (a key only where the leaves lie so: no other model's reply
            # moves)
            **({"kv_layout": "head-major [L, B, K, T, D]: head-indexed "
                             "writes (a decode step's scales by a select)"
                             ", a lane a (slot, head)"}
               if head_major else {}),
            # (likewise: the homogeneous trunk's one-query programs alone)
            **({"kv_append": "step"}
               if tp_mesh is None and queries == 1 and not head_major
               and getattr(config, "sparse", None) is None
               and not getattr(config, "layer_types", None) else {})}


def _window_paths(config: "HybridConfig", capacity: int, tp_mesh, *,
                  batch: int, kv_bytes: int, ring: int | None = None
                  ) -> dict:
    """`attention_paths` of a window / full model asked without a kind: the
    route of each kind's leaf beside the other's (`startup.attention`). The
    served ring is exactly the window — or `ring` rows where it carries a
    draft beside it (models/residents.py ring_rows)."""
    from symmetry_tpu.ops import flash

    c, span = config, config.sliding_window
    ring = span if ring is None else ring

    def of(kind, rows, name):
        paths = attention_paths(c, rows, tp_mesh, batch=batch,
                                kv_bytes=kv_bytes, kind=kind)
        layers = c.layers_of(name)
        roped = {c.layer_rope(i) for i in layers}
        prefill = paths.pop("prefill")
        return prefill, {
            "layers": len(layers),
            "rope": roped.pop() if len(roped) == 1 else "mixed", **paths}

    prefill, full = of("full", capacity, c.attention_kind)
    _, window = of("window", ring, c.window_kind)
    routes = {full["decode"], window["decode"]}
    out = {"kind": "window+full", "prefill": prefill,
           "decode": routes.pop() if len(routes) == 1 else "mixed",
           "full": {**full, "capacity": capacity},
           "window": {**window, "span": span, "ring": ring}}
    if prefill != "xla":
        out["prefill_form"] = (
            f"{flash.WIDE_NAME}: tiles of {flash.WIDE_TILE}, {c.num_heads} "
            f"query heads over {c.num_kv_heads} KV heads; a window layer "
            f"walks the tiles its window leaves"
            if flash.wide_takes(span) else
            f"{flash.WIDE_NAME} on the full layers; flash_prefill in tiles "
            f"of 128 on the window layers (a window of {span} is no "
            f"multiple of {flash.WIDE_TILE})")
    return out


def _latent_paths(latent: LatentAttention, capacity: int, kernel: str,
                  kv_bytes: int) -> dict:
    """`attention_paths` of a latent-attention model."""
    from symmetry_tpu.ops import flash, mla_attention as mla

    block_t = mla.geometry(capacity, latent.lanes, kv_bytes)
    out = {"kind": "latent", "prefill": kernel,
           "prefill_form": (f"expanded: keys of {latent.nope + latent.rope}"
                            f", values of {latent.v} a head (flash)"),
           **({} if kernel == "xla" else {"prefill_tile": flash.WIDE_TILE}),
           "decode_form": (f"absorbed: one row of {latent.row} values "
                           f"({latent.lanes} lanes) a position for every "
                           f"head")}
    if block_t is None:
        return {**out, "decode": "xla", "decode_why": (
            f"ops/mla_attention.py has no block for a capacity of "
            f"{capacity} (no multiple of {mla.LANES}): the absorbed form "
            f"runs as two einsums over the layer's rows")}
    return {**out, "decode": kernel, "decode_block_t": block_t}


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
    tp_mesh=None,               # static: Mesh the trunk is GSPMD-sharded over
    rope_positions=None,        # [3, B, S] multimodal rotary components
    rope: bool | None = None,   # static: this LAYER's rotary choice (None:
                                # the config's)
    window: int | None = None,  # static: this layer's window, of a model
                                # with a window a KIND (any other model's
                                # is `config.sliding_window`, model-wide)
    ring: bool = False,         # static: `cache` is a window layer's
                                # `ring_view` and its leaf a ring
    own_row: bool = False,      # static: the caller appends this position's
                                # row itself (`run_layers`, where
                                # `attention_paths` says `kv_append`)
) -> tuple:
    """The attention mixer: projections, the cache write, attention by the
    routed path, the output projection -> ([B, S, E], cache).

    With `own_row` (one position a slot through the decode kernel) NOTHING
    is written: the position's K/V row, quantised as the cache will hold
    it, is an operand of attention — the kernel walks the rows below it
    and starts each slot's softmax from the row (ops/decode_attention.py
    `own`) — and comes back BESIDE the cache, -> ([B, 1, E], cache, (k, v,
    k_scale, v_scale) a slot) as `append_step` takes them stacked over the
    layers: the cache is read, never written, here.

    A window layer over a RING (`ring`) of M rows: the write goes to
    position mod M, a decode step attends over rows [0, min(length, M)) —
    by the kernel where M is the window, each row's own position masked in
    gqa_attention otherwise. Queries of S > 1 over a ring are right where
    no key a query needs was overwritten by this call's own later
    positions: the slot's length stays within M, or M >= window + S - 1
    (the engine refuses the programs that could break it: chunks,
    speculative verify)."""
    windowed = getattr(config, "window_kind", None) is not None
    if not windowed:
        window = config.sliding_window
    if rope is None:
        rope = getattr(config, "rope", True)
    if getattr(config, "latent", None) is not None:
        if tp_mesh is not None:
            raise ValueError("a model with latent attention runs on one "
                             "device: no tp_mesh")
        return _latent_attention(x, lp, cache, layer, positions, kv_valid,
                                 seq_lens, config, prefill_flash)
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
    if rope and rope_positions is not None:
        # a multimodal rotary's three components (equal ones, the served
        # text path, are `positions`: the branch below)
        section = config.mrope_section
        q = apply_rope(q, rope_positions, config.rope_theta,
                       mrope_section=section)
        k = apply_rope(k, rope_positions, config.rope_theta,
                       mrope_section=section)
    elif rope:
        rot = int(D * getattr(config, "partial_rotary_factor", 1.0))
        q = apply_rope(q, positions, config.rope_theta, rot)
        k = apply_rope(k, positions, config.rope_theta, rot)
    scale = getattr(config, "attention_multiplier", None)
    if scale is not None:
        # every attention path scales scores by 1 / sqrt(D): fold the
        # ratio to the published multiplier into q
        q = q * jnp.asarray(scale * D ** 0.5, q.dtype)

    sparse = getattr(config, "sparse", None)
    diffusion = getattr(config, "diffusion", None)
    # (a keyword only where there is a block: no other model's call moves)
    blocked = {} if diffusion is None else {"block_len": diffusion.block}
    paths = attention_paths(
        config, cache.k.shape[2], tp_mesh, batch=cache.k.shape[1],
        kv_bytes=cache.k.dtype.itemsize,
        **({"kind": "window" if window else "full"} if windowed else {}))
    if own_row:
        if S != 1 or paths.get("kv_append") != "step" or ring:
            raise ValueError("own_row: this program writes its rows a "
                             "layer (attention_paths has no kv_append)")
        # the position's row as the leaves will hold it (`kv_row`: heads of
        # 64 in pairs), and an int8 cache's scales a head
        fold = (B, *cache.k.shape[3:])
        if cache.quantized:
            (own_k, k_scale), (own_v, v_scale) = (quantize_kv(k[:, 0]),
                                                  quantize_kv(v[:, 0]))
            own = (own_k.reshape(fold), own_v.reshape(fold), k_scale,
                   v_scale)
        else:
            own = (k[:, 0].astype(cache.k.dtype).reshape(fold),
                   v[:, 0].astype(cache.v.dtype).reshape(fold), None, None)
    elif ring:
        cache = write_kv(cache, layer, positions, k, v, by_head=False,
                         ring_valid=kv_valid)
    elif sparse is None:
        cache = write_kv(cache, layer, positions, k, v,
                         by_head=tp_mesh is not None)
    else:
        # the lightning indexer: index queries, this position's index key
        # (cached beside K and V) and the head weights, from the same
        # normed input; plain rotary by the temporal component
        from symmetry_tpu.ops import sparse_attention as sa

        if tp_mesh is not None:
            raise ValueError("a model with learned sparse attention runs "
                             "on one device: no tp_mesh")
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

        kw = dict(window=window,
                  interpret=interpret_mode(), **blocked)
        if keep is not None:
            # (the keep-set carries causality and the prompt's length)
            attn = sa.flash_sparse(q, k, v, keep,
                                   interpret=interpret_mode())
        elif windowed and flash.wide_takes(window):
            # prompts of 5-8k tokens: the 512-tile walk, its K/V of one
            # KV head shared by the head's group, a window layer's
            # tiles bounded by the window (PERF.md, PR 58)
            attn = flash.flash_prefill_wide(
                q, k, v, seq_lens, window=window,
                interpret=interpret_mode())
        elif tp_mesh is None:
            attn = flash.flash_prefill(q, k, v, seq_lens, **kw)
        else:
            attn = flash.flash_prefill_tp(q, k, v, seq_lens,
                                          mesh=tp_mesh, **kw)
    elif paths["decode"] != "xla" and S == paths.get("decode_queries",
                                                     1):
        # Decode — one position a slot, or a block-diffusion forward's
        # block of them from a block boundary, which all see the same
        # keys: the Pallas kernel reads only each slot's occupied KV
        # prefix; the full cache is its operand and stays where it
        # lies, layer and slot are DMA addressing
        # (ops/decode_attention.py).
        from symmetry_tpu.ops import decode_attention as da

        scales = ((cache.k_scale, cache.v_scale) if cache.quantized
                  else (None, None))
        if S > 1 and cache.quantized:
            # a block's forwards hand the kernel this layer's scale
            # planes (1.3 MB each at 128 x 640, read out of XLA's fast
            # memory): the whole arrays are small for a model this
            # shallow, XLA keeps them there across the layer loop for
            # the in-place scatters, and the kernel's pin to HBM on
            # them has it evict and refetch both every layer — at
            # sdar's twelve layers the v5e compiler's repacker crashes
            # on that program (tests/test_chip_compile.py compiles it)
            scales = tuple(at_layer(x)[None] for x in scales)
        args = (q[:, 0] if S == 1 else q, cache.k, cache.v, layer,
                # a ring holds the window's keys and no other: every
                # written row is live, whichever position it holds
                jnp.minimum(kv_valid, cache.k.shape[2]) if ring
                # the cached rows alone: the own row is an operand
                else jnp.maximum(kv_valid - 1, 0) if own_row
                else kv_valid, *scales)
        if keep is not None:
            args += (keep[:, 0],)
        kw = dict(window=None if ring else window,
                  interpret=interpret_mode(),
                  # (named in a trace for this model alone: no other
                  # model's call moves)
                  **({"name": da.WINDOW_NAME} if windowed else {}))
        if own_row:
            kw["own"] = own
        attn = (da.decode_attention(*args, **kw) if tp_mesh is None
                else da.decode_attention_tp(*args, mesh=tp_mesh, **kw))
        if own_row and cache.quantized:
            # (the planes, untouched, through the call: no constants of the
            # layer loop for XLA to stage whole around it)
            attn, *planes = attn
            cache = cache._replace(k_scale=planes[0], v_scale=planes[1])
        attn = attn[:, None] if S == 1 else attn
    elif (windowed and not ring and not prefill_flash and S == 2
          and paths["decode"] != "xla" and tp_mesh is None):
        # A verify of ONE drafted position over a full leaf of a window /
        # full model (no such model could be verified before the rings
        # carried a draft): the decode kernel once a position, each over
        # the rows up to its own — twice the live rows, where
        # gqa_attention would read the leaf's whole capacity and
        # dequantise it beside itself. (A position past `seq_lens` reads
        # a row of garbage more for an output nobody takes.)
        from symmetry_tpu.ops import decode_attention as da

        scales = ((cache.k_scale, cache.v_scale) if cache.quantized
                  else (None, None))
        T = cache.k.shape[2]
        attn = jnp.stack([
            da.decode_attention(
                q[:, i], cache.k, cache.v, layer,
                jnp.minimum(positions[:, i] + 1, T), *scales,
                interpret=interpret_mode(), name=da.WINDOW_NAME)
            for i in range(S)], axis=1)
    else:
        attn = gqa_attention(
            q, heads_at_layer(cache.k), heads_at_layer(cache.v),
            positions, kv_valid,
            sliding_window=window,
            k_scale=at_layer(cache.k_scale) if cache.quantized else None,
            v_scale=at_layer(cache.v_scale) if cache.quantized else None,
            keep=keep, **blocked,
            **({"kv_positions": ring_positions(kv_valid,
                                               cache.k.shape[2])}
               if ring else {}))
    attn = attn.reshape(B, S, nq * D)
    if gate is not None:
        attn = attn * jax.nn.sigmoid(gate)
    if own_row:
        return qmatmul(attn, lp["wo"]), cache, own
    return qmatmul(attn, lp["wo"]), cache


def ring_positions(kv_valid: jnp.ndarray, rows: int) -> jnp.ndarray:
    """[B, rows] int32: the position each row of a ring holds for a slot of
    `kv_valid` written positions — the newest p = r mod rows under the
    length — or -1 for a row not written yet."""
    r = jnp.arange(rows, dtype=jnp.int32)[None, :]
    n = kv_valid[:, None]
    return jnp.where(r < n, r + rows * ((n - 1 - r) // rows), -1)


def absorbed_factors(wkvb, latent: LatentAttention, num_heads: int,
                     dtype=jnp.bfloat16) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`W_kvb` ([.., rank, H * (nope + v)], dense or int8 with column
    scales) split a head into the absorbed form's factors, (`wuk` [.., H,
    nope, rank], `wuv` [.., H, rank, v]) in `dtype`: what the decode step
    multiplies the nope query and the latent-space output by instead of
    expanding the cache. `absorb_latent` alone calls it for a tree that is
    served, and holds the factors beside `wkvb`, which the expanded prefill
    reads."""
    w = dequantize(wkvb) if isinstance(
        wkvb, QuantizedTensor) else wkvb.astype(jnp.float32)
    w = w.reshape(*w.shape[:-1], num_heads, latent.nope + latent.v)
    wuk = jnp.moveaxis(w[..., :latent.nope], -3, -1)      # [.., H, nope, R]
    wuv = jnp.moveaxis(w[..., latent.nope:], -3, -2)      # [.., H, R, v]
    return wuk.astype(dtype), wuv.astype(dtype)


def absorb_latent(params: dict, config: ModelConfig,
                  dtype=jnp.bfloat16) -> dict:
    """`params` with the absorbed factors of every latent-attention layer
    (`layers.attn.wuk` / `wuv`, `absorbed_factors` of `wkvb`) in place; a
    model without latent attention is returned as it is. THE derivation,
    made once on the finished tree — after quantisation, so that decode
    multiplies by the same int8-rounded `W_kvb` the prefill expands
    through: `init_params` ends with it (a preset's leaves are drawn
    quantised), and a checkpoint's tree passes it after `quantize_params`
    (engine.py `from_tpu_config`); no converter and no forward derives
    them again."""
    latent = getattr(config, "latent", None)
    if latent is None:
        return params
    attn = params["layers"]["attn"]
    attn["wuk"], attn["wuv"] = jax.jit(
        lambda w: absorbed_factors(w, latent, config.num_heads, dtype))(
            attn["wkvb"])
    return params


def _latent_attention(
    x: jnp.ndarray,             # [B, S, E], already normed
    lp: dict,
    cache: KVCache,             # k: FULL [L, B, T, rank + rope]; no v
    layer: jnp.ndarray,
    positions: jnp.ndarray,     # [B, S]
    kv_valid: jnp.ndarray,      # [B]
    seq_lens: jnp.ndarray,      # [B]
    config: ModelConfig,
    prefill_flash: bool,
) -> tuple[jnp.ndarray, KVCache]:
    """`_attention` of a latent-attention layer (HF `DeepseekV3Attention`
    without a query latent). The cache takes the row `c_n | k_r` alone. A
    prompt from an empty cache attends EXPANDED over its own rows — K =
    (c_n W_kvb's nope part | k_r for every head), V its v part — on the
    flash route; every call over the cache attends ABSORBED
    (ops/mla_attention.py): the decode step through the kernel, anything
    else through the `jnp` form. Scores scale by (nope + rope) ** -0.5."""
    from symmetry_tpu.ops import mla_attention as mla

    la = config.latent
    B, S, _ = x.shape
    H, R = config.num_heads, la.rank
    rope = dict(theta=config.rope_theta, interleaved=la.rope_interleave)

    q = qmatmul(x, lp["wq"]).reshape(B, S, H, la.nope + la.rope)
    q_nope = q[..., :la.nope]
    q_pe = apply_rope(q[..., la.nope:], positions, **rope)
    ckr = qmatmul(x, lp["wkva"])                          # [B, S, R + rope]
    c_n = rms_norm(ckr[..., :R], _norm_w(lp["kv_norm"], config),
                   config.rms_eps)
    k_r = apply_rope(ckr[:, :, None, R:], positions, **rope)  # [B, S, 1, r]
    cache = write_latent(cache, layer, positions,
                         jnp.concatenate([c_n, k_r[:, :, 0]], axis=-1))
    scale = (la.nope + la.rope) ** -0.5
    paths = attention_paths(config, cache.k.shape[2], batch=cache.k.shape[1],
                            kv_bytes=cache.k.dtype.itemsize)
    if prefill_flash and paths["prefill"] != "xla":
        from symmetry_tpu.ops import flash

        kv = qmatmul(c_n, lp["wkvb"]).reshape(B, S, H, la.nope + la.v)
        k = jnp.concatenate(
            [kv[..., :la.nope], jnp.broadcast_to(k_r, (B, S, H, la.rope))],
            axis=-1)
        # (tiles of 512: 32 heads over 6-9k tokens in 128 x 128 tiles is
        # bound by the walk's turns, 11% of the MXU's peak against 39-41%
        # — tools/flash_tile_ab.py on the chip, PERF.md PR 54)
        attn = flash.flash_prefill_wide(
            jnp.concatenate([q_nope, q_pe], axis=-1), k, kv[..., la.nope:],
            seq_lens, interpret=interpret_mode())
    else:
        # (zeros against the row's padding lanes)
        q_abs = jnp.concatenate(
            [jnp.einsum("bshd,hdr->bshr", q_nope, lp["wuk"]),
             q_pe, jnp.zeros((B, S, H, la.lanes - la.row), x.dtype)],
            axis=-1)                                      # [B, S, H, lanes]
        if S == 1 and paths["decode"] != "xla":
            o_lat = mla.mla_decode(q_abs[:, 0], cache.k, layer, kv_valid,
                                   scale=scale, rank=R,
                                   interpret=interpret_mode())[:, None]
        else:
            rows = jax.lax.dynamic_index_in_dim(cache.k, layer, 0,
                                                keepdims=False)
            o_lat = mla.absorbed_attention(q_abs, rows, positions, kv_valid,
                                           scale, R)
        attn = jnp.einsum("bshr,hrd->bshd", o_lat, lp["wuv"])
    return qmatmul(attn.reshape(B, S, H * la.v), lp["wo"]), cache


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
    tp_mesh=None,
    rope_positions=None,
    stack=None,
    own_row: bool = False,
) -> tuple[jnp.ndarray, KVCache, tuple | None]:
    """One decoder layer of the homogeneous stack: attention, then the FFN
    (dense or routed experts); arguments as `_attention`'s, and `stack` as
    `moe_mlp`'s -> (h, cache, the position's K/V row where the caller
    appends it: `own_row`)."""
    x = rms_norm(h, _norm_w(lp["attn_norm"], config), config.rms_eps)
    attn, cache, *row = _attention(x, lp, cache, layer, positions, kv_valid,
                                   seq_lens, config, prefill_flash,
                                   tp_mesh=tp_mesh,
                                   rope_positions=rope_positions,
                                   own_row=own_row)
    h = h + attn

    x = rms_norm(h, _norm_w(lp["mlp_norm"], config), config.rms_eps)
    if "router" in lp:
        from symmetry_tpu.models.moe import moe_mlp

        y, pairs = moe_mlp(x, lp, config, seq_lens, tp_mesh, stack)
        h = h + y
        cache = add_expert_pairs(cache, pairs, config)
    else:
        h = h + qmatmul(_act(qmatmul(x, lp["wg"]), config)
                        * qmatmul(x, lp["wu"]), lp["wd"])
    return h, cache, row[0] if row else None


def add_expert_pairs(cache: KVCache, pairs: jnp.ndarray,
                     config) -> KVCache:
    """`cache` with one layer's valid (token, expert) pairs added to its
    counter, where it carries one: a count an expert routed over, and —
    from a model that holds a share (models/moe.py _count_pairs) — the
    held experts hit behind them, which goes to HELD_COUNTS' word in the
    tail (the other rows' words are left alone)."""
    if cache.expert_pairs is None:
        return cache
    vector, n = cache.expert_pairs, config.num_experts
    extra = vector.shape[0] - pairs.shape[0]
    if pairs.shape[0] > n and extra:
        # the hits' word does not end the vector: each part to its place
        return cache._replace(expert_pairs=tail_add(
            vector.at[:n].add(pairs[:n]), config, HELD_COUNTS, pairs[n:]))
    return cache._replace(expert_pairs=vector + (
        jnp.pad(pairs, (0, extra)) if extra else pairs))


def _act(x: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    """Gated-MLP activation: silu (llama/mistral/qwen), tanh-approx gelu
    (gemma's GeGLU) or relu (smallthinker's ReGLU)."""
    if config.hidden_act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if config.hidden_act == "relu":
        return jax.nn.relu(x)
    if config.hidden_act == "relu2":
        return jnp.square(jax.nn.relu(x))
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

    With `config.diffusion`, a forward of exactly one block over the cache
    starts on a block boundary (`cache.lengths % block == 0`: the engine
    commits whole blocks), so its rows share one key set and may take the
    decode kernel (`attention_paths`).

    prefill_flash=True routes attention through the Pallas flash kernel.
    VALID ONLY when cache.lengths are all zero (engine prefill's case) —
    the kernel attends to this call's own K/V, not the cache.
    Sliding-window models (mistral-v0.1) use the window-bounded flash
    kernel for prefill.
    """
    if getattr(config, "layer_types", None):
        from symmetry_tpu.models import hybrid

        if tp_mesh is not None:
            raise ValueError("a model with recurrent layers runs on one "
                             "device: no tp_mesh")
        return hybrid.forward_hidden(params, config, tokens, cache, seq_lens,
                                     prefill_flash=prefill_flash)
    B, S = tokens.shape
    if seq_lens is None:
        seq_lens = jnp.full((B,), S, jnp.int32)
    positions = cache.lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    kv_valid = cache.lengths + seq_lens
    # (flash prefill handles sliding windows natively: a window-bounded
    # block range)
    use_flash = prefill_flash and S > 1

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
                              use_flash=use_flash, tp_mesh=tp_mesh,
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
    tp_mesh=None,
    rope_positions=None,
) -> tuple[jnp.ndarray, KVCache]:
    """Scan a stack of decoder layers over `h`; layer indices inside are
    local to the stack passed in, whose leading dim is the cache's.

    Where `attention_paths` says `kv_append: "step"`, a forward of one
    position a slot WRITES NO CACHE inside the scan: the K/V leaves are its
    constants, the scale planes ride the carry untouched, each layer's row
    leaves as the scan's `ys` (4 MB a step at mistral-7b's cell) and
    `append_step` writes all of them behind it, once."""
    stacks = None
    if "router" in layers_params:
        from symmetry_tpu.models.moe import whole_stacks

        stacks = whole_stacks(layers_params, tp_mesh)
    by_step = h.shape[1] == 1 and attention_paths(
        config, cache.k.shape[2], tp_mesh, batch=cache.k.shape[1],
        kv_bytes=cache.k.dtype.itemsize).get("kv_append") == "step"

    def bare(c):
        # by step the scan's carry is the cache WITHOUT its K/V leaves: the
        # layers read those as the scan's constants (the scale planes stay,
        # handed through each kernel call: an operand XLA's fast memory
        # could hold must not be a constant of the loop)
        return c._replace(k=None, v=None) if by_step else c

    def whole(c):
        return c._replace(k=cache.k, v=cache.v) if by_step else c

    def body(carry, xs):
        # Every other forward's cache rides the CARRY, scatter-updated in
        # place a layer (`write_kv`): scan xs/ys would stream the full
        # [L, B, T, K, D] arrays through HBM every forward — at decode that
        # re-writes ~0.5 GB per token.
        h, c = carry
        lp, l = xs
        h, c, row = _layer(h, lp, whole(c), l, positions, kv_valid,
                           seq_lens, config, use_flash, tp_mesh=tp_mesh,
                           rope_positions=rope_positions,
                           stack=stacks and (stacks, l), own_row=by_step)
        return (h, bare(c)), row

    n_layers = jax.tree.leaves(layers_params)[0].shape[0]
    (h, new_cache), rows = jax.lax.scan(
        body, (h, bare(cache)),
        (layers_params, jnp.arange(n_layers, dtype=jnp.int32)))
    if by_step:
        new_cache = append_step(whole(new_cache), positions[:, 0], rows)
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
# `wqi` / `wki` are the sparse attention's index projections, `wkva` /
# `wkvb` the latent attention's down- and up-projection (its absorbed
# factors `wuk` / `wuv` are derived from `wkvb` and held in bfloat16).
QUANT_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "in_proj",
              "out_proj", "sg", "su", "sd", "wqi", "wki", "wkva", "wkvb",
              "lm_head")
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


def hf_config_diffusion(config: MoEConfig) -> dict:
    """A block-diffusion config as its published `config.json` keys (what
    `config_from_hf` reads back, and what the plain reference —
    `benchmarks/reference/block_diffusion_moe_decoder.py` — is given).
    `block_length` and `mask_token_id` are not keys of the published file:
    they are the generation settings, carried here so one dict says all."""
    c = config
    return {
        "model_type": "sdar_moe", "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads, "head_dim": c.dim_per_head,
        "moe_intermediate_size": c.intermediate_size,
        "num_experts": c.num_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "rope_theta": c.rope_theta,
        "rope_scaling": None, "rms_norm_eps": c.rms_eps,
        "max_position_embeddings": c.max_position,
        "tie_word_embeddings": c.tie_embeddings,
        "attention_bias": c.attention_bias, "use_sliding_window": False,
        "block_length": c.diffusion.block,
        "mask_token_id": c.diffusion.mask_token_id,
    }


def hf_config_latent(config: "HybridConfig") -> dict:
    """A latent-attention config as its published `config.json` keys (HF
    `deepseek_v3`: what `config_from_hf` reads back, and what the plain
    reference — `benchmarks/reference/latent_moe_decoder.py` — is given)."""
    c, la = config, config.latent
    return {
        "model_type": "deepseek_v3", "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads, "head_dim": la.rope,
        "q_lora_rank": None, "kv_lora_rank": la.rank,
        "qk_nope_head_dim": la.nope, "qk_rope_head_dim": la.rope,
        "qk_head_dim": la.nope + la.rope, "v_head_dim": la.v,
        "rope_interleave": la.rope_interleave,
        "intermediate_size": c.dense_intermediate_size,
        "moe_intermediate_size": c.intermediate_size,
        "first_k_dense_replace": c.num_dense_layers, "moe_layer_freq": 1,
        "n_routed_experts": c.num_experts,
        "n_shared_experts": (c.shared_intermediate_size
                             // c.intermediate_size),
        "num_experts_per_tok": c.num_experts_per_tok,
        "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
        "scoring_func": "sigmoid", "norm_topk_prob": True,
        "routed_scaling_factor": c.routed_scaling_factor,
        "hidden_act": "silu", "attention_bias": False,
        "rope_theta": c.rope_theta, "rope_scaling": None,
        "rms_norm_eps": c.rms_eps,
        "max_position_embeddings": c.max_position,
        "tie_word_embeddings": c.tie_embeddings,
    }


def _latent_config_from_hf(hf: dict[str, Any]) -> "HybridConfig":
    """`config_from_hf` of HF `deepseek_v3`. What would change the layer
    and is not built raises, in a sentence, rather than being ignored."""
    if hf.get("q_lora_rank") is not None:
        raise ValueError(
            f"deepseek_v3 with q_lora_rank {hf['q_lora_rank']}: a query "
            f"latent (q_a_proj, q_a_layernorm, q_b_proj) is not implemented")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError(
            f"deepseek_v3 with n_group {hf.get('n_group')} / topk_group "
            f"{hf.get('topk_group')}: group-limited routing is not "
            f"implemented (one group alone, where the limit is the identity)")
    if hf.get("rope_scaling") is not None:
        raise ValueError(
            "deepseek_v3 with rope_scaling: rotary scaling by frequency "
            "bands (and its mscale on the softmax scale) is not implemented")
    if hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(
            f"deepseek_v3 with topk_method {hf['topk_method']!r}: only "
            f"noaux_tc (selection by score + e_score_correction_bias) is "
            f"implemented")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(
            f"deepseek_v3 with scoring_func {hf['scoring_func']!r}: only "
            f"the sigmoid router is implemented")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("deepseek_v3 with norm_topk_prob false is not "
                         "implemented")
    if hf.get("moe_layer_freq", 1) != 1:
        raise ValueError("deepseek_v3 with moe_layer_freq other than 1 is "
                         "not implemented")
    if hf.get("attention_bias"):
        raise ValueError("deepseek_v3 with attention_bias is not "
                         "implemented")
    n_layers, n_dense = hf["num_hidden_layers"], hf.get(
        "first_k_dense_replace", 0)
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    return HybridConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=n_layers, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads",
                            hf["num_attention_heads"]),
        # the routed expert's width; `intermediate_size` is the leading
        # dense layers'
        intermediate_size=hf["moe_intermediate_size"],
        # the published key: the rotary width, which sizes no matrix here
        # (a head's q and k are nope + rope wide: `latent`)
        head_dim=hf.get("head_dim", rope),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        max_position=hf.get("max_position_embeddings", 4096),
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        shared_intermediate_size=(hf.get("n_shared_experts", 0)
                                  * hf["moe_intermediate_size"]),
        latent=LatentAttention(
            rank=hf["kv_lora_rank"], rope=rope, nope=nope,
            v=hf["v_head_dim"],
            rope_interleave=bool(hf.get("rope_interleave", True))),
        layer_types=("latent_attention",) * n_layers,
        num_dense_layers=n_dense,
        dense_intermediate_size=hf["intermediate_size"] if n_dense else 0,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        router_norm_eps=1e-20,
    )


def hf_config_window(config: "HybridConfig") -> dict:
    """A window / full attention config as its published `config.json` keys
    (PowerInfer `smallthinker`: what `config_from_hf` reads back, and what
    the plain reference — `benchmarks/reference/swa_moe_decoder.py` — is
    given)."""
    c = config
    return {
        "model_type": "smallthinker", "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads, "head_dim": c.dim_per_head,
        "moe_ffn_hidden_size": c.intermediate_size,
        "moe_num_primary_experts": c.num_experts,
        "moe_num_active_primary_experts": c.num_experts_per_tok,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "sliding_window_size": c.sliding_window,
        "sliding_window_layout": [int(t == "sliding_attention")
                                  for t in c.layer_types],
        "rope_layout": [int(c.layer_rope(i)) for i in range(c.num_layers)],
        "rope_theta": c.rope_theta, "rope_scaling": None,
        "rms_norm_eps": c.rms_eps,
        "max_position_embeddings": c.max_position,
        "tie_word_embeddings": c.tie_embeddings,
    }


def _window_config_from_hf(hf: dict[str, Any]) -> "HybridConfig":
    """`config_from_hf` of PowerInfer `smallthinker`. The two layouts are
    taken as given — any pattern of window / full layers, a rotary choice a
    layer that need not follow it; what would change the layer and is not
    built raises, in a sentence, rather than being ignored."""
    if hf.get("rope_scaling") is not None:
        raise ValueError("smallthinker with rope_scaling is not implemented")
    if not hf.get("moe_primary_router_apply_softmax", True):
        raise ValueError(
            "smallthinker with moe_primary_router_apply_softmax false (gates "
            "by sigmoid of the selected logits) is not implemented")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("smallthinker with norm_topk_prob false is not "
                         "implemented")
    if hf.get("moe_num_secondary_experts") or hf.get(
            "moe_enable_secondary_experts"):
        raise ValueError("smallthinker with secondary experts is not "
                         "implemented (an expert is one dense ReGLU FFN)")
    n_layers = hf["num_hidden_layers"]
    sliding = [int(bool(x)) for x in (hf.get("sliding_window_layout")
                                      or [0] * n_layers)]
    rope = [int(bool(x)) for x in (hf.get("rope_layout") or sliding)]
    if len(sliding) != n_layers or len(rope) != n_layers:
        raise ValueError(
            f"smallthinker: sliding_window_layout ({len(sliding)}) and "
            f"rope_layout ({len(rope)}) must name {n_layers} layers each")
    return HybridConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=n_layers, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads",
                            hf["num_attention_heads"]),
        # the expert's width: there is no dense FFN
        intermediate_size=hf["moe_ffn_hidden_size"],
        head_dim=hf.get("head_dim"),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        max_position=hf.get("max_position_embeddings", 16384),
        sliding_window=(hf["sliding_window_size"] if any(sliding) else None),
        hidden_act="relu",
        num_experts=hf["moe_num_primary_experts"],
        num_experts_per_tok=hf["moe_num_active_primary_experts"],
        layer_types=tuple("sliding_attention" if w else "full_attention"
                          for w in sliding),
        rope_layout=tuple(rope), router_input="layer_input",
    )


def hf_config_exaone(config: "HybridConfig") -> dict:
    """A K-EXAONE config as its published `config.json` keys (HF
    `exaone_moe`: what `_exaone_config_from_hf` reads back, and what the
    plain reference — benchmarks/reference/exaone_moe_decoder.py — is
    given). Where a share of the experts is held, `num_experts` is the
    HELD count — the benchmark configuration file's convention — beside
    `experts_routed_over` and `experts_held`, keys of ours that a published
    config.json lacks."""
    c = config
    window = [c.sliding_window if t == "sliding_attention" else 0
              for t in c.layer_types]
    out = {
        "model_type": "exaone_moe", "vocab_size": c.vocab_size,
        "hidden_size": c.hidden_size, "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads, "head_dim": c.dim_per_head,
        "hidden_act": c.hidden_act,
        "intermediate_size": c.dense_intermediate_size,
        "moe_intermediate_size": c.intermediate_size,
        "first_k_dense_replace": c.num_dense_layers,
        "mlp_layer_types": ["dense" if c.ffn_kind(i) == "dense" else "sparse"
                            for i in range(c.num_layers)],
        "layer_types": list(c.layer_types),
        "sliding_window": c.sliding_window, "sliding_windows": window,
        "num_experts": c.experts_here,
        "num_experts_per_tok": c.num_experts_per_tok,
        "num_shared_experts": 1, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "scoring_func": c.router_score,
        "routed_scaling_factor": c.routed_scaling_factor,
        "num_nextn_predict_layers": c.mtp_layers,
        "mtp_layer_types": ["full_attention"] * c.mtp_layers,
        "mtp_sliding_windows": [0] * c.mtp_layers,
        "rope_parameters": {"rope_theta": c.rope_theta,
                            "rope_type": "default"},
        "rms_norm_eps": c.rms_eps,
        "max_position_embeddings": c.max_position,
        "tie_word_embeddings": c.tie_embeddings,
    }
    if c.experts_held is not None:
        out["experts_routed_over"] = c.num_experts
        out["experts_held"] = list(c.experts_held)
    return out


def _exaone_config_from_hf(hf: dict[str, Any]) -> "HybridConfig":
    """HF `exaone_moe` (K-EXAONE-236B-A23B): window (rotary) and full
    (NoPE) attention layers by `layer_types`, q and k normed per head,
    `first_k_dense_replace` leading dense layers, then experts chosen by
    deepseek_v3's sigmoid router at one group plus one shared expert of
    the expert's width, and `num_nextn_predict_layers` multi-token-
    prediction modules. What would change the layer and is not built
    raises, in a sentence. `experts_routed_over` / `experts_held` as
    nemotron_h's (a benchmark configuration's share of a deployment)."""
    n_layers = hf["num_hidden_layers"]
    types = tuple(hf["layer_types"])
    ffns = list(hf.get("mlp_layer_types")
                or ["dense"] * hf.get("first_k_dense_replace", 0)
                + ["sparse"] * (n_layers - hf.get("first_k_dense_replace",
                                                  0)))
    n_dense = ffns.count("dense")
    if ffns != ["dense"] * n_dense + ["sparse"] * (n_layers - n_dense):
        raise ValueError("exaone_moe with dense layers that do not lead "
                         f"is not implemented; got {ffns!r}")
    for key, want in (("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                      ("n_group", 1), ("topk_group", 1),
                      ("num_shared_experts", 1), ("norm_topk_prob", True)):
        if hf.get(key, want) != want:
            raise ValueError(f"exaone_moe with {key} {hf[key]!r} is not "
                             f"implemented")
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default" or hf.get(
            "rope_scaling"):
        raise ValueError("exaone_moe with a scaled rotary is not "
                         "implemented")
    mtp = hf.get("num_nextn_predict_layers", 0)
    if list(hf.get("mtp_layer_types") or ["full_attention"] * mtp) != [
            "full_attention"] * mtp:
        raise ValueError("exaone_moe with a multi-token-prediction module "
                         "that is no full-attention block is not "
                         "implemented")
    window = hf.get("sliding_window")
    windows = hf.get("sliding_windows")
    if windows is not None and list(windows) != [
            window if t == "sliding_attention" else 0 for t in types]:
        raise ValueError("exaone_moe with windows of several sizes is not "
                         "implemented")
    held = hf.get("experts_held")
    routed_over = hf.get("experts_routed_over", hf["num_experts"])
    if held is not None and held[1] != hf["num_experts"]:
        raise ValueError(f"experts_held {held!r} and num_experts "
                         f"{hf['num_experts']} (the count held) differ")
    return HybridConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=n_layers, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        # the routed (and the shared) expert's width
        intermediate_size=hf["moe_intermediate_size"],
        head_dim=hf.get("head_dim"),
        rope_theta=float(rope.get("rope_theta",
                                  hf.get("rope_theta", 10000.0))),
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        max_position=hf.get("max_position_embeddings", 8192),
        sliding_window=window if "sliding_attention" in types else None,
        qk_norm=True,
        num_experts=routed_over,
        num_experts_per_tok=hf["num_experts_per_tok"],
        experts_held=None if held is None else tuple(held),
        shared_intermediate_size=hf["moe_intermediate_size"],
        num_dense_layers=n_dense,
        dense_intermediate_size=hf["intermediate_size"] if n_dense else 0,
        layer_types=types,
        # rotary on the window layers alone (the EXAONE-4 family's hybrid
        # attention): a full layer has no positional embedding
        rope_layout=tuple(int(t == "sliding_attention") for t in types),
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        router_norm_eps=1e-20, mtp_layers=mtp,
    )


def hf_config_nemotron_h(config: "HybridConfig") -> dict:
    """The config as nemotron_h's published `config.json` keys (what
    `_nemotron_h_config_from_hf` reads back, and what the plain reference,
    benchmarks/reference/nemotron_h_decoder.py, is given). Where a share of
    the experts is held, `n_routed_experts` is the HELD count — the
    benchmark configuration file's convention — beside `experts_routed_over`
    and `experts_held`, keys of ours that a published config.json lacks."""
    c = config
    out = {
        "architectures": ["NemotronHForCausalLM"],
        "model_type": "nemotron_h",
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_blocks,
        "hybrid_override_pattern": blocks_of(c),
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads, "head_dim": c.dim_per_head,
        "intermediate_size": c.intermediate_size,
        "moe_intermediate_size": c.intermediate_size,
        "moe_shared_expert_intermediate_size": c.shared_intermediate_size,
        "n_routed_experts": c.experts_here, "n_shared_experts": 1,
        "num_experts_per_tok": c.num_experts_per_tok,
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": c.routed_scaling_factor,
        "mlp_hidden_act": c.hidden_act, "mamba_hidden_act": "silu",
        "mamba_num_heads": c.mamba_n_heads,
        "mamba_head_dim": c.mamba_d_head,
        "ssm_state_size": c.mamba_d_state, "conv_kernel": c.mamba_d_conv,
        "n_groups": c.mamba_n_groups, "chunk_size": c.mamba_chunk_size,
        "use_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "mlp_bias": False, "use_bias": False,
        "rope_theta": c.rope_theta, "norm_eps": c.rms_eps,
        "layer_norm_epsilon": c.rms_eps,
        "tie_word_embeddings": c.tie_embeddings,
        "max_position_embeddings": c.max_position,
    }
    if c.experts_held is not None:
        out["experts_routed_over"] = c.num_experts
        out["experts_held"] = list(c.experts_held)
    return out


def _nemotron_h_config_from_hf(hf: dict[str, Any]) -> "HybridConfig":
    """HF `nemotron_h` (NVIDIA-Nemotron-3-Nano): blocks of one sub-layer
    each, paired into trunk layers by `pair_blocks`; Mamba-2 with
    `n_groups` groups; GQA without rotary (`rope_theta` is carried and
    unused); ungated relu2 experts chosen by deepseek_v3's sigmoid router
    at one group, plus an ungated shared expert. `experts_routed_over` and
    `experts_held`, where the file has them (a benchmark configuration's
    share of a deployment; no published config.json does), say that
    `n_routed_experts` counts the experts held HERE."""
    for key, want in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act",
                                                    "silu")):
        if hf.get(key, want) != want:
            raise ValueError(f"nemotron_h with {key} {hf[key]!r} is not "
                             f"implemented")
    for key in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias"):
        if hf.get(key):
            raise ValueError(f"nemotron_h with {key} is not implemented")
    if not hf.get("use_conv_bias", True):
        raise ValueError("nemotron_h without use_conv_bias is not "
                         "implemented")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("nemotron_h with group-limited routing (n_group, "
                         "topk_group) is not implemented")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("nemotron_h with norm_topk_prob false is not "
                         "implemented")
    if hf.get("n_shared_experts", 1) != 1:
        raise ValueError("nemotron_h with other than one shared expert is "
                         "not implemented")
    if hf.get("time_step_limit"):
        raise ValueError("nemotron_h with a time_step_limit is not "
                         "implemented")
    pattern = hf["hybrid_override_pattern"]
    if len(pattern) != hf["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern names {len(pattern)} "
                         f"blocks, num_hidden_layers "
                         f"{hf['num_hidden_layers']}")
    types, ffns = pair_blocks(pattern)
    held = hf.get("experts_held")
    routed_over = hf.get("experts_routed_over", hf["n_routed_experts"])
    if held is not None and held[1] != hf["n_routed_experts"]:
        raise ValueError(f"experts_held {held!r} and n_routed_experts "
                         f"{hf['n_routed_experts']} (the count held) differ")
    return HybridConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        # the routed expert's width
        intermediate_size=hf["moe_intermediate_size"],
        head_dim=hf.get("head_dim"),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=hf.get("norm_eps", hf.get("layer_norm_epsilon", 1e-5)),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        max_position=hf.get("max_position_embeddings", 8192),
        hidden_act="relu2", gated_ffn=False,
        num_experts=routed_over,
        num_experts_per_tok=hf["num_experts_per_tok"],
        experts_held=None if held is None else tuple(held),
        shared_intermediate_size=hf["moe_shared_expert_intermediate_size"],
        layer_types=types, ffn_layout=ffns,
        mamba_n_heads=hf["mamba_num_heads"],
        mamba_d_head=hf["mamba_head_dim"],
        mamba_d_state=hf["ssm_state_size"],
        mamba_d_conv=hf.get("conv_kernel", 4),
        mamba_chunk_size=hf.get("chunk_size", 128),
        mamba_n_groups=hf.get("n_groups", 1), rope=False,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        router_norm_eps=1e-20,
    )


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
    if hf.get("model_type") == "deepseek_v3":
        return _latent_config_from_hf(hf)
    if hf.get("model_type") == "exaone_moe":
        return _exaone_config_from_hf(hf)
    if (hf.get("model_type") == "smallthinker"
            or str(hf.get("model_name", "")).startswith("smallthinker")):
        return _window_config_from_hf(hf)
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
    if hf.get("model_type") == "sdar_moe":
        # the Qwen3-MoE block under a block mask, generated by diffusion
        if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
            raise ValueError("sdar_moe with dense-MLP layers (mlp_only_layers,"
                             " decoder_sparse_step) is not implemented")
        if not hf.get("norm_topk_prob", True):
            raise ValueError("sdar_moe with norm_topk_prob false is not "
                             "implemented")
        if hf.get("rope_scaling"):
            raise ValueError("sdar_moe with rope_scaling is not implemented")
        return MoEConfig(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            intermediate_size=hf["moe_intermediate_size"],
            head_dim=hf.get("head_dim"),
            rope_theta=float(hf.get("rope_theta", 1000000.0)),
            rms_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            sliding_window=sliding,
            attention_bias=hf.get("attention_bias", False),
            max_position=hf.get("max_position_embeddings", 32768),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            qk_norm=True,
            diffusion=BlockDiffusion(
                block=hf.get("block_length", SDAR_BLOCK_LENGTH),
                mask_token_id=hf.get("mask_token_id", SDAR_MASK_TOKEN_ID)),
            hf_block="qwen3_moe", init_fan_in=True,
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
    if hf.get("model_type") == "nemotron_h":
        return _nemotron_h_config_from_hf(hf)
    if hf.get("model_type") == "granitemoehybrid":
        types = tuple(hf["layer_types"])
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
            mamba_n_groups=hf.get("mamba_n_groups", 1),
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
