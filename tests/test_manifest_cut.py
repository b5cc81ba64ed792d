"""Every benchmark configuration that was CUT (a non-empty `reduced`) is held
to its contract: the file is the program's preset, `published` holds the
source's value of every cut key, and `reduced` lists exactly the keys that
differ from the source — no more, no fewer. For a model in the guide's
catalog the source is its row there (where the catalog is installed).

`benchmarks/tests/test_manifest.py` compares every file with the preset too,
but it was written when no configuration was cut and ends in
`reduced == []`; this file is where a cut is checked (PERF.md, Open
questions)."""

import json
import os

import pytest

from symmetry_tpu.models.llama import preset

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(CHECKOUT, "benchmarks", "configs")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# widths may never be cut (the builder's contract on `reduced`)
WIDTH_ENDS = ("_size", "_dim", "_rank", "_d_head", "_d_state", "_d_conv",
              "_expand", "_n_heads", "experts_per_tok")


def load(name):
    with open(os.path.join(CONFIGS, name)) as fh:
        return json.load(fh)


def cut_files():
    return sorted(f for f in os.listdir(CONFIGS) if load(f).get("reduced"))


def test_there_is_a_cut_configuration_to_hold():
    assert "granite-4.0-h-small.json" in cut_files()
    assert "qwen3-next-80b-a3b.json" in cut_files()
    assert "keye-vl-2.0-30b-a3b.json" in cut_files()
    assert "sdar-30b-a3b-chat.json" in cut_files()
    assert "kanana-2-30b-a3b.json" in cut_files()
    assert "smallthinker-21b-a3b.json" in cut_files()
    assert "nemotron-3-nano-30b-a3b.json" in cut_files()
    assert "k-exaone-236b-a23b.json" in cut_files()


@pytest.mark.parametrize("name", cut_files())
def test_reduced_lists_exactly_the_keys_that_differ_from_published(name):
    c = load(name)
    published = c["published"]
    assert sorted(published) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert c[key] != published[key], key
        # (`vocab_size` counts rows, as `num_experts` counts experts: a
        # chip's slice of the vocabulary is a share, not a width)
        assert key == "vocab_size" or not key.endswith(WIDTH_ENDS), key
    manifest = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"]
                 if e["file"] == f"benchmarks/configs/{name}")
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    for key in ("assumed", "deployment"):
        assert c[key], key


@pytest.mark.parametrize("name", cut_files())
def test_a_cut_keeps_a_whole_period_of_the_published_pattern(name):
    c = load(name)
    if "full_attention_interval" in c:
        # the pattern is the interval's (qwen3_next): a whole number of
        # periods, at least one and at least four layers
        every = c["full_attention_interval"]
        assert c["num_hidden_layers"] % every == 0
        assert c["published"]["num_hidden_layers"] % every == 0
        assert c["num_hidden_layers"] >= max(4, every)
        assert list(preset(c["tpu"]["model_preset"]).layer_types) == [
            "full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(c["num_hidden_layers"])]
        return
    if c.get("model_type") == "KeyeVL2":
        # 48 identical layers: a period is one layer, and four is the
        # floor for the layers kept
        assert c["num_hidden_layers"] == 4
        assert c["published"]["num_hidden_layers"] == 48
        assert c["mlp_only_layers"] == [] and c["decoder_sparse_step"] == 1
        return
    if c.get("model_type") == "sdar_moe":
        # 48 identical layers: a period is one layer; twelve are kept, a
        # quarter of the model (stage 1 of 4), over the floor of four
        assert c["num_hidden_layers"] == 12
        assert c["published"]["num_hidden_layers"] == 48
        assert c["mlp_only_layers"] == [] and c["decoder_sparse_step"] == 1
        return
    if c.get("model_type") == "deepseek_v3":
        # a leading dense layer, then 47 identical expert layers: the dense
        # layer is kept with seven expert layers after it (over the floor of
        # four), a sixth of the model (stage 1 of 6)
        assert c["num_hidden_layers"] == 8
        assert c["published"] == {"num_hidden_layers": 48}
        assert c["first_k_dense_replace"] == 1 and c["moe_layer_freq"] == 1
        assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
        return
    if str(c.get("model_name", "")).startswith("smallthinker"):
        # the pattern is the two layouts' (full, window, window, window):
        # three whole periods of the published thirteen, both layouts cut
        # alike and in the published order
        assert c["num_hidden_layers"] == 12
        for key in ("sliding_window_layout", "rope_layout"):
            full, kept = c["published"][key], c[key]
            assert len(kept) == 12 and len(full) == 52
            assert full[:12] == kept == [0, 1, 1, 1] * 3
            assert full == [0, 1, 1, 1] * 13
        assert c["published"]["num_hidden_layers"] == 52
        return
    if c.get("model_type") == "nemotron_h":
        # NOT cut in depth: all 52 published blocks, the pattern as
        # published; the cut is the chip's share of each expert block
        assert c["reduced"] == ["n_routed_experts"]
        assert len(c["hybrid_override_pattern"]) == c["num_hidden_layers"] \
            == 52
        assert (c["n_routed_experts"], c["published"]) == (
            32, {"n_routed_experts": 128})
        assert c["n_routed_experts"] >= 8   # the guide's floor for a share
        return
    if "layer_types" not in c["reduced"]:
        pytest.skip("no layer pattern was cut")
    full, kept = c["published"]["layer_types"], c["layer_types"]
    assert len(kept) == c["num_hidden_layers"] >= 4
    assert len(full) == c["published"]["num_hidden_layers"]
    assert full[:len(kept)] == kept                 # the published order
    period = len(kept)
    assert len(full) % period == 0
    # the cut is a whole period: the pattern repeats with the same count of
    # each kind in every stretch of that length
    for start in range(0, len(full), period):
        stretch = full[start:start + period]
        assert sorted(stretch) == sorted(kept), (start, stretch)


@pytest.mark.parametrize("name", cut_files())
def test_a_cut_file_is_the_programs_preset(name):
    c = load(name)
    p = preset(c["tpu"]["model_preset"])
    # a qwen3_next file's `intermediate_size` is the dense width no layer
    # uses; the preset's is the routed expert's
    width = c.get("moe_intermediate_size", c.get(
        "moe_ffn_hidden_size", c.get("intermediate_size")))
    assert (p.vocab_size, p.hidden_size, p.num_layers, p.num_heads,
            p.num_kv_heads, p.intermediate_size, p.dim_per_head) == (
        c["vocab_size"], c["hidden_size"], c["num_hidden_layers"],
        c["num_attention_heads"], c["num_key_value_heads"],
        width, c["head_dim"])
    # (the eps key is the family's own: nemotron_h's is `norm_eps`)
    # (exaone_moe nests its rotary keys: `rope_parameters`)
    assert p.rope_theta == c.get(
        "rope_theta", (c.get("rope_parameters") or {}).get("rope_theta"))
    assert p.rms_eps == c.get("rms_norm_eps", c.get("norm_eps"))
    assert p.tie_embeddings == c["tie_word_embeddings"]
    if c.get("model_type") == "nemotron_h":
        from symmetry_tpu.models.llama import (
            blocks_of, config_from_hf, pair_blocks)

        # every published key the program reads, through its own reader: the
        # file IS the preset — every width as published, the depth whole, and
        # the share stated by keys of the file's own
        assert config_from_hf(c) == p
        assert c["reduced"] == ["n_routed_experts"]
        assert p.num_layers == p.num_blocks == c["num_hidden_layers"] == 52
        assert blocks_of(p) == c["hybrid_override_pattern"]
        assert (p.layer_types, p.ffn_layout) == pair_blocks(
            c["hybrid_override_pattern"])
        assert len(p.layer_types) == 29 and p.ffn_layout.count("none") == 6
        assert (p.num_experts, p.experts_held, p.num_experts_per_tok) == (
            c["experts_routed_over"], tuple(c["experts_held"]),
            c["num_experts_per_tok"]) == (128, (0, 32), 6)
        assert c["n_routed_experts"] == c["experts_held"][1] == 32
        assert c["published"] == {"n_routed_experts": 128}
        assert (p.intermediate_size, p.shared_intermediate_size) == (
            c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"]) == (1856, 3712)
        assert (p.hidden_act, p.gated_ffn) == (c["mlp_hidden_act"], False)
        assert (p.mamba_n_heads, p.mamba_d_head, p.mamba_d_state,
                p.mamba_d_conv, p.mamba_chunk_size, p.mamba_n_groups) == (
            c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"],
            c["conv_kernel"], c["chunk_size"], c["n_groups"]) == (
            64, 64, 128, 4, 128, 8)
        assert (p.router_score, p.router_bias, p.routed_scaling_factor,
                p.router_norm_eps) == ("sigmoid", True, 2.5, 1e-20)
        assert (c["n_group"], c["topk_group"], c["norm_topk_prob"]) == (
            1, 1, True)
        assert p.rope is False and p.vocab_size == 131072
        assert p.max_position == c["max_position_embeddings"] == 262144
        tpu = c["tpu"]
        assert (tpu["max_batch_size"], tpu["max_seq_len"],
                tpu["decode_block"]) == (64, 640, 16)
        assert tpu["prefill_buckets"] == [64, 128, 256]
        assert tpu["prefill_chunk"] is None
        assert (tpu["quantization"], tpu["kv_quantization"],
                tpu["dtype"]) == ("int8", "int8", "bfloat16")
        assert (c["decode_program"], c["prefill_program"]) == (
            "decode_block", "prefill")
        assert c["reference"].endswith("nemotron_h_decoder.py")
        assert os.path.exists(os.path.join(CHECKOUT, c["reference"]))
        # every `assumed` item the issue lists is stated
        text = " ".join(c["assumed"])
        for word in ("float32", "bfloat16", "no rotary", "unclamped",
                     "NOT renormalised", "512 channels", "relu(x W_up)^2",
                     "640", "int8", "byte tokenizer", "experts_held",
                     "e_score_correction_bias", "fan_in"):
            assert word in text, word
        for word in ("four-chip", "32 a chip", "replicated", "9.924 GB",
                     "3.141 GB", "78.0%", "quarter of the rows"):
            assert word in c["deployment"], word
    if c.get("model_type") == "exaone_moe":
        from symmetry_tpu.models.llama import config_from_hf

        # every published key the program reads, through its own reader:
        # every width as published; the cuts are the depth (three whole
        # periods, the leading dense layer among them), this chip's share
        # of the experts and its slice of the vocabulary
        assert config_from_hf(c) == p
        assert c["reduced"] == [
            "num_hidden_layers", "layer_types", "mlp_layer_types",
            "sliding_windows", "num_experts", "vocab_size"]
        assert (c["published"]["num_hidden_layers"],
                c["published"]["num_experts"],
                c["published"]["vocab_size"]) == (48, 128, 153600)
        assert c["layer_types"] == (["sliding_attention"] * 3
                                    + ["full_attention"]) * 3
        assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 11
        assert c["sliding_windows"] == [128, 128, 128, 0] * 3
        assert (p.num_experts, p.experts_held, p.num_experts_per_tok) == (
            c["experts_routed_over"], tuple(c["experts_held"]),
            c["num_experts_per_tok"]) == (128, (0, 16), 8)
        assert c["num_experts"] == c["experts_held"][1] == 16 >= 8
        assert c["published"]["vocab_size"] == 8 * c["vocab_size"]
        assert (p.intermediate_size, p.shared_intermediate_size,
                p.dense_intermediate_size, p.num_dense_layers) == (
            c["moe_intermediate_size"], c["moe_intermediate_size"],
            c["intermediate_size"], c["first_k_dense_replace"]) == (
            2048, 2048, 18432, 1)
        assert (p.sliding_window, p.qk_norm, p.mtp_layers) == (
            c["sliding_window"], True, c["num_nextn_predict_layers"]) == (
            128, True, 1)
        assert p.rope_layout == (1, 1, 1, 0) * 3
        assert (p.router_score, p.router_bias, p.routed_scaling_factor,
                p.router_norm_eps) == ("sigmoid", True, 2.5, 1e-20)
        assert p.max_position == c["max_position_embeddings"] == 262144
        tpu = c["tpu"]
        assert (tpu["max_batch_size"], tpu["max_seq_len"],
                tpu["decode_block"], tpu["speculative"]) == (
            64, 5376, 16, "mtp")
        assert tpu["prefill_buckets"] == [128, 384, 512, 768, 1152]
        assert tpu["prefill_chunk"] is None
        assert (tpu["quantization"], tpu["kv_quantization"],
                tpu["dtype"]) == ("int8", "int8", "bfloat16")
        assert c["reference"].endswith("exaone_moe_decoder.py")
        assert os.path.exists(os.path.join(CHECKOUT, c["reference"]))
        # every `assumed` item the issue lists is stated
        text = " ".join(c["assumed"])
        for word in ("pre-norm", "RMS-normed per head", "WINDOW layers alone",
                     "e_score_correction_bias", "NOT renormalised",
                     "hidden state FIRST", "AFTER its final norm",
                     "sparse layer like layer 47", "about zero", "5376",
                     "int8", "byte tokenizer", "experts_held", "fan_in",
                     "256 rows"):
            assert word in text, word
        for word in ("eight v5e chips", "16 a chip", "replicated",
                     "9.971 GB", "2.907 GB", "78.0%",
                     "an eighth of the rows", "1/13", "1/49"):
            assert word in c["deployment"], word
    if c.get("model_type") == "qwen3_next":
        from symmetry_tpu.models.llama import config_from_hf

        # every published key the program reads, through its own reader
        assert config_from_hf(c) == p
        assert (p.num_experts, p.num_experts_per_tok,
                p.shared_intermediate_size) == (
            c["num_experts"], c["num_experts_per_tok"],
            c["shared_expert_intermediate_size"]) == (512, 10, 512)
        assert (p.linear_num_key_heads, p.linear_key_head_dim,
                p.linear_num_value_heads, p.linear_value_head_dim,
                p.linear_conv_kernel_dim) == (
            c["linear_num_key_heads"], c["linear_key_head_dim"],
            c["linear_num_value_heads"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"])
        assert p.partial_rotary_factor == c["partial_rotary_factor"]
        assert p.max_position == c["max_position_embeddings"]
        assert c["mlp_only_layers"] == [] and c["norm_topk_prob"] is True
    if c.get("model_type") == "KeyeVL2":
        from symmetry_tpu.models.llama import config_from_hf

        # every published key the program reads, through its own reader
        assert config_from_hf(c) == p
        assert (p.num_experts, p.num_experts_per_tok) == (
            c["num_experts"], c["num_experts_per_tok"]) == (128, 8)
        assert c["num_local_experts"] == c["num_experts"]
        sa = c["sa_config"]
        assert (p.sparse.topk, p.sparse.index_heads,
                p.sparse.index_head_dim) == (
            sa["topk"], sa["indexer_num_heads"], sa["indexer_head_dim"]) == (
            2048, 16, 64)
        assert sa["indexer_num_kv_heads"] == 1
        assert list(p.mrope_section) == c["rope_scaling"]["mrope_section"]
        assert sum(p.mrope_section) == c["head_dim"] // 2
        assert p.qk_norm and p.sliding_window is None
        assert p.max_position == c["max_position_embeddings"]
        assert c["tpu"]["max_seq_len"] == 16384
        assert c["tpu"]["prefill_chunk"] is None
        # every `assumed` item the issue lists is stated
        text = " ".join(c["assumed"])
        for word in ("RMSNorm", "normed hidden state", "temporal",
                     "top_k", "chunk_size", "FP8", "vision tower", "16384",
                     "byte tokenizer", "6144"):
            assert word in text, word
        assert "stage 1 of 12" in c["deployment"]
    if c.get("model_type") == "sdar_moe":
        from symmetry_tpu.models.llama import config_from_hf

        # every published key the program reads, through its own reader;
        # the block and the mask token are the preset's (`assumed`)
        assert config_from_hf(c) == p
        assert (p.num_experts, p.num_experts_per_tok) == (
            c["num_experts"], c["num_experts_per_tok"]) == (128, 8)
        assert (p.diffusion.block, p.diffusion.mask_token_id) == (4, 151669)
        assert p.diffusion.mask_token_id < c["vocab_size"]
        assert p.qk_norm and p.sliding_window is None and p.sparse is None
        assert p.max_position == c["max_position_embeddings"] == 32768
        tpu = c["tpu"]
        assert (tpu["max_batch_size"], tpu["max_seq_len"],
                tpu["decode_block"]) == (128, 640, 16)
        assert tpu["prefill_chunk"] is None
        assert (tpu["diffusion_steps"], tpu["diffusion_threshold"]) == (
            2, None)
        assert tpu["decode_block"] % p.diffusion.block == 0
        assert all(b % p.diffusion.block == 0
                   and b + p.diffusion.block <= tpu["max_seq_len"]
                   for b in tpu["prefill_buckets"])
        assert (c["decode_program"], c["prefill_program"]) == (
            "bd_decode_block", "bd_prefill")
        assert c["reference"].endswith("block_diffusion_moe_decoder.py")
        # every `assumed` item the issue lists is stated
        text = " ".join(c["assumed"])
        for word in ("block length 4", "151669", "unshifted", "RMSNorm",
                     "low_confidence_static", "low_confidence_dynamic",
                     "one position a step", "steps 2", "plane of their own",
                     "640", "byte tokenizer", "6144", "fan_in"):
            assert word in text, word
        assert "stage 1 of 4" in c["deployment"]
    if str(c.get("model_name", "")).startswith("smallthinker"):
        from symmetry_tpu.models.llama import config_from_hf

        # every published key the program reads, through its own reader: the
        # file IS the preset, and no width was cut
        assert config_from_hf(c) == p
        assert c["reduced"] == ["num_hidden_layers", "sliding_window_layout",
                                "rope_layout"]
        assert (p.num_experts, p.num_experts_per_tok) == (
            c["moe_num_primary_experts"],
            c["moe_num_active_primary_experts"]) == (64, 6)
        assert p.sliding_window == c["sliding_window_size"] == 4096
        assert [int(t == "sliding_attention") for t in p.layer_types] == \
            c["sliding_window_layout"]
        assert list(p.rope_layout) == c["rope_layout"]
        assert (p.hidden_act, p.router_input, p.shared_intermediate_size
                ) == ("relu", "layer_input", 0)
        assert p.recurrent_kind is None and p.attention_kinds == (
            "full_attention", "sliding_attention")
        assert p.vocab_size == 151936 and c["rope_scaling"] is None
        assert p.max_position == c["max_position_embeddings"] == 16384
        tpu = c["tpu"]
        assert (tpu["max_batch_size"], tpu["max_seq_len"],
                tpu["decode_block"]) == (64, 11776, 16)
        assert tpu["prefill_chunk"] is None
        assert (tpu["quantization"], tpu["kv_quantization"]) == (
            "int8", "int8")
        # every bucket a multiple of the flash kernel's least tile, the
        # cell's longest prompt (8,192 + 19 of template) inside the largest,
        # room in a slot for the longest answer and the lookahead, and a
        # capacity the decode kernel's 256-position block divides
        assert all(b % 128 == 0 for b in tpu["prefill_buckets"])
        assert max(tpu["prefill_buckets"]) >= 8192 + c["template_tokens"]
        assert tpu["max_seq_len"] >= 8192 + 19 + 3072 + 2 * 16
        assert tpu["max_seq_len"] % 256 == 0
        assert (c["decode_program"], c["prefill_program"]) == (
            "decode_block", "prefill")
        assert c["reference"].endswith("swa_moe_decoder.py")
        assert os.path.exists(os.path.join(CHECKOUT, c["reference"]))
        # every `assumed` item the issue lists is stated
        text = " ".join(c["assumed"])
        for word in ("before input_layernorm", "kv_pos > q_pos", "ReGLU",
                     "primary keys only", "11776", "16384",
                     "byte tokenizer", "primary_router", "fan_in"):
            assert word in text, word
        assert "(12, 12, 12, 16" in c["deployment"]
        assert "9.550 GB" in c["deployment"]
    if c.get("model_type") == "deepseek_v3":
        from symmetry_tpu.models.llama import LatentAttention, config_from_hf

        # every published key the program reads, through its own reader: the
        # file IS the preset, and no width was cut
        assert config_from_hf(c) == p
        assert c["reduced"] == ["num_hidden_layers"]
        assert p.latent == LatentAttention(
            rank=c["kv_lora_rank"], rope=c["qk_rope_head_dim"],
            nope=c["qk_nope_head_dim"], v=c["v_head_dim"],
            rope_interleave=c["rope_interleave"]) == LatentAttention(
            rank=512, rope=64, nope=128, v=128, rope_interleave=True)
        assert c["qk_head_dim"] == p.latent.nope + p.latent.rope == 192
        assert c["q_lora_rank"] is None and c["rope_scaling"] is None
        assert (p.num_experts, p.num_experts_per_tok) == (
            c["n_routed_experts"], c["num_experts_per_tok"]) == (128, 6)
        assert p.shared_intermediate_size == (
            c["n_shared_experts"] * c["moe_intermediate_size"]) == 1536
        assert (p.num_dense_layers, p.dense_intermediate_size) == (
            c["first_k_dense_replace"], c["intermediate_size"]) == (1, 6144)
        assert (p.router_score, p.router_bias, p.routed_scaling_factor,
                p.router_norm_eps) == ("sigmoid", True, 2.448, 1e-20)
        assert (c["n_group"], c["topk_group"], c["topk_method"]) == (
            1, 1, "noaux_tc")
        assert p.vocab_size == 128256
        assert p.max_position == c["max_position_embeddings"] == 32768
        assert set(p.layer_types) == {"latent_attention"}
        tpu = c["tpu"]
        assert (tpu["max_batch_size"], tpu["max_seq_len"],
                tpu["decode_block"]) == (64, 11776, 16)
        assert tpu["prefill_chunk"] is None
        assert tpu["kv_quantization"] is None
        assert tpu["quantization"] == "int8"
        # every bucket a multiple of the flash kernel's block, the cell's
        # longest prompt (9,216 + 19 of template) inside the largest, and
        # room in a slot for the longest answer and the lookahead
        assert all(b % 128 == 0 for b in tpu["prefill_buckets"])
        assert max(tpu["prefill_buckets"]) >= 9216 + c["template_tokens"]
        assert tpu["max_seq_len"] >= 9216 + 19 + 2048 + 2 * 16
        assert tpu["max_seq_len"] % 512 == 0
        assert (c["decode_program"], c["prefill_program"]) == (
            "decode_block", "prefill")
        assert c["reference"].endswith("latent_moe_decoder.py")
        # every `assumed` item the issue lists is stated
        text = " ".join(c["assumed"])
        for word in ("192 ** -0.5", "mscale", "rope_interleave",
                     "kv_a_layernorm", "1e-20", "bfloat16", "FP8", "11776",
                     "byte tokenizer", "128256", "kv_a_proj_with_mqa",
                     "e_score_correction_bias", "fan_in"):
            assert word in text, word
        assert "stage 1 of 6" in c["deployment"]
        assert "bfloat16" in c["deployment"] and "W_UK" in c["deployment"]
    if "layer_types" in c:
        assert list(p.layer_types) == c["layer_types"]
    if "layer_types" in c and "num_local_experts" in c:
        assert (p.num_experts, p.num_experts_per_tok,
                p.shared_intermediate_size) == (
            c["num_local_experts"], c["num_experts_per_tok"],
            c["shared_intermediate_size"])
        assert (p.mamba_n_heads, p.mamba_d_head, p.mamba_d_state,
                p.mamba_d_conv, p.mamba_chunk_size) == (
            c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_d_conv"], c["mamba_chunk_size"])
        assert c["mamba_n_groups"] == 1 and c["mamba_conv_bias"] is True
        assert c["mamba_expand"] * c["hidden_size"] == (
            p.mamba_n_heads * p.mamba_d_head)
        assert (p.embedding_multiplier, p.residual_multiplier,
                p.attention_multiplier, p.logits_scaling) == (
            c["embedding_multiplier"], c["residual_multiplier"],
            c["attention_multiplier"], c["logits_scaling"])
        assert p.rope is (c["position_embedding_type"] != "nope")


@pytest.mark.parametrize("name", cut_files())
def test_every_uncut_key_is_the_catalogs(name):
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not installed here")
    c = load(name)
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next((r for r in rows if r["source_url"] == c["source"]), None)
    if row is None:
        pytest.skip("not a catalog model")
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key


# ---------------------------------------------------------------------------
# lfm2-8b-a1b is NOT cut (`reduced == []`: all 24 layers, every expert, the
# whole vocabulary): held to the preset and to the catalog's row all the same
# (`benchmarks/tests/test_manifest.py` cannot hold a file whose
# `intermediate_size` is the dense layers' width and whose eps key is
# `norm_eps`).

LFM2 = "lfm2-8b-a1b.json"


def test_the_uncut_lfm2_file_is_the_programs_preset():
    from symmetry_tpu.models.llama import config_from_hf

    c = load(LFM2)
    p = preset(c["tpu"]["model_preset"])
    assert c["reduced"] == [] and "published" not in c
    assert LFM2 not in cut_files()
    # every published key the program reads, through its own reader
    assert config_from_hf(c) == p
    assert (p.vocab_size, p.hidden_size, p.num_layers, p.num_heads,
            p.num_kv_heads, p.dim_per_head) == (
        c["vocab_size"], c["hidden_size"], c["num_hidden_layers"],
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"])
    assert (p.intermediate_size, p.dense_intermediate_size,
            p.num_dense_layers) == (
        c["moe_intermediate_size"], c["intermediate_size"],
        c["num_dense_layers"]) == (1792, 7168, 2)
    assert (p.num_experts, p.num_experts_per_tok, p.router_score,
            p.router_bias, p.routed_scaling_factor) == (
        c["num_experts"], c["num_experts_per_tok"], "sigmoid",
        c["use_expert_bias"], c["routed_scaling_factor"]) == (
        32, 4, "sigmoid", True, 1)
    assert list(p.layer_types) == c["layer_types"]
    assert len(c["layer_types"]) == c["num_hidden_layers"] == 24
    assert p.conv_L_cache == c["conv_L_cache"] == 3
    assert c["conv_bias"] is False and c["norm_topk_prob"] is True
    assert p.rope_theta == c["rope_theta"] and p.rms_eps == c["norm_eps"]
    assert p.max_position == c["max_position_embeddings"] == 128000
    assert p.tie_embeddings is c["tie_embedding"] is True
    assert p.qk_norm and p.shared_intermediate_size == 0


def test_the_lfm2_file_states_its_source_its_assumptions_and_its_run():
    c = load(LFM2)
    manifest = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    entry = next(e for e in manifest["configs"]
                 if e["file"] == f"benchmarks/configs/{LFM2}")
    assert entry["reduced"] == [] == c["reduced"]
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    text = " ".join(c["assumed"])
    for word in ("head_dim 64", "q_layernorm", "tied", "bfloat16", "640",
                 "byte tokenizer", "expert_bias", "[-0.25, 0.25]"):
        assert word in text, word
    assert "whole model on one v5e chip" in c["deployment"]
    assert "nothing is cut" in c["deployment"]
    assert "expert_bias uniform in [-0.25, 0.25]" in c["weights"]
    assert c["reference"] == "benchmarks/reference/sconv_moe_decoder.py"
    assert os.path.exists(os.path.join(CHECKOUT, c["reference"]))
    tpu = c["tpu"]
    assert (tpu["quantization"], tpu["kv_quantization"], tpu["dtype"]) == (
        "int8", "int8", "bfloat16")
    assert (tpu["max_batch_size"], tpu["max_seq_len"],
            tpu["decode_block"]) == (128, 640, 16)
    assert tpu["prefill_buckets"] == [64, 128, 256]
    assert tpu["prefill_chunk"] is None and c["template_tokens"] == 19
    cell = next(w for w in manifest["workloads"]
                if w["config"] == "lfm2-8b-a1b")
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b.batch-closed", "batch-closed", 1)


def test_every_lfm2_key_is_the_catalogs():
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not installed here")
    c = load(LFM2)
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["source_url"] == c["source"])
    assert row["name"] == "LFM2-8B-A1B"
    for key, value in row["config"].items():
        assert c[key] == value, key
