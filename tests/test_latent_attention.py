"""Latent attention (HF `deepseek_v3`, kanana-2-30b-a3b's family) at a small
size on the CPU: the model against the plain reference
(`benchmarks/reference/latent_moe_decoder.py`), the cache's one row a
position, the absorbed and expanded forms on the same inputs, the decode
kernel against the `jnp` form, the sigmoid router with its bias, scale and
1e-20, the leading dense layer, the engine and scheduler token for token,
`config_from_hf` and every refusal, and the HF names there and back."""

import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.engine.engine import (
    EngineError, InferenceEngine, SamplingParams)
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler
from symmetry_tpu.engine.tokenizer import get_tokenizer
from symmetry_tpu.models import hybrid, llama, moe
from symmetry_tpu.ops import flash, mla_attention as mla
from symmetry_tpu.ops.rope import apply_rope

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from reference import latent_moe_decoder as ref  # noqa: E402

CFG = llama.preset("tiny-mla")
MODEL = llama.hf_config_latent(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(1), jnp.float32)


def ids_of(n, key=0):
    return [int(t) for t in jax.random.randint(jax.random.key(key), (n,), 0,
                                               256)]


def reference_logits(params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.reference_logits(params, MODEL,
                                               jnp.asarray(ids)))


# ------------------------------------------------------------------ the model

def test_a_full_forward_matches_the_reference(params):
    ids = ids_of(40)
    cache = llama.init_cache(CFG, 1, 128, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(params, CFG, jnp.asarray([ids]), cache)
    want = reference_logits(params, ids)
    assert np.abs(np.asarray(got[0]) - want).max() < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("capacity", [128, 96])
def test_prefill_then_decode_through_the_cache_match_the_reference(
        params, capacity):
    """Expanded over the prompt (flash), then absorbed a position at a time
    — through the kernel at 128, the `jnp` form at a capacity it has no
    block for — against the reference's full pass."""
    ids = ids_of(44, key=3)
    P = 31
    cache = llama.init_cache(CFG, 2, capacity, jnp.float32)
    assert cache.v is None and cache.k.shape == (3, 2, capacity, 128)
    paths = llama.attention_paths(CFG, capacity, batch=2, kv_bytes=4)
    assert (paths["decode"] == "xla") == (capacity == 96)
    toks = jnp.zeros((2, 32), jnp.int32).at[:, :P].set(jnp.asarray(ids[:P]))
    lens = jnp.asarray([P, P - 4], jnp.int32)
    with jax.default_matmul_precision("highest"):
        h, cache = llama.forward_hidden(params, CFG, toks, cache, lens,
                                        prefill_flash=True)
        rows = [llama.logits_from_hidden(params, CFG, h)[0, :P]]
        cache = cache._replace(lengths=jnp.asarray([P, P], jnp.int32))
        for t in ids[P:]:
            h, cache = llama.forward_hidden(
                params, CFG, jnp.asarray([[t], [t]]), cache)
            rows.append(llama.logits_from_hidden(params, CFG, h)[0])
    got = np.concatenate([np.asarray(r) for r in rows])
    want = reference_logits(params, ids)
    assert np.abs(got - want).max() < 3e-5 * np.abs(want).max()
    # the padding lanes of a written row stay zero
    assert not np.asarray(cache.k[:, 0, :len(ids), CFG.latent.row:]).any()


def test_a_continuation_of_several_positions_attends_absorbed(params):
    ids = ids_of(30, key=5)
    cache = llama.init_cache(CFG, 1, 64, jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, cache = llama.forward_hidden(params, CFG, jnp.asarray([ids[:20]]),
                                        cache, prefill_flash=True)
        h, _ = llama.forward_hidden(params, CFG, jnp.asarray([ids[20:]]),
                                    cache)
        got = np.asarray(llama.logits_from_hidden(params, CFG, h)[0])
    want = reference_logits(params, ids)[20:]
    assert np.abs(got - want).max() < 3e-5 * np.abs(want).max()


def test_absorbed_and_expanded_agree_on_the_same_inputs():
    la, H, S = CFG.latent, CFG.num_heads, 24
    ks = jax.random.split(jax.random.key(7), 5)
    q_nope = jax.random.normal(ks[0], (1, S, H, la.nope))
    q_pe = jax.random.normal(ks[1], (1, S, H, la.rope))
    c_n = jax.random.normal(ks[2], (1, S, la.rank))
    k_r = jax.random.normal(ks[3], (1, S, la.rope))
    wkvb = jax.random.normal(ks[4], (la.rank, H * (la.nope + la.v))) * 0.2
    scale = (la.nope + la.rope) ** -0.5
    pos = jnp.arange(S)[None]
    with jax.default_matmul_precision("highest"):
        wuk, wuv = llama.absorbed_factors(wkvb, la, H, jnp.float32)
        q_abs = jnp.concatenate(
            [jnp.einsum("bshd,hdr->bshr", q_nope, wuk), q_pe], -1)
        o_lat = mla.absorbed_attention(
            q_abs, jnp.concatenate([c_n, k_r], -1), pos,
            jnp.asarray([S]), scale, la.rank)
        absorbed = jnp.einsum("bshr,hrd->bshd", o_lat, wuv)
        kv = (c_n @ wkvb).reshape(1, S, H, la.nope + la.v)
        s = (jnp.einsum("bshd,bthd->bhst", q_nope, kv[..., :la.nope])
             + jnp.einsum("bshd,btd->bhst", q_pe, k_r)) * scale
        s = jnp.where(pos[0][None, :] <= pos[0][:, None], s, -jnp.inf)
        expanded = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1),
                              kv[..., la.nope:])
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1])
def test_the_decode_kernel_reads_each_slots_live_rows(layer):
    # (float32: the CPU's interpreter has no bf16 x bf16 -> f32 dot at
    # these shapes; tests/test_chip_compile.py compiles the bf16 kernel)
    dtype, tol = jnp.float32, 2e-6
    L, B, T, W, H, R = 2, 5, 384, 128, 4, 96
    ks = jax.random.split(jax.random.key(0), 2)
    cache = jax.random.normal(ks[0], (L, B, T, W), jnp.float32).astype(dtype)
    q = jax.random.normal(ks[1], (B, H, W), jnp.float32).astype(dtype)
    lens = jnp.asarray([1, 128, 129, 384, 0], jnp.int32)
    assert mla.geometry(T, W, 2) == 128
    out = mla.mla_decode(q, cache, jnp.int32(layer), lens, scale=0.1,
                         rank=R, interpret=True)
    want = mla.absorbed_attention(q[:, None], cache[layer],
                                  (lens - 1)[:, None], lens, 0.1, R)[:, 0]
    got, want = (np.asarray(a, np.float32)[:4] for a in (out, want))
    assert np.abs(got - want).max() < tol * max(1.0, np.abs(want).max())
    assert np.isfinite(np.asarray(out, np.float32)).all()   # the empty slot


def test_the_kernel_has_a_block_for_whole_lane_tiles_alone():
    assert mla.geometry(11776, 640, 2) == 512
    assert mla.geometry(2048, 640, 2) == 1024
    assert mla.geometry(96, 128, 4) is None
    with pytest.raises(ValueError, match="no mla_decode block"):
        mla.mla_decode(jnp.zeros((1, 4, 128)), jnp.zeros((1, 1, 96, 128)),
                       jnp.int32(0), jnp.asarray([1]), scale=1.0, rank=64,
                       interpret=True)


def test_flash_takes_a_value_width_of_its_own():
    B, S, H, D, Dv = 2, 64, 4, 24, 12
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    lens = jnp.asarray([S, 40], jnp.int32)
    got = flash.flash_prefill(q, k, v, lens, block_q=32, block_k=32,
                              interpret=True)
    assert got.shape == (B, S, H, Dv)
    s = jnp.einsum("bshd,bthd->bhst", q, k) * D ** -0.5
    t = jnp.arange(S)
    s = jnp.where(t[None, :] <= t[:, None], s, -jnp.inf)
    want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1, :40], want[1, :40], atol=2e-5)


@pytest.mark.parametrize("S,block,lens", [
    (96, 32, (96, 70)),     # whole tiles; a prompt that ends inside one
    (96, 64, (96, 30)),     # S padded up to the tile; a tile of padding alone
    (64, 512, (64, 5)),     # a tile larger than the bucket
    (160, 32, (33, 1)),     # most query tiles lie in the padding: zeros
])
def test_the_wide_flash_walk_is_the_same_attention(S, block, lens):
    B, H, D, Dv = 2, 3, 24, 16
    ks = jax.random.split(jax.random.key(S + block), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    n = jnp.asarray(lens, jnp.int32)
    got = flash.flash_prefill_wide(q, k, v, n, block=block, interpret=True)
    assert got.shape == (B, S, H, Dv)
    assert np.isfinite(np.asarray(got)).all()
    s = jnp.einsum("bshd,bthd->bhst", q, k) * D ** -0.5
    t = jnp.arange(S)
    s = jnp.where(t[None, :] <= t[:, None], s, -jnp.inf)
    want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
    narrow = flash.flash_prefill(q, k, v, n, block_q=32, block_k=32,
                                 interpret=True)
    for row, length in enumerate(lens):
        np.testing.assert_allclose(got[row, :length], want[row, :length],
                                   atol=2e-5)
        np.testing.assert_allclose(got[row, :length], narrow[row, :length],
                                   atol=2e-5)
    # a query tile wholly past the prompt is not walked
    tile = min(block, S)
    first_dead = -(-lens[1] // tile) * tile
    assert not np.asarray(got[1, first_dead:]).any()


def test_the_interleaved_rotary_turns_pairs_up_to_one_shared_permutation():
    x = jax.random.normal(jax.random.key(4), (1, 9, 3, 8))
    y = jax.random.normal(jax.random.key(5), (1, 9, 3, 8))
    pos = jnp.arange(9)[None] + 3
    ours_x = apply_rope(x, pos, 10000.0, interleaved=True)
    ours_y = apply_rope(y, pos, 10000.0, interleaved=True)
    pairs_x = ref.rope_pairs(x[0], 10000.0, pos[0])
    pairs_y = ref.rope_pairs(y[0], 10000.0, pos[0])
    # evens | odds of the pairwise rotation, so every dot product is its
    np.testing.assert_allclose(
        ours_x[0], jnp.concatenate([pairs_x[..., 0::2], pairs_x[..., 1::2]],
                                   -1), atol=1e-5)
    np.testing.assert_allclose(jnp.einsum("shd,thd->hst", ours_x[0],
                                          ours_y[0]),
                               jnp.einsum("shd,thd->hst", pairs_x, pairs_y),
                               atol=1e-4)
    # and it is NOT the rotation by halves of the channels as they lie
    assert np.abs(np.asarray(ours_x - apply_rope(x, pos, 10000.0))).max() > .1


# ----------------------------------------------------------------- the router

def test_the_router_is_the_references(params):
    lp = {k: v[0] for k, v in params["layers"]["ffn"].items()}
    y = jax.random.normal(jax.random.key(6), (50, CFG.hidden_size))
    gates, experts = moe.route_top_k(y, lp["router"], 2,
                                     **moe.routing_of(CFG, lp))
    want_g, want_e, _ = ref.route(y, lp, MODEL)
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(gates, want_g, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.448, rtol=1e-5)
    assert moe.routing_of(CFG, lp)["eps"] == 1e-20
    # lfm2_moe's 1e-6 is the router's default (the same Python constant in
    # its trace: tools/lowered_programs.py holds its programs byte-identical)
    assert moe.routing_of(llama.preset("tiny-sconv"), {})["eps"] == 1e-6


def test_the_bias_moves_the_selection_and_not_the_gate():
    y = jnp.ones((1, 4))
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    router = jnp.linalg.pinv(y) @ logits
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0])
    plain_g, plain_e = moe.route_top_k(y, router, 2, score="sigmoid",
                                       scale=2.448, eps=1e-20)
    gates, experts = moe.route_top_k(y, router, 2, score="sigmoid",
                                     bias=bias, scale=2.448, eps=1e-20)
    assert plain_e.tolist() == [[0, 1]] and experts.tolist() == [[3, 0]]
    s = jax.nn.sigmoid(logits[0])
    np.testing.assert_allclose(
        gates[0], 2.448 * jnp.asarray([s[3], s[0]]) / (s[3] + s[0]),
        rtol=1e-5)


def test_the_epsilon_is_the_keywords_and_ties_go_to_the_lower_index():
    y, router = jnp.ones((1, 2)), jnp.full((2, 4), -60.0)
    # scores ~ 1e-52 each: under 1e-6 the gates vanish, under 1e-20 too,
    # but differently — the keyword is what divides
    tiny = jax.nn.sigmoid(jnp.float32(-120.0))
    for eps in (1e-6, 1e-20):
        gates, experts = moe.route_top_k(y, router, 2, score="sigmoid",
                                         eps=eps)
        assert experts.tolist() == [[0, 1]]
        np.testing.assert_allclose(gates[0, 0], tiny / (2 * tiny + eps),
                                   rtol=1e-5)


# ------------------------------------------------------------ the composition

def test_the_trunk_is_a_dense_run_and_an_expert_run(params):
    assert hybrid.runs(CFG) == [("latent_attention", 0, 1),
                                ("latent_attention", 1, 2)]
    assert CFG.recurrent_kind is None
    assert CFG.attention_kind == "latent_attention"
    lay = params["layers"]
    assert lay["dense"]["wg"].shape == (1, 64, 128)
    assert lay["ffn"]["wg"].shape == (2, 8, 64, 32)
    assert lay["ffn"]["sg"].shape == (2, 64, 64) and "sgate" not in lay["ffn"]
    assert lay["ffn"]["expert_bias"].shape == (2, 8)
    assert lay["attn"]["wkva"].shape == (3, 64, 24)
    assert lay["attn"]["wuk"].shape == (3, 4, 16, 16)
    assert lay["attn"]["wuv"].shape == (3, 4, 16, 12)


def test_the_dense_layer_is_the_references(params):
    ids = ids_of(12, key=8)
    model = dict(MODEL, num_hidden_layers=1)
    one = {**params, "layers": {
        "attn": {k: v[:1] for k, v in params["layers"]["attn"].items()},
        "dense": params["layers"]["dense"]}}
    h0 = ref.embed(params, MODEL, jnp.asarray(ids))
    want, margin = ref.layer_forward(one, model, h0, 0)
    assert np.isinf(np.asarray(margin)).all()
    cfg1 = llama.config_from_hf(dict(model, first_k_dense_replace=1))
    one["layers"]["ffn"] = {k: v[:0] for k, v in
                            params["layers"]["ffn"].items()}
    with jax.default_matmul_precision("highest"):
        h, _ = hybrid.forward_hidden(
            {**one, "final_norm": jnp.ones_like(params["final_norm"])},
            cfg1, jnp.asarray([ids]), llama.init_cache(cfg1, 1, 32,
                                                       jnp.float32))
    normed = ref.norm(want, 1.0, MODEL["rms_norm_eps"])
    np.testing.assert_allclose(h[0], normed, atol=2e-5)


def test_the_absorbed_factors_are_derived_from_the_int8_up_projection():
    q = llama.init_params(CFG, jax.random.key(1), jnp.bfloat16,
                          quantize=True)
    attn = q["layers"]["attn"]
    assert attn["wkvb"].q.dtype == jnp.int8 and attn["wkva"].q.dtype == \
        jnp.int8
    assert attn["wuk"].dtype == jnp.bfloat16
    w = (np.asarray(attn["wkvb"].q, np.float32)
         * np.asarray(attn["wkvb"].scale)[:, None, :]).reshape(3, 16, 4, 28)
    np.testing.assert_allclose(
        np.asarray(attn["wuk"], np.float32),
        np.moveaxis(w[..., :16], 1, -1), rtol=1e-2)
    np.testing.assert_allclose(
        np.asarray(attn["wuv"], np.float32),
        np.moveaxis(w[..., 16:], 1, 2), rtol=1e-2)


def test_no_other_models_leaves_or_programs_gain_anything():
    for name in ("tiny-sconv", "tiny-hybrid", "tiny-dsa"):
        cfg = llama.preset(name)
        assert cfg.latent is None
        cache = jax.eval_shape(lambda cfg=cfg: llama.init_cache(
            cfg, 2, 64, jnp.bfloat16, count_experts=True))
        assert cache.v is not None and cache.k.ndim == 5
    assert llama.absorb_latent({"x": 1}, llama.preset("tiny")) == {"x": 1}


def test_the_counters_ride_the_expert_vector(params):
    cache = llama.init_cache(CFG, 2, 64, jnp.float32, count_experts=True)
    assert cache.expert_pairs.shape == (8 + len(llama.LATENT_COUNTS),)
    toks = jnp.zeros((2, 32), jnp.int32)
    _, cache = llama.forward_hidden(params, CFG, toks, cache,
                                    jnp.asarray([20, 9]), prefill_flash=True)
    assert cache.expert_pairs[-2:].tolist() == [0, 0]
    assert int(cache.expert_pairs[:8].sum()) == 2 * 2 * 29
    cache = cache._replace(lengths=jnp.asarray([20, 0]))     # lane 1 parked
    _, cache = llama.forward_hidden(params, CFG, toks[:, :1], cache)
    assert cache.expert_pairs[-2:].tolist() == [1, 21]


# ---------------------------------------------------- config_from_hf, refusals

def catalog_row():
    with open(CATALOG) as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "kanana-2-30b-a3b-instruct-2601":
                return row
    raise AssertionError("the catalog has no kanana row")


def test_config_from_hf_of_the_catalog_row_is_the_preset_at_48_layers():
    import dataclasses

    got = llama.config_from_hf(catalog_row()["config"])
    cut = llama.preset("kanana-2-30b-a3b")
    assert got == dataclasses.replace(
        cut, num_layers=48, layer_types=("latent_attention",) * 48)
    assert got.latent == llama.LatentAttention(rank=512, rope=64, nope=128,
                                               v=128, rope_interleave=True)
    assert got.latent.row == 576 and got.latent.lanes == 640
    assert (got.num_experts, got.num_experts_per_tok) == (128, 6)
    assert got.shared_intermediate_size == 2 * 768
    assert (got.num_dense_layers, got.dense_intermediate_size) == (1, 6144)
    assert got.vocab_size == 128256 and got.router_norm_eps == 1e-20
    assert llama.config_from_hf(llama.hf_config_latent(cut)) == cut


@pytest.mark.parametrize("key,value,says", [
    ("q_lora_rank", 1536, "query latent"),
    ("n_group", 8, "group-limited routing"),
    ("topk_group", 4, "group-limited routing"),
    ("rope_scaling", {"type": "yarn", "factor": 40}, "frequency bands"),
    ("topk_method", "greedy", "noaux_tc"),
    ("scoring_func", "softmax", "sigmoid router"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
])
def test_config_from_hf_refuses_what_would_change_the_layer(key, value,
                                                            says):
    with pytest.raises(ValueError, match=says):
        llama.config_from_hf(dict(catalog_row()["config"], **{key: value}))


def test_latent_layers_and_their_sizes_go_together():
    with pytest.raises(ValueError, match="go together"):
        llama.HybridConfig(
            vocab_size=8, hidden_size=8, num_layers=1, num_heads=1,
            num_kv_heads=1, intermediate_size=8,
            layer_types=("latent_attention",))


def make_engine(**kw):
    params = llama.init_params(CFG, jax.random.key(0), jnp.float32)
    args = dict(max_slots=4, max_seq_len=128, prefill_buckets=(32, 64),
                decode_block=4, prefill_chunk=None, cache_dtype=jnp.float32)
    args.update(kw)
    return InferenceEngine(
        CFG, params, get_tokenizer(None, vocab_size=CFG.vocab_size), **args)


REFUSED = {
    "prefix_cache_mb": dict(prefix_cache_bytes=1 << 20),
    "prefill_chunk": dict(prefill_chunk=16),
    "role": dict(role="prefill"),
    "kv_quantization": dict(kv_quant=True),
}


@pytest.mark.parametrize("setting", sorted(REFUSED))
def test_the_engine_refuses_what_cannot_carry_a_latent_row(setting):
    with pytest.raises(EngineError, match=f"tpu.{setting}"):
        make_engine(**REFUSED[setting])


CONFIG_REFUSED = {
    "prefill_chunk": {"prefill_chunk": 64},
    "prefix_cache_mb": {"prefix_cache_mb": 64},
    "speculative": {"speculative": {"k_draft": 4}},
    "role": {"role": "disagg"},
    "mesh": {"mesh": {"model": 4}},
    "kv_quantization": {"kv_quantization": "int8"},
}


@pytest.mark.parametrize("preset", ["tiny-mla", "kanana-2-30b-a3b"])
@pytest.mark.parametrize("setting", sorted(CONFIG_REFUSED))
def test_each_refused_setting_is_a_config_error_before_anything_is_built(
        setting, preset):
    from symmetry_tpu.provider.config import ConfigError, ConfigManager

    def config(**tpu):
        return {"name": "p", "public": True, "serverKey": "00" * 32,
                "modelName": "m", "apiProvider": "tpu_native",
                "tpu": {"model_preset": preset, "prefill_chunk": None,
                        **tpu}}

    ConfigManager(config=config())      # the plain configuration is fine
    with pytest.raises(ConfigError, match=f"tpu.{setting}"):
        ConfigManager(config=config(**CONFIG_REFUSED[setting]))


# ----------------------------------------------------------------- HF's names

def test_an_hf_state_dict_round_trips_through_the_name_map(params):
    tensors = hybrid.to_hf_state_dict(params, CFG)
    assert tensors["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"
                   ].shape == (24, 64)
    assert tensors["model.layers.1.self_attn.kv_b_proj.weight"].shape == \
        (4 * 28, 16)
    assert tensors["model.layers.0.mlp.gate_proj.weight"].shape == (128, 64)
    assert tensors["model.layers.1.mlp.gate.e_score_correction_bias"
                   ].shape == (8,)
    assert "model.layers.2.mlp.experts.7.down_proj.weight" in tensors
    assert "model.layers.1.mlp.shared_experts.up_proj.weight" in tensors
    assert not any("wuk" in n or "wuv" in n for n in tensors)
    back = hybrid.convert_hf_state_dict(tensors, CFG)
    assert "wuk" not in back["layers"]["attn"]   # absorb_latent's alone
    flat = dict(jax.tree_util.tree_leaves_with_path(params))
    for path, leaf in jax.tree_util.tree_leaves_with_path(back):
        np.testing.assert_allclose(leaf, flat[path], atol=1e-6,
                                   err_msg=str(path))
    ids = ids_of(16, key=2)
    # the converted tree is the reference's weights: the interleaved rotary
    # needs no reordering of a checkpoint's rope channels
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(
            llama.absorb_latent(jax.tree.map(jnp.asarray, back), CFG,
                                jnp.float32), CFG, jnp.asarray([ids]),
                               llama.init_cache(CFG, 1, 32, jnp.float32))
    want = reference_logits(params, ids)
    assert np.abs(np.asarray(got[0]) - want).max() < 3e-5 * np.abs(want).max()
    with pytest.raises(ValueError, match="unmapped HF tensors"):
        hybrid.convert_hf_state_dict(
            {**tensors, "model.layers.0.self_attn.q_a_proj.weight":
             np.zeros((4, 4), np.float32)}, CFG)


def test_a_checkpoint_saves_and_loads_by_its_config(tmp_path, params):
    from symmetry_tpu.engine.weights import load_checkpoint, save_checkpoint

    save_checkpoint(str(tmp_path), params, CFG)
    loaded, cfg = load_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert cfg == CFG
    # the loader derives nothing: the factors come from the finished tree
    assert "wuk" not in loaded["layers"]["attn"]
    absorbed = llama.absorb_latent(loaded, CFG, jnp.float32)
    np.testing.assert_allclose(absorbed["layers"]["attn"]["wuk"],
                               params["layers"]["attn"]["wuk"], atol=1e-6)


# ------------------------------------------------------ engine and scheduler

@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    eng.warmup()
    return eng


GREEDY = SamplingParams()


def reference_stream(params, ids, n):
    """The reference's loop: the full pass over everything so far, the
    argmax of its last row, `n` times."""
    ids, out = list(ids), []
    for _ in range(n):
        out.append(int(np.argmax(reference_logits(params, ids)[-1])))
        ids.append(out[-1])
    return out


def test_the_engine_reports_the_latent_cache_and_both_routes(engine):
    paths = engine.attention_paths()
    assert paths["kind"] == "latent"
    assert paths["prefill"] == paths["decode"] == "pallas-interpret"
    assert paths["decode_block_t"] == 128
    assert paths["prefill_tile"] == flash.WIDE_TILE
    report = engine.cache_report()
    assert (report["rank"], report["rope"], report["row"],
            report["lanes"]) == (16, 8, 24, 128)
    assert report["bytes_per_token"] == 3 * 128 * 4 == \
        engine.kv_bytes_per_token()
    assert report["cache_bytes"] == engine.state.cache.k.nbytes
    assert report["absorbed_factors"]["bytes"] == 3 * 4 * 16 * (16 + 12) * 4
    assert engine.ssm_report() is None and engine.state_bytes_per_slot() == 0
    assert make_engine(max_seq_len=96, prefill_buckets=(32,)
                       ).attention_paths()["decode_why"].startswith(
        "ops/mla_attention.py has no block")
    assert InferenceEngine(
        llama.preset("tiny"), llama.init_params(
            llama.preset("tiny"), jax.random.key(0), jnp.float32),
        get_tokenizer(None, vocab_size=512), max_slots=2, max_seq_len=64,
        prefill_buckets=(32,)).cache_report() is None


def test_engine_and_scheduler_stream_the_references_tokens(engine):
    """Greedy requests admitted together and between dispatches, through
    the scheduler: every stream is the reference's loop token for token;
    nothing compiles after warm-up and the counters count."""
    params = jax.tree.map(lambda a: a, engine.params)
    requests = [(ids_of(20 + 5 * r, key=10 + r), 9 + r) for r in range(3)]
    requests.append((ids_of(40, key=20), 6))
    before = engine.compile_cache_sizes()
    counted = dict(engine.counters["mla"])
    got = {i: [] for i in range(len(requests))}
    done = {i: threading.Event() for i in range(len(requests))}

    def sink(batch):
        for req, ev in batch:
            got[req.id].append(ev)
            if ev.done:
                done[req.id].set()

    engine.tokenizer.eos_ids = frozenset({511})
    sched = Scheduler(engine, emit_batch=sink)
    sched.start()
    try:
        for i, (ids, max_new) in enumerate(requests):
            if i == 3:
                done[0].wait(5)
            sched.submit(GenRequest(
                prompt_ids=list(ids), sampling=GREEDY,
                max_new_tokens=max_new, emit=lambda ev: None,
                cancelled=lambda: False, id=i))
        for i, ev in done.items():
            assert ev.wait(180), f"request {i} hung"
        stats = sched.stats()
    finally:
        sched.stop(timeout=10)
    for i, (ids, max_new) in enumerate(requests):
        last = got[i][-1]
        assert last.done and not last.error, last
        want = reference_stream(params, ids, max_new)
        dec = engine.tokenizer.stream_decoder()
        assert "".join(ev.text for ev in got[i]) == \
            dec.push_many(want) + dec.flush(), i
        assert last.tokens_emitted == max_new
    assert engine.compile_cache_sizes() == before
    grew = {k: engine.counters["mla"][k] - counted[k] for k in counted}
    assert grew["prefill_tokens"] == sum(len(ids) for ids, _ in requests)
    assert grew["decode_steps"] >= 12 and grew["decode_steps"] % 4 == 0
    assert grew["live_positions"] > grew["decode_steps"] * 20
    # (a block behind)
    assert stats["mla"].keys() == engine.counters["mla"].keys()
    assert len(engine.expert_pairs) == CFG.num_experts
