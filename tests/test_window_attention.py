"""Window AND full attention layers in one model (PowerInfer `smallthinker`,
smallthinker-21b-a3b's family) at a small size on the CPU: the model against
the plain reference (`benchmarks/reference/swa_moe_decoder.py`) through a
prefill, an insert and decode steps across ring wraps; the ring against one
uniform cache under a window mask; the engine's two-piece ring copy against
a position-by-position write; the wide flash walk with a head map and a
window; the router on the layer's input and ReGLU in both expert forms; the
engine and scheduler token for token; `config_from_hf` and every refusal;
the HF names there and back."""

import dataclasses
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
from symmetry_tpu.ops import flash
from symmetry_tpu.ops.attention import gqa_attention

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from reference import swa_moe_decoder as ref  # noqa: E402

CFG = llama.preset("tiny-swa")
MODEL = llama.hf_config_window(CFG)
W = CFG.sliding_window
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# both sides compute in float32 on the CPU and differ in the order of
# accumulation: 2e-5 of the logit scale (measured 1.4e-6). A reference in
# bfloat16, a rope on a full layer, a window off by one and the router fed
# the normed tensor each read over 1e-3 (below).
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(1), jnp.float32)


def ids_of(n, key=0):
    return [int(t) for t in jax.random.randint(jax.random.key(key), (n,), 0,
                                               256)]


def reference_logits(params, ids, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.reference_logits(params, MODEL,
                                               jnp.asarray(ids), **kw))


def worst(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@jax.jit
def _step(params, token, cache):
    h, cache = llama.forward_hidden(params, CFG, token[None, None], cache)
    return llama.logits_from_hidden(params, CFG, h)[0], cache


def ring_insert(scratch, cache, n, row=0, slot=0):
    """A prompt's rows out of its scratch into a served cache, a position
    at a time: every row of the full leaves, the last `W` of the window
    leaves at position mod W."""
    bucket = scratch.k.shape[2]
    k = cache.k.at[:, slot, :bucket].set(scratch.k[:, row])
    v = cache.v.at[:, slot, :bucket].set(scratch.v[:, row])
    kw, vw = cache.kw, cache.vw
    ring = kw.shape[2]
    for p in range(max(0, n - ring), n):
        kw = kw.at[:, slot, p % ring].set(scratch.kw[:, row, p])
        vw = vw.at[:, slot, p % ring].set(scratch.vw[:, row, p])
    return cache._replace(k=k, v=v, kw=kw, vw=vw,
                          lengths=cache.lengths.at[slot].set(n))


def prefill_then_decode(params, ids, n, bucket, ring=W, capacity=64):
    """Logit rows of `ids`: a prefill from empty of its first `n` through a
    scratch of `bucket` rows, the insert, then single positions through
    both leaves."""
    scratch = llama.init_cache(CFG, 1, bucket, jnp.float32)
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
        jnp.asarray(ids[:n]))
    with jax.default_matmul_precision("highest"):
        h, scratch = llama.forward_hidden(params, CFG, padded, scratch,
                                          jnp.asarray([n]),
                                          prefill_flash=True)
        rows = [llama.logits_from_hidden(params, CFG, h)[0, :n]]
        cache = ring_insert(scratch, llama.init_cache(
            CFG, 1, capacity, jnp.float32, ring=ring), n)
        for t in ids[n:]:
            row, cache = _step(params, jnp.int32(t), cache)
            rows.append(row)
    return np.concatenate([np.asarray(r) for r in rows]), cache


# ------------------------------------------------------------------ the model

def test_a_full_forward_over_a_uniform_cache_matches_the_reference(params):
    ids = ids_of(40)
    cache = llama.init_cache(CFG, 1, 64, jnp.float32, ring=64)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(params, CFG, jnp.asarray([ids]), cache)
    assert worst(got[0], reference_logits(params, ids)) < TOL


@pytest.mark.parametrize("n,bucket", [
    (5, 16),      # under the window
    (8, 16),      # at it
    (13, 16),     # over it
    (16, 16),     # a bucket's last row
    (17, 32),     # a bucket's first
    (29, 32),
])
def test_prefill_insert_and_decode_across_wraps_match_the_reference(
        params, n, bucket):
    """46 positions in all: a ring of 8 wraps at least twice after every
    prompt, and every decode step overwrites the oldest key."""
    ids = ids_of(46, key=n)
    got, cache = prefill_then_decode(params, ids, n, bucket)
    assert worst(got, reference_logits(params, ids)) < TOL
    assert cache.kw.shape == (6, 1, W, 2, 16) and cache.k.shape == (
        2, 1, 64, 2, 16)
    assert int(cache.lengths[0]) == 46 >= n + 2 * W


@pytest.mark.parametrize("wrong,least", [
    ("rope_full", 0.1), ("window_short", 0.01), ("router_normed", 0.1),
    ("silu", 0.01)])
def test_the_tolerance_tells_each_departure(params, wrong, least):
    """A rope on a full layer, a window off by one, the router fed the
    normed tensor and another activation each move the logits by far more
    than the tolerance the program is held to."""
    ids = ids_of(46, key=5)
    want = reference_logits(params, ids)
    assert worst(reference_logits(params, ids, wrong=wrong), want) > least \
        > 100 * TOL


def test_the_tolerance_tells_bfloat16_from_float32(params):
    ids = ids_of(46, key=5)
    want = reference_logits(params, ids)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                       params)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.reference_logits(
            low, MODEL, jnp.asarray(ids), softmax_dtype=jnp.bfloat16))
    assert worst(got, want) > 50 * TOL


def test_ring_decode_is_one_uniform_cache_under_a_window_mask(params):
    """The property that lets the decode kernel go without a start offset:
    the same steps over rings of exactly the window's rows and over window
    leaves as long as the full ones, masked by each row's own position,
    give the same logits."""
    ids = ids_of(40, key=7)
    ringed, _ = prefill_then_decode(params, ids, 13, 16, ring=W)
    uniform, cache = prefill_then_decode(params, ids, 13, 16, ring=64)
    assert cache.kw.shape[2] == 64
    np.testing.assert_allclose(ringed, uniform, atol=2e-5)


def test_a_ring_write_keeps_the_last_rows_and_drops_the_rest():
    cache = llama.init_cache(CFG, 2, 32, jnp.float32, ring=W)
    view = llama.ring_view(cache)
    k = jnp.arange(2 * 20 * 2 * 16, dtype=jnp.float32).reshape(2, 20, 2, 16)
    positions = jnp.broadcast_to(jnp.arange(20), (2, 20))
    valid = jnp.asarray([20, 11])     # lane 1: nine padded positions
    out = llama.write_kv(view, jnp.int32(3), positions, k, k, by_head=False,
                         ring_valid=valid)
    for lane, n in enumerate([20, 11]):
        held = np.asarray(llama.ring_positions(valid, W))[lane]
        assert sorted(held) == list(range(n - W, n))
        for r, p in enumerate(held):
            np.testing.assert_array_equal(out.k[3, lane, r], k[lane, p])
    assert not np.asarray(out.k[:3]).any()         # no other layer touched
    back = llama.ring_restore(cache, out)
    assert back.kw is out.k and back.k is cache.k
    assert np.asarray(llama.ring_positions(jnp.asarray([3]), W))[0].tolist() \
        == [0, 1, 2, -1, -1, -1, -1, -1]


def test_gqa_attention_masks_by_the_position_a_row_holds():
    key = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(key[0], (1, 1, 4, 16))
    k = jax.random.normal(key[1], (1, 20, 2, 16))
    v = jax.random.normal(key[2], (1, 20, 2, 16))
    n = jnp.asarray([20])
    want = gqa_attention(q, k, v, jnp.asarray([[19]]), n, sliding_window=W)
    held = llama.ring_positions(n, W)
    ring_k = jnp.zeros((1, W, 2, 16)).at[0, jnp.arange(12, 20) % W].set(
        k[0, 12:20])
    ring_v = jnp.zeros((1, W, 2, 16)).at[0, jnp.arange(12, 20) % W].set(
        v[0, 12:20])
    got = gqa_attention(q, ring_k, ring_v, jnp.asarray([[19]]), n,
                        sliding_window=W, kv_positions=held)
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("S,block,window,lens", [
    (64, 16, 32, [64]), (64, 16, 16, [37]), (96, 32, 64, [70, 96]),
    (64, 16, None, [50]), (48, 16, 64, [48]),   # a window past the prompt
])
def test_the_wide_flash_walk_takes_a_head_map_and_a_window(S, block, window,
                                                           lens):
    key = jax.random.split(jax.random.key(S), 3)
    B = len(lens)
    q = jax.random.normal(key[0], (B, S, 4, 16), jnp.float32)
    k = jax.random.normal(key[1], (B, S, 2, 16), jnp.float32)
    v = jax.random.normal(key[2], (B, S, 2, 16), jnp.float32)
    n = jnp.asarray(lens, jnp.int32)
    got = flash.flash_prefill_wide(q, k, v, n, block=block, window=window,
                                   interpret=True)
    want = flash.flash_prefill(q, k, v, n, block_q=16, block_k=16,
                               window=window, interpret=True)
    for b, m in enumerate(lens):
        np.testing.assert_allclose(got[b, :m], want[b, :m], atol=2e-5)
    assert flash.wide_takes(None) and flash.wide_takes(4096)
    assert not flash.wide_takes(W)
    with pytest.raises(ValueError, match="no multiple of the tile"):
        flash.flash_prefill_wide(q, k, v, n, block=16, window=24,
                                 interpret=True)


# ----------------------------------------------------- the router, the experts

def test_the_router_reads_the_layers_input_and_not_the_ffns(params):
    lp = jax.tree.map(lambda a: a[1], params["layers"]["ffn"])
    key = jax.random.split(jax.random.key(9), 2)
    x = jax.random.normal(key[0], (1, 24, 64))
    entered = jax.random.normal(key[1], (1, 24, 64))
    y_in, pairs_in = moe.moe_mlp(x, lp, CFG, route_from=entered)
    y_self, pairs_self = moe.moe_mlp(x, lp, CFG)
    assert (np.asarray(pairs_in) != np.asarray(pairs_self)).any()
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(x[0], entered[0], lp, MODEL)
        wrong, _ = ref.moe(x[0], entered[0], lp, MODEL,
                           wrong="router_normed")
    np.testing.assert_allclose(y_in[0], want, atol=2e-5)
    np.testing.assert_allclose(y_self[0], wrong, atol=2e-5)
    assert float(jnp.abs(want - wrong).max()) > 1e-2
    assert moe.routing_of(CFG, lp) == {"act": "relu"}
    assert moe.routing_of(llama.preset("tiny-moe"), lp) == {}


@pytest.mark.parametrize("quantized", [False, True])
def test_reglu_is_the_same_in_both_expert_forms_and_the_references(
        params, quantized):
    from symmetry_tpu.ops.quant import dequantize, quantize

    lp = jax.tree.map(lambda a: a[2], params["layers"]["ffn"])
    if quantized:
        lp = {**lp, **{k: quantize(lp[k]) for k in moe.EXPERT_LEAVES}}
    key = jax.random.split(jax.random.key(11), 2)
    x = jax.random.normal(key[0], (40, 64))
    entered = jax.random.normal(key[1], (40, 64))
    valid = jnp.ones((40,), bool)
    routing = moe.routing_of(CFG, lp, entered)
    args = (x, valid, lp["router"], lp["wg"], lp["wu"], lp["wd"], 2)
    with jax.default_matmul_precision("highest"):
        routed, pairs_r = moe._routed_ffn(*args, None, routing)
        mixture, pairs_m = moe._dense_mixture(*args, routing)
        plain = {k: (dequantize(v) if quantized and k in moe.EXPERT_LEAVES
                     else v) for k, v in lp.items()}
        want, _ = ref.moe(x, entered, plain, MODEL)
        silu, _ = ref.moe(x, entered, plain, MODEL, wrong="silu")
    np.testing.assert_array_equal(pairs_r, pairs_m)
    np.testing.assert_allclose(routed, mixture, atol=3e-5)
    np.testing.assert_allclose(routed, want, atol=3e-5)
    assert float(jnp.abs(want - silu).max()) > 1e-2


def test_the_trunk_breaks_a_run_on_the_kind_and_on_the_rotary_choice():
    assert hybrid.runs(CFG) == [
        ("full_attention", 0, 1), ("sliding_attention", 1, 3),
        ("full_attention", 4, 1), ("sliding_attention", 5, 3)]
    assert len(hybrid.runs(llama.preset("smallthinker-21b-a3b"))) == 6
    whole = dataclasses.replace(
        llama.preset("smallthinker-21b-a3b"), num_layers=52,
        layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 13,
        rope_layout=(0, 1, 1, 1) * 13)
    assert len(hybrid.runs(whole)) == 26
    # a rotary choice that does not follow the kind breaks a run of one kind
    odd = dataclasses.replace(CFG, rope_layout=(0, 1, 0, 1, 0, 1, 1, 1))
    assert [r[1:] for r in hybrid.runs(odd)] == [
        (0, 1), (1, 1), (2, 1), (3, 1), (4, 1), (5, 3)]
    assert [hybrid.stack_index(CFG, i) for i in range(8)] == [
        0, 0, 1, 2, 1, 3, 4, 5]
    assert CFG.recurrent_kind is None and CFG.window_kind == \
        "sliding_attention" and CFG.attention_kind == "full_attention"


def test_no_other_models_leaves_or_programs_gain_anything():
    for name in ("tiny", "tiny-moe", "tiny-sconv", "tiny-hybrid", "tiny-dsa",
                 "tiny-mla", "tiny-gdn"):
        cfg = llama.preset(name)
        assert getattr(cfg, "window_kind", None) is None
        cache = jax.eval_shape(lambda cfg=cfg: llama.init_cache(
            cfg, 2, 64, jnp.bfloat16,
            count_experts=bool(getattr(cfg, "num_experts", 0))))
        assert cache.kw is cache.vw is cache.kw_scale is cache.vw_scale \
            is None
    mine = jax.eval_shape(lambda: llama.init_cache(
        CFG, 2, 64, jnp.bfloat16, quantized=True, count_experts=True,
        ring=W))
    assert mine.kw.shape == (6, 2, W, 2, 16) and mine.kw.dtype == jnp.int8
    assert mine.kw_scale.shape == (6, 2, 2, W)
    assert mine.k_scale.shape == (2, 2, 2, 64)
    assert mine.expert_pairs.shape == (8 + len(llama.WINDOW_COUNTS),)


def test_the_counters_ride_the_expert_vector(params):
    cache = llama.init_cache(CFG, 2, 64, jnp.float32, count_experts=True,
                             ring=W)
    cache = cache._replace(lengths=jnp.asarray([15, 0]))    # lane 1 parked
    toks = jnp.zeros((2, 1), jnp.int32)
    _, cache = llama.forward_hidden(params, CFG, toks, cache)
    # one forward; 16 full rows and a ring of 8; position 15: no wrap
    assert cache.expert_pairs[-4:].tolist() == [1, 16, 8, 0]
    cache = cache._replace(lengths=jnp.asarray([16, 0]))
    _, cache = llama.forward_hidden(params, CFG, toks, cache)
    # position 16 = 2 x 8 comes back to row 0 of the full ring
    assert cache.expert_pairs[-4:].tolist() == [2, 33, 16, 1]
    assert int(cache.expert_pairs[:8].sum()) == 2 * 2 * 2 * 8


# ---------------------------------------------------- config_from_hf, refusals

def catalog_row():
    with open(CATALOG) as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "SmallThinker-21BA3B-Instruct":
                return row
    raise AssertionError("the catalog has no SmallThinker-21BA3B row")


def test_config_from_hf_of_the_catalog_row_is_the_preset_at_52_layers():
    if not os.path.exists(CATALOG):
        pytest.skip("the guide's catalog is not installed here")
    got = llama.config_from_hf(catalog_row()["config"])
    cut = llama.preset("smallthinker-21b-a3b")
    assert got == dataclasses.replace(
        cut, num_layers=52,
        layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 13,
        rope_layout=(0, 1, 1, 1) * 13)
    assert (got.num_experts, got.num_experts_per_tok) == (64, 6)
    assert (got.sliding_window, got.rope_theta) == (4096, 1500000.0)
    assert (got.hidden_act, got.router_input) == ("relu", "layer_input")
    assert got.vocab_size == 151936 and not got.tie_embeddings
    assert llama.config_from_hf(llama.hf_config_window(cut)) == cut
    assert llama.config_from_hf(MODEL) == CFG


def test_layouts_are_taken_as_given():
    """A layout that is not (0, 1, 1, 1)-periodic, and a rope layout that
    differs from the window layout, are configurations and no errors."""
    hf = dict(MODEL, sliding_window_layout=[1, 1, 0, 1, 0, 0, 1, 1],
              rope_layout=[1, 0, 0, 1, 1, 0, 1, 1])
    cfg = llama.config_from_hf(hf)
    assert [t == "sliding_attention" for t in cfg.layer_types] == [
        bool(w) for w in hf["sliding_window_layout"]]
    assert list(cfg.rope_layout) == hf["rope_layout"]
    params = llama.init_params(cfg, jax.random.key(2), jnp.float32)
    ids = ids_of(30, key=4)
    cache = llama.init_cache(cfg, 1, 32, jnp.float32, ring=32)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(params, cfg, jnp.asarray([ids]), cache)
        want = ref.reference_logits(params, hf, jnp.asarray(ids))
    assert worst(got[0], np.asarray(want)) < TOL
    # no window layer at all: full attention alone, no ring leaf
    plain = llama.config_from_hf(dict(MODEL, sliding_window_layout=[0] * 8))
    assert plain.sliding_window is None and plain.window_kind is None
    assert plain.recurrent_kind is None


@pytest.mark.parametrize("key,value,says", [
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("moe_primary_router_apply_softmax", False, "apply_softmax"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("moe_num_secondary_experts", 4, "secondary experts"),
    ("rope_layout", [0, 1], "must name 8 layers"),
])
def test_config_from_hf_refuses_what_would_change_the_layer(key, value,
                                                            says):
    with pytest.raises(ValueError, match=says):
        llama.config_from_hf(dict(MODEL, **{key: value}))


@pytest.mark.parametrize("change,says", [
    (dict(sliding_window=None), "go together"),
    (dict(rope_layout=(0, 1)), "rope_layout must have 8"),
    (dict(router_input="attention"), "router_input must be"),
])
def test_the_config_holds_its_fields_to_each_other(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)


def make_engine(**kw):
    params = llama.init_params(CFG, jax.random.key(0), jnp.float32)
    args = dict(max_slots=4, max_seq_len=64, prefill_buckets=(16, 32),
                decode_block=4, prefill_chunk=None, cache_dtype=jnp.float32)
    args.update(kw)
    return InferenceEngine(
        CFG, params, get_tokenizer(None, vocab_size=CFG.vocab_size), **args)


REFUSED = {
    "prefix_cache_mb": dict(prefix_cache_bytes=1 << 20),
    "prefill_chunk": dict(prefill_chunk=16),
    "role": dict(role="prefill"),
}


@pytest.mark.parametrize("setting", sorted(REFUSED))
def test_the_engine_refuses_what_cannot_carry_a_ring(setting):
    with pytest.raises(EngineError, match=f"tpu.{setting}"):
        make_engine(**REFUSED[setting])


CONFIG_REFUSED = {
    "prefill_chunk": {"prefill_chunk": 64},
    "prefix_cache_mb": {"prefix_cache_mb": 64},
    "speculative": {"speculative": {"k_draft": 4}},
    "role": {"role": "disagg"},
    "mesh": {"mesh": {"model": 2}},
}


@pytest.mark.parametrize("preset", ["tiny-swa", "smallthinker-21b-a3b"])
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
    if setting == "speculative":
        # since PR 65 a ring carries a draft (the window's rows and the
        # drafted positions', each row masked by the position it holds):
        # the n-gram drafter is served; the module's needs a module
        ConfigManager(config=config(**CONFIG_REFUSED[setting]))
        with pytest.raises(ConfigError, match="tpu.speculative mtp"):
            ConfigManager(config=config(speculative="mtp"))
        return
    with pytest.raises(ConfigError, match=f"tpu.{setting}"):
        ConfigManager(config=config(**CONFIG_REFUSED[setting]))


# ----------------------------------------------------------------- HF's names

def test_an_hf_state_dict_round_trips_through_the_name_map(params):
    tensors = hybrid.to_hf_state_dict(params, CFG)
    assert tensors["model.layers.0.self_attn.q_proj.weight"].shape == (64, 64)
    assert tensors["model.layers.1.self_attn.k_proj.weight"].shape == (32, 64)
    assert tensors["model.layers.3.block_sparse_moe.primary_router.weight"
                   ].shape == (8, 64)
    assert tensors["model.layers.7.block_sparse_moe.experts.7.down.weight"
                   ].shape == (64, 32)
    assert tensors["lm_head.weight"].shape == (512, 64)
    back = hybrid.convert_hf_state_dict(tensors, CFG)
    assert set(back["layers"]) == {"attn", "swa", "ffn"}
    flat = dict(jax.tree_util.tree_leaves_with_path(params))
    for path, leaf in jax.tree_util.tree_leaves_with_path(back):
        np.testing.assert_allclose(leaf, flat[path], atol=1e-6,
                                   err_msg=str(path))
    with pytest.raises(ValueError, match="unmapped HF tensors"):
        hybrid.convert_hf_state_dict(
            {**tensors, "model.layers.0.self_attn.q_norm.weight":
             np.zeros((4,), np.float32)}, CFG)


def test_a_checkpoint_saves_and_loads_by_its_config(tmp_path, params):
    from symmetry_tpu.engine.weights import load_checkpoint, save_checkpoint

    save_checkpoint(str(tmp_path), params, CFG)
    loaded, cfg = load_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert cfg == CFG
    np.testing.assert_allclose(loaded["layers"]["swa"]["wq"],
                               params["layers"]["swa"]["wq"], atol=1e-6)


# ------------------------------------------------------ engine and scheduler

@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("n,bucket", [(5, 8), (8, 8),    # a bucket the ring
                                      # holds whole: no roll
                                      (5, 16), (8, 16), (13, 16), (16, 16),
                                      (21, 32), (32, 32)])
def test_inserts_two_piece_ring_copy_is_a_position_by_position_write(
        n, bucket, kv_quant):
    engine = make_engine(kv_quant=kv_quant, prefill_buckets=(8, 16, 32))
    scratch = llama.init_cache(CFG, 2, bucket, jnp.float32,
                               quantized=kv_quant, count_experts=True)
    key = jax.random.key(n)
    scratch = scratch._replace(**{
        name: (jax.random.randint(key, leaf.shape, -100, 100).astype(
            leaf.dtype) if leaf.dtype == jnp.int8
            else jax.random.normal(key, leaf.shape, leaf.dtype))
        for name, leaf in scratch._asdict().items()
        if leaf is not None and name not in ("lengths", "expert_pairs")})
    lens = [3, n]
    zeros = jnp.zeros((2,), jnp.int32)
    state = engine._insert_all(
        engine.state, scratch, jnp.asarray([0, 2], jnp.int32),
        jnp.asarray(lens, jnp.int32), zeros, zeros.astype(jnp.float32),
        zeros.astype(jnp.float32), zeros,
        jax.random.split(jax.random.key(0), 2))
    cache = state.cache
    for row, slot in ((0, 0), (1, 2)):
        m = lens[row]
        assert int(cache.lengths[slot]) == m
        for p in range(max(0, m - W), m):
            np.testing.assert_array_equal(cache.kw[:, slot, p % W],
                                          scratch.kw[:, row, p])
            np.testing.assert_array_equal(cache.vw[:, slot, p % W],
                                          scratch.vw[:, row, p])
            if kv_quant:
                np.testing.assert_array_equal(
                    cache.kw_scale[:, slot, :, p % W],
                    scratch.kw_scale[:, row, :, p])
                np.testing.assert_array_equal(
                    cache.vw_scale[:, slot, :, p % W],
                    scratch.vw_scale[:, row, :, p])
        np.testing.assert_array_equal(cache.k[:, slot, :bucket],
                                      scratch.k[:, row])
    assert not np.asarray(cache.kw[:, 1]).any()     # no other lane touched


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    eng.warmup()
    return eng


GREEDY = SamplingParams()


@jax.jit
def _padded_reference(params, tokens):
    with jax.default_matmul_precision("highest"):
        return ref.reference_logits(params, MODEL, tokens)


def reference_stream(params, ids, n, total=40):
    """The reference's loop: the full pass over everything so far, the
    argmax of its last row, `n` times — each pass over the tokens so far
    padded to `total` (a causal pass: a row does not see what follows it),
    so the loop is ONE compiled program at one shape. (Op by op at a new
    length a token, the loop was most of this file's slowest test's 70
    seconds: 57 passes, each recompiling its scans.)"""
    assert len(ids) + n <= total
    toks, out = np.zeros((total,), np.int32), []
    toks[:len(ids)] = ids
    for i in range(n):
        rows = _padded_reference(params, jnp.asarray(toks))
        out.append(int(np.argmax(rows[len(ids) - 1 + i])))
        toks[len(ids) + i] = out[-1]
    return out


def test_the_engine_reports_both_kinds_and_both_leaves(engine):
    paths = engine.attention_paths()
    assert paths["kind"] == "window+full"
    assert paths["prefill"] == "pallas-interpret" and paths["decode"] == "xla"
    assert (paths["full"]["rope"], paths["full"]["capacity"],
            paths["full"]["layers"]) == (False, 64, 2)
    assert (paths["window"]["rope"], paths["window"]["span"],
            paths["window"]["ring"], paths["window"]["layers"]) == (
        True, W, W, 6)
    for kind in ("full", "window"):
        assert "head of 16" in paths[kind]["decode_why"]
    report = engine.cache_report()
    assert report["kind"] == "window+full"
    row = 2 * 2 * 16 * 4
    assert report["full"]["bytes_per_token"] == 2 * row == \
        engine.kv_bytes_per_token("full")
    assert report["window"]["bytes_per_token"] == 6 * row
    assert engine.kv_bytes_per_token() == 8 * row
    cache = engine.state.cache
    assert report["cache_bytes"] == sum(
        int(a.nbytes) for a in (cache.k, cache.v, cache.kw, cache.vw))
    assert report["uniform_cache_bytes"] == 4 * 64 * 8 * row
    moe_report = engine.moe_report()
    assert (moe_report["router_input"], moe_report["activation"]) == (
        "layer_input", "relu")
    assert engine.ssm_report() is None and engine.state_bytes_per_slot() == 0
    with pytest.raises(EngineError, match="no place for a window"):
        engine.extract_slot_kv(0, 4)
    # the real shape's routes, from the shapes alone: the kernel for both
    real = llama.attention_paths(
        llama.preset("smallthinker-21b-a3b"), 11776, batch=64, kv_bytes=1)
    assert (real["full"]["decode_slot_tile"],
            real["full"]["decode_block_t"]) == (16, 256)
    assert (real["window"]["decode_slot_tile"],
            real["window"]["decode_block_t"]) == (64, 256)
    assert real["decode"] == real["prefill"] and "flash_wide" in \
        real["prefill_form"]
    assert "no ring of the window" in llama.attention_paths(
        llama.preset("smallthinker-21b-a3b"), 8192, batch=64, kv_bytes=1,
        kind="window")["decode_why"]


def test_engine_and_scheduler_stream_the_references_tokens(engine):
    """Greedy requests admitted together and between dispatches, through
    the scheduler: every stream is the reference's loop token for token
    through prompts under, at and over the window; nothing compiles after
    warm-up and the counters count."""
    params = jax.tree.map(lambda a: a, engine.params)
    # (under, AT and over the window of 8; streams of two to three decode
    # blocks each — PR 65: they were 12-16 tokens after prompts of 5-30,
    # four blocks and 57 reference passes, 70 s alone and past the 180 s
    # wait under the driver's six workers)
    requests = [(ids_of(n, key=10 + r), 9 + r)
                for r, n in enumerate((5, W, 21))]
    requests.append((ids_of(30, key=20), 8))
    before = engine.compile_cache_sizes()
    counted = dict(engine.counters["swa"])
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
    grew = {k: engine.counters["swa"][k] - counted[k] for k in counted}
    assert grew["prefill_tokens"] == sum(len(ids) for ids, _ in requests)
    assert grew["decode_steps"] >= 8 and grew["decode_steps"] % 4 == 0
    assert grew["full_rows"] > grew["ring_rows"] > grew["decode_steps"] * 8
    assert grew["ring_wraps"] >= 4
    # (a block behind)
    assert stats["swa"].keys() == engine.counters["swa"].keys()
    assert len(engine.expert_pairs) == CFG.num_experts
