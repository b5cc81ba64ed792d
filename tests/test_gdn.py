"""The qwen3_next decoder (models/hybrid.py with models/gdn.py: Gated DeltaNet
layers with a per-slot MATRIX state beside one gated-attention layer, 16
routed experts top 4 and a gated shared expert at the `tiny-gdn` preset)
against the plain reference `benchmarks/reference/gdn_moe_decoder.py`, on
seeded random weights — and what the engine does with a lane that carries
such a state.

What is compared is LOGITS. Tolerances as tests/test_hybrid.py's:

- float32 weights, float32 cache: the same mathematics in another order (a
  chunked triangular solve against a scan over time, a mixture against a
  loop over experts, the softmax over the selected logits against the full
  softmax renormalised). Kept tokens agree to 2e-5 on logits of order 0.5; a
  token within 1e-4 of a router tie is left out — at most a tenth may be.
- bfloat16 / int8 weights, int8 KV: the reference is fed the SAME weights
  dequantised; the median error is held to 5% of the logit scale and the
  90th percentile to 25%.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import gdn_moe_decoder as ref  # noqa: E402

from symmetry_tpu.engine.engine import (  # noqa: E402
    EngineError, InferenceEngine, SamplingParams)
from symmetry_tpu.engine.tokenizer import get_tokenizer  # noqa: E402
from symmetry_tpu.models import gdn, hybrid, llama, moe  # noqa: E402
from symmetry_tpu.ops.quant import (  # noqa: E402
    QuantizedTensor, dequantize)
from symmetry_tpu.ops.rope import apply_rope  # noqa: E402

CFG = llama.preset("tiny-gdn")
EXACT = dict(eps=1e-4, atol=2e-5, max_excluded=0.10)
NOISY = dict(median=0.05, p90=0.25)
NORMS = ("norm", "q_norm", "k_norm", "final_norm", "gate_norm")


def as_float32(params):
    """What the reference is fed: the program's weights, dequantised."""
    return jax.tree.map(
        lambda a: (dequantize(a) if isinstance(a, QuantizedTensor)
                   else a.astype(jnp.float32)),
        params, is_leaf=lambda a: isinstance(a, QuantizedTensor))


def make_params(weights: str, cfg=CFG, key=35):
    """Seeded weights with every norm moved off its identity, so that a
    (1 + w) read as w — or the reverse — shows."""
    dtype = jnp.bfloat16 if weights == "bfloat16" else jnp.float32
    params = llama.init_params(cfg, jax.random.key(key), dtype)

    def bump(path, a):
        name = path[-1].key
        if name not in NORMS:
            return a
        noise = jax.random.normal(
            jax.random.fold_in(jax.random.key(key + 1), NORMS.index(name)),
            a.shape, jnp.float32)
        return (a.astype(jnp.float32) + 0.1 * noise).astype(a.dtype)

    params = jax.tree_util.tree_map_with_path(bump, params)
    if weights == "int8":
        params = llama.quantize_params(params)
    return params, dtype


def fwd(params, cfg):
    def run(tokens, cache, seq_lens=None, prefill_flash=False):
        h, cache = llama.forward_hidden(params, cfg, tokens, cache, seq_lens,
                                        prefill_flash=prefill_flash)
        return llama.logits_from_hidden(params, cfg, h), cache
    return jax.jit(run, static_argnames=("prefill_flash",))


def reference(params, cfg, tokens):
    model = hybrid.hf_config(cfg)
    weights = as_float32(params)
    out = [ref.reference_logits(weights, model, row, with_margins=True)
           for row in tokens]
    return (np.stack([np.asarray(w) for w, _ in out]),
            np.stack([np.asarray(m).min(axis=0) for _, m in out]))


def check(got, want, margins, weights):
    err = np.abs(np.asarray(got, np.float32) - want).max(axis=-1)
    scale = np.abs(want).max()
    if weights == "float32":
        kept = margins >= EXACT["eps"]
        assert 1 - kept.mean() <= EXACT["max_excluded"]
        assert err[kept].max() <= EXACT["atol"], err[kept].max()
    else:
        assert np.median(err) <= NOISY["median"] * scale, np.median(err)
        assert np.quantile(err, 0.9) <= NOISY["p90"] * scale


@pytest.mark.parametrize("weights", ["float32", "bfloat16", "int8"])
def test_prefill_logits_match_the_reference(weights):
    params, dtype = make_params(weights)
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0,
                                CFG.vocab_size)
    cache = llama.init_cache(CFG, 2, 64, dtype, quantized=weights == "int8")
    got, cache = fwd(params, CFG)(tokens, cache, prefill_flash=True)
    want, margins = reference(params, CFG, tokens)
    check(got, want, margins, weights)
    assert cache.lengths.tolist() == [40, 40]
    assert cache.k.shape[0] == 1 and cache.ssm.shape == (3, 2, 4, 16, 16)
    assert cache.conv.shape == (3, 3, 2, 2 * 2 * 16 + 4 * 16)


@pytest.mark.parametrize("weights", ["float32", "bfloat16", "int8"])
def test_prefill_then_decode_through_cache_and_state_match_the_reference(
        weights):
    """Prefill of 23 tokens from empty (two chunks of 16, the second
    padded), then 17 single-token steps through the K/V cache, the matrix
    state and the conv tail, teacher-forced: against the reference's full
    forward over all 40."""
    params, dtype = make_params(weights)
    tokens = jax.random.randint(jax.random.key(2), (2, 40), 0,
                                CFG.vocab_size)
    run = fwd(params, CFG)
    cache = llama.init_cache(CFG, 2, 64, dtype, quantized=weights == "int8")
    first, cache = run(tokens[:, :23], cache, prefill_flash=True)
    got = [first]
    for t in range(23, 40):
        logits, cache = run(tokens[:, t:t + 1], cache)
        got.append(logits)
    want, margins = reference(params, CFG, tokens)
    check(jnp.concatenate(got, axis=1), want, margins, weights)


def test_the_final_state_is_the_references():
    """Layer 0's matrix state after a prompt, against the reference's scan
    over time (no routing decision upstream of it)."""
    params, dtype = make_params("float32")
    tokens = jax.random.randint(jax.random.key(4), (1, 40), 0,
                                CFG.vocab_size)
    cache = llama.init_cache(CFG, 1, 64, dtype)
    _, cache = fwd(params, CFG)(tokens, cache, prefill_flash=True)
    weights = as_float32(params)
    model = dict(hybrid.hf_config(CFG), layer_types=["linear_attention"])
    states: list = []
    one = {"layers": {
        "gdn": jax.tree.map(lambda a: a[:1], weights["layers"]["gdn"]),
        "ffn": jax.tree.map(lambda a: a[:1], weights["layers"]["ffn"])}}
    ref.run_layers(one, model, ref.embed(weights, model, tokens[0]),
                   layers=[0], states=states)
    np.testing.assert_allclose(np.asarray(cache.ssm[0, 0]),
                               np.asarray(states[0]), atol=2e-6)


def test_a_continuation_call_starts_from_the_caches_state():
    """Several positions at once WITHOUT the empty-cache contract: the
    chunked form starts from the state and the tail the cache holds."""
    params, dtype = make_params("float32")
    tokens = jax.random.randint(jax.random.key(3), (2, 40), 0,
                                CFG.vocab_size)
    run = fwd(params, CFG)
    cache = llama.init_cache(CFG, 2, 64, dtype)
    first, cache = run(tokens[:, :9], cache, prefill_flash=True)
    second, cache = run(tokens[:, 9:], cache)
    want, margins = reference(params, CFG, tokens)
    check(jnp.concatenate([first, second], axis=1), want, margins, "float32")


# ------------------------------------------------- the two forms of the mixer

def gdn_layer(key=5):
    params, _ = make_params("float32", key=key)
    return jax.tree.map(lambda a: a[1], params["layers"]["gdn"])


def by_steps(u, lp, state, conv, lens, cfg):
    """The recurrence a position at a time, each row stopping at its own
    length: what `chunked` has to equal."""
    outs = []
    for t in range(u.shape[1]):
        out, new, tail = gdn.step_at(u[:, t], lp, state[None], jnp.int32(0),
                                     conv, cfg)
        live = jnp.asarray(t < np.asarray(lens))
        state = jnp.where(live[:, None, None, None], new[0], state)
        conv = jnp.where(live[None, :, None], tail, conv)
        outs.append(out)
    return jnp.stack(outs, axis=1), state, conv


@pytest.mark.parametrize("length,chunk,lens", [
    (16, 16, (16, 16)),         # one whole chunk
    (40, 16, (40, 17)),         # a row that ends one past a chunk boundary
    (37, 8, (33, 5)),           # a padded last chunk, a row inside chunk 0
])
def test_chunked_form_is_the_recurrence_from_a_state_that_is_not_empty(
        length, chunk, lens):
    cfg = dataclasses.replace(CFG, linear_chunk_size=chunk)
    z = gdn.sizes(cfg)
    lp = gdn_layer()
    keys = jax.random.split(jax.random.key(length), 3)
    u = jax.random.normal(keys[0], (2, length, cfg.hidden_size), jnp.float32)
    state = 0.5 * jax.random.normal(keys[1], (2, z["Hv"], z["Dk"], z["Dv"]),
                                    jnp.float32)
    conv = jax.random.normal(keys[2], (z["K"] - 1, 2, z["conv"]),
                             jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    got, got_state, got_conv = gdn.chunked(u, lp, state, conv, lens, cfg)
    want, want_state, want_conv = by_steps(u, lp, state, conv, lens, cfg)
    for b, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=3e-6)
    np.testing.assert_allclose(got_state, want_state, atol=3e-6)
    np.testing.assert_allclose(got_conv, want_conv, atol=1e-6)


def test_repeated_keys_do_not_break_the_triangular_solve():
    """Every key of a chunk the same unit vector and beta near 1: the worst
    case for a power series of the chunk's system (its terms grow like
    binomials and cancel); forward substitution stays at rounding."""
    cfg = dataclasses.replace(CFG, linear_chunk_size=16)
    z = gdn.sizes(cfg)
    B, Q, H = 1, 16, z["Hv"]
    k = jnp.broadcast_to(jnp.eye(z["Dk"])[0], (B, Q, H, z["Dk"]))
    q = k * z["Dk"] ** -0.5
    v = jax.random.normal(jax.random.key(0), (B, Q, H, z["Dv"]))
    beta = jnp.full((B, Q, H), 0.999)
    g = jnp.full((B, Q, H), -1e-3)
    state = jnp.zeros((B, H, z["Dk"], z["Dv"]))
    got, got_state = gdn._chunk(q, k, v, beta, g, state)
    want = []
    for t in range(Q):
        o, state = gdn.recurrence(state, jnp.exp(g[:, t]), beta[:, t],
                                  q[:, t], k[:, t], v[:, t])
        want.append(o)
    np.testing.assert_allclose(got, jnp.stack(want, axis=1), atol=1e-5)
    np.testing.assert_allclose(got_state, state, atol=1e-5)


def test_partial_rotary_turns_the_leading_channels_alone():
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 16), jnp.float32)
    pos = jnp.arange(5)[None, :] + jnp.asarray([[0], [7]])
    out = apply_rope(x, pos, 10000.0, 4)
    np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
    np.testing.assert_allclose(out[..., :4],
                               apply_rope(x[..., :4], pos, 10000.0))
    np.testing.assert_array_equal(apply_rope(x, pos, 10000.0, 16),
                                  apply_rope(x, pos, 10000.0))
    # the reference's own rotation, one row at position 0..4
    np.testing.assert_allclose(
        out[0], ref._rope(x[0], 4, 10000.0), atol=1e-6)


# ---------------------------------------------------------------- the experts

def test_softmax_over_the_selected_is_the_full_softmax_renormalised():
    """HF qwen3_next: softmax over all 512 router logits, the 10 largest,
    renormalised to sum 1. `route_top_k`: softmax over the 10 selected
    logits. The full softmax's denominator cancels: the same gates."""
    x = jax.random.normal(jax.random.key(0), (64, 32), jnp.float32)
    router = jax.random.normal(jax.random.key(1), (32, 512), jnp.float32)
    gates, experts = moe.route_top_k(x, router, 10)
    probs = jax.nn.softmax(x @ router, axis=-1)
    vals, idx = jax.lax.top_k(probs, 10)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(idx))
    np.testing.assert_allclose(gates, vals / vals.sum(-1, keepdims=True),
                               rtol=2e-6)


@pytest.mark.parametrize("tokens,form", [(24, "dense-mixture"),
                                         (24, "routed")])
def test_512_way_routing_and_the_gated_shared_expert_drop_no_pair(
        tokens, form, monkeypatch):
    """`moe_mlp` at 512 experts top 10 (narrow experts) in each of its two
    forms against the reference's loop over experts: every (token, expert)
    pair is computed, the shared expert is weighted by its gate."""
    cfg = dataclasses.replace(CFG, num_experts=512, num_experts_per_tok=10)
    monkeypatch.setattr(moe, "moe_route", lambda *a: form)
    keys = jax.random.split(jax.random.key(7), 9)
    E, F, X = cfg.hidden_size, cfg.intermediate_size, 512

    def w(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5

    lp = {"router": w(keys[0], (E, X)), "wg": w(keys[1], (X, E, F)),
          "wu": w(keys[2], (X, E, F)), "wd": w(keys[3], (X, F, E)),
          "sg": w(keys[4], (E, F)), "su": w(keys[5], (E, F)),
          "sd": w(keys[6], (F, E)), "sgate": w(keys[7], (E, 1))}
    x = jax.random.normal(keys[8], (1, tokens, E), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, pairs = moe.moe_mlp(x, lp, cfg)
        want, margin = ref.moe_and_shared(
            x[0], lp, {"num_experts_per_tok": 10})
    assert int(pairs.sum()) == tokens * 10 and pairs.shape == (512,)
    kept = np.asarray(margin) >= 1e-4
    assert kept.mean() >= 0.9
    np.testing.assert_allclose(np.asarray(got[0])[kept],
                               np.asarray(want)[kept], atol=2e-5)
    # without the gate column the shared expert counts whole: the gate is
    # not a no-op in this comparison
    plain, _ = moe.moe_mlp(x, {k: v for k, v in lp.items() if k != "sgate"},
                           cfg)
    assert float(jnp.abs(plain - got).max()) > 1e-2


def test_moe_route_at_512_experts_is_a_measured_entry():
    """Over the grouped-matmul kernel the routed form wins at every size
    measured (16-2,048 tokens: it reads the hit experts alone, the mixture
    all 512), decode's 128 tokens among them (PR 36)."""
    assert moe.ROUTED_FROM[(512, 10)] == (0, 1)     # the mixture: never
    for tokens in (1, 16, 64, 128, 512, 2048):
        assert moe.moe_route(tokens, 512, 10) == "routed"
    # the other shapes keep crossings of their own
    assert moe.moe_route(1023) == "dense-mixture"
    assert moe.moe_route(1024) == "routed"
    assert moe.moe_route(128, 72, 10) == "dense-mixture"


# ---------------------------------------------------------------- the engine

def make_engine(**kw):
    params = llama.init_params(CFG, jax.random.key(0), jnp.bfloat16,
                               quantize=True)
    args = dict(max_slots=4, max_seq_len=96, prefill_buckets=(16, 32, 64),
                decode_block=4, kv_quant=True, prefill_chunk=None)
    args.update(kw)
    return InferenceEngine(
        CFG, params, get_tokenizer(None, vocab_size=CFG.vocab_size), **args)


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    eng.warmup()
    return eng


GREEDY = SamplingParams()
PROMPT_A = list(range(5, 30))
PROMPT_B = list(range(100, 140))


def stream(eng, slot, ids, blocks=3):
    out = [eng.prefill_and_insert(slot, ids, GREEDY)]
    for _ in range(blocks):
        out += [int(t) for t in eng.decode_steps()[:, slot]]
    return out


def test_a_reused_lane_gives_the_first_requests_tokens_again(engine):
    """The insert overwrites the lane's whole matrix state and conv tail:
    it is the lane's reset. Parking it (length 0) is not, and the lane
    steps garbage into its state while it idles."""
    first = stream(engine, 1, PROMPT_A)
    engine.release_slot(1)
    other = stream(engine, 1, PROMPT_B)
    engine.release_slot(1)
    engine.decode_steps()      # parked: the lane's state keeps moving
    again = stream(engine, 1, PROMPT_A)
    assert first == again and first != other
    engine.release_slot(1)


def test_an_insert_writes_the_rows_state_into_the_lane_and_no_other(engine):
    before = np.asarray(engine.state.cache.ssm)
    engine.prefill_and_insert(3, PROMPT_A, GREEDY)
    after = np.asarray(engine.state.cache.ssm)
    assert np.abs(after[:, 3] - before[:, 3]).max() > 0
    for lane in (0, 1, 2):
        np.testing.assert_array_equal(after[:, lane], before[:, lane])
    # the lane now holds what a prefill of the prompt from empty leaves
    cache = llama.init_cache(CFG, 1, 96, jnp.bfloat16, quantized=True)
    ids = jnp.zeros((1, 32), jnp.int32).at[0, :len(PROMPT_A)].set(
        jnp.asarray(PROMPT_A))
    _, cache = llama.forward_hidden(
        engine.params, CFG, ids, cache,
        jnp.asarray([len(PROMPT_A)], jnp.int32), prefill_flash=True)
    np.testing.assert_allclose(after[:, 3], np.asarray(cache.ssm[:, 0]),
                               atol=1e-5)
    engine.release_slot(3)


def test_a_coalesced_prefill_of_unequal_lengths_matches_single_prefills(
        engine):
    single = {}
    for ids in (PROMPT_A, PROMPT_B, PROMPT_A[:7]):
        single[len(ids)] = stream(engine, 2, ids)
        engine.release_slot(2)
    firsts = engine.prefill_and_insert_many(
        [(0, PROMPT_A, GREEDY), (2, PROMPT_B, GREEDY),
         (3, PROMPT_A[:7], GREEDY)])
    toks = np.concatenate([engine.decode_steps() for _ in range(3)])
    for row, (slot, ids) in enumerate(((0, PROMPT_A), (2, PROMPT_B),
                                       (3, PROMPT_A[:7]))):
        assert [firsts[row]] + toks[:, slot].tolist() == single[len(ids)]
    for slot in (0, 2, 3):
        engine.release_slot(slot)


def test_serving_compiles_nothing_after_warmup_and_counts_what_it_did(
        engine):
    before = engine.compile_cache_sizes()
    counted = dict(engine.counters["ssm"])
    stream(engine, 0, PROMPT_B)
    engine.release_slot(0)
    assert engine.compile_cache_sizes() == before
    assert engine.counters["ssm"]["prefill_tokens"] == (
        counted["prefill_tokens"] + len(PROMPT_B))
    assert engine.counters["ssm"]["state_installs"] == (
        counted["state_installs"] + 1)


def test_the_engine_reports_the_kind_its_state_and_each_programs_form(
        engine):
    report = engine.ssm_report()
    assert report["kind"] == "gated_deltanet"
    assert report["linear_attention_layers"] == 3
    assert report["attention_layers"] == 1 and "mamba_layers" not in report
    assert report["state_bytes_per_slot"] == 3 * 4 * 16 * 16 * 4
    assert report["conv_bytes_per_slot"] == 3 * 3 * 128 * 2
    assert report["state_bytes"] == 4 * (report["state_bytes_per_slot"]
                                         + report["conv_bytes_per_slot"])
    assert report["state_dtype"] == "float32"
    assert report["prefill"] == {"form": "chunked (jnp)", "chunk": 16}
    assert report["decode"] == gdn.step_form(CFG)
    assert engine.state_bytes_per_slot() == (
        report["state_bytes_per_slot"] + report["conv_bytes_per_slot"])
    # one attention layer: K and V, 2 heads x (16 int8 + one f32 scale)
    assert engine.kv_bytes_per_token() == 2 * 1 * 2 * (16 + 4)
    moe_report = engine.moe_report()
    assert moe_report["experts"] == 16 and moe_report["top_k"] == 4
    assert "sigmoid" in moe_report["shared_expert"]["form"]
    assert set(engine.attention_paths()) >= {"prefill", "decode"}
    # the published widths: 6.29 MB of state + 147 KB of tails a row, so the
    # scratch bound (160 MB) leaves the widest batch there is
    full = InferenceEngine.__new__(InferenceEngine)
    full._has_state, full.cache_dtype = True, jnp.bfloat16
    full.config = llama.preset("qwen3-next-80b-a3b")
    assert full.state_bytes_per_slot() == 6_291_456 + 147_456
    assert full._state_rows_max() == full.PREFILL_BATCHES[-1]
    # and the mamba kind reports as it did
    granite = InferenceEngine.__new__(InferenceEngine)
    granite._has_state, granite.cache_dtype = True, jnp.bfloat16
    granite.config = llama.preset("granite-4.0-h-small")
    assert granite.state_bytes_per_slot() == 37_748_736 + 456_192


REFUSED = {
    "prefix_cache_mb": dict(prefix_cache_bytes=1 << 20),
    "speculative": dict(speculative=object()),
    "prefill_chunk": dict(prefill_chunk=16),
    "role": dict(role="prefill"),
}


@pytest.mark.parametrize("setting", sorted(REFUSED))
def test_the_engine_refuses_what_cannot_carry_a_state(setting):
    with pytest.raises(EngineError, match=f"tpu.{setting}"):
        make_engine(**REFUSED[setting])


CONFIG_REFUSED = {
    "prefix_cache_mb": {"prefix_cache_mb": 64},
    "speculative": {"speculative": {"k_draft": 4}},
    "prefill_chunk": {"prefill_chunk": 256},
    "role": {"role": "disagg"},
    "mesh": {"mesh": {"model": 4}},
}


@pytest.mark.parametrize("preset", ["tiny-gdn", "qwen3-next-80b-a3b"])
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


def test_layer_types_come_in_one_familys_names():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, num_layers=5)
    with pytest.raises(ValueError, match="layer_types"):   # two families
        dataclasses.replace(CFG, layer_types=(
            "linear_attention", "mamba", "linear_attention",
            "full_attention"))
    assert CFG.recurrent_kind == "linear_attention"
    assert CFG.attention_kind == "full_attention"
    granite = llama.preset("granite-4.0-h-small")
    assert (granite.recurrent_kind, granite.attention_kind) == (
        "mamba", "attention")
    assert hybrid.runs(CFG) == [("linear_attention", 0, 3),
                                ("full_attention", 3, 1)]
    assert hybrid.stack_index(CFG, 3) == 0 and hybrid.stack_index(CFG, 2) == 2


def test_config_from_hf_reads_the_published_keys():
    """The catalog row's keys -> the preset, but for the depth that was
    cut; the pattern comes from `full_attention_interval`."""
    published = {
        "model_type": "qwen3_next", "decoder_sparse_step": 1,
        "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "mlp_only_layers": [], "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    full = llama.config_from_hf(published)
    assert full.num_layers == 48
    assert full.layer_types == (("linear_attention",) * 3
                                + ("full_attention",)) * 12
    cut = llama.config_from_hf(dict(published, num_hidden_layers=4))
    assert cut == llama.preset("qwen3-next-80b-a3b")
    assert llama.config_from_hf(hybrid.hf_config(cut)) == cut
    with pytest.raises(ValueError, match="mlp_only_layers"):
        llama.config_from_hf(dict(published, mlp_only_layers=[0]))


def test_an_hf_checkpoint_round_trips_through_the_name_map(tmp_path):
    """Our tree -> HF qwen3_next names and layouts (in_proj_qkvz and
    in_proj_ba fused per key-head group, experts named one by one, [C, 1, K]
    convolution, [out, in] linears) -> a safetensors directory ->
    load_checkpoint: the same config, the same leaves."""
    from symmetry_tpu.engine.weights import (
        CheckpointError, convert_hf_state_dict, load_checkpoint,
        save_checkpoint)

    params, _ = make_params("float32")
    save_checkpoint(str(tmp_path), params, CFG)
    loaded, cfg = load_checkpoint(str(tmp_path), dtype=jnp.float32)
    # the chunk is the program's choice, not a published key
    assert cfg == dataclasses.replace(CFG, linear_chunk_size=64)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(params),
                                 jax.tree_util.tree_leaves_with_path(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))
    hf = hybrid.to_hf_state_dict(params, CFG)
    assert hf["model.layers.0.linear_attn.conv1d.weight"].shape == (128, 1, 4)
    assert hf["model.layers.0.linear_attn.in_proj_qkvz.weight"].shape == (
        192, 64)
    assert hf["model.layers.0.linear_attn.in_proj_ba.weight"].shape == (8, 64)
    assert hf["model.layers.3.self_attn.q_proj.weight"].shape == (128, 64)
    assert hf["model.layers.3.self_attn.q_norm.weight"].shape == (16,)
    assert hf["model.layers.1.mlp.experts.15.down_proj.weight"].shape == (
        64, 32)
    assert hf["model.layers.2.mlp.shared_expert_gate.weight"].shape == (1, 64)
    assert hf["lm_head.weight"].shape == (512, 64)
    # HF's rows are fused per key-head group: group 1's q rows are ours
    # columns 16..31 of the q block, its first v rows value head 2's
    fused = hf["model.layers.0.linear_attn.in_proj_qkvz.weight"]
    ours = np.asarray(params["layers"]["gdn"]["in_proj"][0])
    group = 2 * 16 + 2 * 2 * 16
    np.testing.assert_array_equal(fused[group:group + 16], ours[:, 16:32].T)
    np.testing.assert_array_equal(fused[group + 32:group + 48],
                                  ours[:, 64 + 32:64 + 48].T)
    del hf["model.layers.2.linear_attn.A_log"]
    with pytest.raises(CheckpointError, match="A_log"):
        convert_hf_state_dict(hf, CFG)
