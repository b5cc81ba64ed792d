"""The hybrid decoder (models/hybrid.py: Mamba-2 layers with a per-slot
recurrent state beside attention, routed + shared experts) against the plain
reference `benchmarks/reference/hybrid_decoder.py`, on seeded random weights
at the tiny size — and what the engine does with a lane that carries a state.

What is compared is LOGITS (with random weights the largest logit changes on
rounding). Tolerances:

- float32 weights, float32 cache: the two sides do the same mathematics in
  another order (a chunked dual form against a scan over time, grouped
  experts against a loop). Kept tokens agree to 2e-5 on logits of order 0.3;
  a token within 1e-4 of a router tie (the k-th against the (k+1)-th logit)
  may route otherwise on the two sides and is left out — at most a tenth may
  be.
- bfloat16 / int8 weights, int8 KV: the reference is fed the SAME weights
  dequantised, so what is left is bfloat16 activations and the int8 cache.
  No token is left out; the median error is held to 5% of the logit scale
  and the 90th percentile to 25% (a routing flip moves a logit by about the
  scale itself).
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
from reference.hybrid_decoder import reference_logits  # noqa: E402

from symmetry_tpu.engine.engine import (  # noqa: E402
    EngineError, InferenceEngine, SamplingParams)
from symmetry_tpu.engine.tokenizer import get_tokenizer  # noqa: E402
from symmetry_tpu.models import hybrid, llama, mamba2, moe  # noqa: E402
from symmetry_tpu.ops.quant import (  # noqa: E402
    QuantizedTensor, dequantize)

CFG = llama.preset("tiny-hybrid")
EXACT = dict(eps=1e-4, atol=2e-5, max_excluded=0.10)
NOISY = dict(median=0.05, p90=0.25)


def model_keys(cfg) -> dict:
    return hybrid.hf_config(cfg)


def as_float32(params):
    """What the reference is fed: the program's weights, dequantised."""
    return jax.tree.map(
        lambda a: (dequantize(a) if isinstance(a, QuantizedTensor)
                   else a.astype(jnp.float32)),
        params, is_leaf=lambda a: isinstance(a, QuantizedTensor))


def make_params(weights: str, cfg=CFG, key=33):
    dtype = jnp.bfloat16 if weights == "bfloat16" else jnp.float32
    params = llama.init_params(cfg, jax.random.key(key), dtype)
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
    model = model_keys(cfg)
    ref = as_float32(params)
    out = [reference_logits(ref, model, row, with_margins=True)
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
    assert cache.k.shape[0] == 1 and cache.ssm.shape[0] == 3


@pytest.mark.parametrize("weights", ["float32", "bfloat16", "int8"])
def test_prefill_then_decode_through_cache_and_state_match_the_reference(
        weights):
    """Prefill of 23 tokens from empty (two chunks of the dual form, the
    second padded), then 17 single-token steps through the K/V cache, the
    recurrent state and the conv tail, teacher-forced: against the
    reference's full forward over all 40."""
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


def test_a_continuation_call_starts_from_the_caches_state():
    """Several positions at once WITHOUT the empty-cache contract: the
    chunked form starts from the state and the tail the cache holds."""
    params, dtype = make_params("float32")
    tokens = jax.random.randint(jax.random.key(3), (2, 40), 0,
                                CFG.vocab_size)
    run = fwd(params, CFG)
    cache = llama.init_cache(CFG, 2, 64, dtype)
    a, cache = run(tokens[:, :19], cache, prefill_flash=True)
    b, cache = run(tokens[:, 19:], cache)
    want, margins = reference(params, CFG, tokens)
    check(jnp.concatenate([a, b], axis=1), want, margins, "float32")


def mamba_layer(key=5):
    params, _ = make_params("float32", key=key)
    lp = jax.tree.map(lambda a: a[1], params["layers"]["mamba"])
    z = mamba2.sizes(CFG)
    return lp, z


@pytest.mark.parametrize("length,chunk", [(16, 16), (40, 16), (37, 8)])
def test_chunked_form_is_the_step_by_step_recurrence(length, chunk):
    cfg = dataclasses.replace(CFG, mamba_chunk_size=chunk)
    lp, z = mamba_layer()
    B = 3
    u = jax.random.normal(jax.random.key(7), (B, length, cfg.hidden_size))
    ssm0 = jax.random.normal(jax.random.key(8), (B, z["H"], z["P"], z["N"]))
    conv0 = jax.random.normal(jax.random.key(9), (z["K"] - 1, B, z["conv"]))
    out, ssm, conv = mamba2.chunked(
        u, lp, ssm0, conv0, jnp.full((B,), length, jnp.int32), cfg)
    s_ssm, s_conv, outs = ssm0, conv0, []
    for t in range(length):
        o, s_ssm, s_conv = mamba2.step(u[:, t], lp, s_ssm, s_conv, cfg)
        outs.append(o)
    np.testing.assert_allclose(out, jnp.stack(outs, axis=1), atol=2e-5)
    np.testing.assert_allclose(ssm, s_ssm, atol=2e-5)
    np.testing.assert_allclose(conv, s_conv, atol=1e-6)


def test_a_padded_row_keeps_the_state_and_tail_of_its_last_valid_token():
    """Rows of 40, 23, 2 and 0 valid tokens right-padded to 40: each row's
    state and conv tail are those of a run over its own tokens alone, and a
    row shorter than the tail keeps what was there before."""
    lp, z = mamba_layer()
    lens = [40, 23, 2, 0]
    B = len(lens)
    u = jax.random.normal(jax.random.key(11), (B, 40, CFG.hidden_size))
    ssm0 = jax.random.normal(jax.random.key(12), (B, z["H"], z["P"], z["N"]))
    conv0 = jax.random.normal(jax.random.key(13), (z["K"] - 1, B, z["conv"]))
    out, ssm, conv = mamba2.chunked(u, lp, ssm0, conv0,
                                    jnp.asarray(lens, jnp.int32), CFG)
    for b, n in enumerate(lens):
        if n == 0:
            np.testing.assert_allclose(ssm[b], ssm0[b], atol=1e-6)
            np.testing.assert_allclose(conv[:, b], conv0[:, b], atol=1e-6)
            continue
        o1, s1, c1 = mamba2.chunked(
            u[b:b + 1, :n], lp, ssm0[b:b + 1], conv0[:, b:b + 1],
            jnp.asarray([n], jnp.int32), CFG)
        np.testing.assert_allclose(out[b, :n], o1[0], atol=2e-5)
        np.testing.assert_allclose(ssm[b], s1[0], atol=2e-5)
        np.testing.assert_allclose(conv[:, b], c1[:, 0], atol=1e-6)


@pytest.mark.parametrize("tokens,form", [(24, "dense-mixture"),
                                         (400, "dense-mixture"),
                                         (400, "routed")])
def test_72_way_routing_and_the_shared_expert_against_a_per_token_loop(
        tokens, form, monkeypatch):
    """72 experts top 10 (granite's routing shape) with a shared expert, both
    forms of the expert FFN, against the sum written out token by token."""
    X, k, D, F, Fs = 72, 10, 32, 16, 24
    cfg = dataclasses.replace(CFG, num_experts=X, num_experts_per_tok=k)
    # each form at 400 tokens, whichever side of the crossing that is
    monkeypatch.setitem(moe.ROUTED_FROM, (X, k),
                        (0, 1 if form == "routed" else 401))
    assert moe.moe_route(tokens, X, k) == form
    keys = jax.random.split(jax.random.key(17), 8)
    lp = {"router": jax.random.normal(keys[0], (D, X)),
          "wg": jax.random.normal(keys[1], (X, D, F)) * D ** -0.5,
          "wu": jax.random.normal(keys[2], (X, D, F)) * D ** -0.5,
          "wd": jax.random.normal(keys[3], (X, F, D)) * F ** -0.5,
          "sg": jax.random.normal(keys[4], (D, Fs)) * D ** -0.5,
          "su": jax.random.normal(keys[5], (D, Fs)) * D ** -0.5,
          "sd": jax.random.normal(keys[6], (Fs, D)) * Fs ** -0.5}
    x = jax.random.normal(keys[7], (1, tokens, D))
    with jax.default_matmul_precision("highest"):
        got, pairs = moe.moe_mlp(x, lp, cfg)
        want = []
        for t in np.asarray(x[0]):
            logits = t @ np.asarray(lp["router"])
            top = np.argsort(-logits)[:k]
            gates = np.exp(logits[top] - logits[top].max())
            gates /= gates.sum()
            y = (np.asarray(jax.nn.silu(t @ lp["sg"])) * (t @ lp["su"])
                 ) @ lp["sd"]
            for g, e in zip(gates, top):
                y = y + g * ((np.asarray(jax.nn.silu(t @ lp["wg"][e]))
                              * (t @ lp["wu"][e])) @ lp["wd"][e])
            want.append(y)
    np.testing.assert_allclose(got[0], np.stack(want), atol=2e-4)
    assert int(pairs.sum()) == tokens * k


def test_moe_route_leaves_mixtrals_choices_where_they_were():
    for tokens in (1, 64, 256, 768, 1023):
        assert moe.moe_route(tokens) == "dense-mixture"
        assert moe.moe_route(tokens, 8, 2) == "dense-mixture"
        assert moe.moe_route(tokens, 4, 2) == "dense-mixture"
    for tokens in (1024, 1280, 2048, 4096):
        assert moe.moe_route(tokens) == "routed"
        assert moe.moe_route(tokens, 8, 2) == "routed"
        assert moe.moe_route(tokens, 4, 2) == "routed"
    # 72 top 10 has its own crossing, measured over the grouped-matmul
    # kernel (PR 36): decode's 128 tokens keep the mixture, a prefill
    # dispatch of 256 tokens or more is routed
    for tokens in (16, 64, 128, 255):
        assert moe.moe_route(tokens, 72, 10) == "dense-mixture"
    for tokens in (256, 512, 1024, 2048):
        assert moe.moe_route(tokens, 72, 10) == "routed"


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
    """The harness's probe at tiny size: the same greedy request, another
    request through the same lane in between, then the same again. The
    insert overwrites the lane's whole state; parking it (length 0) does
    not, and the lane steps garbage into its state while it idles."""
    first = stream(engine, 1, PROMPT_A)
    engine.release_slot(1)
    other = stream(engine, 1, PROMPT_B)
    engine.release_slot(1)
    engine.decode_steps()      # parked: the lane's state keeps moving
    again = stream(engine, 1, PROMPT_A)
    assert first == again and first != other
    engine.release_slot(1)


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


def test_the_engine_reports_the_state_and_counts_kv_for_attention_only(
        engine):
    z = mamba2.sizes(CFG)
    report = engine.ssm_report()
    assert report["mamba_layers"] == 3 and report["attention_layers"] == 1
    assert report["state_bytes_per_slot"] == 3 * z["H"] * z["P"] * z["N"] * 4
    assert report["conv_bytes_per_slot"] == 3 * 3 * z["conv"] * 2
    assert report["state_dtype"] == "float32"
    assert engine.state_bytes_per_slot() == (
        report["state_bytes_per_slot"] + report["conv_bytes_per_slot"])
    # one attention layer: K and V, 2 heads x (16 int8 + one f32 scale)
    assert engine.kv_bytes_per_token() == 2 * 1 * 2 * (16 + 4)
    moe_report = engine.moe_report()
    assert moe_report["shared_expert"]["width"] == 48
    assert moe_report["route"]["decode"] == "dense-mixture"
    dense = InferenceEngine.__new__(InferenceEngine)
    dense._has_state = False
    assert dense.state_bytes_per_slot() == 0 and dense.ssm_report() is None


def test_the_scratch_pool_is_bounded_by_rows_of_state(engine):
    """Every row of a prefill buffer carries a whole recurrent state, so the
    pool is bounded by rows (the widest batch), not by tokens alone."""
    assert engine._state_rows_max() == engine.PREFILL_BATCHES[-1]  # tiny
    per_slot = engine.state_bytes_per_slot()
    engine.STATE_SCRATCH_BYTES = 4 * per_slot           # as if it were big
    try:
        assert engine._state_rows_max() == 4
        assert engine.prefill_batches_for(16) == (1, 2, 4)
        assert engine.ssm_report()["scratch_rows_max"] == 8
        for batch, bucket in ((4, 16), (4, 32), (4, 64), (2, 16), (2, 32),
                              (1, 16), (1, 32), (1, 64), (2, 64)):
            engine._store_prefill_scratch(
                batch, bucket, engine._prefill_scratch_for(batch, bucket))
            assert sum(b for b, _ in engine._prefill_scratch) <= 8
            assert (batch, bucket) in engine._prefill_scratch
    finally:
        del engine.STATE_SCRATCH_BYTES
    # granite-4.0-h-small: 37.7 MB of state + 0.46 MB of tails a row
    granite = InferenceEngine.__new__(InferenceEngine)
    granite._has_state, granite.cache_dtype = True, jnp.bfloat16
    granite.config = llama.preset("granite-4.0-h-small")
    assert granite.state_bytes_per_slot() == 37_748_736 + 456_192
    assert granite._state_rows_max() == 4


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


@pytest.mark.parametrize("setting", sorted(CONFIG_REFUSED))
def test_each_refused_setting_is_a_config_error_before_anything_is_built(
        setting):
    from symmetry_tpu.provider.config import ConfigError, ConfigManager

    def config(**tpu):
        return {"name": "p", "public": True, "serverKey": "00" * 32,
                "modelName": "m", "apiProvider": "tpu_native",
                "tpu": {"model_preset": "tiny-hybrid",
                        "prefill_chunk": None, **tpu}}

    ConfigManager(config=config())      # the plain configuration is fine
    with pytest.raises(ConfigError, match=f"tpu.{setting}"):
        ConfigManager(config=config(**CONFIG_REFUSED[setting]))
    # the same settings on a model without recurrent layers stay legal
    plain = config(**CONFIG_REFUSED[setting])
    plain["tpu"]["model_preset"] = "tiny"
    ConfigManager(config=plain)


def test_layer_types_must_cover_the_depth():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, num_layers=5)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, layer_types=("mamba", "conv", "attention",
                                              "mamba"))
    assert hybrid.runs(CFG) == [("mamba", 0, 2), ("attention", 2, 1),
                                ("mamba", 3, 1)]
    granite = llama.preset("granite-4.0-h-small")
    assert hybrid.runs(granite) == [("mamba", 0, 5), ("attention", 5, 1),
                                    ("mamba", 6, 4)]
    assert hybrid.stack_index(granite, 6) == 5


def test_an_hf_checkpoint_round_trips_through_the_name_map(tmp_path):
    """Our tree -> HF granitemoehybrid names and layouts (fused
    input_linear, [C, 1, K] convolution, [out, in] linears) -> a
    safetensors directory -> load_checkpoint: the same config, the same
    leaves, the same logits."""
    from symmetry_tpu.engine.weights import (
        CheckpointError, convert_hf_state_dict, load_checkpoint,
        save_checkpoint)

    params, _ = make_params("float32")
    save_checkpoint(str(tmp_path), params, CFG)
    loaded, cfg = load_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert cfg == dataclasses.replace(CFG)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(params),
                                 jax.tree_util.tree_leaves_with_path(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))
    hf = hybrid.to_hf_state_dict(params, CFG)
    assert hf["model.layers.0.mamba.conv1d.weight"].shape == (160, 1, 4)
    assert hf["model.layers.2.self_attn.q_proj.weight"].shape == (64, 64)
    assert hf["model.layers.1.block_sparse_moe.input_linear.weight"
              ].shape == (8, 64, 64)
    assert hf["model.layers.3.shared_mlp.input_linear.weight"].shape == (
        96, 64)
    del hf["model.layers.3.mamba.A_log"]
    with pytest.raises(CheckpointError, match="A_log"):
        convert_hf_state_dict(hf, CFG)
