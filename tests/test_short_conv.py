"""The lfm2_moe decoder (models/hybrid.py with models/sconv.py: gated short
convolutions whose only state is a two-position tail, among GQA layers with
per-head q/k norms; two leading dense layers, then 8 experts top 2 chosen by
sigmoid scores plus a selection bias at the `tiny-sconv` preset) against the
plain reference `benchmarks/reference/sconv_moe_decoder.py`, on seeded random
weights — and what the engine does with a lane whose state is a tail.

What is compared is LOGITS. Tolerances as tests/test_gdn.py's:

- float32 weights, float32 cache: the same mathematics in another order (a
  mixture against a loop over experts, shifted products against a padded
  sum). Kept tokens agree to 2e-5 on logits of order 0.5; a token within 1e-4
  of a router tie is left out — at most a tenth may be.
- bfloat16 / int8 weights, int8 KV: the reference is fed the SAME weights
  dequantised; the median error is held to 5% of the logit scale and the
  90th percentile to 25%.

Five falsifications of the model (the bias left out of the selection, gates
from the biased scores, softmax for the sigmoid, the taps reversed, the dense
FFN at layer 2) each have to FAIL the float32 comparison.
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
from reference import sconv_moe_decoder as ref  # noqa: E402

from symmetry_tpu.engine.engine import (  # noqa: E402
    EngineError, InferenceEngine, SamplingParams)
from symmetry_tpu.engine.tokenizer import get_tokenizer  # noqa: E402
from symmetry_tpu.models import hybrid, llama, moe, sconv  # noqa: E402
from symmetry_tpu.ops.quant import (  # noqa: E402
    QuantizedTensor, dequantize)

CFG = llama.preset("tiny-sconv")
FULL = llama.preset("lfm2-8b-a1b")
EXACT = dict(eps=1e-4, atol=2e-5, max_excluded=0.10)
NOISY = dict(median=0.05, p90=0.25)
NORMS = ("norm", "q_norm", "k_norm", "final_norm")
# with `expert_bias` uniform in [-0.25, 0.25] the biased top 2 of 8 is
# another SET than the unbiased one for this share of tokens, at least
BIAS_MOVES_AT_LEAST = 0.30


def as_float32(params):
    """What the reference is fed: the program's weights, dequantised."""
    return jax.tree.map(
        lambda a: (dequantize(a) if isinstance(a, QuantizedTensor)
                   else a.astype(jnp.float32)),
        params, is_leaf=lambda a: isinstance(a, QuantizedTensor))


def make_params(weights: str, cfg=CFG, key=42):
    """Seeded weights with every norm moved off its identity."""
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


def prefill_then_decode(params, cfg, tokens, dtype, quantized, split=23):
    """Prefill of `split` tokens from empty, then single-token steps through
    the K/V cache and the tails, teacher-forced -> logits [B, S, V]."""
    run = fwd(params, cfg)
    b, s = tokens.shape
    cache = llama.init_cache(cfg, b, 64, dtype, quantized=quantized)
    first, cache = run(tokens[:, :split], cache, prefill_flash=True)
    got = [first]
    for t in range(split, s):
        logits, cache = run(tokens[:, t:t + 1], cache)
        got.append(logits)
    return jnp.concatenate(got, axis=1)


TOKENS = jax.random.randint(jax.random.key(2), (2, 31), 0, CFG.vocab_size)


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
    # K/V for the two attention layers alone; a tail a conv layer; no `ssm`
    assert cache.k.shape[0] == 2 and cache.ssm is None
    assert cache.conv.shape == (6, 2, 2, 64)


@pytest.mark.parametrize("weights", ["float32", "bfloat16", "int8"])
def test_prefill_then_8_decode_steps_through_the_cache_match_the_reference(
        weights):
    """Prefill of 23 tokens from empty, then 8 single-token steps through
    the K/V cache and the tails: against the reference's full forward over
    all 31."""
    params, dtype = make_params(weights)
    got = prefill_then_decode(params, CFG, TOKENS, dtype, weights == "int8")
    want, margins = reference(params, CFG, TOKENS)
    check(got, want, margins, weights)


def test_a_continuation_call_starts_from_the_caches_tail():
    """Several positions at once WITHOUT the empty-cache contract (chunked
    prefill, verify: refused in the engine, right in the model function)."""
    params, dtype = make_params("float32")
    run = fwd(params, CFG)
    cache = llama.init_cache(CFG, 2, 64, dtype)
    first, cache = run(TOKENS[:, :9], cache, prefill_flash=True)
    second, cache = run(TOKENS[:, 9:], cache)
    want, margins = reference(params, CFG, TOKENS)
    check(jnp.concatenate([first, second], axis=1), want, margins, "float32")


def test_prefill_flash_starts_from_an_empty_tail_whatever_the_scratch_holds():
    params, dtype = make_params("float32")
    run = fwd(params, CFG)
    clean = llama.init_cache(CFG, 2, 64, dtype)
    dirty = clean._replace(conv=jnp.full_like(clean.conv, 3.0))
    a, _ = run(TOKENS, clean, prefill_flash=True)
    b, _ = run(TOKENS, dirty, prefill_flash=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- the two forms of the mixer

def conv_layer(key=5, dtype=jnp.float32):
    params = llama.init_params(CFG, jax.random.key(key), dtype)
    return jax.tree.map(lambda a: a[1], params["layers"]["sconv"])


def test_the_step_is_the_whole_prompt_form_position_by_position():
    lp = conv_layer()
    u = jax.random.normal(jax.random.key(6), (3, 11, 64), jnp.float32)
    tail0 = jax.random.normal(jax.random.key(7), (2, 3, 64), jnp.float32)
    lens = jnp.asarray([11, 11, 11], jnp.int32)
    whole, none, tail = sconv.chunked(u, lp, None, tail0, lens, CFG)
    assert none is None
    conv, outs = tail0, []
    for t in range(11):
        out, none, conv = sconv.step_at(u[:, t], lp, None, 0, conv, CFG)
        assert none is None
        outs.append(out)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(jnp.stack(outs, axis=1)),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(conv), atol=1e-7)


def test_the_last_tap_meets_the_current_position():
    """conv.conv.weight is a cross-correlation: c_t = w0 z_{t-2} + w1 z_{t-1}
    + w2 z_t, by hand for one channel."""
    lp = conv_layer()
    u = jax.random.normal(jax.random.key(8), (1, 5, 64), jnp.float32)
    b, c, x = jnp.split(u[0] @ lp["in_proj"], 3, axis=-1)
    z = np.asarray(b * x)
    w = np.asarray(lp["conv_w"])
    conv = np.stack([
        w[2] * z[t] + (w[1] * z[t - 1] if t >= 1 else 0)
        + (w[0] * z[t - 2] if t >= 2 else 0) for t in range(5)])
    want = (np.asarray(c) * conv) @ np.asarray(lp["out_proj"])
    got, _, tail = sconv.chunked(u, lp, None, jnp.zeros((2, 1, 64)),
                                 jnp.asarray([5], jnp.int32), CFG)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(tail[:, 0]), z[3:5], atol=1e-6)


@pytest.mark.parametrize("short", [0, 1, 2, 7])
def test_a_row_stops_at_its_own_length(short):
    """Rows of unequal length in one bucket: the short row's outputs and
    tail equal its own unpadded run, whatever the padding holds; a row
    shorter than the tail keeps zeros (what it started from) ahead."""
    lp = conv_layer()
    u = jax.random.normal(jax.random.key(9), (2, 16, 64), jnp.float32)
    lens = jnp.asarray([16, short], jnp.int32)
    zeros = jnp.zeros((2, 2, 64), jnp.float32)
    out, _, tail = sconv.chunked(u, lp, None, zeros, lens, CFG)
    other = u.at[1, short:].set(99.0)       # the padding, rewritten
    out2, _, tail2 = sconv.chunked(other, lp, None, zeros, lens, CFG)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(tail2))
    np.testing.assert_array_equal(np.asarray(out[1, :short]),
                                  np.asarray(out2[1, :short]))
    if short:
        alone, _, tail1 = sconv.chunked(
            u[1:, :short], lp, None, zeros[:, :1],
            jnp.asarray([short], jnp.int32), CFG)
        np.testing.assert_allclose(np.asarray(out[1, :short]),
                                   np.asarray(alone[0]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(tail[:, 1]),
                                   np.asarray(tail1[:, 0]), atol=1e-7)
    if short < 2:
        assert not np.asarray(tail[:2 - short, 1]).any()


def test_the_tail_is_kept_in_the_caches_dtype_and_read_as_it_was_written():
    """z is rounded to the tail's dtype before either form convolves it: a
    bfloat16 step from a bfloat16 tail equals the whole-prompt form."""
    lp = conv_layer(dtype=jnp.bfloat16)
    u = jax.random.normal(jax.random.key(10), (2, 9, 64)).astype(jnp.bfloat16)
    zeros = jnp.zeros((2, 2, 64), jnp.bfloat16)
    whole, _, tail = sconv.chunked(u, lp, None, zeros,
                                   jnp.asarray([9, 9], jnp.int32), CFG)
    conv, outs = zeros, []
    for t in range(9):
        out, _, conv = sconv.step_at(u[:, t], lp, None, 0, conv, CFG)
        outs.append(out)
    assert tail.dtype == conv.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(tail.astype(jnp.float32)),
                                  np.asarray(conv.astype(jnp.float32)))
    np.testing.assert_allclose(
        np.asarray(whole.astype(jnp.float32)),
        np.asarray(jnp.stack(outs, axis=1).astype(jnp.float32)), atol=2e-2)


# ------------------------------------------------------------ falsifications

def biased_gates(x, router, k, *, score="softmax", bias=None, scale=1.0,
                 eps=1e-6):
    """`route_top_k` with the gates taken from the BIASED scores."""
    scores = jax.nn.sigmoid(jnp.dot(x, router,
                                    preferred_element_type=jnp.float32))
    top, idx = jax.lax.top_k(scores + bias, k)
    return (top / (jnp.sum(top, -1, keepdims=True) + eps) * scale,
            idx.astype(jnp.int32))


def falsified(name, params, monkeypatch):
    """(params, config) of the program with one part of the model wrong."""
    lay = params["layers"]

    def with_ffn(**leaves):
        return dict(params, layers=dict(lay, ffn=dict(lay["ffn"], **leaves)))

    if name == "bias left out of the selection":
        return with_ffn(expert_bias=jnp.zeros_like(
            lay["ffn"]["expert_bias"])), CFG
    if name == "gates from the biased scores":
        monkeypatch.setattr(moe, "route_top_k", biased_gates)
        return params, CFG
    if name == "softmax for the sigmoid":
        return params, dataclasses.replace(CFG, router_score="softmax")
    if name == "taps reversed":
        return dict(params, layers=dict(lay, sconv=dict(
            lay["sconv"], conv_w=lay["sconv"]["conv_w"][:, ::-1]))), CFG
    assert name == "the dense FFN at layer 2"
    # layer 2 gets a dense FFN (layer 1's weights again), the expert stack
    # loses its first layer
    dense = jax.tree.map(lambda a: jnp.concatenate([a, a[1:]]), lay["dense"])
    ffn = jax.tree.map(lambda a: a[1:], lay["ffn"])
    return (dict(params, layers=dict(lay, dense=dense, ffn=ffn)),
            dataclasses.replace(CFG, num_dense_layers=3))


@pytest.mark.parametrize("name", [
    "bias left out of the selection", "gates from the biased scores",
    "softmax for the sigmoid", "taps reversed", "the dense FFN at layer 2"])
def test_each_falsification_fails_the_comparison(name, monkeypatch):
    params, dtype = make_params("float32")
    want, margins = reference(params, CFG, TOKENS)
    check(prefill_then_decode(params, CFG, TOKENS, dtype, False),
          want, margins, "float32")         # the model itself passes
    wrong, cfg = falsified(name, params, monkeypatch)
    with pytest.raises(AssertionError):
        check(prefill_then_decode(wrong, cfg, TOKENS, dtype, False),
              want, margins, "float32")


# ---------------------------------------------------------------- the router

def router_inputs(tokens=256):
    x = jax.random.normal(jax.random.key(11), (tokens, 64), jnp.float32)
    params = llama.init_params(CFG, jax.random.key(12), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["ffn"])
    return x, lp


def test_the_sigmoid_router_selects_by_the_bias_and_weighs_without_it():
    x, lp = router_inputs()
    gates, experts = moe.route_top_k(x, lp["router"], 2,
                                     **moe.routing_of(CFG, lp))
    scores = jax.nn.sigmoid(x @ lp["router"])
    _, want = jax.lax.top_k(scores + lp["expert_bias"], 2)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(want))
    # (s / (s + 1e-6) with s, the two scores' sum, of order 1)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=3e-6)
    picked = jnp.take_along_axis(scores, want, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates), np.asarray(picked / (picked.sum(-1, keepdims=True)
                                                + 1e-6)), atol=1e-7)
    # `routed_scaling_factor` scales the gates and nothing else
    scaled, same = moe.route_top_k(x, lp["router"], 2, score="sigmoid",
                                   bias=lp["expert_bias"], scale=2.5)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(experts))
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(gates),
                               rtol=1e-6)


def test_the_drawn_bias_moves_the_selection_of_a_stated_share_of_tokens():
    """The published initial bias is zero, under which leaving it out
    changes nothing; the run's weights draw it in [-0.25, 0.25]."""
    x, lp = router_inputs(1024)
    _, biased = moe.route_top_k(x, lp["router"], 2, score="sigmoid",
                                bias=lp["expert_bias"])
    _, plain = moe.route_top_k(x, lp["router"], 2, score="sigmoid")
    differ = np.mean(np.any(np.sort(np.asarray(biased), -1)
                            != np.sort(np.asarray(plain), -1), axis=-1))
    assert differ >= BIAS_MOVES_AT_LEAST, differ
    assert np.abs(np.asarray(lp["expert_bias"])).max() <= 0.25


def test_the_softmax_form_is_the_default_and_takes_no_bias():
    x, lp = router_inputs(32)
    assert moe.routing_of(llama.preset("tiny-gdn"), lp) == {}
    gates, experts = moe.route_top_k(x, lp["router"], 2)
    top, idx = jax.lax.top_k(x @ lp["router"], 2)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(jax.nn.softmax(top, -1)), atol=1e-6)


@pytest.mark.parametrize("tokens,form", [(24, "dense-mixture"),
                                         (1024, "routed")])
def test_both_expert_forms_take_the_sigmoid_gates(tokens, form):
    """Mixtral's crossing (1,024) serves the tiny shape: under it the
    mixture, from it the routed form; both against a loop over experts."""
    assert moe.moe_route(tokens, 8, 2) == form
    x, lp = router_inputs(tokens)
    got, pairs = moe.moe_mlp(x[None], lp, CFG)
    want, _ = ref.moe(x, lp, hybrid.hf_config(CFG))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)
    assert int(pairs.sum()) == 2 * tokens


def test_moe_route_at_32_experts_top_4_is_a_measured_entry():
    assert (32, 4) in moe.ROUTED_FROM
    lo, least = moe.ROUTED_FROM[(32, 4)]    # the mixture's band
    assert lo == 0                          # no measured lower edge
    assert moe.moe_route(least, 32, 4) == "routed"
    if least > 1:
        assert moe.moe_route(least - 1, 32, 4) == "dense-mixture"


# ------------------------------------------------------------- the pattern

def test_runs_break_where_the_mixer_or_the_ffn_kind_changes():
    assert hybrid.runs(CFG) == [
        ("conv", 0, 2), ("full_attention", 2, 1), ("conv", 3, 3),
        ("full_attention", 6, 1), ("conv", 7, 1)]
    published = hybrid.runs(FULL)
    assert published[:3] == [("conv", 0, 2), ("full_attention", 2, 1),
                             ("conv", 3, 3)]
    assert len(published) == 13 and sum(n for _, _, n in published) == 24
    assert [FULL.ffn_kind(i) for i in range(24)] == ["dense"] * 2 + [
        "moe"] * 22
    assert FULL.layers_of("full_attention") == (2, 6, 10, 14, 18, 21)
    # a run is of one FFN kind: dense layers that outlast a conv run split it
    three = dataclasses.replace(CFG, num_dense_layers=4)
    assert hybrid.runs(three)[:4] == [
        ("conv", 0, 2), ("full_attention", 2, 1), ("conv", 3, 1),
        ("conv", 4, 2)]
    # and the older kinds' runs are what they were
    assert hybrid.runs(llama.preset("granite-4.0-h-small")) == [
        ("mamba", 0, 5), ("attention", 5, 1), ("mamba", 6, 4)]


def test_the_published_shapes_by_eval_shape():
    """The whole model's leaves from shapes alone: 8.34 B parameters, two
    FFN stacks, 147,456 bytes of tails a slot, 6,528 bytes of K/V a token."""
    shapes = jax.eval_shape(lambda: llama.init_params(
        FULL, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 62))
    lay = shapes["layers"]
    assert lay["dense"]["wg"].q.shape == (2, 2048, 7168)
    assert lay["ffn"]["wg"].q.shape == (22, 32, 2048, 1792)
    assert lay["ffn"]["expert_bias"].shape == (22, 32)
    assert lay["ffn"]["expert_bias"].dtype == jnp.float32
    assert lay["sconv"]["in_proj"].q.shape == (18, 2048, 6144)
    assert lay["sconv"]["conv_w"].shape == (18, 3, 2048)
    assert lay["attn"]["wq"].q.shape == (6, 2048, 2048)
    assert lay["attn"]["wk"].q.shape == (6, 2048, 512)
    assert "lm_head" not in shapes
    # parameters: an int8 leaf's payload (not its scales), every other leaf
    n = sum(int(np.prod((a.q if isinstance(a, QuantizedTensor) else a).shape))
            for a in jax.tree.leaves(
                shapes, is_leaf=lambda a: isinstance(a, QuantizedTensor)))
    assert 8.30e9 < n < 8.40e9, n
    cache = jax.eval_shape(lambda: llama.init_cache(
        FULL, 128, 640, jnp.bfloat16, quantized=True))
    assert cache.ssm is None and cache.conv.shape == (18, 2, 128, 2048)
    # 8 heads of 64 a position, in four pairs of 128 lanes (kv_row)
    assert cache.k.shape == (6, 128, 640, 4, 128)
    assert cache.k_scale.shape == (6, 128, 8, 640)
    assert hybrid.state_bytes_per_slot(FULL) == {"ssm": 0, "conv": 147_456}


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
    """The insert overwrites the lane's tail: it is the lane's reset. A slot
    reused after a longer stream gives a fresh one's tokens."""
    first = stream(engine, 1, PROMPT_A)
    engine.release_slot(1)
    other = stream(engine, 1, PROMPT_B, blocks=5)
    engine.release_slot(1)
    engine.decode_steps()      # parked: the lane's tail keeps moving
    again = stream(engine, 1, PROMPT_A)
    assert first == again and first != other[:len(first)]
    engine.release_slot(1)


def test_an_insert_writes_the_rows_tail_into_the_lane_and_no_other(engine):
    before = np.asarray(engine.state.cache.conv.astype(jnp.float32))
    engine.prefill_and_insert(3, PROMPT_A, GREEDY)
    after = np.asarray(engine.state.cache.conv.astype(jnp.float32))
    assert np.abs(after[:, :, 3] - before[:, :, 3]).max() > 0
    for lane in (0, 1, 2):
        np.testing.assert_array_equal(after[:, :, lane], before[:, :, lane])
    # the lane now holds what a prefill of the prompt from empty leaves: z at
    # the prompt's last two positions, not at the bucket's end
    cache = llama.init_cache(CFG, 1, 96, jnp.bfloat16, quantized=True)
    ids = jnp.zeros((1, 32), jnp.int32).at[0, :len(PROMPT_A)].set(
        jnp.asarray(PROMPT_A))
    _, cache = llama.forward_hidden(
        engine.params, CFG, ids, cache,
        jnp.asarray([len(PROMPT_A)], jnp.int32), prefill_flash=True)
    np.testing.assert_allclose(
        after[:, :, 3], np.asarray(cache.conv[:, :, 0].astype(jnp.float32)),
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
    pairs = sum(engine.expert_pairs)
    stream(engine, 0, PROMPT_B)
    engine.release_slot(0)
    assert engine.compile_cache_sizes() == before
    assert engine.counters["ssm"]["prefill_tokens"] == (
        counted["prefill_tokens"] + len(PROMPT_B))
    assert engine.counters["ssm"]["state_installs"] == (
        counted["state_installs"] + 1)
    # pairs are counted in the six expert layers alone: top 2 a token (the
    # prompt's valid positions, then 12 steps of all four lanes: idle lanes
    # step too)
    assert sum(engine.expert_pairs) - pairs == 6 * 2 * (len(PROMPT_B)
                                                        + 4 * 12)


def test_the_engine_reports_the_kind_its_tails_the_router_and_the_routes(
        engine):
    report = engine.ssm_report()
    assert report["kind"] == "short_conv" and report["layers"] == 6
    assert report["taps"] == 3 and report["attention_layers"] == 2
    assert report["state_bytes_per_slot"] == 6 * 2 * 64 * 2
    assert report["state_bytes"] == 4 * report["state_bytes_per_slot"]
    assert report["state_dtype"] == "bfloat16"
    assert report["prefill"] == {
        "form": "whole prompt, rows stop at their lengths"}
    assert report["decode"] == {"form": "step (jnp)"} == sconv.step_form(CFG)
    assert engine.state_bytes_per_slot() == report["state_bytes_per_slot"]
    # two attention layers: K and V, 2 heads x (16 int8 + one f32 scale)
    assert engine.kv_bytes_per_token() == 2 * 2 * 2 * (16 + 4)
    moe_report = engine.moe_report()
    assert moe_report["experts"] == 8 and moe_report["top_k"] == 2
    assert moe_report["router"] == {"score": "sigmoid", "bias": True,
                                    "norm_topk": True, "scale": 1.0}
    assert moe_report["dense_layers"] == 2
    assert moe_report["expert_layers"] == 6
    assert "shared_expert" not in moe_report
    # heads of 16 here are no lane tile, so decode says `xla` and why;
    # prefill takes the flash kernel. The published widths' heads of 64
    # lie in the cache in pairs and take the decode kernel at qwen2-7b's
    # tiles (four 128-lane rows a position)
    paths = engine.attention_paths()
    assert paths["prefill"] == "pallas-interpret" and paths["decode"] == "xla"
    assert "head of 16" in paths["decode_why"]
    full = llama.attention_paths(FULL, 640, None, batch=128, kv_bytes=1)
    assert full == {"prefill": "pallas-interpret",
                    "decode": "pallas-interpret",
                    "decode_slot_tile": 128, "decode_block_t": 256}
    assert llama.kv_row(FULL) == (4, 128) and llama.kv_row(CFG) == (2, 16)
    # the published widths: 147 KB of tails a row, so the scratch bound
    # leaves the widest batch there is; 6,528 bytes of K/V a token
    whole = InferenceEngine.__new__(InferenceEngine)
    whole._has_state, whole.cache_dtype = True, jnp.bfloat16
    whole._sparse, whole.kv_quant = None, True
    whole.config = FULL
    assert whole.state_bytes_per_slot() == 147_456
    assert whole._state_rows_max() == whole.PREFILL_BATCHES[-1]
    assert whole.kv_bytes_per_token() == 6_528
    # and the other kinds report as they did
    assert "router" not in (make_other("tiny-gdn").moe_report() or {})


def test_symtop_prints_the_kind_and_its_state(engine):
    import tools.symtop as symtop

    startup = {"ssm": engine.ssm_report()}
    rows = symtop.build_rows("prov", {}, None, now=0.0,
                             engine={"startup": startup})
    assert rows[0]["recur"] == "short_conv 0M"
    full = {"startup": {"ssm": {"kind": "short_conv",
                                "state_bytes": 128 * 147_456}}}
    assert symtop.read_recurrent(full) == "short_conv 18M"
    head, first = symtop.render_table(rows).splitlines()[:2]
    assert head.split()[-5] == "RECUR" and "short_conv" in first
    for other in (None, {}, {"startup": {}}):
        assert symtop.read_recurrent(other) is None


def make_other(name):
    cfg = llama.preset(name)
    params = llama.init_params(cfg, jax.random.key(0), jnp.bfloat16,
                               quantize=True)
    return InferenceEngine(
        cfg, params, get_tokenizer(None, vocab_size=cfg.vocab_size),
        max_slots=2, max_seq_len=64, prefill_buckets=(16,), decode_block=2,
        kv_quant=True, prefill_chunk=None)


REFUSED = {
    "prefix_cache_mb": dict(prefix_cache_bytes=1 << 20),
    "speculative": dict(speculative=object()),
    "prefill_chunk": dict(prefill_chunk=16),
    "role": dict(role="prefill"),
}


@pytest.mark.parametrize("setting", sorted(REFUSED))
def test_the_engine_refuses_what_cannot_carry_a_tail(setting):
    with pytest.raises(EngineError, match=f"tpu.{setting}"):
        make_engine(**REFUSED[setting])


def test_layer_types_come_in_one_familys_names_with_their_fields():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, layer_types=("conv", "mamba") * 4)
    with pytest.raises(ValueError, match="conv_L_cache"):
        dataclasses.replace(CFG, conv_L_cache=0)
    with pytest.raises(ValueError, match="router_score"):
        dataclasses.replace(CFG, router_score="tanh")
    with pytest.raises(ValueError, match="dense_intermediate_size"):
        dataclasses.replace(CFG, dense_intermediate_size=0)
    assert CFG.recurrent_kind == "conv"
    assert CFG.attention_kind == "full_attention"
    assert llama.preset("tiny-gdn").recurrent_kind == "linear_attention"
    assert llama.preset("tiny-hybrid").attention_kind == "attention"


def test_config_from_hf_reads_the_published_keys():
    hf = hybrid.hf_config(FULL)
    assert hf["model_type"] == "lfm2_moe" and hf["intermediate_size"] == 7168
    assert hf["moe_intermediate_size"] == 1792 and hf["norm_eps"] == 1e-5
    assert llama.config_from_hf(hf) == FULL
    assert llama.config_from_hf(hybrid.hf_config(CFG)) == CFG
    # the catalog's row: no head_dim, no tie key — both the family's
    row = {k: v for k, v in hf.items()
           if k not in ("head_dim", "tie_embedding", "architectures")}
    got = llama.config_from_hf(row)
    assert got.dim_per_head == 64 and got.tie_embeddings
    with pytest.raises(ValueError, match="conv_bias"):
        llama.config_from_hf(dict(hf, conv_bias=True))


def test_an_hf_checkpoint_round_trips_through_the_name_map():
    params, _ = make_params("float32")
    tensors = hybrid.to_hf_state_dict(params, CFG)
    assert tensors["model.layers.0.conv.conv.weight"].shape == (64, 1, 3)
    assert tensors["model.layers.0.conv.in_proj.weight"].shape == (192, 64)
    assert tensors["model.layers.1.feed_forward.w1.weight"].shape == (128, 64)
    assert tensors["model.layers.2.feed_forward.expert_bias"].shape == (8,)
    assert tensors["model.layers.2.feed_forward.experts.7.w2.weight"
                   ].shape == (64, 32)
    assert tensors["model.layers.2.self_attn.q_layernorm.weight"
                   ].shape == (16,)
    assert "model.layers.2.self_attn.out_proj.weight" in tensors
    assert "model.embedding_norm.weight" in tensors
    assert "model.layers.0.feed_forward.gate.weight" not in tensors
    back = hybrid.convert_hf_state_dict(tensors, CFG)
    flat_a = jax.tree_util.tree_leaves_with_path(params)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(back))
    assert len(flat_a) == len(flat_b)
    for path, a in flat_a:
        np.testing.assert_array_equal(np.asarray(a), flat_b[path])
    with pytest.raises(ValueError, match="unmapped"):
        hybrid.convert_hf_state_dict(dict(tensors, extra=np.zeros(1)), CFG)
    from symmetry_tpu.engine.weights import (
        CheckpointError, convert_hf_state_dict)

    broken = {k: v for k, v in tensors.items()
              if k != "model.layers.3.conv.in_proj.weight"}
    with pytest.raises(CheckpointError, match="lfm2_moe"):
        convert_hf_state_dict(broken, CFG)
