"""The expert FFN's two forms (models/moe.py: routed, dense mixture) and the MoE
decoder around them against the plain float32 reference (benchmarks/reference/moe_decoder.py): prefill
into the KV cache, then single-token decode through it, on seeded random
weights at `tiny-moe` widths. Logits, not tokens.

Which form a program takes follows its token count (`moe_route`), and every
shape here is under the threshold: `form="routed"` lowers the threshold to 1
for the test, `form="by-shape"` leaves it (the dense mixture, at these sizes).

Router near-ties. With random weights a token's 2nd and 3rd router logits
sometimes sit within rounding of each other, and the two sides then pick
different experts: a jump of the size of a logit that is no error. Every
comparison here leaves out tokens whose REFERENCE margin between the k-th and
(k+1)-th router logit is under a stated eps at any layer, and asserts how many
it left out.

Tolerances, and why:

- exact cases — float32 activations and a float32 KV cache, weights float32
  or int8 (the reference is fed the dequantised weights, and a per-column
  scale on the float32 accumulator is the same mathematics): both sides
  compute in float32 and differ in the order of accumulation only. atol 2e-4
  on logits of order 0.7 (measured 3e-5 at worst), eps 1e-3; a token is also
  left out when an EARLIER token of its sequence was a near-tie (its K/V may
  differ). Accumulating an expert matmul in bfloat16 (8-bit mantissa, ~4e-3
  relative) instead of float32 gives errors of 1e-2 and fails this.
- noisy cases — bfloat16 weights and activations, or an int8 KV cache: at
  these widths (hidden 64, head_dim 16) rounding every activation to 8 bits,
  or every cached K/V row to 127 levels, moves the output logits by ~0.01 at
  the median (the dense `tiny` preset under an int8 cache reaches 0.11) and
  the router's logits by up to ~0.1 — so margins of that size flip, each
  flip moving its token's logits by up to their own size (0.3-0.7), and no
  eps separates the two. The bound is therefore on the distribution of the
  per-token worst error over all tokens: median <= 0.05 (measured
  0.012-0.020) and 90th percentile <= 0.25 (measured 0.05-0.14). A path
  that is wrong (a scale, a cache layout, a dtype) errs by the size of a
  logit on every token and fails the median. These cases guard the path,
  not the last digit.
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

from reference.moe_decoder import (  # noqa: E402
    reference_logits, sparse_moe_block)

from symmetry_tpu.models import llama, moe  # noqa: E402
from symmetry_tpu.models.moe import moe_mlp  # noqa: E402
from symmetry_tpu.ops.quant import (  # noqa: E402
    QuantizedTensor, dequantize, leaf_is_sliced, make_leaf, quantize)

EXACT = dict(eps=1e-3, atol=2e-4, max_excluded=0.10)
NOISY = dict(median=0.05, p90=0.25)


FORMS = ["routed", "by-shape"]


@pytest.fixture
def form(request, monkeypatch):
    if request.param == "routed":
        monkeypatch.setattr(moe, "ROUTED_MIN_TOKENS", 1)
    return request.param


def moe_config(experts: int, **kw) -> llama.MoEConfig:
    return dataclasses.replace(llama.preset("tiny-moe"),
                               num_experts=experts, **kw)


def model_keys(cfg) -> dict:
    return {"num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "hidden_size": cfg.hidden_size,
            "num_hidden_layers": cfg.num_layers,
            "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.num_experts_per_tok}


def as_float32(params):
    """What the reference is fed: the program's weights, dequantised."""
    return jax.tree.map(
        lambda a: (dequantize(a) if isinstance(a, QuantizedTensor)
                   else a.astype(jnp.float32)),
        params, is_leaf=lambda a: isinstance(a, QuantizedTensor))


def make_params(cfg, weights: str, key=23):
    dtype = jnp.bfloat16 if weights == "bfloat16" else jnp.float32
    params = llama.init_params(cfg, jax.random.key(key), dtype)
    if weights == "int8":
        params = llama.quantize_params(params)
    return params, dtype


def prefill_then_decode(params, cfg, tokens, n_prompt, cache, **kw):
    def fwd(t, c):
        h, c = llama.forward_hidden(params, cfg, t, c, **kw)
        return llama.logits_from_hidden(params, cfg, h), c

    fwd = jax.jit(fwd)
    got, cache = fwd(tokens[:, :n_prompt], cache)
    got = [got]
    for i in range(n_prompt, tokens.shape[1]):
        step, cache = fwd(tokens[:, i:i + 1], cache)
        got.append(step)
    return np.asarray(jnp.concatenate(got, axis=1), np.float32), cache


def assert_matches_reference(got, ref_params, cfg, tokens, *, eps=None,
                             atol=None, max_excluded=None, median=None,
                             p90=None):
    kept, errors = 0, []
    for b in range(tokens.shape[0]):
        want, margins = reference_logits(ref_params, model_keys(cfg),
                                         tokens[b], with_margins=True)
        want = np.asarray(want)
        assert np.abs(want).max() > 0.05, "logits too small to tell"
        errors.append(np.abs(got[b] - want).max(axis=-1))
        if eps is not None:
            ok = ~np.logical_or.accumulate(
                (np.asarray(margins) < eps).any(axis=0))
            kept += ok.sum()
            np.testing.assert_allclose(got[b][ok], want[ok], atol=atol,
                                       rtol=0)
    errors = np.concatenate(errors)
    if eps is not None:
        assert 1 - kept / errors.size <= max_excluded, (
            f"{1 - kept / errors.size:.0%} of the tokens were near-ties")
    else:
        assert np.median(errors) <= median, np.median(errors)
        assert np.quantile(errors, 0.9) <= p90, np.quantile(errors, 0.9)


@pytest.mark.parametrize("kv", ["dense", "int8"])
@pytest.mark.parametrize("weights", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("experts", [4, 8])
@pytest.mark.parametrize("form", FORMS, indirect=True)
def test_prefill_then_decode_matches_the_plain_reference(form, experts,
                                                         weights, kv):
    cfg = moe_config(experts)
    params, dtype = make_params(cfg, weights)
    tokens = jax.random.randint(jax.random.key(1), (4, 40), 0,
                                cfg.vocab_size)
    cache = llama.init_cache(cfg, 4, 64, dtype, quantized=kv == "int8")
    got, _ = prefill_then_decode(params, cfg, tokens, 29, cache)
    exact = weights != "bfloat16" and kv == "dense"
    assert_matches_reference(got, as_float32(params), cfg, tokens,
                             **(EXACT if exact else NOISY))


def skewed_layer(cfg, routing: str, weights: str):
    """One layer's params and T = 256 tokens whose routing is skewed: every
    token carries 4 x a fixed unit direction u, and the router adds
    bias_e x (x . u) to expert e's logit. `same-two`: experts 0 and 1 for
    every token — with 8 experts, 512 pairs on two experts, where a
    capacity of 2 x the mean (128 a expert) dropped 256 of them. `zipf`:
    bias log(1 / rank), so expert e is picked about 1/(e+1) as often."""
    X, D = cfg.num_experts, cfg.hidden_size
    params = llama.init_params(cfg, jax.random.key(7), jnp.float32)
    lp = {k: v[0] for k, v in params["layers"].items()}
    u = jnp.ones((D,), jnp.float32) / np.sqrt(D)
    if routing == "same-two":
        bias = jnp.asarray([8.0, 7.0] + [0.0] * (X - 2))
        lp["router"] = 0.05 * lp["router"] + jnp.outer(u, bias)
    else:
        bias = jnp.log(1.0 / jnp.arange(1, X + 1))
        lp["router"] = lp["router"] + jnp.outer(u, bias)
    x = jax.random.normal(jax.random.key(8), (4, 64, D)) + 4.0 * u
    if weights == "int8":
        for name in ("wg", "wu", "wd"):
            lp[name] = quantize(lp[name])
    return lp, x


@pytest.mark.parametrize("weights", ["float32", "int8"])
@pytest.mark.parametrize("routing", ["same-two", "zipf"])
@pytest.mark.parametrize("experts", [4, 8])
@pytest.mark.parametrize("form", FORMS, indirect=True)
def test_neither_form_drops_a_pair_under_skew(form, experts, routing,
                                              weights):
    """The block alone against the reference's loop over experts. Both in
    float32, another order of accumulation: atol 4e-6 of the largest output
    (the inputs are not normalised here; measured 1.4e-6); eps 1e-3."""
    cfg = moe_config(experts)
    lp, x = skewed_layer(cfg, routing, weights)
    got, pairs = jax.jit(lambda x, lp: moe_mlp(x, lp, cfg))(x, lp)
    ref = as_float32(lp)
    want, margin = sparse_moe_block(
        x.reshape(-1, cfg.hidden_size), ref["router"], ref["wg"],
        ref["wu"], ref["wd"], cfg.num_experts_per_tok)
    ok = np.asarray(margin) >= 1e-3
    assert ok.mean() >= 0.95
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 0.5
    np.testing.assert_allclose(
        np.asarray(got).reshape(-1, cfg.hidden_size)[ok],
        np.asarray(want)[ok], atol=4e-6 * scale, rtol=0)
    pairs = np.asarray(pairs)
    assert pairs.sum() == 256 * cfg.num_experts_per_tok  # every pair
    if routing == "same-two":
        assert pairs.tolist() == [256, 256] + [0] * (experts - 2)
    else:
        assert pairs[0] > 2 * pairs[-1], pairs


@pytest.mark.parametrize("form", FORMS, indirect=True)
def test_padded_positions_are_computed_but_not_counted(form):
    cfg = moe_config(4)
    lp, x = skewed_layer(cfg, "zipf", "float32")
    seq_lens = jnp.asarray([64, 10, 1, 0], jnp.int32)
    full, _ = moe_mlp(x, lp, cfg)
    got, pairs = moe_mlp(x, lp, cfg, seq_lens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))
    assert int(pairs.sum()) == 75 * cfg.num_experts_per_tok


@pytest.mark.parametrize("weights,grouped", [("int8", "moe_gmm"),
                                             ("float32", "ragged_dot")])
def test_the_form_follows_the_token_count(weights, grouped):
    """The mixture under the crossing, the routed form from it — over the
    Pallas grouped matmul for an int8 stack on one device, over
    `lax.ragged_dot` for any other."""
    cfg = moe_config(8)
    lp, _ = skewed_layer(cfg, "zipf", weights)
    assert moe.moe_route(64) == "dense-mixture"
    assert moe.moe_route(moe.ROUTED_MIN_TOKENS) == "routed"
    for tokens, routed in ((64, False), (moe.ROUTED_MIN_TOKENS, True)):
        x = jnp.zeros((1, tokens, cfg.hidden_size))
        jaxpr = str(jax.make_jaxpr(lambda x: moe_mlp(x, lp, cfg))(x))
        assert (grouped in jaxpr) == routed
        (other,) = {"moe_gmm", "ragged_dot"} - {grouped}
        assert other not in jaxpr


# What each routing shape's single "routed from" threshold was before an
# entry became the band of counts the mixture keeps (PR 57); None: the shape
# without a reading of its own, which takes mixtral's `ROUTED_MIN_TOKENS`.
THRESHOLD_WAS = {(72, 10): 256, (512, 10): 1, (128, 8): 128, (32, 4): 256,
                 (128, 6): 128, None: 1024}


@pytest.mark.parametrize("shape", list(THRESHOLD_WAS), ids=str)
def test_a_band_answers_as_its_threshold_did(shape):
    """Every entry is `(lo, hi)`: the mixture iff lo <= tokens < hi. At
    every count the old threshold decided — under a lower edge only (128, 8)
    has one — the answer is the one it gave."""
    # (an entry newer than the bands had no threshold to answer as; a
    # three-part key is a SHARE's: held, k, routed over — PR 61, PR 65)
    assert set(moe.ROUTED_FROM) == set(THRESHOLD_WAS) - {None} | {
        (64, 6), (32, 6, 128), (16, 8, 128)}
    assert moe.ROUTED_FROM[(16, 8, 128)] == (0, 1)      # routed at any size
    assert moe.moe_route(128, 128, 8, held=16) == "routed"
    lo, hi = moe.ROUTED_FROM.get(shape, (0, moe.ROUTED_MIN_TOKENS))
    assert hi == THRESHOLD_WAS[shape] and 0 <= lo < hi
    assert (lo > 0) == (shape == (128, 8))
    args = shape or (8, 2)
    for tokens in (1, lo - 1, lo, hi - 1, hi, 4096):
        if tokens < max(lo, 1):
            continue                    # the band's new side, or no count
        was = "routed" if tokens >= THRESHOLD_WAS[shape] else "dense-mixture"
        assert moe.moe_route(tokens, *args) == was, tokens


@pytest.mark.parametrize("tokens,form", [
    (1, "routed"), (16, "routed"), (31, "routed"), (32, "dense-mixture"),
    (64, "dense-mixture"), (128, "dense-mixture"), (255, "dense-mixture"),
    (256, "routed"), (1024, "routed"), (8320, "routed")])
def test_64_top_6_keeps_the_mixture_from_32_to_255(tokens, form):
    """smallthinker's shape, from its own reading (PR 58; `models/moe.py`'s
    table): the kernel under 32 tokens (few experts hit), the mixture
    through decode's 64 slots and the tie at 128, routed from 256 — every
    prefill of its cell."""
    assert moe.moe_route(tokens, 64, 6) == form


@pytest.mark.parametrize("tokens,form", [
    (4, "routed"), (8, "routed"), (16, "routed"), (32, "routed"),
    (63, "routed"), (64, "dense-mixture"), (127, "dense-mixture"),
    (128, "routed"), (512, "routed")])
def test_128_top_8_keeps_the_mixture_from_64_to_127_alone(tokens, form):
    """sdar's opening blocks (batch 1-8 x 4 positions) are routed: the
    kernel reads the hit experts, the mixture all 128 (`models/moe.py`'s
    table). 64 (keye's decode, sdar's 16-row opening block and its 1 x 64
    prompt forward) keeps the mixture; 128 and up were always routed."""
    assert moe.moe_route(tokens, 128, 8) == form


def small_dispatch(tokens: int, routing: str):
    """One layer of 128 experts top 8 (int8 stacks [1, 128, 64, 32]) and
    `tokens` rows as an admission's opening block holds them. `pad-rows`:
    the last request's rows repeated, bit-identical, to fill the batch (the
    scheduler's pad rows). `same-eight`: every token picks experts 0..7, so
    eight groups hold all the rows and 120 are empty."""
    X, k, D, F = 128, 8, 64, 32
    keys = jax.random.split(jax.random.key(57), 5)
    stacks = {"wg": make_leaf(keys[0], (1, X, D, F), D ** -0.5,
                              jnp.float32, True),
              "wu": make_leaf(keys[1], (1, X, D, F), D ** -0.5,
                              jnp.float32, True),
              "wd": make_leaf(keys[2], (1, X, F, D), F ** -0.5,
                              jnp.float32, True)}
    router = jax.random.normal(keys[3], (D, X), jnp.float32)
    x = jax.random.normal(keys[4], (tokens, D), jnp.float32)
    if routing == "pad-rows":
        x = jnp.tile(x[:4], (tokens // 4, 1))
    else:
        u = jnp.ones((D,), jnp.float32) / np.sqrt(D)
        bias = jnp.asarray([9.0 - 0.25 * e for e in range(k)]
                           + [0.0] * (X - k))
        router = 0.05 * router + jnp.outer(u, bias)
        x = x + 4.0 * u
    return stacks, router, x, k


@pytest.mark.parametrize("routing", ["pad-rows", "same-eight"])
@pytest.mark.parametrize("tokens", [4, 8, 16, 32])
def test_a_small_dispatch_is_the_same_sum_in_both_forms(tokens, routing):
    """What (128, 8)'s lower edge changes: 4-32 tokens through the routed
    form over the kernel (32-256 rows; a row tile of 32 at 32 rows) against
    the mixture they took before and against the float32 reference's loop
    over experts. All three in float32 on the dequantised int8 weights,
    another order of accumulation: atol 4e-6 of the largest output."""
    from reference import block_diffusion_moe_decoder as bd_ref

    stacks, router, x, k = small_dispatch(tokens, routing)
    lp = {name: jax.tree.map(lambda a: a[0], w)
          for name, w in stacks.items()}
    valid = jnp.ones((tokens,), bool)
    form = moe.grouped_matmul_form(stacks["wg"], tokens * k)
    assert form == {"form": "pallas-interpret",
                    "row_tile": min(64, tokens * k)}
    routed, pairs = jax.jit(
        lambda x: moe._routed_ffn(x, valid, router, lp["wg"], lp["wu"],
                                  lp["wd"], k, (stacks, jnp.int32(0))))(x)
    mixture, pairs_m = jax.jit(
        lambda x: moe._dense_mixture(x, valid, router, lp["wg"], lp["wu"],
                                     lp["wd"], k))(x)
    with jax.default_matmul_precision("highest"):
        want, margin = bd_ref.sparse_moe_block(
            x, router, *(dequantize(lp[n]) for n in ("wg", "wu", "wd")), k)
    assert float(np.asarray(margin).min()) >= 1e-3
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 0.05
    for got in (routed, mixture):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=4e-6 * scale, rtol=0)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(pairs_m))
    assert int(pairs.sum()) == tokens * k
    if routing == "same-eight":
        assert np.asarray(pairs).tolist() == [tokens] * k + [0] * (128 - k)
    else:
        # a pad row is the row it repeats, bit for bit, in the routed form
        np.testing.assert_array_equal(
            np.asarray(routed), np.tile(np.asarray(routed[:4]),
                                        (tokens // 4, 1)))


@pytest.mark.parametrize("kv", ["dense", "int8"])
@pytest.mark.parametrize("weights", ["float32", "int8"])
@pytest.mark.parametrize("form", FORMS, indirect=True)
def test_model4_on_four_devices_matches_the_reference_and_unsharded(
        form, weights, kv):
    """The cell's layout, `mesh {model: 4}`: experts' FFN width, heads, KV
    heads and vocabulary over `model`, the routed FFN and the attention
    kernels per shard (shard_map). Against the unsharded program: float32
    on both sides, the psum adds the four partial sums in another order,
    atol 2e-4. Against the reference: as the unsharded cases."""
    from symmetry_tpu.parallel import MeshSpec, build_mesh, shardings_for

    cfg = llama.preset("tiny-moe8")  # 4 KV heads: one a device
    params, dtype = make_params(cfg, weights)
    tokens = jax.random.randint(jax.random.key(2), (2, 24), 0,
                                cfg.vocab_size)
    quantized = kv == "int8"

    def cache():
        return llama.init_cache(cfg, 2, 32, dtype, quantized=quantized,
                                count_experts=True)

    plain, plain_cache = prefill_then_decode(params, cfg, tokens, 17,
                                             cache())
    mesh = build_mesh(MeshSpec(model=4), jax.devices()[:4])
    axes = llama.param_logical_axes(cfg)
    if weights == "int8":
        axes = llama.quantized_logical_axes(axes)
    sharded = jax.device_put(params, shardings_for(axes, mesh))
    cache_shard = llama.KVCache(*(
        None if axes is None else shardings_for(axes, mesh)
        for axes in llama.cache_logical_axes(quantized=quantized,
                                             count_experts=True)))
    got, got_cache = prefill_then_decode(
        sharded, cfg, tokens, 17, jax.device_put(cache(), cache_shard),
        tp_mesh=mesh)
    np.testing.assert_allclose(got, plain, atol=2e-4, rtol=0)
    np.testing.assert_array_equal(np.asarray(got_cache.expert_pairs),
                                  np.asarray(plain_cache.expert_pairs))
    assert int(got_cache.expert_pairs.sum()) == (
        2 * 24 * cfg.num_experts_per_tok * cfg.num_layers)
    assert_matches_reference(got, as_float32(params), cfg, tokens,
                             **(NOISY if quantized else EXACT))


# ---------------------------------------------------------------------------
# Initialisation: a leaf that fits keeps the one-piece form and its values.


def one_piece_init(cfg, key, dtype, quantize_leaves):
    """init_params as the parent of PR 28 computed it: one make_leaf per
    leaf, keys in this order."""
    keys = iter(jax.random.split(key, 16))
    L, E, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size

    def leaf(shape, scale=None, name=None):
        return make_leaf(next(keys), shape,
                         scale if scale is not None else shape[0] ** -0.5,
                         dtype, quantized=quantize_leaves
                         and name in llama.QUANT_KEYS)

    out = {"embed": leaf((cfg.vocab_size, E), 0.02), "layers": {}}
    for name, shape in (("wq", (L, E, cfg.q_dim)), ("wk", (L, E, cfg.kv_dim)),
                        ("wv", (L, E, cfg.kv_dim)), ("wo", (L, cfg.q_dim, E)),
                        ("wg", (L, E, F)), ("wu", (L, E, F)),
                        ("wd", (L, F, E))):
        out["layers"][name] = leaf(shape, name=name)
    out["lm_head"] = leaf((E, cfg.vocab_size), 0.02, "lm_head")
    return out


@pytest.mark.parametrize("quantize_leaves", [False, True])
@pytest.mark.parametrize("preset", ["tiny", "tiny-qwen"])
def test_dense_presets_keep_their_random_weights(preset, quantize_leaves):
    """Bit for bit, also when init runs sharded under `model: 4` with a
    slicing limit in force that these leaves stay under."""
    from symmetry_tpu.parallel import MeshSpec, build_mesh, shardings_for

    cfg = llama.preset(preset)
    want = one_piece_init(cfg, jax.random.key(0), jnp.bfloat16,
                          quantize_leaves)
    mesh = build_mesh(MeshSpec(model=4), jax.devices()[:4])
    axes = llama.param_logical_axes(cfg)
    if quantize_leaves:
        axes = llama.quantized_logical_axes(axes)
    shardings = shardings_for(axes, mesh)
    got = jax.jit(
        lambda: llama.init_params(
            cfg, jax.random.key(0), jnp.bfloat16, quantize=quantize_leaves,
            shardings=shardings, slice_above=1 << 20),
        out_shardings=shardings)()
    for name in ("embed", "lm_head"):
        for a, b in zip(jax.tree.leaves(got[name]),
                        jax.tree.leaves(want[name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name, leaf in want["layers"].items():
        for a, b in zip(jax.tree.leaves(got["layers"][name]),
                        jax.tree.leaves(leaf)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


V5E_BYTES = 16_909_336_576  # one v5e chip's `bytes_limit`


@pytest.mark.parametrize("preset,mesh_model,sliced", [
    ("mistral-7b", 1, set()), ("qwen2-7b", 1, set()),
    ("mixtral-8x7b", 4, {"wg", "wu", "wd"})])
def test_which_leaves_are_built_a_layer_at_a_time(preset, mesh_model,
                                                  sliced):
    """Shape arithmetic only: at a v5e's limit the two one-chip benchmark
    models keep every leaf in one piece (their cells' outputs are judged),
    and mixtral-8x7b under `model: 4` slices exactly its expert stacks."""
    from symmetry_tpu.parallel import MeshSpec, build_mesh, shardings_for

    cfg = llama.preset(preset)
    mesh = build_mesh(MeshSpec(model=mesh_model),
                      jax.devices()[:mesh_model])
    shardings = shardings_for(llama.param_logical_axes(cfg), mesh)
    shapes = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, slice_above=None))
    got = {name for name, s in shapes["layers"].items()
           if name in llama.STACKED_KEYS and leaf_is_sliced(
               s.shape, jnp.bfloat16, shardings["layers"][name],
               V5E_BYTES // 4)}
    assert got == sliced


@pytest.mark.parametrize("sharded", [False, True])
def test_sliced_init_is_the_same_sharded_and_quantises_the_dense_slices(
        sharded):
    """Forced on `tiny-moe` by a limit of 0: each layer's slice comes from
    its own key (so layers differ), the int8 form is the quantisation of
    the dense form, and under `model: 4` the values are those of one
    device (the partitionable threefry)."""
    from symmetry_tpu.parallel import MeshSpec, build_mesh, shardings_for

    cfg = llama.preset("tiny-moe")

    def init(q, shardings=None):
        return llama.init_params(cfg, jax.random.key(3), jnp.float32,
                                 quantize=q, shardings=shardings,
                                 slice_above=0)

    dense, quant = init(False), init(True)
    if sharded:
        mesh = build_mesh(MeshSpec(model=4), jax.devices()[:4])
        sh = shardings_for(llama.quantized_logical_axes(
            llama.param_logical_axes(cfg)), mesh)
        quant = jax.jit(lambda: init(True, sh), out_shardings=sh)()
        assert quant["layers"]["wg"].q.sharding.spec[-1] == "model"
    for name in ("wq", "wo", "wg", "wd"):
        w = dense["layers"][name]
        assert w.shape == llama.init_params(
            cfg, jax.random.key(3), jnp.float32)["layers"][name].shape
        assert not np.array_equal(np.asarray(w[0]), np.asarray(w[1]))
        # init scales a stacked leaf by shape[0] ** -0.5, as make_leaf does
        assert abs(float(jnp.std(w)) * w.shape[0] ** 0.5 - 1) < 0.05
        want = quantize(w)
        np.testing.assert_array_equal(np.asarray(quant["layers"][name].q),
                                      np.asarray(want.q))
        np.testing.assert_allclose(np.asarray(quant["layers"][name].scale),
                                   np.asarray(want.scale), rtol=1e-6)


# ---------------------------------------------------------------------------
# The engine's counters and report.


def test_engine_counts_expert_pairs_and_reports_the_route():
    from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
    from symmetry_tpu.engine.tokenizer import ByteTokenizer

    cfg = llama.preset("tiny-moe")
    params = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    eng = InferenceEngine(cfg, params, ByteTokenizer(), max_slots=2,
                          max_seq_len=64, prefill_buckets=(16,),
                          cache_dtype=jnp.float32, decode_block=4)
    per_token = cfg.num_experts_per_tok * cfg.num_layers
    eng.prefill_and_insert(0, list(b"ten tokens"), SamplingParams())
    assert sum(eng.expert_pairs) == 0  # comes back with a decode block
    eng.decode_steps()
    # the prompt's 10 valid tokens, then 4 steps of both slots
    assert sum(eng.expert_pairs) == (10 + 4 * 2) * per_token
    eng.decode_steps()
    assert sum(eng.expert_pairs) == (10 + 8 * 2) * per_token
    assert len(eng.expert_pairs) == cfg.num_experts
    report = eng.moe_report()
    # 2 slots a decode step; a prefill dispatch is 1 or 2 prompts x 16
    assert report["route"] == {"decode": "dense-mixture",
                               "prefill": {"16": "dense-mixture",
                                           "32": "dense-mixture"}}
    assert report["experts"] == 4 and report["top_k"] == 2
    assert "one device" in report["layout"]
    # float32 stacks: the routed form would run over lax.ragged_dot
    assert report["grouped_matmul"] == {
        "form": "ragged_dot", "why": "the expert stack is not int8"}


def test_a_dense_engine_reports_no_moe():
    from symmetry_tpu.engine.engine import InferenceEngine
    from symmetry_tpu.engine.tokenizer import ByteTokenizer

    cfg = llama.preset("tiny")
    eng = InferenceEngine(cfg, llama.init_params(cfg, jax.random.key(0),
                                                 jnp.float32),
                          ByteTokenizer(), max_slots=2, max_seq_len=64,
                          prefill_buckets=(16,), cache_dtype=jnp.float32)
    assert eng.moe_report() is None and eng.expert_pairs == []
    eng.decode_steps()
    assert eng.state.cache.expert_pairs is None
