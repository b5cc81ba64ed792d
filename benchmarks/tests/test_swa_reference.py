"""The plain float32 reference `reference/swa_moe_decoder.py` (every
position's attention over the whole sequence under its layer's own mask, the
router on the layer's input, ReGLU experts as a loop) against the program's
model path — the full forward in one call over a uniform cache, and a prefill
from empty through the flash kernel followed by single positions through the
full leaves and the rings — at `tiny-swa` widths on seeded random weights:
logits.

Tolerance: both sides compute in float32 on the CPU and differ in the order of
accumulation: 3e-5 of the logit scale (measured 1.4e-6). The repo's tier-1
suite (`tests/test_window_attention.py`) runs the wider grid."""

import jax
import jax.numpy as jnp
import numpy as np

import reference.swa_moe_decoder as ref


def program():
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-swa")
    return llama, config, llama.hf_config_window(config), llama.init_params(
        config, jax.random.key(58), jnp.float32)


def test_the_reference_imports_nothing_from_the_program():
    source = open(ref.__file__).read()
    assert "symmetry_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "Departures from the published code" in source


def test_the_full_forward_matches_the_reference():
    llama, config, model, params = program()
    tokens = jax.random.randint(jax.random.key(1), (1, 37), 0, 500)
    cache = llama.init_cache(config, 1, 64, jnp.float32, ring=64)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(params, config, tokens, cache)
        want, margins = ref.reference_logits(params, model, tokens[0],
                                             with_margins=True)
    assert np.abs(np.asarray(got[0] - want)).max() < 3e-5 * float(
        jnp.abs(want).max())
    assert margins.shape == (8, 37)
    assert np.isfinite(np.asarray(margins)).all()


def test_prefill_then_decode_through_both_leaves_match_the_reference():
    llama, config, model, params = program()
    ids = jax.random.randint(jax.random.key(2), (45,), 0, 500)
    P, W = 29, config.sliding_window
    scratch = llama.init_cache(config, 1, 32, jnp.float32)
    assert scratch.kw.shape == (6, 1, 32, 2, 16)        # plain rows
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :P].set(ids[:P])
    step = jax.jit(lambda t, c: llama.forward_hidden(params, config, t, c))
    with jax.default_matmul_precision("highest"):
        h, scratch = llama.forward_hidden(params, config, padded, scratch,
                                          jnp.asarray([P]),
                                          prefill_flash=True)
        rows = [llama.logits_from_hidden(params, config, h)[0, :P]]
        cache = llama.init_cache(config, 1, 64, jnp.float32, ring=W)
        assert cache.kw.shape == (6, 1, W, 2, 16)       # rings
        at = jnp.arange(P - W, P)
        cache = cache._replace(
            k=cache.k.at[:, :, :32].set(scratch.k),
            v=cache.v.at[:, :, :32].set(scratch.v),
            kw=cache.kw.at[:, :, at % W].set(scratch.kw[:, :, at]),
            vw=cache.vw.at[:, :, at % W].set(scratch.vw[:, :, at]),
            lengths=jnp.asarray([P], jnp.int32))
        for t in ids[P:]:
            h, cache = step(t[None, None], cache)
            rows.append(llama.logits_from_hidden(params, config, h)[0])
        want = ref.reference_logits(params, model, ids)
    got = jnp.concatenate(rows)
    assert np.abs(np.asarray(got - want)).max() < 3e-5 * float(
        jnp.abs(want).max())


def test_a_layer_at_a_time_and_tiled_queries_are_the_whole_pass():
    _, config, model, params = program()
    ids = jax.random.randint(jax.random.key(3), (23,), 0, 500)
    want = ref.reference_logits(params, model, ids)
    h = ref.embed(params, model, ids)
    for i in range(8):
        name, j = ref.stack_of(model, i)
        assert name == ("attn" if i % 4 == 0 else "swa")
        one = {"layers": {
            name: {k: v[j:j + 1] for k, v in
                   params["layers"][name].items()},
            "ffn": {k: v[i:i + 1] for k, v in
                    params["layers"]["ffn"].items()}}}
        taps = {}
        h, _ = ref.layer_forward(
            one, dict(model, num_hidden_layers=1,
                      sliding_window_layout=[model["sliding_window_layout"][i]],
                      rope_layout=[model["rope_layout"][i]]), h, 0, taps,
            tile=8)
        assert taps["attn"].shape == (23, 4, 16)
        assert taps["k"].shape == (23, 2, 16)
        assert taps["experts"].shape == (23, 2)
    np.testing.assert_allclose(ref.head(params, model, h), want, atol=2e-6)


def test_each_wrong_variant_is_told_from_the_stated_one():
    _, config, model, params = program()
    ids = jax.random.randint(jax.random.key(4), (40,), 0, 500)
    want = ref.reference_logits(params, model, ids)
    scale = float(jnp.abs(want).max())
    for wrong, least in (("rope_full", 0.1), ("window_short", 0.01),
                         ("router_normed", 0.1), ("silu", 0.01)):
        got = ref.reference_logits(params, model, ids, wrong=wrong)
        assert float(jnp.abs(got - want).max()) / scale > least, wrong
    soft = ref.reference_logits(params, model, ids,
                                softmax_dtype=jnp.bfloat16)
    # (a bfloat16 softmax moves a near-tie of the router at these widths:
    # a row then errs by an expert's output, not by a rounding)
    assert 1e-4 < float(jnp.abs(soft - want).max()) / scale < 1.0
