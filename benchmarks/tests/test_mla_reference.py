"""The plain float32 reference `reference/latent_moe_decoder.py` (expanded
attention for every position, routed and shared experts as loops) against the
program's model path — the full forward in one call, and a prefill from empty
through the flash kernel followed by single positions through the latent
cache and the absorbed decode kernel — at `tiny-mla` widths on seeded random
weights: logits.

Tolerance: both sides compute in float32 on the CPU and differ in the order of
accumulation: 3e-5 of the logit scale (measured 1e-6). The repo's tier-1
suite (`tests/test_latent_attention.py`) runs the wider grid."""

import jax
import jax.numpy as jnp
import numpy as np

import reference.latent_moe_decoder as ref


def program():
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-mla")
    return llama, config, llama.hf_config_latent(config), llama.init_params(
        config, jax.random.key(54), jnp.float32)


def test_the_reference_imports_nothing_from_the_program():
    source = open(ref.__file__).read()
    assert "symmetry_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "Departures from HF's `DeepseekV3`" in source


def test_the_full_forward_matches_the_reference():
    llama, config, model, params = program()
    tokens = jax.random.randint(jax.random.key(1), (1, 37), 0, 500)
    cache = llama.init_cache(config, 1, 64, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(params, config, tokens, cache)
        want, margins = ref.reference_logits(params, model, tokens[0],
                                             with_margins=True)
    assert np.abs(np.asarray(got[0] - want)).max() < 3e-5 * float(
        jnp.abs(want).max())
    assert margins.shape == (3, 37)
    assert np.isinf(np.asarray(margins[0])).all()       # the dense layer
    assert np.isfinite(np.asarray(margins[1:])).all()


def test_prefill_then_decode_through_the_latent_cache_match_the_reference():
    llama, config, model, params = program()
    ids = jax.random.randint(jax.random.key(2), (45,), 0, 500)
    P = 29
    cache = llama.init_cache(config, 1, 128, jnp.float32)
    assert cache.v is None and cache.k.shape == (3, 1, 128, 128)
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :P].set(ids[:P])
    with jax.default_matmul_precision("highest"):
        h, cache = llama.forward_hidden(params, config, padded, cache,
                                        jnp.asarray([P]), prefill_flash=True)
        rows = [llama.logits_from_hidden(params, config, h)[0, :P]]
        for t in ids[P:]:
            h, cache = llama.forward_hidden(params, config, t[None, None],
                                            cache)
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
    for i in range(3):
        dense = i < 1
        one = {"layers": {
            "attn": {k: v[i:i + 1] for k, v in
                     params["layers"]["attn"].items()},
            **({"dense": params["layers"]["dense"]} if dense else
               {"ffn": {k: v[i - 1:i] for k, v in
                        params["layers"]["ffn"].items()}})}}
        taps = {}
        h, _ = ref.layer_forward(
            one, dict(model, first_k_dense_replace=int(dense)), h, 0, taps,
            tile=8)
        assert taps["attn"].shape == (23, 4, 12)
        assert taps["latent"].shape == (23, 24)
    np.testing.assert_allclose(ref.head(params, model, h), want, atol=2e-6)


def test_each_wrong_variant_is_told_from_the_stated_one():
    _, config, model, params = program()
    p = {k: v[0] for k, v in params["layers"]["attn"].items()}
    x = jax.random.normal(jax.random.key(4), (40, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x, p, model)
        for wrong, least in (("no_kv_norm", 0.05), ("rope_halves", 0.05),
                             ("latent_int8", 1e-3)):
            got = ref.attention(x, p, model, wrong=wrong)
            err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            assert err > least, (wrong, err)
        soft = ref.attention(x, p, model, softmax_dtype=jnp.bfloat16)
    assert 1e-4 < float(jnp.linalg.norm(soft - want)
                        / jnp.linalg.norm(want)) < 0.05
