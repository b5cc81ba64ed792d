"""The plain float32 reference against the program's model path (prefill
into the KV cache, then single-token decode through it) at tiny widths, on
seeded random weights. Logits, not tokens: with random weights the largest
logit flips on rounding.

Tolerance: both sides compute in float32 on the CPU; they differ in the order
of accumulation (fused vs plain attention, cache read-back), so 2e-4 absolute
on logits of order 1 — two orders tighter than what a bfloat16 (8-bit
mantissa, ~4e-3 relative) or int8 computation would give, so computing in a
lower precision than stated fails it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference.dense_decoder import reference_logits


@pytest.mark.parametrize("preset", ["tiny", "tiny-qwen"])
def test_prefill_then_decode_matches_the_plain_reference(preset):
    from symmetry_tpu.models import llama

    config = llama.preset(preset)
    params = llama.init_params(config, jax.random.key(23), jnp.float32)
    if config.attention_bias:  # zero biases would prove nothing
        k = jax.random.split(jax.random.key(5), 3)
        for name, kk in zip(("bq", "bk", "bv"), k):
            params["layers"][name] = 0.1 * jax.random.normal(
                kk, params["layers"][name].shape, jnp.float32)
    model = {"num_attention_heads": config.num_heads,
             "num_key_value_heads": config.num_kv_heads,
             "hidden_size": config.hidden_size,
             "num_hidden_layers": config.num_layers,
             "rms_norm_eps": config.rms_eps, "rope_theta": config.rope_theta}
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0,
                                config.vocab_size)
    n_prompt = 17
    cache = llama.init_cache(config, 2, 64, jnp.float32)
    got, cache = llama.forward(params, config, tokens[:, :n_prompt], cache)
    got = [got]
    for i in range(n_prompt, tokens.shape[1]):
        step, cache = llama.forward(params, config, tokens[:, i:i + 1], cache)
        got.append(step)
    got = np.asarray(jnp.concatenate(got, axis=1))
    for b in range(2):
        want = np.asarray(reference_logits(params, model, tokens[b]))
        np.testing.assert_allclose(got[b], want, atol=2e-4, rtol=0)
    assert np.abs(want).max() > 0.05, "logits too small to tell anything"
