"""The plain float32 hybrid reference (`reference/hybrid_decoder.py`: Mamba-2
layers as a scan over time, attention without rotary embedding, routed and
shared experts as a loop) against the program's model path — prefill from
empty through the chunked form, then single-token steps through the K/V
cache, the recurrent state and the conv tail — at `tiny-hybrid` widths, on
seeded random weights. Logits, not tokens.

Tolerance: both sides compute in float32 on the CPU and differ in the order
of accumulation (a chunked dual form against the recurrence, grouped against
per-expert matmuls): 2e-5 absolute on logits of order 0.3 (measured 2e-7). A
token within 1e-4 of a router tie may route otherwise on the two sides; it is
left out, and at most a tenth may be. The repo's tier-1 suite
(`tests/test_hybrid.py`) runs bfloat16 and int8 weights and the engine."""

import jax
import jax.numpy as jnp
import numpy as np

from reference.hybrid_decoder import reference_logits, run_layers, embed, head


def model_keys(c) -> dict:
    from symmetry_tpu.models import hybrid

    return hybrid.hf_config(c)


def test_prefill_then_decode_matches_the_plain_reference():
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-hybrid")
    params = llama.init_params(config, jax.random.key(33), jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0,
                                config.vocab_size)
    n_prompt = 23
    cache = llama.init_cache(config, 2, 64, jnp.float32)
    with jax.default_matmul_precision("highest"):
        h, cache = llama.forward_hidden(
            params, config, tokens[:, :n_prompt], cache, prefill_flash=True)
        got = [llama.logits_from_hidden(params, config, h)]
        for i in range(n_prompt, tokens.shape[1]):
            h, cache = llama.forward_hidden(params, config,
                                            tokens[:, i:i + 1], cache)
            got.append(llama.logits_from_hidden(params, config, h))
    got = np.asarray(jnp.concatenate(got, axis=1))
    kept = 0
    for b in range(2):
        want, margins = reference_logits(params, model_keys(config),
                                         tokens[b], with_margins=True)
        ok = (np.asarray(margins) >= 1e-4).all(axis=0)
        kept += ok.sum()
        np.testing.assert_allclose(got[b][ok], np.asarray(want)[ok],
                                   atol=2e-5, rtol=0)
        assert np.abs(want).max() > 0.05, "logits too small to tell"
    assert kept >= 0.9 * tokens.size


def test_the_reference_runs_a_layer_at_a_time():
    """`run_layers(layers=...)` from given hidden states is the whole pass
    in pieces: what lets a caller hold one layer's float32 weights at a
    time at the published widths (tools/hybrid_parity.py)."""
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-hybrid")
    params = llama.init_params(config, jax.random.key(5), jnp.float32)
    model = model_keys(config)
    tokens = jax.random.randint(jax.random.key(2), (30,), 0,
                                config.vocab_size)
    whole = reference_logits(params, model, tokens)
    h = embed(params, model, tokens)
    for i in range(config.num_layers):
        h, _ = run_layers(params, model, h, layers=[i])
    np.testing.assert_allclose(head(params, model, h), whole, atol=1e-6)
