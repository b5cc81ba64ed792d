"""The plain float32 reference `reference/gdn_moe_decoder.py` (the delta rule
as a scan over time, gated attention with partial rotary and per-head norms,
routed experts as a loop, a gated shared expert) against the program's model
path — prefill from empty through the chunked form, then single-token steps
through the K/V cache, the matrix state and the conv tail — at `tiny-gdn`
widths, on seeded random weights. Logits, not tokens.

Tolerance: both sides compute in float32 on the CPU and differ in the order
of accumulation (a chunked triangular solve against the recurrence, a
mixture against per-expert matmuls, a softmax over the selected logits
against the full softmax renormalised): 2e-5 absolute on logits of order 0.5
(measured 2e-6). A token within 1e-4 of a router tie may route otherwise on
the two sides; it is left out, and at most a tenth may be. The repo's tier-1
suite (`tests/test_gdn.py`) runs bfloat16 and int8 weights and the engine."""

import jax
import jax.numpy as jnp
import numpy as np

from reference.gdn_moe_decoder import (
    embed, head, layer_kinds, reference_logits, run_layers)


def model_keys(c) -> dict:
    from symmetry_tpu.models import hybrid

    return hybrid.hf_config(c)


def test_the_reference_imports_nothing_from_the_program():
    import reference.gdn_moe_decoder as ref

    source = open(ref.__file__).read()
    assert "symmetry_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


def test_prefill_then_decode_matches_the_plain_reference():
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-gdn")
    params = llama.init_params(config, jax.random.key(35), jnp.float32)
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

    config = llama.preset("tiny-gdn")
    params = llama.init_params(config, jax.random.key(5), jnp.float32)
    model = model_keys(config)
    tokens = jax.random.randint(jax.random.key(2), (30,), 0,
                                config.vocab_size)
    whole = reference_logits(params, model, tokens)
    h = embed(params, model, tokens)
    for i in range(config.num_layers):
        h, _ = run_layers(params, model, h, layers=[i])
    np.testing.assert_allclose(head(params, model, h), whole, atol=1e-6)
    # without `layer_types` the pattern is the interval's
    bare = {k: v for k, v in model.items() if k != "layer_types"}
    assert layer_kinds(bare) == list(config.layer_types)
