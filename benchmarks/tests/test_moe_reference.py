"""The plain float32 MoE reference against the program's model path (prefill
into the KV cache, then single-token decode through it) at `tiny-moe` widths,
on seeded random weights. Logits, not tokens.

Tolerance: both sides compute in float32 on the CPU and differ in the order
of accumulation (grouped vs per-expert matmuls, fused vs plain attention,
cache read-back): 2e-4 absolute on logits of order 0.7 (measured 2e-5) — two
orders tighter than a bfloat16 accumulation would give. A token whose
reference margin between its 2nd and 3rd router logit is under 1e-3 at any
layer is a coin toss between two correct answers; it and the tokens after it
in its sequence are left out, and at most a tenth may be (none is, with
these seeds). The repo's tier-1 suite (`tests/test_moe_reference.py`) runs
the wider grid: int8 and bfloat16 weights, an int8 cache, skewed routers,
`mesh {model: 4}`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference.moe_decoder import reference_logits


@pytest.mark.parametrize("experts", [4, 8])
def test_prefill_then_decode_matches_the_plain_reference(experts):
    from symmetry_tpu.models import llama

    config = dataclasses.replace(llama.preset("tiny-moe"),
                                 num_experts=experts)
    params = llama.init_params(config, jax.random.key(23), jnp.float32)
    model = {"num_attention_heads": config.num_heads,
             "num_key_value_heads": config.num_kv_heads,
             "hidden_size": config.hidden_size,
             "num_hidden_layers": config.num_layers,
             "rms_norm_eps": config.rms_eps, "rope_theta": config.rope_theta,
             "num_experts_per_tok": config.num_experts_per_tok}
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0,
                                config.vocab_size)
    n_prompt = 17
    cache = llama.init_cache(config, 2, 64, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, cache = llama.forward(params, config, tokens[:, :n_prompt],
                                   cache)
        got = [got]
        for i in range(n_prompt, tokens.shape[1]):
            step, cache = llama.forward(params, config, tokens[:, i:i + 1],
                                        cache)
            got.append(step)
    got = np.asarray(jnp.concatenate(got, axis=1))
    kept = 0
    for b in range(2):
        want, margins = reference_logits(params, model, tokens[b],
                                         with_margins=True)
        ok = ~np.logical_or.accumulate(
            (np.asarray(margins) < 1e-3).any(axis=0))
        kept += ok.sum()
        np.testing.assert_allclose(got[b][ok], np.asarray(want)[ok],
                                   atol=2e-4, rtol=0)
        assert np.abs(want).max() > 0.05, "logits too small to tell"
    assert kept >= 0.9 * tokens.size
