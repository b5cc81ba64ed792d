"""The plain float32 reference `reference/sparse_moe_decoder.py` (a lightning
indexer's scores, `jax.lax.top_k`'s selection, attention over the selected
positions, a rotary of three components, routed experts as a loop) against
the program's model path — prefill from empty through the masked flash
kernel, then single-token steps through the K/V and index caches — at
`tiny-dsa` widths (topk 16, shorter than the prompt), on seeded random
weights. Logits and selected sets.

Tolerance: both sides compute in float32 on the CPU and differ in the order
of accumulation and in how the set is found (a threshold against a sort): 2e-5
absolute on logits of order 0.5 (measured 4e-6). A token within 1e-4 of a
router tie may route otherwise on the two sides; it and what follows it are
left out, and at most a tenth may be. The repo's tier-1 suite
(`tests/test_sparse_attention.py`) runs the wider grid."""

import jax
import jax.numpy as jnp
import numpy as np

from reference.sparse_moe_decoder import (
    embed, head, reference_logits, run_layers, select)


def program():
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-dsa")
    return llama, config, llama.hf_config_sparse(config), llama.init_params(
        config, jax.random.key(40), jnp.float32)


def test_the_reference_imports_nothing_from_the_program():
    import reference.sparse_moe_decoder as ref

    source = open(ref.__file__).read()
    assert "symmetry_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "jax.lax.top_k" in source


def test_the_reference_selects_by_top_k_with_ties_toward_the_lower_position():
    scores = jnp.array([[5., 9., 9., 9.],
                        [1., 1., 1., 9.],
                        [3., 2., 1., 9.],
                        [2., 7., 7., 7.]])
    keep = np.asarray(select(scores, 2))
    np.testing.assert_array_equal(keep, [[1, 0, 0, 0], [1, 1, 0, 0],
                                         [1, 1, 0, 0], [0, 1, 1, 0]])


def test_prefill_then_decode_matches_the_plain_reference():
    llama, config, model, params = program()
    tokens = jax.random.randint(jax.random.key(1), (2, 44), 0,
                                config.vocab_size)
    n_prompt = 30
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
        want, details = reference_logits(params, model, tokens[b],
                                         with_details=True)
        margins = np.stack([np.asarray(d["margin"]) for d in details])
        ok = ~np.logical_or.accumulate((margins < 1e-4).any(axis=0))
        kept += ok.sum()
        np.testing.assert_allclose(got[b][ok], np.asarray(want)[ok],
                                   atol=2e-5, rtol=0)
        # the selection bit: the late queries chose 16 of their positions
        sizes = np.asarray(details[0]["keep"]).sum(axis=1)
        np.testing.assert_array_equal(
            sizes, np.minimum(np.arange(44) + 1, 16))
    assert kept >= 0.9 * tokens.size


def test_the_reference_runs_a_layer_at_a_time_and_takes_a_selection():
    _, config, model, params = program()
    tokens = jax.random.randint(jax.random.key(2), (33,), 0,
                                config.vocab_size)
    want, details = reference_logits(params, model, tokens,
                                     with_details=True)
    pos = jnp.broadcast_to(jnp.arange(33), (3, 33))
    h = embed(params, tokens)
    for i in range(config.num_layers):
        one = {"layers": {k: v[i:i + 1]
                          for k, v in params["layers"].items()}}
        h, _ = run_layers(one, model, h, pos, layers=[i])
    np.testing.assert_allclose(np.asarray(head(params, model, h)),
                               np.asarray(want), atol=1e-6, rtol=0)
    # given its own sets it returns its own logits; given every causal
    # position (dense attention) it returns others
    keep = [d["keep"] for d in details]
    np.testing.assert_allclose(
        np.asarray(reference_logits(params, model, tokens, selection=keep)),
        np.asarray(want), atol=1e-6, rtol=0)
    causal = np.tril(np.ones((33, 33), bool))
    dense = reference_logits(params, model, tokens,
                             selection=[causal] * config.num_layers)
    assert np.abs(np.asarray(dense) - np.asarray(want))[20:].max() > 1e-3
    np.testing.assert_allclose(np.asarray(dense)[:16], np.asarray(want)[:16],
                               atol=1e-6, rtol=0)


def test_a_query_tile_changes_no_result_and_a_bf16_softmax_does():
    """Blocked as the chip's comparison runs it (a tile of queries at a
    time, each expert over its own rows) the reference gives what it gives
    whole; with its softmax rounded to bfloat16 — the control's nearest
    precision below — the heads' outputs move by parts in a thousand."""
    from reference.sparse_moe_decoder import softmax_bf16

    _, config, model, params = program()
    tokens = jax.random.randint(jax.random.key(5), (45,), 0,
                                config.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(45), (3, 45))
    h = embed(params, tokens)
    whole, d_whole = run_layers(params, model, h, pos)
    tiled, d_tiled = run_layers(params, model, h, pos, query_tile=16)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(whole),
                               atol=2e-6, rtol=0)
    for a, b in zip(d_whole, d_tiled):
        np.testing.assert_array_equal(np.asarray(a["keep"]),
                                      np.asarray(b["keep"]))
        np.testing.assert_allclose(np.asarray(a["margin"]),
                                   np.asarray(b["margin"]), atol=1e-6)
    _, d_low = run_layers(params, model, h, pos, query_tile=16,
                          layers=[0], softmax=softmax_bf16,
                          selection={0: np.asarray(d_whole[0]["keep"])})
    want, got = np.asarray(d_whole[0]["attn"]), np.asarray(d_low[0]["attn"])
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    assert 1e-4 < np.median(err) < 2e-2, np.median(err)
