"""The plain float32 reference `reference/sconv_moe_decoder.py` (the gated
short convolution as written, GQA with per-head norms, two dense layers, the
sigmoid router with its selection bias, experts as a loop): against a
hand-written three-position example, and against the program's model path —
prefill from empty, then single-token steps through the K/V cache and the
tails — at `tiny-sconv` widths, on seeded random weights. Logits, not tokens.

Tolerance: both sides compute in float32 on the CPU and differ in the order
of accumulation: 2e-5 absolute on logits of order 0.5. A token within 1e-4 of
a router tie may route otherwise on the two sides; it is left out, and at
most a tenth may be. The repo's tier-1 suite (`tests/test_short_conv.py`)
runs bfloat16 and int8 weights, the falsifications and the engine."""

import jax
import jax.numpy as jnp
import numpy as np

from reference.sconv_moe_decoder import (
    embed, head, moe, reference_logits, route, run_layers, short_conv)


def model_keys(c) -> dict:
    from symmetry_tpu.models import hybrid

    return hybrid.hf_config(c)


def test_the_reference_imports_nothing_from_the_program():
    import reference.sconv_moe_decoder as ref

    source = open(ref.__file__).read()
    assert "symmetry_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


def test_the_short_convolution_by_hand_on_three_positions():
    """Two channels, three taps, three positions; `in_proj` is [I | 2I | 3I],
    so B, C, x are u, 2u, 3u.

        z = B * x;  c_t = w0 z_{t-2} + w1 z_{t-1} + w2 z_t;  out = C * c
    """
    e = 2
    eye = np.eye(e, dtype=np.float32)
    # u is [S, E]; in_proj [E, 3E] = [I | 2I | 3I]: B = u, C = 2u, x = 3u
    p = {"in_proj": jnp.asarray(np.concatenate([eye, 2 * eye, 3 * eye], 1)),
         "conv_w": jnp.asarray([[1.0, 10.0], [2.0, 20.0], [3.0, 30.0]]),
         "out_proj": jnp.asarray(eye)}
    u = jnp.asarray([[1.0, 1.0], [2.0, 0.5], [-1.0, 2.0]])
    z = 3 * np.asarray(u) ** 2          # B * x = u * 3u
    assert z.tolist() == [[3, 3], [12, 0.75], [3, 12]]
    c = np.asarray([
        [3 * 3, 30 * 3],                                   # t = 0: w2 z_0
        [2 * 3 + 3 * 12, 20 * 3 + 30 * 0.75],              # w1 z_0 + w2 z_1
        [1 * 3 + 2 * 12 + 3 * 3, 10 * 3 + 20 * 0.75 + 30 * 12]])
    want = 2 * np.asarray(u) * c
    tails = []
    got = short_conv(u, p, {"conv_L_cache": 3}, tails)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    # the state a slot would keep: z at the last two positions
    np.testing.assert_allclose(np.asarray(tails[0]), z[1:], rtol=1e-6)


def test_the_router_by_hand():
    """Four experts top 2: the bias moves the SELECTION (expert 3 for expert
    1), the gates are the unbiased scores of the selected, renormalised."""
    model = {"num_experts_per_tok": 2, "use_expert_bias": True,
             "routed_scaling_factor": 1.0}
    logits = np.asarray([[2.0, 1.0, -1.0, 0.5]], np.float32)
    p = {"router": jnp.asarray(np.eye(4, dtype=np.float32)),
         "expert_bias": jnp.asarray([0.0, -0.2, 0.0, 0.2])}
    gates, experts, margin = route(jnp.asarray(logits), p, model)
    s = 1 / (1 + np.exp(-logits[0]))
    assert s[1] > s[3] and s[1] - 0.2 < s[3] + 0.2
    assert experts.tolist() == [[0, 3]]
    np.testing.assert_allclose(
        np.asarray(gates[0]), [s[0], s[3]] / (s[0] + s[3] + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(float(margin[0]),
                               (s[3] + 0.2) - (s[1] - 0.2), rtol=1e-5)
    plain = dict(model, use_expert_bias=False)
    _, experts, _ = route(jnp.asarray(logits), p, plain)
    assert experts.tolist() == [[0, 1]]
    # moe(): the gated sum over the selected experts alone
    w = {"wg": jnp.ones((4, 4, 3)), "wu": jnp.ones((4, 4, 3)),
         "wd": jnp.arange(4.0)[:, None, None] * jnp.ones((4, 3, 4))}
    y, _ = moe(jnp.asarray(logits), dict(p, **w), model)
    x = logits[0].sum()
    hidden = x / (1 + np.exp(-x)) * x           # silu(x) * x, 3 columns
    want = float(gates[0, 1]) * 3 * 3 * hidden  # expert 0's wd is 0
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-5)


def test_prefill_then_decode_matches_the_plain_reference():
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-sconv")
    params = llama.init_params(config, jax.random.key(42), jnp.float32)
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
    in pieces, and a ONE-layer model with that layer's stacks alone is the
    piece: what lets a caller hold one layer's float32 weights at a time at
    the published widths (tools/hybrid_parity.py)."""
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-sconv")
    params = llama.init_params(config, jax.random.key(5), jnp.float32)
    model = model_keys(config)
    tokens = jax.random.randint(jax.random.key(2), (30,), 0,
                                config.vocab_size)
    whole = reference_logits(params, model, tokens)
    h = embed(params, model, tokens)
    for i in range(config.num_layers):
        h, _ = run_layers(params, model, h, layers=[i])
    np.testing.assert_allclose(head(params, model, h), whole, atol=1e-6)
    kinds = list(config.layer_types)
    h = embed(params, model, tokens)
    for i, kind in enumerate(kinds):
        stack = "attn" if kind == "full_attention" else "sconv"
        j = sum(t == kind for t in kinds[:i])
        dense = i < config.num_dense_layers
        ffn, at = ("dense", i) if dense else (
            "ffn", i - config.num_dense_layers)
        one = {"layers": {
            stack: jax.tree.map(lambda a: a[j:j + 1],
                                params["layers"][stack]),
            ffn: jax.tree.map(lambda a: a[at:at + 1],
                              params["layers"][ffn])}}
        h, _ = run_layers(one, dict(model, layer_types=[kind],
                                    num_dense_layers=int(dense)), h,
                          layers=[0])
    np.testing.assert_allclose(head(params, model, h), whole, atol=1e-6)
