"""The plain float32 reference `reference/nemotron_h_decoder.py` (a loop over
the published blocks, one sub-layer each; the grouped recurrence as a scan
over time; the held experts as a loop) against the program's model path — the
full forward in one call, and a prefill from empty followed by single
positions through the cache, the state and the tails — at `tiny-nh` widths on
seeded random weights: logits.

Tolerance: both sides compute in float32 on the CPU and differ in the order of
accumulation: 3e-5 of the logit scale (measured 1.1e-6). The repo's tier-1
suite (`tests/test_nemotron_h.py`) runs the wider grid."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference.nemotron_h_decoder as ref


def program():
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-nh")
    return (llama, config, llama.hf_config_nemotron_h(config),
            llama.init_params(config, jax.random.key(61), jnp.float32))


def test_the_reference_imports_nothing_from_the_program():
    source = open(ref.__file__).read()
    assert "symmetry_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "Departures from the published code" in source


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
    assert margins.shape == (5, 37)         # the five expert blocks
    assert np.isfinite(np.asarray(margins)).all()


def test_prefill_then_decode_through_the_cache_match_the_reference():
    llama, config, model, params = program()
    ids = jax.random.randint(jax.random.key(2), (45,), 0, 500)
    P = 29
    cache = llama.init_cache(config, 1, 64, jnp.float32)
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :P].set(ids[:P])
    step = jax.jit(lambda t, c: llama.forward_hidden(params, config, t, c))
    with jax.default_matmul_precision("highest"):
        h, cache = llama.forward_hidden(params, config, padded, cache,
                                        jnp.asarray([P]), prefill_flash=True)
        rows = [llama.logits_from_hidden(params, config, h)[0, :P]]
        for t in ids[P:]:
            h, cache = step(t[None, None], cache)
            rows.append(llama.logits_from_hidden(params, config, h)[0])
        want = ref.reference_logits(params, model, ids)
    got = jnp.concatenate(rows)
    assert np.abs(np.asarray(got - want)).max() < 3e-5 * float(
        jnp.abs(want).max())


def test_a_block_at_a_time_is_the_whole_pass_and_yields_states_and_choices():
    _, config, model, params = program()
    ids = jax.random.randint(jax.random.key(3), (21,), 0, 500)
    with jax.default_matmul_precision("highest"):
        want = ref.reference_logits(params, model, ids)
        h = ref.embed(params, model, ids)
        states, selected = [], []
        for i in range(len(model["hybrid_override_pattern"])):
            h, _ = ref.run_blocks(params, model, h, blocks=[i],
                                  states=states, selected=selected)
        got = ref.head(params, model, h)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert len(states) == 5 and states[0].shape == (8, 16, 16)
    assert len(selected) == 5 and selected[0].shape == (21, 2)


@pytest.mark.parametrize("control", ["one-group", "norm-all", "gated",
                                     "renormalised", "rotary", "state-bf16"])
def test_each_control_moves_the_logits(control):
    _, config, model, params = program()
    ids = jax.random.randint(jax.random.key(4), (40,), 0, 500)
    want = ref.reference_logits(params, model, ids)
    wrong = ref.reference_logits(params, model, ids, controls=(control,))
    moved = float(jnp.abs(wrong - want).max() / jnp.abs(want).max())
    assert moved > (1e-4 if control == "state-bf16" else 0.05), moved


def test_the_absent_experts_terms_are_left_out_and_the_gates_kept():
    """One expert block of the reference, uncut, is the sum of what two
    shares give with the shared expert counted once."""
    _, config, model, params = program()
    p = {k: v[1] for k, v in params["layers"]["ffn"].items()}
    x = jax.random.normal(jax.random.key(5), (16, 64))
    whole = dict(model, n_routed_experts=8)
    whole.pop("experts_held"), whole.pop("experts_routed_over")
    full = dict(p, wu=jnp.concatenate([p["wu"], p["wu"]]),
                wd=jnp.concatenate([p["wd"], p["wd"]]))
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.experts_and_shared(x, full, whole)
        shared = ref.relu2(x @ p["su"]) @ p["sd"]
        parts = [ref.experts_and_shared(
            x, p, dict(model, experts_held=[first, 4]))[0] - shared
            for first in (0, 4)]
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                               atol=2e-5)
