"""`reference/exaone_moe_decoder.py` against the program's model path at
`tiny-xm` widths, float32 on the CPU: the trunk's logits and the module's,
whole and as a held share; the reference imports nothing from the program."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from reference import exaone_moe_decoder as ref


@pytest.fixture(scope="module")
def tiny():
    from symmetry_tpu.models import hybrid, llama

    config = llama.preset("tiny-xm")
    return llama, hybrid, config, hybrid.hf_config(config), \
        llama.init_params(config, jax.random.key(2), jnp.float32)


def worst(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def test_the_reference_imports_nothing_from_the_program():
    text = open(os.path.join(BENCH, "reference",
                             "exaone_moe_decoder.py")).read()
    assert "symmetry_tpu" not in text.split('"""', 2)[2]
    assert "import jax" in text


def test_the_programs_prefill_is_the_references_pass(tiny):
    llama, hybrid, config, model, params = tiny
    ids = [int(t) for t in jax.random.randint(jax.random.key(5), (40,), 0,
                                              config.vocab_size)]
    with jax.default_matmul_precision("highest"):
        trunk, module = ref.reference_logits(params, model, jnp.asarray(ids))
        cache = llama.init_cache(config, 1, 64, jnp.float32)
        tokens = jnp.zeros((1, 64), jnp.int32).at[0, :40].set(
            jnp.asarray(ids))
        h, cache = llama.forward_hidden(params, config, tokens, cache,
                                        jnp.asarray([40]),
                                        prefill_flash=True)
        got = llama.logits_from_hidden(params, config, h)[0, :40]
        hm, _ = hybrid.mtp_forward(
            params, config, h, jnp.roll(tokens, -1, 1),
            cache._replace(lengths=jnp.zeros_like(cache.lengths)),
            jnp.asarray([39]), prefill_flash=True)
        drafts = llama.logits_from_hidden(params, config, hm)[0, :39]
    assert trunk.shape == (40, config.vocab_size)
    assert module.shape == (39, config.vocab_size)
    assert worst(got, trunk) < 5e-5
    assert worst(drafts, module) < 5e-5


@pytest.mark.parametrize("wrong", ["no_qk_norm", "rope_full", "window_short",
                                   "no_bias", "renormed_held",
                                   "mtp_swapped"])
def test_each_departure_moves_the_logits_it_should(tiny, wrong):
    _, _, config, model, params = tiny
    ids = jnp.asarray(jax.random.randint(jax.random.key(6), (36,), 0,
                                         config.vocab_size))
    with jax.default_matmul_precision("highest"):
        want = ref.reference_logits(params, model, ids)
        got = ref.reference_logits(params, model, ids, wrong=wrong)
    moved = worst(got[1], want[1]) if wrong == "mtp_swapped" else worst(
        got[0], want[0])
    assert moved > 1e-3


def test_a_config_without_a_module_has_no_second_output(tiny):
    _, _, config, model, params = tiny
    plain = {k: v for k, v in model.items()
             if k != "num_nextn_predict_layers"}
    trunk, module = ref.reference_logits(params, plain, jnp.arange(12) % 32)
    assert module is None and trunk.shape == (12, config.vocab_size)
