"""The plain float32 reference `reference/block_diffusion_moe_decoder.py` (a
dense softmax under the block mask, routed experts as a loop, a Python
generation loop) against the program's model path — prefill from empty through
the flash kernel under the block mask, then a block through the cache — and
against the program's denoise programs, at `tiny-bd` widths on seeded random
weights. Logits for the forward, tokens under greedy for the loop.

Tolerance: both sides compute in float32 on the CPU and differ in the order of
accumulation: 2e-5 absolute on logits of order 0.5 (measured 5e-7). The repo's
tier-1 suite (`tests/test_block_diffusion.py`) runs the wider grid."""

import jax
import jax.numpy as jnp
import numpy as np

import reference.block_diffusion_moe_decoder as ref


def program():
    from symmetry_tpu.models import llama

    config = llama.preset("tiny-bd")
    return llama, config, llama.hf_config_diffusion(config), llama.init_params(
        config, jax.random.key(47), jnp.float32)


def test_the_reference_imports_nothing_from_the_program():
    source = open(ref.__file__).read()
    assert "symmetry_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "Departures from the published script" in source


def test_the_masks():
    m = np.asarray(ref.block_mask(6, 4))
    assert m[0, 3] and m[3, 0] and not m[3, 4] and m[4, 0] and m[4, 5]
    c = np.asarray(ref.causal_mask(6))
    assert not c[0, 3] and c[3, 0]


def test_the_unmask_rules():
    conf = [0.2, 0.95, 0.97, 0.99]
    none = np.zeros(4, bool)
    assert ref.unmask(conf, none, 2).tolist() == [False, False, True, True]
    assert ref.unmask(conf, none, 2, 0.9).tolist() == [False, True, True,
                                                       True]
    assert ref.unmask(conf, none, 2, 0.98).tolist() == [False, False, True,
                                                        True]
    assert ref.unmask([0.5] * 4, none, 1).tolist() == [True, False, False,
                                                       False]
    known = np.array([True, False, False, True])
    assert ref.unmask(conf, known, 1, final=True).tolist() == [
        False, True, True, False]
    assert ref.transfer_schedule(4, 3) == [2, 1, 1]


def test_prefill_and_a_block_through_the_cache_match_the_reference():
    llama, config, model, params = program()
    tokens = jax.random.randint(jax.random.key(1), (1, 22), 0, 500)
    cache = llama.init_cache(config, 1, 64, jnp.float32)
    padded = jnp.pad(tokens, ((0, 0), (0, 10)))
    h, after = llama.forward_hidden(params, config, padded, cache,
                                    jnp.array([22]), prefill_flash=True)
    got = llama.logits_from_hidden(params, config, h)[0, :22]
    want = ref.reference_logits(params, model, np.asarray(tokens[0]))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the whole blocks are committed; the next block goes through the cache
    committed = after._replace(lengths=jnp.array([20]))
    block = [int(tokens[0, 20]), int(tokens[0, 21]),
             model["mask_token_id"], model["mask_token_id"]]
    got, _ = llama.forward(params, config, jnp.asarray([block]), committed)
    want = ref.reference_logits(
        params, model, np.asarray(list(tokens[0, :20]) + block))[-4:]
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_the_generation_loop_is_what_the_engine_serves():
    from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
    from symmetry_tpu.engine.tokenizer import get_tokenizer

    _, config, model, params = program()
    engine = InferenceEngine(
        config, params, get_tokenizer(None, vocab_size=config.vocab_size),
        max_slots=2, max_seq_len=128, prefill_buckets=(32,),
        cache_dtype=jnp.float32, decode_block=8, prefill_chunk=None,
        diffusion_steps=2, diffusion_threshold=0.01)
    prompt = [int(t) for t in
              jax.random.randint(jax.random.key(2), (14,), 0, 500)]
    first = np.asarray(engine.prefill_and_insert_many_dispatch(
        [(1, prompt, SamplingParams())]))[0, 14 % 4:]
    got = [int(t) for t in first] + [int(t) for t in
                                     engine.decode_steps()[:, 1]]
    want, trace = ref.generate(params, model, prompt, len(got), steps=2,
                               threshold=0.01)
    assert got == want
    # the trace holds each block's denoise forwards and its commit
    assert sum(1 for t in trace if t.get("commit")) == 3
    assert all(t["context"] % 4 == 0 for t in trace)
