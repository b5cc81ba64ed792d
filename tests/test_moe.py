"""MoE model family (models/moe.py): routing, forward, engine, sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
from symmetry_tpu.engine.tokenizer import ByteTokenizer
from symmetry_tpu.models import forward, init_cache, init_params, preset
from symmetry_tpu.models.llama import (
    MoEConfig,
    param_logical_axes,
    quantize_params,
)
from symmetry_tpu.models import moe
from symmetry_tpu.models.moe import route_top_k
from symmetry_tpu.ops.quant import quantize


class TestRouting:
    def test_gates_topk_normalized(self):
        x = jax.random.normal(jax.random.key(0), (6, 16))
        router = jax.random.normal(jax.random.key(1), (16, 8))
        gates, experts = route_top_k(x, router, 2)
        assert gates.shape == experts.shape == (6, 2)
        assert gates.dtype == jnp.float32
        # k distinct experts per token, gates summing to 1
        assert (np.asarray(experts[:, 0]) != np.asarray(experts[:, 1])).all()
        np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-5)

    def test_gates_pick_largest(self):
        # identity router: the logits are the token itself
        x = jnp.asarray([[1.0, 5.0, 3.0, -2.0]])
        gates, experts = route_top_k(x, jnp.eye(4), 2)
        assert np.asarray(experts).tolist() == [[1, 2]]
        assert gates[0, 0] > gates[0, 1] > 0


def int8_layer(X, k, D, F_, key):
    keys = jax.random.split(key, 4)

    def leaf(key, shape, fan_in):
        return quantize(jax.random.normal(key, shape) * fan_in ** -0.5)

    return {"router": jax.random.normal(keys[0], (D, X)),
            "wg": leaf(keys[1], (X, D, F_), D),
            "wu": leaf(keys[2], (X, D, F_), D),
            "wd": leaf(keys[3], (X, F_, D), F_)}


@pytest.mark.parametrize("X,k,tokens", [(72, 10, 48), (512, 10, 24),
                                        (8, 2, 40)])
def test_the_routed_form_over_the_kernel_is_the_dense_mixture(X, k, tokens):
    """`_routed_ffn` with the kernel under it against `_dense_mixture` and
    against itself over `lax.ragged_dot`, at the served routing shapes
    (72 and 512 experts top 10: most of 512 experts have no row)."""
    D, F_ = 32, 16
    lp = int8_layer(X, k, D, F_, jax.random.key(X))
    x = jax.random.normal(jax.random.key(X + 1), (tokens, D))
    valid = jnp.arange(tokens) < tokens - 5
    args = (x, valid, lp["router"], lp["wg"], lp["wu"], lp["wd"], k)
    stacks = jax.tree.map(lambda a: a[None],
                          {n: lp[n] for n in moe.EXPERT_LEAVES})
    jaxpr = str(jax.make_jaxpr(
        lambda: moe._routed_ffn(*args, (stacks, jnp.int32(0))))())
    # gate and up in one call, down in another
    assert jaxpr.count("moe_gmm") == 2 and "ragged_dot" not in jaxpr
    with jax.default_matmul_precision("highest"):
        kernel, pairs = moe._routed_ffn(*args, (stacks, jnp.int32(0)))
        ragged, pairs_ragged = moe._routed_ffn(*args)
        dense, pairs_dense = moe._dense_mixture(*args)
    np.testing.assert_allclose(kernel, ragged, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(kernel, dense, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(pairs, pairs_dense)
    np.testing.assert_array_equal(pairs, pairs_ragged)
    assert int(pairs.sum()) == (tokens - 5) * k


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all", "a share"])
def test_a_share_and_an_ungated_expert_take_the_kernel_as_the_mixture(
        gated, held):
    """Two kernel calls a layer either way: (gate, up) in one and down, or
    — ungated, act(x W_up) W_down — up and down, the form that has no pair
    to join; a chip's share leaves rows past its groups unwritten in
    both."""
    X, k, D, F_, tokens = 8, 2, 32, 16, 40
    lp = int8_layer(X, k, D, F_, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (tokens, D))
    routing = {"act": "silu" if gated else "relu2"}
    names = moe.EXPERT_LEAVES if gated else ("wu", "wd")
    if held is not None:
        routing["held"] = held
        lp.update({n: jax.tree.map(
            lambda a: a[held[0]:held[0] + held[1]], lp[n]) for n in names})
    args = (x, jnp.ones((tokens,), bool), lp["router"],
            lp["wg"] if gated else None, lp["wu"], lp["wd"], k)
    at = (jax.tree.map(lambda a: a[None], {n: lp[n] for n in names}),
          jnp.int32(0))
    jaxpr = str(jax.make_jaxpr(
        lambda: moe._routed_ffn(*args, at, routing))())
    assert jaxpr.count("moe_gmm") == 2 and "ragged_dot" not in jaxpr
    with jax.default_matmul_precision("highest"):
        kernel, pairs = moe._routed_ffn(*args, at, routing)
        ragged, _ = moe._routed_ffn(*args, None, routing)
        dense, pairs_dense = moe._dense_mixture(*args, routing)
    np.testing.assert_allclose(kernel, ragged, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(kernel, dense, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(pairs, pairs_dense)
    assert np.abs(np.asarray(kernel)).max() > 0


class TestMoEForward:
    def test_forward_and_greedy_decode(self):
        cfg = preset("tiny-moe")
        assert isinstance(cfg, MoEConfig)
        params = init_params(cfg, jax.random.key(0), jnp.float32)
        assert params["layers"]["wg"].shape == (2, 4, 64, 128)
        assert params["layers"]["router"].shape == (2, 64, 4)

        cache = init_cache(cfg, 1, 32, jnp.float32)
        tokens = jnp.asarray([[5, 9, 2, 7]], jnp.int32)
        logits, cache = forward(params, cfg, tokens, cache)
        assert logits.shape == (1, 4, 512)
        assert np.isfinite(np.asarray(logits)).all()
        # decode continues from the cache
        last = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        logits2, cache = forward(params, cfg, last[:, None], cache)
        assert logits2.shape == (1, 1, 512)
        assert int(cache.lengths[0]) == 5

    def test_quantized_matches_dense_approximately(self):
        cfg = preset("tiny-moe")
        params = init_params(cfg, jax.random.key(1), jnp.float32)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 512, (1, 8)), jnp.int32)
        dense, _ = forward(params, cfg, tokens,
                           init_cache(cfg, 1, 16, jnp.float32))
        qparams = quantize_params(jax.tree.map(lambda a: a, params))
        quant, _ = forward(qparams, cfg, tokens,
                           init_cache(cfg, 1, 16, jnp.float32))
        d, q = np.asarray(dense[:, -1]), np.asarray(quant[:, -1])
        assert np.abs(d - q).max() <= 0.05 * np.abs(d).max() + 0.05

    def test_engine_serves_moe(self):
        cfg = preset("tiny-moe")
        params = init_params(cfg, jax.random.key(0), jnp.float32)
        eng = InferenceEngine(cfg, params, ByteTokenizer(), max_slots=2,
                              max_seq_len=64, prefill_buckets=(16,),
                              cache_dtype=jnp.float32)
        first = eng.prefill_and_insert(0, list(b"moe prompt"),
                                       SamplingParams())
        toks = eng.decode_step()
        assert toks.shape == (2,)
        assert 0 <= first < cfg.vocab_size

    def test_engine_greedy_deterministic_across_slots(self):
        """Continuous-batch invariance holds for MoE too: slot 1's traffic
        must not perturb slot 0's greedy tokens."""
        cfg = preset("tiny-moe")
        params = init_params(cfg, jax.random.key(0), jnp.float32)

        def solo():
            eng = InferenceEngine(cfg, params, ByteTokenizer(), max_slots=2,
                                  max_seq_len=64, prefill_buckets=(16,),
                                  cache_dtype=jnp.float32)
            out = [eng.prefill_and_insert(0, list(b"abc"), SamplingParams())]
            for _ in range(5):
                out.append(int(eng.decode_step()[0]))
            return out

        def batched():
            eng = InferenceEngine(cfg, params, ByteTokenizer(), max_slots=2,
                                  max_seq_len=64, prefill_buckets=(16,),
                                  cache_dtype=jnp.float32)
            out = [eng.prefill_and_insert(0, list(b"abc"), SamplingParams())]
            eng.prefill_and_insert(1, list(b"other stream"), SamplingParams())
            for _ in range(5):
                out.append(int(eng.decode_step()[0]))
            return out

        assert solo() == batched()


class TestExpertParallel:
    def test_ep_sharded_forward_matches_unsharded(self):
        """(expert=2, model=2, data=2) mesh over 8 virtual CPU devices:
        EP+TP+DP sharded forward must equal the single-device result."""
        from symmetry_tpu.parallel import MeshSpec, build_mesh, shardings_for

        cfg = preset("tiny-moe")
        params = init_params(cfg, jax.random.key(0), jnp.float32)
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, 512, (2, 8)), jnp.int32)
        want, _ = forward(params, cfg, tokens,
                          init_cache(cfg, 2, 16, jnp.float32))

        mesh = build_mesh(MeshSpec(data=2, expert=2, model=2))
        sharded = jax.device_put(
            params, shardings_for(param_logical_axes(cfg), mesh))

        @jax.jit
        def run(p, t):
            logits, _ = forward(p, cfg, t, init_cache(cfg, 2, 16, jnp.float32))
            return logits

        got = run(sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestMoECheckpoint:
    def test_save_load_roundtrip_streaming(self, tmp_path):
        """tiny-moe params → HF mixtral-layout safetensors → streaming
        loader → identical forward logits."""
        pytest.importorskip("safetensors")
        from symmetry_tpu.engine.weights import load_checkpoint, save_checkpoint

        cfg = preset("tiny-moe")
        params = init_params(cfg, jax.random.key(2), jnp.float32)
        path = str(tmp_path / "ckpt")
        save_checkpoint(path, params, cfg)

        loaded, loaded_cfg = load_checkpoint(path, dtype=jnp.float32)
        assert getattr(loaded_cfg, "num_experts", 0) == 4
        assert loaded["layers"]["wg"].shape == (2, 4, 64, 128)

        tokens = jnp.asarray(
            np.random.default_rng(2).integers(0, 512, (1, 6)), jnp.int32)
        want, _ = forward(params, cfg, tokens,
                          init_cache(cfg, 1, 16, jnp.float32))
        got, _ = forward(loaded, loaded_cfg, tokens,
                         init_cache(cfg, 1, 16, jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_convert_hf_state_dict_moe(self, tmp_path):
        pytest.importorskip("safetensors")
        from safetensors.numpy import load_file

        from symmetry_tpu.engine.weights import (
            convert_hf_state_dict, save_checkpoint)

        cfg = preset("tiny-moe")
        params = init_params(cfg, jax.random.key(3), jnp.float32)
        path = str(tmp_path / "ckpt2")
        save_checkpoint(path, params, cfg)
        tensors = load_file(path + "/model.safetensors")
        assert any("block_sparse_moe.experts" in n for n in tensors)

        converted = convert_hf_state_dict(tensors, cfg)
        np.testing.assert_allclose(
            converted["layers"]["router"],
            np.asarray(params["layers"]["router"], np.float32), rtol=1e-6)
        np.testing.assert_allclose(
            converted["layers"]["wd"],
            np.asarray(params["layers"]["wd"], np.float32), rtol=1e-6)
