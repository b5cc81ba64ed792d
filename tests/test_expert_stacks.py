"""The homogeneous trunk hands the routed experts their stacks whole
(models/llama.py `run_layers` -> `moe_mlp(stack=...)`): the same numbers as
the stack of one a layer's slice makes, bit for bit, in both expert forms,
and only where a kernel can take the stack — int8 leaves on one device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.models import llama, moe
from symmetry_tpu.ops import gmm

TOKENS = (2, 12)   # three blocks of four a row under `tiny-bd`'s mask


def forward(cfg, params, dtype, **kw):
    tokens = jax.random.randint(jax.random.key(5), TOKENS, 0,
                                cfg.vocab_size - 1)
    cache = llama.init_cache(cfg, TOKENS[0], 16, dtype, count_experts=True)
    h, cache = jax.jit(lambda p, t, c: llama.forward_hidden(
        p, cfg, t, c, jnp.array([12, 9], jnp.int32), **kw))(
            params, tokens, cache)
    return (np.asarray(llama.logits_from_hidden(params, cfg, h)),
            np.asarray(cache.expert_pairs))


def make_params(cfg, weights):
    return llama.init_params(cfg, jax.random.key(3), jnp.bfloat16,
                             quantize=weights == "int8"), jnp.bfloat16


@pytest.fixture
def kernel_stacks(monkeypatch):
    """The leading (layers) extent of every stack the kernel is given."""
    seen = []
    inner = gmm.grouped_matmul

    def spy(rows, q, *a, **kw):
        # a gated layer's gate and up arrive as one call's pair
        seen.extend(s.shape[0] for s in (q if isinstance(q, tuple) else (q,)))
        return inner(rows, q, *a, **kw)

    monkeypatch.setattr(gmm, "grouped_matmul", spy)
    return seen


@pytest.mark.parametrize("form", ["routed", "dense-mixture"])
@pytest.mark.parametrize("weights", ["int8", "bfloat16"])
@pytest.mark.parametrize("preset", ["tiny-bd", "tiny-moe8"])
def test_whole_stacks_equal_the_stack_of_one(monkeypatch, kernel_stacks,
                                             preset, weights, form):
    cfg = llama.preset(preset)
    if form == "routed":
        monkeypatch.setattr(moe, "ROUTED_MIN_TOKENS", 1)
    assert moe.moe_route(TOKENS[0] * TOKENS[1], cfg.num_experts,
                         cfg.num_experts_per_tok) == form
    params, dtype = make_params(cfg, weights)
    logits, pairs = forward(cfg, params, dtype)
    # the kernel's operands: all the layers' experts, three stacks over two
    # calls, traced once in the scan's body — or no kernel at all: the mixture, and a
    # bf16 stack's `ragged_dot`, read the layer's slice
    kernel = form == "routed" and weights == "int8"
    assert kernel_stacks == ([cfg.num_layers] * 3 if kernel else [])
    del kernel_stacks[:]

    monkeypatch.setattr(moe, "whole_stacks", lambda layers, mesh=None: None)
    want, want_pairs = forward(cfg, params, dtype)
    assert kernel_stacks == ([1] * 3 if kernel else [])
    np.testing.assert_array_equal(logits, want)
    np.testing.assert_array_equal(pairs, want_pairs)
    valid = 12 + 9
    assert pairs.sum() == valid * cfg.num_experts_per_tok * cfg.num_layers


def test_a_meshed_trunk_is_handed_no_stack(monkeypatch):
    """Under a mesh the expert FFN is partitioned (`shard_map` over the FFN
    width): every shard reads its slice of the layer, never a stack."""
    from symmetry_tpu.parallel import MeshSpec, build_mesh

    cfg = llama.preset("tiny-moe8")
    params, dtype = make_params(cfg, "int8")
    mesh = build_mesh(MeshSpec(model=4), jax.devices()[:4])
    assert moe.whole_stacks(params["layers"], mesh) is None
    stacks = []
    inner = moe.moe_mlp

    def spy(x, lp, config, seq_lens=None, tp_mesh=None, stack=None):
        stacks.append(stack)
        return inner(x, lp, config, seq_lens, tp_mesh, stack)

    monkeypatch.setattr(moe, "moe_mlp", spy)
    cache = llama.init_cache(cfg, 2, 16, dtype)
    tokens = jnp.zeros(TOKENS, jnp.int32)
    jax.eval_shape(lambda p, t, c: llama.forward_hidden(
        p, cfg, t, c, tp_mesh=mesh), params, tokens, cache)
    assert stacks == [None]
    jax.eval_shape(lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
                   params, tokens, cache)
    whole, layer = stacks[1]
    assert set(whole) == set(moe.EXPERT_LEAVES)
    assert whole["wd"].q.shape[:2] == (cfg.num_layers, cfg.num_experts)
    assert layer.shape == () and layer.dtype == jnp.int32


def test_a_dense_trunk_asks_for_no_stack(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("a trunk without a router has no experts")

    monkeypatch.setattr(moe, "whole_stacks", refuse)
    cfg = llama.preset("tiny")
    params = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    jax.eval_shape(lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
                   params, jnp.zeros(TOKENS, jnp.int32),
                   llama.init_cache(cfg, 2, 16, jnp.float32))


REPORTS = {   # preset: the engine's keywords
    "tiny-bd": dict(prefill_chunk=None, decode_block=8, diffusion_steps=2),
    "tiny-moe8": {},
    "tiny-hybrid": dict(prefill_chunk=None),
}


@pytest.mark.parametrize("preset", list(REPORTS))
def test_startup_says_what_the_kernel_is_handed(monkeypatch, preset):
    """`startup.moe.grouped_matmul.operand`: the layers' stack with its
    shape on either trunk; a stack of one where `run_layers` hands none
    (what the homogeneous trunk did before PR 49, and chip_smoke.py fails
    on); no such field where the matmuls are `ragged_dot`'s."""
    from symmetry_tpu.engine.engine import InferenceEngine
    from symmetry_tpu.engine.tokenizer import get_tokenizer

    cfg = llama.preset(preset)

    def engine(quantize):
        params = llama.init_params(cfg, jax.random.key(0), jnp.bfloat16,
                                   quantize=quantize)
        return InferenceEngine(
            cfg, params, get_tokenizer(None, vocab_size=cfg.vocab_size),
            max_slots=4, max_seq_len=64, prefill_buckets=(32,),
            **REPORTS[preset])

    eng = engine(True)
    layers = eng.params["layers"]
    wg = layers.get("ffn", layers)["wg"]
    report = eng.moe_report()["grouped_matmul"]
    assert report["form"] == "pallas-interpret"
    assert report["operand"] == f"layers' stack {list(wg.q.shape)}"

    hybrid = "ffn" in layers
    monkeypatch.setattr(moe, "whole_stacks", lambda layers, mesh=None: None)
    eng._moe_report = None
    again = eng.moe_report()["grouped_matmul"]
    assert again["operand"] == (report["operand"] if hybrid
                                else "stack of one")

    plain = engine(False).moe_report()["grouped_matmul"]
    assert plain["form"] == "ragged_dot" and "operand" not in plain


@pytest.mark.parametrize("form,meshed,reason", [
    ({"form": "pallas", "row_tile": 64,
      "operand": "layers' stack [12, 128, 2048, 768]"}, False, None),
    ({"form": "pallas", "row_tile": 64, "operand": "stack of one"}, False,
     "a layer's slice"),
    ({"form": "pallas-interpret", "row_tile": 64,
      "operand": "layers' stack [2, 8, 64, 32]"}, False, "did not run"),
    ({"form": "ragged_dot", "why": "the expert stack is not int8"}, False,
     "did not run"),
    ({"form": "ragged_dot", "why": "traced under a mesh"}, True, None),
    (None, False, None),
])
def test_the_smoke_fails_on_a_stack_of_one(form, meshed, reason):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    failures = chip_smoke.grouped_matmul_failures(form, meshed)
    assert len(failures) == (reason is not None)
    assert reason is None or reason in failures[0]
