"""Learned sparse attention (a DeepSeek-Sparse-Attention lightning indexer
under GQA: `config.sparse`, ops/sparse_attention.py) at the `tiny-dsa` preset
— 4 index heads of 8 over one shared index key, topk 16 SHORTER than the
prompts, q/k norms, a rotary of three position components, 8 experts top 2 —
against the plain reference `benchmarks/reference/sparse_moe_decoder.py`
(`jax.lax.top_k` for the selection, no cache, no kernels), on seeded random
weights; the forms against each other; and what the engine does with the
index cache.

What is compared is LOGITS and SELECTED SETS. Tolerances:

- float32 weights, float32 cache: the same mathematics in another order (a
  threshold against a sort, a mask against a gather, a cache against none).
  Logits agree to 2e-5 on logits of order 0.5 (measured 4e-6); a token within
  1e-4 of a router tie is left out, at most a tenth may be.
- int8 K/V: the masked Pallas decode kernel against the masked XLA path over
  the SAME int8 cache and the SAME selection: 2e-2 on outputs of order 1 (the
  kernel casts probabilities to bfloat16 before the output product).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import sparse_moe_decoder as ref  # noqa: E402

from symmetry_tpu.engine.engine import (  # noqa: E402
    EngineError, InferenceEngine, SamplingParams)
from symmetry_tpu.engine.tokenizer import get_tokenizer  # noqa: E402
from symmetry_tpu.models import llama, moe  # noqa: E402
from symmetry_tpu.ops import decode_attention as da  # noqa: E402
from symmetry_tpu.ops import sparse_attention as sa  # noqa: E402
from symmetry_tpu.ops.attention import gqa_attention  # noqa: E402
from symmetry_tpu.ops.quant import quantize_kv  # noqa: E402
from symmetry_tpu.ops.rope import apply_rope  # noqa: E402

CFG = llama.preset("tiny-dsa")
TOPK = CFG.sparse.topk
MODEL = llama.hf_config_sparse(CFG)
ATOL = 2e-5


def params32(key=40):
    return llama.init_params(CFG, jax.random.key(key), jnp.float32)


def tokens_of(n, batch=2, key=1):
    return jax.random.randint(jax.random.key(key), (batch, n), 0,
                              CFG.vocab_size)


def kept_rows(details, eps=1e-4):
    """Positions from which no router choice up to there was a coin toss."""
    margins = np.stack([np.asarray(d["margin"]) for d in details])
    return ~np.logical_or.accumulate((margins < eps).any(axis=0))


# ------------------------------------------------------------ the selection

def top_k_sets(scores, valid, k):
    """The sets `jax.lax.top_k` names, row by row (the reference's way)."""
    masked = np.where(valid, scores, -np.inf)
    out = np.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        _, idx = jax.lax.top_k(jnp.asarray(masked[r]), min(k, scores.shape[1]))
        out[r, np.asarray(idx)] = True
    return out & valid


@pytest.mark.parametrize("case", ["random", "ties", "all_equal", "negatives",
                                  "signed_zeros", "few_candidates"])
@pytest.mark.parametrize("k", [1, 5, 16])
def test_the_threshold_selects_exactly_top_ks_set(case, k):
    rng = np.random.default_rng(7)
    rows, T = 12, 40
    scores = rng.normal(size=(rows, T)).astype(np.float32)
    valid = np.arange(T)[None, :] <= rng.integers(0, T, size=(rows, 1))
    if case == "ties":          # a constructed tie AT the threshold
        scores = np.round(scores * 2) / 2
    elif case == "all_equal":
        scores[:] = 0.25
    elif case == "negatives":
        scores = -np.abs(scores) - 1.0
    elif case == "signed_zeros":
        scores = np.where(rng.random((rows, T)) < 0.5, 0.0, -0.0
                          ).astype(np.float32)
        scores[:, ::7] = 1.0
    elif case == "few_candidates":
        valid = np.arange(T)[None, :] <= rng.integers(0, k + 1,
                                                      size=(rows, 1))
    got = np.asarray(sa.select(jnp.asarray(scores), jnp.asarray(valid), k))
    np.testing.assert_array_equal(got, top_k_sets(scores, valid, k))
    assert (got.sum(1) == np.minimum(valid.sum(1), k)).all()


def test_order_keys_order_as_the_values_do():
    x = np.array([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, np.inf],
                 np.float32)
    keys = np.asarray(sa._order_keys(jnp.asarray(x))).astype(np.int64)
    assert (np.diff(keys) > 0).all()      # the total order: -0.0 < +0.0


def test_counters_split_in_words_carry_and_read_back_exactly():
    vec = jnp.zeros((8 + sa.N_COUNTS,), jnp.int32).at[:8].set(3)
    keep = jnp.ones((2, 1500, 1500), bool)
    one = sa.counts(keep, keep, 16)
    for _ in range(3):
        vec = sa.add_counts(vec, one)
    got = sa.read_counts(np.asarray(vec[-sa.N_COUNTS:]))
    assert got == {"queries": 9000, "dense_queries": 0,
                   "candidates": 3 * 2 * 1500 * 1500,
                   "selected": 3 * 2 * 1500 * 1500}
    assert (np.asarray(vec[:8]) == 3).all()
    assert int(vec[-1]) < 1 << sa.COUNT_BITS       # the low word carried


# ------------------------------------------------- the dsa_select kernel
#
# Whole-number index queries, keys and weights: every score is exact in
# float32 whatever the order of its sums, and ties are plentiful — the
# kernel's set has to be `select`'s and `lax.top_k`'s position for position.

def whole(key, shape, dtype, lo=-2, hi=3):
    return jax.random.randint(jax.random.key(key), shape, lo, hi
                              ).astype(dtype)


def prompt_inputs(B, S, H, Di, dtype, key=0):
    return (whole(key, (B, S, H, Di), dtype), whole(key + 1, (B, S, Di),
                                                    dtype),
            whole(key + 2, (B, S, H), dtype, -1, 3))


def prompt_oracle(qi, ki, w, lens, topk):
    """`select` over `index_scores`, every query against the whole prompt,
    and its validity mask."""
    B, S = qi.shape[:2]
    t = jnp.arange(S, dtype=jnp.int32)
    valid = (t[None, None, :] <= t[None, :, None]) & (
        t[None, :, None] < lens[:, None, None])
    keep = sa.select(sa.index_scores(qi, ki, w), valid, topk)
    return np.asarray(keep), np.asarray(valid)


SELECT_CASES = {
    # B, S, index heads, index dim, topk, query tile, lengths, dtype
    "ragged lengths in one batch": (3, 128, 4, 8, 16, 32, [128, 5, 77],
                                    jnp.bfloat16),
    "a tile straddles topk": (2, 128, 4, 8, 40, 32, [128, 100],
                              jnp.float32),
    "a tile wholly under topk": (1, 128, 4, 8, 64, 32, [128], jnp.float32),
    "every query under topk": (2, 64, 4, 8, 64, 32, [64, 33], jnp.bfloat16),
    "padded queries": (2, 128, 4, 8, 16, 64, [70, 1], jnp.float32),
    "several key blocks, the cell's heads": (2, 512, 16, 64, 100, 256,
                                            [512, 300], jnp.bfloat16),
    "row groups inside a tile": (1, 512, 4, 8, 48, 256, [400], jnp.float32),
}


@pytest.mark.parametrize("case", list(SELECT_CASES))
def test_the_select_kernel_picks_selects_and_top_ks_sets_exactly(case):
    B, S, H, Di, topk, tile, lens, dtype = SELECT_CASES[case]
    qi, ki, w = prompt_inputs(B, S, H, Di, dtype, key=len(case))
    lens = jnp.asarray(lens, jnp.int32)
    keep, n = sa.prefill_keep(qi, ki, w, lens, topk, tile=tile)
    assert keep.dtype == jnp.int8 and keep.shape == (B, S, S)
    got = np.asarray(keep) != 0
    want, valid = prompt_oracle(qi, ki, w, lens, topk)
    np.testing.assert_array_equal(got, want)
    scores = np.asarray(sa.index_scores(qi, ki, w))
    for b in range(B):
        np.testing.assert_array_equal(
            got[b], top_k_sets(scores[b], valid[b], topk))
    # ties AT the threshold were there to be broken, and rows under topk
    # (and padded ones) kept every candidate (none)
    assert (got.sum(-1) == np.minimum(valid.sum(-1), topk)).all()
    kth = np.sort(np.where(valid, scores, -np.inf), axis=-1)[..., -topk]
    tied = ((scores == kth[..., None]) & valid).sum(-1)
    assert case.startswith("every query") or (tied > 1).any()
    # the four counters, from the lengths alone
    np.testing.assert_array_equal(
        np.asarray(n), np.asarray(sa.counts(want, valid, topk)))


@pytest.mark.parametrize("S, tile, why", [
    (384, 128, "key block"),      # 384 = 1.5 key blocks of 256
    (96, 64, "query tile"),
])
def test_the_select_kernel_refuses_a_prompt_its_blocks_do_not_divide(
        S, tile, why):
    qi, ki, w = prompt_inputs(1, S, 4, 8, jnp.float32)
    with pytest.raises(ValueError, match=why):
        sa.prefill_keep(qi, ki, w, jnp.asarray([S]), 16, tile=tile)


DECODE_CASES = {
    # layers, capacity, index heads, index dim, topk, lengths, dtype
    "a dead lane, a lane under topk": (2, 128, 4, 8, 16,
                                       [0, 5, 128, 77, 17], jnp.float32),
    "a lane exactly topk long": (2, 384, 4, 8, 16, [384, 16, 33, 0],
                                 jnp.bfloat16),
    "several groups a lane": (2, 6144, 16, 64, 100,
                              [6144, 0, 1500, 99, 101, 4097], jnp.bfloat16),
    "every lane under topk": (1, 256, 4, 8, 300, [256, 1, 0, 200],
                              jnp.float32),
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_the_select_kernels_decode_form_follows_each_lanes_length(case):
    L, T, H, Di, topk, lens, dtype = DECODE_CASES[case]
    B = len(lens)
    qi = whole(1, (B, 1, H, Di), dtype)
    idx = whole(2, (L, B, T, Di), dtype)
    w = whole(3, (B, 1, H), dtype, -1, 3)
    kv = jnp.asarray(lens, jnp.int32)
    pos = jnp.maximum(kv - 1, 0)[:, None]
    assert sa.decode_group(T, B, Di) is not None
    step = jax.jit(lambda layer: sa.cache_keep(qi, idx, w, pos, kv, topk,
                                               layer=layer))
    for layer in range(L):
        keep, n = step(jnp.int32(layer))
        assert keep.dtype == jnp.bool_ and keep.shape == (B, 1, T)
        want, n_want = sa._masks(qi, idx[layer], w, pos, kv, topk)
        np.testing.assert_array_equal(np.asarray(keep), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(n), np.asarray(n_want))
        scores = np.asarray(sa.index_scores(qi, idx[layer], w))[:, 0]
        valid = np.arange(T)[None, :] < np.asarray(kv)[:, None]
        np.testing.assert_array_equal(np.asarray(keep)[:, 0],
                                      top_k_sets(scores, valid, topk))
    # another layer's keys give another set: the layer is DMA addressing
    assert case.startswith("every lane") or (
        np.asarray(step(jnp.int32(0))[0]) != np.asarray(keep)).any()


def test_a_cache_without_the_decode_layout_selects_through_xla():
    """A capacity no group of keys divides (and any S > 1 over a cache)
    keeps the `jnp` form: the same sets, no kernel."""
    L, B, T, H, Di, topk = 2, 3, 100, 4, 8, 16
    assert sa.decode_group(T, B, Di) is None
    assert sa.decode_group(16384, 64, 64) == 2048
    assert sa.decode_group(16384, 512, 64) is None   # over the kernel's VMEM
    assert sa.decode_group(16384, 64, 128) is None   # a row-major cache
    qi, idx = whole(1, (B, 1, H, Di), jnp.float32), whole(
        2, (L, B, T, Di), jnp.float32)
    w, kv = whole(3, (B, 1, H), jnp.float32), jnp.asarray([100, 0, 37])
    pos = jnp.maximum(kv - 1, 0)[:, None]
    jaxpr = str(jax.make_jaxpr(lambda: sa.cache_keep(
        qi, idx, w, pos, kv, topk, layer=jnp.int32(1)))())
    assert "pallas_call" not in jaxpr
    keep, n = sa.cache_keep(qi, idx, w, pos, kv, topk, layer=jnp.int32(1))
    want, n_want = sa._masks(qi, idx[1], w, pos, kv, topk)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(n), np.asarray(n_want))
    paths = {"prefill": "pallas", "decode": "pallas"}
    assert llama.sparse_select(paths, 16384, 64, 64) == {
        "prefill": "dsa_select kernel", "decode": "dsa_select kernel"}
    assert llama.sparse_select({**paths, "prefill": "xla"}, 100, 3, 8) == {
        "prefill": "xla", "decode": "xla"}


@pytest.mark.parametrize("form", ["prefill", "decode"])
def test_attention_under_the_kernels_mask_is_attention_under_selects(form):
    """`flash_sparse` and `decode_attention(keep=)` fed the kernel's mask
    give what they give under `select`'s."""
    ks = jax.random.split(jax.random.key(9), 3)
    if form == "prefill":
        B, S, H, K, D, topk = 2, 128, 4, 2, 16, 24
        qi, ki, w = prompt_inputs(B, S, 4, 8, jnp.float32, key=5)
        lens = jnp.asarray([S, 90])
        keep, _ = sa.prefill_keep(qi, ki, w, lens, topk, tile=32)
        want, _ = prompt_oracle(qi, ki, w, lens, topk)
        q, k, v = (jax.random.normal(ks[i], (B, S, n, D), jnp.float32)
                   for i, n in enumerate((H, K, K)))
        run = lambda m: sa.flash_sparse(  # noqa: E731
            q, k, v, m, block_q=32, block_k=64, interpret=True)
        got, ref_out = run(keep), run(jnp.asarray(want, jnp.int8))
    else:
        L, B, T, D, nq, n_kv, topk = 2, 4, 256, 128, 32, 4, 40
        qi, idx = whole(1, (B, 1, 4, 8), jnp.float32), whole(
            2, (L, B, T, 8), jnp.float32)
        w, lens = whole(3, (B, 1, 4), jnp.float32), jnp.asarray(
            [5, 130, 256, 77], jnp.int32)
        pos = (lens - 1)[:, None]
        keep, _ = sa.cache_keep(qi, idx, w, pos, lens, topk,
                                layer=jnp.int32(1))
        want, _ = sa._masks(qi, idx[1], w, pos, lens, topk)
        kq, ksc = quantize_kv(jax.random.normal(ks[0], (L, B, T, n_kv, D)))
        vq, vsc = quantize_kv(jax.random.normal(ks[1], (L, B, T, n_kv, D)))
        ksc, vsc = (jnp.moveaxis(s, 2, 3) for s in (ksc, vsc))
        q = jax.random.normal(ks[2], (B, nq, D), jnp.bfloat16)
        run = lambda m: da.decode_attention(  # noqa: E731
            q, kq, vq, jnp.int32(1), lens, ksc, vsc, m[:, 0],
            interpret=True)
        got, ref_out = run(keep), run(want)
    np.testing.assert_array_equal(np.asarray(keep) != 0, np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref_out, np.float32))


def test_length_counts_are_the_masks_counts_past_one_words_range():
    """From the lengths alone, a row of the batch at a time: two prompts of
    1,500 (2.25 M candidates) carry into the high words as `counts` does."""
    n = jnp.broadcast_to(jnp.arange(1, 1501, dtype=jnp.int32), (2, 1500))
    valid = jnp.broadcast_to(jnp.tril(jnp.ones((1500, 1500), bool)),
                             (2, 1500, 1500))
    got = sa.read_counts(np.asarray(sa.length_counts(n, 16)))
    assert got == sa.read_counts(np.asarray(sa.counts(
        sa.select(jnp.zeros((2, 1500, 1500)), valid, 16), valid, 16)))
    assert got == {"queries": 3000, "dense_queries": 32,
                   "candidates": 2 * 1500 * 1501 // 2,
                   "selected": 2 * (16 * 17 // 2 + 1484 * 16)}


# ------------------------------------------------- against the reference

def program_logits(params, tokens, n_prompt, *, flash, cache_dtype=jnp.float32,
                   rope_positions=None):
    """Prefill `n_prompt` tokens (flash: the kernel route; else the XLA
    route over the cache), then one token at a time through the cache."""
    B, n = tokens.shape
    cache = llama.init_cache(CFG, B, 64, cache_dtype, count_experts=True)
    kw = {}
    with jax.default_matmul_precision("highest"):
        if rope_positions is not None:
            kw["rope_positions"] = rope_positions[:, :, :n_prompt]
        h, cache = llama.forward_hidden(params, CFG, tokens[:, :n_prompt],
                                        cache, prefill_flash=flash, **kw)
        got = [llama.logits_from_hidden(params, CFG, h)]
        for i in range(n_prompt, n):
            if rope_positions is not None:
                kw["rope_positions"] = rope_positions[:, :, i:i + 1]
            h, cache = llama.forward_hidden(params, CFG, tokens[:, i:i + 1],
                                            cache, **kw)
            got.append(llama.logits_from_hidden(params, CFG, h))
    return np.asarray(jnp.concatenate(got, axis=1)), cache


# prompt lengths under / at / over topk; 16 more tokens decode through
@pytest.mark.parametrize("n_prompt", [8, TOPK, 32])
@pytest.mark.parametrize("flash", [True, False],
                         ids=["prefill-kernel", "prefill-xla"])
def test_prefill_then_decode_through_the_cache_match_the_reference(
        n_prompt, flash):
    params = params32()
    tokens = tokens_of(n_prompt + 16)
    got, cache = program_logits(params, tokens, n_prompt, flash=flash)
    kept = 0
    for b in range(tokens.shape[0]):
        want, details = ref.reference_logits(params, MODEL, tokens[b],
                                             with_details=True)
        ok = kept_rows(details)
        kept += ok.sum()
        np.testing.assert_allclose(got[b][ok], np.asarray(want)[ok],
                                   atol=ATOL, rtol=0)
    assert kept >= 0.9 * tokens.size
    # what was counted is what the lengths say: every query selects
    # min(t + 1, topk) of its t + 1 candidates, in each of the layers
    t = np.arange(tokens.shape[1]) + 1
    counted = sa.read_counts(np.asarray(cache.expert_pairs[-sa.N_COUNTS:]))
    per = CFG.num_layers * tokens.shape[0]
    assert counted == {"queries": per * len(t),
                       "dense_queries": per * int((t <= TOPK).sum()),
                       "candidates": per * int(t.sum()),
                       "selected": per * int(np.minimum(t, TOPK).sum())}


def test_a_full_forward_in_one_call_matches_the_reference():
    params = params32(41)
    tokens = tokens_of(40, key=2)
    cache = llama.init_cache(CFG, 2, 64, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(params, CFG, tokens, cache)
    for b in range(2):
        want, details = ref.reference_logits(params, MODEL, tokens[b],
                                             with_details=True)
        ok = kept_rows(details)
        assert ok.mean() >= 0.9
        np.testing.assert_allclose(np.asarray(got[b])[ok],
                                   np.asarray(want)[ok], atol=ATOL, rtol=0)


def test_the_programs_selected_sets_are_the_references():
    """Layer 0's sets (same input on both sides) are top_k's, query by
    query; and the reference, GIVEN the program's sets for every layer,
    returns the program's logits."""
    params = params32(42)
    tokens = tokens_of(40, batch=1, key=3)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    with jax.default_matmul_precision("highest"):
        x = llama.rms_norm(jnp.take(params["embed"], tokens, axis=0),
                           lp["attn_norm"], CFG.rms_eps)
        pos = jnp.arange(40)[None]
        qi = apply_rope((x @ lp["wqi"]).reshape(1, 40, 4, 8), pos,
                        CFG.rope_theta)
        ki = apply_rope((x @ lp["wki"])[:, :, None], pos,
                        CFG.rope_theta)[:, :, 0]
        keep, _ = sa.prefill_keep(qi, ki, x @ lp["wwi"], jnp.array([40]),
                                  TOPK, tile=8)
    _, details = ref.reference_logits(params, MODEL, tokens[0],
                                      with_details=True)
    np.testing.assert_array_equal(np.asarray(keep[0]) != 0,
                                  np.asarray(details[0]["keep"]))
    given = ref.reference_logits(
        params, MODEL, tokens[0],
        selection=[d["keep"] for d in details])
    free = ref.reference_logits(params, MODEL, tokens[0])
    np.testing.assert_allclose(np.asarray(given), np.asarray(free),
                               atol=1e-6, rtol=0)


def test_unequal_position_components_turn_their_own_frequency_pairs():
    params = params32(43)
    tokens = tokens_of(24, key=4)
    rng = np.random.default_rng(5)
    # an image patch grid in the middle of the text: temporal stands
    # still while height and width walk
    pos3 = np.broadcast_to(np.arange(24), (3, 2, 24)).copy()
    pos3[1, :, 8:16] = 8 + rng.integers(0, 4, size=(2, 8))
    pos3[2, :, 8:16] = 8 + rng.integers(0, 4, size=(2, 8))
    pos3[0, :, 8:16] = 8
    got, _ = program_logits(params, tokens, 16, flash=True,
                            rope_positions=jnp.asarray(pos3))
    plain, _ = program_logits(params, tokens, 16, flash=True)
    assert np.abs(got - plain).max() > 1e-3       # the components matter
    for b in range(2):
        want, details = ref.reference_logits(
            params, MODEL, tokens[b], positions=pos3[:, b],
            with_details=True)
        ok = kept_rows(details)
        np.testing.assert_allclose(got[b][ok], np.asarray(want)[ok],
                                   atol=ATOL, rtol=0)


def test_three_equal_components_are_the_plain_rotary():
    x = jax.random.normal(jax.random.key(0), (2, 9, 4, 16))
    pos = jnp.arange(9)[None] + jnp.array([[0], [5]])
    np.testing.assert_array_equal(
        np.asarray(apply_rope(x, jnp.broadcast_to(pos, (3, 2, 9)), 1e4,
                              mrope_section=(2, 3, 3))),
        np.asarray(apply_rope(x, pos, 1e4)))
    with pytest.raises(ValueError, match="mrope_section"):
        apply_rope(x, jnp.broadcast_to(pos, (3, 2, 9)), 1e4,
                   mrope_section=(2, 3, 4))
    with pytest.raises(ValueError, match="mrope_section"):
        llama.forward_hidden(
            llama.init_params(llama.preset("tiny-moe"), jax.random.key(0)),
            llama.preset("tiny-moe"), jnp.zeros((1, 4), jnp.int32),
            llama.init_cache(llama.preset("tiny-moe"), 1, 8),
            rope_positions=jnp.zeros((3, 1, 4), jnp.int32))


# ----------------------------------------------- the forms, one to another

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S", [64, 128])
def test_flash_under_a_keep_set_is_masked_attention(dtype, S):
    B, H, K, D = 2, 4, 2, 16
    ks = jax.random.split(jax.random.key(S), 4)
    q, k, v = (jax.random.normal(ks[i], (B, S, n, D), dtype)
               for i, n in enumerate((H, K, K)))
    pos = jnp.arange(S)
    lens = jnp.array([S - 7, S])
    valid = ((pos[None, None] <= pos[None, :, None])
             & (pos[None, None] < lens[:, None, None])
             & (pos[None, :, None] < lens[:, None, None]))
    keep = sa.select(jax.random.normal(ks[3], (B, S, S)), valid, 9)
    got = sa.flash_sparse(q, k, v, keep.astype(jnp.int8), block_q=32,
                          block_k=64, interpret=True)
    want = gqa_attention(q, k, v, jnp.broadcast_to(pos, (B, S)), lens,
                         keep=keep)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    for b in range(B):
        n = int(lens[b])
        np.testing.assert_allclose(
            np.asarray(got[b, :n], np.float32),
            np.asarray(want[b, :n], np.float32), atol=tol, rtol=0)
    assert np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("n_kv", [4, 8])
@pytest.mark.parametrize("lengths", [(5, 130, 256, 77), (256, 1, 200, 31)])
def test_the_decode_kernel_under_a_selection_is_the_masked_xla_path(
        n_kv, lengths):
    """At the served head size (128) and an int8 cache of interleaved
    heads: the selection rides with the scale planes."""
    L, B, T, D, nq = 2, 4, 256, 128, 32
    ks = jax.random.split(jax.random.key(n_kv), 4)
    k = jax.random.normal(ks[0], (L, B, T, n_kv, D), jnp.float32)
    v = jax.random.normal(ks[1], (L, B, T, n_kv, D), jnp.float32)
    kq, ksc = quantize_kv(k)
    vq, vsc = quantize_kv(v)
    ksc, vsc = (jnp.moveaxis(s, 2, 3) for s in (ksc, vsc))    # [L, B, K, T]
    q = jax.random.normal(ks[2], (B, nq, D), jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    pos = jnp.arange(T)
    valid = pos[None] < lens[:, None]
    keep = sa.select(jax.random.normal(ks[3], (B, T)), valid, 40)
    assert da.keep_supported(n_kv, 1, True)
    for layer in range(L):
        got = da.decode_attention(q, kq, vq, jnp.int32(layer), lens, ksc,
                                  vsc, keep, interpret=True)
        want = gqa_attention(q[:, None], kq[layer], vq[layer],
                             (lens - 1)[:, None], lens, k_scale=ksc[layer],
                             v_scale=vsc[layer], keep=keep[:, None])[:, 0]
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=2e-2,
                                   rtol=0)
    # and the selection matters: without it the output is another
    dense = da.decode_attention(q, kq, vq, jnp.int32(0), lens, ksc, vsc,
                                interpret=True)
    assert np.abs(np.asarray(dense - got, np.float32))[1].max() > 0.05


def test_a_selection_needs_the_scale_planes_of_interleaved_int8_lanes():
    assert not da.keep_supported(4, 2, False)      # a bf16 cache
    assert not da.keep_supported(2, 1, True)       # head-major int8 lanes
    paths = llama.attention_paths(
        llama.preset("keye-vl-2.0-30b-a3b"), 16384, batch=64, kv_bytes=1)
    assert paths["decode"] != "xla" and paths["decode_block_t"] == 256
    assert llama.attention_paths(
        llama.preset("keye-vl-2.0-30b-a3b"), 16384, batch=64,
        kv_bytes=2)["decode"] == "xla"
    forms = llama.sparse_forms(paths)
    assert forms["decode"].startswith("masked (decode kernel")
    assert forms["prefill"].startswith("masked (dsa_flash kernel")


@pytest.mark.parametrize("quantized", [False, True], ids=["f32-kv", "int8-kv"])
def test_a_chunk_over_a_cache_selects_as_single_positions_do(quantized):
    """The model function's S > 1 continuation over a non-empty cache
    (the XLA path under a selection, as the XLA decode is; the engine
    refuses `prefill_chunk`, which would serve it) against one position at
    a time, over the same cache."""
    params = params32(44)
    tokens = tokens_of(40, key=6)

    def run(step):
        cache = llama.init_cache(CFG, 2, 64, jnp.float32,
                                 quantized=quantized)
        out = []
        with jax.default_matmul_precision("highest"):
            _, cache = llama.forward_hidden(params, CFG, tokens[:, :24],
                                            cache)
            for i in range(24, 40, step):
                h, cache = llama.forward_hidden(
                    params, CFG, tokens[:, i:i + step], cache)
                out.append(h)
        return np.asarray(jnp.concatenate(out, axis=1)), cache

    one, c1 = run(1)
    many, c8 = run(8)
    # (hidden states of order 8: the same sets, another order of sums)
    np.testing.assert_allclose(many, one, atol=1e-4, rtol=0)
    np.testing.assert_allclose(np.asarray(c8.idx), np.asarray(c1.idx),
                               atol=1e-4)


# ------------------------------------------------------- params and config

def test_only_a_sparse_config_gains_leaves_and_no_other_models_weights_move():
    p = params32()["layers"]
    assert p["wqi"].shape == (2, 64, 32) and p["wki"].shape == (2, 64, 8)
    assert p["wwi"].shape == (2, 64, 4) and p["q_norm"].shape == (2, 16)
    plain = dataclasses.replace(CFG, sparse=None, qk_norm=False,
                                mrope_section=None)
    q = llama.init_params(plain, jax.random.key(40), jnp.float32)["layers"]
    assert sorted(set(p) - set(q)) == ["k_norm", "q_norm", "wki", "wqi",
                                       "wwi"]
    for name in q:                     # the same keys draw the same leaves
        np.testing.assert_array_equal(np.asarray(p[name]),
                                      np.asarray(q[name]))
    cache = llama.init_cache(plain, 2, 32, count_experts=True)
    assert cache.idx is None and cache.expert_pairs.shape == (8,)
    cache = llama.init_cache(CFG, 2, 32, jnp.bfloat16, quantized=True,
                             count_experts=True)
    assert cache.idx.shape == (2, 2, 32, 8) and cache.idx.dtype == jnp.bfloat16
    assert cache.expert_pairs.shape == (8 + sa.N_COUNTS,)
    int8 = llama.init_params(CFG, jax.random.key(0), jnp.bfloat16,
                             quantize=True)["layers"]
    assert int8["wqi"].q.dtype == jnp.int8 and int8["wki"].q.dtype == jnp.int8
    assert int8["wwi"].dtype == jnp.bfloat16       # like the router
    axes = llama.param_logical_axes(CFG)["layers"]
    assert set(axes) == set(p)


def test_config_from_hf_reads_the_published_keys():
    cut = llama.preset("keye-vl-2.0-30b-a3b")
    published = llama.hf_config_sparse(cut)
    assert published["sa_config"] == {
        "topk": 2048, "indexer_num_heads": 16, "indexer_head_dim": 64,
        "indexer_num_kv_heads": 1}
    assert llama.config_from_hf(published) == cut
    assert llama.config_from_hf(MODEL) == CFG
    with pytest.raises(ValueError, match="mlp_only_layers"):
        llama.config_from_hf(dict(published, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="key head"):
        llama.config_from_hf(dict(published, sa_config=dict(
            published["sa_config"], indexer_num_kv_heads=2)))
    # a reading of its own, with both edges: the mixture at decode's 64
    # tokens alone of this cell's programs (every prefill is routed)
    lo, hi = moe.ROUTED_FROM[(128, 8)]
    assert lo <= 64 < hi and moe.moe_route(64, 128, 8) == "dense-mixture"
    assert moe.moe_route(4096, 128, 8) == "routed"


def test_an_hf_checkpoint_round_trips_through_the_name_map(tmp_path):
    from symmetry_tpu.engine.weights import (
        CheckpointError, convert_hf_state_dict, load_checkpoint,
        save_checkpoint)
    from safetensors.numpy import load_file

    params = params32()
    save_checkpoint(str(tmp_path), params, CFG)
    loaded, cfg = load_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert cfg == CFG
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(params),
                                 jax.tree_util.tree_leaves_with_path(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))
    hf = load_file(os.path.join(tmp_path, "model.safetensors"))
    assert hf["model.layers.0.self_attn.indexer.wq.weight"].shape == (32, 64)
    assert hf["model.layers.1.self_attn.indexer.wk.weight"].shape == (8, 64)
    assert hf["model.layers.1.self_attn.indexer.weights_proj.weight"
              ].shape == (4, 64)
    assert hf["model.layers.0.self_attn.q_norm.weight"].shape == (16,)
    assert hf["model.layers.0.mlp.gate.weight"].shape == (8, 64)
    assert hf["model.layers.1.mlp.experts.7.down_proj.weight"].shape == (
        64, 32)
    del hf["model.layers.1.self_attn.indexer.wk.weight"]
    with pytest.raises(CheckpointError, match="wki"):
        convert_hf_state_dict(hf, CFG)
    # and a model without an indexer refuses a tensor of one
    with pytest.raises(CheckpointError, match="wqi"):
        convert_hf_state_dict(
            {"model.layers.0.self_attn.indexer.wq.weight":
             np.zeros((32, 64), np.float32)}, llama.preset("tiny"))


# ---------------------------------------------------------------- the engine

def make_engine(**kw):
    params = llama.init_params(CFG, jax.random.key(0), jnp.bfloat16,
                               quantize=True)
    args = dict(max_slots=4, max_seq_len=128, prefill_buckets=(32, 64),
                decode_block=4, kv_quant=True, prefill_chunk=None)
    args.update(kw)
    return InferenceEngine(
        CFG, params, get_tokenizer(None, vocab_size=CFG.vocab_size), **args)


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    eng.warmup()
    return eng


GREEDY = SamplingParams()
PROMPT_A = list(range(5, 45))          # 40 tokens: 2.5 x topk
PROMPT_B = list(range(100, 160))


def stream(eng, slot, ids, blocks=3):
    out = [eng.prefill_and_insert(slot, ids, GREEDY)]
    for _ in range(blocks):
        out += [int(t) for t in eng.decode_steps()[:, slot]]
    return out


def test_an_insert_copies_the_rows_index_keys_into_the_lane_and_no_other(
        engine):
    before = np.asarray(engine.state.cache.idx, np.float32)
    engine.prefill_and_insert(3, PROMPT_A, GREEDY)
    after = np.asarray(engine.state.cache.idx, np.float32)
    assert np.abs(after[:, 3, :40] - before[:, 3, :40]).max() > 0
    for lane in (0, 1, 2):
        np.testing.assert_array_equal(after[:, lane], before[:, lane])
    cache = llama.init_cache(CFG, 1, 64, jnp.bfloat16, quantized=True)
    ids = jnp.zeros((1, 64), jnp.int32).at[0, :40].set(jnp.asarray(PROMPT_A))
    _, cache = llama.forward_hidden(engine.params, CFG, ids, cache,
                                    jnp.asarray([40], jnp.int32),
                                    prefill_flash=True)
    np.testing.assert_array_equal(after[:, 3, :40],
                                  np.asarray(cache.idx[:, 0, :40],
                                             np.float32))
    engine.release_slot(3)


def test_a_reused_lane_gives_the_first_requests_tokens_again(engine):
    first = stream(engine, 1, PROMPT_A)
    engine.release_slot(1)
    other = stream(engine, 1, PROMPT_B)
    engine.release_slot(1)
    again = stream(engine, 1, PROMPT_A)
    assert first == again and first != other
    engine.release_slot(1)


def test_a_coalesced_prefill_of_unequal_lengths_matches_single_prefills(
        engine):
    single = {}
    for ids in (PROMPT_A[:20], PROMPT_A[:30]):
        single[len(ids)] = stream(engine, 2, ids)
        engine.release_slot(2)
    firsts = engine.prefill_and_insert_many(
        [(0, PROMPT_A[:20], GREEDY), (2, PROMPT_A[:30], GREEDY)])
    toks = np.concatenate([engine.decode_steps() for _ in range(3)])
    for row, (slot, n) in enumerate(((0, 20), (2, 30))):
        assert [firsts[row]] + toks[:, slot].tolist() == single[n]
    for slot in (0, 2):
        engine.release_slot(slot)


def test_serving_compiles_nothing_after_warmup_and_counts_what_it_selected(
        engine):
    before = engine.compile_cache_sizes()
    counted = dict(engine.counters["dsa"])
    stream(engine, 0, PROMPT_B, blocks=2)
    engine.release_slot(0)
    assert engine.compile_cache_sizes() == before
    grew = {k: engine.counters["dsa"][k] - counted[k] for k in counted}
    # the prompt's 60 queries in each of 2 layers, then 8 steps of 4 lanes
    # (an idle lane's query is counted too: its stale rows are candidates)
    t = np.arange(60) + 1
    assert grew["queries"] >= 2 * (60 + 8)
    assert grew["selected"] >= 2 * (int(np.minimum(t, TOPK).sum()) + 8 * TOPK)
    assert grew["selected"] < grew["candidates"]
    assert grew["dense_queries"] >= 2 * TOPK
    assert len(engine.expert_pairs) == CFG.num_experts     # no counter in it


def test_the_engine_reports_the_sparse_form_and_the_index_cache(engine):
    report = engine.attention_paths()
    # head size 16 has no decode-kernel geometry: the tiny model decodes on
    # the XLA path; the prefill kernel interprets on the CPU
    assert report["sparse"] == {
        "topk": TOPK, "index_heads": 4,
        "form": {"prefill": "masked (dsa_flash kernel)",
                 "decode": "masked (xla)"},
        # (the selection is the kernel's in both: a cache of 128 positions,
        # one lane tile, has its decode layout)
        "select": {"prefill": "dsa_select kernel",
                   "decode": "dsa_select kernel"},
        "index_bytes_per_token": 2 * 8 * 2,
        "index_cache_bytes": 2 * 8 * 2 * 4 * 128}
    # K (16) + V (16) int8 + two float32 scales, 2 KV heads, 2 layers, and
    # the index keys
    assert engine.kv_bytes_per_token() == 2 * 2 * 2 * (16 + 4) + 32
    plain = InferenceEngine(
        llama.preset("tiny"),
        llama.init_params(llama.preset("tiny"), jax.random.key(0)),
        get_tokenizer(None, vocab_size=512), max_slots=2, max_seq_len=64,
        prefill_buckets=(32,))
    assert "sparse" not in plain.attention_paths()
    assert "dsa" not in plain.counters
    assert plain.index_bytes_per_token() == 0


REFUSED = {
    "prefix_cache_mb": dict(prefix_cache_bytes=1 << 20, prefill_chunk=16),
    "prefill_chunk": dict(prefill_chunk=16),
    "role": dict(role="prefill"),
}


@pytest.mark.parametrize("setting", sorted(REFUSED))
def test_the_engine_refuses_what_cannot_carry_the_index_cache(setting):
    with pytest.raises(EngineError, match=f"tpu.{setting}"):
        make_engine(**REFUSED[setting])


CONFIG_REFUSED = {
    "prefill_chunk": {"prefill_chunk": 64},
    "prefix_cache_mb": {"prefix_cache_mb": 64},
    "speculative": {"speculative": {"k_draft": 4}},
    "role": {"role": "disagg"},
    "mesh": {"mesh": {"model": 4}},
}


@pytest.mark.parametrize("preset", ["tiny-dsa", "keye-vl-2.0-30b-a3b"])
@pytest.mark.parametrize("setting", sorted(CONFIG_REFUSED))
def test_each_refused_setting_is_a_config_error_before_anything_is_built(
        setting, preset):
    from symmetry_tpu.provider.config import ConfigError, ConfigManager

    def config(**tpu):
        return {"name": "p", "public": True, "serverKey": "00" * 32,
                "modelName": "m", "apiProvider": "tpu_native",
                "tpu": {"model_preset": preset, "prefill_chunk": None,
                        **tpu}}

    ConfigManager(config=config())      # the plain configuration is fine
    with pytest.raises(ConfigError, match=f"tpu.{setting}"):
        ConfigManager(config=config(**CONFIG_REFUSED[setting]))


def test_the_default_chunk_is_refused_with_the_setting_that_serves():
    """`tpu.prefill_chunk` defaults to 256: a configuration that names the
    preset and nothing else is told what to set."""
    from symmetry_tpu.provider.config import ConfigError, ConfigManager

    with pytest.raises(ConfigError, match="set prefill_chunk: null"):
        ConfigManager(config={
            "name": "p", "public": True, "serverKey": "00" * 32,
            "modelName": "m", "apiProvider": "tpu_native",
            "tpu": {"model_preset": "keye-vl-2.0-30b-a3b"}})


def test_symtop_shows_the_selected_share_and_the_form_beside_the_tail():
    import tools.symtop as symtop

    engine = {"dsa": {"queries": 10, "candidates": 4000, "selected": 1240,
                      "dense_queries": 2},
              "startup": {"attention": {"sparse": {
                  "form": {"decode": "masked (decode kernel)"}}}}}
    rows = symtop.build_rows("prov", {}, None, now=0.0, engine=engine)
    assert rows[0]["dsa"] == "31% masked"
    engine["startup"]["attention"]["sparse"]["select"] = {
        "prefill": "dsa_select kernel", "decode": "dsa_select kernel"}
    assert symtop.read_dsa(engine) == "31% masked/kernel"
    engine["startup"]["attention"]["sparse"]["select"]["decode"] = "xla"
    assert symtop.read_dsa(engine) == "31% masked/xla"
    del engine["startup"]["attention"]["sparse"]["select"]
    head, first = symtop.render_table(rows).splitlines()[:2]
    assert head.split()[-4] == "DSA" and "31% masked" in first
    for other in (None, {}, {"dsa": {"candidates": 0, "selected": 0}}):
        assert symtop.build_rows("prov", {}, None, now=0.0,
                                 engine=other)[0]["dsa"] is None


def _parity_readings(**over):
    def stat(m, w):
        return {"n": 10, "median": m, "p99": w, "worst": w}

    r = {"set_sizes": True, "excluded_share": 0.19,
         "selection_agreement_layer0": {"min": 0.994, "median": 0.999},
         "selection_agreement": {"min": 0.17, "median": 0.918},
         "attn0": {"prefill": stat(0.00426, 0.00471),
                   "decode": stat(0.0086, 0.0093)},
         "attn0_lower": {"prefill": stat(0.00631, 0.0078),
                         "decode": stat(0.0098, 0.0104)},
         "given": {"all": stat(0.0128, 0.308)},
         "given_clear": stat(0.0115, 0.0169),
         "free": {"all": stat(0.098, 0.382)}}
    r.update(over)
    return r


@pytest.mark.parametrize("fault, failed", [
    (dict(), None),
    # a kernel's fault errs by the output itself
    (dict(attn0={"prefill": {"median": 1.0, "worst": 1.0},
                 "decode": {"median": 0.0086, "worst": 0.0093}}),
     "attn0_prefill_median"),
    (dict(selection_agreement_layer0={"min": 0.5, "median": 0.9}),
     "agree0_min"),
    (dict(set_sizes=False), "set_sizes"),
    (dict(given_clear={"worst": 0.2}), "given_clear_atol"),
], ids=["the-chips-reading", "kernel-fault", "block-selection", "set-size",
        "arithmetic"])
def test_the_parity_verdict_passes_the_chips_reading_and_fails_the_control(
        fault, failed):
    """tools/dsa_parity.py `verdict` under its LIMITS: PR 40's chip reading
    is ok, the bfloat16-softmax control is not (by `attn0_prefill_median`
    alone), and each fault fails by its own check."""
    from tools.dsa_parity import LIMITS, verdict

    v = verdict(_parity_readings(**fault), LIMITS)
    assert not v["lower_ok"]
    assert v["lower_checks"] == {"attn0_prefill_median": False}
    if failed is None:
        assert v["ok"] and all(v["checks"].values())
    else:
        assert not v["ok"] and not v["checks"][failed]


@pytest.mark.parametrize("hf_block, router", [
    ("qwen3_moe", "mlp.gate.weight"),
    ("mixtral", "block_sparse_moe.gate.weight")])
def test_checkpoint_names_follow_the_expert_blocks_family_not_the_indexer(
        hf_block, router):
    """`hf_moe_names` keys on `MoEConfig.hf_block`: a Mixtral-named block
    WITH an indexer keeps Mixtral's names, and a Qwen3-MoE block without
    one keeps its own."""
    import dataclasses

    with_indexer = dataclasses.replace(CFG, hf_block=hf_block)
    without = dataclasses.replace(CFG, hf_block=hf_block, sparse=None)
    for config in (with_indexer, without):
        assert llama.hf_moe_names(config)[0] == router
    assert llama.preset("mixtral-8x7b").hf_block == "mixtral"
    assert llama.preset("keye-vl-2.0-30b-a3b").hf_block == "qwen3_moe"


def test_only_the_new_presets_draw_at_the_fan_in():
    """`init_fan_in` redraws a preset's random weights: the older presets
    (whose cells' weights may not move) keep `layers ** -0.5`."""
    fan_in = {name for name, c in llama.PRESETS.items()
              if getattr(c, "init_fan_in", False)}
    assert fan_in == {"tiny-dsa", "keye-vl-2.0-30b-a3b",
                      "tiny-bd", "sdar-30b-a3b-chat"}     # PR 47's pair
    p = llama.init_params(CFG, jax.random.key(0), jnp.float32)["layers"]
    for name, fan in (("wq", CFG.hidden_size), ("wd", CFG.intermediate_size),
                      ("router", CFG.hidden_size)):
        assert abs(float(jnp.std(p[name])) * fan ** 0.5 - 1) < 0.1, name
    old = llama.init_params(llama.preset("tiny-moe8"), jax.random.key(0),
                            jnp.float32)["layers"]
    assert abs(float(jnp.std(old["router"])) * 2 ** 0.5 - 1) < 0.1
