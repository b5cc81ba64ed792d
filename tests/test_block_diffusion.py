"""Generation by diffusion over blocks (`config.diffusion`, the SDAR family)
at the `tiny-bd` preset — blocks of four under the block mask, GQA with q/k
norms, 8 experts top 2, an untied head — against the plain reference
`benchmarks/reference/block_diffusion_moe_decoder.py` (a dense masked softmax,
no cache, no kernels; a Python generation loop), on seeded random weights.

What is compared is LOGITS for the forward (prefill, then blocks through the
cache) and TOKENS under greedy for the generation loop, served through the
engine and the scheduler. float32 weights and a float32 cache: the same
mathematics in another order, so logits agree to 2e-5 on logits of order 0.5
(measured 5e-7) and greedy tokens are equal token for token.
"""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from reference import block_diffusion_moe_decoder as ref  # noqa: E402

from symmetry_tpu.engine.engine import (  # noqa: E402
    EngineError, InferenceEngine, SamplingParams)
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler  # noqa: E402
from symmetry_tpu.engine.tokenizer import get_tokenizer  # noqa: E402
from symmetry_tpu.models import llama  # noqa: E402
from symmetry_tpu.ops import sampling  # noqa: E402
from symmetry_tpu.ops.attention import gqa_attention  # noqa: E402
from symmetry_tpu.ops.flash import flash_prefill  # noqa: E402

CFG = llama.preset("tiny-bd")
BLOCK = CFG.diffusion.block
MASK = CFG.diffusion.mask_token_id
MODEL = llama.hf_config_diffusion(CFG)
ATOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(3), jnp.float32)


def ids_of(n, key=0):
    return [int(t) for t in jax.random.randint(jax.random.key(100 * key + n),
                                               (n,), 0, 500)]


# ---------------------------------------------------------------------------
# the mask in the two attention routes


def dense_block_attention(q, k, v, lens, block):
    """[B, S, H, D] x [B, S, K, D] -> [B, S, H, D]: a dense masked softmax."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(D)
    blk = jnp.arange(S) // block
    mask = (blk[None, :] <= blk[:, None])[None] & (
        jnp.arange(S)[None, None, :] < lens[:, None, None])
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("n", [29, 30, 31, 32, 61])
def test_flash_prefill_block_mask(n):
    S = 32 if n <= 32 else 64
    ks = jax.random.split(jax.random.key(n), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 16))
    k = jax.random.normal(ks[1], (2, S, 2, 16))
    v = jax.random.normal(ks[2], (2, S, 2, 16))
    lens = jnp.array([n, n - 5], jnp.int32)
    got = flash_prefill(q, k, v, lens, interpret=True, block_len=BLOCK)
    want = dense_block_attention(q, k, v, lens, BLOCK)
    for b in range(2):
        np.testing.assert_allclose(got[b, :lens[b]], want[b, :lens[b]],
                                   atol=1e-5)
    # and it is not the causal kernel: a block's first row sees its last
    plain = flash_prefill(q, k, v, lens, interpret=True)
    assert float(jnp.abs(plain[0, :n] - got[0, :n]).max()) > 1e-2


def test_flash_prefill_refuses_a_block_that_splits_a_tile():
    x = jnp.zeros((1, 32, 2, 16))
    with pytest.raises(ValueError, match="block_len"):
        flash_prefill(x, x, x, jnp.array([32]), interpret=True, block_len=5)


@pytest.mark.parametrize("context", [0, 8, 20])
@pytest.mark.parametrize("valid", [1, 2, 3, 4])
def test_gqa_attention_block_mask(context, valid):
    """A block of 4 queries at `context` (a block boundary) over a cache that
    holds context + `valid` written positions."""
    T = 32
    ks = jax.random.split(jax.random.key(context + valid), 3)
    q = jax.random.normal(ks[0], (2, BLOCK, 4, 16))
    kc = jax.random.normal(ks[1], (2, T, 2, 16))
    vc = jax.random.normal(ks[2], (2, T, 2, 16))
    pos = context + jnp.arange(BLOCK)[None].repeat(2, 0)
    length = jnp.full((2,), context + valid, jnp.int32)
    got = gqa_attention(q, kc, vc, pos, length, block_len=BLOCK)
    full_q = jnp.zeros((2, T, 4, 16)).at[:, context:context + BLOCK].set(q)
    want = dense_block_attention(full_q, kc, vc, length, BLOCK)[
        :, context:context + BLOCK]
    np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# the model function against the reference's full forward


@pytest.mark.parametrize("n", [12, 13, 14, 15])
@pytest.mark.parametrize("route", ["cache", "flash"])
def test_forward_matches_reference(params, n, route):
    toks = jnp.asarray([ids_of(n)])
    cache = llama.init_cache(CFG, 1, 32, jnp.float32)
    if route == "cache":
        got, _ = llama.forward(params, CFG, toks, cache)
        got = got[0]
    else:
        padded = jnp.pad(toks, ((0, 0), (0, 32 - n)))
        h, _ = llama.forward_hidden(params, CFG, padded, cache,
                                    jnp.array([n]), prefill_flash=True)
        got = llama.logits_from_hidden(params, CFG, h)[0, :n]
    want = ref.reference_logits(params, MODEL, np.asarray(toks[0]))
    np.testing.assert_allclose(got, want, atol=ATOL)
    causal = ref.reference_logits(params, MODEL, np.asarray(toks[0]),
                                  causal=True)
    assert float(jnp.abs(causal - want).max()) > 100 * ATOL


def test_blocks_through_the_cache_match_reference(params):
    """Prefill three whole blocks, then two blocks through the cache — the
    first forward of each with masked positions, not committed; the second
    the finished block, committed — against the reference's full forward over
    [context || the block as it stood]."""
    context = ids_of(12)
    cache = llama.init_cache(CFG, 1, 32, jnp.float32)
    _, cache = llama.forward(params, CFG, jnp.asarray([context]), cache)
    for b in range(2):
        final = ids_of(BLOCK, key=7 + b)
        stood = [final[0], MASK, final[2], MASK]
        for block, commit in ((stood, False), (final, True)):
            got, after = llama.forward(params, CFG, jnp.asarray([block]),
                                       cache)
            want = ref.reference_logits(params, MODEL,
                                        np.asarray(context + block))[-BLOCK:]
            np.testing.assert_allclose(got[0], want, atol=ATOL)
            if commit:
                cache = after
            else:  # K/V written in place, the length left where it was
                cache = after._replace(lengths=cache.lengths)
        context = context + final
    assert int(cache.lengths[0]) == 20


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_a_block_takes_the_decode_kernel_where_the_cache_has_a_geometry(
        monkeypatch, quantized):
    """The tiny preset with a head that is a lane tile, over 128 positions:
    `attention_paths` reports the kernel with the block as its q tile, a
    block's forwards over the cache take it (the prompt's 12 positions, no
    block, do not), and the logits are the XLA route's."""
    import dataclasses

    from symmetry_tpu.ops import decode_attention as da

    cfg = dataclasses.replace(CFG, head_dim=128)
    params = llama.init_params(cfg, jax.random.key(5), jnp.float32)
    paths = llama.attention_paths(cfg, 128, batch=2,
                                  kv_bytes=1 if quantized else 4)
    assert (paths["decode"], paths["decode_queries"]) == (
        "pallas-interpret", BLOCK)
    assert llama.attention_paths(cfg, 64 + BLOCK, batch=2, kv_bytes=4)[
        "decode"] == "xla"   # an admission's scratch: no multiple of 128
    taken = []
    kernel = da.decode_attention
    monkeypatch.setattr(
        da, "decode_attention",
        lambda q, *a, **kw: taken.append(q.shape) or kernel(q, *a, **kw))

    def run():
        cache = llama.init_cache(cfg, 2, 128, jnp.float32,
                                 quantized=quantized)
        _, cache = llama.forward(
            params, cfg, jnp.asarray([ids_of(12), ids_of(12, key=1)]), cache)
        out = []
        for b in range(3):
            block = jnp.asarray([ids_of(BLOCK, key=2 + b),
                                 [MASK, 7, MASK, MASK]])
            logits, cache = llama.forward(params, cfg, block, cache)
            out.append(logits)
        return jnp.stack(out)

    got = run()
    # (the layer scan traces its body once a forward)
    assert taken == [(2, BLOCK, cfg.num_heads, 128)] * 3
    monkeypatch.setattr(da, "geometry", lambda *a: None)
    want = run()
    assert len(taken) == 3    # the XLA route this time
    np.testing.assert_allclose(got, want, atol=2e-4 if quantized else ATOL)


# ---------------------------------------------------------------------------
# candidates, confidence and the choice of positions


def test_transfer_schedule():
    assert sampling.transfer_schedule(4, 2) == (2, 2)
    assert sampling.transfer_schedule(4, 3) == (2, 1, 1)
    assert sampling.transfer_schedule(4, 4) == (1, 1, 1, 1)
    assert list(sampling.transfer_schedule(4, 3)) == ref.transfer_schedule(4, 3)
    with pytest.raises(ValueError):
        sampling.transfer_schedule(4, 5)


def test_candidates_and_confidence_greedy():
    logits = jax.random.normal(jax.random.key(5), (3, BLOCK, 512)) * 3
    zeros, ones = jnp.zeros((3,)), jnp.ones((3,))
    cand, conf = sampling.diffusion_candidates(
        logits, jax.random.split(jax.random.key(0), 3), zeros, ones,
        jnp.zeros((3,), jnp.int32))
    np.testing.assert_array_equal(cand, logits.argmax(-1))
    np.testing.assert_allclose(conf, jax.nn.softmax(logits, -1).max(-1),
                               rtol=1e-5)


def test_candidates_sampled_confidence_is_the_models_probability():
    logits = jax.random.normal(jax.random.key(6), (2, BLOCK, 512)) * 3
    temp = jnp.array([0.7, 1.3])
    cand, conf = sampling.diffusion_candidates(
        logits, jax.random.split(jax.random.key(1), 2), temp,
        jnp.array([0.9, 1.0]), jnp.array([8, 0], jnp.int32))
    probs = jax.nn.softmax(logits / temp[:, None, None], -1)
    want = jnp.take_along_axis(probs, cand[..., None], -1)[..., 0]
    np.testing.assert_allclose(conf, want, rtol=1e-5)
    # a top-k of 8 never leaves the eight best
    top8 = jnp.argsort(-logits[0], -1)[:, :8]
    assert all(int(cand[0, j]) in top8[j].tolist() for j in range(BLOCK))


UNMASK_CASES = [
    # confidence, known, n, threshold, final
    ([0.5, 0.5, 0.9, 0.1], [0, 0, 0, 1], 2, None, False),   # a tie: lower
    ([0.2, 0.95, 0.97, 0.99], [0, 0, 0, 0], 2, None, False),
    ([0.2, 0.95, 0.97, 0.99], [0, 0, 0, 0], 2, 0.9, False),  # dynamic: 3 > 2
    ([0.2, 0.95, 0.3, 0.1], [0, 0, 0, 0], 2, 0.9, False),    # falls back
    ([0.2, 0.95, 0.3, 0.1], [0, 0, 0, 0], 1, 0.9, False),    # exactly n
    ([0.3, 0.3, 0.3, 0.3], [0, 0, 0, 0], 1, None, False),    # all tied
    ([0.3, 0.3, 0.3, 0.3], [1, 0, 0, 0], 2, 0.5, False),
    ([0.1, 0.2, 0.3, 0.4], [1, 1, 0, 0], 1, None, True),     # the last forward
    ([0.1, 0.2, 0.3, 0.4], [1, 1, 1, 0], 2, None, False),    # fewer left
    ([0.99, 0.2, 0.3, 0.4], [1, 0, 0, 0], 2, 0.5, False),    # known is high
    ([0.1, 0.2, 0.3, 0.4], [1, 1, 1, 1], 2, 0.0, False),     # nothing left
]


@pytest.mark.parametrize("conf,known,n,threshold,final", UNMASK_CASES)
def test_unmask_matches_reference(conf, known, n, threshold, final):
    known = np.asarray(known, bool)
    got = sampling.diffusion_unmask(
        jnp.asarray([conf], jnp.float32), jnp.asarray([known]),
        jnp.int32(n), jnp.bool_(final), threshold)[0]
    want = ref.unmask(np.asarray(conf, np.float32), known, n, threshold,
                      final)
    np.testing.assert_array_equal(got, want)
    assert not (np.asarray(got) & known).any()


# ---------------------------------------------------------------------------
# the engine and the scheduler against the reference's generation loop


def engine_of(params, *, steps=2, threshold=None, **kw):
    args = dict(max_slots=4, max_seq_len=128, prefill_buckets=(32, 64),
                cache_dtype=jnp.float32, decode_block=8, prefill_chunk=None,
                diffusion_steps=steps, diffusion_threshold=threshold)
    return InferenceEngine(
        CFG, params, get_tokenizer(None, vocab_size=CFG.vocab_size),
        **{**args, **kw})


@pytest.fixture(scope="module")
def engines(params):
    """One engine a rule (a compile each), shared by the cases below."""
    return {"static": engine_of(params, steps=2),
            "dynamic": engine_of(params, steps=2, threshold=0.01)}


def serve(engine, requests, *, stop_ids=(), gaps=()):
    """Run `requests` ([(prompt ids, max_new)]) through a Scheduler over
    `engine`; returns ({index: (token count, finish, text)}, scheduler
    stats). `gaps[i]` seconds pass before request i is submitted."""
    engine.tokenizer.eos_ids = frozenset(stop_ids) or frozenset(
        {engine.tokenizer.EOS})
    got = {i: [] for i in range(len(requests))}
    done = {i: threading.Event() for i in range(len(requests))}

    def sink(batch):
        for req, ev in batch:
            got[req.id].append(ev)
            if ev.done:
                done[req.id].set()

    sched = Scheduler(engine, emit_batch=sink)
    sched.start()
    try:
        for i, (ids, max_new) in enumerate(requests):
            if i < len(gaps) and gaps[i]:
                done[i - 1].wait(gaps[i])
            sched.submit(GenRequest(
                prompt_ids=list(ids), sampling=SamplingParams(),
                max_new_tokens=max_new, emit=lambda ev: None,
                cancelled=lambda: False, id=i))
        for i, ev in done.items():
            assert ev.wait(120), f"request {i} hung"
        stats = sched.stats()
    finally:
        sched.stop(timeout=10)
    out = {}
    for i, evs in got.items():
        last = evs[-1]
        assert last.done and not last.error, last
        out[i] = (last.tokens_emitted, last.finish_reason,
                  "".join(ev.text for ev in evs))
    return out, stats


def text_of(engine, tokens):
    dec = engine.tokenizer.stream_decoder()
    return dec.push_many([int(t) for t in tokens]) + dec.flush()


@pytest.mark.parametrize("rule", ["static", "dynamic"])
def test_served_tokens_match_reference(params, engines, rule):
    """Prompts with every left-over r = 0..3 and budgets that cut the final
    block at each offset, admitted together and between dispatches: every
    stream is the reference's generation loop token for token, exactly the
    tokens asked for."""
    engine = engines[rule]
    threshold = 0.01 if rule == "dynamic" else None
    requests = [(ids_of(12 + r, key=r), 17 + r) for r in range(4)]
    requests += [(ids_of(21, key=9), 3), (ids_of(18, key=8), 1),
                 (ids_of(3, key=5), 6)]          # P < block: no whole block
    got, stats = serve(engine, requests, gaps=[0, 0, 0, 0, 0.3, 0.3, 0.2])
    pushed = 0
    for i, (ids, max_new) in enumerate(requests):
        want, _ = ref.generate(params, MODEL, ids, max_new, steps=2,
                               threshold=threshold)
        assert len(want) == max_new
        n, finish, text = got[i]
        assert (n, finish) == (max_new, "length"), (i, got[i])
        assert text == text_of(engine, want), i
        pushed += n
    assert stats["tokens"] == pushed
    bd = stats["diffusion"]
    assert bd["rule"] == ("low_confidence_dynamic" if threshold
                          else "low_confidence_static")
    assert bd["tokens_committed"] == pushed
    assert sum(bd["opening_block_tokens"].values()) == len(requests)
    assert bd["opening_block_tokens"] == {"1": 2, "2": 2, "3": 2, "4": 1}
    assert bd["forwards"] == 3 * bd["commit_forwards"] > 0
    assert bd["tokens_dropped"] > 0 and bd["live_slot_forwards"] > 0


def test_stop_token_inside_a_block_ends_the_stream_there(params, engines):
    ids = ids_of(14, key=2)
    want, _ = ref.generate(params, MODEL, ids, 24, steps=2)
    # a stop token the stream first meets mid-block, past the opening block
    at = next(i for i in range(3, 20)
              if (i - 2) % BLOCK in (1, 2) and want[i] not in want[:i])
    stop = want[at]
    cut, _ = ref.generate(params, MODEL, ids, 24, steps=2, stop_ids=[stop])
    assert cut == want[:at]
    got, stats = serve(engines["static"], [(ids, 24)], stop_ids=[stop])
    n, finish, text = got[0]
    assert (n, finish) == (at, "stop")
    assert text == text_of(engines["static"], want[:at])
    assert stats["tokens"] == at


def test_stop_token_in_the_opening_block(params, engines):
    # a prompt with one left-over token whose opening block's second new
    # token is not its first: the stream stops after one token
    for key in range(1, 9):
        ids = ids_of(13, key=key)
        want, _ = ref.generate(params, MODEL, ids, 8, steps=2)
        if want[1] != want[0]:
            break
    else:
        pytest.fail("no prompt with two distinct first tokens")
    got, _ = serve(engines["static"], [(ids, 8)], stop_ids=[want[1]])
    assert got[0][:2] == (1, "stop")


def test_one_position_a_step_is_the_default(params):
    engine = engine_of(params, steps=None)
    assert engine.diffusion_report()["steps"] == BLOCK
    assert engine.diffusion_report()["transfer_schedule"] == [1, 1, 1, 1]
    ids = ids_of(13, key=4)
    first = np.asarray(engine.prefill_and_insert_many_dispatch(
        [(0, ids, SamplingParams())]))[0, len(ids) % BLOCK:]
    got = list(first) + list(engine.decode_steps()[:, 0])
    want, _ = ref.generate(params, MODEL, ids, len(got))
    assert [int(t) for t in got] == want
    assert engine.slot_length(0) == 16 + 8


def test_startup_reports(params, engines):
    engine = engines["static"]
    paths = engine.attention_paths()
    # a head of 16 is no lane tile: the block's forwards keep the XLA route
    # by shape, over the slots' cache and the admission's scratch alike
    assert paths["decode"] == "xla" and "head of 16" in paths["decode_why"]
    assert paths["opening_block"] == "xla" and "opening_block_why" in paths
    report = engine.diffusion_report()
    assert report["forwards_per_dispatch"] == 6
    assert report["programs"] == {"prefill": "bd_prefill",
                                  "decode": "bd_decode_block"}
    # a decode forward routes slots x block tokens
    assert engine.moe_report()["route"]["decode"] == \
        engine.moe_report()["route"]["prefill"]["32"]


@pytest.mark.parametrize("band,forms", [
    (None, ["dense-mixture", "dense-mixture", "dense-mixture"]),
    ((16, 64), ["routed", "routed", "dense-mixture"])])
def test_startup_reports_the_opening_blocks_route(engines, monkeypatch, band,
                                                  forms):
    """`startup.moe.route.opening`: the form of the opening block's forwards
    (batch x block tokens) for every admission batch warm-up compiles — 1, 2
    and 4 rows of 4 positions here; times `diffusion.admit_forwards` it says
    how many forwards took which form. The tiny shape (8 experts top 2) has
    no band of its own: the mixture everywhere, until it is given one."""
    from symmetry_tpu.models import moe

    engine = engines["static"]
    monkeypatch.setattr(engine, "_moe_report", None)    # built once
    if band is not None:
        monkeypatch.setitem(moe.ROUTED_FROM, (8, 2), band)
    route = engine.moe_report()["route"]
    assert route["opening"] == dict(zip(("4", "8", "16"), forms))
    assert set(route) == {"decode", "prefill", "opening"}
    # the decode forward's 16 tokens and the opening block's take one form
    assert route["decode"] == route["opening"]["16"]


# ---------------------------------------------------------------------------
# what is refused


REFUSED = [
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(prefix_cache_bytes=1 << 20), "prefix_cache_mb"),
    (dict(role="prefill"), "tpu.role"),
    (dict(decode_block=6), "multiple of the block length"),
    (dict(steps=5), "diffusion_steps"),
    (dict(threshold=1.5), "diffusion_threshold"),
]


@pytest.mark.parametrize("kw,match", REFUSED)
def test_engine_refuses(params, kw, match):
    with pytest.raises(EngineError, match=match):
        engine_of(params, **kw)


def test_engine_refuses_speculation(params):
    from symmetry_tpu.engine.spec import SpecConfig

    with pytest.raises(EngineError, match="speculative"):
        engine_of(params, speculative=SpecConfig.from_knob(True))


def test_settings_refused_for_a_model_without_a_block():
    cfg = llama.preset("tiny-moe")
    p = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    with pytest.raises(EngineError, match="no block length"):
        InferenceEngine(cfg, p, get_tokenizer(None, vocab_size=512),
                        max_slots=2, max_seq_len=64, prefill_buckets=(32,),
                        diffusion_steps=2)


def test_config_round_trip():
    assert llama.config_from_hf(MODEL) == CFG
    full = llama.preset("sdar-30b-a3b-chat")
    assert llama.config_from_hf(llama.hf_config_diffusion(full)) == full
    published = {k: v for k, v in llama.hf_config_diffusion(full).items()
                 if k not in ("block_length", "mask_token_id")}
    assert llama.config_from_hf(published).diffusion == full.diffusion


# ---------------------------------------------------------------------------
# tools/bd_parity.py's verdict (no JAX in it)

# medians (and worst rows) the chip read at the cell's shapes, four seeds of
# weights (my chip runs, PR 47): prefill, its bfloat16-softmax control, block,
# its two wrong-mask controls, logits, and the three controls at the logits
CHIP_READINGS = {
    47: (0.004499, 0.005302, 0.005886, 0.007685, 0.008587, 0.0687, 0.1426,
         0.04272, 0.10629, 0.296, 0.5222, 0.1157, 0.1979),
    48: (0.004456, 0.005242, 0.005810, 0.007655, 0.008401, 0.0712, 0.1486,
         0.04219, 0.09319, 0.333, 0.4767, 0.1063, 0.2087),
    49: (0.004558, 0.005256, 0.005940, 0.007662, 0.008314, 0.0617, 0.1361,
         0.03862, 0.08279, 0.265, 0.4730, 0.0947, 0.1962),
    50: (0.004474, 0.005028, 0.005849, 0.007461, 0.008201, 0.0765, 0.1445,
         0.04446, 0.10580, 0.302, 0.5093, 0.1087, 0.2237)}


def _parity_line(seed, **over):
    (pre, pre_max, bf16, blk, blk_max, causal, skipped, logit, logit_max,
     excluded, causal_l, skipped_l, int4_l) = CHIP_READINGS[seed]
    r = {"seed": seed,
         "attn0_prefill": {"median": pre, "max": pre_max},
         "attn0_block": {"median": blk, "max": blk_max},
         "logits": {"median": logit, "max": logit_max, "excluded": excluded},
         "choices": {"forwards": 40, "differ": 0},
         "controls": {name: {"median": m} for name, m in (
             ("softmax_bf16", bf16), ("causal_mask", causal),
             ("skipped_commit", skipped), ("causal_mask_logits", causal_l),
             ("skipped_commit_logits", skipped_l),
             ("kv_int4_logits", int4_l))}}
    for path, value in over.items():
        part, key = path.split("__")
        if part == "controls":
            r["controls"][key]["median"] = value
        else:
            r[part][key] = value
    return r


@pytest.mark.parametrize("seed", sorted(CHIP_READINGS))
def test_the_parity_verdict_passes_each_seed_and_fails_every_control(seed):
    from tools.bd_parity import CONTROLS, LIMITS, verdict

    v = verdict(_parity_line(seed), LIMITS)
    assert v["ok"]
    assert set(v["controls_ok"]) == set(CONTROLS)
    assert not any(v["controls_ok"].values())


@pytest.mark.parametrize("fault", [
    {"attn0_prefill__median": 0.0053}, {"attn0_prefill__max": 0.03},
    {"attn0_block__median": 0.02}, {"attn0_block__max": 0.05},
    {"logits__median": 0.07}, {"logits__max": 0.25},
    {"logits__excluded": 0.6}, {"choices__differ": 1}])
def test_the_parity_verdict_fails_by_each_limit(fault):
    from tools.bd_parity import LIMITS, verdict

    assert not verdict(_parity_line(48, **fault), LIMITS)["ok"]


def test_the_parity_verdict_across_seeds_sets_the_readings_side_by_side():
    from tools.bd_parity import LIMITS, across

    lines = [_parity_line(seed) for seed in sorted(CHIP_READINGS)]
    got = across(lines, LIMITS)
    assert got["ok"] and not any(got["controls_ok"].values())
    # every limit lies between the largest stated reading and the smallest
    # control held to it
    for limit, row in got["table"].items():
        assert row["largest"] < row["limit"] == LIMITS[limit]
        for name, smallest in row.get("controls_smallest", {}).items():
            assert row["limit"] < smallest, (limit, name)
    row = got["table"]["logit_median"]
    assert row["largest"] == 0.04446
    assert row["controls_smallest"] == {"causal_mask_logits": 0.4730,
                                        "skipped_commit_logits": 0.0947,
                                        "kv_int4_logits": 0.1962}
    # a control that slips under its limit on ONE seed is reported
    lines[2] = _parity_line(49, controls__skipped_commit_logits=0.06)
    got = across(lines, LIMITS)
    assert got["controls_ok"]["skipped_commit_logits"]
    assert not got["controls_ok"]["kv_int4_logits"]
