"""The nemotron_h decoder (blocks of ONE sub-layer each through the hybrid
trunk; Mamba-2 with groups of B and C; GQA without rotary; ungated relu2
experts of which a chip holds a SHARE) against the plain reference
`benchmarks/reference/nemotron_h_decoder.py`, on seeded random weights at
`tiny-nh` — whose pattern "MEM*EMEM*EME" has every pairing (a mamba block
before experts, before attention; attention before experts) and whose chip
holds the SECOND four of eight experts.

What is compared is LOGITS. Tolerances are tests/test_hybrid.py's:

- float32: the two sides do the same mathematics in another order (29-style
  trunk layers against a loop over the published blocks, a chunked dual form
  against a scan over time, grouped experts against a loop). Kept tokens
  agree to 2e-5 on logits of order 0.6; a token within 1e-4 of a router tie
  may route otherwise on the two sides and is left out — at most a tenth.
- bfloat16 / int8 weights, int8 KV: the reference is fed the SAME weights
  dequantised; the median error is held to 5% of the logit scale and the
  90th percentile to 25%.
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
import reference.nemotron_h_decoder as ref  # noqa: E402

from symmetry_tpu.engine.engine import (  # noqa: E402
    EngineError, InferenceEngine, SamplingParams)
from symmetry_tpu.engine.tokenizer import get_tokenizer  # noqa: E402
from symmetry_tpu.models import hybrid, llama, mamba2, moe  # noqa: E402
from symmetry_tpu.ops.quant import (  # noqa: E402
    QuantizedTensor, dequantize)

CFG = llama.preset("tiny-nh")
REAL = llama.preset("nemotron-3-nano-30b-a3b")
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
EXACT = dict(eps=1e-4, atol=2e-5, max_excluded=0.10)
NOISY = dict(median=0.05, p90=0.25)


def as_float32(params):
    return jax.tree.map(
        lambda a: (dequantize(a) if isinstance(a, QuantizedTensor)
                   else a.astype(jnp.float32)),
        params, is_leaf=lambda a: isinstance(a, QuantizedTensor))


def make_params(weights: str, cfg=CFG, key=62):
    # (key 62: at 61 bfloat16 flips the router on a tenth of the 80 rows and
    # their logits move by the scale itself — the 90th percentile reads 25.4%
    # of it against the 25% below; at 62-64 it reads 1.3-2.2%)
    dtype = jnp.bfloat16 if weights == "bfloat16" else jnp.float32
    params = llama.init_params(cfg, jax.random.key(key), dtype)
    if weights == "int8":
        params = llama.quantize_params(params)
    return params, dtype


def fwd(params, cfg):
    def run(tokens, cache, seq_lens=None, prefill_flash=False):
        h, cache = llama.forward_hidden(params, cfg, tokens, cache, seq_lens,
                                        prefill_flash=prefill_flash)
        return llama.logits_from_hidden(params, cfg, h), cache
    return jax.jit(run, static_argnames=("prefill_flash",))


def reference(params, cfg, tokens, **kw):
    model = hybrid.hf_config(cfg)
    floats = as_float32(params)
    out = [ref.reference_logits(floats, model, row, with_margins=True, **kw)
           for row in tokens]
    return (np.stack([np.asarray(w) for w, _ in out]),
            np.stack([np.asarray(m).min(axis=0) for _, m in out]))


def check(got, want, margins, weights):
    err = np.abs(np.asarray(got, np.float32) - want).max(axis=-1)
    scale = np.abs(want).max()
    if weights == "float32":
        kept = margins >= EXACT["eps"]
        assert 1 - kept.mean() <= EXACT["max_excluded"]
        assert err[kept].max() <= EXACT["atol"], err[kept].max()
    else:
        assert np.median(err) <= NOISY["median"] * scale, np.median(err)
        assert np.quantile(err, 0.9) <= NOISY["p90"] * scale


# ---------------------------------------------------------------------------
# blocks of one sub-layer: the pairing


def test_the_published_pattern_pairs_into_29_trunk_layers():
    kinds, ffns = llama.pair_blocks(PUBLISHED)
    assert len(PUBLISHED) == 52 and len(kinds) == len(ffns) == 29
    assert (kinds.count("mamba"), kinds.count("attention")) == (23, 6)
    assert (ffns.count("moe"), ffns.count("none")) == (23, 6)
    # an attention block is always followed by experts; the six layers that
    # end in nothing are the mamba blocks before an attention block
    assert all(f == "moe" for k, f in zip(kinds, ffns) if k == "attention")
    assert [i for i, f in enumerate(ffns) if f == "none"] == [
        i - 1 for i, k in enumerate(kinds) if k == "attention"]
    assert (REAL.layer_types, REAL.ffn_layout) == (kinds, ffns)
    assert REAL.num_layers == REAL.num_blocks == 52
    assert llama.blocks_of(REAL) == PUBLISHED
    assert len(hybrid.runs(REAL)) == 19
    # the stack a layer's FFN lies in is indexed among the layers of ITS kind
    assert [REAL.ffn_index(i) for i in REAL.layers_ending_in("moe")] == list(
        range(23))


@pytest.mark.parametrize("pattern", ["EM", "MEE", "M-E", "ME-", "*EXM"])
def test_a_pattern_that_cannot_be_paired_is_refused(pattern):
    with pytest.raises(ValueError, match="cannot be paired"):
        llama.pair_blocks(pattern)


def test_the_tiny_pattern_has_every_pairing_and_its_runs_break_on_the_ffn():
    assert llama.blocks_of(CFG) == "MEM*EMEM*EME"
    pairs = set(zip(CFG.layer_types, CFG.ffn_layout))
    assert pairs == {("mamba", "moe"), ("mamba", "none"),
                     ("attention", "moe")}
    assert hybrid.runs(CFG) == [
        ("mamba", 0, 1), ("mamba", 1, 1), ("attention", 2, 1),
        ("mamba", 3, 1), ("mamba", 4, 1), ("attention", 5, 1),
        ("mamba", 6, 1)]
    assert [CFG.ffn_index(i) for i in range(7)] == [0, 0, 1, 2, 1, 3, 4]
    # the older families answer as they did
    lfm2 = llama.preset("lfm2-8b-a1b")
    assert [lfm2.ffn_kind(i) for i in (0, 1, 2)] == ["dense", "dense", "moe"]
    assert lfm2.ffn_index(1) == 1 and lfm2.ffn_index(2) == 0
    assert lfm2.num_blocks == lfm2.num_layers == 24


def test_the_layout_and_the_share_are_checked_where_the_config_is_made():
    with pytest.raises(ValueError, match="ffn_layout"):
        dataclasses.replace(CFG, ffn_layout=("moe",) * 6)
    with pytest.raises(ValueError, match="ffn_layout"):
        dataclasses.replace(CFG, ffn_layout=("moe",) * 6 + ("swiglu",))
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, num_layers=9)
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(CFG, experts_held=(6, 4))
    with pytest.raises(ValueError, match="mamba_n_groups"):
        dataclasses.replace(CFG, mamba_n_groups=3)
    # the trunk's own count is as good as the blocks'
    assert dataclasses.replace(CFG, num_layers=7).num_blocks == 12


def test_the_config_round_trips_through_its_published_keys():
    for cfg in (CFG, REAL, dataclasses.replace(CFG, experts_held=None)):
        keys = hybrid.hf_config(cfg)
        assert keys["model_type"] == "nemotron_h"
        assert keys["num_hidden_layers"] == len(
            keys["hybrid_override_pattern"])
        assert llama.config_from_hf(keys) == cfg
    keys = hybrid.hf_config(REAL)
    assert (keys["n_routed_experts"], keys["experts_routed_over"],
            keys["experts_held"]) == (32, 128, [0, 32])
    assert "experts_held" not in hybrid.hf_config(
        dataclasses.replace(CFG, experts_held=None))
    for key, bad in (("mlp_hidden_act", "silu"), ("n_group", 2),
                     ("attention_bias", True), ("use_conv_bias", False),
                     ("n_shared_experts", 2), ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match="not implemented"):
            llama.config_from_hf({**keys, key: bad})
    with pytest.raises(ValueError, match="blocks"):
        llama.config_from_hf({**keys, "num_hidden_layers": 51})
    with pytest.raises(ValueError, match="no tensor-name map"):
        hybrid.convert_hf_state_dict({}, CFG)


def test_the_real_presets_shapes_are_the_published_ones():
    z = mamba2.sizes(REAL)
    assert (z["inner"], z["conv"], z["proj"], z["G"]) == (
        4096, 6144, 10304, 8)
    assert (REAL.q_dim, REAL.kv_dim, REAL.num_heads // REAL.num_kv_heads
            ) == (4096, 256, 16)
    shapes = jax.eval_shape(lambda: llama.init_params(
        REAL, jax.random.key(0), jnp.bfloat16, quantize=True))
    ffn = shapes["layers"]["ffn"]
    assert set(ffn) == {"norm", "router", "expert_bias", "wu", "wd", "su",
                        "sd"}
    # the published 1,856 stored in whole lane tiles (`expert_columns`)
    assert ffn["wu"].q.shape == (23, 32, 2688, 1920)
    assert ffn["wd"].q.shape == (23, 32, 1920, 2688)
    assert ffn["router"].shape == (23, 2688, 128)
    assert ffn["su"].q.shape == (23, 2688, 3712)
    assert shapes["layers"]["mamba"]["in_proj"].q.shape == (23, 2688, 10304)
    assert shapes["layers"]["attn"]["wk"].q.shape == (6, 2688, 256)
    assert shapes["lm_head"].q.shape == (2688, 131072)
    assert hybrid.state_bytes_per_slot(REAL) == {
        "ssm": 23 * 64 * 64 * 128 * 4, "conv": 23 * 3 * 6144 * 2}
    # granite's sizes did not move
    g = mamba2.sizes(llama.preset("granite-4.0-h-small"))
    assert (g["G"], g["conv"], g["proj"]) == (1, 8192 + 256, 16384 + 256 + 128)


# ---------------------------------------------------------------------------
# the model against the reference


@pytest.mark.parametrize("weights", ["float32", "bfloat16", "int8"])
def test_prefill_logits_match_the_52_block_style_loop(weights):
    """The trunk's 7 (mixer, FFN) layers against the reference's loop over
    the 12 published blocks, one sub-layer each."""
    params, dtype = make_params(weights)
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0,
                                CFG.vocab_size)
    cache = llama.init_cache(CFG, 2, 64, dtype, quantized=weights == "int8")
    got, cache = fwd(params, CFG)(tokens, cache, prefill_flash=True)
    want, margins = reference(params, CFG, tokens)
    check(got, want, margins, weights)
    assert cache.lengths.tolist() == [40, 40]
    assert cache.k.shape[0] == 2 and cache.ssm.shape == (5, 2, 8, 16, 16)
    assert cache.conv.shape == (5, 3, 2, 8 * 16 + 2 * 2 * 16)


@pytest.mark.parametrize("weights", ["float32", "bfloat16", "int8"])
def test_prefill_then_decode_through_the_cache_match_the_reference(weights):
    """Prefill of 23 tokens from empty (two chunks of the grouped dual
    form, the second padded), then 17 single-token steps through the K/V
    cache, the grouped recurrence kernel (interpreted) and the conv tail,
    teacher-forced: against the reference's full forward over all 40."""
    params, dtype = make_params(weights)
    tokens = jax.random.randint(jax.random.key(2), (2, 40), 0,
                                CFG.vocab_size)
    run = fwd(params, CFG)
    cache = llama.init_cache(CFG, 2, 64, dtype, quantized=weights == "int8")
    first, cache = run(tokens[:, :23], cache, prefill_flash=True)
    got = [first]
    for t in range(23, 40):
        logits, cache = run(tokens[:, t:t + 1], cache)
        got.append(logits)
    want, margins = reference(params, CFG, tokens)
    check(jnp.concatenate(got, axis=1), want, margins, weights)


@pytest.mark.parametrize("control", ["one-group", "norm-all", "renormalised",
                                     "rotary"])
def test_each_falsification_of_the_reference_is_far_outside_the_tolerance(
        control):
    """The comparison can tell: one line of the reference made wrong on
    purpose moves the logits by thousands of times the float32 tolerance."""
    params, _ = make_params("float32")
    tokens = jax.random.randint(jax.random.key(3), (1, 40), 0,
                                CFG.vocab_size)
    want, _ = reference(params, CFG, tokens)
    wrong, _ = reference(params, CFG, tokens, controls=(control,))
    assert np.abs(wrong - want).max() > 1000 * EXACT["atol"]


def test_the_reference_imports_nothing_from_the_program():
    source = open(ref.__file__).read()
    assert "symmetry_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "Departures from the published code" in source


def test_an_experts_width_is_stored_in_whole_lane_tiles_of_zeros():
    """`intermediate_size` 24 lies in leaves of 128 columns (rows), zero past
    the 24th — and a matrix of the published width gives the same logits."""
    for weights in ("float32", "int8"):
        params, _ = make_params(weights)
        ffn = as_float32(params)["layers"]["ffn"]
        assert ffn["wu"].shape == (5, 4, 64, 128)
        assert ffn["wd"].shape == (5, 4, 128, 64)
        assert float(jnp.abs(ffn["wu"][..., 24:]).max()) == 0.0
        assert float(jnp.abs(ffn["wd"][..., 24:, :]).max()) == 0.0
        assert float(jnp.abs(ffn["wu"][..., :24]).min(axis=-2).max()) > 0.0
    params, _ = make_params("float32")
    tokens = jax.random.randint(jax.random.key(9), (1, 24), 0,
                                CFG.vocab_size)
    floats = as_float32(params)
    cut = jax.tree.map(lambda a: a, floats)
    cut["layers"]["ffn"] = dict(floats["layers"]["ffn"],
                                wu=floats["layers"]["ffn"]["wu"][..., :24],
                                wd=floats["layers"]["ffn"]["wd"][..., :24, :])
    model = hybrid.hf_config(CFG)
    np.testing.assert_allclose(
        ref.reference_logits(cut, model, tokens[0]),
        ref.reference_logits(floats, model, tokens[0]), atol=1e-6)
    assert hybrid.expert_columns(1856) == 1920
    assert hybrid.expert_columns(768) == 768


# ---------------------------------------------------------------------------
# groups


@pytest.mark.parametrize("length,chunk", [(16, 16), (40, 16), (37, 8)])
def test_the_grouped_chunked_form_is_the_step_by_step_recurrence(length,
                                                                 chunk):
    cfg = dataclasses.replace(CFG, mamba_chunk_size=chunk)
    params, _ = make_params("float32", cfg)
    lp = jax.tree.map(lambda a: a[1], params["layers"]["mamba"])
    z = mamba2.sizes(cfg)
    u = jax.random.normal(jax.random.key(4), (2, length, cfg.hidden_size))
    ssm = jnp.zeros((2, z["H"], z["P"], z["N"]), jnp.float32)
    conv = jnp.zeros((z["K"] - 1, 2, z["conv"]), jnp.float32)
    lens = jnp.asarray([length, length - 5], jnp.int32)
    with jax.default_matmul_precision("highest"):
        out, s_end, tail = mamba2.chunked(u, lp, ssm, conv, lens, cfg)
        rows, s, c = [], ssm, conv
        at_len = {}
        for t in range(length):
            o, s, c = mamba2.step(u[:, t], lp, s, c, cfg)
            rows.append(o)
            for b in range(2):
                if t + 1 == int(lens[b]):
                    at_len[b] = (s[b], c[:, b])
    steps = jnp.stack(rows, axis=1)
    for b in range(2):
        n = int(lens[b])
        np.testing.assert_allclose(out[b, :n], steps[b, :n], atol=2e-5)
        np.testing.assert_allclose(s_end[b], at_len[b][0], atol=2e-5)
        np.testing.assert_allclose(tail[:, b], at_len[b][1], atol=1e-6)


def test_a_head_reads_its_own_groups_rows_and_the_norm_its_own_channels():
    """Changing group 1's B and C moves the heads of group 1 alone; the
    gated norm of one group's channels does not see the other's."""
    B, H, P, N, G = 2, 8, 4, 16, 2
    ks = jax.random.split(jax.random.key(5), 6)
    ssm = jax.random.normal(ks[0], (B, H, P, N))
    a = jax.random.uniform(ks[1], (B, H))
    dx, skip = (jax.random.normal(k, (B, H, P)) for k in ks[2:4])
    b, c = (jax.random.normal(k, (B, G, N)) for k in ks[4:6])
    y0, s0 = mamba2.recurrence(ssm, a, dx, b, c, skip)
    y1, s1 = mamba2.recurrence(ssm, a, dx, b.at[:, 1].add(1.0),
                               c.at[:, 1].add(1.0), skip)
    assert np.array_equal(y0[:, :4], y1[:, :4])
    assert np.array_equal(s0[:, :4], s1[:, :4])
    assert not np.allclose(y0[:, 4:], y1[:, 4:])
    lp = {"gate_norm": jnp.ones((H * P,)), "out_proj": jnp.eye(H * P)}
    y = jax.random.normal(ks[0], (B, H * P))
    gate = jnp.ones((B, H * P))
    g0 = mamba2._gate_out(y, gate, lp, 1e-5, jnp.float32, G)
    g1 = mamba2._gate_out(y.at[:, H * P // 2:].multiply(3.0), gate, lp, 1e-5,
                          jnp.float32, G)
    np.testing.assert_allclose(g0[:, :H * P // 2], g1[:, :H * P // 2],
                               atol=1e-6)
    whole = mamba2._gate_out(y, gate, lp, 1e-5, jnp.float32, 1)
    assert not np.allclose(whole, g0, atol=1e-3)


# ---------------------------------------------------------------------------
# a chip's share of the experts, ungated


def layer_and_tokens(tokens: int, held, key=7):
    """One expert layer's float32 leaves, UNCUT (all 8 experts), the slice
    of them a share `held` holds, and `tokens` inputs."""
    whole = dataclasses.replace(CFG, experts_held=None)
    params, _ = make_params("float32", whole, key)
    lp = jax.tree.map(lambda a: a[2], params["layers"]["ffn"])
    x = jax.random.normal(jax.random.key(key + 1), (tokens, CFG.hidden_size))
    if held is None:
        return whole, lp, x
    first, count = held
    mine = dict(lp, wu=lp["wu"][first:first + count],
                wd=lp["wd"][first:first + count])
    return dataclasses.replace(CFG, experts_held=held), mine, x


def form(name, cfg, lp, x):
    routing = moe.routing_of(cfg, lp)
    fn = moe._routed_ffn if name == "routed" else moe._dense_mixture
    args = (x, jnp.ones((x.shape[0],), bool), lp["router"], None, lp["wu"],
            lp["wd"], cfg.num_experts_per_tok)
    if name == "routed":
        return fn(*args, None, routing)
    return fn(*args, routing)


@pytest.mark.parametrize("name", ["routed", "dense-mixture"])
@pytest.mark.parametrize("shares", [((0, 4), (4, 4)),
                                    ((0, 2), (2, 3), (5, 3)),
                                    ((0, 8),)])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(
        name, shares):
    """The share tied to the model: what every share computes of the routed
    sum, added up, with the shared expert (which every chip computes alike)
    counted ONCE, is the reference's uncut layer — gates as routed over all
    eight, never renormalised over a share."""
    whole, lp, x = layer_and_tokens(24, None)
    model = hybrid.hf_config(whole)
    with jax.default_matmul_precision("highest"):
        want, _, selected = ref.experts_and_shared(x, lp, model)
        shared = ref.relu2(x @ lp["su"]) @ lp["sd"]
        total, counted = shared, 0
        for held in shares:
            cfg, mine, _ = layer_and_tokens(24, held)
            part, pairs = form(name, cfg, mine, x)
            total = total + part
            counted = counted + int(pairs[held[0]:held[0] + held[1]].sum())
            # the pair counter is over ALL the router scores, on every chip,
            # and ends in the held experts a valid pair fell on
            assert pairs.shape == (9,)
            assert int(pairs[:8].sum()) == 24 * CFG.num_experts_per_tok
            assert int(pairs[8]) == int(
                (pairs[held[0]:held[0] + held[1]] > 0).sum())
            # and one share alone is the reference's share
            alone, _, _ = ref.experts_and_shared(
                x, mine, hybrid.hf_config(cfg))
            np.testing.assert_allclose(part + shared, alone, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert counted == 24 * CFG.num_experts_per_tok
    assert selected.shape == (24, 2)


def test_a_token_with_no_held_expert_gets_the_shared_expert_alone():
    cfg, lp, x = layer_and_tokens(64, (4, 4))
    _, experts = moe._route(x, lp["router"], 2, moe.routing_of(cfg, lp))
    none_held = np.asarray(((experts < 4) | (experts >= 8)).all(axis=-1))
    assert none_held.any() and not none_held.all()
    for name in ("routed", "dense-mixture"):
        part, _ = form(name, cfg, lp, x)
        assert np.abs(np.asarray(part)[none_held]).max() == 0.0
        assert np.abs(np.asarray(part)[~none_held]).max() > 0.0
    y, _ = moe.moe_mlp(x[None], lp, cfg)
    with jax.default_matmul_precision("highest"):
        shared = ref.relu2(x @ lp["su"]) @ lp["sd"]
    np.testing.assert_allclose(np.asarray(y[0])[none_held],
                               np.asarray(shared)[none_held], atol=2e-5)


@pytest.mark.parametrize("weights", ["float32", "int8"])
def test_both_ungated_forms_are_one_mathematics(weights):
    cfg, lp, x = layer_and_tokens(40, (4, 4))
    if weights == "int8":
        lp = llama.quantize_params({"layers": lp})["layers"]
        x = x.astype(jnp.bfloat16)
    routed, p0 = form("routed", cfg, lp, x)
    mixed, p1 = form("dense-mixture", cfg, lp, x)
    assert p0.tolist() == p1.tolist() and 1 <= int(p0[8]) <= 4
    tol = 2e-5 if weights == "float32" else 0.05 * float(
        jnp.abs(routed).max())
    np.testing.assert_allclose(routed, mixed, atol=tol)


def test_the_band_is_keyed_by_what_is_held_and_every_older_key_stands():
    assert moe.ROUTED_FROM[(72, 10)] == (0, 256)
    assert moe.ROUTED_FROM[(128, 6)] == (0, 128)
    assert moe.ROUTED_FROM[(64, 6)] == (32, 256)
    # kanana's (128, 6) — all held — is not the share's (32 of 128, top 6)
    assert moe.moe_route(64, 128, 6) == "dense-mixture"
    assert moe.moe_route(128, 128, 6) == "routed"
    assert moe.moe_route(64, 128, 6, 128) == "dense-mixture"
    assert (32, 6, 128) in moe.ROUTED_FROM
    lo, hi = moe.ROUTED_FROM[(32, 6, 128)]
    assert moe.moe_route(max(lo, hi - 1), 128, 6, 32) == (
        "dense-mixture" if lo < hi else "routed")
    assert moe.moe_route(8192, 128, 6, 32) == "routed"


# ---------------------------------------------------------------------------
# the engine


def make_engine(**kw):
    params = llama.init_params(CFG, jax.random.key(61), jnp.bfloat16,
                               quantize=True)
    args = dict(max_slots=4, max_seq_len=96, prefill_buckets=(16, 32, 64),
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
PROMPT_A = list(range(5, 30))
PROMPT_B = list(range(100, 140))


def stream(eng, slot, ids, blocks=3):
    out = [eng.prefill_and_insert(slot, ids, GREEDY)]
    for _ in range(blocks):
        out += [int(t) for t in eng.decode_steps()[:, slot]]
    return out


def test_a_reused_lane_gives_the_first_requests_tokens_again(engine):
    first = stream(engine, 1, PROMPT_A)
    engine.release_slot(1)
    other = stream(engine, 1, PROMPT_B)
    engine.release_slot(1)
    engine.decode_steps()      # parked: the lane's state keeps moving
    again = stream(engine, 1, PROMPT_A)
    assert first == again and first != other
    engine.release_slot(1)


def test_the_engine_reports_the_groups_the_share_and_the_forms(engine):
    ssm = engine.ssm_report()
    assert (ssm["mamba_layers"], ssm["attention_layers"]) == (5, 2)
    assert (ssm["groups"], ssm["heads_per_group"]) == (2, 4)
    assert ssm["decode"] == {"form": "pallas-interpret", "head_tile": 8,
                             "groups": 2}
    assert ssm["state_bytes_per_slot"] == 5 * 8 * 16 * 16 * 4
    report = engine.moe_report()
    assert report["held"] == {
        "first": 4, "count": 4, "routed_over": 8,
        "absent": "dropped before the sort, gates not renormalised"}
    assert (report["experts"], report["top_k"]) == (8, 2)
    assert (report["expert_layers"], report["layers_without_ffn"]) == (5, 2)
    assert report["expert_form"].startswith("ungated: relu2")
    assert report["shared_expert"] == {
        "width": 40, "form": "dense ungated FFN, every token, weight 1"}
    assert report["grouped_matmul"]["operand"] == (
        "layers' stack [5, 4, 64, 128]")


def test_the_counters_count_held_and_absent_pairs_and_no_ffnless_layer(
        engine):
    """A decode block of 4 steps x 4 lanes x 5 EXPERT layers x top 2: the
    two layers that end in nothing are in no expert counter."""
    before = list(engine.expert_pairs)
    engine.decode_steps()
    hits = engine.counters["moe"]["expert_hits"]
    engine.decode_steps()
    grew = [b - a for a, b in zip(before, engine.expert_pairs)]
    assert len(grew) == 8 and sum(grew) == 2 * 4 * 4 * 5 * 2
    # a held expert hit, a layer and a step: at most 4 of them a call
    assert 0 < (engine.counters["moe"]["expert_hits"] - hits
                ) <= 2 * 4 * 5 * 4
    counts = engine.moe_counts()
    total = sum(engine.expert_pairs)
    assert counts["pairs"] == total
    assert counts["expert_pairs"] == engine.expert_pairs[4:8]
    assert counts["held_pairs"] == sum(engine.expert_pairs[4:8])
    assert counts["held_pairs"] + counts["absent_pairs"] == total
    assert 0 < counts["held_pairs"] < total
    assert counts["expert_hits"] == engine.counters["moe"]["expert_hits"]
    # an engine that holds all it routes over counts as it did
    plain = InferenceEngine.__new__(InferenceEngine)
    plain.config, plain.expert_pairs = llama.preset("tiny-moe"), [3, 1, 0, 2]
    assert plain.moe_counts() == {"pairs": 6, "expert_pairs": [3, 1, 0, 2]}


REFUSED = {
    "prefix_cache_mb": dict(prefix_cache_bytes=1 << 20),
    "speculative": dict(speculative=object()),
    "prefill_chunk": dict(prefill_chunk=16),
    "role": dict(role="prefill"),
}


@pytest.mark.parametrize("setting", sorted(REFUSED))
def test_the_engine_refuses_what_cannot_carry_a_state(setting):
    with pytest.raises(EngineError, match=f"tpu.{setting}"):
        make_engine(**REFUSED[setting])


CONFIG_REFUSED = {
    "prefix_cache_mb": {"prefix_cache_mb": 64},
    "speculative": {"speculative": {"k_draft": 4}},
    "prefill_chunk": {"prefill_chunk": 256},
    "role": {"role": "disagg"},
    "mesh": {"mesh": {"model": 4}},
}


@pytest.mark.parametrize("preset", ["tiny-nh", "nemotron-3-nano-30b-a3b"])
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
