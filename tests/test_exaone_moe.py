"""K-EXAONE's family (HF `exaone_moe`) at a small size on the CPU: window
(rotary) and full (NoPE) layers under q/k norms, a leading dense layer,
sigmoid-routed experts of which this chip holds a SHARE beside a shared
expert, and the multi-token-prediction module drafting ON THE DEVICE inside
the decode block. The program against the plain reference
(`benchmarks/reference/exaone_moe_decoder.py`) by LOGITS — the trunk's at
every position a step scored, the module's that made each draft — through
accepted and rejected drafts (a vocabulary of 32 makes a random module's
drafts land); greedy drafting token for token against plain decode; a
window layer's ring after scripted accepts and rejects through an oracle
drafter behind the engine's seam; the eight shares adding up to the uncut
layer; `config_from_hf` against the catalog's row; the refusals."""

import dataclasses
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.engine.engine import (
    DRAFT_OFF, EngineError, InferenceEngine, SamplingParams)
from symmetry_tpu.engine.scheduler import GenRequest, Scheduler
from symmetry_tpu.engine.spec import SpecConfig
from symmetry_tpu.engine.tokenizer import get_tokenizer
from symmetry_tpu.models import hybrid, llama, residents

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from reference import exaone_moe_decoder as ref  # noqa: E402

CFG = llama.preset("tiny-xm")
MODEL = hybrid.hf_config(CFG)
W = CFG.sliding_window
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# both sides compute in float32 on the CPU and differ in the order of
# accumulation: 5e-5 of the logit scale (measured under 5e-6). A dropped
# q/k norm, a rope on a full layer, a window off by one, a selection
# without its bias, gates renormalised over the held experts and the
# module's two inputs swapped each read over 1e-3 (below).
TOL = 5e-5


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(1), jnp.float32)


def ids_of(n, key=0):
    return [int(t) for t in jax.random.randint(jax.random.key(key), (n,), 0,
                                               CFG.vocab_size)]


def reference(params, ids, **kw):
    with jax.default_matmul_precision("highest"):
        trunk, module = ref.reference_logits(params, MODEL,
                                             jnp.asarray(ids), **kw)
    return np.asarray(trunk), np.asarray(module)


def worst(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def make_engine(params, spec="mtp", cls=InferenceEngine, **kw):
    args = dict(max_slots=4, max_seq_len=128, prefill_buckets=(16, 32),
                decode_block=4, prefill_chunk=None,
                cache_dtype=jnp.float32,
                speculative=SpecConfig.from_knob(spec))
    args.update(kw)
    return cls(CFG, params,
               get_tokenizer(None, vocab_size=CFG.vocab_size), **args)


class Tapped:
    """An engine whose programs hand their logits out (`engine.tap`), and
    what each lane's stream was."""

    def __init__(self, params, temperature, cls=InferenceEngine, **kw):
        self.engine = make_engine(params, cls=cls, **kw)
        self.records = []
        self.engine.tap = lambda *a: self.records.append(
            tuple(np.asarray(x) if not isinstance(x, str) else x
                  for x in a))
        self.engine._build_jits()
        self.sampling = SamplingParams(temperature=temperature, seed=7)

    def run(self, prompts, blocks):
        eng = self.engine
        with jax.default_matmul_precision("highest"):
            firsts = eng.prefill_and_insert_many(
                [(i, p, self.sampling) for i, p in enumerate(prompts)])
            streams = [[f] for f in firsts]
            for _ in range(blocks):
                toks = eng.decode_steps()
                for b, stream in enumerate(streams):
                    stream.extend(int(t) for t in toks[:toks[-1, b], b])
            jax.effects_barrier()
        return streams


def check_against_the_reference(params, prompts, streams, records):
    """Every tapped row of logits against the reference's full pass over
    the lane's whole sequence; returns (drafts accepted, drafts rejected,
    rows compared)."""
    full = [list(p) + s for p, s in zip(prompts, streams)]
    want = [reference(params, ids) for ids in full]
    accepted = rejected = rows = 0
    for kind, lengths, *rest in records:
        for b, ids in enumerate(full):
            at = int(lengths[b])
            if kind == "prefill":
                if b >= len(prompts) or at != len(prompts[b]):
                    continue    # a pad row replays the last request
                last, first = rest
                assert worst(last[b], want[b][0][at - 1]) < TOL
                assert worst(first[b], want[b][1][at - 1]) < TOL
                rows += 2
            elif kind == "trunk" and at:
                logits, draft, out, n_emit = rest
                assert worst(logits[b, 0], want[b][0][at]) < TOL, (b, at)
                assert ids[at + 1] == out[b, 0]
                rows += 1
                if draft[b] >= 0 and n_emit[b] == 2:
                    # the drafted position stayed: its row scored the bonus
                    assert ids[at + 1] == draft[b]
                    assert worst(logits[b, 1], want[b][0][at + 1]) < TOL
                    accepted, rows = accepted + 1, rows + 1
                elif draft[b] >= 0:
                    rejected += 1
            elif kind == "module" and at:
                # the row that made the next draft: the module's at the
                # lane's last position that stayed
                logits, n_emit = rest
                pos = at + int(n_emit[b]) - 1
                if pos < len(want[b][1]):
                    assert worst(logits[b], want[b][1][pos]) < TOL, (b, pos)
                    rows += 1
    return accepted, rejected, rows


PROMPTS = [ids_of(5, 1), ids_of(W, 2), ids_of(21, 3)]


# ------------------------------------------------ the program vs the reference

@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_drafting_steps_score_the_references_logits(params, temperature):
    """Prefill, insert, then decode blocks WITH the module drafting: every
    row of logits a step scored and every row that made a draft is the
    reference's, through accepted AND rejected drafts, prompts under, at
    and over the window, rings wrapping."""
    tapped = Tapped(params, temperature)
    streams = tapped.run(PROMPTS, blocks=6)
    accepted, rejected, rows = check_against_the_reference(
        params, PROMPTS, streams, tapped.records)
    assert accepted >= 2 and rejected >= 10 and rows > 150
    counts = tapped.engine.counters["mtp"]
    assert counts["accepted"] == accepted
    assert counts["drafted"] == accepted + rejected == counts["steps"]
    assert counts["emitted"] == sum(len(s) - 1 for s in streams)
    lengths = np.asarray(tapped.engine.state.cache.lengths)
    assert lengths[:3].tolist() == [len(p) + len(s) - 1
                                    for p, s in zip(PROMPTS, streams)]


def test_greedy_drafting_is_plain_decode_token_for_token(params):
    plain = make_engine(params, spec=None)
    drafting = make_engine(params)
    for eng in (plain, drafting):
        eng.prefill_and_insert_many(
            [(i, p, SamplingParams()) for i, p in enumerate(PROMPTS)])
    want = np.concatenate([plain.decode_steps() for _ in range(8)])
    blocks = [drafting.decode_steps() for _ in range(5)]
    for b in range(3):
        got = np.concatenate([t[:t[-1, b], b] for t in blocks])
        assert 20 <= len(got) <= 40
        n = min(len(got), len(want))
        assert got[:n].tolist() == want[:n, b].tolist()
    counts = drafting.counters["mtp"]
    assert 0 < counts["accepted"] < counts["drafted"]
    # the parked lane drafts nothing and counts nothing
    assert counts["steps"] == 3 * 5 * 4


@pytest.mark.parametrize("wrong,least", [
    ("no_qk_norm", 1e-2), ("rope_full", 1e-2), ("window_short", 1e-3),
    ("no_bias", 1e-3), ("renormed_held", 1e-2)])
def test_the_tolerance_tells_each_departure_of_the_trunk(params, wrong,
                                                         least):
    ids = ids_of(40, 5)
    want = reference(params, ids)
    got = reference(params, ids, wrong=wrong)
    assert worst(got[0], want[0]) > least > TOL


@pytest.mark.parametrize("wrong", ["mtp_swapped", "mtp_prenorm_hidden"])
def test_the_tolerance_tells_each_departure_of_the_module(params, wrong):
    """(The hidden state handed over BEFORE the trunk's final norm is told
    only where that norm's weight is not all ones: the module norms what it
    is handed, and an RMSNorm of an RMSNorm under unit weights is the same
    function — so the control runs on a drawn weight.)"""
    drawn = {**params, "final_norm": 1.0 + 0.5 * jax.random.normal(
        jax.random.key(9), params["final_norm"].shape)}
    ids = ids_of(40, 5)
    want = reference(drawn, ids)
    got = reference(drawn, ids, wrong=wrong)
    assert worst(got[0], want[0]) == 0.0        # the trunk does not move
    assert worst(got[1], want[1]) > 1e-2 > TOL


def test_the_modules_rows_lie_behind_the_full_layers(params):
    cache = llama.init_cache(CFG, 2, 32, jnp.float32, ring=W)
    assert cache.k.shape[0] == len(CFG.layers_of("full_attention")) + 1
    assert cache.kw.shape[:3] == (len(CFG.layers_of("sliding_attention")),
                                  2, W)
    # the trunk's forward leaves the module's layer as it was
    toks = jnp.asarray([ids_of(12, 1), ids_of(12, 2)])
    scratch = llama.init_cache(CFG, 2, 16, jnp.float32)
    h, after = llama.forward_hidden(
        params, CFG, jnp.pad(toks, ((0, 0), (0, 4))), scratch,
        jnp.asarray([12, 9]), prefill_flash=True)
    assert not np.asarray(after.k[-1]).any()
    assert np.asarray(after.k[:-1, 0, :12]).any()
    hm, module = hybrid.mtp_forward(
        params, CFG, h, jnp.roll(jnp.pad(toks, ((0, 0), (0, 4))), -1, 1),
        after._replace(lengths=jnp.zeros_like(after.lengths)),
        jnp.asarray([12, 9]), prefill_flash=True)
    assert np.asarray(module.k[-1, 0, :12]).all(axis=(-1, -2)).any()
    np.testing.assert_array_equal(module.k[:-1], after.k[:-1])
    assert module.lengths.tolist() == [12, 9] and hm.shape == h.shape


# ------------------------------------------------------- the ring and the seam

class Scripted(InferenceEngine):
    """An oracle behind the engine's seam: lane b's draft for the token
    after position p is `script[b, p]` — the token plain greedy decode
    emits there (accepted) or another (rejected), as the test wrote it."""

    script = None

    def device_drafter(self, params, h, out, n_emit, cache):
        rows = jnp.arange(out.shape[0])
        # after the step the lane's last token lies at lengths + n_emit
        return self.script[rows, cache.lengths + n_emit + 1], cache


@pytest.mark.parametrize("pattern", [
    "a", "r", "ar", "aar", "rra", "arraarrr"])
def test_a_ring_survives_any_mix_of_accepted_and_rejected_drafts(params,
                                                                 pattern):
    """After scripted accepts and rejects, through prompts under, at and
    over the window: the stream is plain greedy decode's, and every window
    layer's ring holds, at row p mod ring, exactly the keys of the slot's
    last positions — the rows a fresh prefill of the same tokens writes —
    so a window layer attends to t - window < s <= t and nothing else."""
    plain = make_engine(params, spec=None)
    plain.prefill_and_insert_many(
        [(i, p, SamplingParams()) for i, p in enumerate(PROMPTS)])
    want = np.concatenate([plain.decode_steps() for _ in range(12)])
    seqs = [list(p) + [0] + want[:, b].tolist()
            for b, p in enumerate(PROMPTS)]
    script = np.zeros((4, 128), np.int32)
    for b, (p, seq) in enumerate(zip(PROMPTS, seqs)):
        firsts = plain_first(params, p)
        seq[len(p)] = firsts
        for t in range(len(p), len(seq)):
            accept = pattern[(t - len(p)) % len(pattern)] == "a"
            script[b, t] = seq[t] if accept else (seq[t] + 1) % CFG.vocab_size
    eng = make_engine(params, cls=Scripted)
    eng.script = jnp.asarray(script)
    eng._build_jits()
    eng.prefill_and_insert_many(
        [(i, p, SamplingParams()) for i, p in enumerate(PROMPTS)])
    blocks = [eng.decode_steps() for _ in range(6)]
    got = [np.concatenate([t[:t[-1, b], b] for t in blocks])
           for b in range(3)]
    for b, p in enumerate(PROMPTS):
        n = min(len(got[b]), len(want))
        assert n >= 24 and got[b][:n].tolist() == want[:n, b].tolist()
    counts = eng.counters["mtp"]
    if "a" in pattern and "r" in pattern:
        assert 0 < counts["accepted"] < counts["drafted"]
    ring = eng.state.cache.kw.shape[2]
    assert ring == residents.ring_rows(CFG, 1) == 128 and ring > W
    # the rows a fresh pass over the same tokens writes, position by position
    for b, p in enumerate(PROMPTS):
        length = int(eng.state.cache.lengths[b])
        ids = (list(p) + [plain_first(params, p)] + got[b].tolist())[:length]
        scratch = llama.init_cache(CFG, 1, 128, jnp.float32)
        _, fresh = llama.forward_hidden(
            params, CFG, jnp.asarray([ids + [0] * (128 - length)]), scratch,
            jnp.asarray([length]), prefill_flash=True)
        for t in range(length - W, length):
            if t < 0:
                continue
            np.testing.assert_allclose(
                eng.state.cache.kw[:, b, t % ring], fresh.kw[:, 0, t],
                atol=2e-5, err_msg=f"lane {b} position {t}")
            np.testing.assert_allclose(
                eng.state.cache.vw[:, b, t % ring], fresh.vw[:, 0, t],
                atol=2e-5)
        # the full leaves hold every position
        np.testing.assert_allclose(
            eng.state.cache.k[:-1, b, :length], fresh.k[:-1, 0, :length],
            atol=2e-5)


_FIRSTS = {}


def plain_first(params, prompt):
    key = tuple(prompt)
    if key not in _FIRSTS:
        trunk, _ = reference(params, prompt)
        _FIRSTS[key] = int(np.argmax(trunk[-1]))
    return _FIRSTS[key]


def test_a_request_that_opts_out_advances_one_token_a_step(params):
    eng = make_engine(params)
    eng.prefill_and_insert_many(
        [(i, p, SamplingParams()) for i, p in enumerate(PROMPTS)])
    eng.draft_off(1)
    assert np.asarray(eng.state.draft).tolist()[1] == DRAFT_OFF
    toks = [eng.decode_steps() for _ in range(3)]
    assert [int(t[-1, 1]) for t in toks] == [4, 4, 4]
    assert int(np.asarray(eng.state.draft)[1]) == DRAFT_OFF
    assert eng.counters["mtp"]["drafted"] == 2 * 12
    # a plain engine ignores the call
    make_engine(params, spec=None).draft_off(1)


# ------------------------------------------------------------------ the share

def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer(
        params):
    """A sparse layer's output over ALL the experts is the sum of the eight
    shares' routed parts (one expert each here) plus the shared expert
    ONCE — what the deployment's exchange adds up — in the reference, and
    the program's share is the reference's share."""
    full_cfg = dataclasses.replace(CFG, experts_held=None)
    whole = llama.init_params(full_cfg, jax.random.key(3), jnp.float32)
    p = ref.at(whole["layers"]["ffn"], 2)
    n = jax.random.normal(jax.random.key(4), (24, CFG.hidden_size))
    uncut_model = hybrid.hf_config(full_cfg)
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.sparse_ffn(n, p, uncut_model)
        parts = []
        for e in range(CFG.num_experts):
            model = dict(uncut_model, num_experts=1,
                         experts_routed_over=CFG.num_experts,
                         experts_held=[e, 1])
            mine = {**p, **{k: p[k][e:e + 1] for k in ("wg", "wu", "wd")}}
            parts.append(ref.routed(n, mine, model)[0])
        total = sum(parts) + ref.shared(n, p)
    assert worst(total, np.asarray(uncut)) < 1e-6
    # (an expert whose bias is low may go unselected over 24 tokens)
    assert sum(float(jnp.abs(part).max()) > 0 for part in parts) >= 6
    # the program's share [0, 4) against the reference's same share
    from symmetry_tpu.models.moe import moe_mlp

    held = {**p, **{k: p[k][:4] for k in ("wg", "wu", "wd")}}
    with jax.default_matmul_precision("highest"):
        got, pairs = moe_mlp(n[None], held, CFG)
        want, _ = ref.sparse_ffn(n, held, MODEL)
    assert worst(got[0], np.asarray(want)) < TOL
    assert int(pairs[:CFG.num_experts].sum()) == 24 * 2
    assert int(pairs[-1]) == int((np.asarray(pairs[:4]) > 0).sum())


# ------------------------------------------------------------- the config keys

def test_config_from_hf_of_the_catalog_row_is_the_preset_uncut():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "K-EXAONE-236B-A23B")
    got = llama.config_from_hf(row["config"])
    cut = llama.preset("k-exaone-236b-a23b")
    assert got == dataclasses.replace(
        cut, num_layers=48, vocab_size=153600, experts_held=None,
        layer_types=cut.layer_types[:4] * 12,
        rope_layout=cut.rope_layout[:4] * 12)
    assert (got.num_dense_layers, got.dense_intermediate_size,
            got.intermediate_size, got.shared_intermediate_size) == (
        1, 18432, 2048, 2048)
    assert got.mtp_layers == 1 and got.qk_norm and got.router_bias
    assert llama.config_from_hf(hybrid.hf_config(cut)) == cut
    assert llama.config_from_hf(MODEL) == CFG


@pytest.mark.parametrize("key,value,says", [
    ("scoring_func", "softmax", "scoring_func"),
    ("n_group", 4, "n_group"), ("num_shared_experts", 2, "num_shared"),
    ("mtp_layer_types", ["sliding_attention"], "no full-attention block"),
    ("mlp_layer_types", ["sparse", "dense"] + ["sparse"] * 6,
     "do not lead"),
    ("sliding_windows", [4] * 8, "several sizes"),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 1e6},
     "scaled rotary"),
    ("experts_held", [0, 3], "differ")])
def test_config_from_hf_refuses_what_would_change_the_layer(key, value,
                                                            says):
    with pytest.raises(ValueError, match=says):
        llama.config_from_hf({**MODEL, key: value})


def test_a_module_goes_with_a_window_trunk_and_is_one():
    with pytest.raises(ValueError, match="mtp_layers"):
        dataclasses.replace(llama.preset("tiny-nh"), mtp_layers=1)
    with pytest.raises(ValueError, match="mtp_layers"):
        dataclasses.replace(CFG, mtp_layers=2)


# ---------------------------------------------------------------- the refusals

@pytest.mark.parametrize("setting,kw", [
    ("prefix_cache_mb", dict(prefix_cache_bytes=1 << 20)),
    ("prefill_chunk", dict(prefill_chunk=16)),
    ("role", dict(role="prefill"))])
def test_the_engine_refuses_what_cannot_carry_a_ring_or_a_module(params,
                                                                 setting, kw):
    with pytest.raises(EngineError, match=f"tpu.{setting}"):
        make_engine(params, **kw)


def test_the_modules_drafter_is_the_knobs_value_and_needs_a_module():
    spec = SpecConfig.from_knob("mtp")
    assert (spec.drafter, spec.k_draft) == ("mtp", 1)
    assert SpecConfig.from_knob(True).drafter == "ngram"
    with pytest.raises(ValueError, match="k_draft is 1"):
        SpecConfig(drafter="mtp", k_draft=4)
    with pytest.raises(ValueError, match="'ngram' or 'mtp'"):
        SpecConfig(drafter="eagle")
    swa = llama.preset("tiny-swa")
    with pytest.raises(EngineError, match="tpu.speculative mtp"):
        InferenceEngine(
            swa, llama.init_params(swa, jax.random.key(0), jnp.float32),
            get_tokenizer(None, vocab_size=swa.vocab_size), max_slots=2,
            max_seq_len=64, prefill_buckets=(16,), prefill_chunk=None,
            speculative=spec)


def test_the_config_layer_refuses_the_module_for_a_preset_without_one():
    from symmetry_tpu.provider.config import ConfigError, ConfigManager

    def cfg(preset, **tpu):
        return {"name": "p", "public": True, "serverKey": "00" * 32,
                "modelName": "m", "apiProvider": "tpu_native",
                "tpu": {"model_preset": preset, "prefill_chunk": None,
                        **tpu}}

    with pytest.raises(ConfigError, match="tpu.speculative mtp"):
        ConfigManager(config=cfg("tiny-swa", speculative="mtp"))
    ConfigManager(config=cfg("tiny-xm", speculative="mtp"))
    ConfigManager(config=cfg("tiny-swa", speculative=True))
    with pytest.raises(ConfigError, match="tpu.prefill_chunk 64"):
        ConfigManager(config=cfg("tiny-xm", prefill_chunk=64))


# ----------------------------------------------------- through the scheduler

def test_the_scheduler_streams_one_or_two_tokens_a_step(params):
    """Greedy requests through the scheduler with the module drafting: each
    stream is plain decode's, token for token and to its exact length; the
    counts the wire would carry are the engine's; nothing compiles after
    warm-up; a request that opts out still streams the same tokens."""
    eng = make_engine(params)
    eng.warmup()
    before = eng.compile_cache_sizes()
    requests = [(PROMPTS[0], 11, None), (PROMPTS[1], 9, False),
                (PROMPTS[2], 14, None)]
    plain = make_engine(params, spec=None)
    plain.prefill_and_insert_many(
        [(i, p, SamplingParams()) for i, (p, _, _) in enumerate(requests)])
    want = np.concatenate([plain.decode_steps() for _ in range(4)])
    got = {i: [] for i in range(len(requests))}
    done = {i: threading.Event() for i in range(len(requests))}

    def sink(batch):
        for req, ev in batch:
            got[req.id].append(ev)
            if ev.done:
                done[req.id].set()

    eng.tokenizer.eos_ids = frozenset({CFG.vocab_size + 5})
    sched = Scheduler(eng, emit_batch=sink)
    sched.start()
    try:
        for i, (ids, max_new, spec) in enumerate(requests):
            sched.submit(GenRequest(
                prompt_ids=list(ids), sampling=SamplingParams(),
                max_new_tokens=max_new, emit=lambda ev: None,
                cancelled=lambda: False, id=i, speculative=spec))
        for i, ev in done.items():
            assert ev.wait(120), f"request {i} hung"
        stats = sched.stats()
    finally:
        sched.stop(timeout=10)
    for i, (ids, max_new, _) in enumerate(requests):
        last = got[i][-1]
        assert last.done and not last.error, last
        assert last.tokens_emitted == max_new
        tokens = [plain_first(params, ids)] + want[:max_new - 1, i].tolist()
        dec = eng.tokenizer.stream_decoder()
        assert "".join(ev.text for ev in got[i]) == \
            dec.push_many(tokens) + dec.flush(), i
    assert eng.compile_cache_sizes() == before
    assert stats["tokens"] == sum(n for _, n, _ in requests)
    assert stats["mtp"].keys() == eng.counters["mtp"].keys()
    assert eng.counters["mtp"]["prefill_tokens"] == sum(
        len(p) for p, _, _ in requests)
