"""What a slot keeps beyond K and V a head at one capacity is ONE table
(models/residents.py): every refusal sentence, the tail of `expert_pairs`
and the stats block a row's counters go to are read off it. The engine- and
config-level tests of each mechanism pin the sentences where they are
raised; these pin the table itself."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from symmetry_tpu.models import llama, residents

ROWS = {row.name: row for row in residents.RESIDENTS}
# the smallest preset that has each row
HAS = {"recurrent state": "tiny-hybrid", "index keys": "tiny-dsa",
       "latent row": "tiny-mla", "window ring": "tiny-swa",
       "diffusion block": "tiny-bd", "held share": "tiny-nh",
       "drafting module": "tiny-xm"}
# a setting as `refusals` takes it, and as its sentence names it
SETTINGS = {"prefix_cache": (dict(prefix_cache=True), "prefix_cache_mb"),
            "speculative": (dict(speculative=True), "speculative"),
            "prefill_chunk": (dict(prefill_chunk=256), "prefill_chunk 256"),
            "role": (dict(role="prefill"), "role 'prefill'"),
            "mesh": (dict(mesh=True), "mesh"),
            "kv_quant": (dict(kv_quant=True), "kv_quantization int8")}
PAIRS = [(row.name, setting) for row in residents.RESIDENTS
         for setting in row.reasons]


def test_the_table_is_the_seven_rows_and_the_29_sentences():
    # (the window ring carries a draft since PR 65: 26 - 1, and the
    # drafting module's four)
    assert list(ROWS) == list(HAS) and len(PAIRS) == 29
    assert "speculative" not in ROWS["window ring"].reasons
    assert set(SETTINGS) == set(residents.SETTINGS)
    for name, preset in HAS.items():
        assert ROWS[name] in residents.kept(llama.preset(preset))


@pytest.mark.parametrize("name,setting", PAIRS)
def test_a_row_refuses_a_setting_in_one_sentence_of_its_own(name, setting):
    row, config = ROWS[name], llama.preset(HAS[name])
    kwargs, named = SETTINGS[setting]
    every = residents.refusals(
        config, **{k: v for kw, _ in SETTINGS.values()
                   for k, v in kw.items()})
    assert len(every) == sum(len(r.reasons) for r in residents.kept(config))
    # (a model may have several rows that refuse one setting — tiny-xm's
    # ring and module both refuse the prefix cache: this row's sentence)
    (why,) = [s for s in residents.refusals(config, **kwargs)
              if row.phrase in s]
    assert [s for s in every if s.startswith(f"tpu.{named}:")
            and row.phrase in s] == [why]
    assert row.reasons[setting] in why and row.phrase in why
    assert not any(other.phrase in why for other in residents.RESIDENTS
                   if other.phrase and other is not row)
    # whatever the recurrent kind, the sentence names none
    assert "mamba" not in why


@pytest.mark.parametrize("name", sorted(llama.PRESETS))
def test_a_preset_is_served_as_it_is_and_its_tail_is_the_tables(name):
    config = llama.preset(name)
    assert residents.refusals(config) == []
    words = residents.tail_words(config)
    assert len(words) == sum(len(row.words)
                             for row in residents.kept(config))
    if not getattr(config, "num_experts", 0):
        assert words == ()      # no experts: no `expert_pairs` to end
        return
    # one slot of a few rows, from shapes alone: nothing is allocated
    cache = jax.eval_shape(lambda: llama.init_cache(
        config, 1, 8, jnp.bfloat16, count_experts=True))
    assert cache.expert_pairs.shape == (config.num_experts + len(words),)
    assert cache.expert_pairs.dtype == jnp.int32


def test_two_rows_with_words_lie_in_the_tables_order_and_each_finds_its_own():
    """A window / full model that holds a share of its experts (K-EXAONE's
    one-chip share) has BOTH tails: the table's order lays them out, and a
    device function adds to its own row's words wherever they lie."""
    both = dataclasses.replace(llama.preset("tiny-swa"), experts_held=(0, 4))
    assert residents.tail_words(both) == (llama.WINDOW_COUNTS
                                          + llama.HELD_COUNTS)
    X = both.num_experts
    assert residents.tail_at(both, llama.WINDOW_COUNTS) == (X, X + 4)
    assert residents.tail_at(both, llama.HELD_COUNTS) == (X + 4, X + 5)
    with pytest.raises(ValueError, match="no row that counts"):
        residents.tail_at(both, llama.LATENT_COUNTS)
    cache = llama.init_cache(both, 2, 8, count_experts=True)
    assert cache.expert_pairs.shape == (X + 5,)
    # one layer's pairs + hits, then one decode forward's window counts
    pairs = jnp.arange(1, X + 2, dtype=jnp.int32)       # [X] pairs, 1 hit word
    cache = llama.add_expert_pairs(cache, pairs, both)
    vector = llama.count_window(cache.expert_pairs, jnp.asarray([3, 0]),
                                jnp.asarray([4, 1]), 8, both)
    assert vector[:X].tolist() == list(range(1, X + 1))
    assert vector[X:X + 4].tolist() == [1, 4, 4, 0]      # the live lane's
    assert vector[X + 4:].tolist() == [X + 1]            # the hits' word
    # where nothing counts the experts there is no vector to lay out
    assert llama.init_cache(both, 1, 8).expert_pairs is None


@pytest.mark.parametrize("preset,words", [
    ("tiny-xm", llama.WINDOW_COUNTS + llama.HELD_COUNTS + llama.MTP_COUNTS),
    ("k-exaone-236b-a23b",
     llama.WINDOW_COUNTS + llama.HELD_COUNTS + llama.MTP_COUNTS),
    ("tiny-nh", llama.HELD_COUNTS), ("tiny-swa", llama.WINDOW_COUNTS)])
def test_tail_words_of_a_model_with_several_rows(preset, words):
    config = llama.preset(preset)
    assert residents.tail_words(config) == words
    at = config.num_experts
    for row in residents.kept(config):
        if row.words:
            assert residents.tail_at(config, row.words) == (
                at, at + len(row.words))
            at += len(row.words)


def test_the_modules_drafter_is_refused_a_model_without_a_module():
    (why,) = residents.refusals(llama.preset("tiny-swa"),
                                speculative=residents.MTP)
    assert why.startswith("tpu.speculative mtp:")
    assert "num_nextn_predict_layers" in why
    # the n-gram drafter is carried by a ring since PR 65, the module's by
    # a model that has one
    assert residents.refusals(llama.preset("tiny-swa"),
                              speculative=True) == []
    assert residents.refusals(llama.preset("tiny-xm"),
                              speculative=residents.MTP) == []
    assert residents.ring_rows(llama.preset("tiny-swa")) == 8
    assert residents.ring_rows(llama.preset("tiny-swa"), 4) == 128
    assert residents.ring_rows(llama.preset("k-exaone-236b-a23b"), 1) == 256
    assert residents.ring_rows(llama.preset("tiny-nh"), 1) is None


def test_each_block_is_a_path_the_readers_dig_with_the_keys_it_had():
    """`benchmarks/readers/` dig `stats.engine.<block>`: a rename fails
    here before it fails on the chip."""
    from symmetry_tpu.ops import sparse_attention as sa

    assert {row.name: row.block for row in residents.RESIDENTS} == {
        "recurrent state": "ssm", "index keys": "dsa", "latent row": "mla",
        "window ring": "swa", "diffusion block": "diffusion",
        "held share": "moe", "drafting module": "mtp"}
    assert {row.block: set(row.counters()) for row in residents.RESIDENTS
            } == {
        "ssm": {"prefill_tokens", "state_installs"},
        "dsa": {"queries", "dense_queries", "candidates", "selected"},
        "mla": {"decode_steps", "live_positions", "prefill_tokens"},
        "swa": {"decode_steps", "full_rows", "ring_rows", "ring_wraps",
                "prefill_tokens"},
        "diffusion": set(),         # the engine's and the scheduler's own
        "moe": {"expert_hits"},
        "mtp": {"drafted", "accepted", "emitted", "steps",
                "prefill_tokens"}}
    # the index keys' words are the device function's, and read back whole
    assert len(ROWS["index keys"].words) == sa.N_COUNTS
    tail = sa.add_counts(jnp.zeros((sa.N_COUNTS,), jnp.int32),
                         jnp.asarray([3, 1, 0, 5 << 18, 0, 7], jnp.int32))
    assert ROWS["index keys"].decode(tail) == {
        "queries": 3, "dense_queries": 1, "candidates": 5 << 18,
        "selected": 7}
