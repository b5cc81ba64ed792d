"""Unit tests for the tensor ops floor (rope/attention/sampling)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.ops import (
    apply_rope, gqa_attention, rms_norm, sample_tokens, sampling)
from symmetry_tpu.ops.attention import NEG_INF


class TestRope:
    def test_position_zero_is_identity(self):
        x = jax.random.normal(jax.random.key(0), (2, 1, 4, 16))
        pos = jnp.zeros((2, 1), jnp.int32)
        np.testing.assert_allclose(apply_rope(x, pos), x, atol=1e-6)

    def test_preserves_norm(self):
        x = jax.random.normal(jax.random.key(1), (1, 8, 2, 32))
        pos = jnp.arange(8, dtype=jnp.int32)[None, :]
        out = apply_rope(x, pos)
        # Rotation acts on (i, i+d/2) pairs — pairwise norms are invariant.
        def pair_norms(a):
            h = a.shape[-1] // 2
            return a[..., :h] ** 2 + a[..., h:] ** 2
        np.testing.assert_allclose(pair_norms(out), pair_norms(x), atol=1e-4)

    def test_relative_property(self):
        # <rope(q,p), rope(k,p)> depends only on content for equal positions.
        q = jax.random.normal(jax.random.key(2), (1, 1, 1, 32))
        k = jax.random.normal(jax.random.key(3), (1, 1, 1, 32))
        def dot_at(p):
            pos = jnp.full((1, 1), p, jnp.int32)
            return jnp.sum(apply_rope(q, pos) * apply_rope(k, pos))
        np.testing.assert_allclose(dot_at(0), dot_at(17), rtol=1e-4)


class TestRmsNorm:
    def test_matches_reference_formula(self):
        x = np.random.default_rng(0).normal(size=(2, 3, 8)).astype(np.float32)
        w = np.random.default_rng(1).normal(size=(8,)).astype(np.float32)
        want = x / np.sqrt((x**2).mean(-1, keepdims=True) + 1e-5) * w
        got = rms_norm(jnp.asarray(x), jnp.asarray(w))
        np.testing.assert_allclose(got, want, rtol=1e-5)


def naive_attention(q, k, v, q_pos, kv_len, window=None):
    """Straight numpy reference: per-sample, per-head loops."""
    B, S, nq, D = q.shape
    T, nkv = k.shape[1], k.shape[2]
    group = nq // nkv
    out = np.zeros_like(q)
    for b in range(B):
        for h in range(nq):
            kh = h // group
            for s in range(S):
                scores = q[b, s, h] @ k[b, :, kh].T / np.sqrt(D)
                mask = (np.arange(T) <= q_pos[b, s]) & (np.arange(T) < kv_len[b])
                if window is not None:
                    mask &= np.arange(T) > q_pos[b, s] - window
                scores = np.where(mask, scores, -1e30)
                p = np.exp(scores - scores.max())
                p /= p.sum()
                out[b, s, h] = p @ v[b, :, kh]
    return out


class TestAttention:
    @pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2), (8, 1)])
    def test_matches_naive(self, nq, nkv):
        rng = np.random.default_rng(42)
        B, S, T, D = 2, 3, 10, 8
        q = rng.normal(size=(B, S, nq, D)).astype(np.float32)
        k = rng.normal(size=(B, T, nkv, D)).astype(np.float32)
        v = rng.normal(size=(B, T, nkv, D)).astype(np.float32)
        q_pos = np.array([[4, 5, 6], [0, 1, 2]], np.int32)
        kv_len = np.array([7, 3], np.int32)
        got = gqa_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            jnp.asarray(q_pos), jnp.asarray(kv_len))
        want = naive_attention(q, k, v, q_pos, kv_len)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_sliding_window(self):
        rng = np.random.default_rng(7)
        B, S, T, D, nh = 1, 2, 12, 4, 2
        q = rng.normal(size=(B, S, nh, D)).astype(np.float32)
        k = rng.normal(size=(B, T, nh, D)).astype(np.float32)
        v = rng.normal(size=(B, T, nh, D)).astype(np.float32)
        q_pos = np.array([[8, 9]], np.int32)
        kv_len = np.array([10], np.int32)
        got = gqa_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            jnp.asarray(q_pos), jnp.asarray(kv_len),
                            sliding_window=4)
        want = naive_attention(q, k, v, q_pos, kv_len, window=4)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


class TestSampling:
    def setup_method(self):
        self.logits = jnp.asarray(
            np.random.default_rng(0).normal(size=(4, 32)).astype(np.float32))

    def test_greedy_when_temperature_zero(self):
        out = sample_tokens(self.logits, jax.random.key(0),
                            temperature=jnp.zeros(4),
                            top_p=jnp.ones(4), top_k=jnp.zeros(4, jnp.int32))
        np.testing.assert_array_equal(out, jnp.argmax(self.logits, -1))

    def test_top_k_one_is_greedy(self):
        out = sample_tokens(self.logits, jax.random.key(1),
                            temperature=jnp.ones(4),
                            top_p=jnp.ones(4),
                            top_k=jnp.ones(4, jnp.int32))
        np.testing.assert_array_equal(out, jnp.argmax(self.logits, -1))

    def test_tiny_top_p_is_greedy(self):
        out = sample_tokens(self.logits, jax.random.key(2),
                            temperature=jnp.ones(4),
                            top_p=jnp.full(4, 1e-6),
                            top_k=jnp.zeros(4, jnp.int32))
        np.testing.assert_array_equal(out, jnp.argmax(self.logits, -1))

    def test_samples_follow_distribution(self):
        # Two-token vocab with known probabilities; check empirical frequency.
        logits = jnp.log(jnp.asarray([[0.8, 0.2]])).repeat(512, axis=0)
        out = sample_tokens(logits, jax.random.key(3),
                            temperature=jnp.ones(512),
                            top_p=jnp.ones(512), top_k=jnp.zeros(512, jnp.int32))
        frac = float(jnp.mean(out == 0))
        assert 0.7 < frac < 0.9

    def test_per_slot_controls_mixed(self):
        # Slot 0 greedy, slot 1 sampled — one call, both semantics.
        logits = jnp.asarray([[1.0, 5.0, 2.0], [1.0, 5.0, 2.0]])
        out = sample_tokens(logits, jax.random.key(4),
                            temperature=jnp.asarray([0.0, 1.0]),
                            top_p=jnp.ones(2), top_k=jnp.zeros(2, jnp.int32))
        assert int(out[0]) == 1
        assert 0 <= int(out[1]) < 3


# Vocabularies of the staged selection's test: qwen2-7b, mistral-7b,
# OLMoE, one that TOP_K_GROUP_WIDTH does not divide (padded last group),
# one small enough that the groups do not reach the threshold, tiny's, and
# (PR 55) the other cells': sdar / keye / qwen3-next, llama-3, lfm2,
# granite / kanana, mixtral (a padded last group again).
TOP_K_VOCABS = (152064, 32768, 50304, 100000, 1000, 512,
                151936, 128256, 65536, 100352, 32000)


def top_k_case(kind: str, vocab: int, cap: int = 64,
               width: int = sampling.TOP_K_GROUP_WIDTH,
               sub: int = sampling.TOP_K_SUBGROUP_WIDTH) -> np.ndarray:
    """[3, vocab] float32 rows (or [2, 3, vocab], [2, 4, vocab]) built to
    stress one property of the selection; seeded by (kind, vocab). `sub` is
    the second stage's width: a group is `width // sub` sub-groups."""
    rng = np.random.default_rng([len(kind), vocab])
    groups = -(-vocab // width)
    if kind == "one_subgroup":  # the window's head fills ONE sub-group,
        x = rng.normal(size=(3, vocab)).astype(np.float32)  # whole
        g = int(rng.integers(groups - 1))
        at = g * width + sub * int(rng.integers(max(width // sub, 1)))
        x[:, at:at + sub] += 100.0
        return x
    if kind == "one_per_subgroup":  # one winner in each of `cap` sub-groups
        x = np.ones((3, vocab), np.float32)  # packed into cap * sub / width
        per = max(width // sub, 1)           # groups; every other maximum
        for row in x:                        # ties
            chosen = rng.choice(groups - 1, replace=False,
                                size=min(-(-cap // per), groups - 1))
            at = (chosen[:, None] * width
                  + np.arange(per)[None, :] * sub).reshape(-1)[:cap]
            row[at + rng.integers(sub, size=at.size)] = 2.0
        return x
    if kind == "ties_straddle_subgroups":  # the window ends INSIDE a run of
        x = rng.normal(size=(3, vocab)).astype(np.float32) * 0.1  # equals
        for row in x:                      # that crosses sub-group borders,
            g = rng.choice(groups - 1, size=2, replace=False)  # with a
            for start in g * width + sub - 5:  # second such run elsewhere
                row[start:start + cap // 2 + sub] = 3.0
            row[rng.choice(vocab, size=cap // 4 + 7, replace=False)] = 5.0
        return x
    if kind == "block_of_four":  # diffusion_candidates' [R, 4, V]
        x = jnp.asarray(rng.normal(size=(2, 4, vocab)), jnp.bfloat16)
        return np.asarray(x.astype(jnp.float32) / 0.7)
    if kind == "gaussian":
        return rng.normal(size=(3, vocab)).astype(np.float32) * 3
    if kind == "bf16_ties":  # what the sampler sees: bf16 logits / 0.7
        x = jnp.asarray(rng.normal(size=(3, vocab)), jnp.bfloat16)
        return np.asarray(x.astype(jnp.float32) / 0.7)
    if kind == "one_group":  # the whole top window inside a single group
        x = rng.normal(size=(3, vocab)).astype(np.float32)
        g = int(rng.integers(groups - 1))
        x[:, g * width:g * width + min(width, cap + 8)] += 100.0
        return x
    if kind == "one_per_group":  # one winner in each of `cap` groups;
        x = np.ones((3, vocab), np.float32)  # every other maximum ties
        for row in x:
            chosen = rng.choice(groups - 1, size=min(cap, groups - 1),
                                replace=False)
            row[chosen * width + rng.integers(width, size=chosen.size)] = 2.0
        return x
    if kind == "ties_across_groups":  # the window's tail is decided by
        x = np.zeros((3, vocab), np.float32)  # index order among equals
        x[:, rng.choice(vocab, size=vocab // 3, replace=False)] = 1.0
        x[:, rng.choice(vocab, size=cap // 2, replace=False)] = 2.0
        return x
    if kind == "constant":
        return np.full((3, vocab), 0.25, np.float32)
    if kind == "neg_inf":  # masked vocabularies: fewer finite than cap
        x = np.full((3, vocab), -np.inf, np.float32)
        x[1] = NEG_INF
        x[:, rng.choice(vocab, size=cap // 2, replace=False)] = \
            rng.normal(size=cap // 2).astype(np.float32)
        x[2] = -np.inf
        return x
    if kind == "batch_seq":  # verify_tokens' [B, S, V]
        return rng.normal(size=(2, 3, vocab)).astype(np.float32)
    raise ValueError(kind)


TOP_K_KINDS = ("gaussian", "bf16_ties", "one_group", "one_per_group",
               "ties_across_groups", "constant", "neg_inf", "batch_seq",
               "one_subgroup", "one_per_subgroup", "ties_straddle_subgroups",
               "block_of_four")


class TestTwoStageTopK:
    """ops/sampling.py _top_k (the selection by stages; two of them until
    PR 55, hence the name) against the single lax.top_k it replaces: values
    AND indices equal, ties included."""

    @pytest.mark.parametrize("vocab", TOP_K_VOCABS)
    @pytest.mark.parametrize("kind", TOP_K_KINDS)
    def test_equals_single_call(self, kind, vocab):
        x = jnp.asarray(top_k_case(kind, vocab))
        want_v, want_i = jax.lax.top_k(x, 64)
        got_v, got_i = jax.jit(sampling._top_k, static_argnums=1)(x, 64)
        np.testing.assert_array_equal(got_v, want_v)
        np.testing.assert_array_equal(got_i, want_i)

    @pytest.mark.parametrize("widths", [(8,), (13,), (13, 5), (8, 3)],
                             ids=lambda w: "x".join(map(str, w)))
    @pytest.mark.parametrize("kind", TOP_K_KINDS)
    def test_padded_last_group_at_any_width(self, kind, widths):
        # The staged form itself, below the route's threshold: 1000 is 125
        # groups of 8 (divides) or 77 of 13 (a padded last group); the 832
        # kept of 13 are 167 sub-groups of 5, the 512 kept of 8 are 171 of
        # 3 (neither divides: a padded last sub-group).
        x = jnp.asarray(top_k_case(kind, 1000, width=widths[0],
                                   sub=widths[-1]))
        want_v, want_i = jax.lax.top_k(x, 64)
        got_v, got_i = sampling._grouped_top_k(x, 64, widths)
        np.testing.assert_array_equal(got_v, want_v)
        np.testing.assert_array_equal(got_i, want_i)

    @pytest.mark.parametrize("vocab,route", [
        (152064, {"top_k": "grouped", "cap": 64, "ranked": 2048,
                  "stages": [{"groups": 1188, "width": 128},
                             {"groups": 256, "width": 32}]}),
        (151936, {"top_k": "grouped", "cap": 64, "ranked": 2048,
                  "stages": [{"groups": 1187, "width": 128},
                             {"groups": 256, "width": 32}]}),
        (32768, {"top_k": "grouped", "cap": 64, "ranked": 2048,
                 "stages": [{"groups": 256, "width": 128},
                            {"groups": 256, "width": 32}]}),
        (32000, {"top_k": "grouped", "cap": 64, "ranked": 2048,
                 "stages": [{"groups": 250, "width": 128},
                            {"groups": 256, "width": 32}]}),
        (100000, {"top_k": "grouped", "cap": 64, "ranked": 2048,
                  "stages": [{"groups": 782, "width": 128},
                             {"groups": 256, "width": 32}]}),
        (1000, {"top_k": "direct"}),
        (512, {"top_k": "direct"}),
        (32, {"top_k": "direct"}),  # cap clamps to the vocabulary
    ])
    def test_route_follows_the_shape(self, vocab, route):
        assert sampling.top_k_route(vocab) == route

    def test_a_stage_is_taken_only_with_enough_groups(self, monkeypatch):
        # two groups a kept entry, stage by stage: 8,192 kept entries are
        # 64 sub-groups of 128 (too few: ranked whole, PR 26's form) or
        # 128 of 64
        monkeypatch.setattr(sampling, "TOP_K_SUBGROUP_WIDTH", 128)
        assert sampling.top_k_route(32768) == {
            "top_k": "grouped", "cap": 64, "ranked": 8192,
            "stages": [{"groups": 256, "width": 128}]}
        monkeypatch.setattr(sampling, "TOP_K_SUBGROUP_WIDTH", 64)
        assert sampling.top_k_route(32768)["stages"][1] == {
            "groups": 128, "width": 64}

    def test_benchmark_presets_take_the_grouped_route(self):
        from symmetry_tpu.models.llama import preset
        for name in ("qwen2-7b", "mistral-7b"):
            assert sampling.top_k_route(
                preset(name).vocab_size)["top_k"] == "grouped"
        assert sampling.top_k_route(
            preset("tiny").vocab_size) == {"top_k": "direct"}

    @pytest.mark.parametrize("vocab", (152064, 32768))
    def test_sample_tokens_identical_to_single_call(self, vocab,
                                                    monkeypatch):
        B = 6
        logits = jnp.asarray(
            np.random.default_rng(vocab).normal(size=(B, vocab)),
            jnp.bfloat16).astype(jnp.float32)
        args = (logits, jax.random.split(jax.random.key(7), B),
                jnp.asarray([0.0, 0.7, 0.7, 1.0, 1.3, 0.7], jnp.float32),
                jnp.asarray([1.0, 1.0, 0.9, 0.5, 1.0, 1.0], jnp.float32),
                jnp.asarray([0, 0, 0, 40, 5, 64], jnp.int32))
        got = sample_tokens(*args)
        monkeypatch.setattr(sampling, "_top_k", jax.lax.top_k)
        np.testing.assert_array_equal(got, sample_tokens(*args))

    @pytest.mark.parametrize("vocab", (151936, 32768))
    def test_diffusion_candidates_identical_to_single_call(self, vocab,
                                                           monkeypatch):
        R, S = 5, 4
        logits = jnp.asarray(
            np.random.default_rng(vocab + 2).normal(size=(R, S, vocab)),
            jnp.bfloat16).astype(jnp.float32)
        args = (logits, jax.random.split(jax.random.key(13), R),
                jnp.asarray([0.0, 0.7, 0.7, 1.0, 1.3], jnp.float32),
                jnp.asarray([1.0, 1.0, 0.9, 0.5, 1.0], jnp.float32),
                jnp.asarray([0, 0, 0, 40, 5], jnp.int32))
        got = sampling.diffusion_candidates(*args)
        monkeypatch.setattr(sampling, "_top_k", jax.lax.top_k)
        want = sampling.diffusion_candidates(*args)
        np.testing.assert_array_equal(got[0], want[0])  # candidates
        np.testing.assert_array_equal(got[1], want[1])  # confidences

    @pytest.mark.parametrize("vocab", (152064, 32768))
    def test_verify_tokens_identical_to_single_call(self, vocab,
                                                    monkeypatch):
        B, k = 4, 3
        rng = np.random.default_rng(vocab + 1)
        logits = jnp.asarray(rng.normal(size=(B, 1 + k, vocab)),
                             jnp.bfloat16).astype(jnp.float32)
        # Proposals the target likes (its own argmax) and ones it does
        # not, so acceptance and both bonus draws are exercised.
        draft = np.array(jnp.argmax(logits[:, :k], -1), np.int32)
        draft[1, 1] = 17
        draft[3, 0] = 23
        args = (logits, jnp.asarray(draft),
                jnp.asarray([3, 3, 2, 3], jnp.int32),
                jax.random.split(jax.random.key(11), B),
                jnp.asarray([0.0, 0.7, 1.0, 0.7], jnp.float32),
                jnp.asarray([1.0, 1.0, 0.9, 1.0], jnp.float32),
                jnp.asarray([0, 0, 0, 8], jnp.int32))
        got = sampling.verify_tokens(*args)
        monkeypatch.setattr(sampling, "_top_k", jax.lax.top_k)
        want = sampling.verify_tokens(*args)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])

    @pytest.mark.parametrize("vocab", (32768, 100000))
    def test_vocab_sharded_logits_under_a_mesh(self, vocab):
        # The tensor-parallel build hands the sampler logits sharded over
        # `model` on the vocabulary axis (parallel/sharding.py): the
        # [.., V] -> [.., G, W] view must stay legal under GSPMD, also
        # where a shard's slice is not a whole number of groups (100000).
        from jax.sharding import NamedSharding, PartitionSpec as P

        from symmetry_tpu.parallel.mesh import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(data=2, model=4))
        x = jnp.asarray(top_k_case("bf16_ties", vocab)[:2])
        want_v, want_i = jax.lax.top_k(x, 64)
        sharded = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
        got_v, got_i = jax.jit(sampling._top_k, static_argnums=1)(sharded, 64)
        np.testing.assert_array_equal(got_v, want_v)
        np.testing.assert_array_equal(got_i, want_i)
