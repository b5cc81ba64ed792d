"""Pallas ragged decode attention vs the XLA reference (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import symmetry_tpu.ops.decode_attention as da
from symmetry_tpu.ops.attention import gqa_attention
from symmetry_tpu.ops.decode_attention import (
    TP_MIN_CAPACITY, decode_attention, geometry)
from symmetry_tpu.ops.quant import quantize_kv


@pytest.fixture()
def tiled(monkeypatch):
    """decode_attention with the router's constants made small, so a
    handful of slots on the CPU walk every tiling the chip sees at 128
    and more: `tiled(lanes=2)` holds a grid step to 2 lanes,
    `tiled(block_rows=512)` a block to 512 rows. Its own jit of its own
    function each time: the constants are read when a shape is first
    traced, and jits of ONE function share their traces."""
    def make(lanes=None, block_rows=None):
        if lanes is not None:
            monkeypatch.setattr(da, "MAX_TILE_LANES", lanes)
        if block_rows is not None:
            monkeypatch.setattr(da, "BLOCK_ROWS", block_rows)

        def fresh(*args, **kw):
            return decode_attention.__wrapped__(*args, **kw)

        return jax.jit(fresh, static_argnames=("window", "interpret"))
    return make


def make_case(B=3, T=384, K=2, G=4, D=128, L=2, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    nq = K * G
    q = jax.random.normal(ks[0], (B, nq, D), dtype)
    k = jax.random.normal(ks[1], (L, B, T, K, D), dtype)
    v = jax.random.normal(ks[2], (L, B, T, K, D), dtype)
    # ragged: slot 0 nearly full, slot 1 short, slot 2 mid
    lengths = jnp.asarray([T - 3, 5, T // 2][:B], jnp.int32)
    return q, k, v, lengths


def reference(q, k_layer, v_layer, lengths, k_scale=None, v_scale=None):
    # decode: q position is the last valid entry; scales are [B, K, T]
    positions = (lengths - 1)[:, None]
    out = gqa_attention(q[:, None], k_layer, v_layer, positions, lengths,
                        k_scale=k_scale, v_scale=v_scale)
    return out[:, 0]


def block_reference(q, k_layer, v_layer, lengths, k_scale=None, v_scale=None):
    """q [B, S, nq, D] at each slot's block boundary `lengths - S`: the
    XLA route under the block mask."""
    S = q.shape[1]
    positions = (lengths - S)[:, None] + jnp.arange(S)[None]
    return gqa_attention(q, k_layer, v_layer, positions, lengths,
                         k_scale=k_scale, v_scale=v_scale, block_len=S)


def rows(x):
    """K/V as the cache holds it (models/llama.py kv_row): heads of 64 in
    pairs, [..., K / 2, 128]; any other head as it is."""
    return x.reshape(*x.shape[:-2], -1, 128) if x.shape[-1] == 64 else x


def to_minor(scale):
    """quantize_kv emits [L, B, T, K]; caches store position-minor [L, B, K, T]."""
    return jnp.moveaxis(scale, -1, -2)


GEOMETRIES = [
    (128, 640, 8, (128, 128)),       # mistral-7b's cell
    (128, 640, 4, (128, 256)),       # qwen2-7b's: 2.5 blocks a slot
    (64, 2048, 2, (64, 1024)),       # head-major lanes: 128 a grid step
    (128, 640, 2, (64, 640)),
    (8, 4096, 8, (8, 128)),
    (8, 4096, 1, (8, 1024)),
    (8, 256, 2, (8, 256)),           # never over the capacity
    (192, 640, 16, (96, 128)),       # never under a lane tile;
    (7, 1024, 8, (7, 128)),          # two grid steps of 96 slots
    (128, 672, 8, None),
    (128, 8192, 8, (16, 128)),       # the work lists stay in SMEM:
    (128, 32768, 8, (4, 128)),       # 1,024 (slot, block) items a list
    (8, 4096, 3, None),              # heads that tile nothing
    (8, 4096, 12, None),
    (8, 4096, 16, (8, 128)),
    # (heads, head size, bytes): heads of 64 lie in pairs of 128 lanes
    (128, 640, (8, 64, 1), (128, 256)),   # lfm2-8b-a1b's cell: qwen2's
    (128, 640, (8, 64, 2), (128, 256)),   # items; bf16 alike
    (128, 640, (16, 64, 1), (128, 128)),
    (8, 4096, (4, 64, 2), (8, 512)),      # two bf16 pairs interleave
    (8, 4096, (4, 64, 1), None),     # two int8 pairs lie head-major
    (128, 640, (7, 64, 1), None),    # an odd count makes no pairs
    (128, 640, (2, 64, 1), None),    # one pair: a row a slab has no form
    (8, 256, (2, 16, 1), None),      # the tiny configurations' head
    (8, 256, (8, 32, 1), None),
]


class TestDecodeAttentionKernel:
    @pytest.mark.parametrize("layer", [0, 1])
    @pytest.mark.parametrize("K", [2, 4, 8])  # blocks of 384, 256, 128
    def test_matches_xla_reference(self, layer, K):
        q, k, v, lengths = make_case(K=K)
        got = decode_attention(q, k, v, jnp.int32(layer), lengths,
                               interpret=True)
        want = reference(q, k[layer], v[layer], lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_quantized_matches_folded_xla(self):
        q, k, v, lengths = make_case(seed=1)
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        ksc, vsc = to_minor(ksc), to_minor(vsc)
        got = decode_attention(q, kq, vq, jnp.int32(1), lengths,
                               k_scale=ksc, v_scale=vsc, interpret=True)
        want = reference(q, kq[1], vq[1], lengths,
                         k_scale=ksc[1], v_scale=vsc[1])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_empty_slot_no_nan(self):
        q, k, v, lengths = make_case()
        lengths = lengths.at[1].set(0)  # empty slot: garbage out, not NaN
        got = decode_attention(q, k, v, jnp.int32(0), lengths,
                               interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        want = reference(q, k[0], v[0], jnp.maximum(lengths, 1))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                                   rtol=2e-5, atol=2e-5)

    def test_single_block(self):
        q, k, v, lengths = make_case(T=128)  # the block is clamped to T
        got = decode_attention(q, k, v, jnp.int32(0), lengths,
                               interpret=True)
        want = reference(q, k[0], v[0], lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("name, capacity, want", [
        ("llama3-8b", 8192, True),
        ("llama3-8b", 640, True),        # the dense cells' capacity
        ("llama3-8b", 2048, True),       # no capacity floor on one chip
        ("tiny", 8192, False),           # D = 16: no lane tile
        ("mistral-7b", 128, True),       # one block
        ("llama3-8b", 4096 + 640, True),
        ("llama3-8b", 672, False),       # not a multiple of 128: no
        ("qwen2-7b", 640, True),         # 2.5 blocks: the last starts early
        ("tiny-mha", 640, False),
        ("mixtral-8x7b", 2048, True),
        ("gemma-2b", 8192, True),        # one KV head of 256
        ("lfm2-8b-a1b", 640, True),      # 8 heads of 64: four pairs a row
        ("llama3.2-1b", 640, True),
    ])
    def test_supports_gate(self, name, capacity, want):
        """geometry() is the one gate: None is the XLA path."""
        from symmetry_tpu.models import preset

        cfg = preset(name)
        assert (geometry(128, capacity, cfg.num_kv_heads,
                         cfg.dim_per_head) is not None) is want

    def test_two_heads_a_chip_are_served_as_they_lie(self):
        """2 KV heads a chip (mistral, llama3 or mixtral over model: 4):
        XLA keeps an int8 cache head-major and each (slot, head) is a
        lane of its own; a bf16 one stays interleaved
        (tests/test_chip_compile.py shows neither view is a copy)."""
        # gemma-7b: 128 positions of 16 bf16 heads of 256 are 1 MB a buffer
        assert geometry(8, 4096, 16, 256, kv_bytes=1) == (8, 128)
        assert geometry(8, 4096, 16, 256, kv_bytes=2) is None
        assert geometry(8, 4096, 2, kv_bytes=1) == (8, 1024)
        assert geometry(8, 4096, 2, kv_bytes=2) == (8, 512)
        assert da._lanes(2, 1) == (2, 1) and da._lanes(2, 2) == (1, 2)

    @pytest.mark.parametrize("batch, capacity, n_kv, want", GEOMETRIES)
    def test_geometry_follows_the_cache_shape(self, batch, capacity, n_kv,
                                              want):
        assert geometry(batch, capacity, *np.atleast_1d(n_kv)) == want

    def test_routes_by_shape_and_mesh(self):
        """One route per observable case, each named in the reply."""
        import dataclasses

        from symmetry_tpu.models import preset
        from symmetry_tpu.models.llama import attention_paths

        class Mesh:  # attention_paths reads nothing but the shape
            shape = {"model": 4}

        def paths(cfg, capacity, mesh=None, batch=128, kv_bytes=1):
            return attention_paths(cfg, capacity, mesh, batch=batch,
                                   kv_bytes=kv_bytes)

        cfg = preset("mistral-7b")
        assert paths(cfg, 640) == {
            "prefill": "pallas-interpret", "decode": "pallas-interpret",
            "decode_slot_tile": 128, "decode_block_t": 128,
            "kv_append": "step"}
        assert paths(preset("qwen2-7b"), 640)["decode_block_t"] == 256
        assert paths(cfg, 672) == {
            "prefill": "pallas-interpret", "decode": "xla"}
        # a sharded trunk keeps the XLA path under TP_MIN_CAPACITY and
        # the per-shard kernel from there up, 2 KV heads a chip or 4
        assert paths(cfg, 2048, Mesh(), batch=64) == {
            "prefill": "pallas-interpret", "decode": "xla"}
        assert paths(cfg, TP_MIN_CAPACITY, Mesh(), batch=8) == {
            "prefill": "pallas-interpret", "decode": "pallas-interpret",
            "decode_slot_tile": 8, "decode_block_t": 1024}

        class Mesh2:
            shape = {"model": 2}

        assert paths(cfg, 2048, Mesh2())["decode"] == "xla"
        assert paths(cfg, TP_MIN_CAPACITY, Mesh2())["decode"] == \
            "pallas-interpret"
        sliding = dataclasses.replace(cfg, sliding_window=4096)
        assert paths(sliding, 8192)["decode"] == "pallas-interpret"
        # one chip, 2 KV heads or 1 at a long capacity: the kernel too
        for name in ("gemma-2b", "tiny-qwen"):
            small = dataclasses.replace(preset(name), head_dim=128)
            assert paths(small, 4096, batch=8)["decode"] == \
                "pallas-interpret"
        # heads of 64 in interleaved pairs: the kernel, at qwen2's tiles
        lfm2 = preset("lfm2-8b-a1b")
        assert paths(lfm2, 640) == {
            "prefill": "pallas-interpret", "decode": "pallas-interpret",
            "decode_slot_tile": 128, "decode_block_t": 256}
        assert paths(lfm2, 640, kv_bytes=2)["decode_block_t"] == 256
        odd = paths(dataclasses.replace(lfm2, num_kv_heads=4), 640)
        assert odd["decode"] == "xla" and "head of 64" in odd["decode_why"]
        # no selection over pairs: the third plane is a row a whole head
        assert da.keep_supported(8, 1, True)
        assert not da.keep_supported(8, 1, True, head_dim=64)


LENGTHS_640 = [0, 1, 127, 128, 129, 640]


def case_640(K, G, quantized, dtype=jnp.bfloat16, seed=0, B=6, T=640,
             D=128, S=None):
    """One layer pair of a 6-slot x 640 cache at the dense cells' head
    shapes, lengths mixed in the batch; scales position-minor. K and V as
    [.., K, D]: `rows` makes the cache's form of a head of 64. `S`: a
    block of that many query positions a slot."""
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(
        ks[0], (B, K * G, D) if S is None else (B, S, K * G, D), dtype)
    k = jax.random.normal(ks[1], (2, B, T, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (2, B, T, K, D), jnp.float32)
    if not quantized:
        return q, k.astype(dtype), v.astype(dtype), ()
    kq, ksc = quantize_kv(k)
    vq, vsc = quantize_kv(v)
    return q, kq, vq, (to_minor(ksc), to_minor(vsc))


class TestCellShapes:
    """Capacity 640 with mistral-7b's (8 KV x 4), qwen2-7b's (4 KV x 7),
    lfm2-8b-a1b's (8 KV of 64 x 4), nemotron-3-nano-30b-a3b's (2 KV x 16)
    and qwen3-next-80b-a3b's (2 KV of 256 x 8) heads: the shapes the
    benchmark's one-chip cells decode at."""

    @pytest.mark.parametrize("slot_tile", [1, 2, 3, 6])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("K, G, D", [(8, 4, 128), (4, 7, 128),
                                         (8, 4, 64), (16, 2, 64),
                                         # 2 heads: as int8 a lane a (slot,
                                         # head) of the head-major leaf,
                                         # 16 query rows a lane (nemotron-
                                         # 3-nano-30b-a3b) or 8 of 256
                                         # (qwen3-next-80b-a3b); as bf16
                                         # interleaved rows of one lane
                                         (2, 16, 128), (2, 8, 256)])
    def test_matches_gqa_attention(self, tiled, K, G, D, quantized,
                                   slot_tile):
        q, k, v, scales = case_640(K, G, quantized, D=D)
        lengths = jnp.asarray(LENGTHS_640, jnp.int32)
        assert geometry(6, 640, K, D)[0] == 6
        got = tiled(lanes=slot_tile)(q, rows(k), rows(v), jnp.int32(1),
                                     lengths, *scales, interpret=True)
        assert got.shape == q.shape and got.dtype == q.dtype
        assert np.isfinite(np.asarray(got, np.float32)).all()
        want = reference(q, k[1], v[1], jnp.maximum(lengths, 1),
                         *(s[1] for s in scales))
        live = np.asarray(lengths) > 0   # an empty slot's row is garbage
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(want, np.float32)[live], rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("K, G, D, S", [
        (8, 4, 128, None), (4, 7, 128, None), (2, 4, 128, None),
        (8, 4, 64, None), (4, 8, 128, 4), (8, 4, 128, 4)])
    def test_a_slot_does_not_see_its_neighbours(self, tiled, K, G, D, S,
                                                quantized):
        """check_correct compares two identical greedy requests that land
        in different slots beside different neighbours: a slot's result
        must be bit-identical whatever the others' lengths, wherever the
        tile boundaries fall — one query a slot or a block of four."""
        q, k, v, scales = case_640(K, G, quantized, seed=2, D=D, S=S)
        k, v = rows(k), rows(v)
        mine, slot = 300, 2
        outs = []
        for others, slot_tile in (([640, 1, 0, 129, 513], 6),
                                  ([0, 0, 0, 0, 0], 6),
                                  ([128, 640, 640, 640, 127], 3),
                                  ([5, 257, 384, 1, 640], 1)):
            lengths = others[:slot] + [mine] + others[slot:]
            got = tiled(lanes=slot_tile * (S or 1))(
                q, k, v, jnp.int32(0), jnp.asarray(lengths, jnp.int32),
                *scales, interpret=True)
            outs.append(np.asarray(got[slot], np.float32))
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    @pytest.mark.parametrize("K, G, quantized", [
        (2, 4, True),    # head-major lanes, as XLA lays 2 int8 heads out
        (1, 8, True),    # MQA (gemma-2b)
        (2, 4, False),   # 2 bf16 heads stay interleaved: blocks of 512
        (1, 4, False),
    ])
    def test_few_heads_at_a_long_capacity(self, tiled, K, G, quantized):
        """1 or 2 KV heads a chip (a trunk sharded over model: 4, MQA) at
        a capacity of several 1,024-position blocks: lengths mixed, the
        batch split over grid steps."""
        q, k, v, scales = case_640(K, G, quantized, seed=6, T=2304)
        lengths = jnp.asarray([0, 1, 1023, 1025, 2304, 2049], jnp.int32)
        got = tiled(lanes=4)(q, k, v, jnp.int32(1), lengths, *scales,
                             interpret=True)
        assert np.isfinite(np.asarray(got, np.float32)).all()
        want = reference(q, k[1], v[1], jnp.maximum(lengths, 1),
                         *(s[1] for s in scales))
        np.testing.assert_allclose(np.asarray(got, np.float32)[1:],
                                   np.asarray(want, np.float32)[1:],
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("K, G, D", [(4, 7, 128), (8, 4, 64)])
    @pytest.mark.parametrize("block_t", [128, 256, 384, 640])
    def test_every_block_size_reads_the_same(self, tiled, block_t, K, G, D):
        """640 = 2.5 x 256: the last block starts early and masks what the
        one before it covered; whatever the block, the same result (four
        rows a position either way: 4 heads of 128, 4 pairs of 64)."""
        q, k, v, scales = case_640(K, G, True, seed=5, D=D)
        lengths = jnp.asarray([640, 513, 512, 257, 256, 3], jnp.int32)
        got = tiled(block_rows=4 * block_t)(
            q, rows(k), rows(v), jnp.int32(1), lengths, *scales, window=300,
            interpret=True)
        want = gqa_attention(q[:, None], k[1], v[1], (lengths - 1)[:, None],
                             lengths, sliding_window=300,
                             k_scale=scales[0][1], v_scale=scales[1][1])[:, 0]
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_blocks_of_two_lane_tiles(self, tiled):
        """Blocks over 128 positions: the scale planes are spread one
        128-position chunk at a time."""
        q, k, v, scales = case_640(8, 4, True, seed=4, B=3, T=512)
        lengths = jnp.asarray([512, 257, 3], jnp.int32)
        got = tiled(block_rows=8 * 256)(q, k, v, jnp.int32(0), lengths,
                                        *scales, interpret=True)
        want = reference(q, k[0], v[0], lengths, *(s[0] for s in scales))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["f32", "int8"])
    def test_a_head_never_reads_its_pairs_sibling(self, quantized):
        """Heads 2p and 2p + 1 share a 128-lane row: with the odd heads'
        V a thousand times the even heads' (and K unrelated), every head's
        result is still its own to float32 rounding — the sibling's half
        of the output product is dropped, its half of the contraction
        meets the query's zeros."""
        q, k, v, _ = case_640(8, 4, False, jnp.float32, seed=7, D=64)
        v = v * jnp.where(jnp.arange(8) % 2, 1000.0, 1.0)[:, None]
        scales = ()
        if quantized:
            (k, ksc), (v, vsc) = quantize_kv(k), quantize_kv(v)
            scales = (to_minor(ksc), to_minor(vsc))
        lengths = jnp.asarray([257, 1, 127, 128, 129, 640], jnp.int32)
        got = decode_attention(q, rows(k), rows(v), jnp.int32(1), lengths,
                               *scales, interpret=True)
        want = reference(q, k[1], v[1], lengths, *(s[1] for s in scales))
        got, want = (np.asarray(x).reshape(6, 8, 4, 64) for x in (got, want))
        assert np.abs(want[:, 1::2]).mean() > 50 * np.abs(want[:, ::2]).mean()
        np.testing.assert_allclose(got[:, ::2], want[:, ::2],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got[:, 1::2], want[:, 1::2],
                                   rtol=2e-5, atol=2e-2)

    def test_heads_that_tile_no_sublanes_are_refused(self):
        assert geometry(2, 128, 3) is None
        q, k, v, _ = case_640(3, 2, False, B=2, T=128)
        with pytest.raises(ValueError, match="geometry"):
            decode_attention(q, k, v, jnp.int32(0),
                             jnp.asarray([5, 9], jnp.int32), interpret=True)

    def test_window_at_640(self):
        q, k, v, scales = case_640(8, 4, True, seed=3)
        lengths = jnp.asarray([640, 400, 257, 256, 130, 7], jnp.int32)
        got = decode_attention(q, k, v, jnp.int32(1), lengths, *scales,
                               window=256, interpret=True)
        want = gqa_attention(q[:, None], k[1], v[1], (lengths - 1)[:, None],
                             lengths, sliding_window=256,
                             k_scale=scales[0][1], v_scale=scales[1][1])[:, 0]
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)


# block boundaries a slot (the length is the block's END, base + 4): an
# empty slot's first block, a block of 256 just filled, a capacity's last
BASES_640 = [0, 4, 124, 252, 256, 636]


class TestBlockOfQueries:
    """S query positions a slot that share the slot's keys — a
    block-diffusion forward over the cache from a block boundary — against
    `gqa_attention(block_len=S)`: sdar-30b-a3b-chat's heads (4 KV x 8) and
    mistral's (8 KV x 4) at the cells' capacity, which the block of 256
    does not divide."""

    @pytest.mark.parametrize("slot_tile", [1, 2, 6])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("K, G", [(4, 8), (8, 4)])
    def test_matches_gqa_attention_under_the_block_mask(
            self, tiled, K, G, quantized, slot_tile):
        q, k, v, scales = case_640(K, G, quantized, seed=8, S=4)
        lengths = jnp.asarray(BASES_640, jnp.int32) + 4
        got = tiled(lanes=4 * slot_tile)(q, k, v, jnp.int32(1), lengths,
                                         *scales, interpret=True)
        assert got.shape == q.shape and got.dtype == q.dtype
        want = block_reference(q, k[1], v[1], lengths,
                               *(s[1] for s in scales))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("K", [4, 8])
    def test_float32_to_rounding_with_an_empty_slot(self, K):
        """float32 end to end: the same mathematics to 2e-5. A slot with
        nothing valid (length 0: a parked lane) is garbage by contract and
        finite by construction; every row of a live slot is written."""
        q, k, v, _ = case_640(K, 32 // K, False, jnp.float32, seed=9, S=4)
        lengths = jnp.asarray([0, 4, 128, 256, 640, 0], jnp.int32)
        got = decode_attention(q, k, v, jnp.int32(0), lengths,
                               interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        want = block_reference(q, k[0], v[0], jnp.maximum(lengths, 4))
        np.testing.assert_allclose(np.asarray(got)[1:5],
                                   np.asarray(want)[1:5],
                                   rtol=2e-5, atol=2e-5)

    def test_one_layers_scale_planes(self):
        """The planes may come as the caller's slice of `layer`
        ([1, B, K, T]: models/llama.py hands a block's forwards that):
        the same bits as reading them out of the whole arrays."""
        q, k, v, scales = case_640(4, 8, True, seed=10, S=4)
        lengths = jnp.asarray(BASES_640, jnp.int32) + 4
        whole = decode_attention(q, k, v, jnp.int32(1), lengths, *scales,
                                 interpret=True)
        sliced = decode_attention(q, k, v, jnp.int32(1), lengths,
                                  *(s[1:2] for s in scales), interpret=True)
        np.testing.assert_array_equal(np.asarray(whole, np.float32),
                                      np.asarray(sliced, np.float32))

    def test_one_position_is_the_one_query_form(self):
        """S = 1 through the four-axis q is today's program's result."""
        q, k, v, scales = case_640(4, 7, True, seed=11)
        lengths = jnp.asarray(LENGTHS_640, jnp.int32)
        one = decode_attention(q, k, v, jnp.int32(0), lengths, *scales,
                               interpret=True)
        four_axes = decode_attention(q[:, None], k, v, jnp.int32(0), lengths,
                                     *scales, interpret=True)
        np.testing.assert_array_equal(np.asarray(one, np.float32),
                                      np.asarray(four_axes[:, 0], np.float32))

    def test_a_mask_a_position_is_refused(self):
        """No causal multi-position kernel: a window or a selection tells
        a slot's rows apart, and keeps `gqa_attention`."""
        q, k, v, scales = case_640(8, 4, True, S=4)
        lengths = jnp.full((6,), 64, jnp.int32)
        with pytest.raises(ValueError, match="a mask a position"):
            decode_attention(q, k, v, jnp.int32(0), lengths, *scales,
                             window=32, interpret=True)
        with pytest.raises(ValueError, match="a mask a position"):
            decode_attention(q, k, v, jnp.int32(0), lengths, *scales,
                             jnp.ones((6, 640), bool), interpret=True)

    @pytest.mark.parametrize("batch, capacity, n_kv, want", [
        (128, 640, 4, (32, 256)),        # sdar-30b-a3b-chat's cell
        (128, 640, 8, (32, 128)),
        (8, 4096, 4, (8, 256)),          # the smoke's shape
        (128, 8192, 8, (16, 128)),       # the work lists bind first
        (64, 2048, 2, (16, 1024)),       # head-major lanes: 32 a grid step
        (6, 640, 4, (6, 256)),
        (128, 672, 4, None),
    ])
    def test_geometry_of_four_queries(self, batch, capacity, n_kv, want):
        assert geometry(batch, capacity, n_kv, queries=4) == want


def test_one_query_takes_todays_tiles_and_four_fit_the_vmem():
    """`queries=1` is the default at every shape the file lists, and the q
    and output tiles of four queries (double-buffered, beside the WAYS x
    NBUF item buffers of K and V) stay inside the 16 MB a kernel may take
    at every one of them that has a geometry — 32 query heads a position,
    the served models' most, in bfloat16."""
    for batch, capacity, n_kv, want in GEOMETRIES:
        shape = tuple(np.atleast_1d(n_kv))
        assert geometry(batch, capacity, *shape, queries=1) == want
        tiles = geometry(batch, capacity, *shape, queries=4)
        assert (tiles is None) == (want is None)
        if tiles is None:
            continue
        heads, head_dim, kv_bytes = (shape + (128, 1))[:3]
        assert tiles[1] == want[1] and want[0] % tiles[0] == 0
        tile_bytes = tiles[0] * 4 * 32 * max(head_dim, 128) * 2
        items = 2 * da.WAYS * da.NBUF * min(
            da.MAX_ITEM_BYTES,
            tiles[1] * heads * max(head_dim, 128) * kv_bytes)
        assert 2 * 2 * tile_bytes + items <= 2**24, (batch, capacity, n_kv)


def plain_layout(x, spread):
    """`_lay_out` as `jnp` says it: every position's value once a KV head."""
    return jnp.repeat(x, spread.shape[1] // spread.shape[0], axis=-1)


# id -> (KV heads, groups, head size, queries a slot, selection, lengths)
LAYOUTS = {
    "8 heads": (8, 4, 128, None, False, LENGTHS_640),
    "4 heads": (4, 7, 128, None, False, LENGTHS_640),
    "pairs of 64": (8, 4, 64, None, False, LENGTHS_640),
    "keep plane": (4, 8, 128, None, True, [640, 0, 257, 129, 300, 1]),
    "four queries": (4, 8, 128, 4, False, [b + 4 for b in BASES_640]),
    # a window layer's ring once it is full: kv_length = min(length, T)
    "full ring": (4, 7, 128, None, False, [640] * 6),
    # the ways' lists of unequal lengths: five items against one or two
    "one long slot": (8, 4, 128, None, False, [640, 0, 0, 0, 0, 0]),
}


class TestScalePlanesOfAStep:
    """The scale planes of the WAYS items a loop step computes go through
    ONE `spread` product a chunk (`_lay_out` over the ways' stacked rows):
    the result is, bit for bit, that of one way with the planes laid out by
    `jnp.repeat` — three bf16 terms and a 0/1 matrix lose nothing, and a
    row of the stacked operand reaches its own way alone — and, to
    rounding, `gqa_attention`'s."""

    @pytest.mark.parametrize("ways, lanes", [(1, 1), (2, 2), (4, 6)])
    @pytest.mark.parametrize("case", list(LAYOUTS))
    def test_one_product_a_chunk_is_the_plain_layout(self, tiled, monkeypatch,
                                                     case, ways, lanes):
        K, G, D, S, selected, lengths = LAYOUTS[case]
        q, k, v, scales = case_640(K, G, True, seed=12, D=D, S=S)
        keep = (jax.random.bernoulli(jax.random.key(13), 0.5, (6, 640))
                .at[:, 0].set(True) if selected else None)
        if case == "one long slot":
            # what a way that has run out still holds — here an empty
            # slot's planes, not even finite — shares the product with the
            # long slot's rows and must not reach them
            scales = tuple(s.at[:, 1:].set(jnp.nan) for s in scales)
        args = (rows(k), rows(v), jnp.int32(1),
                jnp.asarray(lengths, jnp.int32), *scales, keep)
        assert min(da.WAYS, lanes) == ways
        got = tiled(lanes=lanes * (S or 1))(q, *args, interpret=True)
        monkeypatch.setattr(da, "_lay_out", plain_layout)
        want = tiled(lanes=S or 1)(q, *args, interpret=True)
        live = np.asarray(lengths) > 0   # "one long slot": slot 0 alone
        assert np.isfinite(np.asarray(got, np.float32)[live]).all()
        np.testing.assert_array_equal(np.asarray(got, np.float32)[live],
                                      np.asarray(want, np.float32)[live])
        # both sides slice the product's rows alike, so hold them to
        # `gqa_attention` too: a plane or a head taken for another's is
        # the same bits at every `ways`, and not the XLA form's numbers
        kv_length = jnp.asarray(lengths, jnp.int32)
        positions = kv_length[:, None] - (S or 1) + jnp.arange(S or 1)[None]
        ref = gqa_attention(
            q if S else q[:, None], k[1], v[1], positions, kv_length,
            k_scale=scales[0][1], v_scale=scales[1][1], block_len=S,
            keep=keep[:, None] if selected else None).reshape(q.shape)
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(ref, np.float32)[live], rtol=2e-2, atol=2e-2)


# (query heads, KV heads, head size): 2 heads of 128; 8 of 64, which the
# cache holds in pairs (write_kv folds, the XLA path unfolds, the kernel
# reads the pairs as they lie)
HEADS = [(4, 2, 128), (8, 8, 64)]


class TestModelIntegration:
    @pytest.mark.parametrize("heads", HEADS, ids=str)
    def test_forward_decode_uses_kernel_and_matches(self, monkeypatch,
                                                    heads):
        """Full model decode through the kernel (capacity 128: routed by
        shape, interpreted here) must reproduce the XLA path
        token-for-token."""
        import symmetry_tpu.ops.decode_attention as da
        from symmetry_tpu.models import ModelConfig, forward, init_cache, init_params
        from symmetry_tpu.models.llama import attention_paths

        nq, nkv, D = heads
        cfg = ModelConfig(vocab_size=256, hidden_size=128, num_layers=2,
                          num_heads=nq, num_kv_heads=nkv,
                          intermediate_size=256,
                          head_dim=D, rope_theta=10000.0, max_position=256)
        assert attention_paths(cfg, 128, batch=2, kv_bytes=4)[
            "decode"] == "pallas-interpret"
        assert init_cache(cfg, 2, 128, jnp.float32).k.shape[3:] == (
            nkv * D // 128, 128)
        params = init_params(cfg, jax.random.key(0), jnp.float32)
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, 256, (2, 8)), jnp.int32)

        def decode(force_kernel):
            if not force_kernel:
                monkeypatch.setattr(da, "geometry", lambda *a: None)
            cache = init_cache(cfg, 2, 128, jnp.float32)
            logits, cache = forward(params, cfg, prompt, cache)
            last = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            toks = [np.asarray(last)]
            for _ in range(5):
                logits, cache = forward(params, cfg, last[:, None], cache)
                last = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                toks.append(np.asarray(last))
            return np.stack(toks)

        np.testing.assert_array_equal(decode(True), decode(False))

    @pytest.mark.parametrize("heads", HEADS, ids=str)
    def test_forward_decode_kernel_quantized_cache(self, monkeypatch, heads):
        import symmetry_tpu.ops.decode_attention as da
        from symmetry_tpu.models import ModelConfig, forward, init_cache, init_params

        nq, nkv, D = heads
        cfg = ModelConfig(vocab_size=256, hidden_size=128, num_layers=2,
                          num_heads=nq, num_kv_heads=nkv,
                          intermediate_size=256,
                          head_dim=D, rope_theta=10000.0, max_position=256)
        params = init_params(cfg, jax.random.key(1), jnp.float32)
        prompt = jnp.asarray(
            np.random.default_rng(1).integers(0, 256, (1, 6)), jnp.int32)

        def decode(force_kernel):
            if not force_kernel:
                monkeypatch.setattr(da, "geometry", lambda *a: None)
            cache = init_cache(cfg, 1, 128, jnp.float32, quantized=True)
            logits, cache = forward(params, cfg, prompt, cache)
            last = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            outs = [np.asarray(logits[:, -1])]
            for _ in range(3):
                logits, cache = forward(params, cfg, last[:, None], cache)
                last = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                outs.append(np.asarray(logits[:, 0]))
            return np.concatenate(outs)

        np.testing.assert_allclose(decode(True), decode(False),
                                   rtol=2e-4, atol=2e-4)


    @pytest.mark.parametrize("heads, head_major", [
        ((4, 2, 128), True),    # 2 int8 heads of a lane tile: head-major
        ((8, 2, 256), True),    # ... of two (qwen3-next-80b-a3b's)
        ((8, 4, 128), False),   # 4 heads are rows of one tile as written
    ], ids=str)
    def test_engine_decodes_an_int8_cache_as_the_xla_route_does(
            self, monkeypatch, heads, head_major):
        """The engine's own programs over an int8 cache — prefill into a
        scratch, insert into a slot, two decode blocks through the
        interpreted kernel — yield the XLA route's tokens. Two int8 heads
        of whole lane tiles are the leaf that lies head-major on one chip:
        every program writes it by head (models/llama.py write_kv), the
        insert places the scratch's rows as they are, and the reply says
        so (`kv_layout`) — for that leaf alone."""
        from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
        from symmetry_tpu.engine.tokenizer import ByteTokenizer
        from symmetry_tpu.models import ModelConfig, init_params

        nq, nkv, D = heads
        cfg = ModelConfig(vocab_size=512, hidden_size=128, num_layers=2,
                          num_heads=nq, num_kv_heads=nkv,
                          intermediate_size=128, head_dim=D,
                          rope_theta=10000.0, max_position=256)
        params = init_params(cfg, jax.random.key(2), jnp.float32)

        def served():
            engine = InferenceEngine(
                cfg, params, ByteTokenizer(), max_slots=4, max_seq_len=128,
                prefill_buckets=(16,), cache_dtype=jnp.float32,
                kv_quant=True, decode_block=4, prefill_chunk=None)
            assert engine.state.cache.k.dtype == jnp.int8
            first = [engine.prefill_and_insert(slot, list(prompt),
                                               SamplingParams())
                     for slot, prompt in ((2, b"a first prompt"),
                                          (0, b"another"))]
            blocks = np.concatenate([engine.decode_steps()
                                     for _ in range(2)])
            return engine.attention_paths(), first, blocks[:, [2, 0]]

        paths, first, toks = served()
        assert paths["decode"] == "pallas-interpret"
        assert ("kv_layout" in paths) is head_major
        monkeypatch.setattr(da, "geometry", lambda *a: None)
        xla_paths, xla_first, xla_toks = served()
        assert xla_paths["decode"] == "xla" and "kv_layout" not in xla_paths
        assert first == xla_first
        np.testing.assert_array_equal(toks, xla_toks)


class TestSlidingWindow:
    """window= bounds the per-slot block range AND the mask — must match
    gqa_attention's sliding_window semantics exactly."""

    @pytest.mark.parametrize("window", [8, 24, 48, 200])
    @pytest.mark.parametrize("K", [2, 8])  # one block of 384, three of 128
    def test_matches_xla_sliding_reference(self, window, K):
        q, k, v, lengths = make_case(seed=3, K=K)
        got = decode_attention(q, k, v, jnp.int32(0), lengths,
                               window=window, interpret=True)
        positions = (lengths - 1)[:, None]
        want = gqa_attention(q[:, None], k[0], v[0], positions, lengths,
                             sliding_window=window)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_quantized_sliding(self):
        # 2 int8 KV heads: head-major lanes of 1,024-position blocks, the
        # window's floor two blocks up for the longest slot
        q, k, v, lengths = make_case(seed=4, T=3200)
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        ksc, vsc = to_minor(ksc), to_minor(vsc)
        got = decode_attention(q, kq, vq, jnp.int32(1), lengths,
                               k_scale=ksc, v_scale=vsc,
                               window=1100, interpret=True)
        positions = (lengths - 1)[:, None]
        want = gqa_attention(q[:, None], kq[1], vq[1], positions, lengths,
                             sliding_window=1100,
                             k_scale=ksc[1], v_scale=vsc[1])[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_window_larger_than_length_is_full_attention(self):
        q, k, v, lengths = make_case(seed=5)
        got = decode_attention(q, k, v, jnp.int32(0), lengths,
                               window=10_000, interpret=True)
        want = reference(q, k[0], v[0], lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# a slot of length 1 (no cached row), 2, a block's edge at 8 heads (129:
# the cached rows are one block of 128, the row's item is dropped) and at 4
# (257), mid-block, and a full slot — lengths WITH the own row
OWN_ROW_LENGTHS = [1, 2, 129, 257, 300, 640]


def own_rows(k, v, scales, layer, lengths):
    """Each slot's row at `lengths - 1` of `layer` as
    models/llama.py _attention hands it to `decode_attention(own=)`: K and
    V as the cache holds them ([B, K, D]; heads of 64 in their pairs), and
    of an int8 cache the scales a head [B, K]."""
    b = jnp.arange(lengths.shape[0])
    own = [rows(x)[layer, b, lengths - 1] for x in (k, v)]
    return tuple(own + [s[layer, b, :, lengths - 1] for s in scales]
                 + [None] * (2 - len(scales)))


def over_both(q, k, v, scales, layer, lengths, **kw):
    """`decode_attention` over the rows below each slot's position with the
    position's own row as an operand (an int8 cache's planes come back
    beside the result)."""
    got = decode_attention(
        q, rows(k), rows(v), jnp.int32(layer), lengths - 1, *scales,
        own=own_rows(k, v, scales, layer, lengths), interpret=True, **kw)
    if not scales:
        return got
    for plane, same in zip(got[1:], scales):
        np.testing.assert_array_equal(np.asarray(plane), np.asarray(same))
    return got[0]


class TestOwnRow:
    """A decode step of the homogeneous trunk (PR 66) appends to the cache
    behind its layer loop, so inside it the kernel walks the rows BELOW each
    slot's position and takes the position's own row — which the cache does
    not hold yet — as an operand: where the slot's softmax starts."""

    @pytest.mark.parametrize("slot_tile", [2, 6])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("K, G, D", [(8, 4, 128), (4, 7, 128),
                                         (8, 4, 64), (16, 2, 64),
                                         (2, 16, 128)])
    def test_cached_rows_and_the_own_row_are_the_kernel_over_both(
            self, tiled, K, G, D, quantized, slot_tile):
        """The kernel over `length - 1` rows + the own row == the kernel
        over a cache that holds the row (what every step ran before), at
        the dense cells' heads, lfm2's pairs of 64 and 2 heads (head-major
        lanes as int8): a slot of length 1 reads the own row's V alone;
        129 / 257 are a block's edge, where the cached rows need an item
        less."""
        q, k, v, scales = case_640(K, G, quantized, D=D, seed=5)
        lengths = jnp.asarray(OWN_ROW_LENGTHS, jnp.int32)
        want = decode_attention(q, rows(k), rows(v), jnp.int32(1), lengths,
                                *scales, interpret=True)
        fresh = tiled(lanes=slot_tile)
        got = fresh(q, rows(k), rows(v), jnp.int32(1), lengths - 1, *scales,
                    own=own_rows(k, v, scales, 1, lengths), interpret=True)
        got = got[0] if quantized else got
        assert got.shape == q.shape and got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)
        # length 1: softmax over one key is that key's V, dequantised
        alone = v[1, 0, 0].astype(jnp.float32)
        if quantized:
            alone = alone * scales[1][1, 0, :, 0][:, None]
        np.testing.assert_allclose(
            np.asarray(got[0], np.float32).reshape(K, G, D),
            np.broadcast_to(np.asarray(alone)[:, None], (K, G, D)),
            rtol=2e-2, atol=2e-2)

    def test_the_own_row_is_the_online_softmaxs_first_step_in_float32(self):
        """float32 end to end: the result is the kernel's over a cache that
        holds the row to 2e-5 — one sum a query row in another order."""
        q, k, v, _ = make_case(B=3, T=384, K=4, G=2, seed=7)
        lengths = jnp.asarray([1, 257, 384], jnp.int32)
        want = decode_attention(q, k, v, jnp.int32(0), lengths,
                                interpret=True)
        got = over_both(q, k, v, (), 0, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [1, 2, 128, 200])
    def test_a_model_wide_window_counts_the_own_row(self, window):
        """`window` is the model's, the query's own position in it: over
        the cached rows the floor is the same — block edges (length -
        window = 128) and a window of the own row alone included."""
        q, k, v, scales = case_640(8, 4, True, seed=9)
        lengths = jnp.asarray([1, 100, 128, 129, 256, 640], jnp.int32)
        want = decode_attention(q, k, v, jnp.int32(0), lengths, *scales,
                                window=window, interpret=True)
        got = over_both(q, k, v, scales, 0, lengths, window=window)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_a_slots_result_does_not_see_its_neighbours(self):
        """check_correct's rule holds with the own row: bit-identical
        whatever the other slots hold."""
        q, k, v, scales = case_640(4, 7, True, seed=11)
        outs = []
        for others in ([640, 1, 129, 513, 2], [1, 1, 1, 1, 1]):
            lengths = jnp.asarray(others[:2] + [300] + others[2:],
                                  jnp.int32)
            outs.append(np.asarray(over_both(q, k, v, scales, 1, lengths)[2],
                                   np.float32))
        np.testing.assert_array_equal(*outs)

    def test_a_caller_without_an_own_row_lowers_as_before(self):
        """Every other program's call (each S > 1, the hybrid trunk's
        layers, a sharded trunk) has one result and nothing aliased; a
        block of queries, and a selection, cannot take an own row."""
        q, k, v, scales = case_640(8, 4, True)
        lengths = jnp.asarray(LENGTHS_640, jnp.int32)
        args = (q, k, v, jnp.int32(0), lengths, *scales)
        own = own_rows(k, v, scales, 0, jnp.maximum(lengths, 1))

        def lowered(**kw):
            return jax.jit(lambda *a: decode_attention.__wrapped__(
                *a, interpret=True, **kw)).lower(*args).as_text()

        plain = lowered()
        assert plain == lowered(own=None) and plain != lowered(own=own)
        assert isinstance(decode_attention(*args, interpret=True),
                          jax.Array)
        with pytest.raises(ValueError, match="mask a position"):
            decode_attention(jnp.stack([q] * 4, axis=1), *args[1:],
                             own=own, interpret=True)
        with pytest.raises(ValueError, match="no selection"):
            decode_attention(*args, jnp.ones((6, 640), bool), own,
                             interpret=True)
