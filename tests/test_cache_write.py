"""The KV-cache write every program runs (models/llama.py write_kv) against
a NumPy reference: the row-window scatter of the one-chip trunk and the
head-indexed scatter of a sharded one, bf16 and int8 caches, decode (S = 1)
and prefill (S > 1) shapes, ragged positions across slots — over a narrow
leaf (heads of 8) and over the leaf of 2 heads of a whole lane tile (128),
which as int8 lies head-major on one chip and takes the head-indexed scatter
whatever the caller asks (`kv_head_major`).

The inputs are built so the int8 quantiser has one right answer whatever
the compiler does with its division: every (token, head) vector is a vector
of integers in [-127, 127] that reaches ±127, times a power of two — the
payload must be those integers and the scale that power of two.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.models.llama import KVCache, kv_head_major, write_kv

L, B, T, K = 3, 4, 16, 2
WIDTHS = pytest.mark.parametrize("D", [8, 128], ids=["heads-of-8",
                                                     "heads-of-128"])
# slot b's first position: ragged, and slot 3 ends on the last cache row
STARTS = {1: (0, 7, 3, T - 1), 5: (0, 7, 3, T - 5)}


def dirty_cache(quantized: bool, seed: int = 0, D: int = 8) -> KVCache:
    """A cache full of recognisable garbage, so an untouched entry that
    changed — or a touched one that did not — shows."""
    rng = np.random.default_rng(seed)
    lengths = jnp.zeros((B,), jnp.int32)
    if not quantized:
        k, v = (jnp.asarray(rng.normal(size=(L, B, T, K, D)), jnp.bfloat16)
                for _ in range(2))
        return KVCache(k=k, v=v, lengths=lengths)
    k, v = (jnp.asarray(rng.integers(-127, 128, (L, B, T, K, D)), jnp.int8)
            for _ in range(2))
    ks, vs = (jnp.asarray(rng.uniform(1.0, 2.0, (L, B, K, T)), jnp.float32)
              for _ in range(2))
    return KVCache(k=k, v=v, lengths=lengths, k_scale=ks, v_scale=vs)


def new_rows(S: int, seed: int, D: int = 8):
    """(values [B, S, K, D] f32, integers [B, S, K, D], scales [B, S, K]):
    values = integers * scale, exactly, in bf16 as well as f32."""
    rng = np.random.default_rng(seed)
    ints = rng.integers(-126, 127, (B, S, K, D))
    peak = rng.integers(0, D, (B, S, K))
    sign = rng.choice((-127, 127), (B, S, K))
    np.put_along_axis(ints, peak[..., None], sign[..., None], axis=-1)
    scale = np.exp2(rng.integers(-3, 3, (B, S, K))).astype(np.float32)
    return (ints * scale[..., None]).astype(np.float32), ints, scale


def positions_for(S: int, starts) -> np.ndarray:
    return (np.asarray(starts, np.int32)[:, None]
            + np.arange(S, dtype=np.int32)[None, :])


def reference(cache: KVCache, layer: int, positions: np.ndarray,
              k_rows, v_rows) -> dict[str, np.ndarray]:
    """Plain loops over (slot, token): payload at [layer, slot, position],
    scale at [layer, slot, head, position]; a position past the capacity
    writes nothing."""
    out = {"k": np.array(cache.k), "v": np.array(cache.v)}
    if cache.quantized:
        out["k_scale"] = np.array(cache.k_scale)
        out["v_scale"] = np.array(cache.v_scale)
    for name, (values, ints, scale) in (("k", k_rows), ("v", v_rows)):
        for b in range(B):
            for s in range(positions.shape[1]):
                p = int(positions[b, s])
                if not 0 <= p < T:
                    continue
                if cache.quantized:
                    out[name][layer, b, p] = ints[b, s]
                    out[f"{name}_scale"][layer, b, :, p] = scale[b, s]
                else:
                    out[name][layer, b, p] = np.asarray(
                        jnp.asarray(values[b, s], jnp.bfloat16))
    return out


@functools.partial(jax.jit, static_argnames=("by_head",))
def written(cache, layer, positions, k, v, by_head):
    # the layer index is a traced scalar, as it is inside the layer scan
    return write_kv(cache, layer, positions, k, v, by_head=by_head)


def check(got: KVCache, want: dict[str, np.ndarray]) -> None:
    np.testing.assert_array_equal(np.asarray(got.k), want["k"])
    np.testing.assert_array_equal(np.asarray(got.v), want["v"])
    if got.quantized:
        # the quantiser may divide or multiply by a reciprocal: one ulp
        np.testing.assert_allclose(np.asarray(got.k_scale), want["k_scale"],
                                   rtol=1e-6, atol=0)
        np.testing.assert_allclose(np.asarray(got.v_scale), want["v_scale"],
                                   rtol=1e-6, atol=0)
    else:
        assert got.k_scale is None and got.v_scale is None


def untouched_scales_are_bit_identical(got: KVCache, before: KVCache,
                                       layer: int,
                                       positions: np.ndarray) -> None:
    mask = np.ones((L, B, K, T), bool)
    for b in range(B):
        for p in positions[b]:
            if 0 <= p < T:
                mask[layer, b, :, p] = False
    for name in ("k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name))[mask],
            np.asarray(getattr(before, name))[mask])


@WIDTHS
@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("by_head", [False, True],
                         ids=["row-window", "head-indexed"])
@pytest.mark.parametrize("S", [1, 5], ids=["decode", "prefill"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_write_lands_where_the_reference_says(quantized, S, by_head, layer,
                                              D):
    cache = dirty_cache(quantized, seed=layer, D=D)
    k_rows, v_rows = (new_rows(S, seed=10 + S, D=D),
                      new_rows(S, seed=20 + S, D=D))
    positions = positions_for(S, STARTS[S])
    got = written(cache, jnp.int32(layer), jnp.asarray(positions),
                  jnp.asarray(k_rows[0], jnp.bfloat16),
                  jnp.asarray(v_rows[0], jnp.bfloat16), by_head)
    # every entry: the written rows hold the new values, every other row
    # and plane entry what it held before (the reference starts from it)
    check(got, reference(cache, layer, positions, k_rows, v_rows))
    if quantized:
        untouched_scales_are_bit_identical(got, cache, layer, positions)
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(cache.lengths))


@WIDTHS
@pytest.mark.parametrize("S", [3, 1], ids=["prefill", "decode"])
@pytest.mark.parametrize("by_head", [False, True],
                         ids=["row-window", "head-indexed"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_a_position_past_the_capacity_is_dropped(quantized, by_head, S, D):
    """A slot at its capacity (a stale lane; a padded prefill tail) writes
    nothing — it does not wrap, clamp onto the last row, or touch a
    neighbour. (A decode step of the head-major leaf writes its scale
    planes by a select, not a scatter: the same rule.)"""
    cache = dirty_cache(quantized, seed=5, D=D)
    k_rows, v_rows = new_rows(S, seed=31, D=D), new_rows(S, seed=32, D=D)
    # slot 0 runs off the end after one token, slot 1 starts past it
    positions = positions_for(S, (T - 1, T + 4, 2, 9))
    got = written(cache, jnp.int32(1), jnp.asarray(positions),
                  jnp.asarray(k_rows[0], jnp.bfloat16),
                  jnp.asarray(v_rows[0], jnp.bfloat16), by_head)
    want = reference(cache, 1, positions, k_rows, v_rows)
    check(got, want)
    # slot 1 wrote nothing at all; slot 0 only its last row
    np.testing.assert_array_equal(np.asarray(got.k)[:, 1],
                                  np.asarray(cache.k)[:, 1])
    np.testing.assert_array_equal(np.asarray(got.k)[1, 0, :T - 1],
                                  np.asarray(cache.k)[1, 0, :T - 1])
    assert not np.array_equal(np.asarray(got.k)[1, 0, T - 1],
                              np.asarray(cache.k)[1, 0, T - 1])
    if quantized:
        untouched_scales_are_bit_identical(got, cache, 1, positions)


def test_both_forms_write_the_same_cache():
    """The head-indexed scatter is the row-window scatter with the head in
    the indices: same cache out, entry for entry."""
    cache = dirty_cache(True, seed=9)
    k_rows, v_rows = new_rows(5, seed=41), new_rows(5, seed=42)
    args = (cache, jnp.int32(2), jnp.asarray(positions_for(5, STARTS[5])),
            jnp.asarray(k_rows[0], jnp.bfloat16),
            jnp.asarray(v_rows[0], jnp.bfloat16))
    for a, b in zip(written(*args, False), written(*args, True)):
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("quantized, D, head_major", [
    (True, 128, True),     # nemotron-3-nano-30b-a3b's position: 2 x 128
    (True, 256, True),     # qwen3-next-80b-a3b's: 2 x 256
    (True, 8, False),      # no lane tile: the tiny presets' programs stand
    (False, 128, False),   # 2 bf16 heads get a 2-row tile and interleave
], ids=["int8-128", "int8-256", "int8-8", "bf16-128"])
def test_two_int8_heads_take_the_head_indexed_scatter_unasked(quantized, D,
                                                             head_major):
    """A leaf of 2 one-byte heads of whole lane tiles lies head-major on
    one chip, and the row-window scatter is what has XLA relay it whole
    (PERF.md, PR 62): asked for the row window, `write_kv` traces the
    head-indexed scatter for such a leaf — the very program a sharded trunk
    traces — and for no other."""
    cache = dirty_cache(quantized, seed=3, D=D)
    assert kv_head_major(cache.k.shape[3:], cache.k.dtype.itemsize) \
        is head_major
    k_rows, v_rows = new_rows(1, seed=51, D=D), new_rows(1, seed=52, D=D)
    args = (cache, jnp.int32(1), jnp.asarray(positions_for(1, STARTS[1])),
            jnp.asarray(k_rows[0], jnp.bfloat16),
            jnp.asarray(v_rows[0], jnp.bfloat16))
    asked, indexed = (str(jax.make_jaxpr(
        lambda *a: write_kv(*a, by_head=by_head))(*args))
        for by_head in (False, True))
    assert (asked == indexed) is head_major
