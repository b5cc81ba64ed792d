"""The KV-cache write every program runs (models/llama.py write_kv) against
a NumPy reference: the row-window scatter of the one-chip trunk and the
head-indexed scatter of a sharded one, bf16 and int8 caches, decode (S = 1)
and prefill (S > 1) shapes, ragged positions across slots — over a narrow
leaf (heads of 8) and over the leaf of 2 heads of a whole lane tile (128),
which as int8 lies head-major on one chip and takes the head-indexed scatter
whatever the caller asks (`kv_head_major`). And the decode step's ONE write
behind the layer scan (`append_step`, PR 66): against the same reference and
against `write_kv` called a layer, bit for bit.

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


# --- the per-step append (PR 66): every layer's row of a slot in ONE write,
# behind the layer scan (models/llama.py append_step) ---

# slot b's position: ragged, the first row (a parked lane decodes at length
# 0), the last row, AT the capacity (dropped) and past it (dropped)
STEP_T = 256            # whole lane tiles of positions: the planes' kernel
STEP_POSITIONS = (7, 0, STEP_T - 1, STEP_T, STEP_T + 44)


def step_case(quantized: bool, K: int, seed: int = 0, D: int = 128):
    """(cache, per-layer K and V rows as `new_rows` makes them, positions):
    a dirty cache of 3 layers x 5 slots x 256 positions of K heads."""
    B = len(STEP_POSITIONS)
    rng = np.random.default_rng(seed)
    lengths = jnp.zeros((B,), jnp.int32)
    shape, planes = (L, B, STEP_T, K, D), (L, B, K, STEP_T)
    if quantized:
        k, v = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                for _ in range(2))
        ks, vs = (jnp.asarray(rng.uniform(1.0, 2.0, planes), jnp.float32)
                  for _ in range(2))
        cache = KVCache(k=k, v=v, lengths=lengths, k_scale=ks, v_scale=vs)
    else:
        k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                for _ in range(2))
        cache = KVCache(k=k, v=v, lengths=lengths)

    def rows(seed):  # new_rows at this case's (B, K): exact integers x 2^n
        ints = rng.integers(-126, 127, (L, B, K, D))
        peak = rng.integers(0, D, (L, B, K))
        np.put_along_axis(ints, peak[..., None],
                          rng.choice((-127, 127), (L, B, K))[..., None], -1)
        scale = np.exp2(rng.integers(-3, 3, (L, B, K))).astype(np.float32)
        return (ints * scale[..., None]).astype(np.float32), ints, scale

    return cache, rows(1), rows(2), np.asarray(STEP_POSITIONS, np.int32)


def step_reference(cache: KVCache, positions, k_rows, v_rows) -> dict:
    """Plain loops over (layer, slot): `reference` for every layer at once."""
    out = {"k": np.array(cache.k), "v": np.array(cache.v)}
    if cache.quantized:
        out["k_scale"] = np.array(cache.k_scale)
        out["v_scale"] = np.array(cache.v_scale)
    T = cache.k.shape[2]
    for name, (values, ints, scale) in (("k", k_rows), ("v", v_rows)):
        for layer in range(L):
            for b, p in enumerate(positions):
                if not 0 <= p < T:
                    continue
                if cache.quantized:
                    out[name][layer, b, p] = ints[layer, b]
                    out[f"{name}_scale"][layer, b, :, p] = scale[layer, b]
                else:
                    out[name][layer, b, p] = np.asarray(
                        jnp.asarray(values[layer, b], jnp.bfloat16))
    return out


@jax.jit
def appended(cache, positions, k, v):
    """One append a step, its rows made as `_attention(own_row=True)` makes
    them: quantised as the cache will hold them, or cast to its dtype."""
    from symmetry_tpu.models.llama import append_step
    from symmetry_tpu.ops.quant import quantize_kv

    if not cache.quantized:
        return append_step(cache, positions, (k.astype(cache.k.dtype),
                                              v.astype(cache.v.dtype),
                                              None, None))
    (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
    return append_step(cache, positions, (kq, vq, ks, vs))


@jax.jit
def written_a_layer(cache, positions, k, v):
    for layer in range(L):
        cache = write_kv(cache, jnp.int32(layer), positions[:, None],
                         k[layer][:, None], v[layer][:, None],
                         by_head=False)
    return cache


@pytest.mark.parametrize("K", [8, 4], ids=["8-heads", "4-heads"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_step_append_lands_where_the_reference_and_write_kv_say(quantized,
                                                                K):
    """The decode step's ONE write: every layer's row at each slot's own
    position — ragged, a parked lane's row 0, the last row — and nothing
    for a slot at or past the capacity; the payload and the planes are the
    NumPy reference's, and bit for bit what `write_kv` called a layer
    leaves (untouched entries included: the planes' kernel rewrites the
    tile column around a position with what it held)."""
    cache, k_rows, v_rows, positions = step_case(quantized, K, seed=K)
    args = (cache, jnp.asarray(positions),
            jnp.asarray(k_rows[0], jnp.bfloat16),
            jnp.asarray(v_rows[0], jnp.bfloat16))
    got = appended(*args)
    check(got, step_reference(cache, positions, k_rows, v_rows))
    want = written_a_layer(*args)
    for name in ("k", "v", "k_scale", "v_scale", "lengths"):
        a, b = getattr(got, name), getattr(want, name)
        assert (a is None) == (b is None)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the two slots at and past the capacity wrote nothing at all
    for leaf in ("k", "v") + (("k_scale", "v_scale") if quantized else ()):
        np.testing.assert_array_equal(np.asarray(getattr(got, leaf))[:, 3:],
                                      np.asarray(getattr(cache, leaf))[:, 3:])


@pytest.mark.parametrize("quantized, K, D", [
    (True, 4, 128), (False, 8, 128), (True, 8, 64)],
    ids=["int8-4x128", "bf16-8x128", "int8-pairs-of-64"])
def test_a_decode_step_leaves_the_cache_write_kv_a_layer_leaves(
        monkeypatch, quantized, K, D):
    """Through the trunk: five decode steps of a one-chip homogeneous model
    whose step appends once (`attention_paths`: `kv_append`) against the
    same model made to write a layer (the reply without the key) — ragged
    lengths, a parked lane at length 0. Layer 0 sees identical inputs, so
    its rows and scales are bit-identical (heads of 64 in their pairs);
    behind it the two differ by the float32 order of one sum a query row."""
    import dataclasses

    from symmetry_tpu.models import llama

    cfg = dataclasses.replace(llama.preset("tiny"), head_dim=D, num_heads=8,
                              num_kv_heads=K)
    params = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    real = llama.attention_paths
    assert real(cfg, 256, None, batch=4,
                kv_bytes=1 if quantized else 4)["kv_append"] == "step"

    def a_layer(*args, **kw):
        return {k: v for k, v in real(*args, **kw).items()
                if k != "kv_append"}

    def run(paths):
        monkeypatch.setattr(llama, "attention_paths", paths)
        cache = llama.init_cache(cfg, 4, 256, jnp.float32,
                                 quantized=quantized)
        prompt = jax.random.randint(jax.random.key(1), (4, 16), 0, 500)
        lengths = jnp.asarray([16, 3, 9, 0], jnp.int32)
        _, cache = jax.jit(lambda t, c, n: llama.forward_hidden(
            params, cfg, t, c, n))(prompt, cache, lengths)
        cache = cache._replace(lengths=lengths)
        step = jax.jit(lambda t, c: llama.forward_hidden(params, cfg, t, c))
        outs = []
        for i in range(5):
            tok = jax.random.randint(jax.random.key(10 + i), (4, 1), 0, 500)
            h, cache = step(tok, cache)
            outs.append(np.asarray(h))
        return outs, cache

    (got_h, got), (want_h, want) = run(real), run(a_layer)
    for a, b in zip(got_h, want_h):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(want.lengths))
    for name in ("k", "v") + (("k_scale", "v_scale") if quantized else ()):
        a, b = (np.asarray(getattr(c, name)) for c in (got, want))
        np.testing.assert_array_equal(a[0], b[0])
        if a.dtype != np.int8:   # (an int8 entry may round the other way)
            np.testing.assert_allclose(a[1], b[1], rtol=2e-4, atol=2e-4)
