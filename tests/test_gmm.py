"""The Pallas grouped matmul (ops/gmm.py), interpreted on the CPU: against
`lax.ragged_dot` with the scales gathered per row (what it replaces) and
against a float32 loop over the groups (what both mean)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.models import moe
from symmetry_tpu.ops import gmm
from symmetry_tpu.ops.quant import quantize

L, LAYER, A, F = 3, 1, 32, 16

# name: (group sizes, row tile)
GROUPS = {
    "empty groups between full ones": ([10, 0, 25, 0, 5], 16),
    "a group that straddles three row tiles": ([3, 40, 5], 16),
    "one group holds every row": ([0, 48, 0, 0], 16),
    "rows no multiple of the row tile": ([3, 0, 30, 4], 8),
    "every group within one tile": ([2, 1, 3, 2], 8),
    "the first and the last group empty": ([0, 7, 9, 0], 8),
    "more groups than rows": ([0, 1, 0, 0, 2, 0, 0, 1, 0, 0, 0, 1], 8),
    "the kernel's own row tile": ([5, 0, 70, 1, 60], None),
}


def operands(sizes, dtype):
    R, X = sum(sizes), len(sizes)
    keys = jax.random.split(jax.random.key(R * 31 + X), 3)
    rows = jax.random.normal(keys[0], (R, A), dtype)
    q = jax.random.randint(keys[1], (L, X, A, F), -127, 128, jnp.int8)
    scale = jax.random.uniform(keys[2], (L, X, F), jnp.float32, 0.5, 1.5)
    return rows, q, scale / 127.0, jnp.asarray(sizes, jnp.int32)


def by_group(rows, q, scale, sizes):
    """out[r] = (rows[r] @ q[e(r)]) * scale[e(r)], a group at a time in
    float64 on the host."""
    out, start = [], 0
    rows = np.asarray(rows, np.float64)
    for e, n in enumerate(np.asarray(sizes)):
        out.append(rows[start:start + n] @ np.asarray(q[e], np.float64)
                   * np.asarray(scale[e], np.float64))
        start += n
    return np.concatenate(out)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_the_kernel_is_ragged_dot_with_the_scale_on_the_accumulator(
        case, dtype):
    sizes, tile = GROUPS[case]
    rows, q, scale, group_sizes = operands(sizes, dtype)
    got = gmm.grouped_matmul(rows, q, scale, group_sizes, jnp.int32(LAYER),
                             row_tile=tile, interpret=True)
    assert got.shape == (sum(sizes), F) and got.dtype == jnp.float32
    row_expert = jnp.repeat(jnp.arange(len(sizes)), group_sizes,
                            total_repeat_length=sum(sizes))
    with jax.default_matmul_precision("highest"):
        ragged = jax.lax.ragged_dot(
            rows, q[LAYER], group_sizes, preferred_element_type=jnp.float32
        ) * jnp.take(scale[LAYER], row_expert, axis=0)
    # int8 widened exactly, float32 accumulator: the two differ by the
    # order of a 32-term float32 sum alone
    np.testing.assert_allclose(got, ragged, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got, by_group(rows, q[LAYER], scale[LAYER], sizes),
        rtol=1e-5, atol=1e-5)


# a chip's share of the experts: pairs on experts it does not hold sort
# behind every group and are in no group size (models/moe.py `_held`)
PAIRS = {**GROUPS, "rows past the last group (a held share)": ([6, 0, 9], 8)}


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(PAIRS))
def test_a_gated_pair_in_one_call_is_the_two_calls_to_the_last_bit(
        case, dtype, act):
    """One call over (gate, up): act(gate product) * up product, formed in
    float32 and rounded once — what two calls and the fusion between them
    gave, bit for bit, with half the visits."""
    sizes, tile = PAIRS[case]
    rows, q, scale, group_sizes = operands(sizes, dtype)
    _, qu, su, _ = operands(sizes[::-1], dtype)     # another draw
    qu, su = qu[:, ::-1], su[:, ::-1] * 1.5
    if "held" in case:
        rows = jnp.concatenate([rows, rows[:5]])
    written = sum(sizes)

    def call(q, scale, **kw):
        return gmm.grouped_matmul(rows, q, scale, group_sizes,
                                  jnp.int32(LAYER), row_tile=tile,
                                  interpret=True, **kw)

    got = call((q, qu), (scale, su), act=moe.ACTS[act])
    assert got.shape == (rows.shape[0], F) and got.dtype == dtype
    want = jax.jit(lambda g, u: (moe.ACTS[act](g) * u).astype(dtype))(
        call(q, scale), call(qu, su))
    np.testing.assert_array_equal(np.asarray(got[:written], np.float32),
                                  np.asarray(want[:written], np.float32))
    assert np.asarray(got[:written], np.float32).any()


def test_the_layer_is_an_address_into_the_stack():
    sizes = [4, 0, 9, 3]
    rows, q, scale, group_sizes = operands(sizes, jnp.float32)
    outs = [gmm.grouped_matmul(rows, q, scale, group_sizes, jnp.int32(i),
                               row_tile=8, interpret=True) for i in range(L)]
    for i, out in enumerate(outs):
        np.testing.assert_allclose(
            out, by_group(rows, q[i], scale[i], sizes), rtol=1e-5, atol=1e-5)
    assert not np.allclose(outs[0], outs[1])


@pytest.mark.parametrize("sizes,tile,visits", [
    # one visit a group and one more for each tile boundary inside a group
    ([10, 0, 25, 0, 5], 16, [(0, 0), (2, 0), (2, 1), (2, 2), (4, 2)]),
    ([0, 48, 0, 0], 16, [(1, 0), (1, 1), (1, 2)]),
    ([16, 16], 16, [(0, 0), (1, 1)]),
    ([3, 0, 30, 4], 8, [(0, 0), (2, 0), (2, 1), (2, 2), (2, 3), (2, 4),
                        (3, 4)]),
])
def test_the_walk_visits_each_hit_group_once_a_row_tile_it_touches(
        sizes, tile, visits):
    """An expert with no row is never visited (never read); the tail of the
    static walk repeats the last visit, whose blocks are already there."""
    R = sum(sizes)
    offsets, group, tiles, count = gmm.visits(
        jnp.asarray(sizes, jnp.int32), R, tile)
    assert int(count[0]) == len(visits)
    assert group.shape == tiles.shape == (
        -(-R // tile) + min(len(sizes), R) - 1,)
    walk = list(zip(np.asarray(group).tolist(), np.asarray(tiles).tolist()))
    assert walk[:len(visits)] == visits
    assert set(walk[len(visits):]) <= {visits[-1]}
    assert np.asarray(offsets).tolist() == [0, *np.cumsum(sizes).tolist()]


def test_geometry_follows_the_shape_and_refuses_what_mosaic_cannot_tile():
    # the served shapes: an expert is one column tile, 64 rows a visit
    assert gmm.geometry(5120, 2048, 512) == (64, 512)       # qwen3-next up
    assert gmm.geometry(5120, 512, 2048) == (64, 2048)      # and down
    assert gmm.geometry(5120, 4096, 768) == (64, 768)       # granite up
    assert gmm.geometry(1280, 768, 4096) == (64, 4096)      # and down
    # fewer rows than a tile: all of them, in whole sublane tiles
    assert gmm.geometry(20, 2048, 512) == (32, 512)
    assert gmm.geometry(20, 2048, 512, itemsize=4) == (24, 512)
    # an expert over the column tile's bytes is cut in whole lanes
    assert gmm.geometry(4096, 4096, 3584) == (64, 896)      # mixtral's shard
    # no whole lanes: no geometry on the chip, any shape interprets
    assert gmm.geometry(64, 64, 32) is None
    assert gmm.geometry(64, 64, 32, interpret=True) == (64, 32)
    with pytest.raises(ValueError, match="no grouped-matmul geometry"):
        gmm.grouped_matmul(jnp.zeros((8, 64), jnp.bfloat16),
                           jnp.zeros((1, 2, 64, 32), jnp.int8),
                           jnp.ones((1, 2, 32)), jnp.asarray([4, 4]),
                           jnp.int32(0))


@pytest.mark.parametrize("one_device,weights,form", [
    (True, "int8", "pallas-interpret"),
    (True, "float32", "ragged_dot"),
    (False, "int8", "ragged_dot"),
])
def test_the_form_reported_is_the_form_taken(one_device, weights, form):
    w = jnp.zeros((8, 32, 16))
    if weights == "int8":
        w = quantize(w)
    report = moe.grouped_matmul_form(w, 40, one_device)
    assert report["form"] == form
    if form == "ragged_dot":
        assert report["why"]
    else:
        assert report["row_tile"] == 48     # 40 rows, whole bf16 tiles
