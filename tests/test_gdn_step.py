"""The Pallas Gated DeltaNet decode step (ops/ssm_step.py gdn_step),
interpreted on the CPU, against the jnp recurrence it replaced
(models/gdn.py recurrence): the output and the new state, that only the
addressed layer of the stack moves, the edge values of the decay, of the
write strength and of a lane, and that the hybrid trunk takes the kernel at
one position a slot and the engine says so."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.models import gdn, hybrid, llama
from symmetry_tpu.ops import ssm_step as op

TINY = llama.preset("tiny-gdn")

SHAPES = {
    # layers, slots, value heads, d_key, d_value, head tile (None: the gate's)
    "tiny-gdn": (3, 4, 4, 16, 16, None),
    "tiny-gdn, two heads a grid step": (3, 4, 4, 16, 16, 2),
    "one tile of the served shape": (2, 2, 4, 128, 128, 4),
    "two tiles a slot of the served plane": (2, 1, 4, 128, 128, 2),
    "a plane that is not square": (2, 2, 6, 8, 128, 3),
    "one head a grid step": (2, 3, 3, 16, 32, 1),
}


def inputs(B, H, Dk, Dv, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)

    def unit(key):      # l2-normed, as the layer's q and k are
        x = jax.random.normal(key, (B, H, Dk), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (jax.random.uniform(k[0], (B, H), jnp.float32, 0.2, 0.999),
            jax.random.uniform(k[1], (B, H), jnp.float32, 0.05, 0.95),
            unit(k[2]) * Dk ** -0.5, unit(k[3]),
            jax.random.normal(k[4], (B, H, Dv), jnp.float32))


def stack_of(L, B, H, Dk, Dv, seed=7, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), (L, B, H, Dk, Dv),
                             jnp.float32).astype(dtype)


def kernel(stack, layer, xs, tile=None):
    return op.gdn_step(stack, jnp.int32(layer), *xs, tile=tile,
                       interpret=True)


def close(got, want, rel=1e-6):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= rel * np.max(np.abs(want))


@pytest.mark.parametrize("case", list(SHAPES))
def test_kernel_is_the_jnp_recurrence(case):
    L, B, H, Dk, Dv, tile = SHAPES[case]
    stack, xs = stack_of(L, B, H, Dk, Dv), inputs(B, H, Dk, Dv)
    o, new = kernel(stack, L - 1, xs, tile)
    want_o, want = gdn.recurrence(stack[L - 1], *xs)
    assert o.shape == (B, H, Dv) and new.shape == stack.shape
    assert o.dtype == new.dtype == jnp.float32
    close(o, want_o)
    close(new[L - 1], want)


def test_a_bfloat16_state_is_stepped_in_float32_and_rounded_once():
    L, B, H, Dk, Dv, _ = SHAPES["tiny-gdn"]
    stack = stack_of(L, B, H, Dk, Dv, dtype=jnp.bfloat16)
    xs = inputs(B, H, Dk, Dv)
    o, new = kernel(stack, 0, xs)
    want_o, want = gdn.recurrence(stack[0].astype(jnp.float32), *xs)
    assert new.dtype == jnp.bfloat16 and o.dtype == jnp.float32
    close(o, want_o)
    # one rounding to bfloat16 of a float32 result that differs in its
    # last bits: at most one step of the 8-bit mantissa apart
    close(new[0].astype(jnp.float32), want, rel=2 ** -7)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_only_the_addressed_layer_of_the_stack_changes(layer):
    L, B, H, Dk, Dv, _ = SHAPES["tiny-gdn"]
    stack, xs = stack_of(L, B, H, Dk, Dv), inputs(B, H, Dk, Dv)
    _, new = kernel(stack, layer, xs)
    for other in range(L):
        same = np.array_equal(np.asarray(new[other]),
                              np.asarray(stack[other]))
        assert same == (other != layer)


@pytest.mark.parametrize("edge", ["no write, no decay", "forgets"])
def test_no_write_keeps_the_state_to_the_bit_and_no_memory_forgets(edge):
    """beta = 0 and a = 1 (what a position past a row's end is given) leave
    the state bit-identical and read it; a = 0 leaves k (outer) beta v."""
    L, B, H, Dk, Dv, _ = SHAPES["tiny-gdn"]
    stack = stack_of(L, B, H, Dk, Dv)
    a, beta, q, k, v = inputs(B, H, Dk, Dv)
    if edge == "no write, no decay":
        a, beta = jnp.ones_like(a), jnp.zeros_like(beta)
        want = stack[1]
    else:
        a = jnp.zeros_like(a)
        want = k[..., :, None] * (beta[..., None] * v)[..., None, :]
    o, new = kernel(stack, 1, (a, beta, q, k, v))
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(want))
    close(o, gdn.recurrence(stack[1], a, beta, q, k, v)[0])


@pytest.mark.parametrize("lane", ["all zero", "large"])
def test_one_lane_at_an_edge_leaves_the_others_alone(lane):
    """An empty lane (zero state, zero value) stays exactly zero, a lane
    of values near 1e30 stays finite, and neither moves its neighbours."""
    L, B, H, Dk, Dv, _ = SHAPES["tiny-gdn"]
    stack, xs = stack_of(L, B, H, Dk, Dv), inputs(B, H, Dk, Dv)
    o0, new0 = kernel(stack, 0, xs)
    a, beta, q, k, v = xs
    if lane == "all zero":
        stack, v = stack.at[:, 2].set(0.0), v.at[2].set(0.0)
    else:
        stack = stack.at[:, 2].multiply(1e30)
    o, new = kernel(stack, 0, (a, beta, q, k, v))
    if lane == "all zero":
        assert not np.asarray(new[0, 2]).any() and not np.asarray(o[2]).any()
    else:
        assert np.isfinite(np.asarray(new[0, 2])).all()
        assert np.isfinite(np.asarray(o[2])).all()
        close(o[2], gdn.recurrence(stack[0], a, beta, q, k, v)[0][2])
    others = np.array([0, 1, 3])
    np.testing.assert_array_equal(np.asarray(o)[others],
                                  np.asarray(o0)[others])
    np.testing.assert_array_equal(np.asarray(new[0])[others],
                                  np.asarray(new0[0])[others])


def test_two_identical_lanes_give_identical_results():
    L, B, H, Dk, Dv, _ = SHAPES["tiny-gdn"]
    stack, xs = stack_of(L, B, H, Dk, Dv), inputs(B, H, Dk, Dv)
    stack = stack.at[:, 3].set(stack[:, 0])
    xs = tuple(x.at[3].set(x[0]) for x in xs)
    o, new = kernel(stack, 2, xs)
    np.testing.assert_array_equal(np.asarray(o[3]), np.asarray(o[0]))
    np.testing.assert_array_equal(np.asarray(new[2, 3]),
                                  np.asarray(new[2, 0]))


def test_the_gate_is_the_planes_tiling_and_the_tile_its_bytes():
    # qwen3-next's state: whole (8, 128) tiles a head, a whole slot (32
    # heads x 64 KB = TILE_BYTES) a grid step
    assert op.head_tile(32, 128, 128) == 32
    assert 32 * 128 * 128 * 4 == op.TILE_BYTES
    assert gdn.sizes(llama.preset("qwen3-next-80b-a3b"))["Hv"] == 32
    # no Mosaic geometry for a plane that is no whole tile; any interprets
    assert op.head_tile(4, 16, 16) is None
    assert op.head_tile(4, 128, 64) is None
    assert op.head_tile(4, 16, 16, interpret=True) == 4
    with pytest.raises(ValueError, match="no gdn-step geometry"):
        kernel(stack_of(1, 1, 4, 16, 16), 0, inputs(1, 4, 16, 16), tile=3)


def test_a_state_the_kernel_has_no_geometry_for_keeps_the_jnp_form(
        monkeypatch):
    """On a chip tiny-gdn's 16 x 16 planes are no whole tiles: the step
    says so and runs the recurrence, with the same result; the published
    widths take the kernel there."""
    z = gdn.sizes(TINY)
    assert gdn.step_form(TINY) == {"form": "pallas-interpret",
                                   "head_tile": z["Hv"]}
    params = llama.init_params(TINY, jax.random.key(1), jnp.float32)
    lp = hybrid._at(params["layers"]["gdn"], 1)
    u = jax.random.normal(jax.random.key(2), (3, TINY.hidden_size))
    state = jax.random.normal(jax.random.key(3),
                              (2, 3, z["Hv"], z["Dk"], z["Dv"]))
    conv = jax.random.normal(jax.random.key(4), (z["K"] - 1, 3, z["conv"]))
    by_kernel = gdn.step_at(u, lp, state, jnp.int32(1), conv, TINY)
    monkeypatch.setattr(gdn, "interpret_mode", lambda: False)
    assert gdn.step_form(TINY) == {
        "form": "step (jnp), read-outs from the old state"}
    assert gdn.step_form(llama.preset("qwen3-next-80b-a3b")) == {
        "form": "pallas", "head_tile": 32}
    by_jnp = gdn.step_at(u, lp, state, jnp.int32(1), conv, TINY)
    for got, want in zip(by_kernel, by_jnp):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(by_kernel[1][0]),
                                  np.asarray(state[0]))


def count_kernels(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += (eqn.primitive.name == "pallas_call"
              and eqn.params["name"] == op.GDN_NAME)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += count_kernels(sub)
    return n


@pytest.mark.parametrize("positions,calls", [(1, 1), (5, 0)])
def test_the_trunk_takes_the_kernel_at_one_position_a_slot(positions, calls):
    """tiny-gdn (linear attention x 3, full attention): one kernel call for
    the RUN of Gated DeltaNet layers at S == 1 — the whole stack its
    operand, the layer the scan's index — and none in the chunked form."""
    params = jax.eval_shape(
        lambda: llama.init_params(TINY, jax.random.key(0), jnp.float32))
    cache = jax.eval_shape(lambda: llama.init_cache(TINY, 2, 32, jnp.float32))
    tokens = jax.ShapeDtypeStruct((2, positions), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: hybrid.forward_hidden(p, TINY, t, c))(
            params, tokens, cache)
    assert count_kernels(jaxpr.jaxpr) == calls
