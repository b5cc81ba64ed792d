"""The Pallas Mamba-2 decode step (ops/ssm_step.py), interpreted on the CPU,
against the jnp recurrence it replaced (models/mamba2.py recurrence): the
output and the new state, that only the addressed layer of the stack moves,
the edge values of the decay and of a lane, and that the hybrid trunk takes
the kernel at one position a slot and the engine says so."""

import importlib
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.models import hybrid, llama, mamba2
from symmetry_tpu.ops import ssm_step as op

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = llama.preset("tiny-hybrid")

SHAPES = {
    # layers, slots, heads, d_head, d_state, head tile (None: the gate's)
    "tiny-hybrid": (3, 4, 8, 16, 16, None),
    "tiny-hybrid, two heads a grid step": (3, 4, 8, 16, 16, 2),
    "one tile of the served shape": (2, 2, 16, 64, 128, 16),
    "two tiles a slot, groups of 16": (2, 1, 64, 64, 128, 32),
    "heads that fill no lane tile or group": (2, 2, 24, 8, 128, 12),
}


def inputs(B, H, P, N, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.uniform(k[0], (B, H), jnp.float32, 0.2, 0.999),
            jax.random.normal(k[1], (B, H, P), jnp.float32),
            jax.random.normal(k[2], (B, N), jnp.float32),
            jax.random.normal(k[3], (B, N), jnp.float32),
            jax.random.normal(k[4], (B, H, P), jnp.float32))


def stack_of(L, B, H, P, N, seed=7):
    return jax.random.normal(jax.random.key(seed), (L, B, H, P, N),
                             jnp.float32)


def kernel(stack, layer, xs, tile=None):
    return op.ssm_step(stack, jnp.int32(layer), *xs, tile=tile,
                       interpret=True)


def close(got, want, rel=1e-6):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= rel * np.max(np.abs(want))


@pytest.mark.parametrize("case", list(SHAPES))
def test_kernel_is_the_jnp_recurrence(case):
    L, B, H, P, N, tile = SHAPES[case]
    stack, xs = stack_of(L, B, H, P, N), inputs(B, H, P, N)
    y, new = kernel(stack, L - 1, xs, tile)
    want_y, want = mamba2.recurrence(stack[L - 1], *xs)
    assert y.shape == (B, H, P) and new.shape == stack.shape
    assert y.dtype == new.dtype == jnp.float32
    close(y, want_y)
    close(new[L - 1], want)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_only_the_addressed_layer_of_the_stack_changes(layer):
    L, B, H, P, N, _ = SHAPES["tiny-hybrid"]
    stack, xs = stack_of(L, B, H, P, N), inputs(B, H, P, N)
    _, new = kernel(stack, layer, xs)
    for other in range(L):
        same = np.array_equal(np.asarray(new[other]),
                              np.asarray(stack[other]))
        assert same == (other != layer)


@pytest.mark.parametrize("decay", [0.0, 1.0])
def test_a_decay_of_zero_forgets_and_of_one_keeps(decay):
    L, B, H, P, N, _ = SHAPES["tiny-hybrid"]
    stack = stack_of(L, B, H, P, N)
    a, dx, b, c, skip = inputs(B, H, P, N)
    a = jnp.full_like(a, decay)
    y, new = kernel(stack, 1, (a, dx, b, c, skip))
    outer = dx[..., None] * b[:, None, None, :]
    np.testing.assert_array_equal(
        np.asarray(new[1]), np.asarray(decay * stack[1] + outer))
    close(y, mamba2.recurrence(stack[1], a, dx, b, c, skip)[0])


@pytest.mark.parametrize("lane", ["all zero", "large"])
def test_one_lane_at_an_edge_leaves_the_others_alone(lane):
    """An empty lane (zero state, zero input) stays exactly zero, a lane
    of values near 1e30 stays finite, and neither moves its neighbours."""
    L, B, H, P, N, _ = SHAPES["tiny-hybrid"]
    stack, xs = stack_of(L, B, H, P, N), inputs(B, H, P, N)
    y0, new0 = kernel(stack, 0, xs)
    a, dx, b, c, skip = xs
    if lane == "all zero":
        stack = stack.at[:, 2].set(0.0)
        dx, skip = dx.at[2].set(0.0), skip.at[2].set(0.0)
    else:
        stack = stack.at[:, 2].multiply(1e30)
    y, new = kernel(stack, 0, (a, dx, b, c, skip))
    if lane == "all zero":
        assert not np.asarray(new[0, 2]).any() and not np.asarray(y[2]).any()
    else:
        assert np.isfinite(np.asarray(new[0, 2])).all()
        assert np.isfinite(np.asarray(y[2])).all()
        close(y[2], mamba2.recurrence(stack[0], a, dx, b, c, skip)[0][2])
    others = np.array([0, 1, 3])
    np.testing.assert_array_equal(np.asarray(y)[others],
                                  np.asarray(y0)[others])
    np.testing.assert_array_equal(np.asarray(new[0])[others],
                                  np.asarray(new0[0])[others])


def test_two_identical_lanes_give_identical_results():
    L, B, H, P, N, _ = SHAPES["tiny-hybrid"]
    stack, xs = stack_of(L, B, H, P, N), inputs(B, H, P, N)
    stack = stack.at[:, 3].set(stack[:, 0])
    xs = tuple(v.at[3].set(v[0]) for v in xs)
    y, new = kernel(stack, 2, xs)
    np.testing.assert_array_equal(np.asarray(y[3]), np.asarray(y[0]))
    np.testing.assert_array_equal(np.asarray(new[2, 3]),
                                  np.asarray(new[2, 0]))


def test_the_gate_is_the_planes_tiling_and_the_tile_its_bytes():
    # granite's state: whole (8, 128) tiles a head, TILE_BYTES a grid step
    tile = op.head_tile(128, 64, 128)
    assert tile is not None and 128 % tile == 0
    assert tile * 64 * 128 * 4 <= op.TILE_BYTES < 2 * tile * 64 * 128 * 4
    assert op.head_tile(24, 64, 128) in (1, 2, 3, 4, 6, 8, 12, 24)
    # no Mosaic geometry for a plane that is no whole tile; any interprets
    assert op.head_tile(8, 16, 16) is None
    assert op.head_tile(8, 12, 128) is None
    assert op.head_tile(8, 16, 16, interpret=True) == 8
    with pytest.raises(ValueError, match="no ssm-step geometry"):
        kernel(stack_of(1, 1, 8, 16, 16), 0, inputs(1, 8, 16, 16), tile=3)


def test_a_state_the_kernel_has_no_geometry_for_keeps_the_jnp_form(
        monkeypatch):
    """On a chip tiny-hybrid's 16 x 16 planes are no whole tiles: the step
    says so and runs the recurrence, with the same result."""
    z = mamba2.sizes(TINY)
    assert mamba2.step_form(TINY) == {"form": "pallas-interpret",
                                      "head_tile": z["H"]}
    params = llama.init_params(TINY, jax.random.key(1), jnp.float32)
    lp = hybrid._at(params["layers"]["mamba"], 1)
    u = jax.random.normal(jax.random.key(2), (3, TINY.hidden_size))
    ssm = jax.random.normal(jax.random.key(3),
                            (3, z["H"], z["P"], z["N"]))
    conv = jax.random.normal(jax.random.key(4), (z["K"] - 1, 3, z["conv"]))
    by_kernel = mamba2.step(u, lp, ssm, conv, TINY)
    monkeypatch.setattr(mamba2, "interpret_mode", lambda: False)
    assert mamba2.step_form(TINY) == {
        "form": "step (jnp), two passes over the state"}
    by_jnp = mamba2.step(u, lp, ssm, conv, TINY)
    for got, want in zip(by_kernel, by_jnp):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def count_kernels(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += (eqn.primitive.name == "pallas_call"
              and eqn.params["name"] == op.NAME)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += count_kernels(sub)
    return n


@pytest.mark.parametrize("positions,calls", [(1, 2), (5, 0)])
def test_the_trunk_takes_the_kernel_at_one_position_a_slot(positions, calls):
    """tiny-hybrid (mamba x 2, attention, mamba): one kernel call a RUN of
    mamba layers at S == 1 — the whole stack its operand — and none in the
    chunked form."""
    params = jax.eval_shape(
        lambda: llama.init_params(TINY, jax.random.key(0), jnp.float32))
    cache = jax.eval_shape(lambda: llama.init_cache(TINY, 2, 32, jnp.float32))
    tokens = jax.ShapeDtypeStruct((2, positions), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: hybrid.forward_hidden(p, TINY, t, c))(
            params, tokens, cache)
    assert count_kernels(jaxpr.jaxpr) == calls


def reader_ctx(config=None):
    cfg = {"layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
           "hidden_size": 4096, "num_attention_heads": 32, "head_dim": 128,
           "num_key_value_heads": 8, "intermediate_size": 768,
           "num_local_experts": 72, "num_experts_per_tok": 10,
           "vocab_size": 100352, "mamba_n_heads": 128, "mamba_d_head": 64,
           "mamba_d_state": 128, "mamba_d_conv": 4} if config is None \
        else config
    cell = types.SimpleNamespace(
        config=cfg, tpu={"max_batch_size": 128, "decode_block": 16,
                         "dtype": "bfloat16"})
    return types.SimpleNamespace(
        cell=cell, trace={"ops": []}, device={"kind": "TPU v5 lite"},
        phase=types.SimpleNamespace(trace_path=None))


@pytest.fixture()
def reader(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(CHECKOUT, "benchmarks"))
    return importlib.import_module("readers.ssm")


def plane(name, events):
    line = types.SimpleNamespace(name="XLA Ops", events=[
        types.SimpleNamespace(name=n, start_ns=0, duration_ns=d)
        for n, d in events])
    other = types.SimpleNamespace(name="XLA Modules", events=[
        types.SimpleNamespace(name="%ssm_step.8 = f32[1] custom-call()",
                              start_ns=0, duration_ns=10**9)])
    return types.SimpleNamespace(name=name, lines=[other, line])


KERNEL = "%ssm_step.8 = (f32[128,64,128]{2,1,0}, f32[9,128,128,64,128]) " \
    "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""


def test_the_kernels_events_are_counted_from_the_capture_a_chip(reader):
    """One event is one layer's pass: counted by name on the device
    planes' op lines, whatever piece of a run the capture cut."""
    events = [(KERNEL, 1_700_000), ("%fusion.1 = f32[8] fusion()", 900),
              (KERNEL.replace("ssm_step.8", "ssm_step.9"), 1_500_000),
              ("%ssm_step_other.2 = f32[8] fusion(%x)", 5_000_000),
              (KERNEL.replace("ssm_step.8", "ssm_step"), 1_600_000)]
    data = types.SimpleNamespace(planes=[
        plane("/device:TPU:0", events), plane("/device:TPU:1", events),
        plane("/host:CPU", [(KERNEL, 10**9)])])
    got = reader.count_op(data, "ssm_step")
    assert got["events"] == 3 and got["seconds"] == pytest.approx(4.8e-3)
    assert reader.count_op(data, "decode_attention") == {
        "events": 0, "seconds": 0.0}


@pytest.mark.parametrize("case", ["809 passes", "no such op (parent)",
                                  "no trace", "a dense configuration"])
def test_the_roofline_reader_counts_the_state_once_each_way(
        case, reader, monkeypatch):
    # 1.5 ms a layer's pass, however many the capture holds: 87.4%
    counted = {"events": 809.0, "seconds": 809 * 1.5e-3}
    if case == "no such op (parent)":
        counted = {"events": 0.0, "seconds": 0.0}
    monkeypatch.setattr(reader, "_counted", lambda ctx, op: counted)
    ctx = reader_ctx()
    if case == "809 passes":
        got = reader.step_roofline(ctx, op="ssm_step")
        layer_bytes = 2 * 128 * 128 * 64 * 128 * 4
        assert got == pytest.approx(100 * layer_bytes / 1.5e-3 / 819e9)
        assert 87 < got < 88
    elif case == "no such op (parent)":
        assert reader.step_roofline(ctx, op="ssm_step") is None
    elif case == "no trace":
        monkeypatch.undo()
        ctx.trace = None
        assert importlib.import_module("readers.ssm").step_roofline(
            ctx, op="ssm_step") is None
    else:
        ctx = reader_ctx(config={"hidden_size": 4096})
        assert reader.step_roofline(ctx, op="ssm_step") is None


# ---------------------------------------------------------------------------
# groups of B and C (nemotron_h): head h reads row h // (heads / groups)

GROUPED = {
    # layers, slots, heads, d_head, d_state, groups, head tile (None: the
    # gate's)
    "tiny-nh": (3, 4, 8, 16, 16, 2, None),
    "tiny-nh, a tile inside a group": (3, 4, 8, 16, 16, 2, 2),
    "a tile of one whole group": (2, 2, 8, 16, 16, 2, 4),
    "the served shape's slot: 64 heads in 8 groups, 16 unrolled over two": (
        2, 1, 64, 8, 128, 8, 64),
    "two tiles a slot of four groups each": (2, 2, 64, 8, 128, 8, 32),
    "a group a head": (2, 2, 8, 8, 128, 8, None),
    "groups of 3 heads, 12 unrolled": (2, 2, 24, 8, 128, 8, 12),
}


def grouped_inputs(B, H, P, N, G, seed=0):
    a, dx, _, _, skip = inputs(B, H, P, N, seed)
    k = jax.random.split(jax.random.key(seed + 100), 2)
    return (a, dx, jax.random.normal(k[0], (B, G, N), jnp.float32),
            jax.random.normal(k[1], (B, G, N), jnp.float32), skip)


def by_head(ssm, a, dx, b, c, skip):
    """The recurrence with each head's own rows spelled out."""
    H, G = ssm.shape[1], b.shape[1]
    bh, ch = (jnp.repeat(t, H // G, axis=1) for t in (b, c))
    new = a[..., None, None] * ssm + dx[..., None] * bh[:, :, None, :]
    return jnp.einsum("bhpn,bhn->bhp", new, ch,
                      precision=jax.lax.Precision.HIGHEST) + skip, new


@pytest.mark.parametrize("case", list(GROUPED))
def test_the_grouped_kernel_is_the_grouped_recurrence(case):
    L, B, H, P, N, G, tile = GROUPED[case]
    stack, xs = stack_of(L, B, H, P, N), grouped_inputs(B, H, P, N, G)
    y, new = kernel(stack, L - 1, xs, tile)
    want_y, want = by_head(stack[L - 1], *xs)
    close(y, want_y, 2e-6)
    close(new[L - 1], want)
    assert np.array_equal(np.asarray(new[:L - 1]), np.asarray(stack[:L - 1]))
    jnp_y, jnp_new = mamba2.recurrence(stack[L - 1], *xs)
    close(jnp_y, want_y, 2e-6)
    close(jnp_new, want)


def test_a_group_of_the_kernel_reads_no_other_groups_rows():
    L, B, H, P, N, G = 2, 2, 8, 16, 16, 2
    stack = stack_of(L, B, H, P, N)
    a, dx, b, c, skip = grouped_inputs(B, H, P, N, G)
    y0, s0 = kernel(stack, 0, (a, dx, b, c, skip))
    y1, s1 = kernel(stack, 0, (a, dx, b.at[:, 1].add(2.0),
                               c.at[:, 1].multiply(-1.0), skip))
    assert np.array_equal(np.asarray(y0[:, :4]), np.asarray(y1[:, :4]))
    assert np.array_equal(np.asarray(s0[0, :, :4]), np.asarray(s1[0, :, :4]))
    assert not np.allclose(np.asarray(y0[:, 4:]), np.asarray(y1[:, 4:]))
    # one group IS the one-row form
    y_one, s_one = kernel(stack, 0, (a, dx, b[:, :1], c[:, :1], skip))
    y_row, s_row = kernel(stack, 0, (a, dx, b[:, 0], c[:, 0], skip))
    close(y_one, y_row)
    close(s_one, s_row)


def test_the_gate_keeps_a_tile_whole_groups_or_inside_one():
    # nemotron-3-nano-30b-a3b: a slot's 2 MB is one tile of all 8 groups
    assert op.head_tile(64, 64, 128, groups=8) == 64
    assert op.step_form(64, 64, 128, 4, interpret=False, otherwise="jnp",
                        groups=8) == {"form": "pallas", "head_tile": 64,
                                      "groups": 8}
    # 24 heads in 8 groups of 3 at 16 KB a head: 24 fits; at a budget of 16
    # heads the divisors 12 (whole groups) and 8 (neither) — 12
    assert op.head_tile(24, 8, 128, groups=8) == 24
    assert op.head_tile(128, 64, 128, groups=16) == 64
    # one group: every divisor, as it was (granite-4.0-h-small's 64 of 128)
    assert op.head_tile(128, 64, 128) == 64
    assert "groups" not in op.step_form(128, 64, 128, 4, interpret=False,
                                        otherwise="jnp")
    with pytest.raises(ValueError, match="in 8 groups"):
        kernel(stack_of(1, 1, 24, 8, 128), 0,
               grouped_inputs(1, 24, 8, 128, 8), tile=8)
    nh = llama.preset("nemotron-3-nano-30b-a3b")
    assert mamba2.sizes(nh)["G"] == 8
    assert mamba2.sizes(TINY)["G"] == 1


def test_the_grouped_trunk_takes_one_kernel_call_a_run_of_mamba_layers():
    cfg = llama.preset("tiny-nh")
    params = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    cache = llama.init_cache(cfg, 2, 32, jnp.float32)
    tokens = jnp.zeros((2, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda t, c: llama.forward_hidden(params, cfg, t, c))(tokens, cache)
    runs = sum(kind == "mamba" for kind, _, _ in hybrid.runs(cfg))
    assert runs == 5 and count_kernels(jaxpr.jaxpr) == runs
