"""Compile the decode-attention kernel for a v5e that is described, not
attached: what the Mosaic and XLA TPU compilers refuse or rearrange at the
benchmark cells' real shapes, which interpret mode at tiny shapes cannot
show (tiling of the int8 payload and the 4-row scale planes, VMEM, and
whether XLA stages an operand of the call in its fast memory). Nothing
runs. One file, one fixture: only the worker given this file loads the
TPU's compiler library (on-chip-measurement guide, section 2).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from symmetry_tpu.ops.decode_attention import (
    decode_attention, decode_attention_tp)


@pytest.fixture(scope="module")
def host():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(host):
    return SingleDeviceSharding(host.devices[0])


@pytest.fixture()
def no_cache():
    # an executable compiled for a described chip cannot be read back
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


CASES = {
    # layers, slots, capacity, KV heads, query heads, cache dtype, window,
    # head size
    "mistral-7b cell": (32, 128, 640, 8, 32, jnp.int8, None, 128),
    "qwen2-7b cell": (28, 128, 640, 4, 28, jnp.int8, None, 128),
    "8 x 4096 bf16": (2, 8, 4096, 8, 32, jnp.bfloat16, None, 128),
    "8 x 8192 int8 window": (2, 8, 8192, 8, 32, jnp.int8, 4096, 128),
    "16 KV heads": (2, 8, 1024, 16, 32, jnp.int8, None, 128),
    # many slots of a long capacity: the work lists have to fit SMEM
    "128 x 8192 int8 window": (2, 128, 8192, 8, 32, jnp.int8, 4096, 128),
    "128 x 32768 int8": (1, 128, 32768, 8, 32, jnp.int8, None, 128),
    # 2 KV heads a chip (a shard of mistral-7b over model: 4): XLA keeps
    # the int8 cache head-major, the bf16 one interleaved — neither view
    # the kernel takes may be a copy
    "2 KV heads int8": (32, 8, 4096, 2, 8, jnp.int8, 4096, 128),
    "2 KV heads bf16": (32, 8, 4096, 2, 8, jnp.bfloat16, None, 128),
    "2 KV heads at 640": (32, 128, 640, 2, 8, jnp.int8, None, 128),
    "gemma-2b: 1 KV head of 256": (18, 8, 8192, 1, 8, jnp.int8, None, 256),
    "gemma-7b: 16 KV heads of 256": (2, 8, 4096, 16, 16, jnp.int8, None,
                                     256),
    # heads of 64 lie in the cache in pairs, [L, B, T, K / 2, 128]
    # (models/llama.py kv_row): as written XLA pads the 64 to a lane tile
    "lfm2-8b-a1b cell": (6, 128, 640, 8, 32, jnp.int8, None, 64),
    "8 KV heads of 64 bf16": (6, 128, 640, 8, 32, jnp.bfloat16, None, 64),
    # a block of 4 query positions a slot (a ninth entry): 128 rows a slot,
    # q and the output in tiles of 32 slots
    "sdar-30b-a3b-chat cell": (12, 128, 640, 4, 32, jnp.int8, None, 128, 4),
    "4 queries at 8 x 4096": (12, 8, 4096, 4, 32, jnp.int8, None, 128, 4),
}


def whole_cache_copies(text: str) -> list[str]:
    """Lines of a compiled program that copy or relay out a five-axis
    array of int8 or bf16 — the cache — or a four-axis f32 one, its
    scales."""
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(r"= (s8|bf16)\[\d+,\d+,\d+,\d+,\d+\]\S* "
                         r"(copy|transpose|fusion)\(", line)
            or re.search(r"= f32\[\d+,\d+,\d+,\d+\]\S* "
                         r"(copy|transpose|reshape|fusion)\(", line)]


def leaf_moves(text: str, L: int, B: int, T: int,
               D: int) -> tuple[list[str], list[str]]:
    """(moved, written): the lines of a compiled program whose result is a
    K/V leaf of 2 int8 heads in either order — `s8[L,B,T,2,D]` as declared
    or `s8[L,B,2,T,D]` as the decode kernel views it — made by a `copy`, a
    `transpose` or a fusion that is no write in place: each one is the
    whole leaf through HBM. And the writes in place themselves (a scatter
    is a `kCustom` fusion whose result IS its operand's buffer, the
    insert's placement a dynamic-update-slice fusion), so that a test can
    count them."""
    shape = rf"= s8\[{L},{B},({T},2|2,{T}),{D}\]\S* "
    moved, written = [], []
    for line in text.splitlines():
        if re.search(shape + r"(copy|transpose)\(", line):
            moved.append(line.strip()[:160])
        elif re.search(shape + r"fusion\(", line):
            in_place = ("kind=kCustom" in line
                        or "dynamic-update-slice" in line.split(" = ")[0])
            (written if in_place else moved).append(line.strip()[:160])
    return moved, written


def gmm_results(text: str) -> list[str]:
    """What each `moe_gmm` call of a compiled program returns, sorted: a
    routed GATED layer is two calls (PR 64) — the (gate, up) pair's, whose
    one visit copies both tiles and writes `act(g) * u` in the activations'
    dtype, `bf16[rows,F]`, and down's `f32[rows,D]` — where it was three,
    with two `f32[rows,F]` results and a fusion between them; an ungated
    layer's two are up's and down's, both float32."""
    return sorted(re.findall(r"%moe_gmm[.\d]* = (\w+\[[\d,]+\])", text))


def plane_moves(text: str, L: int, B: int, T: int) -> list[str]:
    """Lines of a compiled program that copy or relay a scale plane of 2
    heads, `f32[L,B,2,T]`: the planes lie in (2, 128) tiles, the decode
    kernel copies blocks out of them as they lie, and a scatter into them
    has XLA relay the whole plane to a padded (8, 128) tile and back, a
    layer (a decode step of a head-major leaf writes them by a select over
    the layer's slice instead: models/llama.py _put_scales)."""
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= f32\[{L},{B},2,{T}\]\S* "
                         rf"(copy|copy-start|transpose|scatter)\(", line)]


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_at_served_shapes(one_chip, no_cache, case):
    L, B, T, K, nq, dtype, window, D, *queries = CASES[case]

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    q = shape((B, *queries, nq, D), jnp.bfloat16)
    kv = shape((L, B, T, K * D // 128, 128) if D == 64 else (L, B, T, K, D),
               dtype)
    scale = shape((L, B, K, T), jnp.float32) if dtype == jnp.int8 else None
    compiled = jax.jit(
        lambda q, k, v, layer, n, ks, vs: decode_attention(
            q, k, v, layer, n, ks, vs, window=window)
    ).lower(q, kv, kv, shape((), jnp.int32), shape((B,), jnp.int32),
            scale, scale).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    if D == 64:
        # the leaves lie dense — four-row tiles of 128 lanes — and the
        # kernel's [T * 4, 128] view of them is a bitcast
        tile = "T(4,128)(4,1)" if dtype == jnp.int8 else "T(4,128)(2,1)"
        assert f"[6,128,640,4,128]{{4,3,2,1,0:{tile}}} parameter(1)" in text
        assert re.search(r"\[6,128,2560,128\]\S* bitcast\(%k\.", text)
    # the cache is the call's operand as it lies: viewed, never copied
    assert not re.search(r" copy\(%[kv]\.", text)
    assert not whole_cache_copies(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16],
                         ids=["int8", "bf16"])
def test_sharded_kernel_reads_each_shard_in_place(host, no_cache, dtype):
    """chip_smoke.py --mesh-model 4: mistral-7b over model: 4 at 8 x
    4,096, 2 KV heads a chip — one kernel call a shard, its share of the
    cache neither gathered nor copied."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(host.devices).reshape(1, 4), ("data", "model"))

    def shape(dims, dt, *spec):
        return jax.ShapeDtypeStruct(dims, dt,
                                    sharding=NamedSharding(mesh, P(*spec)))

    L, B, T, K, nq = 32, 8, 4096, 8, 32
    q = shape((B, nq, 128), jnp.bfloat16, None, "model", None)
    kv = shape((L, B, T, K, 128), dtype, None, None, None, "model", None)
    scale = (shape((L, B, K, T), jnp.float32, None, None, "model", None)
             if dtype == jnp.int8 else None)
    compiled = jax.jit(
        lambda q, k, v, layer, n, ks, vs: decode_attention_tp(
            q, k, v, layer, n, ks, vs, mesh=mesh, window=4096)
    ).lower(q, kv, kv, shape((), jnp.int32), shape((B,), jnp.int32),
            scale, scale).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "all-gather" not in text
    assert not whole_cache_copies(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


def test_trunk_reads_the_cache_in_place(one_chip, no_cache, monkeypatch):
    """The whole decode trunk at qwen2-7b's cell: one kernel call in the
    layer loop (and the step's one `scale_append` behind it: PR 66), no
    per-layer slice of the int8 cache, and none of the cache's arrays
    staged whole in XLA's fast memory around the call (its 9 MB scale
    arrays were: three copies a layer until the operands were pinned to
    HBM)."""
    from symmetry_tpu.models import llama

    monkeypatch.setattr(llama, "interpret_mode", lambda: False)
    cfg = llama.preset("qwen2-7b")
    B, T = 128, 640

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True))
    cache = shaped(lambda: llama.init_cache(cfg, B, T, jnp.bfloat16,
                                            quantized=True))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    text = jax.jit(
        lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
        donate_argnums=(2,)).lower(params, tok, cache).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert len(re.findall(r"%decode_attention[.\d]* = ", text)) == 1
    assert "s8[1,128,640,4,128]" not in text
    staged = [line for line in text.splitlines()
              if "copy-start" in line and "[28,128," in line]
    assert not staged, staged[0][:200]


def computations(text: str) -> dict[str, list[str]]:
    """A compiled program's computations, name -> lines."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.]+) \(", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


@pytest.mark.parametrize("program", ["step", "block"])
@pytest.mark.parametrize("preset", ["mistral-7b", "qwen2-7b"])
def test_dense_decode_appends_to_the_cache_once_a_step(
        one_chip, no_cache, monkeypatch, preset, program):
    """The dense cells' decode step (`forward_hidden`, one position a slot)
    and the engine's decode block of 16 (`tools/lowered_programs.py`), 128
    slots x 640, for a described v5e (PR 66): the layer loop — the
    computation that calls the decode kernel — WRITES no cache-shaped array
    (no scatter, no dynamic-update-slice, no fusion, no copy: the parent's
    held four scatters); it reads the K/V leaves as constants and hands the
    two scale planes THROUGH the kernel call, aliased and untouched (as
    constants of the loop XLA staged qwen2-7b's 37 MB `k_scale` whole
    around every call: ops/decode_attention.py). The step behind the loop
    writes each K/V leaf by ONE scatter in place and both planes by ONE
    `scale_append` call aliased onto them, and nowhere is a leaf or a plane
    copied, staged, transposed or relaid (a window scatter of the planes
    is: ops/scale_append.py). No second cache: the program's temporaries
    stay under a tenth of one leaf."""
    from symmetry_tpu.models import llama

    monkeypatch.setattr(llama, "interpret_mode", lambda: False)
    tool, shaped = _lowered_programs_tool(one_chip, monkeypatch)
    cfg = llama.preset(preset)
    L, K, B, T = cfg.num_layers, cfg.num_kv_heads, tool.SLOTS, tool.CAPACITY
    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True))
    if program == "block":
        e = tool.bare_engine(cfg)
        assert llama.attention_paths(
            cfg, T, None, batch=B, kv_bytes=1)["kv_append"] == "step"
        with jax.default_matmul_precision("default"):  # as served
            lowered = next(low for prog, low in tool.programs(
                e, params, tool.decode_state(e, cfg, B))
                if prog == "decode_block")
    else:
        cache = shaped(lambda: llama.init_cache(cfg, B, T, jnp.bfloat16,
                                                quantized=True))
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
        lowered = jax.jit(
            lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
            donate_argnums=(2,)).lower(params, tok, cache)
    compiled = lowered.compile()
    text = compiled.as_text()
    leaf, plane = rf"s8\[{L},{B},{T},{K},128\]", rf"f32\[{L},{B},{K},{T}\]"
    made = rf"= \(?({leaf}|{plane})\S*(, ({leaf}|{plane})\S*)*\)? " \
           rf"(fusion|scatter|dynamic-update-slice|copy|copy-start|" \
           rf"transpose|custom-call)\("
    bodies = computations(text)
    loop, = (n for n, lines in bodies.items()
             if any("%decode_attention" in ln.split(" = ")[0]
                    for ln in lines))
    step, = (n for n, lines in bodies.items()
             if any("%scale_append" in ln.split(" = ")[0] for ln in lines))
    assert loop != step
    through = rf"= \(bf16\[{B},\d+,128\]\S*, {plane}\S*, {plane}\S*\) " \
              rf"custom-call\("
    in_loop = [ln.strip()[:160] for ln in bodies[loop]
               if re.search(made, ln) and not re.search(through, ln)]
    assert not in_loop, in_loop[0]
    assert sum(bool(re.search(through, ln)) for ln in bodies[loop]) == 1
    writes = [ln.strip() for n, lines in bodies.items() for ln in lines
              if re.search(made, ln) and not n.startswith("%fused")
              and not re.search(through, ln)]
    scatters = [ln for ln in writes if re.search(
        rf"= {leaf}\S* fusion\(", ln) and "kind=kCustom" in ln]
    appends = [ln for ln in writes if re.search(
        rf"= \({plane}\S*, {plane}\S*\) custom-call\(", ln)
        and "%scale_append" in ln.split(" = ")[0]]
    assert len(scatters) == 2 and len(appends) == 1, writes
    assert sorted(writes) == sorted(scatters + appends), writes
    behind = [ln.strip() for ln in bodies[step]]
    assert all(ln in behind for ln in writes)
    moved = [ln.strip()[:160] for ln in text.splitlines() if re.search(
        rf"= ({leaf}|{plane})\S* (copy|copy-start|transpose)\(", ln)]
    assert not moved, moved[0]
    assert compiled.memory_analysis().temp_size_in_bytes < L * B * T * K * 13


def test_hybrid_decode_step_updates_the_recurrent_state_in_place(
        one_chip, no_cache, monkeypatch):
    """granite-4.0-h-small's decode trunk at its cell (128 slots x 640): the
    4.83 GB recurrent state is donated in, aliased out and updated where it
    lies — no second copy of it among the program's temporaries, no copy
    or relayout of the whole array — by ONE kernel call a run of mamba
    layers (ops/ssm_step.py, the stack its operand) and no XLA fusion: the
    two passes XLA made of the step (a reduction for `S C`, then the
    update) cannot come back unseen. The one attention layer still takes
    the decode kernel. (PR 28 and PR 29 both met XLA copying a cache the
    source said was updated in place.)"""
    from symmetry_tpu.models import hybrid, llama, mamba2

    monkeypatch.setattr(llama, "interpret_mode", lambda: False)
    monkeypatch.setattr(mamba2, "interpret_mode", lambda: False)
    cfg = llama.preset("granite-4.0-h-small")
    B, T = 128, 640

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    cache = shaped(lambda: llama.init_cache(cfg, B, T, jnp.bfloat16,
                                            quantized=True))
    assert cache.ssm.shape == (9, 128, 128, 64, 128)
    assert cache.k.shape == (1, 128, 640, 8, 128)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
        donate_argnums=(2,)).lower(params, tok, cache).compile()
    memory = compiled.memory_analysis()
    state_bytes = 9 * 128 * 128 * 64 * 128 * 4
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 9    # under one layer
    text = compiled.as_text()
    mamba_runs = sum(kind == "mamba" for kind, _, _ in hybrid.runs(cfg))
    assert mamba_runs == 2
    assert text.count("tpu_custom_call") == 1 + mamba_runs
    assert len(re.findall(r"%ssm_step[.\d]* = ", text)) == mamba_runs
    whole = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= f32\[9,128,128,64,128\]\S* (copy|transpose)\(",
                          line)]
    assert not whole, whole[0]
    # the stack is an operand of the kernel calls (and of the plumbing that
    # carries it: tuples, loops, bitcasts) and of no fusion, in or out
    shape = r"f32\[9,128,128,64,128\]"
    stack = set(re.findall(rf"(%[\w.\-]+)(?: =|:) {shape}", text))
    fused = [line.strip()[:160] for line in text.splitlines()
             if " fusion(" in line and (
                 re.search(shape, line.split(" fusion(")[0])
                 or stack & set(re.findall(r"%[\w.\-]+",
                                           line.split(" fusion(")[1])))]
    assert stack and not fused, fused[:1]


def test_gdn_decode_step_updates_the_matrix_state_in_place(
        one_chip, no_cache, monkeypatch):
    """qwen3-next-80b-a3b's decode trunk at its cell (128 slots x 640): the
    0.81 GB matrix state is donated in, aliased out and updated where it
    lies — no copy or relayout of the whole stack, temporaries under one
    layer's state — by ONE kernel call for the run of Gated DeltaNet layers
    (ops/ssm_step.py gdn_step, the stack its operand) and no XLA fusion: the
    two fusions a layer XLA made of the jnp step (a reduction that read the
    state, then an update that read it again and wrote it: PR 35 – PR 44)
    cannot come back unseen. The one gated-attention layer (2 KV heads of
    256) takes the decode kernel. At 512 experts top 10 the step's experts
    are the routed form (PR 36): two `moe_gmm` calls in the body of each of
    the two runs' scans (PR 64: gate and up are ONE call, and no
    `f32[1280,512]` product of either goes to HBM between the calls)."""
    from symmetry_tpu.models import gdn, hybrid, llama, moe

    for module in (llama, gdn, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cfg = llama.preset("qwen3-next-80b-a3b")
    B, T = 128, 640

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    cache = shaped(lambda: llama.init_cache(cfg, B, T, jnp.bfloat16,
                                            quantized=True))
    assert cache.ssm.shape == (3, 128, 32, 128, 128)
    assert cache.conv.shape == (3, 3, 128, 8192)
    assert cache.k.shape == (1, 128, 640, 2, 256)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
        donate_argnums=(2,)).lower(params, tok, cache).compile()
    memory = compiled.memory_analysis()
    state_bytes = 3 * 128 * 32 * 128 * 128 * 4
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 3    # under one layer
    text = compiled.as_text()
    # the attention layer's K/V (2 int8 heads of 256 a position) lies
    # head-major where it is written and read: until PR 62 the row-window
    # scatter had the step relay both 42 MB leaves (`copy.460` / `.461
    # s8[1,128,2,640,256]`, 2.4% of the cell's busy time)
    moved, _ = leaf_moves(text, 1, 128, 640, 256)
    assert not moved, moved[0]
    moved = plane_moves(text, 1, 128, 640)      # nor its scale planes
    assert not moved, moved[0]
    gmm_calls = len(re.findall(r"%moe_gmm[.\d]* = ", text))
    assert gmm_calls == 2 * len(hybrid.runs(cfg))
    assert gmm_results(text) == (["bf16[1280,512]"] * 2
                                 + ["f32[1280,2048]"] * 2)
    assert "f32[1280,512]" not in text
    gdn_runs = sum(kind == "linear_attention"
                   for kind, _, _ in hybrid.runs(cfg))
    assert gdn_runs == 1
    assert len(re.findall(r"%gdn_step[.\d]* = ", text)) == gdn_runs
    # + the attention layer's decode kernel
    assert text.count("tpu_custom_call") == 1 + gmm_calls + gdn_runs
    shape = r"f32\[3,128,32,128,128\]"
    whole = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= {shape}\S* (copy|transpose)\(", line)]
    assert not whole, whole[0]
    # the stack is an operand of the kernel call (and of the plumbing that
    # carries it: tuples, loops, bitcasts) and of no fusion, in or out
    stack = set(re.findall(rf"(%[\w.\-]+)(?: =|:) {shape}", text))
    fused = [line.strip()[:160] for line in text.splitlines()
             if " fusion(" in line and (
                 re.search(shape, line.split(" fusion(")[0])
                 or stack & set(re.findall(r"%[\w.\-]+",
                                           line.split(" fusion(")[1])))]
    assert stack and not fused, fused[:1]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gdn_step_compiles_at_the_cells_state(one_chip, no_cache, dtype):
    """The one-pass delta-rule step alone at qwen3-next-80b-a3b's cell —
    `[3, 128, 32, 128, 128]`, 128 slots, the gate's head tile (a whole slot,
    2 MB of float32, a grid step: Mosaic refuses here what overruns VMEM) —
    with the layer traced: the donated stack is aliased through the call,
    and the program holds no copy of it and no temporary of a layer's
    size."""
    from symmetry_tpu.ops import ssm_step

    L, B, H, Dk, Dv = 3, 128, 32, 128, 128
    assert ssm_step.head_tile(H, Dk, Dv, jnp.dtype(dtype).itemsize) == 32

    def arg(shape, of=jnp.float32):
        return jax.ShapeDtypeStruct(shape, of, sharding=one_chip)

    compiled = jax.jit(ssm_step.gdn_step, donate_argnums=(0,)).lower(
        arg((L, B, H, Dk, Dv), dtype), arg((), jnp.int32), arg((B, H)),
        arg((B, H)), arg((B, H, Dk)), arg((B, H, Dk)), arg((B, H, Dv))
    ).compile()
    memory = compiled.memory_analysis()
    state_bytes = L * B * H * Dk * Dv * jnp.dtype(dtype).itemsize
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // (8 * L)
    text = compiled.as_text()
    assert len(re.findall(r"%gdn_step[.\d]* = ", text)) == 1
    whole = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= (f32|bf16)\[3,128,32,128,128\]\S* "
                          r"(copy|transpose|fusion)\(", line)]
    assert not whole, whole[0]


@pytest.mark.parametrize("preset,rows,bucket,stack", [
    ("qwen3-next-80b-a3b", 2, 256, r"s8\[4,512,(2048,512|512,2048)\]"),
    ("granite-4.0-h-small", 4, 128, r"s8\[10,72,(4096,768|768,4096)\]"),
    # the homogeneous trunk (models/llama.py run_layers; PR 49): one scan
    # over all the layers, the stacks its constants and not its operands.
    # sdar's 512 tokens are a DECODE forward's too (128 slots x a block)
    ("sdar-30b-a3b-chat", 4, 128, r"s8\[12,128,(2048,768|768,2048)\]"),
    ("keye-vl-2.0-30b-a3b", 1, 6144, r"s8\[4,128,(2048,768|768,2048)\]"),
])
def test_prefill_reads_the_expert_stacks_where_they_lie(
        one_chip, no_cache, monkeypatch, preset, rows, bucket, stack):
    """A routed prefill dispatch of each one-chip expert cell (512 tokens;
    keye's smallest routed bucket): the routed
    form's three matmuls a layer are TWO `moe_gmm` calls (ops/gmm.py: the
    (gate, up) pair's and down's, in the body of a run's scan: the pair
    fits VMEM at every cell's shape) whose weight operands are the WHOLE
    int8 stacks as they lie in HBM — no slice, copy or relayout of a stack
    or of a layer of it beside a call (PR 29's lesson: a slice around a
    kernel is a copy; here 0.5 GB a matmul), no stack an operand of an XLA
    fusion, and no dense mixture left (its [tokens, experts, width]
    product)."""
    from symmetry_tpu.models import hybrid, llama, mamba2, moe
    from symmetry_tpu.ops import sparse_attention

    for module in (llama, mamba2, moe, sparse_attention):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cfg = llama.preset(preset)
    # one scan a run of layers of one kind; the homogeneous trunk is one
    n_runs = len(hybrid.runs(cfg)) if getattr(cfg, "layer_types",
                                              None) else 1
    tokens = rows * bucket
    assert moe.moe_route(tokens, cfg.num_experts,
                         cfg.num_experts_per_tok) == "routed"

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    cache = shaped(lambda: llama.init_cache(cfg, rows, bucket, jnp.bfloat16,
                                            quantized=True))
    tok = jax.ShapeDtypeStruct((rows, bucket), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    # the engine's precision, not the CPU tests' "highest" (conftest.py),
    # which the flash kernel's bf16 dots cannot take
    with jax.default_matmul_precision("default"):
        text = jax.jit(
            lambda p, t, c, n: llama.forward_hidden(p, cfg, t, c, n,
                                                    prefill_flash=True),
            donate_argnums=(2,)).lower(params, tok, cache, lens).compile(
        ).as_text()
    pairs, (A, F) = tokens * cfg.num_experts_per_tok, re.search(
        r"\((\d+),(\d+)\|", stack).groups()
    assert gmm_results(text) == ([f"bf16[{pairs},{F}]"] * n_runs
                                 + [f"f32[{pairs},{A}]"] * n_runs)
    names = set(re.findall(rf"(%[\w.\-]+)(?: =|:) {stack}", text))
    assert names
    touched = [line.strip()[:160] for line in text.splitlines()
               if re.search(rf"= {stack}\S* (copy|transpose|fusion|"
                            rf"dynamic-slice)\(", line)
               or (" fusion(" in line and names & set(re.findall(
                   r"%[\w.\-]+", line.split(" fusion(")[1])))]
    assert not touched, touched[0]
    # one layer's leaf, with or without the stack axis kept
    layer = re.sub(r"^s8\\\[\d+,", r"s8\\[(1,)?", stack)
    sliced = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= {layer}", line)]
    assert not sliced, sliced[0]
    mixture = [line.strip()[:160] for line in text.splitlines()
               if re.search(rf"\[{tokens},{cfg.num_experts},\d+\]", line)]
    assert not mixture, mixture[0]


def test_decode_kernel_takes_a_selection_at_the_long_document_cell(
        one_chip, no_cache):
    """keye-vl-2.0-30b-a3b's cell (64 slots x 16,384, 4 int8 KV heads): the
    selection rides as a third scale plane; the cache is still read where
    it lies, and the keep plane is the one array built for the call."""
    L, B, T, K, nq, D = 4, 64, 16384, 4, 32, 128

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    kv = shape((L, B, T, K, D), jnp.int8)
    scale = shape((L, B, K, T), jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, layer, n, ks, vs, keep: decode_attention(
            q, k, v, layer, n, ks, vs, keep)
    ).lower(shape((B, nq, D), jnp.bfloat16), kv, kv, shape((), jnp.int32),
            shape((B,), jnp.int32), scale, scale,
            shape((B, T), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r" copy\(%[kv]\.", text)
    assert not whole_cache_copies(text)
    # the [B, K, T] float32 keep plane (16 MB) and nothing of the cache
    assert compiled.memory_analysis().temp_size_in_bytes < 20 * 2**20


@pytest.mark.parametrize("bucket", [1024, 8192, 14848])
def test_sparse_flash_kernel_compiles_at_the_cells_buckets(
        one_chip, no_cache, bucket):
    """`dsa_flash` (ops/sparse_attention.py) at keye-vl-2.0-30b-a3b's head
    shape and the cell's buckets: K and V are blocks of a grid axis, never
    whole in VMEM, so a 14,848-token prompt compiles as a 1,024-token one
    does."""
    from symmetry_tpu.ops import sparse_attention as sa

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    kv = shape((1, bucket, 4, 128), jnp.bfloat16)
    compiled = jax.jit(sa.flash_sparse).lower(
        shape((1, bucket, 32, 128), jnp.bfloat16), kv, kv,
        shape((1, bucket, bucket), jnp.int8)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert sa.NAME in text


@pytest.mark.parametrize("bucket", [6144, 14848])
def test_select_kernel_compiles_at_the_cells_buckets(one_chip, no_cache,
                                                     bucket):
    """`dsa_select` (ops/sparse_attention.py) at keye-vl-2.0-30b-a3b's
    indexer (16 heads of 64, topk 2,048): a tile's [256, S] keys, the
    prompt's index keys and the mask block fit the kernel's VMEM, and what
    XLA builds around the call is the operands' transposes alone — no
    [256, 16, S] products, no [256, S] scores."""
    from symmetry_tpu.ops import sparse_attention as sa

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    compiled = jax.jit(
        lambda qi, ki, w, n: sa.prefill_keep(qi, ki, w, n, 2048,
                                             interpret=False)
    ).lower(shape((1, bucket, 16, 64), jnp.bfloat16),
            shape((1, bucket, 64), jnp.bfloat16),
            shape((1, bucket, 16), jnp.bfloat16),
            shape((1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and sa.SELECT_NAME in text
    assert not re.search(rf"f32\[[\d,]*{bucket}\]", text)
    # the transposed index queries (bf16) and weights (f32), nothing S x S
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**20


def test_select_decode_form_reads_the_index_cache_where_it_lies(
        one_chip, no_cache):
    """The decode form at the cell's cache (4 layers x 64 slots x 16,384
    index keys of 64): XLA keeps that cache position-minor, so the swap the
    kernel asks for is a bitcast, and layer, slot and the live groups are
    DMA addressing — no layer's slice, no transpose, no copy of it."""
    from symmetry_tpu.ops import sparse_attention as sa

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    B, T = 64, 16384
    compiled = jax.jit(
        lambda qi, idx, w, pos, kv, layer: sa.cache_keep(
            qi, idx, w, pos, kv, 2048, layer=layer, interpret=False)
    ).lower(shape((B, 1, 16, 64), jnp.bfloat16),
            shape((4, B, T, 64), jnp.bfloat16),
            shape((B, 1, 16), jnp.bfloat16), shape((B, 1), jnp.int32),
            shape((B,), jnp.int32), shape((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and sa.SELECT_NAME in text
    made = [line.strip()[:160] for line in text.splitlines()
            if re.search(r"= bf16\[(4,)?64,(16384,64|64,16384)\]\S* "
                         r"(?!bitcast|parameter)\w", line)]
    assert not made, made[0]
    # the [B, T] int32 mask and the work list: no score, no cache
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**20


@pytest.mark.parametrize("program", ["prefill 6144", "decode 64 x 16384"])
def test_keye_programs_select_in_the_kernel_alone(one_chip, no_cache,
                                                  monkeypatch, program):
    """keye-vl-2.0-30b-a3b's served programs, whole: the indexer and its
    threshold are the `dsa_select` call — no float32 [.., 16 heads, S]
    products, no [tile, S] / [slots, capacity] scores, and the index cache
    is written in place and read where it lies."""
    from symmetry_tpu.models import llama, moe
    from symmetry_tpu.ops import sparse_attention as sa

    for module in (llama, moe, sa):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cfg = llama.preset("keye-vl-2.0-30b-a3b")
    prefill = program.startswith("prefill")
    rows, capacity, S = (1, 6144, 6144) if prefill else (64, 16384, 1)

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    cache = shaped(lambda: llama.init_cache(cfg, rows, capacity,
                                            jnp.bfloat16, quantized=True))
    tok = jax.ShapeDtypeStruct((rows, S), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    with jax.default_matmul_precision("default"):
        text = jax.jit(
            (lambda p, t, c, n: llama.forward_hidden(
                p, cfg, t, c, n, prefill_flash=True)) if prefill else
            (lambda p, t, c, n: llama.forward_hidden(p, cfg, t, c)),
            donate_argnums=(2,)).lower(params, tok, cache, lens).compile(
        ).as_text()
    assert len(re.findall(rf"%{sa.SELECT_NAME}[.\d]* = ", text)) == 1
    heads, T = cfg.sparse.index_heads, capacity
    scored = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= f32\[[\d,]*{heads},{T}\]", line)
              or (prefill and re.search(rf"= [fsu]32\[(1,)?256,{T}\]", line))]
    assert not scored, scored[0]
    # (a prefill selects over its own index keys and only writes its one-
    # row scratch cache; the decode step reads the slots' cache)
    moved = [line.strip()[:160] for line in text.splitlines()
             if not prefill and re.search(
                 rf"= bf16\[(4,)?{rows},({T},64|64,{T})\]\S* "
                 rf"(copy|transpose|dynamic-slice)\(", line)]
    assert not moved, moved[0]


def _lfm2_shapes(one_chip, rows, capacity):
    from symmetry_tpu.models import llama

    cfg = llama.preset("lfm2-8b-a1b")

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    cache = shaped(lambda: llama.init_cache(cfg, rows, capacity,
                                            jnp.bfloat16, quantized=True))
    return cfg, params, cache


@pytest.fixture(scope="module")
def lfm2_decode_step(one_chip):
    """lfm2-8b-a1b's decode trunk at its cell (128 slots x 640), all 24
    layers in 13 scans, compiled once for a v5e: (compiled program, its
    text)."""
    from jax.experimental.compilation_cache import compilation_cache
    from symmetry_tpu.models import llama, moe

    B, T = 128, 640
    cfg, params, cache = _lfm2_shapes(one_chip, B, T)
    assert cache.ssm is None and cache.conv.shape == (18, 2, 128, 2048)
    assert cache.k.shape == (6, 128, 640, 4, 128)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    before = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as patch:
        for module in (llama, moe):
            patch.setattr(module, "interpret_mode", lambda: False)
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            paths = llama.attention_paths(cfg, T, None, batch=B, kv_bytes=1)
            assert paths == {"prefill": "pallas", "decode": "pallas",
                             "decode_slot_tile": 128, "decode_block_t": 256}
            assert moe.moe_route(B, 32, 4) == "dense-mixture"
            with jax.default_matmul_precision("default"):
                compiled = jax.jit(
                    lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
                    donate_argnums=(2,)).lower(params, tok, cache).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", before)
            compilation_cache.reset_cache()
    return compiled, compiled.as_text()


def test_lfm2_decode_step_moves_the_tails_in_place(lfm2_decode_step):
    """The 18.9 MB stack of tails (`bf16[18,2,128,2048]`: a short
    convolution's whole state) is donated in, aliased out and never copied
    or relaid whole; the step's experts are the dense mixture (128 tokens
    are under `ROUTED_FROM[(32, 4)]`), so the program's only custom calls
    are the six attention layers' decode kernel."""
    compiled, text = lfm2_decode_step
    memory = compiled.memory_analysis()
    tails = 18 * 2 * 128 * 2048 * 2
    kv = 2 * 6 * 128 * 640 * 8 * 64
    assert memory.alias_size_in_bytes >= tails + kv
    assert text.count("tpu_custom_call") == 6
    whole = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= bf16\[18,2,128,2048\]\S* (copy|transpose)\(",
                          line)]
    assert not whole, whole[0]
    # the weights stream once: no expert stack is copied or relaid
    stacks = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= s8\[22,32,\d+,\d+\]\S* (copy|transpose)\(",
                           line)]
    assert not stacks, stacks[0]


def test_lfm2_decode_step_reads_the_kv_pairs_where_they_lie(lfm2_decode_step):
    """Heads of 64 through the decode kernel: the K/V leaves lie as the
    pair view assumes (`s8[6,128,640,4,128]` in four-row tiles of 128
    lanes, dense: no padded half), each of the six calls takes the WHOLE
    stack as a bitcast, and no layer's 42 MB of K or V — nor the stack —
    is sliced, copied, relaid or staged in fast memory (the XLA path made
    twelve such copies a step: 1 GB moved where 0.27 GB is live); the
    stack's only writers are the in-place scatters of `write_kv`. The
    step's temporaries are megabytes, not the 1.5 GB of staged layers."""
    compiled, text = lfm2_decode_step
    assert len(re.findall(r"%decode_attention[.\d]* = ", text)) == 6
    leaf = r"s8\[6,128,640,4,128\]"
    laid = set(re.findall(leaf + r"(\{[^}]*\})", text))
    assert laid == {"{4,3,2,1,0:T(4,128)(4,1)}"}, laid
    assert len(re.findall(r"= s8\[6,128,2560,128\]\S* bitcast\(", text)) == 12
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= s8\[\d+,128,(640,4|2560),128\]\S* (copy|"
                          r"copy-start|transpose|slice|dynamic-slice)\(", line)
             or re.search(r"= s8\[128,(640,4|2560),128\]", line)
             or (re.search(rf"= {leaf}\S* fusion\(", line)
                 and "scatter" not in line)]
    assert not moved, moved[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("rows,bucket", [(2, 256), (8, 64)])
def test_lfm2_prefill_lowers_flash_at_a_head_of_64_and_routes_its_experts(
        one_chip, no_cache, monkeypatch, rows, bucket):
    """A 512-token prefill dispatch of the lfm2 cell: the flash kernel
    lowers at a `[block, 64]` tile (one call in each of the six attention
    runs: what `startup.attention.prefill: pallas` promises), the 22
    expert layers are routed (`moe_gmm`: two calls — the (gate, up) pair's
    and down's — in each of the twelve
    scans that hold expert layers; the run of the two dense layers holds
    none) over the WHOLE `[22, 32, ...]` int8 stacks, indexed by the
    layer's place among the expert layers."""
    from symmetry_tpu.models import hybrid, llama, moe

    for module in (llama, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cfg, params, cache = _lfm2_shapes(one_chip, rows, bucket)
    assert moe.moe_route(rows * bucket, 32, 4) == "routed"
    tok = jax.ShapeDtypeStruct((rows, bucket), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    with jax.default_matmul_precision("default"):
        text = jax.jit(
            lambda p, t, c, n: llama.forward_hidden(p, cfg, t, c, n,
                                                    prefill_flash=True),
            donate_argnums=(2,)).lower(params, tok, cache, lens).compile(
        ).as_text()
    expert_runs = [r for r in hybrid.runs(cfg) if cfg.ffn_kind(r[1]) == "moe"]
    assert len(expert_runs) == 12 and len(hybrid.runs(cfg)) == 13
    gmm_calls = len(re.findall(r"%moe_gmm[.\d]* = ", text))
    assert gmm_calls == 2 * len(expert_runs)
    assert gmm_results(text) == (["bf16[2048,1792]"] * 12
                                 + ["f32[2048,2048]"] * 12)
    assert text.count("tpu_custom_call") == gmm_calls + 6   # + flash
    stack = r"s8\[22,32,(2048,1792|1792,2048)\]"
    touched = [line.strip()[:160] for line in text.splitlines()
               if re.search(rf"= {stack}\S* (copy|transpose|fusion|"
                            rf"dynamic-slice)\(", line)]
    assert not touched, touched[0]


def _lowered_programs_tool(one_chip, monkeypatch):
    """`tools/lowered_programs.py` (the engine's own jits without its
    arrays: `bare_engine`, `decode_state`, `programs`) with every shape it
    makes placed on the described chip, and that `shaped` itself: a
    function's output shapes there."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lowered_programs", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "lowered_programs.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    monkeypatch.setattr(tool, "shapes", shaped)
    return tool, shaped


def _sdar_engine(one_chip, monkeypatch):
    """sdar-30b-a3b-chat's engine programs without its arrays
    (`tools/lowered_programs.py bare_engine`: 128 slots x 640, two denoise
    steps a block), the kernels compiled and not interpreted: (config,
    engine, `shaped` — a function's output shapes on the described chip —
    and the int8 parameters' shapes)."""
    from symmetry_tpu.models import llama, moe

    for module in (llama, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    tool, shaped = _lowered_programs_tool(one_chip, monkeypatch)
    cfg = llama.preset("sdar-30b-a3b-chat")
    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    return cfg, tool.bare_engine(cfg), shaped, params


def test_sdar_decode_dispatch_denoises_and_commits_in_place(
        one_chip, no_cache, monkeypatch):
    """sdar-30b-a3b-chat's decode dispatch, whole, for a described v5e: four
    blocks a slot, each two denoise forwards and a commit over [128, 4]
    positions (a scan of blocks around a loop of forwards around the scan
    of layers). The 1 GB cache rides every loop's carry: no level of the
    nesting may copy or relay it, the experts run the grouped-matmul kernel
    (a forward routes 4,096 pairs), and attention is the decode kernel with
    the block of queries as its q tile: no layer's K or V is sliced out of
    the cache, staged or relaid (the XLA route's 42 MB each a layer)."""
    from symmetry_tpu.engine import engine as eng_mod
    from symmetry_tpu.models import llama

    cfg, e, shaped, params = _sdar_engine(one_chip, monkeypatch)
    state = shaped(lambda: eng_mod.DecodeState(
        cache=llama.init_cache(cfg, 128, 640, jnp.bfloat16, quantized=True,
                               count_experts=True),
        last_token=jnp.zeros((128,), jnp.int32),
        temperature=jnp.zeros((128,), jnp.float32),
        top_p=jnp.ones((128,), jnp.float32),
        top_k=jnp.zeros((128,), jnp.int32),
        rng=jax.random.split(jax.random.key(0), 128)))
    park = jax.ShapeDtypeStruct((128,), bool, sharding=one_chip)
    with jax.default_matmul_precision("default"):
        compiled = e._decode.lower(params, state, park).compile()
    text = compiled.as_text()
    whole = r"= (s8\[12,128,640,4,128\]|f32\[12,128,4,640\])\S* "
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(whole + r"(copy|transpose)\(", line)
             or (re.search(whole + r"fusion\(", line)
                 and "kind=kCustom" not in line)]   # the in-place scatters
    assert not moved, moved[0]
    assert len(re.findall(whole + r"fusion\(", text)) == 8  # k, v, 2 scales
    # ... and nothing yields ONE layer's K or V (what the XLA route read: a
    # [1, 128, 640, 4, 128] slice of each, staged and relaid, whatever the
    # slots' fill): the kernel reads each slot's live blocks where they lie
    layer = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= s8\[(1,)?128,640,4,128\]", line)]
    assert not layer, layer[0]
    # two forwards' trunks (the denoise loop's and the commit's), a call a
    # scan body each
    assert len(re.findall(r"%decode_attention[.\d]* = ", text)) == 2
    # two forwards' trunks (the denoise loop's and the commit's), a layer's
    # (gate, up) pair call and its down call each, compiled once a scan
    # body: [128 slots x 4 positions x top 8, ...] and no float32
    # [4096, 768] product between them
    assert gmm_results(text) == (["bf16[4096,768]"] * 2
                                 + ["f32[4096,2048]"] * 2)
    assert "f32[4096,768]" not in text
    # ... whose weight operands are the layers' stacks as they lie: nothing
    # yields one layer's experts (sliced by the layer scan they were a
    # 0.6 GB copy a layer, 40% of the device's time: PERF.md, PR 49)
    sliced = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= s8\[(1,)?128,(2048,768|768,2048)\]", line)]
    assert not sliced, sliced[0]
    # the sampler's window over [128, 4, 151936] logits is selected in
    # stages (ops/sampling.py top_k_route): no sort ranks more entries a row
    # than the largest stage — the 8,192 candidates of the two-stage form
    # were one `sort f32[128,4,8192]`, the capture's largest op (PERF.md,
    # PR 55) — and a stage's rows are ranked flat, [512, n]
    from symmetry_tpu.ops import sampling
    route = sampling.top_k_route(cfg.vocab_size)
    ranked = [s["groups"] for s in route["stages"]] + [route["ranked"]]
    assert ranked == [1187, 256, 2048]
    sorts = [(tuple(int(d) for d in m.group(1).split(",")), int(m.group(2)))
             for m in re.finditer(
                 r"= \(?\w+\[([\d,]+)\][^\n]*? sort\([^\n]*dimensions=\{(\d+)\}",
                 text)]
    assert sorts
    # (the routed experts' sort of a forward's 4,096 pairs is one row)
    wide = [dims for dims, axis in sorts
            if len(dims) > 1 and dims[axis] > max(ranked)]
    assert not wide, wide
    for n in ranked:
        assert ((512, n), 1) in sorts, (n, sorts)
    assert not [dims for dims, _ in sorts if dims[:2] == (128, 4)], sorts
    # weights 8.4 GB and the cache 1.04 GB are arguments; what the program
    # adds (logits of [512, 151936] and the sampler's windows) stays small
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


@pytest.mark.parametrize("rows,gmm_calls,mixture_tokens", [
    # the prompt's forward: 1 x 64 tokens a mixture, 256 and 1,024 routed;
    # the opening block's two trunks (the denoise loop's and the commit's):
    # 4 and 16 tokens routed, two `moe_gmm` calls a scan body each (the
    # (gate, up) pair's and down's)
    (1, 4, [64]), (4, 6, []),
    # 16 rows x 4 positions = 64 tokens: inside (128, 8)'s band, the mixture
    (16, 2, [64])])
def test_sdar_admission_routes_its_small_opening_blocks(
        one_chip, no_cache, monkeypatch, rows, gmm_calls, mixture_tokens):
    """sdar-30b-a3b-chat's admission program (`bd_prefill`: the prompt's
    forward, then two denoise forwards and the commit over [rows, 4]
    positions), whole, at the 64 bucket for a described v5e. Under 64
    tokens the opening block's forwards take the routed form over the
    kernel — no `[tokens, 128, 768]` product of every expert (PR 57: 604 MB
    a layer streamed for 32-256 pairs) — and from 64 the mixture, as
    `models/moe.py moe_route` reads its band. (This model's scale planes
    have crashed the v5e compiler's repacker once: PERF.md §7 (7).)"""
    from symmetry_tpu.models import llama, moe

    cfg, e, shaped, params = _sdar_engine(one_chip, monkeypatch)
    block, bucket = cfg.diffusion.block, 64

    def vec(dtype):
        return jax.ShapeDtypeStruct((rows,), dtype, sharding=one_chip)

    scratch = shaped(lambda: llama.init_cache(
        cfg, rows, bucket + block, jnp.bfloat16, quantized=True,
        count_experts=True))
    keys = shaped(lambda: jax.random.split(jax.random.key(0), rows))
    with jax.default_matmul_precision("default"):
        text = e._prefill.lower(
            params, jax.ShapeDtypeStruct((rows, bucket), jnp.int32,
                                         sharding=one_chip),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.float32),
            vec(jnp.int32), keys, scratch).compile().as_text()
    opening = rows * block
    assert moe.moe_route(opening, 128, 8) == (
        "dense-mixture" if opening in mixture_tokens else "routed")
    assert len(re.findall(r"%moe_gmm[.\d]* = ", text)) == gmm_calls
    for tokens in {opening, rows * bucket}:
        product = re.findall(rf"(?:bf16|f32)\[{tokens},128,768\]", text)
        assert bool(product) == (tokens in mixture_tokens), (tokens,
                                                             product[:1])
    if not mixture_tokens:
        # every forward routed: nothing yields one layer's experts (the
        # mixture's dots are what read a layer's slice of the stacks)
        sliced = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= s8\[(1,)?128,(2048,768|768,2048)\]",
                               line)]
        assert not sliced, sliced[0]


def _kanana_text(one_chip, monkeypatch, rows, capacity, S):
    """kanana-2-30b-a3b's trunk (all 8 layers: the dense one and seven
    expert layers) compiled for a described v5e at [rows, S] tokens over a
    rows x capacity latent cache: (config, optimised HLO)."""
    from symmetry_tpu.models import llama, moe

    for module in (llama, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cfg = llama.preset("kanana-2-30b-a3b")

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    cache = shaped(lambda: llama.init_cache(cfg, rows, capacity,
                                            jnp.bfloat16))
    assert cache.k.shape == (8, rows, capacity, 640) and cache.v is None
    tok = jax.ShapeDtypeStruct((rows, S), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    prefill = S > 1
    with jax.default_matmul_precision("default"):
        text = jax.jit(
            (lambda p, t, c, n: llama.forward_hidden(
                p, cfg, t, c, n, prefill_flash=True)) if prefill else
            (lambda p, t, c, n: llama.forward_hidden(p, cfg, t, c)),
            donate_argnums=(2,)).lower(params, tok, cache, lens).compile(
        ).as_text()
    return cfg, text


def test_kanana_decode_step_reads_the_latent_rows_where_they_lie(
        one_chip, no_cache, monkeypatch):
    """The decode step at the cell (64 slots x 11,776): attention is the
    `mla_decode` call in each of the two scans (the dense layer's, the
    expert layers'), the absorbed form — no [slots, capacity, 32 heads, ..]
    expansion of the cache exists, and the cache's one leaf is neither
    copied nor sliced a layer at a time."""
    from symmetry_tpu.ops import mla_attention as mla

    B, T = 64, 11776
    cfg, text = _kanana_text(one_chip, monkeypatch, B, T, 1)
    assert len(re.findall(rf"%{mla.DECODE_NAME}[.\d]* = ", text)) == 2
    H = cfg.num_heads
    expanded = [line.strip()[:160] for line in text.splitlines()
                if re.search(rf"= \w+\[{B},{T},{H},\d+\]", line)
                or re.search(rf"= \w+\[{B},{H},{T},\d+\]", line)
                or re.search(rf"= \w+\[{B},{H},(1,)?{T}\]", line)]
    assert not expanded, expanded[0]
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= bf16\[(\d+,)?{B},{T},640\]\S* "
                          rf"(copy|transpose|dynamic-slice|fusion)\(", line)
             and "dynamic-update-slice" not in line
             and "scatter" not in line]
    assert not moved, moved[0]


def test_kanana_prefill_expands_through_flash_and_routes_its_experts(
        one_chip, no_cache, monkeypatch):
    """A 9,728-token prefill row: attention is the flash kernel at keys of
    192 and values of 128 in its wide tiles (one call a scan: Mosaic takes
    the 512 x 512 walk with a head's whole K and V in VMEM at this length),
    the seven expert layers are
    routed — two `moe_gmm` calls, the (gate, up) pair's and down's, whose
    weight operands are the layers' int8 stacks as they lie — and no
    layer's experts are sliced out."""
    from symmetry_tpu.ops import flash, gmm

    cfg, text = _kanana_text(one_chip, monkeypatch, 1, 9728, 9728)
    assert len(re.findall(rf"%{flash.WIDE_NAME}[.\d]* = ", text)) == 2
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) >= 4
    assert len(re.findall(rf"%{gmm.NAME}[.\d]* = ", text)) == 2
    sliced = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= s8\[(1,)?128,(2048,768|768,2048)\]\S* "
                           r"(copy|dynamic-slice|fusion)\(", line)]
    assert not sliced, sliced[0]


def _smallthinker_text(one_chip, monkeypatch, rows, capacity, S):
    """smallthinker-21b-a3b's trunk (all 12 layers: six runs of one
    attention kind) compiled for a described v5e at [rows, S] tokens over
    int8 leaves — a served cache's (full leaves of `capacity` rows, rings
    of the window's 4,096) for a decode step, a prefill scratch's (both
    `capacity` long) for S > 1: (config, optimised HLO)."""
    from symmetry_tpu.models import llama, moe

    for module in (llama, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cfg = llama.preset("smallthinker-21b-a3b")

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    prefill = S > 1
    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    cache = shaped(lambda: llama.init_cache(
        cfg, rows, capacity, jnp.bfloat16, quantized=True,
        count_experts=True, ring=None if prefill else cfg.sliding_window))
    tok = jax.ShapeDtypeStruct((rows, S), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    with jax.default_matmul_precision("default"):
        text = jax.jit(
            (lambda p, t, c, n: llama.forward_hidden(
                p, cfg, t, c, n, prefill_flash=True)) if prefill else
            (lambda p, t, c, n: llama.forward_hidden(p, cfg, t, c)),
            donate_argnums=(2,)).lower(params, tok, cache, lens).compile(
        ).as_text()
    return cfg, cache, text


def test_smallthinker_decode_step_reads_both_leaves_where_they_lie(
        one_chip, no_cache, monkeypatch):
    """The decode step at the cell (64 slots: full leaves of 11,776 rows,
    rings of 4,096): attention is one `swa_decode` call in each of the six
    scans, over the full leaves in three and over the rings in three, and
    neither leaf is copied or sliced a layer at a time."""
    from symmetry_tpu.ops import decode_attention as da

    B, T = 64, 11776
    cfg, cache, text = _smallthinker_text(one_chip, monkeypatch, B, T, 1)
    assert cache.k.shape == (3, B, T, 4, 128)
    assert cache.kw.shape == (9, B, 4096, 4, 128)
    assert len(re.findall(rf"%{da.WINDOW_NAME}[.\d]* = ", text)) == 6
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= s8\[(\d+,)?{B},({T}|4096),4,128\]\S* "
                          rf"(copy|transpose|dynamic-slice|fusion)\(", line)
             and "dynamic-update-slice" not in line
             and "scatter" not in line]
    assert not moved, moved[0]


def test_smallthinker_prefill_walks_wide_tiles_and_routes_its_experts(
        one_chip, no_cache, monkeypatch):
    """An 8,320-token prefill row over its scratch: attention is the wide
    flash walk in every one of the six scans (28 query heads over 4 KV
    heads; a window layer's call bounded by its window), the experts are
    routed — two `moe_gmm` calls a scan, the (gate, up) pair's and down's,
    whose weight operands are the layers' int8 stacks as they lie."""
    from symmetry_tpu.ops import flash, gmm

    cfg, cache, text = _smallthinker_text(one_chip, monkeypatch, 1, 8320,
                                          8320)
    assert cache.kw.shape == (9, 1, 8320, 4, 128)
    assert len(re.findall(rf"%{flash.WIDE_NAME}[.\d]* = ", text)) == 6
    assert len(re.findall(rf"%{gmm.NAME}[.\d]* = ", text)) == 12
    sliced = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= s8\[(1,)?64,(2560,768|768,2560)\]\S* "
                           r"(copy|dynamic-slice|fusion)\(", line)]
    assert not sliced, sliced[0]


def _nemotron_shapes(one_chip, rows, capacity):
    from symmetry_tpu.models import llama

    cfg = llama.preset("nemotron-3-nano-30b-a3b")

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
        slice_above=1 << 40))
    cache = shaped(lambda: llama.init_cache(cfg, rows, capacity,
                                            jnp.bfloat16, quantized=True))
    return cfg, params, cache


def test_nemotron_decode_step_steps_the_grouped_state_in_place(
        one_chip, no_cache, monkeypatch):
    """nemotron-3-nano-30b-a3b's decode trunk at its cell (64 slots x 640,
    all 52 blocks as 29 trunk layers in 19 runs): the 3.09 GB recurrent
    state of 23 mamba blocks is donated in, aliased out and stepped where it
    lies by ONE grouped `ssm_step` call a run of mamba layers (8 rows of B
    and C a slot, 16 heads unrolled over two groups) and no XLA fusion; the
    attention blocks (16 queries a K/V head, a row of 256 values) take the
    decode kernel over K/V leaves that no op copies or relays (PR 62); the
    expert layers are ROUTED — two `moe_gmm` calls a run
    over the 32 HELD experts' stacks where they lie: the mixture had XLA
    copy each layer's two [32, 2688, 1920] slices out of the stack first
    (375 MB of temporaries; 46.5 ms a step on the chip, PERF.md PR 61) —
    and the whole program fits the chip."""
    from symmetry_tpu.models import hybrid, llama, mamba2, moe

    for module in (llama, mamba2, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cfg, params, cache = _nemotron_shapes(one_chip, 64, 640)
    assert cache.ssm.shape == (23, 64, 64, 64, 128)
    assert cache.k.shape == (6, 64, 640, 2, 128)
    assert mamba2.step_form(cfg) == {"form": "pallas", "head_tile": 64,
                                     "groups": 8}
    assert moe.moe_route(64, 128, 6, 32) == "routed"
    tok = jax.ShapeDtypeStruct((64, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
        donate_argnums=(2,)).lower(params, tok, cache).compile()
    memory = compiled.memory_analysis()
    state_bytes = 23 * 64 * 64 * 64 * 128 * 4
    assert memory.alias_size_in_bytes >= state_bytes
    # the K/V leaves (2 int8 heads a position: s8[6, 64, 640, 2, 128]) lie
    # head-major — the chip's own layout of the donated argument,
    # `{4,2,3,1,0:T(8,128)(4,1)}` — through the whole step: the six layers'
    # head-indexed scatters write them in place (`write_kv` under
    # `kv_head_major`) and the decode kernel's [L, B * 2, T, D] view is a
    # bitcast. Under the row-window scatter this program held 36 copies of
    # a 63 MB leaf (to a (4, 128) tile for the scatter, back, and out to
    # the kernel's view, K and V, a layer: two fifths of the cell's step,
    # PERF.md PR 62) and 374.5 MiB of temporaries
    text = compiled.as_text()
    moved, written = leaf_moves(text, 6, 64, 640, 128)
    assert not moved, moved[0]
    assert len(written) == 2 * 6, written
    # ... and their scale planes `f32[6,64,2,640]` in the (2, 128) tiles the
    # kernel reads, written by a select over the layer's slice: a scatter
    # had the step relay each plane to a padded tile and back, a layer (22
    # + 12 copies of 2 MB at 0.19 ms a pass, 5.1 ms of the 26 ms step left)
    moved = plane_moves(text, 6, 64, 640)
    assert not moved, moved[0]
    assert memory.temp_size_in_bytes < 64 * 2**20        # 38.8 MiB
    # weights 9.92 GB + state and K/V 3.27 GB + temporaries: inside 16 GB
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 14.5e9)
    runs = hybrid.runs(cfg)
    mamba_runs = sum(kind == "mamba" for kind, _, _ in runs)
    attn_runs = sum(kind == "attention" for kind, _, _ in runs)
    assert (len(runs), mamba_runs, attn_runs) == (19, 13, 6)
    assert len(re.findall(r"%ssm_step[.\d]* = ", text)) == mamba_runs
    expert_runs = sum(cfg.ffn_kind(first) == "moe" for _, first, _ in runs)
    assert len(re.findall(r"%moe_gmm[.\d]* = ", text)) == 2 * expert_runs
    assert text.count("tpu_custom_call") == (attn_runs + mamba_runs
                                             + 2 * expert_runs)
    shape = r"f32\[23,64,64,64,128\]"
    whole = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= {shape}\S* (copy|transpose)\(", line)]
    assert not whole, whole[0]
    stack = set(re.findall(rf"(%[\w.\-]+)(?: =|:) {shape}", text))
    fused = [line.strip()[:160] for line in text.splitlines()
             if " fusion(" in line and (
                 re.search(shape, line.split(" fusion(")[0])
                 or stack & set(re.findall(r"%[\w.\-]+",
                                           line.split(" fusion(")[1])))]
    assert stack and not fused, fused[:1]
    # no layer's slice of an expert stack is copied out, no mixture is left
    assert not re.search(r"= s8\[(1,)?32,(2688,1920|1920,2688)\]", text)
    assert not re.search(r"\[64,(32|128),\d+\]", text)


def test_nemotron_routed_prefill_reads_the_held_stacks_where_they_lie(
        one_chip, no_cache, monkeypatch):
    """The cell's widest admission (two rows of bucket 256: 512 tokens, the
    one dispatch its band routes): the ungated experts are TWO `moe_gmm`
    calls a run that ends in experts, over the held experts' int8 stacks
    as they lie — [23, 32, 2688, 1920] / [23, 32, 1920, 2688]: the
    published 1,856 stored in whole lane tiles (models/hybrid.py
    `expert_columns`). At 1,856 the chip laid the up-projection's stack
    out with 2,688 minor and this program held TWO 3.5 GB copies of it for
    the kernel (16.15 GB of the chip's 15.75: it did not compile). No
    slice, copy or fusion of a stack, and no mixture left."""
    from symmetry_tpu.models import hybrid, llama, mamba2, moe
    from symmetry_tpu.ops import gmm

    for module in (llama, mamba2, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    assert hybrid.expert_columns(1856) == 1920
    assert hybrid.expert_columns(768) == 768
    assert gmm.geometry(3072, 2688, 1856) is None       # 14.5 lane tiles
    assert gmm.geometry(3072, 2688, 1920) == (64, 640)
    assert gmm.geometry(3072, 1920, 2688) == (64, 896)
    cfg, params, cache = _nemotron_shapes(one_chip, 2, 256)
    assert moe.moe_route(512, 128, 6, 32) == "routed"
    tok = jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    with jax.default_matmul_precision("default"):
        text = jax.jit(
            lambda p, t, c, n: llama.forward_hidden(p, cfg, t, c, n,
                                                    prefill_flash=True),
            donate_argnums=(2,)).lower(params, tok, cache, lens).compile(
        ).as_text()
    expert_runs = sum(cfg.ffn_kind(first) == "moe"
                      for _, first, _ in hybrid.runs(cfg))
    assert expert_runs == 13
    assert len(re.findall(r"%moe_gmm[.\d]* = ", text)) == 2 * expert_runs
    stack = r"s8\[23,32,(2688,1920|1920,2688)\]"
    names = set(re.findall(rf"(%[\w.\-]+)(?: =|:) {stack}", text))
    assert names
    touched = [line.strip()[:160] for line in text.splitlines()
               if re.search(rf"= {stack}\S* (copy|transpose|fusion|"
                            rf"dynamic-slice)\(", line)
               or (" fusion(" in line and names & set(re.findall(
                   r"%[\w.\-]+", line.split(" fusion(")[1])))]
    assert not touched, touched[0]
    sliced = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= s8\[(1,)?32,(2688,1920|1920,2688)\]", line)]
    assert not sliced, sliced[0]
    mixture = [line.strip()[:160] for line in text.splitlines()
               if re.search(r"\[512,(32|128),\d+\]", line)]
    assert not mixture, mixture[0]


@pytest.mark.parametrize("program", ["decode_block", "insert_all",
                                     "prefill"])
def test_nemotron_programs_keep_the_kv_leaf_head_major(
        one_chip, no_cache, monkeypatch, program):
    """The three programs that share the cell's cache — the engine's own
    jits at 64 slots x 640 (`tools/lowered_programs.py`): the decode block
    of 16 steps, the insert of an admission's rows into their slots, and
    the widest admission (two rows of bucket 256) into its scratch — must
    agree on where the K/V leaves lie, or one of them relays the whole leaf
    for the others: each holds the leaf in the chip's own head-major layout
    alone, writes it in place (scatters by head; the insert's placement)
    and never copies, transposes or fuses it into another (PR 62; the
    parent's decode block held 32 such copies, its prefill relaid the
    scratch around twelve scatters)."""
    from symmetry_tpu.models import llama, mamba2, moe

    for module in (llama, mamba2, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    tool, _ = _lowered_programs_tool(one_chip, monkeypatch)
    name = "nemotron-3-nano-30b-a3b"
    slots, (rows, bucket) = tool.SLOTS_OF[name], tool.PREFILL_OF[name]
    cfg, params, _ = _nemotron_shapes(one_chip, slots, tool.CAPACITY)
    e = tool.bare_engine(cfg, slots)
    state = tool.decode_state(e, cfg, slots)
    assert state.cache.k.shape == (6, 64, 640, 2, 128)
    with jax.default_matmul_precision("default"):  # the served setting,
        lowered = next(low for prog, low in tool.programs(  # as traced
            e, params, state, (rows, bucket)) if prog == program)
        text = lowered.compile().as_text()
    # the leaf as each program holds it: the served cache, or the scratch
    B, T = (rows, bucket) if program == "prefill" else (slots, 640)
    moved, written = leaf_moves(text, 6, B, T, 128)
    assert not moved, moved[0]
    if program == "prefill":
        # the scratch (0.8 MB) is staged in fast memory and scattered
        # there through a bitcast to the rows as they lie, [L * B * 2 * T,
        # 128]: twelve scatters, K and V of six attention layers
        written = re.findall(rf"= s8\[{6 * B * 2 * T},128\]\S* scatter\(",
                             text)
    # K and V: of six layers, or placed once a leaf (a re-materialised
    # scatter may be listed twice)
    assert len(written) >= (2 if program == "insert_all" else 2 * 6), written

    def layouts(b, t):
        return set(re.findall(rf"s8\[6,{b},{t},2,128\]\{{([\d,]+):", text))

    assert layouts(B, T) == {"4,2,3,1,0"}       # [L, B, 2, T, D] in HBM
    if program == "decode_block":
        moved = plane_moves(text, 6, slots, 640)
        assert not moved, moved[0]
    if program == "insert_all":
        # ... and the scratch it places lies as the prefill left it
        assert layouts(rows, bucket) == {"4,2,3,1,0"}


def test_k_exaone_drafting_step_verifies_through_the_kernels_in_place(
        one_chip, no_cache, monkeypatch):
    """k-exaone-236b-a23b's drafting step at its cell (64 slots: four full
    leaves of 5,376 rows — the trunk's three and the module's — and rings
    of 256): the trunk over [pending, draft] then the module over the same
    two positions, compiled for a described v5e. A full layer's attention
    is the decode kernel ONCE A POSITION (two `swa_decode` calls in each of
    the three full scans, two in the module), the rings go through
    `gqa_attention` (no kernel: 256 rows are no ring of the window's 128);
    the held share is ROUTED — two `moe_gmm` calls in each of the six
    sparse scans and two in the module, over the 16 held experts' stacks as
    they lie; no op copies or slices a full cache leaf; and the program fits
    the chip beside the 13.2 GB it is handed."""
    from symmetry_tpu.models import hybrid, llama, moe, residents
    from symmetry_tpu.ops import decode_attention as da, gmm

    for module in (llama, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    cfg = llama.preset("k-exaone-236b-a23b")
    B, T = 64, 5376
    ring = residents.ring_rows(cfg, 1)

    def shaped(fn):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    params = shaped(lambda: llama.init_params(
        cfg, jax.random.key(0), jnp.bfloat16, quantize=True))
    cache = shaped(lambda: llama.init_cache(
        cfg, B, T, jnp.bfloat16, quantized=True, count_experts=True,
        ring=ring))
    assert cache.k.shape == (4, B, T, 8, 128)
    assert cache.kw.shape == (9, B, 256, 8, 128)
    assert moe.moe_route(2 * B, 128, 8, 16) == "routed"
    tok = jax.ShapeDtypeStruct((B, 2), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def step(p, t, c, n):
        h, after = llama.forward_hidden(p, cfg, t, c, seq_lens=n)
        hm, after = hybrid.mtp_forward(
            p, cfg, h, t, after._replace(lengths=c.lengths), seq_lens=n)
        return llama.logits_from_hidden(p, cfg, h), hm, after

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params, tok, cache, lens).compile()
    text = compiled.as_text()
    assert len(re.findall(rf"%{da.WINDOW_NAME}[.\d]* = ", text)) == 8
    assert len(re.findall(rf"%{gmm.NAME}[.\d]* = ", text)) == 14
    # (a FULL leaf: a ring's layer, 16.8 MB of K and of V, is what
    # `gqa_attention` slices out and relays a window layer — the price of
    # masking by position until a kernel takes a start offset)
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= s8\[(\d+,)?{B},{T},8,128\]\S* "
                          rf"(copy|transpose|dynamic-slice|fusion)\(", line)
             and "dynamic-update-slice" not in line
             and "scatter" not in line]
    assert not moved, moved[0]
    sliced = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= s8\[(1,)?16,(6144,2048|2048,6144)\]\S* "
                           r"(copy|dynamic-slice|fusion)\(", line)]
    assert not sliced, sliced[0]
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 600 << 20
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.0e9)
