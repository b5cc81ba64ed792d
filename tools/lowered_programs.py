"""Lowered text of the engine's served programs, for "did this refactor
change what an existing configuration runs?".

    JAX_PLATFORMS=cpu python tools/lowered_programs.py OUT_DIR [preset ...]

For each preset (default: mistral-7b, qwen2-7b, granite-4.0-h-small,
qwen3-next-80b-a3b, keye-vl-2.0-30b-a3b, lfm2-8b-a1b, sdar-30b-a3b-chat
(its diffusion programs at 2 denoise steps a block, the static rule),
kanana-2-30b-a3b (its latent cache in bfloat16), smallthinker-21b-a3b
(its window layers' rings of 4,096 rows beside full leaves of 640) and
nemotron-3-nano-30b-a3b (at ITS cell's 64 slots, and its widest admission,
two rows of 256: the dispatch its band routes) at the
closed cells' shape,
128 slots x 640, int8 weights + int8 KV, decode_block 16; and tiny-moe8 —
the stand-in for mixtral-8x7b's sharded programs — on a `model: 4` mesh of
virtual CPU devices: thirty-one programs, sdar's a second
admission at (16, 64) among them) it writes the
StableHLO of the engine's OWN jits — `decode_block`, `prefill` at (8, 256)
and `insert_all` — lowered from shapes alone (nothing is built or run), as
`OUT_DIR/<preset>.<program>.txt` and prints one sha256 a file. The text
carries no source locations, and on the CPU the Pallas kernels lower through
the interpreter (no Mosaic bytecode with file paths in it), so the same
programs give the same bytes on two commits: copy this file into the other
checkout's `tools/`, run it from each, and `diff -r` the two directories.
(PR 41, a kernel under the sparse path alone: 16 of the 18 files identical,
keye's `prefill` and `decode_block` the two that differ. PR 42, a third
recurrent kind: the eighteen older files identical; a parent that lacks a
preset is given the names it has. PR 47, generation by diffusion over
blocks: the twenty-one older files identical. PR 49, the homogeneous trunk
hands the routed experts their stacks whole: 20 of the 24 files identical —
every dense, hybrid and meshed program, and both `insert_all`s — and the
four trunks of the two one-device homogeneous expert presets differ:
sdar-30b-a3b-chat's `decode_block` and `prefill` and keye-vl-2.0-30b-a3b's
`prefill` lose the layer's `[1, 128, ...]` slices before their kernels;
keye's `decode_block`, a dense mixture, differs in the ORDER of its scan's
operands alone — the stacks ride it as constants it never reads. PR 50, a
block of queries a slot through the decode kernel: 23 of the 24 identical,
sdar-30b-a3b-chat's `decode_block` the one that differs. PR 54, latent
attention as a mixer kind of the hybrid trunk: all 24 older files
identical, three new ones. PR 57, (128, 8)'s band: 26 of the 27
identical — sdar-30b-a3b-chat's `prefill` (8 rows: an opening block of 32
tokens, routed now) the one that differs; its `prefill_16x64`, new in the
tool, identical on parent and change. PR 58, a second attention kind with a
ring of its own: all 28 older files identical, three new ones. PR 61,
blocks of one sub-layer, groups of B and C, a share of ungated experts: all
31 older files identical, three new ones. PR 62, the head-indexed K/V
scatter for a leaf of 2 int8 heads of whole lane tiles: 30 of the 34
identical — every `insert_all` and all three programs of the ten other
presets — nemotron-3-nano-30b-a3b's and qwen3-next-80b-a3b's `decode_block`
and `prefill` the four that differ. PR 63, what a slot keeps as one table
(models/residents.py sizes `expert_pairs`) and the sequence-parallel
keywords out of the trunk: all 34 identical. PR 65, a drafting module
behind a window / full trunk, rings that carry a draft and a tail of several
rows: the 34 older files against the parent are in CHANGES.md's line; three
new ones, k-exaone-236b-a23b's, whose `decode_block` is the drafting block
at 64 slots and whose `prefill` is two rows of 512. PR 66, the homogeneous
one-chip trunk appends to the cache once a decode step: 35 of the 37
identical — mistral-7b's and qwen2-7b's `decode_block` the two that
differ.)

It reaches into `InferenceEngine` (an instance made without `__init__`, with
the attributes `_build_jits` reads) so that a 7B model's state is never
allocated; the tiny sharded case builds the real engine.
"""

from __future__ import annotations

import hashlib
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from symmetry_tpu.engine import engine as eng_mod  # noqa: E402
from symmetry_tpu.models import llama  # noqa: E402

SLOTS, CAPACITY, BLOCK = 128, 640, 16
PREFILL = (8, 256)
PREFILL_WIDE = (16, 64)     # a diffusion preset's second admission shape
# a preset whose cell is not 128 slots, and whose widest admission (a
# prefill buffer carries a slot's whole recurrent state a row) is not 8 rows
SLOTS_OF = {"nemotron-3-nano-30b-a3b": 64, "k-exaone-236b-a23b": 64}
PREFILL_OF = {"nemotron-3-nano-30b-a3b": (2, 256),
              "k-exaone-236b-a23b": (2, 512)}
# a preset served with its own multi-token-prediction module drafting
# (`tpu.speculative: mtp`): `decode_block` is then the drafting block
DRAFTING = {"k-exaone-236b-a23b"}


def shapes(fn):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        jax.eval_shape(fn))


def bare_engine(cfg, slots: int = SLOTS, drafting: bool = False):
    """An engine whose jits exist and whose arrays do not."""
    from symmetry_tpu.engine.spec import SpecConfig
    from symmetry_tpu.models import residents

    e = object.__new__(eng_mod.InferenceEngine)
    e._mtp, e.tap = drafting, None
    e._ring = residents.ring_rows(cfg, int(drafting))
    # (a latent cache row has no int8 form: that preset's cache is bfloat16)
    e.config, e.mesh, e.decode_block = cfg, None, BLOCK
    e.kv_quant = getattr(cfg, "latent", None) is None
    e.spec = SpecConfig.from_knob("mtp") if drafting else None
    e.prefix_block, e.cache_dtype = 16, jnp.bfloat16
    e.max_slots, e.max_seq_len = slots, CAPACITY
    e._state_shardings = e._cache_shardings = None
    e._count_experts = bool(getattr(cfg, "num_experts", 0))
    # generation by diffusion over blocks: the sdar cell's two settings
    e._diffusion = getattr(cfg, "diffusion", None)
    e._bd_steps, e._bd_threshold = 2, None
    e._build_jits()
    return e


def decode_state(e, cfg, slots: int):
    """The shapes of the engine's served state at `slots` x CAPACITY."""
    return shapes(lambda: eng_mod.DecodeState(
        cache=llama.init_cache(
            cfg, slots, CAPACITY, jnp.bfloat16, quantized=e.kv_quant,
            count_experts=e._count_experts,
            # (a window layer's ring: the window's rows, and a draft's)
            **({"ring": e._ring}
               if getattr(cfg, "window_kind", None) else {})),
        last_token=jnp.zeros((slots,), jnp.int32),
        temperature=jnp.zeros((slots,), jnp.float32),
        top_p=jnp.ones((slots,), jnp.float32),
        top_k=jnp.zeros((slots,), jnp.int32),
        rng=jax.random.split(jax.random.key(0), slots),
        draft=jnp.zeros((slots,), jnp.int32) if e._mtp else None))


def programs(e, params, state, prefill=PREFILL):
    cfg = e.config
    i32, f32 = jnp.int32, jnp.float32
    # (a block-diffusion admission commits its opening block behind the
    # bucket and hands the block over where the others hand one token)
    block = getattr(getattr(cfg, "diffusion", None), "block", 0)

    def vec(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype)

    def admission(n, bucket):
        """`prefill`'s arguments after `params`: tokens, true lengths, the
        three sampling vectors, the keys and the scratch."""
        keys = shapes(lambda: jax.random.split(jax.random.key(0), n))
        scratch = shapes(lambda: llama.init_cache(
            cfg, n, bucket + block, jnp.bfloat16, quantized=e.kv_quant,
            count_experts=e._count_experts))
        return (jax.ShapeDtypeStruct((n, bucket), i32), vec(n, i32),
                vec(n, f32), vec(n, f32), vec(n, i32), keys, scratch)

    n = prefill[0]
    args = admission(*prefill)
    keys, scratch = args[-2:]
    first = jax.ShapeDtypeStruct((n, block), i32) if block else vec(n, i32)
    if e._mtp:      # the first token and the first draft
        first = jax.ShapeDtypeStruct((n, 2), i32)
    yield "decode_block", e._decode.lower(
        params, state, jax.ShapeDtypeStruct((e.max_slots,), bool))
    yield "prefill", e._prefill.lower(params, *args)
    yield "insert_all", e._insert_all.lower(
        state, scratch, vec(n, i32), vec(n, i32), first, vec(n, f32),
        vec(n, f32), vec(n, i32), keys)
    if block:
        # a second admission shape: 16 rows x 4 positions are an opening
        # block of 64 tokens, inside (128, 8)'s band of the dense mixture,
        # where the 8 rows above are under it and routed (PR 57)
        yield ("prefill_%dx%d" % PREFILL_WIDE,
               e._prefill.lower(params, *admission(*PREFILL_WIDE)))


def main() -> int:
    out_dir = sys.argv[1]
    names = sys.argv[2:] or ["mistral-7b", "qwen2-7b", "tiny-moe8",
                             "granite-4.0-h-small", "qwen3-next-80b-a3b",
                             "keye-vl-2.0-30b-a3b", "lfm2-8b-a1b",
                             "sdar-30b-a3b-chat", "kanana-2-30b-a3b",
                             "smallthinker-21b-a3b",
                             "nemotron-3-nano-30b-a3b",
                             "k-exaone-236b-a23b"]
    os.makedirs(out_dir, exist_ok=True)
    for name in names:
        cfg = llama.preset(name)
        if name.startswith("tiny"):
            from symmetry_tpu.engine.tokenizer import get_tokenizer
            from symmetry_tpu.parallel.mesh import MeshSpec, build_mesh
            from symmetry_tpu.parallel.sharding import shardings_for

            mesh = build_mesh(MeshSpec(model=4))
            sh = shardings_for(llama.quantized_logical_axes(
                llama.param_logical_axes(cfg)), mesh)
            params = jax.jit(lambda: llama.init_params(
                cfg, jax.random.key(0), jnp.bfloat16, quantize=True,
                shardings=sh), out_shardings=sh)()
            e = eng_mod.InferenceEngine(
                cfg, params, get_tokenizer(None, vocab_size=cfg.vocab_size),
                mesh=mesh, max_slots=8, max_seq_len=CAPACITY,
                prefill_buckets=(PREFILL[1],), decode_block=BLOCK,
                kv_quant=True, prefill_chunk=None)
            params, state = e.params, e.state
        else:
            slots = SLOTS_OF.get(name, SLOTS)
            e = bare_engine(cfg, slots, name in DRAFTING)
            params = shapes(lambda: llama.init_params(
                cfg, jax.random.key(0), jnp.bfloat16, quantize=True))
            state = decode_state(e, cfg, slots)
        for prog, lowered in programs(e, params, state,
                                      PREFILL_OF.get(name, PREFILL)):
            text = lowered.as_text()
            path = os.path.join(out_dir, f"{name}.{prog}.txt")
            with open(path, "w") as fh:
                fh.write(text)
            print(f"{hashlib.sha256(text.encode()).hexdigest()}  "
                  f"{os.path.basename(path)}  {len(text)} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
