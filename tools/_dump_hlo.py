"""Optimised HLO of the decode trunk at the dense cells' shape (128 slots x
640, int8 weights + int8 KV), compiled for a v5e — the attached one, or,
off the chip, a described one (nothing runs; see the on-chip-measurement
guide, section 2). What to look for: `tpu_custom_call` (the decode
attention kernel), `copy-start` of whole cache arrays (XLA staging an
operand in its fast memory, S(1)), `dynamic-slice` fusions of
`s8[1,128,640,K,128]` (the XLA path's per-layer slice).

    python tools/_dump_hlo.py [preset] [--xla]   # --xla: the XLA attention

Writes chiprun_out/trunk_<preset>[_xla].hlo and prints the memory analysis.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding
from symmetry_tpu.models import gdn, llama, mamba2
from symmetry_tpu.ops import decode_attention as da

args = [a for a in sys.argv[1:] if not a.startswith("--")]
name = args[0] if args else "mistral-7b"
xla = "--xla" in sys.argv
if jax.default_backend() == "tpu":
    device = jax.devices()[0]
else:
    from jax.experimental import topologies

    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    # the layers ask the default backend whether kernels are interpreted;
    # this compile is for the chip
    llama.interpret_mode = mamba2.interpret_mode = lambda: False
    gdn.interpret_mode = lambda: False
if xla:
    da.geometry = lambda *a, **k: None
one = SingleDeviceSharding(device)
cfg = llama.preset(name)
B, T = 128, 640


def shaped(fn):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(fn))


params = shaped(lambda: llama.init_params(cfg, jax.random.key(0),
                                          jnp.bfloat16, quantize=True))
cache = shaped(lambda: llama.init_cache(cfg, B, T, jnp.bfloat16,
                                        quantized=True))
tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one)
trunk = jax.jit(lambda p, t, c: llama.forward_hidden(p, cfg, t, c),
                donate_argnums=(2,))
compiled = trunk.lower(params, tok, cache).compile()
os.makedirs("chiprun_out", exist_ok=True)
path = f"chiprun_out/trunk_{name}{'_xla' if xla else ''}.hlo"
with open(path, "w") as fh:
    fh.write(compiled.as_text())
print(path, compiled.memory_analysis())
