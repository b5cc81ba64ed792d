"""Probe: W8A16 fused tile-dequant matmul (ops/qmm.py w8a16_matmul) —
tile-size / buffer-depth sweep against the XLA mixed dot it would replace.

The decode convert wall (BASELINE.md rounds 3-4, tools/bisect_decode.py):
XLA's bf16×int8 mixed dot materializes a full bf16 copy of every int8
weight before each dot, pinning decode at the s8→bf16 convert throughput
(~480 GB/s effective in-trunk) instead of HBM bandwidth (740-860 GB/s
for a pure bf16 matmul). The W8A8 route was measured ~50% slower at
decode M (ops/qmm.py). This probe measures the one unattempted lever
(VERDICT r05 #8): weights streamed as pre-packed contiguous int8 tiles,
dequantized tile-by-tile in VMEM inside the pallas grid pipeline —
convert overlapped with DMA and MXU work, no full-tensor bf16 copy.

The (bk, bn) tile size is both the DMA granularity and the effective
double-buffer DEPTH lever: the pallas_call pipeline keeps the NEXT tile
in flight behind the current tile's dequant+dot, so small tiles mean a
shallow fast-turnaround pipeline (launch-bound), large tiles a deep one
(VMEM-bound). The sweep brackets both failure modes; the production
defaults (W8A16_BLOCK_K/N in ops/qmm.py) should be set from this table.

Run: python tools/probe_w8a16.py          (PROBE_M=128 by default — the
     decode slot batch; PROBE_M=1152 probes the verify-block shape)

Measured table (fill per chip; this repo's CI box is CPU-only, so the
kernel rows await the next on-chip bench round — the reference rows are
the round-3 measurements the wall was diagnosed with):

  M=128, K=4096, N=4*14336 (llama3-8b FFN-equivalent read)
  | path                         | ms/loop | eff GB/s |
  |------------------------------|---------|----------|
  | XLA mixed dot (production)   |         | ~480 in-trunk (r03)       |
  | bf16 × bf16 (the ceiling)    |         | 740-860 (r03)             |
  | w8a16 bk=256 bn=256          |         | pending on-chip round     |
  | w8a16 bk=512 bn=256          |         | pending on-chip round     |
  | w8a16 bk=512 bn=512          |         | pending on-chip round     |
  | w8a16 bk=1024 bn=512         |         | pending on-chip round     |
  | w8a16 bk=512 bn=1024         |         | pending on-chip round     |

Decision rule (BASELINE.md decode-floor section): the best kernel point
must beat the mixed dot here AND in the full trunk (`bench.py --engine
--fused-dequant`, then the driver e2e A/B) before `tpu.fused_dequant`
defaults on; a negative result is promoted as the official convert-wall
floor conclusion, closing VERDICT #8 either way.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bench_util import timeit  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from symmetry_tpu.ops.interpret import interpret_mode  # noqa: E402
from symmetry_tpu.ops.qmm import w8a16_matmul  # noqa: E402
from symmetry_tpu.ops.quant import pack_quantized, quantize  # noqa: E402


def loop(body, iters: int):
    """Carry-DEPENDENT benchmark loop (probe_s8_mxu convention): without
    the carry, XLA hoists the loop-invariant matmul out of the scan and
    the timing is fiction (observed: 905 GB/s, above HBM peak)."""

    def run(x, *w):
        def step(carry, _):
            y = body(carry, *w)
            nxt = carry + (y[:, :carry.shape[1]] * 1e-9).astype(carry.dtype)
            return nxt, ()

        out, _ = jax.lax.scan(step, x, None, length=iters)
        return out

    return jax.jit(run)


def main() -> None:
    M = int(os.environ.get("PROBE_M", 128))
    # Default: one llama3-8b layer's fused-FFN-scale read. PROBE_K/N
    # shrink it for an off-chip smoke run (interpret mode cannot afford
    # the real shapes, and its numbers are meaningless anyway).
    K = int(os.environ.get("PROBE_K", 4096))
    N = int(os.environ.get("PROBE_N", 4 * 14336))
    ITERS = int(os.environ.get("PROBE_ITERS", 20))
    interpret = interpret_mode()
    if interpret:
        print("WARNING: no TPU backend — interpret mode measures the "
              "emulator, not the chip; table numbers must come from a "
              "v5e run", flush=True)

    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (M, K), jnp.bfloat16)
    w = jax.random.normal(kw, (K, N), jnp.float32) * 0.02
    qt = quantize(w)
    wb = jnp.asarray(w, jnp.bfloat16)
    del w

    def report(name: str, ms: float) -> None:
        gbs = K * N * ITERS / (ms / 1e3) / 1e9
        print(f"{name:24s} {ms:8.2f} ms/loop  {gbs:7.1f} GB/s", flush=True)

    # Reference 1: the production mixed dot (int8 operand passed direct).
    def mixed(x, q, s):
        y = jax.lax.dot_general(
            x, q, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (y * s).astype(x.dtype)

    report("xla-mixed (production)",
           timeit(loop(mixed, ITERS), x, qt.q, qt.scale, n=10))

    # Reference 2: pure bf16 — the HBM-bandwidth ceiling (2x the bytes).
    report("bf16 (2x bytes)",
           timeit(loop(lambda x, w: (x @ w).astype(x.dtype), ITERS), x, wb))

    # The sweep: each (bk, bn) is a different DMA granularity / pipeline
    # depth for the SAME production kernel (pack once per point — the
    # engine packs at load, so packing cost is off the decode path).
    for bk, bn in ((256, 256), (512, 256), (256, 512), (512, 512),
                   (1024, 512), (512, 1024)):
        if K % bk or N % bn:
            continue
        try:
            pt = pack_quantized(qt, bk=bk, bn=bn)
            f = loop(lambda x, q, s: w8a16_matmul(
                x, q, s, interpret=interpret), ITERS)
            report(f"w8a16 bk{bk} bn{bn}", timeit(f, x, pt.q, pt.scale,
                                                  n=10))
        except Exception as exc:  # noqa: BLE001 — sweep must finish
            print(f"w8a16 bk{bk} bn{bn} failed: "
                  f"{type(exc).__name__}: {exc}"[:300], flush=True)


if __name__ == "__main__":
    main()
