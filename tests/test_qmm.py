"""The W8A16 fused-dequant matmul (ops/qmm.py), Pallas interpret mode.

`tpu.fused_dequant`: the fused tile-dequant kernel is specified to
compute EXACTLY qmatmul's reference semantics — (x @ q) accumulated f32,
per-output-channel scale in the epilogue, cast to the activation dtype —
so it is pinned against the mixed dot across every trunk matmul shape
family (wide/narrow N, GQA head dims, ragged K needing small-tile
fallback, single-row and 32-row edges), and the engine-level contract
(greedy decode token-identical with the knob on vs off, zero
steady-state recompiles after warmup) is enforced on the tiny preset.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symmetry_tpu.ops.qmm import (
    pick_w8a16_block,
    w8a16_matmul,
    w8a16_supports,
)
from symmetry_tpu.ops.quant import (
    PackedQuantizedTensor,
    QuantizedTensor,
    pack_quantized,
    qmatmul,
    quantize,
    unpack_quantized,
)


# ---------------------------------------------------------------------------
# W8A16 fused-dequant kernel (tpu.fused_dequant)

# Every matmul shape family the decoder trunk routes through qmatmul, at
# CPU-testable sizes: (M, K, N) with M covering the decode slot batch,
# coalesced-prefill rows, the verify block (slots × (1+k)), and the
# single-row prefill-head edge; K/N covering wide FFN, narrow GQA kv_dim,
# the wide lm_head, and ragged dims that force the small-tile fallback.
TRUNK_SHAPES = (
    (128, 64, 64),     # wq at decode batch
    (128, 64, 32),     # wk/wv: GQA narrow N (kv_dim < lane tile)
    (128, 64, 128),    # wg/wu: FFN wide
    (128, 128, 64),    # wd: FFN contraction
    (128, 64, 512),    # lm_head: vocab-wide N
    (32, 192, 320),    # ragged K and N: small-tile fallback blocks
    (1, 64, 512),      # single row (batch-1 prefill head projection)
    (2, 64, 64),       # tiny batch
    (1152, 64, 64),    # verify-block rows (128 slots × (1 + k_draft 8))
)


def _reference_qmatmul(x: np.ndarray, qt) -> np.ndarray:
    """The fused kernel's bit-exact SPEC, computed independently in
    numpy: (x @ q) in f32, per-output-channel scale, cast to x.dtype."""
    acc = x.astype(np.float32) @ np.asarray(qt.q, np.float32)
    return (acc * np.asarray(qt.scale)[None, :]).astype(x.dtype)


class TestW8A16:
    def _case(self, m, k, n, seed=0, dtype=jnp.float32):
        kx, kw = jax.random.split(jax.random.key(seed))
        x = jax.random.normal(kx, (m, k), dtype)
        w = jax.random.normal(kw, (k, n), jnp.float32) * 0.05
        return x, quantize(w)

    def test_parity_across_trunk_shapes(self):
        for m, k, n in TRUNK_SHAPES:
            x, qt = self._case(m, k, n, seed=m + k + n)
            pt = pack_quantized(qt)
            assert isinstance(pt, PackedQuantizedTensor), (m, k, n)
            got = np.asarray(w8a16_matmul(x, pt.q, pt.scale,
                                          interpret=True))
            want = _reference_qmatmul(np.asarray(x), qt)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                       err_msg=f"shape {(m, k, n)}")

    def test_matches_mixed_dot_routing(self):
        """qmatmul on the packed leaf == qmatmul on the flat leaf (the
        production routing equivalence, 2-D and 3-D activations)."""
        x, qt = self._case(16, 64, 96, seed=1)
        np.testing.assert_allclose(
            np.asarray(qmatmul(x, pack_quantized(qt))),
            np.asarray(qmatmul(x, qt)), rtol=1e-5, atol=1e-5)
        x3 = x.reshape(4, 4, 64)
        got3 = qmatmul(x3, pack_quantized(qt))
        assert got3.shape == (4, 4, 96)
        np.testing.assert_allclose(np.asarray(got3),
                                   np.asarray(qmatmul(x3, qt)),
                                   rtol=1e-5, atol=1e-5)

    def test_out_dtype_follows_activation(self):
        x, qt = self._case(8, 64, 64, seed=2, dtype=jnp.bfloat16)
        pt = pack_quantized(qt)
        got = w8a16_matmul(x, pt.q, pt.scale, interpret=True)
        assert got.dtype == jnp.bfloat16

    def test_pack_roundtrip_bit_exact(self):
        _, qt = self._case(1, 192, 320, seed=3)
        pt = pack_quantized(qt)
        rt = unpack_quantized(pt)
        assert (np.asarray(rt.q) == np.asarray(qt.q)).all()
        assert (np.asarray(rt.scale) == np.asarray(qt.scale)).all()

    def test_pack_stacked_layers(self):
        """[L, K, N] stacks pack per layer; stripping the leading dim
        (what lax.scan does) yields exactly the 2-D packed layout."""
        w = jax.random.normal(jax.random.key(4), (3, 64, 32), jnp.float32)
        qt = quantize(w)
        pt = pack_quantized(qt)
        assert pt.q.shape[0] == 3 and pt.scale.shape == (3, 32)
        per_layer = pack_quantized(
            QuantizedTensor(q=qt.q[1], scale=qt.scale[1]))
        assert (np.asarray(pt.q[1]) == np.asarray(per_layer.q)).all()

    def test_untileable_stays_flat(self):
        """Shapes the kernel can't tile keep the flat QuantizedTensor —
        the per-leaf mixed-dot fallback, never an error."""
        qt = quantize(jnp.ones((100, 96), jnp.float32))  # K=100 untileable
        assert isinstance(pack_quantized(qt), QuantizedTensor)

    def test_supports_gate(self):
        assert w8a16_supports(4096, 14336, "tpu")   # llama3 FFN
        assert w8a16_supports(4096, 128256, "tpu")  # llama3 lm_head
        assert w8a16_supports(4096, 1024, "tpu")    # GQA kv_dim
        assert not w8a16_supports(100, 14336, "tpu")  # K untileable
        assert not w8a16_supports(4096, 96, "tpu")  # N under the 128 floor
        assert w8a16_supports(64, 32, "cpu")        # tiny presets (tests)

    def test_pick_block(self):
        assert pick_w8a16_block(4096, 512) == 512
        assert pick_w8a16_block(320, 512) == 64
        assert pick_w8a16_block(100, 512) is None
        assert pick_w8a16_block(64, 512, floor=128) is None


class TestFusedDecodeEngine:
    """Engine-level contract of tpu.fused_dequant on the tiny preset."""

    def _engine(self, fused: bool, block: int = 1):
        from symmetry_tpu.engine.engine import InferenceEngine
        from symmetry_tpu.engine.tokenizer import ByteTokenizer
        from symmetry_tpu.models import init_params, preset
        from symmetry_tpu.models.llama import quantize_params

        cfg = preset("tiny")
        params = quantize_params(
            init_params(cfg, jax.random.key(0), jnp.float32))
        return InferenceEngine(
            cfg, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
            prefill_buckets=(16,), cache_dtype=jnp.float32,
            decode_block=block, fused_dequant=fused)

    def test_greedy_token_identical_knob_on_vs_off(self):
        """The decode-equivalence acceptance: greedy output is
        token-identical with the fused path on vs off."""
        from symmetry_tpu.engine.engine import SamplingParams

        prompt = list(b"fused parity")
        outs = {}
        for fused in (False, True):
            eng = self._engine(fused)
            first = eng.prefill_and_insert(0, prompt, SamplingParams())
            toks = [first]
            for _ in range(11):
                toks.append(int(eng.decode_step()[0]))
            outs[fused] = toks
        assert outs[True] == outs[False]

    def test_params_are_packed(self):
        eng = self._engine(True)
        layers = eng.params["layers"]
        for name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
            assert isinstance(layers[name], PackedQuantizedTensor), name
        assert isinstance(eng.params["lm_head"], PackedQuantizedTensor)

    def test_fused_requires_quantized_weights(self):
        from symmetry_tpu.engine.engine import EngineError, InferenceEngine
        from symmetry_tpu.engine.tokenizer import ByteTokenizer
        from symmetry_tpu.models import init_params, preset

        cfg = preset("tiny")
        params = init_params(cfg, jax.random.key(0), jnp.float32)
        with pytest.raises(EngineError, match="quantization"):
            InferenceEngine(cfg, params, ByteTokenizer(), max_slots=2,
                            max_seq_len=64, prefill_buckets=(16,),
                            cache_dtype=jnp.float32, fused_dequant=True)

    def test_warmup_then_zero_steady_state_recompiles(self):
        """Warmup must cover the fused compile set completely: serving
        traffic after warmup may not grow any jit's compiled-variant
        count (a mid-traffic XLA compile is the stall warmup prevents)."""
        from symmetry_tpu.engine.engine import SamplingParams

        eng = self._engine(True, block=2)
        eng.warmup()
        baseline = eng.compile_cache_sizes()
        assert baseline["_decode"] >= 1 and baseline["_prefill"] >= 1
        eng.prefill_and_insert_many(
            [(0, list(b"hello"), SamplingParams()),
             (1, list(b"world"), SamplingParams(temperature=0.5, seed=7))])
        for _ in range(3):
            eng.decode_steps()
        assert eng.compile_cache_sizes() == baseline

    def test_weight_stream_bytes_counts_matmul_weights(self):
        eng = self._engine(True)
        want = sum(
            leaf.nbytes for leaf in jax.tree.leaves(eng.params)) \
            - eng.params["embed"].nbytes
        assert eng.weight_stream_bytes() == want
