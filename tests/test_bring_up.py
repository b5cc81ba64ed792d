"""The rules that keep the system honest about its device (PR 21), on CPU:

  - an engine refuses a platform other than tpu unless the CPU was pinned
    by name, the host turns that into its own exit code, and the backend
    into one error it never respawns;
  - READY and stats name the device the host got;
  - the compile cache is placed by JAX_COMPILATION_CACHE_DIR or lives in
    the checkout, with the persistence thresholds set either way;
  - interpret mode is for the CPU backend only;
  - chip_smoke.py run here ends non-zero saying the platform is cpu.
"""

import asyncio
import io
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from symmetry_tpu.engine import host as host_mod
from symmetry_tpu.ops.interpret import interpret_mode
from symmetry_tpu.protocol.keys import HOST_EXIT_NO_CHIP
from symmetry_tpu.provider.backends.base import (
    BackendError, BackendNoChipError)
from symmetry_tpu.provider.backends.tpu_native import TpuNativeBackend
from symmetry_tpu.provider.config import ConfigManager
from symmetry_tpu.utils import compile_cache, device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HOST_CONFIG = {
    "name": "bring-up", "public": False, "serverKey": "00" * 32,
    "modelName": "tiny:bring-up", "apiProvider": "tpu_native",
    "tpu": {"model_preset": "tiny", "dtype": "float32",
            "max_batch_size": 2, "max_seq_len": 64,
            "prefill_buckets": [32], "decode_block": 4},
}


def fake_jax(platform="cpu", pinned=None, error=None):
    """Stand-in for the `jax` name inside utils/device.py: what JAX would
    report on a machine we do not have."""
    def local_devices():
        if error is not None:
            raise error
        return [SimpleNamespace(platform=platform, device_kind=platform,
                                memory_stats=lambda: None)]

    return SimpleNamespace(local_devices=local_devices,
                           config=SimpleNamespace(jax_platforms=pinned))


class TestRequireChip:
    def test_cpu_fallback_is_refused_and_named(self, monkeypatch):
        monkeypatch.setattr(device, "jax", fake_jax("cpu", pinned=None))
        with pytest.raises(device.NoChipError, match="platform cpu"):
            device.require_chip()

    def test_cpu_pinned_by_name_is_allowed(self, monkeypatch):
        monkeypatch.setattr(device, "jax", fake_jax("cpu", pinned="cpu"))
        device.require_chip()

    def test_a_list_that_merely_contains_cpu_is_not_a_pin(self, monkeypatch):
        monkeypatch.setattr(device, "jax", fake_jax("cpu", pinned="tpu,cpu"))
        with pytest.raises(device.NoChipError):
            device.require_chip()

    def test_tpu_is_allowed(self, monkeypatch):
        monkeypatch.setattr(device, "jax", fake_jax("tpu", pinned=None))
        device.require_chip()

    def test_backend_that_cannot_initialise_is_the_same_refusal(
            self, monkeypatch):
        monkeypatch.setattr(device, "jax", fake_jax(
            error=RuntimeError("Unable to initialize backend 'tpu'")))
        with pytest.raises(device.NoChipError, match="initialise"):
            device.require_chip()

    def test_this_suite_runs_because_it_pins_the_cpu(self):
        assert jax.config.jax_platforms == "cpu"
        device.require_chip()


class TestEngineHost:
    def test_host_refuses_with_its_own_exit_code(self, monkeypatch,
                                                 tmp_path, capsys):
        cfg = tmp_path / "host.json"
        cfg.write_text(json.dumps(HOST_CONFIG))
        monkeypatch.setattr(device, "jax", fake_jax("cpu", pinned=None))
        monkeypatch.setattr(sys, "argv", ["host", str(cfg)])
        assert host_mod.main() == HOST_EXIT_NO_CHIP
        captured = capsys.readouterr()
        assert "platform cpu" in captured.err
        assert captured.out == ""  # no READY frame: nothing was built

    @staticmethod
    def startup_blocks(monkeypatch, capsys):
        """Serve a stats request and a shutdown: READY's block and the
        stats reply's copy of it."""
        monkeypatch.setattr(sys, "stdin", io.StringIO(
            '{"op": "stats"}\n{"op": "shutdown"}\n'))
        host = host_mod.EngineHost(ConfigManager(config=HOST_CONFIG))
        assert host.serve_forever() == 0
        frames = [json.loads(line)
                  for line in capsys.readouterr().out.splitlines()]
        ready = next(f for f in frames if f["op"] == "ready")
        stats = next(f for f in frames if f["op"] == "stats")
        return ready, stats["startup"]

    def test_ready_and_stats_name_the_device(self, monkeypatch, capsys):
        for block in self.startup_blocks(monkeypatch, capsys):
            assert block["device"]["platform"] == "cpu"
            assert block["device"]["device_kind"]
            assert block["device"]["device_count"] == jax.device_count()
            assert block["device"]["hbm"] == []  # CPU reports no HBM
            # tiny on CPU: flash runs interpreted; head size 16 is no
            # lane tile, so decode takes the XLA path and says so.
            assert block["attention"] == {
                "prefill": "pallas-interpret", "decode": "xla",
                "decode_why": "ops/decode_attention.py has no geometry for "
                              "a head of 16: no lane tile of 128"}
            # tiny's 512 logits are 4 groups of 128, below the staged
            # selection's threshold (ops/sampling.py top_k_route).
            assert block["sampling"] == {"top_k": "direct"}
            assert block["compile_cache"] == compile_cache.cache_dir()
            assert block["build_s"] >= 0 and block["warmup_s"] >= 0

    def test_ready_and_stats_carry_the_selection_stages(self, monkeypatch,
                                                        capsys):
        # tiny's 512 logits with the staged selection engaged (128 groups
        # of 4, the 256 kept as 128 sub-groups of 2): READY and the stats
        # reply say which stages every sampling call of the served programs
        # takes, as they do for a cell's vocabulary at the shipped widths.
        from symmetry_tpu.ops import sampling
        monkeypatch.setattr(sampling, "TOP_K_GROUP_WIDTH", 4)
        monkeypatch.setattr(sampling, "TOP_K_SUBGROUP_WIDTH", 2)
        for block in self.startup_blocks(monkeypatch, capsys):
            assert block["sampling"] == {
                "top_k": "grouped", "cap": 64, "ranked": 128,
                "stages": [{"groups": 128, "width": 4},
                           {"groups": 128, "width": 2}]}


class TestBackendSurfacesTheRefusal:
    @staticmethod
    def dead_host(rc):
        async def readline():
            return b""

        async def wait():
            return rc

        return SimpleNamespace(stdout=SimpleNamespace(readline=readline),
                               wait=wait)

    def test_no_chip_exit_code_is_one_named_error(self):
        with pytest.raises(BackendNoChipError, match="chip of its own"):
            asyncio.run(TpuNativeBackend._await_ready(
                self.dead_host(HOST_EXIT_NO_CHIP), "prefill host"))

    def test_any_other_death_stays_a_plain_startup_failure(self):
        with pytest.raises(BackendError, match="rc=1") as info:
            asyncio.run(TpuNativeBackend._await_ready(
                self.dead_host(1), "engine host"))
        assert not isinstance(info.value, BackendNoChipError)


class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls without applying them (the
        suite's own cache must stay where conftest put it)."""
        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda key, value: seen.__setitem__(key, value))
        return seen

    def test_env_places_the_cache_and_code_sets_no_directory(
            self, monkeypatch, updates, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
        # an env-placed cache must keep the fast-compiling programs too
        assert updates["jax_persistent_cache_min_compile_time_secs"] == \
            compile_cache.MIN_COMPILE_TIME_S
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1
        # ...and it wins over a directory named in the config
        cfg = SimpleNamespace(compile_cache="/somewhere/else")
        assert compile_cache.enable_compile_cache(cfg) == str(tmp_path)

    def test_default_is_inside_the_checkout(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
        assert updates["jax_persistent_cache_min_compile_time_secs"] == \
            compile_cache.MIN_COMPILE_TIME_S
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1

    def test_config_names_a_directory_or_disables(self, monkeypatch,
                                                  updates, tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        named = SimpleNamespace(compile_cache=str(tmp_path / "xla"))
        assert compile_cache.enable_compile_cache(named) == \
            str(tmp_path / "xla")
        assert os.path.isdir(tmp_path / "xla")
        updates.clear()
        off = SimpleNamespace(compile_cache=False)
        assert compile_cache.enable_compile_cache(off) is None
        assert updates == {}

    def test_unwritable_directory_warns_and_runs_cold(self, monkeypatch,
                                                      updates, tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        blocker = tmp_path / "file"
        blocker.write_text("not a directory")
        cfg = SimpleNamespace(compile_cache=str(blocker / "cache"))
        assert compile_cache.enable_compile_cache(cfg) is None
        assert "jax_compilation_cache_dir" not in updates


class TestInterpretMode:
    @pytest.mark.parametrize("backend,want",
                             [("cpu", True), ("tpu", False), ("gpu", False)])
    def test_only_the_cpu_backend_interprets(self, monkeypatch, backend,
                                             want):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert interpret_mode() is want


class TestChipSmoke:
    def test_cpu_dry_run_serves_every_phase_then_fails_on_the_platform(self):
        """The guide's "make the command run end to end here first": the
        same script, tiny preset, CPU pinned by name. Every phase runs;
        the verdict is non-zero because the platform is cpu, and no
        result line is printed."""
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"),
             "--preset", "tiny"],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
        assert out.returncode != 0
        assert "platform is cpu, not tpu" in out.stderr
        # the phases themselves passed: tokens agreed, greedy repeated,
        # nothing respawned, drain was clean
        for phrase in ("the wire carried", "greedy requests differ",
                       "respawned", "on drain", "outlived"):
            assert phrase not in out.stderr, out.stderr[-3000:]
        assert out.stdout.strip() == ""

    def test_full_width_model_is_not_built_on_a_pinned_cpu(self):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=60)
        assert out.returncode != 0
        assert "platform is cpu, not tpu" in out.stderr
        assert out.stdout.strip() == ""

    def test_the_last_line_holds_ok_and_the_device_and_nothing_else(self):
        """The driver reads the last line of stdout and refuses any key
        beyond these; the report's other fields go on the line before."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
        chip_smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(chip_smoke)
        report = {"ok": True, "model": "mistral-7b", "warmup_s": 6.4,
                  "device": {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}}
        line = json.loads(json.dumps(chip_smoke.verdict(report)))
        assert line == {"ok": True,
                        "device": {"platform": "tpu", "kind": "TPU v5 lite",
                                   "count": 1}}

    def test_alone_in_a_directory_it_fails_and_prints_no_result(
            self, tmp_path):
        import shutil

        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
        out = subprocess.run(
            [sys.executable, "chip_smoke.py", "--preset", "tiny"],
            env=env, cwd=tmp_path, capture_output=True, text=True,
            timeout=60)
        assert out.returncode != 0
        assert "symmetry_tpu" in out.stderr
        assert out.stdout.strip() == ""


class TestAttentionKernelsUnderTensorParallelism:
    """XLA cannot partition a pallas_call, so under a GSPMD mesh the
    attention kernels run per shard inside one shard_map (KV heads over
    `model`, batch over `data`). On the CPU mesh: same numbers as the
    unsharded kernel, for the shardings the engine actually uses."""

    @pytest.fixture(scope="class")
    def mesh(self):
        from symmetry_tpu.parallel.mesh import MeshSpec, build_mesh

        return build_mesh(MeshSpec(data=2, model=4))

    def test_flash_prefill_tp_matches_unsharded(self, mesh):
        import jax.numpy as jnp
        import numpy as np

        from symmetry_tpu.ops.flash import flash_prefill, flash_prefill_tp

        ks = jax.random.split(jax.random.key(0), 3)
        B, S, H, K, D = 2, 32, 8, 4, 16
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
        seq_lens = jnp.asarray([S, 11], jnp.int32)
        kw = dict(block_q=16, block_k=16, interpret=True)
        want = flash_prefill(q, k, v, seq_lens, **kw)
        got = jax.jit(lambda *a: flash_prefill_tp(*a, mesh=mesh, **kw))(
            q, k, v, seq_lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        # batch 1 does not divide data=2: the wrapper replicates it
        got1 = flash_prefill_tp(q[:1], k[:1], v[:1], seq_lens[:1],
                                mesh=mesh, **kw)
        np.testing.assert_allclose(np.asarray(got1), np.asarray(want[:1]),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("K", [4, 8])  # 1 KV head a chip, and 2: an
    @pytest.mark.parametrize("quantized", [False, True])  # int8 pair lies
    def test_decode_attention_tp_matches_unsharded(self, mesh, quantized,
                                                   K):  # head-major
        import jax.numpy as jnp
        import numpy as np

        from symmetry_tpu.ops.decode_attention import (
            decode_attention, decode_attention_tp)
        from symmetry_tpu.ops.quant import quantize_kv

        ks = jax.random.split(jax.random.key(1), 3)
        L, B, T, G, D = 2, 4, 256, 2, 128
        q = jax.random.normal(ks[0], (B, K * G, D), jnp.float32)
        k = jax.random.normal(ks[1], (L, B, T, K, D), jnp.float32)
        v = jax.random.normal(ks[2], (L, B, T, K, D), jnp.float32)
        lengths = jnp.asarray([T - 3, 5, T // 2, 1], jnp.int32)
        scales = ()
        if quantized:
            k, ksc = quantize_kv(k)
            v, vsc = quantize_kv(v)
            scales = (jnp.moveaxis(ksc, -1, -2), jnp.moveaxis(vsc, -1, -2))
        kw = dict(interpret=True)
        args = (q, k, v, jnp.int32(1), lengths, *scales)
        want = decode_attention(*args, **kw)
        got = jax.jit(lambda *a: decode_attention_tp(*a, mesh=mesh, **kw))(
            *args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_startup_attention_names_route_tile_and_block(self, mesh):
        """What `stats.engine.startup.attention` carries: a one-chip build
        decodes through the kernel (interpreted here) and names the slot
        tile and block it compiled with; a sharded trunk under
        TP_MIN_CAPACITY keeps the XLA path and names no geometry."""
        from symmetry_tpu.models.llama import attention_paths, preset
        from symmetry_tpu.ops.decode_attention import TP_MIN_CAPACITY
        from symmetry_tpu.parallel.mesh import MeshSpec, build_mesh

        # the dense cells: 128 slots x 640
        for name, block_t in (("mistral-7b", 128), ("qwen2-7b", 256)):
            assert attention_paths(preset(name), 640, None, batch=128,
                                   kv_bytes=1) == {
                "prefill": "pallas-interpret", "decode": "pallas-interpret",
                "decode_slot_tile": 128, "decode_block_t": block_t,
                # one chip's homogeneous trunk appends once a step (PR 66)
                "kv_append": "step"}
        # mixtral-8x7b.rag-closed: 64 slots x 2,048 over model: 4
        sharded = preset("mistral-7b")  # mixtral's attention, head for head
        assert attention_paths(sharded, 2048, mesh, batch=64,
                               kv_bytes=1) == {
            "prefill": "pallas-interpret", "decode": "xla"}
        # ... and from TP_MIN_CAPACITY up takes the per-shard kernel, as
        # it did before this gate was the mesh's alone: 2 int8 KV heads a
        # chip lie head-major, each a lane of 1,024-position blocks
        # (chip_smoke.py --mesh-model 4: 8 slots over data: 2)
        assert attention_paths(sharded, TP_MIN_CAPACITY, mesh, batch=8,
                               kv_bytes=1) == {
            "prefill": "pallas-interpret", "decode": "pallas-interpret",
            "decode_slot_tile": 4, "decode_block_t": 1024}
        two = build_mesh(MeshSpec(data=4, model=2))
        assert attention_paths(sharded, TP_MIN_CAPACITY, two, batch=8,
                               kv_bytes=2) == {
            "prefill": "pallas-interpret", "decode": "pallas-interpret",
            "decode_slot_tile": 2, "decode_block_t": 256}

    def test_engine_reports_the_decode_geometry(self):
        """The engine asks with its own slots and capacity, and what it
        reports is what `_layer` traces: a head-size-128 model at capacity
        128 decodes through the interpreted kernel."""
        import jax.numpy as jnp

        from symmetry_tpu.engine.engine import InferenceEngine
        from symmetry_tpu.engine.tokenizer import ByteTokenizer
        from symmetry_tpu.models import ModelConfig, init_params

        cfg = ModelConfig(vocab_size=512, hidden_size=128, num_layers=2,
                          num_heads=4, num_kv_heads=4, intermediate_size=128,
                          head_dim=128, rope_theta=10000.0, max_position=256)
        engine = InferenceEngine(
            cfg, init_params(cfg, jax.random.key(0), jnp.float32),
            ByteTokenizer(), max_slots=4, max_seq_len=128,
            prefill_buckets=(16,),
            cache_dtype=jnp.float32)
        assert engine.attention_paths() == {
            "prefill": "pallas-interpret", "decode": "pallas-interpret",
            "decode_slot_tile": 4, "decode_block_t": 128,
            "kv_append": "step"}
        # ... and decodes through it: two identical greedy prompts in
        # different lanes, beside different neighbours, agree
        from symmetry_tpu.engine.engine import SamplingParams

        prompt = list(b"the same prompt")
        a = [engine.prefill_and_insert(0, prompt, SamplingParams())]
        engine.prefill_and_insert(1, list(b"another"), SamplingParams())
        b = [engine.prefill_and_insert(3, prompt, SamplingParams())]
        for _ in range(4):
            toks = engine.decode_steps()[0]
            a.append(int(toks[0]))
            b.append(int(toks[3]))
        assert a[1:] == b[1:] and a[0] == b[0]

    def test_heads_that_do_not_divide_keep_the_xla_path(self, mesh):
        from symmetry_tpu.models.llama import attention_paths, preset

        tiny = preset("tiny")  # 2 KV heads over model=4
        assert attention_paths(tiny, 4096, mesh, batch=8, kv_bytes=1) == {
            "prefill": "xla", "decode": "xla"}
        assert attention_paths(tiny, 4096, None, batch=8,
                               kv_bytes=1)["prefill"] == "pallas-interpret"
