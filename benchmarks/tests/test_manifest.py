"""BENCHMARK.json, the data files and the program's presets agree."""

import json
import os
import re

import pytest

from conftest import BENCH, CHECKOUT

MANIFEST = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_contract_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert len(set(cells)) == len(cells)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= 1
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in MANIFEST["workloads"])
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in MANIFEST[g]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e
        # the moved metric is reported in every cell where this one is
        mine = set(m.get("workloads", cells))
        assert mine <= set(e2e[m["moves"]].get("workloads", cells)), m


def test_every_cell_reports_enough():
    for w in MANIFEST["workloads"]:
        def mine(group):
            return [m["name"] for m in MANIFEST[group]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine("end_to_end")
        assert len(mine("end_to_end")) >= 2 and mine("per_layer")


def test_ttft_is_judged_in_no_closed_loop_cell():
    """TTFT in a closed loop is the queue wait the client count imposes; no
    end-to-end metric with `ttft` in its name may list a closed-loop cell."""
    for m in MANIFEST["end_to_end"]:
        if "ttft" not in m["name"]:
            continue
        assert "workloads" in m
        for w in MANIFEST["workloads"]:
            t = json.load(open(os.path.join(BENCH, "traffic",
                                            w["traffic"] + ".json")))
            if t["loop"] == "closed":
                assert w["name"] not in m["workloads"]


@pytest.mark.parametrize("group,folder", [("end_to_end", "end_to_end"),
                                          ("per_layer", "layer_metrics")])
def test_every_metric_has_its_reader_file(group, folder):
    import importlib

    for m in MANIFEST[group]:
        spec = json.load(open(os.path.join(BENCH, folder,
                                           m["name"] + ".json")))
        for key in ("unit", "better", "source", "layer", "moves"):
            if key in m:
                assert spec[key] == m[key], (m["name"], key)
        module, func = spec["reader"].rsplit(".", 1)
        assert callable(getattr(
            importlib.import_module(f"readers.{module}"), func))


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))))
def test_config_file_is_the_programs_preset(name):
    from symmetry_tpu.models.llama import preset

    c = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    p = preset(c["tpu"]["model_preset"])
    assert (p.vocab_size, p.hidden_size, p.num_layers, p.num_heads,
            p.num_kv_heads, p.intermediate_size, p.dim_per_head) == (
        c["vocab_size"], c["hidden_size"], c["num_hidden_layers"],
        c["num_attention_heads"], c["num_key_value_heads"],
        c["intermediate_size"], c["head_dim"])
    assert p.rope_theta == c["rope_theta"] and p.rms_eps == c["rms_norm_eps"]
    assert p.attention_bias == bool(c.get("attention_bias", False))
    assert p.tie_embeddings == c["tie_word_embeddings"]
    assert len(c["source"]) <= 200 and c["reduced"] == []
