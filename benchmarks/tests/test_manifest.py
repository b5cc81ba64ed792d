"""BENCHMARK.json, the data files and the program's presets agree."""

import functools
import json
import os
import re

import pytest

from conftest import BENCH, CHECKOUT, cell_entries

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
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            if key in m:
                assert spec[key] == m[key], (m["name"], key)
        module, func = spec["reader"].rsplit(".", 1)
        assert callable(getattr(
            importlib.import_module(f"readers.{module}"), func))


@functools.lru_cache(maxsize=None)
def reading(name):
    """What an entry reads: its file's reader and parameters."""
    spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                       name + ".json")))
    return spec["reader"], json.dumps(spec.get("params") or {},
                                      sort_keys=True)


def read_twice(manifest):
    """(cell, entry, entry) wherever two entries over the same `(reader,
    params)` reach one cell."""
    found = []
    for w in manifest["workloads"]:
        seen = {}
        for m in cell_entries(manifest, w["name"]):
            other = seen.setdefault(reading(m["name"]), m)
            if other is not m:
                found.append((w["name"], other["name"], m["name"]))
    return found


CAP = 128   # the most `per_layer` entries a benchmark may have


@pytest.mark.parametrize("group,folder", [("end_to_end", "end_to_end"),
                                          ("per_layer", "layer_metrics")])
def test_one_entry_a_file_and_one_file_an_entry(group, folder):
    """Every entry has exactly one file and every file one entry (alike in
    every key they share: the test above)."""
    files = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, folder))
                   if f.endswith(".json"))
    assert files == sorted(m["name"] for m in MANIFEST[group])


def test_one_entry_a_reading_and_room_under_the_cap():
    """The fold's rule (PR 52): NO CELL is reached by two entries over the
    same `(reader, params)` — a copy of an entry that has no `workloads` key
    always breaks that, a copy that lists a new cell beside an entry that
    lists others never does — and `per_layer` keeps room for the next
    configuration's own entries."""
    free = CAP - len(MANIFEST["per_layer"])
    assert free >= 0, f"per_layer is {-free} entries over the cap of {CAP}"
    assert read_twice(MANIFEST) == [], f"({free} entries are free)"


def test_every_entry_moves_a_metric_its_cells_report():
    """An entry's `moves` names an end-to-end metric, and every cell the
    entry reaches reports that metric."""
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        target = e2e[m["moves"]]
        if "workloads" in target:
            assert set(m.get("workloads", cells)) <= set(
                target["workloads"]), m["name"]


def test_the_rule_catches_a_copy_and_lets_a_listed_one_pass():
    """Not vacuous: a second entry over `stats.decode_step_ms` for one cell
    (what PRs 28–47 added eight times, under a suffix) reaches that cell
    twice; a second entry over a reader whose first LISTS its cells,
    for a cell that list lacks, does not."""
    def with_copy_of(name, cell):
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        copy = dict(entry, workloads=[cell])
        return dict(MANIFEST, per_layer=MANIFEST["per_layer"] + [copy])

    cell = "mistral-7b.chat-open"
    assert read_twice(with_copy_of("decode_step_ms", cell)) == [
        (cell, "decode_step_ms", "decode_step_ms")]
    assert read_twice(with_copy_of("state_hbm_share", cell)) == []


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))))
def test_config_file_is_the_programs_preset(name):
    """Every published key the program reads, through its own reader
    (`config_from_hf`, as tier-1 `tests/test_manifest_cut.py` reads the cut
    files): a file's `intermediate_size` may be a dense width no layer uses,
    its eps and tie keys the family's own."""
    import dataclasses

    from symmetry_tpu.models.llama import config_from_hf, preset

    c = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    p = preset(c["tpu"]["model_preset"])
    q = config_from_hf(c)
    assert type(q) is type(p)
    # the oldest presets leave `head_dim` to be derived and `max_position`
    # at its default: the head's width is compared as the program uses it
    for f in dataclasses.fields(p):
        if f.name not in ("head_dim", "max_position"):
            assert getattr(q, f.name) == getattr(p, f.name), f.name
    assert q.dim_per_head == p.dim_per_head == c["head_dim"]
    assert (p.vocab_size, p.hidden_size, p.num_layers, p.num_heads,
            p.num_kv_heads) == (
        c["vocab_size"], c["hidden_size"], c["num_hidden_layers"],
        c["num_attention_heads"], c["num_key_value_heads"])
    # a cut is stated, in the file as in the manifest, with what was cut
    assert len(c["source"]) <= 200
    assert sorted(c.get("published", {})) == sorted(c["reduced"])
    for entry in MANIFEST["configs"]:
        if entry["file"] == f"benchmarks/configs/{name}.json":
            assert entry["reduced"] == c["reduced"]
            assert entry["source"] == c["source"]
