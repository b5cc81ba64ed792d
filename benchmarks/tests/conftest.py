"""Tests of the benchmark's own code. CPU, seconds. Run with
`python -m pytest benchmarks/tests -q` from the checkout's root."""

import os
import sys
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
CHECKOUT = os.path.dirname(BENCH)
for p in (BENCH, CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)


def cell_entries(manifest: dict, cell: str, group: str = "per_layer"):
    """The entries of `group` that reach `cell`, by `run.py metric_entries`
    itself: those with no `workloads` key, and those that list the cell."""
    import run

    return run.metric_entries(SimpleNamespace(manifest=manifest, name=cell),
                              group)


def rehome(tiny: dict, real: dict, real_cell: str, tiny_cell: str) -> list:
    """Give `tiny_cell` of the tests' manifest every per-layer entry that
    reaches `real_cell` in the real one, under the same rule: an entry with
    no `workloads` key keeps none, one that lists the real cell lists the
    tiny one. Returns the names that now reach the tiny cell."""
    have = {m["name"]: m for m in tiny["per_layer"]}
    for entry in cell_entries(real, real_cell):
        mine = have.get(entry["name"])
        if mine is None:
            mine = dict(entry)
            tiny["per_layer"].append(mine)
            if "workloads" in mine:
                mine["workloads"] = []
        if "workloads" in mine:
            mine["workloads"].append(tiny_cell)
    return [m["name"] for m in cell_entries(tiny, tiny_cell)]
