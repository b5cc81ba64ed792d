"""Is a judged metric steady enough in a cell to be held to its bound?

    python benchmarks/admit.py --cell <cell> [--also reader:k=v,k=v]... \
        SET [SET ...]

A SET is a glob (or a comma-separated list) of run dumps — what `run.py
--dump <dir>` wrote, `.json` or `.json.gz` — of ONE set of runs of the same
code. Every number is recomputed from the dump's client records by the cell's
own `end_to_end` readers (the files `run.py` reads), so a statistic can be
tried on runs that were made before it existed; `--also` adds candidate
readings that are in no manifest yet (`client.gap_band_mean_s:lo=80,hi=99`).
`setup_s` is the dump's own (dumps older than PR 52 hold none, and the metric
is then left out).

The rule (PERF.md §2): a metric is admitted in a cell where every set's
range, after leaving out the run farthest from the set's median, is at most
half the metric's bound, as a share of the median. The quartile spread the
driver uses (`statistics.quantiles(n=4)`, the same run left out) is printed
beside it. It touches neither JAX nor the chip.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import importlib
import json
import os
import statistics
import sys
from types import SimpleNamespace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from lib import harness  # noqa: E402
import run as bench_run  # noqa: E402


def load_dump(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        return json.load(fh)


def context(cell: harness.Cell, dump: dict) -> SimpleNamespace:
    """What the client and stats readers read of a `RunContext`, from a
    dump: records, window, samples, `setup_s`."""
    samples = [tuple(s) for s in dump.get("samples") or []]
    phase = SimpleNamespace(
        records=dump["records"], w0=dump["w0"], w1=dump["w1"],
        samples=samples, stats_start=samples[0][1] if samples else {},
        stats_end=samples[-1][1] if samples else {})
    return SimpleNamespace(cell=cell, phase=phase,
                           setup_s=dump.get("setup_s"), device={},
                           trace=None)


def candidate(spec: str):
    """`module.function:k=v,k=v` -> (label, callable of a context)."""
    reader, _, raw = spec.partition(":")
    module, func = reader.rsplit(".", 1)
    fn = getattr(importlib.import_module(f"readers.{module}"), func)
    params = {k: float(v) for k, v in
              (kv.split("=") for kv in raw.split(",") if kv)}
    return spec, lambda ctx: fn(ctx, **params)


def trimmed(values: list[float]) -> list[float]:
    """The set without the run farthest from its median (sets of 3+)."""
    if len(values) < 3:
        return list(values)
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def spreads(values: list[float]) -> dict:
    med = statistics.median(values)
    kept = trimmed(values)
    out = {"median": med, "range": (max(values) - min(values)) / med,
           "range_trimmed": (max(kept) - min(kept)) / med}
    for key, vs in (("iqr", values), ("iqr_trimmed", kept)):
        if len(vs) >= 2:
            q = statistics.quantiles(vs, n=4)
            out[key] = (q[2] - q[0]) / statistics.median(vs)
    return out


def expand(spec: str) -> list[str]:
    paths = []
    for part in spec.split(","):
        paths.extend(sorted(glob.glob(part)) or [part])
    return paths


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--also", action="append", default=[])
    ap.add_argument("--json", action="store_true",
                    help="print the table as one JSON object instead")
    ap.add_argument("sets", nargs="+")
    args = ap.parse_args()
    cell = harness.load_cell(args.cell, args.manifest)
    entries = bench_run.metric_entries(cell, "end_to_end")
    readers = [(e["name"], e.get("bound"),
                lambda ctx, e=e: bench_run.read_metric(
                    cell, "end_to_end", e, ctx)) for e in entries]
    readers += [(label, None, fn) for label, fn in map(candidate, args.also)]
    table: dict = {"cell": cell.name, "sets": []}
    admitted: dict[str, bool] = {}
    for k, spec in enumerate(args.sets, 1):
        paths = expand(spec)
        ctxs = [context(cell, load_dump(p)) for p in paths]
        row = {"runs": [os.path.basename(p) for p in paths], "metrics": {}}
        for name, bound, fn in readers:
            values = [fn(c) for c in ctxs]
            if any(v is None for v in values):
                continue
            s = spreads(values)
            row["metrics"][name] = {"values": values, **s}
            if bound is not None and name != "setup_s":
                ok = s["range_trimmed"] <= bound / 2
                admitted[name] = admitted.get(name, True) and ok
        table["sets"].append(row)
        if not args.json:
            print(f"{cell.name} set {k}: {len(paths)} runs")
            for name, m in row["metrics"].items():
                vals = " ".join(f"{v:.5g}" for v in m["values"])
                print(f"  {name:38s} median {m['median']:.5g}  range "
                      f"{100 * m['range']:.2f}%  trimmed "
                      f"{100 * m['range_trimmed']:.2f}%  iqr "
                      f"{100 * m.get('iqr', 0):.2f}% / "
                      f"{100 * m.get('iqr_trimmed', 0):.2f}%  [{vals}]")
    table["admitted"] = admitted
    if args.json:
        print(json.dumps(table))
    else:
        print(f"admitted (every set's trimmed range <= bound / 2): "
              f"{admitted}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
