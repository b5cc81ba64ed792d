"""Count admission dispatches between two decode blocks, on any commit.

An instrument for a measurement, not part of the program: put this
directory on PYTHONPATH and name an output file, and every engine host
started below records, per decode-block dispatch, how many admission
dispatches (prefill + adopt + chunk) the scheduler queued since the block
before it — the number ROADMAP Speed 1 and `admit_seconds_per_block` are
judged by (PERF.md §6, PR 31). It reads only what every commit since PR 25
has: `Scheduler.metrics` and `engine.decode_steps_dispatch`.

    PYTHONPATH=tools/per_block PER_BLOCK_OUT=chiprun_out/per_block.json \\
        python benchmarks/run.py --workload mistral-7b.batch-closed ...
    python tools/per_block/sitecustomize.py chiprun_out/per_block.json

The file holds one [seconds, dispatches, live slots] triple per block; run
as a script this prints the histogram over the blocks dispatched with at
least half the slots live (the steady part of a closed loop).
"""

import atexit
import importlib.abc
import importlib.util
import json
import os
import sys
import time

TARGET = "symmetry_tpu.engine.scheduler"
KEYS = ("admit_dispatches", "adopt_dispatches", "chunk_dispatches")


def _instrument(module, out_path):
    init = module.Scheduler.__init__

    def __init__(self, engine, *args, **kwargs):
        init(self, engine, *args, **kwargs)
        dispatch = engine.decode_steps_dispatch
        rows, last = [], [0]

        def dump():
            tmp = f"{out_path}.{os.getpid()}.tmp"
            with open(tmp, "w") as fh:
                json.dump({"slots": engine.max_slots, "blocks": rows,
                           # PR 31 on: device seconds last measured per
                           # admission shape (kind, batch, bucket)
                           "shape_s": {
                               "/".join(map(str, k)): round(v, 4)
                               for k, v in getattr(
                                   self, "_shape_s", {}).items()}}, fh)
            os.replace(tmp, out_path)

        def decode_steps_dispatch():
            total = sum(self.metrics[k] for k in KEYS)
            rows.append([round(time.monotonic(), 3), total - last[0],
                         len(self._slots)])
            last[0] = total
            if len(rows) % 25 == 0:
                dump()
            return dispatch()

        engine.decode_steps_dispatch = decode_steps_dispatch
        atexit.register(dump)

    module.Scheduler.__init__ = __init__


class _Hook(importlib.abc.MetaPathFinder):
    def __init__(self, out_path):
        self.out_path = out_path

    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        exec_module = spec.loader.exec_module

        def exec_and_instrument(module):
            exec_module(module)
            _instrument(module, self.out_path)

        spec.loader.exec_module = exec_and_instrument
        return spec


def histogram(path):
    with open(path) as fh:
        data = json.load(fh)
    steady = [n for _t, n, live in data["blocks"]
              if live >= data["slots"] / 2]
    hist = {}
    for n in steady:
        hist[n] = hist.get(n, 0) + 1
    return {"blocks": len(steady), "all_blocks": len(data["blocks"]),
            "mean": round(sum(steady) / max(1, len(steady)), 3),
            "max": max(steady, default=0),
            "histogram": {str(k): hist[k] for k in sorted(hist)}}


if __name__ == "__main__":
    for arg in sys.argv[1:]:
        print(arg, json.dumps(histogram(arg)))
elif os.environ.get("PER_BLOCK_OUT"):
    sys.meta_path.insert(0, _Hook(os.path.abspath(
        os.environ["PER_BLOCK_OUT"])))
