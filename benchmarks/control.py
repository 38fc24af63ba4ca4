#!/usr/bin/env python
"""The controls of "how `correct` is decided", run by hand on the chip at the
cell's own size, several seeds in ONE process (set-up is long). Never run by
the benchmark's own runs.

    python benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds 12 --lower int8
    python benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds 20 --set engine.kv_cache_dtype=int8
    python benchmarks/control.py --workload <cell> --seeds 1,2,3 --probe --set engine.weight_only_int8=true

For every seed it runs the cell's driver for a short window and prints the
numbers ``correct`` compares. With ``--set section.key=value`` the PROGRAM runs
with a lower-precision path of its own switched on (the control of a serving
cell), and ``correct`` has to come out false. With ``--lower`` (training) the
reference, put in the program's place at that lower precision, is read beside
the sound program. With ``--probe`` (serving) only the comparison that needs
no window is made, which is much shorter.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


def main() -> int:
    import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--lower", default=None)
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()

    entry = harness.find_cell(harness.load_json(ROOT, "BENCHMARK.json"), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(entry, seed, args.seconds)
        for item in args.set:
            key, _, value = item.partition("=")
            section, _, name = key.partition(".")
            ctx.cell[section][name] = json.loads(value) if value[:1] in "0123456789tf[{\"" else value
        ctx.control = args.lower
        harness.on_the_chip(ctx)
        driver = importlib.import_module(f"lib.drivers.{ctx.cell['driver']}")
        if args.probe:
            print(json.dumps({"seed": seed, "overrides": args.set, **driver.probe_only(ctx)}), flush=True)
            continue
        result = driver.run(ctx)
        for row in result["checks"]:
            ctx.log("check", seed=seed, **row)
        print(json.dumps({"seed": seed, "overrides": args.set, "lower": args.lower,
                          "correct": all(r["ok"] for r in result["checks"]), "e2e": result["e2e"],
                          "memory_peak_bytes": result["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
