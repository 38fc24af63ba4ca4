#!/usr/bin/env python
"""Find a serving cell's knee ONCE, on the chip: the highest of a few fixed
rates at which the engine, already in steady state, keeps up. One process, one
engine; each rate gets its own seed (new token contents) and its own ramp
(``--ramp`` seconds of the same traffic before the window, so that the window
starts on a serving engine), and the engine is drained between rates. A rate
keeps up when, at the half and at the close of the window, no more requests
are in the system than the engine has slots (nobody queues for a slot); the
waits for a first token in each half are printed beside. Prints one JSON line per rate and writes them all to
``chiprun_out/sweeps/<cell>.json``; the chosen points are then kept in
``benchmarks/sweeps/<cell>.json`` and the cell's ``rate_rps`` is set by hand
to 0.8 of the knee.

    python benchmarks/sweep.py --workload <cell> --rates 0.5,0.8,1.1 --seconds 40 --ramp 30 --seed 900
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


def in_system(records, t: float) -> int:
    """Requests due by ``t`` and not finished by ``t``."""
    return sum(1 for r in records if r.request.due_s <= t and (r.done_s is None or r.done_s > t))


def waiting(records, t: float) -> int:
    """Requests due by ``t`` whose first token had not come by ``t``."""
    return sum(1 for r in records if r.request.due_s <= t and (not r.stamps or r.stamps[0] > t))


def main() -> int:
    import run as harness
    from lib import device, program, stats, traffic
    from lib.spans import Spans

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--ramp", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=900)
    args = ap.parse_args()

    entry = harness.find_cell(harness.load_json(ROOT, "BENCHMARK.json"), args.workload)
    ctx = harness.Context(entry, args.seed, args.seconds)
    harness.on_the_chip(ctx)
    driver = importlib.import_module(f"lib.drivers.{ctx.cell['driver']}")
    program.enable_counters()
    obj = driver.build(ctx)
    driver.warm_up(ctx, obj)
    points = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        seed = args.seed + 1000 * (i + 1)
        requests = traffic.make_requests(ctx.mix, rate, args.seconds, obj["cfg"]["vocab_size"], seed, ramp_s=args.ramp)
        steps0 = obj["engine"].stats["steps"]
        win = driver.window(ctx, obj, requests, Spans(), None, ramp_s=args.ramp)
        e = driver.end_to_end(win)
        half, close = 0.5 * win["closed_at"], win["closed_at"]
        recs = win["records"]

        def ttft_p50(lo, hi):
            v = [r.stamps[0] - r.request.due_s for r in recs if lo <= r.request.due_s < hi and r.stamps]
            return 1e3 * stats.percentile(v, 50) if v else None

        pumps = [p for p in win["pumps"] if p[0] >= 0 and p[1] <= close]
        point = {
            "rate_rps": rate, "seed": seed, "seconds": args.seconds, "ramp_s": args.ramp, "sent": e["sent"],
            "failed": e["failed"], "completed_in_window": e["completed_in_window"],
            "in_system_at": [in_system(recs, t) for t in (0.0, half, close)],
            "without_first_token_at": [waiting(recs, t) for t in (0.0, half, close)],
            "ttft_p50_ms_first_half": ttft_p50(0.0, half), "ttft_p50_ms_second_half": ttft_p50(half, close + 1.0),
            "step_s_mean": close / max(1, len(pumps)),
            "kv_live_blocks_mean": sum(p[3] for p in pumps) / max(1, len(pumps)),
            "engine_steps": obj["engine"].stats["steps"] - steps0,
            **e["values"], **{k: e["beside"][k] for k in ("ttft_p50_ms", "itl_p50_ms", "generator_late_max_ms", "tail_phase_s")},
            "max_slots": obj["engine"].max_slots, "num_blocks": obj["engine"].num_blocks,
            "device": device.describe(ctx.devices), "memory_peak_bytes": ctx.memory_peak(),
        }
        points.append(point)
        print(json.dumps(point), flush=True)
        while obj["engine"].has_work():
            obj["frontend"].pump()
    out = os.path.join(ROOT, "chiprun_out", "sweeps")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{args.workload}.json"), "w") as fh:
        json.dump({"cell": args.workload, "points": points}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
