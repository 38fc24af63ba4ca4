#!/usr/bin/env python
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the machine it is started on. It finds the cell in
``BENCHMARK.json``, its parameters in ``benchmarks/workloads/<cell>.json``, its
configuration in ``benchmarks/configs/<config>.json``, its traffic mix in
``benchmarks/traffic/<traffic>.json``, its loop in
``benchmarks/lib/drivers/<driver>.py`` and each per-layer metric's reader in
``benchmarks/metrics/<metric>.py``: this file names none of them. It fails,
with no result line, unless JAX finds a TPU with the chips the cell asks for.
Earlier stdout lines are JSON notes; the LAST line is the result, whose last
key ``checks`` holds every number ``correct`` compared beside its limit; the
same are the last lines of stderr.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


class Context:
    """What a driver gets: the run's arguments, the cell's files, the devices,
    and the harness's clock for set-up."""

    def __init__(self, entry: Dict[str, Any], seed: int, seconds: float, trace: bool = False) -> None:
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.entry = entry
        self.cell = load_json(HERE, "workloads", f"{entry['name']}.json")
        self.config = load_json(HERE, "configs", f"{entry['config']}.json")
        self.mix = load_json(HERE, "traffic", f"{entry['traffic']}.json")
        self.chips = int(entry["chips"])
        self.trace_dir = os.path.join(ROOT, ".bench_trace", entry["name"])
        self.devices: List[Any] = []
        self.setup_s: Optional[float] = None
        self.laps: Dict[str, float] = {}

    def log(self, what: str, **kv: Any) -> None:
        print(json.dumps({"note": what, **kv}, default=float), flush=True)

    def lap(self, name: str) -> None:
        """Seconds since process start at which a part of set-up was done."""
        self.laps[name] = time.perf_counter() - T_PROCESS_START

    def mark_setup_done(self, at: Optional[float] = None) -> None:
        """Process start to the first timed event (``at`` on the host's
        clock, where the driver fixed that moment in advance; else now)."""
        self.setup_s = (time.perf_counter() if at is None else at) - T_PROCESS_START
        self.log("setup", setup_s=self.setup_s, done_at_s=self.laps)

    def memory_peak(self) -> int:
        from lib import device

        return device.memory_peak_bytes(self.devices)


def load_reader(name: str) -> Any:
    """A per-layer metric's reader: ``benchmarks/metrics/<name>.py`` (a name may
    hold dots, so it is loaded by path). A split quantity
    (``<quantity>.<split>``) without a file of its own is read by
    ``benchmarks/metrics/<quantity>.py``."""
    import importlib.util

    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, "metrics", f"{name.rsplit('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json")
    return entry


def on_the_chip(ctx: Context) -> Dict[str, Any]:
    """Turn the compile cache on and take the chips the cell asks for; raises
    where there is no TPU (no fallback). Returns the device as JAX reports it."""
    from paddle_tpu.core.compile_cache import enable_compile_cache

    import jax

    cache_dir = enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx.lap("imports")

    from lib import device

    ctx.devices = device.require_tpu(ctx.chips)
    ctx.lap("devices")
    return dict(device.describe(ctx.devices), compile_cache=cache_dir)


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    entry = find_cell(bench, args.workload)
    ctx = Context(entry, args.seed, args.seconds, bool(args.trace))
    found = on_the_chip(ctx)
    described = {k: found[k] for k in ("platform", "kind", "count")}

    from lib import device

    peaks = device.peaks(described["kind"])
    ctx.log("start", cell=entry["name"], seed=ctx.seed, seconds=ctx.seconds, trace=ctx.trace, **found)

    driver = importlib.import_module(f"lib.drivers.{ctx.cell['driver']}")
    result = driver.run(ctx)
    run = result["run"]
    run.update({"peaks": peaks, "chips": ctx.chips, "cell": ctx.cell, "e2e": result["e2e"],
                "memory_peak_bytes": result["memory_peak_bytes"]})

    for row in result["checks"]:
        ctx.log("check", **row)
    correct = all(row["ok"] for row in result["checks"])

    metrics: Dict[str, Dict[str, Any]] = {}
    if not ctx.trace:
        values = dict(result["e2e"], setup_s=ctx.setup_s)
        for m in bench["end_to_end"]:
            if applies(m, entry["name"]):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if not applies(m, entry["name"]):
                continue
            reader = load_reader(m["name"])
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(result["attempted"]), "failed": int(result["failed"]),
        "metrics": metrics,
        "device": dict(described, memory_peak_bytes=int(result["memory_peak_bytes"])),
    }
    if ctx.trace and run.get("trace"):
        from lib import xplane

        reduced = run["trace"]["reduced"]
        ctx.log("trace", lines=run["trace"]["raw"]["lines"], per_device=reduced["per_device"],
                ops=sorted(reduced["op_time_s"].items(), key=lambda kv: -kv[1])[:40],
                longest_gaps=reduced["longest_gaps"])
        out["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = xplane.breakdown(reduced)
    # every number compared beside its limit: last in the result's line, and the last lines of stderr
    out["checks"] = {row["name"]: {"value": _plain(row["value"]), "limit": _plain(row["limit"]), "ok": bool(row["ok"])}
                     for row in result["checks"]}
    for name, row in out["checks"].items():
        print(f"check {name} = {row['value']} limit {row['limit']} {'ok' if row['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def _plain(x: Any) -> Any:
    """A number as JSON can carry it (a non-finite one as its name)."""
    x = float(x)
    return x if x == x and abs(x) != float("inf") else str(x)


if __name__ == "__main__":
    sys.exit(main())
