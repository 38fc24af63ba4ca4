#!/usr/bin/env python
"""AOT rehearsal, no chip: compile each cell's step program for a DESCRIBED
``v5e:2x2`` at the cell's real shapes and print ``memory_analysis()``, so that
depth, batch and ``num_blocks`` are fixed before chip time is spent.

    JAX_PLATFORMS=cpu python benchmarks/rehearse.py [--workload <cell>] ...

It builds the cell exactly as the driver does (the program's model at real
width, on the CPU backend), but ``jax.jit`` of the step function is wrapped
so that the first call lowers for the described device and compiles there
instead of running. ``jax.default_backend`` is made to say "tpu" so that the
program's kernel dispatch takes its Pallas branch. A compile that passes is
not a run: nothing here is a time or a rate.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

STEP_FUNCTIONS = ("staged", "_step_impl")  # to_static's staged body, the engine's step


class Rehearsed(Exception):
    def __init__(self, report):
        super().__init__("rehearsed")
        self.report = report


def rehearse(ctx, driver, spans):
    """Build the cell and call its step once; the wrapped jit raises with the
    compile's report instead of running."""
    try:
        obj = driver.build(ctx)
        if hasattr(driver, "warm_up"):
            driver.warm_up(ctx, obj)
        else:
            driver.first_steps(ctx, obj, spans)
    except Rehearsed as r:
        return r.report
    return {"error": "the step function was never called"}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", help="cell name (default: every one-chip cell)")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cells = [w for w in bench["workloads"] if (not args.workload or w["name"] in args.workload)]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    real_jit = jax.jit

    def jit(fn, *a, **kw):
        jitted = real_jit(fn, *a, **kw)
        if getattr(fn, "__name__", "") not in STEP_FUNCTIONS:
            return jitted

        def lower_only(*args, **kwargs):
            shaped = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip) if hasattr(x, "shape") else x,
                (args, kwargs),
            )
            with jax.default_matmul_precision("default"):
                compiled = jitted.lower(*shaped[0], **shaped[1]).compile()
            m = compiled.memory_analysis()
            raise Rehearsed({
                "function": fn.__name__,
                "argument_bytes": m.argument_size_in_bytes, "output_bytes": m.output_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes, "temp_bytes": m.temp_size_in_bytes,
                "peak_bytes_estimate": m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes,
                "pallas_custom_calls": compiled.as_text().count("tpu_custom_call"),
            })

        return lower_only

    jax.jit = jit
    import importlib

    from lib import spans

    for entry in cells:
        ctx = harness.Context(entry, seed=0, seconds=1.0)
        ctx.devices = jax.devices()[:1]
        driver = importlib.import_module(f"lib.drivers.{ctx.cell['driver']}")
        report = rehearse(ctx, driver, spans.Spans())
        print(json.dumps({"cell": entry["name"], "compiled_for": "v5e:2x2 (described, one chip)", **report}), flush=True)
        gc.collect()  # the cell's model, before the next is built
    return 0


if __name__ == "__main__":
    sys.exit(main())
