#!/usr/bin/env python
"""Records the small trace kept in ``benchmarks/testdata/`` (run on the chip):
three steps of one jitted matmul chain, each inside a ``bench.step`` span, with
an idle gap between them. Writes ``chiprun_out/testdata/small.xplane.pb``."""

import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = os.path.join(os.getcwd(), "chiprun_out", "testdata")
tmp = os.path.join(os.getcwd(), ".bench_trace", "_record")
shutil.rmtree(tmp, ignore_errors=True)
os.makedirs(out, exist_ok=True)
f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
x = jnp.ones((1024, 1024), jnp.bfloat16)
f(x).block_until_ready()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 2
jax.profiler.start_trace(tmp, profiler_options=options)
for i in range(3):
    with jax.profiler.TraceAnnotation("bench.step"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.pause"):
        time.sleep(0.01)
jax.profiler.stop_trace()
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
from lib import xplane  # noqa: E402

path = xplane.find_xplane(tmp)
shutil.copy(path, os.path.join(out, "small.xplane.pb"))
print(os.path.getsize(path), {k: v for k, v in xplane.load(path)["lines"].items()})
