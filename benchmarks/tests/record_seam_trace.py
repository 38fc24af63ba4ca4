#!/usr/bin/env python
"""Records the third small trace kept in ``benchmarks/testdata/`` (run on the
chip): a small engine behind ``ServingFrontend`` pumped a few times in a row,
each pump inside a ``bench.frontend.pump`` span as the serve driver makes them.
The sub-phases of ``engine.launch`` and ``engine.wait`` (PR 38) are in it,
nested in their parents, beside the device's operations. Writes
``chiprun_out/testdata/seam.xplane.pb`` (the ``/host:metadata`` plane dropped)
and, beside it, ``seam.json``: the engine's counters over the traced pumps and
what ``lib/seam.py`` read from the trace on the day."""

import json
import os
import shutil
import sys

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", ".."))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.serving import ServingFrontend  # noqa: E402

from lib import phases, seam, xplane  # noqa: E402
from lib import xspace  # noqa: E402


def keep_planes(src, dst, drop=("/host:metadata",)):
    """Copy an XSpace without the planes named (``record_phases_trace.py``'s,
    which cannot be imported without recording): ``/host:metadata`` holds the
    program's whole HLO and no reader looks at it."""
    with open(src, "rb") as fh:
        space = fh.read()
    kept = bytearray()
    i = 0
    while i < len(space):
        start = i
        key, i = xspace._varint(space, i)
        if key & 7 != 2:
            raise ValueError("unexpected field in an XSpace")
        size, i = xspace._varint(space, i)
        i += size
        if not (key >> 3 == 1 and xspace._plane(space[i - size:i])["name"] in drop):
            kept += space[start:i]
    with open(dst, "wb") as fh:
        fh.write(bytes(kept))


PUMPS = 8
out = os.path.join(os.getcwd(), "chiprun_out", "testdata")
tmp = os.path.join(os.getcwd(), ".bench_trace", "_record_seam")
shutil.rmtree(tmp, ignore_errors=True)
os.makedirs(out, exist_ok=True)
paddle.seed(7)
cfg = LlamaConfig(vocab_size=512, hidden_size=256, intermediate_size=512, num_hidden_layers=2,
                  num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=512)
model = LlamaForCausalLM(cfg).to(dtype="bfloat16")
model.eval()
engine = ContinuousBatchingEngine(model, max_slots=2, block_size=16, num_blocks=64, max_model_len=256,
                                  prompt_bucket=128)
frontend = ServingFrontend(engine)
rng = np.random.default_rng(7)
prompt = rng.integers(1, cfg.vocab_size, 40).astype(np.int32)
for _ in range(2):  # warm: compile, a prefix-cache hit with a CoW fork, a few decode steps
    h = frontend.submit(prompt, max_new_tokens=4)
    while not h.finished:
        frontend.pump()
handles = [frontend.submit(rng.integers(1, cfg.vocab_size, 24).astype(np.int32), max_new_tokens=64) for _ in range(2)]
for _ in range(4):
    frontend.pump()

options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 2
before = dict(engine.stats)
jax.profiler.start_trace(tmp, profiler_options=options)
for _ in range(PUMPS):
    with jax.profiler.TraceAnnotation("bench.frontend.pump"):
        frontend.pump()
jax.profiler.stop_trace()
delta = {k: engine.stats[k] - before[k] for k in engine.stats}

path = os.path.join(out, "seam.xplane.pb")
keep_planes(xplane.find_xplane(tmp), path)
raw = xplane.load(path)
run = {"trace": {"raw": raw}, "xplane_path": path, "counters": {"engine": delta}}
if phases.program_trace(run) is None:
    sys.exit("the trace holds no TPU plane: record it on the chip")
steps = seam.steps(run)
readings = {"seam_idle_ms": seam.seam_idle_ms(run), "call_to_first_op_ms": seam.call_to_first_op_ms(run),
            "last_op_to_wake_ms": seam.last_op_to_wake_ms(run), "trace_clock_slack_ms": seam.trace_clock_slack_ms(run),
            "launch_to_first_op_ms": phases.launch_to_first_op_ms(run)}
with open(os.path.join(out, "seam.json"), "w") as fh:
    json.dump({"engine": delta, "pumps": PUMPS, "depth": cfg.num_hidden_layers, "readings": readings,
               "steps": steps}, fh, indent=1)
print(os.path.getsize(path), {k: v for k, v in raw["lines"].items() if "TPU" in k or "CPU" in k})
print(json.dumps({"engine": {k: v for k, v in delta.items() if "phase" in k or k == "steps"}, "readings": readings,
                  "spans": {n: len(phases.spans_named(run, n)) for n in phases.PHASES + seam.SUBPHASES},
                  "steps": steps}))
seam.note(run)
