#!/usr/bin/env python
"""Records the second small trace kept in ``benchmarks/testdata/`` (run on the
chip): a small engine behind ``ServingFrontend`` pumped a few times, each pump
inside a ``bench.frontend.pump`` span with a pause after it, then two jitted
train steps of the same small model inside ``bench.train.step`` spans. The
program's phases (``paddle_tpu.*``), its kernel names and its scopes are in it.
Writes ``chiprun_out/testdata/phases.xplane.pb`` and, beside it,
``phases.json``: the engine's counters over the traced pumps."""

import json
import os
import shutil
import sys
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", ".."))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.serving import ServingFrontend  # noqa: E402

from lib import xplane  # noqa: E402

PUMPS, TRAIN_STEPS = 4, 2
out = os.path.join(os.getcwd(), "chiprun_out", "testdata")
tmp = os.path.join(os.getcwd(), ".bench_trace", "_record_phases")
shutil.rmtree(tmp, ignore_errors=True)
os.makedirs(out, exist_ok=True)
paddle.set_flags({"FLAGS_enable_metrics": True})
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

train_model = LlamaForCausalLM(cfg).to(dtype="bfloat16")
opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=train_model.parameters(), multi_precision=True)


@paddle.jit.to_static
def train_step(model, opt, ids, labels):
    loss, _ = model(ids, labels=labels)
    loss.backward()
    opt.step()
    opt.clear_grad()
    return loss


ids = paddle.to_tensor(rng.integers(1, cfg.vocab_size, (2, 256)).astype(np.int32))
for _ in range(3):
    float(train_step(train_model, opt, ids, ids))

options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 2
before = dict(engine.stats)
jax.profiler.start_trace(tmp, profiler_options=options)
for _ in range(PUMPS):
    with jax.profiler.TraceAnnotation("bench.frontend.pump"):
        frontend.pump()
    with jax.profiler.TraceAnnotation("bench.pause"):
        time.sleep(0.003)
for _ in range(TRAIN_STEPS):
    with jax.profiler.TraceAnnotation("bench.train.step"):
        float(train_step(train_model, opt, ids, ids))
jax.profiler.stop_trace()
delta = {k: engine.stats[k] - before[k] for k in engine.stats}

from paddle_tpu.kernels.select import fallback_counts  # noqa: E402



def keep_planes(src, dst, drop=("/host:metadata",)):
    """Copy an XSpace without the planes named: ``/host:metadata`` holds the
    programs' whole HLO (two thirds of the file) and no reader looks at it."""
    from lib import xspace

    with open(src, "rb") as fh:
        space = fh.read()
    kept = bytearray()
    i = 0
    while i < len(space):
        start = i
        key, i = xspace._varint(space, i)
        if key & 7 == 0:
            _v, i = xspace._varint(space, i)
        elif key & 7 == 2:
            size, i = xspace._varint(space, i)
            i += size
        else:
            raise ValueError("unexpected field in an XSpace")
        is_dropped = key == (1 << 3 | 2) and xspace._plane(space[i - size:i])["name"] in drop
        if not is_dropped:
            kept += space[start:i]
    with open(dst, "wb") as fh:
        fh.write(bytes(kept))


path = xplane.find_xplane(tmp)
keep_planes(path, os.path.join(out, "phases.xplane.pb"))
path = os.path.join(out, "phases.xplane.pb")
with open(os.path.join(out, "phases.json"), "w") as fh:
    json.dump({"engine": delta, "pumps": PUMPS, "train_steps": TRAIN_STEPS, "depth": cfg.num_hidden_layers,
               "fallbacks": dict(fallback_counts())}, fh, indent=1)
from lib import phases  # noqa: E402

raw = xplane.load(path)
run = {"trace": {"raw": raw}, "xplane_path": path}
trace = phases.program_trace(run)
print(os.path.getsize(path), {k: v for k, v in raw["lines"].items() if "TPU" in k or "CPU" in k})
if trace is None:
    sys.exit("the trace holds no TPU plane: record it on the chip")
print(json.dumps({"engine": delta, "fallbacks": dict(fallback_counts()),
                  "spans": {n: len(phases.spans_named(run, n)) for n in phases.PHASES},
                  "kernels": sorted({phases.kernel_of(n) for n, *_ in trace["ops"] if phases.kernel_of(n)}),
                  "scopes": sorted({phases.scope_of(trace, n) for n, *_ in trace["ops"] if phases.scope_of(trace, n)}),
                  "named_pct": phases.named_share_pct(run), "paged_pct": phases.kernel_share_pct(run, phases.PAGED_KERNELS),
                  "launch_to_first_op_ms": phases.launch_to_first_op_ms(run),
                  "idle_by_phase": phases.idle_by_phase_s(run)}))
seen = set()
for n, _a, _b, _d in trace["ops"]:
    if ("custom-call" in n or "conditional" in n or "copy" in n[:12]) and n not in seen and len(seen) < 40:
        seen.add(n)
        print(n[:200], "| tf_op:", trace["scopes"].get(n))
