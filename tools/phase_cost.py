#!/usr/bin/env python
"""What the serving step's phases cost the host, per step (PERF.md, PR 23).

Two timings on the machine this runs on, one JSON line:

- ``sequence_us``: the instrumentation of one pump alone, in a loop with no
  work inside: the thirteen ``observability.tracing.phase`` blocks
  (``frontend.pump``, two ``frontend.deliver``, ``engine.decode_step``, its
  four children and the five sub-phases of ``engine.launch`` and
  ``engine.wait``, with the instants they share) and ``close_step`` with its
  one ``thread_time`` read; beside ``stub_us``, the same loop with a phase that
  keeps the two clock reads the step needs anyway (its histogram, devprof) and
  nothing else. The difference is what the primitive adds.
  ``sequence_no_subphases_us`` is the loop with the eight blocks PR 23 had:
  ``subphases_added_us_per_step`` is what PR 38's five cost.
  ``sequence_profiling_us`` is the first loop again while a profile is taken,
  ``sequence_full_rate_us`` at ``FLAGS_trace_sample_rate=1`` (every phase a
  span in the ring).
- ``pump_us`` / ``pump_stub_us``: the median wall of ``ServingFrontend.pump()``
  on a small engine, with the primitive and with the stub in its place, in
  alternating blocks.
"""

import json
import os
import shutil
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference import engine as engine_module  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402
from paddle_tpu.serving import ServingFrontend  # noqa: E402
from paddle_tpu.serving import frontend as frontend_module  # noqa: E402


class StubPhase:
    """The clock reads a step needs with no phases at all, and nothing else."""

    __slots__ = ("start_s", "end_s", "record", "attrs", "step")

    def __init__(self, name, sink=None, key=None, step=None, start_s=None):
        self.start_s, self.end_s, self.record, self.attrs, self.step = start_s, None, False, None, step

    def __enter__(self):
        if self.start_s is None:
            self.start_s = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.end_s is None:
            self.end_s = time.perf_counter()


class _Tracing:
    """``observability.tracing`` with the stub in place of ``phase``."""

    def __init__(self, phase):
        self.phase = phase

    def __getattr__(self, name):
        return getattr(tracing, name)


def sequence(phase, engine, n=20000, subphases=True):
    stats = engine.stats

    def tiled(names, parent):
        """Sub-phases as ``engine._next_subphase`` lays them: each starts where
        the one before ended, the parent ends where the last did."""
        at = parent.start_s
        for name, key in names:
            with phase(name, stats, key, 1, at) as sub:
                pass
            at = sub.end_s
        parent.end_s = at

    launch_subs = (("engine.launch.put", "subphase_s.launch_put"), ("engine.launch.args", "subphase_s.launch_args"),
                   ("engine.launch.call", "subphase_s.launch_call")) if subphases else ()
    wait_subs = (("engine.wait.ready", "subphase_s.wait_ready"),
                 ("engine.wait.fetch", "subphase_s.wait_fetch")) if subphases else ()
    t0 = time.perf_counter()
    for _ in range(n):
        with phase("frontend.pump") as pump:
            with phase("frontend.deliver", stats, "phase_s.deliver", None, pump.start_s) as before:
                pass
            with phase("engine.decode_step", None, None, 1, before.end_s) as whole:
                with phase("engine.plan", stats, "phase_s.plan", 1, whole.start_s) as plan:
                    pass
                with phase("engine.launch", stats, "phase_s.launch", 1, plan.end_s) as launch:
                    if launch_subs:
                        tiled(launch_subs, launch)
                with phase("engine.wait", stats, "phase_s.wait", 1, launch.end_s) as wait:
                    if wait_subs:
                        tiled(wait_subs, wait)
                with phase("engine.commit", stats, "phase_s.commit", 1, wait.end_s) as commit:
                    pass
                whole.end_s = commit.end_s
            if phase is not StubPhase:
                engine._open_step = (1e-3,) * 9
            with phase("frontend.deliver", stats, "phase_s.deliver", None, commit.end_s) as after:
                if phase is not StubPhase:
                    engine.close_step(1e-4)
            pump.end_s = after.end_s
    return 1e6 * (time.perf_counter() - t0) / n


def main():
    paddle.seed(3)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()
    engine = ContinuousBatchingEngine(model, max_slots=2, block_size=4, prompt_bucket=8)
    fe = ServingFrontend(engine)
    rng = np.random.default_rng(3)

    def refill():
        for _ in range(2):
            fe.submit(rng.integers(0, cfg.vocab_size, 6).astype(np.int32), max_new_tokens=100)

    refill()
    for _ in range(20):
        fe.pump()
    walls = {"real": [], "stub": []}
    for block in range(40):
        kind = "real" if block % 2 == 0 else "stub"
        module = tracing if kind == "real" else _Tracing(StubPhase)
        engine_module._tracing = frontend_module._tracing = module
        for _ in range(100):
            if not engine.has_work() or len(engine.live_requests()) < 2:
                refill()
            t0 = time.perf_counter()
            fe.pump()
            walls[kind].append(time.perf_counter() - t0)
    engine_module._tracing = frontend_module._tracing = tracing
    # the two real loops in alternating blocks, so that the host's drift lands on both
    blocks = [(sequence(tracing.phase, engine, 4000), sequence(tracing.phase, engine, 4000, subphases=False))
              for _ in range(5)]
    real, bare = (statistics.median(b[i] for b in blocks) for i in (0, 1))
    stub = sequence(StubPhase, engine)
    traced = _sequence_while_profiling(engine)
    paddle.set_flags({"FLAGS_trace_sample_rate": 1.0})
    try:
        full = sequence(tracing.phase, engine, 4000)
    finally:
        paddle.set_flags({"FLAGS_trace_sample_rate": 0.0})
        tracing.GLOBAL_TRACER.clear()
    print(json.dumps({
        "sequence_us": real, "stub_us": stub, "added_us_per_step": real - stub, "sequence_profiling_us": traced,
        "sequence_no_subphases_us": bare, "subphases_added_us_per_step": real - bare, "sequence_full_rate_us": full,
        "pump_us": 1e6 * statistics.median(walls["real"]), "pump_stub_us": 1e6 * statistics.median(walls["stub"]),
        "pumps_each": len(walls["real"]), "thread_time_us": 1e6 * _cost(time.thread_time),
        "perf_counter_us": 1e6 * _cost(time.perf_counter),
    }))


def _sequence_while_profiling(engine, n=2000):
    """The same sequence while a profile is being taken: the annotations are
    entered, not just a flag checked."""
    import jax

    where = os.path.join(ROOT, ".bench_trace", "_phase_cost")
    shutil.rmtree(where, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    try:
        return sequence(tracing.phase, engine, n)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(where, ignore_errors=True)


def _cost(fn, n=200000):
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


if __name__ == "__main__":
    main()
