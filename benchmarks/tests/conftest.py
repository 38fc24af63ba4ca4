import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import json
import re

import pytest


@pytest.fixture
def on_cpu(monkeypatch):
    """The benchmark's own files shrunk to a size the CPU holds, and the
    harness's look for a TPU skipped: by monkeypatch only, run.py has no option
    for it. The CPU backend's executor thread stands in for the TPU plane's
    "XLA Ops" line."""
    import jax

    import run as harness
    from lib import device, xplane
    from tests import tiny

    real = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda *parts: tiny.shrink(parts, real(*parts)))
    monkeypatch.setattr(device, "require_tpu", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "peaks", lambda kind: {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr("paddle_tpu.core.compile_cache.enable_compile_cache", lambda: "off")
    monkeypatch.setattr(xplane, "DEVICE_PLANE", re.compile(r"^/host:CPU()$"))
    monkeypatch.setattr(xplane, "OP_LINE", re.compile(r"XLAPjRtCpuClient"))


@pytest.fixture
def run_cell(on_cpu, capsys):
    """Run one cell through ``run.main``; returns (result line, notes)."""
    import run as harness

    def go(cell, seed=7, seconds=1.0, trace=0):
        assert harness.main(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]) == 0
        lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.strip()]
        return lines[-1], lines[:-1]

    return go
