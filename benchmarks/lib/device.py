"""What the benchmark asks of the machine: a TPU with enough chips, its peaks
and its memory marks. No fallback to any other platform."""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


class NoAccelerator(RuntimeError):
    pass


def require_tpu(chips: int) -> List[Any]:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"the benchmark needs a TPU; jax found {devices[0].platform!r} ({devices[0].device_kind})"
        )
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chip(s); jax sees {len(devices)}")
    return devices[:chips]


def describe(devices: List[Any]) -> Dict[str, Any]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} in lib/peaks.json")
    return table[device_kind]


def memory_peak_bytes(devices: List[Any]) -> int:
    """The allocator's high-water mark on the fullest chip (one process per
    run, so the mark is this run's own)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
