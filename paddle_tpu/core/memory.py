"""Device memory observability.

Counterpart of the reference's allocator stat registry
(``paddle/phi/core/memory/stats.h:126-133`` ``DeviceMemoryStat*``
peak/current accounting, ``FLAGS_log_memory_stats``) and the Python surface
``paddle.device.cuda.max_memory_allocated`` /
``memory_allocated``/``memory_reserved``
(``python/paddle/device/cuda/__init__.py``).

On TPU the numbers come straight from PJRT's per-device allocator
(``jax.Device.memory_stats()``: ``bytes_in_use``, ``peak_bytes_in_use``,
``bytes_limit`` …). Backends without allocator stats (the CPU test backend)
fall back to summing live ``jax.Array`` buffers on the device, with the peak
tracked at query points by this module. ``reset_max_memory_allocated`` resets
the module-side peak; the PJRT peak cannot be lowered from user code, so
after a reset the reported max is the high-water seen at subsequent queries.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

import jax

__all__ = [
    "memory_stats",
    "memory_allocated",
    "max_memory_allocated",
    "memory_reserved",
    "max_memory_reserved",
    "reset_max_memory_allocated",
    "compiled_memory_stats",
]

_lock = threading.Lock()
_peak_since_reset: Dict[int, int] = {}  # device id -> tracked high-water
_pjrt_peak_baseline: Dict[int, int] = {}  # subtracted after reset


def _resolve(device: Any = None) -> jax.Device:
    from paddle_tpu.core.device import Place, current_place

    if device is None:
        return current_place().jax_device()
    if isinstance(device, jax.Device):
        return device
    if isinstance(device, Place):
        return device.jax_device()
    if isinstance(device, int):
        return jax.devices()[device]
    from paddle_tpu.core.device import _parse

    return _parse(device).jax_device()


def _live_bytes(dev: jax.Device) -> int:
    total = 0
    for arr in jax.live_arrays():
        try:
            for shard in arr.addressable_shards:
                if shard.device == dev:
                    total += shard.data.nbytes
        except Exception:  # deleted/donated buffers
            continue
    return total


def memory_stats(device: Any = None) -> Dict[str, int]:
    """Raw allocator stats for one device. PJRT-backed where available,
    else ``{"bytes_in_use": <live array bytes>}``."""
    dev = _resolve(device)
    stats = None
    if hasattr(dev, "memory_stats"):
        stats = dev.memory_stats()
    if not stats:
        stats = {"bytes_in_use": _live_bytes(dev)}
    return dict(stats)


def memory_allocated(device: Any = None) -> int:
    """Bytes currently allocated on the device
    (``paddle.device.cuda.memory_allocated`` analog)."""
    dev = _resolve(device)
    current = int(memory_stats(dev).get("bytes_in_use", 0))
    with _lock:
        key = id(dev)
        _peak_since_reset[key] = max(_peak_since_reset.get(key, 0), current)
    return current


def max_memory_allocated(device: Any = None) -> int:
    """Peak bytes allocated (``max_memory_allocated`` /
    ``DeviceMemoryStatPeakValue`` analog, stats.h:126)."""
    dev = _resolve(device)
    key = id(dev)
    stats = memory_stats(dev)
    current = int(stats.get("bytes_in_use", 0))
    pjrt_peak = int(stats.get("peak_bytes_in_use", 0)) - _pjrt_peak_baseline.get(key, 0)
    with _lock:
        tracked = max(_peak_since_reset.get(key, 0), current, pjrt_peak)
        _peak_since_reset[key] = tracked
    return tracked


def memory_reserved(device: Any = None) -> int:
    """Bytes reserved by the allocator pool (limit-aware backends)."""
    stats = memory_stats(device)
    return int(stats.get("bytes_reserved", stats.get("pool_bytes", stats.get("bytes_in_use", 0))))


def max_memory_reserved(device: Any = None) -> int:
    stats = memory_stats(device)
    return int(stats.get("peak_bytes_reserved", stats.get("peak_bytes_in_use", 0)) or max_memory_allocated(device))


def reset_max_memory_allocated(device: Any = None) -> None:
    """Restart peak tracking (``paddle.device.cuda.reset_max_memory_allocated``)."""
    dev = _resolve(device)
    key = id(dev)
    stats = memory_stats(dev)
    with _lock:
        _peak_since_reset[key] = int(stats.get("bytes_in_use", 0))
        _pjrt_peak_baseline[key] = int(stats.get("peak_bytes_in_use", 0))


def compiled_memory_stats(compiled: Any) -> Dict[str, int]:
    """Per-program memory footprint of a compiled XLA executable —
    ``jit(f).lower(...).compile().memory_analysis()`` distilled. The TPU
    analog of the reference's executor memory accounting
    (``executor_statistics.cc``): what HBM one step of this program needs."""
    ma = compiled.memory_analysis() if hasattr(compiled, "memory_analysis") else compiled
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "peak_memory_in_bytes",
        "generated_code_size_in_bytes",
    ):
        out[k] = int(getattr(ma, k, 0))
    return out
