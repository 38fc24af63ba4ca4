"""Trace-time fact: will the program being traced run partitioned over devices?

A ``pallas_call`` has no GSPMD partitioning rule: Mosaic refuses it at
lowering, inside the captured step, where nothing can catch it. So the layer
that owns a placement states it here, once, for the traces it starts, and the
kernel dispatch (``kernels/select.py``) reads it:

- ``jit.to_static`` marks a trace whose state or inputs span devices with
  :data:`LAYOUT_UNKNOWN`: how each intermediate is split is the compiler's
  choice, so no site can wrap its kernel for it.
- The serving engine's tensor-parallel shard group (``distributed/tp.py``)
  marks its traces with its ``Mesh``: the Megatron layout is known (hidden
  states replicated, heads split), so sites can ``shard_map`` their kernels.

Thread-local, not a contextvar: the serving pump drives each engine from its
own thread, and the mark must be visible exactly to the trace on that thread.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Iterator, Optional

from jax.sharding import Mesh, SingleDeviceSharding

__all__ = [
    "LAYOUT_UNKNOWN", "partitioned_trace", "shard_group_mesh", "spans_devices",
    "trace_partition",
]

LAYOUT_UNKNOWN = "layout-unknown"


class _State(threading.local):
    partition: Any = None


_STATE = _State()


@contextlib.contextmanager
def partitioned_trace(partition: Any) -> Iterator[None]:
    """Mark the traces started under this context: ``None`` (one device), a
    ``jax.sharding.Mesh`` (a shard group of known layout) or
    :data:`LAYOUT_UNKNOWN`. Re-entrant; restores the previous mark."""
    prev = _STATE.partition
    _STATE.partition = partition
    try:
        yield
    finally:
        _STATE.partition = prev


def trace_partition() -> Any:
    """The innermost :func:`partitioned_trace` mark on this thread."""
    return _STATE.partition


def shard_group_mesh() -> Optional[Mesh]:
    """The mark when it is a shard group's mesh (known layout), else None."""
    mesh = _STATE.partition
    return mesh if isinstance(mesh, Mesh) else None


def spans_devices(array: Any) -> bool:
    """Whether ``array`` is placed on more than one device."""
    sharding = getattr(array, "sharding", None)
    if sharding is None or isinstance(sharding, SingleDeviceSharding):
        return False
    return sharding.num_devices > 1
