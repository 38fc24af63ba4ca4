"""Places / devices.

Counterpart of the reference's ``phi::Place`` + device management
(``paddle/phi/backends/device_manager.h:134``). On TPU the PJRT client owns
devices; a Place is a thin handle onto a ``jax.Device``.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Union

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0) -> None:
        self.device_id = int(device_id)

    def __repr__(self) -> str:
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other: object) -> bool:
        return (
            isinstance(other, Place)
            and other.device_type == self.device_type
            and other.device_id == self.device_id
        )

    def __hash__(self) -> int:
        return hash((self.device_type, self.device_id))

    def jax_device(self) -> jax.Device:
        devices = [d for d in jax.devices() if d.platform == self.device_type]
        if not devices:
            # never hand back a device of another platform: a program placed
            # on "tpu" must not run on the CPU without anyone noticing
            raise RuntimeError(
                f"{self!r}: no {self.device_type!r} device among jax.devices() "
                f"(default backend {jax.default_backend()!r})"
            )
        return devices[self.device_id % len(devices)]


class TPUPlace(Place):
    device_type = "tpu"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self) -> None:
        super().__init__(0)


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0) -> None:
        super().__init__(device_id)
        self.device_type = device_type


_state = threading.local()


def _default_device_str() -> str:
    return "tpu:0" if jax.default_backend() == "tpu" else "cpu"


def set_device(device: str) -> Place:
    """Set the active device, e.g. ``set_device("tpu:0")``. Mirrors ``paddle.set_device``."""
    place = _parse(device)
    _state.place = place
    return place


def get_device() -> str:
    place = getattr(_state, "place", None)
    if place is None:
        return _default_device_str()
    if isinstance(place, CPUPlace):
        return "cpu"
    return f"{place.device_type}:{place.device_id}"


def current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is not None:
        return place
    return _parse(_default_device_str())


def _parse(device: Union[str, Place]) -> Place:
    if isinstance(device, Place):
        return device
    spec = device.lower()
    if spec == "cpu":
        return CPUPlace()
    kind, _, idx = spec.partition(":")
    device_id = int(idx) if idx else 0
    if kind in ("tpu", "gpu", "xpu"):
        # gpu/xpu names are accepted for script compat and map onto the accelerator.
        return TPUPlace(device_id)
    return CustomPlace(kind, device_id)


class device:  # noqa: N801 - mirrors paddle.device module-as-namespace usage
    set_device = staticmethod(set_device)
    get_device = staticmethod(get_device)

    @staticmethod
    def device_count() -> int:
        return len(jax.devices())

    @staticmethod
    def is_compiled_with_cuda() -> bool:
        return False

    @staticmethod
    def synchronize() -> None:
        """Block until all enqueued work is done (async dispatch barrier)."""
        import jax as _jax

        (_jax.device_put(0) + 0).block_until_ready()

    # memory observability (reference stats.h:126 + paddle.device.cuda.*)
    @staticmethod
    def memory_stats(device_: object = None):
        from paddle_tpu.core.memory import memory_stats as _ms

        return _ms(device_)

    @staticmethod
    def memory_allocated(device_: object = None) -> int:
        from paddle_tpu.core.memory import memory_allocated as _ma

        return _ma(device_)

    @staticmethod
    def max_memory_allocated(device_: object = None) -> int:
        from paddle_tpu.core.memory import max_memory_allocated as _mma

        return _mma(device_)

    @staticmethod
    def memory_reserved(device_: object = None) -> int:
        from paddle_tpu.core.memory import memory_reserved as _mr

        return _mr(device_)

    @staticmethod
    def max_memory_reserved(device_: object = None) -> int:
        from paddle_tpu.core.memory import max_memory_reserved as _mmr

        return _mmr(device_)

    @staticmethod
    def reset_max_memory_allocated(device_: object = None) -> None:
        from paddle_tpu.core.memory import reset_max_memory_allocated as _r

        _r(device_)

    class cuda:  # noqa: N801 - paddle.device.cuda.* script compatibility
        """Accelerator-memory API under the reference's ``cuda`` name; maps
        onto the PJRT device (TPU here) so existing scripts keep working.
        Methods are aliased from ``device`` below — one implementation."""


# paddle.device.cuda.* == paddle.device.* (single set of bindings)
for _name in (
    "memory_stats",
    "memory_allocated",
    "max_memory_allocated",
    "memory_reserved",
    "max_memory_reserved",
    "reset_max_memory_allocated",
    "synchronize",
):
    setattr(device.cuda, _name, getattr(device, _name))
del _name
