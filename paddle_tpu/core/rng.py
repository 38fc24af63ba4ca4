"""RNG state management.

Counterpart of the reference's ``phi::Generator`` (``paddle/phi/core/generator.h``)
built on JAX's splittable PRNG: a process-global Generator owns a key and hands
out fresh subkeys per random op (the stateful-seed ↔ functional-key bridge).
``RNGStatesTracker`` (per-name states, used for tensor-parallel dropout seed
control) mirrors ``fleet/layers/mpu/random.py``.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Iterator, Optional

import jax
import numpy as np


class Generator:
    """Stateful wrapper over a splittable jax PRNG key."""

    def __init__(self, seed_: int = 0) -> None:
        self._lock = threading.Lock()
        self._seed = int(seed_)
        # Key creation is deferred: PRNGKey() is a device computation, and a
        # module-scope Generator would otherwise initialize the jax backend
        # (and take the chip) at `import paddle_tpu` time, even in processes
        # that never run a computation.
        self._key: Optional[jax.Array] = None

    def _ensure_key(self) -> jax.Array:
        if self._key is None:
            self._key = jax.random.PRNGKey(self._seed)
        return self._key

    def manual_seed(self, seed_: int) -> "Generator":
        with self._lock:
            self._seed = int(seed_)
            self._key = None
        return self

    def next_key(self) -> jax.Array:
        with self._lock:
            self._key, sub = jax.random.split(self._ensure_key())
            return sub

    def get_state(self) -> np.ndarray:
        with self._lock:
            return np.asarray(jax.random.key_data(self._ensure_key()))

    def set_state(self, state: Any) -> None:
        with self._lock:
            self._key = jax.random.wrap_key_data(
                jax.numpy.asarray(state, dtype=jax.numpy.uint32)
            )

    @property
    def initial_seed(self) -> int:
        return self._seed


_default_generator = Generator(0)


def default_generator() -> Generator:
    return _default_generator


def seed(seed_: int) -> Generator:
    """Set the global random seed (``paddle.seed`` parity)."""
    return _default_generator.manual_seed(seed_)


def next_key() -> jax.Array:
    return _default_generator.next_key()


def get_rng_state() -> np.ndarray:
    return _default_generator.get_state()


def set_rng_state(state: Any) -> None:
    _default_generator.set_state(state)


class RNGStatesTracker:
    """Named RNG states for parallel regions (TP-group dropout determinism).

    Reference: ``python/paddle/distributed/fleet/layers/mpu/random.py``
    ``RNGStatesTracker`` — e.g. 'global_seed' vs 'local_seed' so dropout masks
    are replicated across TP ranks where required and distinct where not.
    """

    def __init__(self) -> None:
        self._states: Dict[str, Generator] = {}

    def add(self, name: str, seed_: int) -> None:
        if name in self._states:
            raise ValueError(f"rng state '{name}' already exists")
        self._states[name] = Generator(seed_)

    def reset(self) -> None:
        self._states.clear()

    def get_states_tracker(self) -> Dict[str, np.ndarray]:
        return {k: g.get_state() for k, g in self._states.items()}

    def set_states_tracker(self, states: Dict[str, Any]) -> None:
        for k, s in states.items():
            self._states.setdefault(k, Generator(0)).set_state(s)

    @contextlib.contextmanager
    def rng_state(self, name: str = "global_seed") -> Iterator[None]:
        if name not in self._states:
            raise KeyError(f"unknown rng state '{name}'; add() it first")
        global _default_generator
        prev = _default_generator
        _default_generator = self._states[name]
        try:
            yield
        finally:
            _default_generator = prev


_global_tracker = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _global_tracker
