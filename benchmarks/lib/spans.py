"""The benchmark's own host spans: a name, a start and an end on the host's
clock, kept in memory; each is also a ``jax.profiler.TraceAnnotation`` named
``bench.<name>`` so that a traced run has them on the device trace's clock."""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, Iterator, List, Tuple


class Spans:
    def __init__(self) -> None:
        self.events: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.events.append((name, t0, time.perf_counter()))

    def durations(self, since: float = 0.0) -> Dict[str, List[float]]:
        out: Dict[str, List[float]] = {}
        for name, a, b in self.events:
            if a >= since:
                out.setdefault(name, []).append(b - a)
        return out


class GcPauses:
    """How long the collector held the interpreter, per collection, from
    ``gc.callbacks``: the drivers leave the collector on (a deployment pays
    its pauses) and print the pauses of the window beside the metrics."""

    def __init__(self) -> None:
        self.pauses: List[Tuple[float, float, int]] = []  # (start, seconds, generation), host clock
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((self._t0, time.perf_counter() - self._t0, info["generation"]))

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def summary(self, since: float, until: float) -> Dict[str, float]:
        inside = [p for p in self.pauses if since <= p[0] <= until]
        return {"gc_collections": len(inside), "gc_full_collections": sum(1 for p in inside if p[2] == 2),
                "gc_pause_total_ms": 1e3 * sum(p[1] for p in inside),
                "gc_pause_max_ms": 1e3 * max((p[1] for p in inside), default=0.0)}
