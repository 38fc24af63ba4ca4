"""Profiler (reference ``python/paddle/profiler``, SURVEY §5.1).

TPU-native: host spans via ``jax.profiler.TraceAnnotation`` (XPlane/TraceMe —
the RecordEvent analog) + device traces via ``jax.profiler`` sessions, exported
to TensorBoard/perfetto; plus a pure-python host-event recorder that writes
chrome://tracing JSON like the reference's ``chrometracing_logger.cc``.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from enum import Enum
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple, Union

__all__ = [
    "Profiler",
    "ProfilerState",
    "ProfilerTarget",
    "RecordEvent",
    "make_scheduler",
    "export_chrome_tracing",
    "load_profiler_result",
    "benchmark",
]

from paddle_tpu.profiler.timer import benchmark  # noqa: E402,F401


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class _HostEventRecorder:
    """Reference ``host_event_recorder.h`` analog. Spans go to the native C++
    recorder (``cpp/host_tracer.cpp``) when built — no allocation per span on
    the hot path — with this python buffer as fallback."""

    def __init__(self) -> None:
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._enabled = False
        self._native = None
        try:
            from paddle_tpu.core.native import load_native

            # build=False: never compile C++ during `import paddle_tpu`
            self._native = load_native(build=False)
        except Exception:  # native extension optional: pure-python recorder suffices
            self._native = None

    @property
    def enabled(self) -> bool:
        return self._enabled

    @enabled.setter
    def enabled(self, on: bool) -> None:
        self._enabled = on
        if self._native is not None:
            self._native.het_enable(1 if on else 0)

    def add(self, name: str, start_us: float, end_us: float, tid: int) -> None:
        if self._native is not None:
            self._native.het_record(name.encode(), start_us, end_us - start_us, tid)
            return
        with self._lock:
            self._events.append(
                {"name": name, "ph": "X", "ts": start_us, "dur": end_us - start_us,
                 "pid": os.getpid(), "tid": tid}
            )

    def drain(self) -> List[Dict[str, Any]]:
        events: List[Dict[str, Any]] = []
        if self._native is not None:
            cap = 1 << 20
            while True:
                import ctypes

                buf = ctypes.create_string_buffer(cap)
                n = self._native.het_drain_json(buf, cap, os.getpid())
                if n < 0:
                    cap = -n
                    continue
                events.extend(json.loads(buf.value.decode()))
                break
        with self._lock:
            events_py, self._events = self._events, []
        return events + events_py


_recorder = _HostEventRecorder()


class RecordEvent:
    """RAII host span (reference ``paddle/phi/api/profiler/event_tracing.h``
    RecordEvent). Its annotation in XLA device traces is made by
    ``observability.tracing.annotate``, the same call the program's own
    phases use; what stays here is the user-facing begin/end API and the
    chrome-export recorder."""

    def __init__(self, name: str, event_type: Any = None) -> None:
        self.name = name
        self._start: Optional[float] = None
        self._jax_ann = None

    def begin(self) -> None:
        self._start = time.perf_counter() * 1e6
        try:
            from paddle_tpu.observability.tracing import annotate

            self._jax_ann = annotate(self.name)
            self._jax_ann.__enter__()
        except Exception:  # device annotation is best-effort; host span still recorded
            self._jax_ann = None

    def end(self) -> None:
        if self._jax_ann is not None:
            self._jax_ann.__exit__(None, None, None)
            self._jax_ann = None
        if self._start is not None and _recorder.enabled:
            _recorder.add(self.name, self._start, time.perf_counter() * 1e6, threading.get_ident())
        self._start = None

    def __enter__(self) -> "RecordEvent":
        self.begin()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end()


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0, skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Window scheduler (reference ``profiler.py`` make_scheduler)."""

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        period = closed + ready + record
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None) -> Callable:
    def handler(prof: "Profiler") -> None:
        os.makedirs(dir_name, exist_ok=True)
        fname = os.path.join(
            dir_name, f"{worker_name or 'paddle_tpu'}_{int(time.time())}.pt.trace.json"
        )
        prof.export(fname, format="json")

    return handler


class Profiler:
    """Reference ``python/paddle/profiler/profiler.py:358`` Profiler parity:
    state machine + scheduler windows + chrome export; device-side capture via
    jax.profiler when a trace dir is configured."""

    def __init__(
        self,
        targets: Optional[Iterable[ProfilerTarget]] = None,
        scheduler: Any = None,
        on_trace_ready: Optional[Callable] = None,
        record_shapes: bool = False,
        profile_memory: bool = False,
        timer_only: bool = False,
        emit_nvtx: bool = False,
        custom_device_types: Any = None,
        with_flops: bool = False,
    ) -> None:
        if isinstance(scheduler, tuple):
            start, end = scheduler
            self._schedule = make_scheduler(closed=start, ready=0, record=end - start, repeat=1)
        elif callable(scheduler):
            self._schedule = scheduler
        else:
            self._schedule = lambda step: ProfilerState.RECORD
        self._on_trace_ready = on_trace_ready
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._events: List[Dict[str, Any]] = []
        self._timer_only = timer_only
        self._profile_memory = profile_memory
        self._jax_dir: Optional[str] = None

    def start(self) -> None:
        self._state = self._schedule(self._step)
        if self._state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            _recorder.enabled = True
        # HBM accounting across the profiled region (reference
        # DeviceMemoryStat peak tracking, stats.h:126). Opt-in via
        # profile_memory: the reset restarts the PROCESS-WIDE interval
        # tracker, which must not silently clobber a user's own measurement.
        if self._profile_memory:
            try:
                from paddle_tpu.core.memory import (
                    memory_allocated,
                    reset_max_memory_allocated,
                )

                reset_max_memory_allocated()
                self.memory_at_start = memory_allocated()
            except Exception:  # allocator stats unavailable on this backend
                self.memory_at_start = 0

    def stop(self) -> None:
        _recorder.enabled = False
        self._events.extend(_recorder.drain())
        try:
            from paddle_tpu.core.memory import max_memory_allocated, memory_allocated

            # peak since the profiler's reset (profile_memory=True) or the
            # process-wide peak (still useful, never destructive)
            self.peak_memory_allocated = max_memory_allocated()
            self.memory_at_stop = memory_allocated()
        except Exception:  # allocator stats unavailable on this backend
            self.peak_memory_allocated = 0
            self.memory_at_stop = 0
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        self._state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None) -> None:
        self._events.extend(_recorder.drain())
        self._step += 1
        prev = self._state
        self._state = self._schedule(self._step)
        if self._state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            _recorder.enabled = True
        elif prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            _recorder.enabled = False
            if self._state == ProfilerState.CLOSED and self._on_trace_ready is not None:
                self._on_trace_ready(self)

    def __enter__(self) -> "Profiler":
        self.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.stop()

    def export(self, path: str, format: str = "json") -> None:  # noqa: A002
        events = self._events + _recorder.drain()
        try:
            # metrics snapshots taken via observability.write_snapshot_jsonl
            # appear as instant events on the same (perf_counter) timeline,
            # linking each snapshot file/seq into the span stream
            from paddle_tpu.observability.exporters import drain_trace_events

            events = events + drain_trace_events()
        except ImportError:  # exporters unavailable mid-teardown: spans still export
            pass
        try:
            # request/engine spans from the distributed tracer land on the
            # same perf_counter timeline as RecordEvent spans, so one chrome
            # trace shows a request's phases against the recorded host spans
            from paddle_tpu.observability.tracing import GLOBAL_TRACER

            events = events + GLOBAL_TRACER.drain_chrome_events()
        except ImportError:  # tracing unavailable mid-teardown: spans still export
            pass
        try:
            # devprof counter tracks (per-category device ms + segment split
            # per sampled step) ride the same perf_counter timeline as "C"
            # events, so the attribution overlays the span stream
            from paddle_tpu.observability import devprof as _devprof

            events = events + _devprof.drain_chrome_events()
        except ImportError:  # devprof unavailable mid-teardown
            pass
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)

    def summary(self, sorted_by: Any = None, op_detail: bool = True, thread_sep: bool = False, time_unit: str = "ms") -> str:
        events = self._events
        agg: Dict[str, Tuple[int, float]] = {}
        for e in events:
            cnt, dur = agg.get(e["name"], (0, 0.0))
            agg[e["name"]] = (cnt + 1, dur + e["dur"])
        lines = [f"{'Name':<50} {'Calls':>8} {'Total(ms)':>12}"]
        for name, (cnt, dur) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<50} {cnt:>8} {dur / 1000.0:>12.3f}")
        return "\n".join(lines)


def load_profiler_result(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)
