"""Per-request distributed tracing: span trees over the serving lifecycle.

Aggregate histograms (PR 2) say *that* p99 decode latency moved; they cannot
say where ONE request's latency went — and in a continuous-batching engine
that question is entangled by design: a request's decode time is a share of
batched steps it rode with strangers ("Ragged Paged Attention", PAPERS.md,
serves exactly such mixed batches). This module provides real span trees,
mirroring the reference fork's profiler layer (SURVEY §5.1: chrome-trace
export, ``RecordEvent`` spans):

- **spans** carry ``trace_id`` / ``span_id`` / ``parent_id`` links, so the
  queue → prefill → decode → stream phases of one request nest under one
  root and sum to its end-to-end latency;
- **head sampling** is seeded: the sampling decision and every generated id
  come from one ``random.Random(FLAGS_trace_seed)``, so a given seed +
  request sequence reproduces the same traces (replayable investigations,
  deterministic tests). ``FLAGS_trace_sample_rate`` is the probability; an
  incoming ``traceparent`` header's sampled flag overrides the coin, so a
  caller's sampling decision propagates through this hop;
- **zero cost when off**: ``tracing_enabled()`` is one cached-bool list
  read (the same flag-listener gate as the metrics layer). Rate 0 means no
  rng draw, no id generation, no store append — nothing;
- **bounded store**: completed spans land in a ``deque(maxlen=...)`` ring —
  a tracer left on for days cannot grow host memory; the newest spans win
  and ``dropped`` counts what the ring evicted;
- **export**: JSONL (one span per line — the flight-recorder CLI converts
  it) and chrome-trace ``traceEvents``; ``profiler.Profiler.export`` drains
  :func:`Tracer.drain_chrome_events` into its existing span stream, so
  request spans land on the same perf_counter timeline as ``RecordEvent``
  spans and metrics-snapshot instants. Exports declare the
  ``tracing.export`` fault site: a failing export must never take down the
  path that called it (callers use the ``safe_*`` forms on failure seams).

**Phases** (:class:`phase`) are the one primitive for timing a stretch of
the serving step from INSIDE the program. A phase is always a
``jax.profiler.TraceAnnotation("paddle_tpu.<name>")`` — so whenever a profile
is being taken (``profiler.Profiler``, ``jax.profiler.start_trace``) it lies
on the device trace's clock, in the same ``.xplane.pb`` as the "XLA Ops"
line — and always adds its wall seconds to a cumulative counter handed to it
(``engine.stats["phase_s.<name>"]``); only at ``FLAGS_trace_sample_rate >= 1``
does it also add a span to the ring, parented to the enclosing phase. Phases
nest: one serving pump is thirteen of them (``frontend.pump``, two
``frontend.deliver``, ``engine.decode_step``, its four children, and the five
sub-phases that tile ``engine.launch`` and ``engine.wait``), so at that rate
the default ring of 4096 records holds about 140 steps (230 before the
sub-phases; the ring was not grown).
``profiler.RecordEvent`` makes its annotation through the same
:func:`annotate`, so every host span the program puts into a device trace
comes from here.

The ``traceparent`` header follows the W3C shape
``00-<32 hex trace_id>-<16 hex span_id>-<2 hex flags>`` (flag bit 0x01 =
sampled); malformed headers are ignored and a fresh trace starts.
"""

from __future__ import annotations

import json
import os
import random
import re
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Union

from jax.profiler import TraceAnnotation as _TraceAnnotation

from paddle_tpu.flags import GLOBAL_FLAGS

__all__ = [
    "GLOBAL_TRACER",
    "PHASE_PREFIX",
    "Span",
    "TraceContext",
    "Tracer",
    "format_traceparent",
    "annotate",
    "get_tracer",
    "parse_traceparent",
    "phase",
    "tracing_enabled",
    "tracing_full",
]

# cached FLAGS_trace_sample_rate: one list read on the off path; the listener
# keeps all three cells in lockstep with set_flags / env seeding
_ENABLED = [False]
_FULL = [False]
_RATE = [0.0]


def _refresh_rate(value: Any) -> None:
    rate = float(value)
    _RATE[0] = rate
    _ENABLED[0] = rate > 0.0
    _FULL[0] = rate >= 1.0


GLOBAL_FLAGS.on_change("trace_sample_rate", _refresh_rate)
_refresh_rate(GLOBAL_FLAGS.get("trace_sample_rate"))  # seeds FLAGS_ env var


def tracing_enabled() -> bool:
    """Current ``FLAGS_trace_sample_rate > 0`` without touching the flag
    registry — the one gate every instrumentation site checks first."""
    return _ENABLED[0]


def tracing_full() -> bool:
    """Current ``FLAGS_trace_sample_rate >= 1`` (same cached-cell cost).
    The gate for spans with NO request context to sample against (e.g. the
    collective wrappers): at a partial rate, emitting every such call would
    flood the bounded ring and evict the rare sampled request trees the
    rate was chosen to capture."""
    return _FULL[0]


_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


class TraceContext:
    """Propagatable identity of one span: where new children attach.

    ``span_id`` is THIS context's span (children parent to it);
    ``parent_id`` is the remote parent from an incoming traceparent hop, if
    any. ``sampled`` is the head-sampling decision — unsampled contexts
    still carry ids so the trace id propagates across hops unbroken."""

    __slots__ = ("trace_id", "span_id", "parent_id", "sampled")

    def __init__(
        self,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str] = None,
        sampled: bool = True,
    ) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = bool(sampled)

    def __repr__(self) -> str:
        return (
            f"TraceContext(trace_id={self.trace_id!r}, span_id={self.span_id!r}, "
            f"parent_id={self.parent_id!r}, sampled={self.sampled})"
        )


def parse_traceparent(header: Optional[str]) -> Optional[TraceContext]:
    """Parse a ``traceparent`` header; malformed/absent -> None (the caller
    starts a fresh trace — a bad header must never fail a request)."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None:
        return None
    _, trace_id, span_id, flags = m.groups()
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None  # all-zero ids are invalid per the header spec
    return TraceContext(trace_id, span_id, None, sampled=bool(int(flags, 16) & 1))


def format_traceparent(ctx: TraceContext) -> str:
    return f"00-{ctx.trace_id}-{ctx.span_id}-{'01' if ctx.sampled else '00'}"


class Span:
    """One in-flight span; a context manager (the ONLY sanctioned open form —
    analyzer check OB601 flags a ``tracer.span(...)`` not under ``with``,
    because an unclosed span never reaches the store and leaks silently).
    Unsampled spans go through the same protocol but record nothing."""

    __slots__ = (
        "_tracer", "name", "trace_id", "span_id", "parent_id",
        "attrs", "sampled", "_start_s",
    )

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str],
        attrs: Optional[Dict[str, Any]],
        sampled: bool,
    ) -> None:
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.sampled = sampled
        self._start_s: float = 0.0

    def set_attr(self, key: str, value: Any) -> None:
        if self.sampled:
            self.attrs[key] = value

    def context(self) -> TraceContext:
        """Attachment point for children of this span."""
        return TraceContext(self.trace_id, self.span_id, self.parent_id, self.sampled)

    def __enter__(self) -> "Span":
        self._start_s = time.perf_counter()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        if not self.sampled:
            return
        status = "ok" if exc_type is None else f"error:{exc_type.__name__}"
        self._tracer.add_span(
            self.name,
            trace_id=self.trace_id,
            span_id=self.span_id,
            parent_id=self.parent_id,
            start_s=self._start_s,
            end_s=time.perf_counter(),
            attrs=self.attrs,
            status=status,
        )


class Tracer:
    """Seeded span factory over a bounded in-process store.

    All id generation and sampling coins come from one private
    ``random.Random(seed)`` under the tracer lock: given the same seed and
    the same sequence of :meth:`start_trace` / :meth:`span` calls, the
    emitted ids and sampling decisions are identical."""

    def __init__(
        self, capacity: Optional[int] = None, seed: Optional[int] = None
    ) -> None:
        cap = int(
            GLOBAL_FLAGS.get("trace_buffer_size") if capacity is None else capacity
        )
        if cap < 1:
            raise ValueError(f"trace buffer capacity must be >= 1, got {cap}")
        self._lock = threading.Lock()
        self._store: deque = deque(maxlen=cap)
        self._rng = random.Random(
            int(GLOBAL_FLAGS.get("trace_seed")) if seed is None else int(seed)
        )
        self.dropped = 0  # spans evicted by the bounded ring

    # -- identity / sampling -------------------------------------------------
    def reseed(self, seed: int) -> None:
        with self._lock:
            self._rng = random.Random(int(seed))

    def _gen_id(self, nbytes: int) -> str:
        return f"{self._rng.getrandbits(nbytes * 8):0{nbytes * 2}x}"

    def start_trace(
        self,
        traceparent: Optional[str] = None,
        sample_rate: Optional[float] = None,
    ) -> TraceContext:
        """Head-sampling decision for one request; returns the request's
        ROOT context (fresh ``span_id``; record the root span against it).
        An incoming traceparent pins the trace id AND the sampling decision
        (the upstream hop already flipped the coin); otherwise one seeded
        coin against the rate decides."""
        parent = parse_traceparent(traceparent)
        with self._lock:
            if parent is not None:
                return TraceContext(
                    parent.trace_id, self._gen_id(8), parent.span_id, parent.sampled
                )
            rate = _RATE[0] if sample_rate is None else float(sample_rate)
            sampled = rate > 0.0 and self._rng.random() < rate
            return TraceContext(self._gen_id(16), self._gen_id(8), None, sampled)

    def new_context(self, parent: Optional[TraceContext] = None) -> TraceContext:
        """Fresh ids for a span the caller will record itself with
        :meth:`add_span`: a child of ``parent`` (same trace), or the root of
        a new trace."""
        with self._lock:
            if parent is None:
                return TraceContext(self._gen_id(16), self._gen_id(8), None, True)
            return TraceContext(
                parent.trace_id, self._gen_id(8), parent.span_id, parent.sampled
            )

    # -- recording -----------------------------------------------------------
    def span(
        self,
        name: str,
        parent: Optional[Union[TraceContext, Span]] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> Span:
        """Open one live span (use ONLY as ``with tracer.span(...) as sp:`` —
        analyzer check OB601). ``parent=None`` starts a fresh single-span
        trace (engine batch steps, collectives); an unsampled parent yields
        a no-op span."""
        if isinstance(parent, Span):
            parent = parent.context()
        if parent is None:
            with self._lock:
                trace_id, span_id = self._gen_id(16), self._gen_id(8)
            return Span(self, name, trace_id, span_id, None, attrs, True)
        with self._lock:
            span_id = self._gen_id(8)
        return Span(
            self, name, parent.trace_id, span_id, parent.span_id, attrs,
            parent.sampled,
        )

    def add_span(
        self,
        name: str,
        trace_id: Optional[str] = None,
        span_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        start_s: float = 0.0,
        end_s: float = 0.0,
        attrs: Optional[Dict[str, Any]] = None,
        status: str = "ok",
    ) -> str:
        """Record one completed span from timestamps the caller already holds
        (how the serving frontend emits a request's phase spans at terminal
        time — no live span object rides the hot path). Returns the span id."""
        with self._lock:
            if trace_id is None:
                trace_id = self._gen_id(16)
            if span_id is None:
                span_id = self._gen_id(8)
            if len(self._store) == self._store.maxlen:
                self.dropped += 1
            self._store.append(
                {
                    "kind": "span",
                    "name": name,
                    "trace_id": trace_id,
                    "span_id": span_id,
                    "parent_id": parent_id,
                    "ts_us": start_s * 1e6,
                    "dur_us": max(0.0, (end_s - start_s) * 1e6),
                    "status": status,
                    "attrs": dict(attrs) if attrs else {},
                }
            )
        return span_id

    def add_event(
        self,
        name: str,
        ctx: Optional[TraceContext] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Record one instant event (chrome ``ph:"i"``) — compile events,
        stream-out chunk marks. Unsampled context -> no-op."""
        if ctx is not None and not ctx.sampled:
            return
        with self._lock:
            if len(self._store) == self._store.maxlen:
                self.dropped += 1
            self._store.append(
                {
                    "kind": "event",
                    "name": name,
                    "trace_id": ctx.trace_id if ctx is not None else None,
                    "parent_id": ctx.span_id if ctx is not None else None,
                    "ts_us": time.perf_counter() * 1e6,
                    "attrs": dict(attrs) if attrs else {},
                }
            )

    # -- read / export -------------------------------------------------------
    def records(self) -> List[Dict[str, Any]]:
        """Snapshot of the bounded store (spans + instant events), oldest
        first; does not drain."""
        with self._lock:
            return [dict(r) for r in self._store]

    def spans(self, trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
        return [
            r
            for r in self.records()
            if r["kind"] == "span"
            and (trace_id is None or r["trace_id"] == trace_id)
        ]

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            out, self._store = list(self._store), deque(maxlen=self._store.maxlen)
        return out

    def clear(self) -> None:
        with self._lock:
            self._store.clear()
            self.dropped = 0

    @staticmethod
    def _to_chrome(rec: Dict[str, Any]) -> Dict[str, Any]:
        args = dict(rec.get("attrs") or {})
        for k in ("trace_id", "span_id", "parent_id", "status"):
            if rec.get(k) is not None:
                args[k] = rec[k]
        ev: Dict[str, Any] = {
            "name": rec["name"],
            "ts": rec["ts_us"],
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": args,
        }
        if rec["kind"] == "event":
            ev["ph"], ev["s"] = "i", "t"
        else:
            ev["ph"], ev["dur"] = "X", rec["dur_us"]
        return ev

    def drain_chrome_events(self) -> List[Dict[str, Any]]:
        """Drain the store as chrome traceEvents — what
        ``profiler.Profiler.export`` merges into its span stream."""
        return [self._to_chrome(r) for r in self.drain()]

    def export_jsonl(self, path: str) -> int:
        """Append every stored record to ``path``, one JSON object per line
        (the dump CLI converts this to a chrome trace); returns the record
        count. Does not drain. Declares the ``tracing.export`` fault site."""
        from paddle_tpu.testing.faults import fault_point  # lazy: import cycle

        fault_point("tracing.export")
        records = self.records()
        with open(path, "a") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
        return len(records)

    def export_chrome(self, path: str) -> int:
        """Write the store as a chrome trace JSON (non-draining)."""
        from paddle_tpu.testing.faults import fault_point  # lazy: import cycle

        fault_point("tracing.export")
        records = self.records()
        with open(path, "w") as f:
            json.dump({"traceEvents": [self._to_chrome(r) for r in records]}, f)
        return len(records)

    def safe_export_jsonl(self, path: str) -> Optional[int]:
        """Export that never raises — the form failure seams (pump death,
        engine failure) use: a broken disk or an injected ``tracing.export``
        fault must not take down the path being post-mortemed."""
        try:
            return self.export_jsonl(path)
        except Exception:  # export is best-effort by contract on failure seams
            return None


GLOBAL_TRACER = Tracer()


def get_tracer() -> Tracer:
    return GLOBAL_TRACER


def _reseed_global(value: Any) -> None:
    GLOBAL_TRACER.reseed(int(value))


GLOBAL_FLAGS.on_change("trace_seed", _reseed_global)


# -- phases: the program's own spans on the device trace's clock -------------
PHASE_PREFIX = "paddle_tpu."
_phase_local = threading.local()  # .stack: contexts of the open recorded phases


def annotate(name: str) -> Any:
    """An un-entered ``jax.profiler.TraceAnnotation``: the one place the
    program makes one. With a profile running, the span lands on the host
    plane of the profile's ``.xplane.pb``, on the same clock as the device's
    "XLA Ops" line."""
    return _TraceAnnotation(name)


class phase:
    """One named stretch of host work, as ``with phase(name, sink, key):``.

    Always: a ``TraceAnnotation("paddle_tpu.<name>")`` (entered whenever a
    profile is being taken; otherwise the check of one flag) and two
    ``perf_counter`` instants (``start_s``/``end_s``, kept on the object for
    whoever else needs them: devprof, the stall accounting), whose difference
    is added to ``sink[key]``. ``start_s=`` hands over the previous phase's
    ``end_s`` so that consecutive phases tile with nothing between them.
    Only at ``FLAGS_trace_sample_rate >= 1``: a span in ``GLOBAL_TRACER``
    whose ``parent_id`` is the enclosing phase's span (per thread), with
    ``step`` and ``attrs`` as attributes. Before exit a caller may set
    ``record`` (store a parentless span at a partial rate) and ``end_s`` (a
    parent that ends where its last child ended reads no clock of its own)."""

    __slots__ = (
        "name", "sink", "key", "step", "attrs", "record", "start_s", "end_s",
        "_ann", "_ctx",
    )

    def __init__(
        self,
        name: str,
        sink: Optional[Dict[str, Any]] = None,
        key: Optional[str] = None,
        step: Optional[int] = None,
        start_s: Optional[float] = None,
    ) -> None:
        self.name = name
        self.sink = sink
        self.key = key
        self.step = step
        self.attrs: Optional[Dict[str, Any]] = None
        self.record = False
        self.start_s = start_s
        self.end_s: Optional[float] = None
        self._ann: Any = None
        self._ctx: Optional[TraceContext] = None

    def __enter__(self) -> "phase":
        if _TraceAnnotation.is_enabled():  # a profile is being taken: one flag
            self._ann = annotate(PHASE_PREFIX + self.name)
            self._ann.__enter__()
        if _FULL[0]:
            stack = getattr(_phase_local, "stack", None)
            if stack is None:
                stack = _phase_local.stack = []
            self._ctx = GLOBAL_TRACER.new_context(stack[-1] if stack else None)
            stack.append(self._ctx)
            self.record = True
        if self.start_s is None:
            self.start_s = time.perf_counter()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        end = self.end_s  # preset by a parent that takes its children's instants
        if end is None:
            self.end_s = end = time.perf_counter()
        if self.sink is not None:
            self.sink[self.key] += end - self.start_s
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        ctx = self._ctx
        if ctx is not None:
            _phase_local.stack.pop()
        if self.record:
            attrs = dict(self.attrs) if self.attrs else {}
            if self.step is not None:
                attrs["step"] = self.step
            GLOBAL_TRACER.add_span(
                self.name,
                trace_id=ctx.trace_id if ctx is not None else None,
                span_id=ctx.span_id if ctx is not None else None,
                parent_id=ctx.parent_id if ctx is not None else None,
                start_s=self.start_s,
                end_s=end,
                attrs=attrs,
                status="ok" if exc_type is None else f"error:{exc_type.__name__}",
            )
