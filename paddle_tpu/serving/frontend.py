"""SLO-aware serving front end over :class:`ContinuousBatchingEngine`.

The host-side policy layer a heavy-traffic deployment lives on (ROADMAP:
"millions of users"): the engine turns a request mix into fixed-shape device
steps; this layer decides *which* requests get to become device work at all
when there is more demand than capacity — explicitly, observably, and
without ever wedging or OOMing the pool:

- **bounded intake** — at most ``max_queue`` requests wait; past that,
  intake raises :class:`Overloaded` (HTTP 429) instead of growing host
  memory without bound;
- **deadlines / TTLs** — each request can carry a deadline; the engine sheds
  it from the queue before wasting a prefill, or evicts it mid-decode with
  its KV blocks reclaimed (``serving_deadline_miss_total{stage}``);
- **priority classes + weighted per-tenant fairness** — admission order is
  the :class:`WeightedFairPolicy` stride scheduler, not FIFO;
- **load shedding with hysteresis** — an :class:`OverloadController` watches
  the same signals the observability gauges export (intake queue depth,
  KV-pool utilization from ``pool_stats()``, and a sliding-window TTFT p99)
  and latches between NORMAL → DEGRADED → SHEDDING. Start and stop
  thresholds are distinct, so the system does not flap at the boundary;
- **graceful degradation** — DEGRADED clamps best-effort ``max_new_tokens``;
  SHEDDING additionally rejects best-effort intake with a typed
  :class:`Overloaded` carrying a retry-after hint and clamps standard
  traffic. Interactive traffic is only ever refused by the bounded queue.

Reading the signals from engine truth (``pool_stats()``, the frontend's own
queue count and TTFT window) rather than the metric cells keeps shedding
correct when ``FLAGS_enable_metrics`` is off — the gauges export the same
values when metrics are on.

Threading model: ``submit``/``cancel`` are thread-safe (HTTP handler
threads); all engine interaction happens under one lock, and the engine is
only ever driven by :meth:`pump` — call it from your own loop, or
:meth:`start` a daemon pump thread. Token streams are per-request queues;
every blocking wait in this module carries an explicit timeout (analyzer
check RB502 — an un-timed wait is how a shed request wedges a worker).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from queue import Empty, Queue
from typing import Any, Dict, Iterator, List, Optional, Tuple

from paddle_tpu.inference.engine import ContinuousBatchingEngine, InferenceRequest
from paddle_tpu.observability import flight_recorder as _flight
from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.observability.serving import priority_name, serving_metrics
from paddle_tpu.serving.errors import Overloaded
from paddle_tpu.serving.scheduler import DEFAULT_WEIGHTS, WeightedFairPolicy
from paddle_tpu.testing.faults import fault_point

__all__ = [
    "Hysteresis",
    "OverloadController",
    "Priority",
    "ServingConfig",
    "ServingFrontend",
    "ServingRequest",
]


class Priority:
    """Priority classes (lower = more important). Label values in metrics
    use the names (see ``observability.serving.PRIORITY_NAMES``)."""

    INTERACTIVE = 0
    STANDARD = 1
    BEST_EFFORT = 2

    @staticmethod
    def parse(value: Any) -> int:
        """Accept ints or the class names (the HTTP request format)."""
        if isinstance(value, bool):
            raise ValueError(f"bad priority {value!r}")
        if isinstance(value, int):
            return value
        names = {"interactive": 0, "standard": 1, "best_effort": 2}
        key = str(value).strip().lower()
        if key in names:
            return names[key]
        raise ValueError(
            f"bad priority {value!r} (expected interactive/standard/best_effort "
            "or an integer class)"
        )


class Hysteresis:
    """A latched threshold: turns ON when the signal reaches ``high``, and
    only turns OFF again below ``low`` — distinct start/stop points, so a
    signal hovering at the boundary cannot flap the state per step."""

    def __init__(self, high: float, low: float) -> None:
        if low > high:
            raise ValueError(f"hysteresis low ({low}) must be <= high ({high})")
        self.high, self.low = float(high), float(low)
        self.active = False

    def update(self, value: float) -> bool:
        if self.active:
            if value < self.low:
                self.active = False
        elif value >= self.high:
            self.active = True
        return self.active


@dataclass
class ServingConfig:
    """Frontend policy knobs. Thresholds are ``(start, stop)`` pairs feeding
    :class:`Hysteresis` gates; queue thresholds are fractions of
    ``max_queue``, utilization thresholds are fractions of the KV pool, TTFT
    thresholds are seconds over the sliding-window p99 (None disables the
    TTFT signal at that level)."""

    max_queue: int = 64
    # per-request default TTL (seconds from submit); None = no deadline
    default_ttl_s: Optional[float] = None
    # DEGRADED: clamp best-effort budgets to this many new tokens
    degrade_max_new_tokens: int = 16
    # stride weights per priority class (admission share under backlog)
    weights: Dict[int, float] = field(default_factory=lambda: dict(DEFAULT_WEIGHTS))
    degrade_queue_frac: Tuple[float, float] = (0.5, 0.25)
    shed_queue_frac: Tuple[float, float] = (0.875, 0.5)
    degrade_util: Tuple[float, float] = (0.85, 0.7)
    shed_util: Tuple[float, float] = (0.97, 0.85)
    degrade_ttft_p99_s: Optional[Tuple[float, float]] = None
    shed_ttft_p99_s: Optional[Tuple[float, float]] = None
    # base retry-after hint; scaled up with queue pressure
    retry_after_s: float = 0.5
    # distinct tenant values exported as metric labels; past this many the
    # label collapses to "overflow" — an HTTP client minting a fresh tenant
    # per request must not grow the process-global registry without bound
    max_tenant_labels: int = 64
    # sliding-window sizes for the controller's TTFT/step-time signals
    ttft_window: int = 128
    # default wait used by stream()/result() when the caller gives none
    default_wait_s: float = 60.0
    # idle nap between pump iterations when the engine has no work
    idle_sleep_s: float = 0.002


NORMAL, DEGRADED, SHEDDING = 0, 1, 2
_LEVEL_NAMES = {NORMAL: "normal", DEGRADED: "degraded", SHEDDING: "shedding"}


class OverloadController:
    """Maps (queue depth, KV utilization, TTFT p99) to an overload level
    through per-signal hysteresis gates. A level is active while ANY of its
    signals' gates is latched; SHEDDING implies DEGRADED."""

    def __init__(self, cfg: ServingConfig) -> None:
        def gates(queue_t, util_t, ttft_t):
            out = [("queue", Hysteresis(*queue_t)), ("util", Hysteresis(*util_t))]
            if ttft_t is not None:
                out.append(("ttft", Hysteresis(*ttft_t)))
            return out

        self._degrade = gates(cfg.degrade_queue_frac, cfg.degrade_util, cfg.degrade_ttft_p99_s)
        self._shed = gates(cfg.shed_queue_frac, cfg.shed_util, cfg.shed_ttft_p99_s)
        self.level = NORMAL

    def update(self, queue_frac: float, util: float, ttft_p99: float) -> int:
        signals = {"queue": queue_frac, "util": util, "ttft": ttft_p99}
        # update EVERY gate (no short-circuit: each must see the new value)
        degraded = [g.update(signals[name]) for name, g in self._degrade]
        shedding = [g.update(signals[name]) for name, g in self._shed]
        self.level = SHEDDING if any(shedding) else DEGRADED if any(degraded) else NORMAL
        return self.level

    @property
    def level_name(self) -> str:
        return _LEVEL_NAMES[self.level]


_END = None  # token-stream terminal sentinel


class ServingRequest:
    """Frontend handle for one accepted request: a token stream plus the
    final outcome. ``outcome`` is ``"ok"`` for a normal finish ("stop" /
    "length") or the shed reason otherwise (``deadline_queued`` /
    ``deadline_decode`` / ``client_disconnect`` / ``engine_failure`` /
    ``cancelled``)."""

    def __init__(self, inner: InferenceRequest, submit_time: float,
                 requested_max_new: int, default_wait_s: float) -> None:
        self.inner = inner
        self.id = inner.req_id
        self.priority = inner.priority
        self.tenant = inner.tenant
        # distributed-tracing context for this request's span tree; set by
        # submit() when tracing is enabled (None otherwise). Kept even when
        # unsampled so the trace id still propagates downstream.
        self.trace_ctx: Optional[_tracing.TraceContext] = None
        self.submit_time = submit_time
        self.requested_max_new_tokens = int(requested_max_new)
        self.degraded = requested_max_new != inner.max_new_tokens
        self.outcome: Optional[str] = None
        self.finish_time: Optional[float] = None
        self.first_token_time: Optional[float] = None
        self._default_wait_s = float(default_wait_s)
        self._q: Queue = Queue()
        self._done = threading.Event()
        self._n_pushed = 0  # tokens forwarded from inner.generated so far

    @property
    def finished(self) -> bool:
        return self._done.is_set()

    @property
    def traceparent(self) -> Optional[str]:
        """Outgoing ``traceparent`` header value for this request's root
        span (None when tracing was off at submit)."""
        if self.trace_ctx is None:
            return None
        return _tracing.format_traceparent(self.trace_ctx)

    @property
    def met_deadline(self) -> bool:
        """Finished normally, and inside the deadline (vacuously true with
        no deadline) — the per-request SLO bit goodput accounting uses."""
        if self.outcome != "ok":
            return False
        if self.inner.deadline is None:
            return True
        return self.finish_time is not None and self.finish_time <= self.inner.deadline

    def tokens(self) -> List[int]:
        return list(self.inner.generated)

    def stream(self, timeout: Optional[float] = None) -> Iterator[int]:
        """Yield token ids as the pump produces them; returns at end of
        stream (check ``outcome``). ``timeout`` bounds the wait for EACH
        token; a stalled pump raises ``TimeoutError`` rather than blocking a
        worker forever."""
        wait = self._default_wait_s if timeout is None else float(timeout)
        while True:
            try:
                item = self._q.get(timeout=wait)
            except Empty:
                raise TimeoutError(
                    f"request {self.id}: no token within {wait}s (pump stalled?)"
                ) from None
            if item is _END:
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> InferenceRequest:
        """Block until the request reaches a terminal state; returns the
        engine-side request (tokens + finish_reason)."""
        wait = self._default_wait_s if timeout is None else float(timeout)
        if not self._done.wait(timeout=wait):
            raise TimeoutError(f"request {self.id} not finished within {wait}s")
        return self.inner

    # -- pump-side (called under the frontend lock) --------------------------
    def _push_new(self, now: float) -> int:
        fresh = self.inner.generated[self._n_pushed:]
        if fresh and self.first_token_time is None:
            self.first_token_time = now
        for tok in fresh:
            self._q.put(tok)
        self._n_pushed += len(fresh)
        return len(fresh)

    def _finalize(self, outcome: str, now: float) -> None:
        self.outcome = outcome
        self.finish_time = now
        self._done.set()
        self._q.put(_END)


class ServingFrontend:
    """See module docstring. Construct over an existing engine; the frontend
    installs its :class:`WeightedFairPolicy` as the engine's admission
    policy (replacing FIFO)."""

    def __init__(
        self,
        engine: ContinuousBatchingEngine,
        config: Optional[ServingConfig] = None,
    ) -> None:
        self.engine = engine
        self.config = config or ServingConfig()
        if self.config.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self.policy = WeightedFairPolicy(self.config.weights)
        engine.set_admission_policy(self.policy)
        self.controller = OverloadController(self.config)
        self._metrics = serving_metrics()
        self._lock = threading.RLock()
        self._live: Dict[int, ServingRequest] = {}  # id -> handle (not yet terminal)
        self._ttfts: deque = deque(maxlen=int(self.config.ttft_window))
        self._step_times: deque = deque(maxlen=32)
        self._tenant_labels: set = set()  # bounded by max_tenant_labels
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._failed: Optional[str] = None  # set when the engine died for good
        # replica observability scope: unscoped until the cluster layer
        # calls set_replica_scope() at replica construction
        self._flight = _flight.GLOBAL_FLIGHT_RECORDER
        self.replica_name: Optional[str] = None

    # -- intake --------------------------------------------------------------
    def submit(
        self,
        prompt_ids: Any,
        max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None,
        priority: int = Priority.STANDARD,
        tenant: str = "default",
        ttl_s: Optional[float] = None,
        traceparent: Optional[str] = None,
    ) -> ServingRequest:
        """Accept one request. Raises a typed
        :class:`~paddle_tpu.inference.engine.IntakeError` (→ 4xx) on
        malformed input, :class:`Overloaded` (→ 429) when shedding, and
        ``RuntimeError`` if the engine is permanently failed.

        ``traceparent`` (the W3C-style header) continues an upstream trace;
        with tracing enabled and no header, seeded head sampling against
        ``FLAGS_trace_sample_rate`` decides. With the rate at 0 the entire
        tracing surface of this call is ONE cached-bool read."""
        fault_point("serving.intake")
        priority = int(priority)
        now = time.perf_counter()
        trace_ctx = None
        if _tracing.tracing_enabled():
            trace_ctx = _tracing.GLOBAL_TRACER.start_trace(traceparent)
        with self._lock:
            if self._failed is not None:
                raise RuntimeError(
                    f"serving frontend stopped: {self._failed}; build a new engine"
                )
            try:
                self._shed_gate(priority)
            except Overloaded as exc:
                # a sampled request rejected at intake still gets a terminal
                # root span — a trace must never just vanish at the door
                if trace_ctx is not None and trace_ctx.sampled:
                    _tracing.GLOBAL_TRACER.add_span(
                        "request", trace_id=trace_ctx.trace_id,
                        span_id=trace_ctx.span_id, parent_id=trace_ctx.parent_id,
                        start_s=now, end_s=time.perf_counter(),
                        attrs={"outcome": exc.reason,
                               "priority": priority_name(priority),
                               "tenant": tenant},
                        status=f"shed:{exc.reason}",
                    )
                raise
            effective_max_new = self._degrade_gate(priority, int(max_new_tokens))
            ttl = self.config.default_ttl_s if ttl_s is None else ttl_s
            deadline = None if ttl is None else now + float(ttl)
            inner = self.engine.make_request(
                prompt_ids, effective_max_new, eos_token_id,
                priority=priority, tenant=tenant, deadline=deadline,
            )
            handle = ServingRequest(
                inner, now, int(max_new_tokens), self.config.default_wait_s
            )
            handle.trace_ctx = trace_ctx
            if trace_ctx is not None and trace_ctx.sampled:
                inner.trace = trace_ctx  # engine-side spans attach here
            self.engine.enqueue(inner)
            self._live[inner.req_id] = handle
            self._metrics["requests"].labels(
                tenant=self._tenant_label(tenant),
                priority=priority_name(priority),
            ).inc()
            self._update_gauges()
            return handle

    def set_replica_scope(self, name: str) -> None:
        """Bind this frontend (and its engine, prefix cache and KV tier) to
        a replica observability scope, resolved ONCE: every ``serving_*``/
        ``engine_*`` series records with a ``replica=name`` label, flight
        events land in one per-replica child ring teed into the global
        black box, and sampled spans carry a ``replica`` attribute (the
        cross-replica failover tree is assembled from those). Called by
        :class:`~paddle_tpu.serving.cluster.ReplicaCluster` at replica
        construction and again on revive."""
        from paddle_tpu.observability.metrics import GLOBAL_METRICS

        with self._lock:
            scope = GLOBAL_METRICS.scope(replica=name)
            flight = _flight.GLOBAL_FLIGHT_RECORDER.child(replica=name)
            self.replica_name = str(name)
            self._metrics = scope.bind_all(serving_metrics())
            self._flight = flight
            self.engine.set_replica_scope(name, scope=scope, flight=flight)

    @property
    def flight(self) -> _flight.FlightRecorder:
        """This frontend's flight ring (the replica's own ring when scoped,
        else the process-global recorder) — the incident writer dumps it."""
        with self._lock:
            return self._flight

    def _tenant_label(self, tenant: str) -> str:
        """Metric-label view of a tenant, bounded in cardinality: scheduling
        always uses the real tenant, but label cells are permanent registry
        state, so unseen tenants past ``max_tenant_labels`` export as
        ``"overflow"``."""
        if tenant in self._tenant_labels:
            return tenant
        if len(self._tenant_labels) < self.config.max_tenant_labels:
            self._tenant_labels.add(tenant)
            return tenant
        return "overflow"

    def _shed_gate(self, priority: int) -> None:
        depth = self.engine.queue_depth()
        if depth >= self.config.max_queue:
            self._count_shed("queue_full")
            raise Overloaded(
                f"intake queue full ({depth}/{self.config.max_queue})",
                retry_after=self._retry_after(), reason="queue_full",
            )
        if self.controller.level >= SHEDDING and priority >= Priority.BEST_EFFORT:
            self._count_shed("overload")
            raise Overloaded(
                f"shedding load (level={self.controller.level_name}); "
                f"priority class {priority_name(priority)} is not being admitted",
                retry_after=self._retry_after(), reason="overload",
            )

    def _degrade_gate(self, priority: int, max_new_tokens: int) -> int:
        """Graceful degradation: clamp token budgets under pressure instead
        of failing requests — best-effort from DEGRADED, standard once
        SHEDDING. Interactive budgets are never clamped."""
        lvl = self.controller.level
        clamp = (lvl >= DEGRADED and priority >= Priority.BEST_EFFORT) or (
            lvl >= SHEDDING and priority >= Priority.STANDARD
        )
        if clamp and max_new_tokens > self.config.degrade_max_new_tokens:
            self._metrics["degraded"].labels(action="clamp_max_new_tokens").inc()
            return self.config.degrade_max_new_tokens
        return max_new_tokens

    def _retry_after(self) -> float:
        """Backoff hint: how long the current backlog takes to drain at the
        recently observed step rate, floored at the configured base."""
        step = (sum(self._step_times) / len(self._step_times)) if self._step_times else 0.0
        est = self.engine.queue_depth() * step
        return round(max(self.config.retry_after_s, est), 3)

    def _count_shed(self, reason: str) -> None:
        self._metrics["shed"].labels(reason=reason).inc()

    # -- lifecycle -----------------------------------------------------------
    def cancel(self, req_id: int, reason: str = "cancelled") -> bool:
        """Shed one request wherever it lives (queued or mid-decode; the
        latter's KV blocks are reclaimed immediately). Returns False when the
        id is unknown or already terminal."""
        with self._lock:
            if req_id not in self._live:
                # unknown or already terminal — and, crucially, NOT ours: a
                # direct engine user's request must never be evicted by a
                # frontend id mix-up, so ownership is checked before the
                # engine is touched at all
                return False
            inner = self.engine.cancel_request(req_id, reason=reason)
            if inner is None:
                return False  # finished this boundary: the handle stays
                # live for pump() to finalize through step()'s delivery
            handle = self._live.pop(req_id)
            self._count_shed(reason)
            now = time.perf_counter()
            handle._push_new(now)  # flush tokens produced so far
            handle._finalize(reason, now)
            self._emit_trace(handle, now)
            self._update_gauges()
            return True

    def pump(self) -> List[ServingRequest]:
        """One scheduling iteration: drive the engine a step, stream fresh
        tokens into the per-request queues, finalize finishes/sheds, update
        the overload controller. Returns handles that reached a terminal
        state during this call."""
        finished: List[ServingRequest] = []
        engine = self.engine
        stats = engine.stats
        # frontend.pump = engine.step()'s four phases + frontend.deliver (two
        # stretches, one counter): the phases tile the pump
        with _tracing.phase("frontend.pump") as whole:
            with self._lock:
                with _tracing.phase(
                    "frontend.deliver", stats, "phase_s.deliver", None, whole.start_s
                ) as before:
                    # sample pressure at boundary ENTRY: the backlog as
                    # offered, not as already drained by this step's
                    # admissions — shedding must react to what clients are
                    # experiencing, and a deep queue that momentarily empties
                    # into slots is still a deep queue
                    self._update_controller()
                    stepping = engine.has_work()
                done_inner: List[InferenceRequest] = []
                if stepping:
                    done_inner = engine.step(since=before.end_s)
                with _tracing.phase(
                    "frontend.deliver", stats, "phase_s.deliver", None,
                    engine.last_step_end_s if stepping else before.end_s,
                ) as after:
                    now = after.start_s
                    if stepping:
                        self._step_times.append(now - before.end_s)
                    # stream tokens for everything still holding a slot
                    for inner in engine.live_requests():
                        handle = self._live.get(inner.req_id)
                        if handle is not None:
                            self._note_progress(handle, now)
                    for inner in done_inner:
                        handle = self._live.pop(inner.req_id, None)
                        if handle is None:
                            continue  # direct engine user / already cancelled
                        self._note_progress(handle, now)
                        finished.append(self._finalize(handle, now))
                    self._update_controller()
                    self._update_gauges()
                    if stepping:
                        engine.close_step(
                            (before.end_s - before.start_s)
                            + (time.perf_counter() - after.start_s)
                        )
            whole.end_s = after.end_s  # the pump ends where its last phase did
        return finished

    def _note_progress(self, handle: ServingRequest, now: float) -> None:
        first = handle.first_token_time is None
        pushed = handle._push_new(now)
        if pushed:
            ctx = handle.trace_ctx
            if ctx is not None and ctx.sampled:
                _tracing.GLOBAL_TRACER.add_event(
                    "stream_chunk", ctx=ctx, attrs={"tokens": pushed}
                )
            pr = priority_name(handle.priority)
            self._metrics["tokens"].labels(priority=pr).inc(pushed)
            if first:
                ttft = now - handle.submit_time
                self._ttfts.append(ttft)
                self._metrics["ttft"].labels(priority=pr).observe(ttft)
                if handle.inner.prefill_start is not None:
                    # queue wait ends when the slot is mapped (chunked
                    # prefill then runs across subsequent engine steps)
                    self._metrics["queue_wait"].labels(priority=pr).observe(
                        handle.inner.prefill_start - handle.submit_time
                    )

    def _finalize(self, handle: ServingRequest, now: float) -> ServingRequest:
        reason = handle.inner.finish_reason
        pr = priority_name(handle.priority)
        if reason in ("stop", "length"):
            handle._finalize("ok", now)
            if handle.met_deadline:
                self._metrics["goodput"].labels(priority=pr).inc(
                    len(handle.inner.generated)
                )
        elif reason == "deadline":
            stage = "queued" if handle.inner.prefill_start is None else "decode"
            outcome = f"deadline_{stage}"
            self._count_shed(outcome)
            self._metrics["deadline_miss"].labels(stage=stage).inc()
            handle._finalize(outcome, now)
        else:  # cancel_request reasons arriving via step() are already counted
            handle._finalize(reason or "unknown", now)
        self._emit_trace(handle, now)
        return handle

    def _emit_trace(self, handle: ServingRequest, now: float) -> None:
        """Emit the request's span tree at terminal time, built from the
        lifecycle timestamps the engine/frontend recorded along the way.
        The phases tile [submit, terminal] contiguously — queue_wait →
        (prefill → decode, when admitted) → stream_out — so their durations
        sum to the request's observed end-to-end latency, and every span is
        parented to the root. No-op unless this request was sampled."""
        ctx = handle.trace_ctx
        if ctx is None or not ctx.sampled:
            return
        t = _tracing.GLOBAL_TRACER
        inner = handle.inner
        tid, root = ctx.trace_id, ctx.span_id
        sub = handle.submit_time
        pstart, admit = inner.prefill_start, inner.admit_time
        fin = inner.finish_wall if inner.finish_wall is not None else now
        admitted = pstart is not None and admit is not None
        q_end = pstart if admitted else fin
        t.add_span(
            "request.queue_wait", trace_id=tid, parent_id=root,
            start_s=sub, end_s=q_end,
        )
        if admitted:
            t.add_span(
                "request.prefill", trace_id=tid, parent_id=root,
                start_s=pstart, end_s=admit,
                attrs={"prompt_len": int(inner.prompt.size)},
            )
            t.add_span(
                "request.decode", trace_id=tid, parent_id=root,
                start_s=admit, end_s=fin,
                attrs={
                    "decode_steps": inner.decode_steps,
                    # batched share: this request's even split of every
                    # decode step it rode (see engine.decode_step spans)
                    "batched_share_s": round(inner.decode_share_s, 6),
                },
            )
        t.add_span(
            "request.stream_out", trace_id=tid, parent_id=root,
            start_s=fin, end_s=now, attrs={"tokens": handle._n_pushed},
        )
        attrs = {
            "req_id": handle.id,
            "priority": priority_name(handle.priority),
            "tenant": handle.tenant,
            "outcome": handle.outcome,
            "finish_reason": inner.finish_reason,
            "n_generated": len(inner.generated),
        }
        if self.replica_name is not None:
            # replica attribution: a failed-over request's trace contains
            # one such span per replica that served it — the incident dump
            # CLI assembles them into one cross-replica tree by trace_id
            attrs["replica"] = self.replica_name
        t.add_span(
            "request", trace_id=tid, span_id=root, parent_id=ctx.parent_id,
            start_s=sub, end_s=now,
            attrs=attrs,
            status="ok" if handle.outcome == "ok" else f"shed:{handle.outcome}",
        )

    def _ttft_p99(self) -> float:
        if not self._ttfts:
            return 0.0
        ordered = sorted(self._ttfts)
        return ordered[min(len(ordered) - 1, int(0.99 * len(ordered)))]

    def _update_controller(self) -> int:
        stats = self.engine.pool_stats()
        # blocks the prefix cache retains warm but surrenders under pressure
        # are headroom, not load — counting them would shed traffic a single
        # eviction could have served
        live = stats["allocated"] - stats.get("cached_reusable", 0)
        util = live / stats["total"] if stats["total"] else 0.0
        queue_frac = self.engine.queue_depth() / self.config.max_queue
        prev = self.controller.level
        level = self.controller.update(queue_frac, util, self._ttft_p99())
        if level != prev:
            # overload transitions are rare and postmortem-critical: the
            # black box shows what pressure looked like before a death
            self._flight.record(
                "overload_level",
                **{"from": _LEVEL_NAMES[prev], "to": _LEVEL_NAMES[level],
                   "queue_frac": round(queue_frac, 4), "util": round(util, 4)},
            )
        return level

    def _update_gauges(self) -> None:
        self._metrics["queue_depth"].set(self.engine.queue_depth())
        self._metrics["level"].set(self.controller.level)
        cache = self.engine.prefix_cache_stats()
        if cache.get("enabled"):
            self._metrics["prefix_hit_rate"].set(cache["hit_rate"])

    # -- pump thread ---------------------------------------------------------
    def start(self) -> "ServingFrontend":
        """Run :meth:`pump` on a daemon thread until :meth:`stop`."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run_loop, daemon=True, name="serving-pump"
            )
            self._thread.start()
        return self

    def _run_loop(self) -> None:
        consecutive_failures = 0
        while not self._stop.is_set():
            try:
                self.pump()
                consecutive_failures = 0
            except Exception as exc:  # classify: engine.step() re-raises
                # transient failures with host state rolled back and the
                # engine still usable (caller-retryable contract) — those we
                # retry with backoff; a PERMANENT failure (engine.broken) or
                # a persistent error storm fails every live stream
                # explicitly instead of letting clients hang
                consecutive_failures += 1
                if self.engine.broken or consecutive_failures > 3:
                    self._fail_all(f"{type(exc).__name__}: {exc}")
                    return
                self._stop.wait(timeout=0.05 * consecutive_failures)
                continue
            if not self.engine.has_work():
                self._stop.wait(timeout=self.config.idle_sleep_s)

    def fail(self, why: str) -> None:
        """Declare this frontend permanently failed: stop the pump thread,
        salvage engine-finished results, and fail every other live stream
        explicitly (``engine_failure``). The cluster layer calls this when a
        replica is declared DEAD so its in-flight requests reach a terminal
        state the router can act on (salvage vs re-dispatch); idempotent."""
        self._stop.set()
        self._fail_all(why)

    def _fail_all(self, why: str) -> None:
        now = time.perf_counter()
        with self._lock:
            if self._failed is not None:
                return  # already failed: one death, one dump, one accounting
            self._failed = why
            # the pump thread is dying: black-box line + postmortem dump
            # (safe_dump never raises — failing every stream still happens)
            self._flight.record(
                "pump_death", why=why[:200], live=len(self._live),
                queue_depth=self.engine.queue_depth(),
            )
            self._flight.safe_dump("serving_pump_death", extra={"why": why[:200]})
            # salvage results the engine already finished but never delivered
            salvaged = {r.req_id for r in self.engine.drain_finished()}
            for rid, handle in list(self._live.items()):
                handle._push_new(now)
                if rid in salvaged and handle.inner.finish_reason in ("stop", "length"):
                    self._finalize(handle, now)
                else:
                    self._count_shed("engine_failure")
                    handle._finalize("engine_failure", now)
                    self._emit_trace(handle, now)
                del self._live[rid]
            self._update_gauges()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        # _thread is guarded by the frontend lock (start() mutates it under
        # the lock); the join itself must happen OUTSIDE the lock or a pump
        # iteration waiting on the lock could never finish its last pass
        with self._lock:
            t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        with self._lock:
            if self._thread is t:
                self._thread = None

    # -- introspection -------------------------------------------------------
    def health_snapshot(self) -> Dict[str, Any]:
        """Per-replica health view for a cluster router's probe loop: the
        liveness facts (engine ``broken`` flag, pump-thread liveness, the
        failure reason) plus the load signals the router's spill decision
        reads. ``pump_alive`` is None when no pump thread was ever started
        (inline drivers), so a router never mistakes inline mode for death.

        Under tensor parallelism the replica's health unit IS the shard
        group: one engine = one ``['tp']`` mesh, so a dead replica takes its
        whole shard group out of rotation at once — ``tp_degree`` rides
        along so the router's capacity view can weight replicas by chips."""
        with self._lock:
            t = self._thread
            stats = self.engine.pool_stats()
            live = stats["allocated"] - stats.get("cached_reusable", 0)
            return {
                "broken": self.engine.broken,
                "failed": self._failed,
                "pump_alive": None if t is None else t.is_alive(),
                "queue_depth": self.engine.queue_depth(),
                "max_queue": self.config.max_queue,
                "live_requests": len(self._live),
                "level": self.controller.level,
                "level_name": self.controller.level_name,
                "kv_utilization": round(
                    live / stats["total"] if stats["total"] else 0.0, 4
                ),
                "tp_degree": getattr(self.engine, "tp_degree", 1),
            }

    def snapshot(self) -> Dict[str, Any]:
        """Cheap health view (the HTTP /healthz payload)."""
        with self._lock:
            stats = self.engine.pool_stats()
            live = stats["allocated"] - stats.get("cached_reusable", 0)
            cache = self.engine.prefix_cache_stats()
            spec = self.engine.spec_decode_stats()
            return {
                "level": self.controller.level_name,
                "queue_depth": self.engine.queue_depth(),
                "max_queue": self.config.max_queue,
                "live_requests": len(self._live),
                "kv_utilization": round(
                    live / stats["total"] if stats["total"] else 0.0, 4
                ),
                # quantized serving surface: the pool's storage dtype and the
                # effective bytes one cached token costs across all layers
                "kv_cache_dtype": stats.get("kv_cache_dtype", "bf16"),
                "kv_bytes_per_token": stats.get("bytes_per_token", 0),
                "ttft_p99_s": round(self._ttft_p99(), 4),
                "failed": self._failed,
                "prefix_cache": {
                    "enabled": bool(cache.get("enabled")),
                    "hit_rate": round(cache.get("hit_rate", 0.0), 4),
                    "tokens_reused": cache.get("tokens_reused", 0),
                    "evictable_blocks": cache.get("evictable_blocks", 0),
                },
                "spec_decode": {
                    "enabled": bool(spec.get("enabled")),
                    "acceptance_rate": round(spec.get("acceptance_rate", 0.0), 4),
                    "accepted_tokens": spec.get("accepted_tokens", 0),
                    "drafted_tokens": spec.get("drafted_tokens", 0),
                },
                # hierarchical KV: the host-RAM spill tier under the prefix
                # cache (enabled: False == FLAGS_kv_host_tier_bytes=0)
                "kv_tier": (
                    self.engine.kv_tier_stats()
                    if hasattr(self.engine, "kv_tier_stats")
                    else {"enabled": False}
                ),
                # the shard-group identity: one engine = one ['tp'] mesh
                "tensor_parallel": (
                    self.engine.tp_stats()
                    if hasattr(self.engine, "tp_stats")
                    else {"tp_degree": 1}
                ),
                # device-time attribution over the step-timeline ring
                # (enabled: False == FLAGS_devprof_sample_rate=0)
                "devprof": (
                    self.engine.devprof_stats()
                    if hasattr(self.engine, "devprof_stats")
                    else {"enabled": False, "sampled_steps": 0}
                ),
            }
