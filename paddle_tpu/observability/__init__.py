"""Runtime telemetry layer (reference: SURVEY §5.1 — exported runtime flags,
profiler, ``DeviceMemoryStat`` accounting).

Three pieces, one substrate every perf/robustness PR reports through:

- a process-global, thread-safe metrics registry (:mod:`.metrics`):
  Counter / Gauge / Histogram with fixed log-scale buckets, near-zero
  overhead while ``FLAGS_enable_metrics`` is off;
- exporters (:mod:`.exporters`): Prometheus text exposition over an opt-in
  localhost HTTP endpoint (``FLAGS_metrics_port``), and a JSONL snapshot
  writer whose snapshots the chrome-trace exporter links into its span
  stream;
- a recompile watchdog (:mod:`.recompile`): compile counts with cause
  attribution (new shape/dtype vs. train/eval flip vs. first call) and a
  ``FLAGS_max_compiles_per_fn`` budget warning;
- a per-request distributed tracer (:mod:`.tracing`): span trees
  (trace/span/parent ids, traceparent propagation) with seeded head
  sampling via ``FLAGS_trace_sample_rate``, zero-cost when off, bounded
  span store, chrome-trace + JSONL export merged by ``profiler.export``;
- a device-time attribution layer (:mod:`.devprof`): compile-time cost
  profiles (``cost_analysis()`` keyed by watchdog signature, with a
  cost-regression ledger), sampled step profiles decomposed into
  host-prep / dispatch-gap / device segments with per-category device
  shares, and a measured per-step collective share — all behind
  ``FLAGS_devprof_sample_rate`` with the same cached-bool off-path;
- an always-on flight recorder (:mod:`.flight_recorder`): lock-cheap ring
  of recent structured events (admits/evicts/recoveries/compiles/faults/
  overload transitions), dumped automatically — redacted — on engine
  permanent failure, watchdog timeout and pump-thread death; read dumps
  with ``python -m paddle_tpu.observability.dump``.

**Phases of the serving step** (:class:`.tracing.phase`, always on). One
``ServingFrontend.pump()`` is tiled by ``frontend.deliver`` (the controller
update at entry; progress, finalisation, controller and gauges after the
step) and the four children of ``engine.decode_step``: ``engine.plan``
(admission up to the jit call), ``engine.launch`` (host-to-device puts and
the call up to its return), ``engine.wait`` (the host blocked on the
device), ``engine.commit`` (bookkeeping and token emission after the sync).
The two phases where the work changes hands are tiled again, into
sub-phases (the same primitive, nested in their parent):
``engine.launch.put`` (the step's seven host-to-device conversions) ->
``engine.launch.args`` (the argument lists: every weight, every cache plane)
-> ``engine.launch.call`` (the jit call up to its return), and
``engine.wait.ready`` (until the result is ready on the device, nothing
copied) -> ``engine.wait.fetch`` (the tokens' copy to the host).
Recovery replays run under ``engine.recover`` and are not timed. Each phase is

- a ``jax.profiler.TraceAnnotation("paddle_tpu.<phase>")``: start
  ``profiler.Profiler`` or ``jax.profiler.start_trace`` and the phases lie
  over the device's "XLA Ops" in the same ``.xplane.pb``, on its clock; the
  kernels there carry their ``pallas_call`` names (``paged_attention_chunk``,
  ``flash_attention_fwd``, ``fused_loss_dw``, ...) and every other operation
  its ``jax.named_scope`` path in the ``tf_op`` stat (``embedding``, ``norm``,
  ``attention``, ``mlp``, ``lm_head``, ``loss_head``, ``optimizer_update``,
  ``kv_cache_update``, ``kv_cow``, ``sample``; a backward operation under its
  forward's);
- seconds added to ``engine.stats``: ``phase_s.plan``, ``phase_s.launch``,
  ``phase_s.wait``, ``phase_s.commit``, ``phase_s.deliver`` (cumulative;
  divide a delta by the delta of ``steps``; these five tile the pump), and
  for the sub-phases ``subphase_s.launch_put`` + ``subphase_s.launch_args`` +
  ``subphase_s.launch_call`` (they sum to ``phase_s.launch``) and
  ``subphase_s.wait_ready`` + ``subphase_s.wait_fetch`` (to ``phase_s.wait``);
- at ``FLAGS_trace_sample_rate >= 1`` a span in the ring, child of the
  enclosing phase, with the step number: thirteen spans a pump, so the
  default ring of 4096 records (``FLAGS_trace_buffer_size``) holds about
  140 steps of a busy engine's requests and phases (230 before the
  sub-phases).

**Stalls.** A step whose wall time passes 5 x the median of the last 64
steps adds what lay above the median to ``engine.stats["stall_s.host"]``
(plan + launch + commit + deliver) or ``["stall_s.device"]`` (wait), bumps
``["stall_steps"]`` and records ONE flight-recorder event ``step_stall``:
each phase's wall seconds (``plan_s``, ``launch_s``, ``wait_s``,
``commit_s``, ``deliver_s``), the sub-phases' (``put_s`` + ``args_s`` +
``call_s`` = ``launch_s``; ``ready_s`` + ``fetch_s`` = ``wait_s``),
``median_wall_s``, and over the stretch from the
previous step's close to this one's the wall seconds (``since_close_s``)
beside the pump thread's CPU seconds (``cpu_s``, ``time.thread_time()``, one
read a step). ``cpu_s`` far below ``since_close_s``: the thread was
descheduled or blocked (a shared host, a lock);
about equal: the program's own Python ran that long. A stall that lies in
``ready_s``: the executable had not finished (the device computed, or the
runtime reported its completion late; a device trace of the step tells the
two apart). One that lies in ``fetch_s``: the result was ready and its copy
to the host took that long (the transfer, or the host). One in ``call_s``:
the jit call itself (a cache miss, a blocked enqueue); in ``put_s``: the
host-to-device copies of the step's small arguments. Steps that compile or
recover are not judged. Read it from a dump
(``obs.GLOBAL_FLIGHT_RECORDER.dump("why")`` then ``python -m
paddle_tpu.observability.dump <file>``) or from ``snapshot()``.

Instrumented call sites: ``inference/engine.py`` (TTFT, decode-step latency,
queue depth, admits/evicts/finished, KV-pool gauges), ``jit/api.py``
(StaticFunction cache misses feed the watchdog), ``distributed/collective.py``
(per-op call/time counters), and the serving front end (:mod:`.serving`
families: shed/deadline/goodput counters, per-priority queue-wait and TTFT
histograms, overload-level gauge).
"""

from paddle_tpu.observability.flight_recorder import (  # noqa: F401
    FlightRecorder,
    GLOBAL_FLIGHT_RECORDER,
    get_flight_recorder,
    record_event,
    safe_dump,
)
from paddle_tpu.observability.tracing import (  # noqa: F401
    GLOBAL_TRACER,
    Span,
    TraceContext,
    Tracer,
    format_traceparent,
    get_tracer,
    parse_traceparent,
    phase,
    tracing_enabled,
    tracing_full,
)
from paddle_tpu.observability.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricScope,
    MetricsRegistry,
    GLOBAL_METRICS,
    get_registry,
    metrics_enabled,
)
from paddle_tpu.observability.slo import (  # noqa: F401
    BurnRateMonitor,
    SLOConfig,
    SLO_STATE_NAMES,
)
from paddle_tpu.observability.aggregate import (  # noqa: F401
    ClusterObserver,
    FLEET_COUNTER_FAMILIES,
    INCIDENT_SCHEMA,
)
from paddle_tpu.observability.devprof import (  # noqa: F401
    CostLedger,
    GLOBAL_COST_LEDGER,
    SampleGate,
    StepTimeline,
    capture_cost_profile,
    devprof_enabled,
    normalize_cost_analysis,
    record_step_profile,
    summarize_timeline,
)
from paddle_tpu.observability.recompile import (  # noqa: F401
    CAUSE_FIRST_CALL,
    CAUSE_MODE_FLIP,
    CAUSE_NEW_SHAPE_DTYPE,
    GLOBAL_WATCHDOG,
    RecompileBudgetWarning,
    RecompileWatchdog,
    get_watchdog,
)
from paddle_tpu.observability.exporters import (  # noqa: F401
    drain_trace_events,
    render_exposition,
    start_metrics_server,
    stop_metrics_server,
    write_snapshot_jsonl,
)
from paddle_tpu.observability.serving import (  # noqa: F401
    PRIORITY_NAMES,
    priority_name,
    serving_metrics,
)

__all__ = [
    "FlightRecorder",
    "GLOBAL_FLIGHT_RECORDER",
    "get_flight_recorder",
    "record_event",
    "safe_dump",
    "GLOBAL_TRACER",
    "Span",
    "TraceContext",
    "Tracer",
    "format_traceparent",
    "get_tracer",
    "parse_traceparent",
    "tracing_enabled",
    "tracing_full",
    "PRIORITY_NAMES",
    "priority_name",
    "serving_metrics",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricScope",
    "MetricsRegistry",
    "GLOBAL_METRICS",
    "get_registry",
    "metrics_enabled",
    "BurnRateMonitor",
    "SLOConfig",
    "SLO_STATE_NAMES",
    "ClusterObserver",
    "FLEET_COUNTER_FAMILIES",
    "INCIDENT_SCHEMA",
    "CostLedger",
    "GLOBAL_COST_LEDGER",
    "SampleGate",
    "StepTimeline",
    "capture_cost_profile",
    "devprof_enabled",
    "normalize_cost_analysis",
    "record_step_profile",
    "summarize_timeline",
    "CAUSE_FIRST_CALL",
    "CAUSE_MODE_FLIP",
    "CAUSE_NEW_SHAPE_DTYPE",
    "GLOBAL_WATCHDOG",
    "RecompileBudgetWarning",
    "RecompileWatchdog",
    "get_watchdog",
    "drain_trace_events",
    "render_exposition",
    "start_metrics_server",
    "stop_metrics_server",
    "write_snapshot_jsonl",
]
