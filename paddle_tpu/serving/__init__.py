"""SLO-aware serving layer over the continuous-batching engine.

The host-side policy stack between clients and ``engine.step()``:

- :mod:`.frontend` — :class:`ServingFrontend`: bounded intake, per-request
  deadlines/TTLs, priority classes with weighted per-tenant fair admission,
  hysteresis load shedding and graceful degradation;
- :mod:`.scheduler` — :class:`WeightedFairPolicy`, the stride scheduler
  installed as the engine's admission policy;
- :mod:`.http` — the streaming localhost HTTP endpoint
  (``start_serving_server``, ``FLAGS_serving_port``); also serves a
  :class:`ReplicaRouter` for the thin multi-replica mode;
- :mod:`.cluster` / :mod:`.router` — cluster-scale serving:
  :class:`ReplicaCluster` (replica lifecycle: UP/DEGRADED/DRAINING/DEAD,
  kill/revive) and :class:`ReplicaRouter` (rendezvous prefix-affinity
  routing, health-gated failover with salvage + bounded deadline-aware
  re-dispatch, drain, cross-replica spill);
- :mod:`.loadgen` — the open-loop Poisson arrival harness behind the overload
  acceptance tests (the benchmark's arrivals are ``benchmarks/lib/traffic.py``);
- :mod:`.errors` — :class:`Overloaded` (429) and the re-exported typed
  :class:`IntakeError` taxonomy (4xx).

See README "Serving & SLOs" and "Cluster serving & failover" for
thresholds, status mapping and flags.
"""

from paddle_tpu.serving.cluster import (  # noqa: F401
    Replica,
    ReplicaCluster,
)
from paddle_tpu.serving.errors import (  # noqa: F401
    EmptyPromptError,
    IntakeError,
    InvalidTokenBudgetError,
    Overloaded,
    PromptTooLongError,
    RequestTooLongError,
    RequestUnservableError,
    ServingError,
)
from paddle_tpu.serving.frontend import (  # noqa: F401
    Hysteresis,
    OverloadController,
    Priority,
    ServingConfig,
    ServingFrontend,
    ServingRequest,
)
from paddle_tpu.serving.http import (  # noqa: F401
    start_serving_server,
    stop_serving_server,
)
from paddle_tpu.serving.router import (  # noqa: F401
    ReplicaRouter,
    RouterConfig,
    RouterRequest,
)
from paddle_tpu.serving.scheduler import WeightedFairPolicy  # noqa: F401

__all__ = [
    "EmptyPromptError",
    "Hysteresis",
    "IntakeError",
    "InvalidTokenBudgetError",
    "Overloaded",
    "OverloadController",
    "Priority",
    "PromptTooLongError",
    "Replica",
    "ReplicaCluster",
    "ReplicaRouter",
    "RequestTooLongError",
    "RequestUnservableError",
    "RouterConfig",
    "RouterRequest",
    "ServingConfig",
    "ServingError",
    "ServingFrontend",
    "ServingRequest",
    "WeightedFairPolicy",
    "start_serving_server",
    "stop_serving_server",
]
