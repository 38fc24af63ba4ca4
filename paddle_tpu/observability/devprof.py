"""Device-time attribution: per-step cost profiles, host-bubble analysis,
and a measured comm/compute breakdown.

Every observability layer to date (metrics, tracing, fleet/SLO) stops at the
dispatch boundary: it knows when a step was *launched* and when its result
was *consumed*, but nothing attributes time below that line — which kernel
categories dominate, how much of a step is host bubble, or what the
collectives actually cost. This module closes that gap in three pieces:

- **Static cost profiles.** On every compile the recompile watchdog (fed a
  ``cost_thunk`` by its call sites) captures ``compiled.cost_analysis()`` —
  flops, HBM bytes — keyed by the watchdog's signature, so each compiled
  program carries a cost model. The thunk is an *introspective AOT
  lowering* (``fn.lower(...).compile()``): it re-runs the Python trace and
  pays one extra XLA compile, which is why capture arms only while
  ``FLAGS_devprof_sample_rate > 0`` — compile seams are seconds-scale
  already, but doubling them must be opt-in. A backend without an XLA cost
  model records ``cost_model: "unavailable"`` with zeroed numbers rather
  than raising, so the CPU tier exercises the full path. A **cost-regression ledger** compares each new
  signature's flops/bytes against the function's previous program and flags
  drift past a tolerance — a re-trace that silently changed the program's
  cost is exactly the regression a recompile count alone cannot see.

- **Sampled step profiles.** Behind ``FLAGS_devprof_sample_rate`` (the same
  listener-cached-bool off-path as metrics/tracing: rate 0 costs one list
  read, and sampling is a deterministic stride — no RNG draw, so profiling
  can never perturb seeded reproducibility). A sampled engine step is timed
  device-sync-honest from four instants (step start, dispatch call,
  dispatch return, sync complete) and decomposed into **host-prep /
  dispatch-gap (bubble) / device** segments that tile the step wall
  exactly. Device time is apportioned across **attention / matmul /
  collective / other** categories using the cost profile as the attribution
  prior (caveat: apportionment, not per-kernel measurement — the prior is
  an analytic flop/byte split reconciled against the XLA cost model).
  Profiles land in share histograms, a bounded per-engine step-timeline
  ring (``FLAGS_devprof_timeline_size``), ``devprof_step`` flight-recorder
  events (so postmortem dumps carry them), and chrome-trace counter tracks
  merged by ``profiler.Profiler.export``.

- **Measured comm share.** While a sampled step is in flight the engine
  arms a thread-local comm window; the instrumented collective wrapper
  (``distributed/collective.py``) feeds its per-op host timings into it.
  When the window caught real wrapper time, the step's collective share is
  measured (``comm_source: "wrapper"``); when the program's collectives are
  GSPMD-inserted (the tp engine's all-reduces — invisible to host
  wrappers), the share falls back to the cost-model prior (``comm_source:
  "cost_model"``) applied to the *measured* device segment: a model, not
  a measurement (ROADMAP D7).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from paddle_tpu.flags import GLOBAL_FLAGS

from . import flight_recorder as _flight
from . import metrics as _metrics

__all__ = [
    "CostLedger",
    "GLOBAL_COST_LEDGER",
    "SampleGate",
    "StepTimeline",
    "begin_comm_window",
    "capture_cost_profile",
    "comm_window_armed",
    "devprof_enabled",
    "drain_chrome_events",
    "end_comm_window",
    "normalize_cost_analysis",
    "record_comm",
    "record_step_profile",
    "summarize_timeline",
]

CATEGORIES = ("attention", "matmul", "collective", "other")

# cached FLAGS_devprof_sample_rate: one list read on the off path; the
# listener keeps both cells in lockstep with set_flags / env seeding
_ENABLED = [False]
_RATE = [0.0]


def _refresh_rate(value: Any) -> None:
    rate = float(value)
    _RATE[0] = rate
    _ENABLED[0] = rate > 0.0


GLOBAL_FLAGS.on_change("devprof_sample_rate", _refresh_rate)
_refresh_rate(GLOBAL_FLAGS.get("devprof_sample_rate"))  # seeds FLAGS_ env var


def devprof_enabled() -> bool:
    """Current ``FLAGS_devprof_sample_rate > 0`` without touching the flag
    registry — the one gate every profiling site checks first."""
    return _ENABLED[0]


# -- metric families ----------------------------------------------------------
_share_hist = _metrics.GLOBAL_METRICS.histogram(
    "devprof_category_share",
    "Per-category share of a sampled step's device segment (attribution by "
    "the compile-time cost prior; shares sum to 1 per sampled step).",
    labelnames=("category",),
)
_bubble_hist = _metrics.GLOBAL_METRICS.histogram(
    "devprof_host_bubble_fraction",
    "Host fraction of a sampled step's wall (host-prep + dispatch-gap over "
    "the device-sync-honest step wall).",
)
_device_hist = _metrics.GLOBAL_METRICS.histogram(
    "devprof_device_seconds",
    "Device segment (dispatch-return to sync-complete) of sampled steps.",
)
_regression_counter = _metrics.GLOBAL_METRICS.counter(
    "devprof_cost_regressions_total",
    "Cost-regression ledger entries: a re-trace whose flops/bytes drifted "
    "from the function's previous compiled program.",
)


# -- cost_analysis -------------------------------------------------------------

_COST_KEYS = {"flops": "flops", "bytes accessed": "bytes_accessed",
              "transcendentals": "transcendentals"}


def normalize_cost_analysis(raw: Any) -> Dict[str, Any]:
    """``compiled.cost_analysis()``'s dict under this module's key names;
    None/garbage records ``cost_model: "unavailable"`` with zeroed numbers
    instead of raising, so backends without an XLA cost model still
    exercise the full capture path."""
    out: Dict[str, Any] = {k: 0.0 for k in _COST_KEYS.values()}
    seen_any = False
    if isinstance(raw, dict):
        for src, dst in _COST_KEYS.items():
            v = raw.get(src)
            if isinstance(v, (int, float)):
                out[dst] = float(v)
                seen_any = True
    out["cost_model"] = "xla" if seen_any else "unavailable"
    return out


def _category_prior(
    profile: Dict[str, Any], hints: Optional[Dict[str, float]]
) -> Dict[str, float]:
    """Normalized attribution prior over :data:`CATEGORIES`. ``hints`` are
    analytic per-category weights from the capturing component (comparable
    units — estimated seconds or flops); the XLA cost model reconciles the
    tail: measured flops beyond the analytic attention+matmul total land in
    "other" (fused epilogues, bookkeeping ops the analytic split ignores).
    Without hints everything is "other" — an honest "unattributed"."""
    weights = {k: 0.0 for k in CATEGORIES}
    if hints:
        for k in CATEGORIES:
            v = hints.get(k)
            if isinstance(v, (int, float)) and v > 0:
                weights[k] = float(v)
    known = weights["attention"] + weights["matmul"]
    xla_flops = float(profile.get("flops") or 0.0)
    if known > 0 and xla_flops > known:
        # hints are flop-denominated when attention/matmul came from flop
        # counts; the excess the cost model measured is real device work
        # the analytic split has no name for
        weights["other"] += xla_flops - known
    total = sum(weights.values())
    if total <= 0:
        return {"attention": 0.0, "matmul": 0.0, "collective": 0.0, "other": 1.0}
    return {k: v / total for k, v in weights.items()}


# -- cost-regression ledger ---------------------------------------------------

class CostLedger:
    """Per-(fn, signature) cost profiles with fn-level drift detection.

    ``record`` compares each new profile against the SAME function's
    previously recorded program (any signature): a shape-bucket re-trace
    that moved flops/bytes past ``drift_tolerance`` (relative) appends a
    regression entry, bumps ``devprof_cost_regressions_total`` and drops a
    ``cost_regression`` line into the flight ring — compile-time truth the
    postmortem can line up against the latency timeline."""

    def __init__(self, drift_tolerance: float = 0.01) -> None:
        self._lock = threading.Lock()
        self.drift_tolerance = float(drift_tolerance)
        # fn -> {signature: profile}; insertion order = capture order
        self._profiles: Dict[str, Dict[str, Dict[str, Any]]] = {}
        self._latest: Dict[str, tuple] = {}  # fn -> (signature, profile)
        self.regressions: List[Dict[str, Any]] = []

    @staticmethod
    def _drift(prev: float, new: float) -> float:
        if prev == 0.0:
            return 0.0 if new == 0.0 else float("inf")
        return abs(new - prev) / abs(prev)

    def record(self, fn: str, signature: str, profile: Dict[str, Any]) -> None:
        sig = str(signature)[:200]
        with self._lock:
            prev = self._latest.get(fn)
            self._profiles.setdefault(fn, {})[sig] = dict(profile)
            self._latest[fn] = (sig, dict(profile))
        if prev is None or prev[0] == sig:
            return
        prev_sig, prev_prof = prev
        if (
            prev_prof.get("cost_model") == "unavailable"
            or profile.get("cost_model") == "unavailable"
        ):
            return  # no numbers on one side: drift is undefined, not zero
        drift_flops = self._drift(
            float(prev_prof.get("flops") or 0.0), float(profile.get("flops") or 0.0)
        )
        drift_bytes = self._drift(
            float(prev_prof.get("bytes_accessed") or 0.0),
            float(profile.get("bytes_accessed") or 0.0),
        )
        if max(drift_flops, drift_bytes) <= self.drift_tolerance:
            return
        entry = {
            "fn": fn,
            "prev_signature": prev_sig,
            "signature": sig,
            "prev_flops": prev_prof.get("flops"),
            "flops": profile.get("flops"),
            "prev_bytes": prev_prof.get("bytes_accessed"),
            "bytes": profile.get("bytes_accessed"),
            "drift_flops": drift_flops,
            "drift_bytes": drift_bytes,
        }
        with self._lock:
            self.regressions.append(entry)
        _regression_counter.inc()
        _flight.record_event(
            "cost_regression", fn=fn, signature=sig,
            drift_flops=round(drift_flops, 4), drift_bytes=round(drift_bytes, 4),
        )

    def profile_for(self, fn: str, signature: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            by_sig = self._profiles.get(fn)
            if not by_sig:
                return None
            prof = by_sig.get(str(signature)[:200])
            if prof is None:
                # an unknown signature still gets the fn's latest profile:
                # better a slightly stale prior than no attribution at all
                prof = self._latest[fn][1]
            return dict(prof)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "profiles": {
                    fn: {sig: dict(p) for sig, p in by_sig.items()}
                    for fn, by_sig in self._profiles.items()
                },
                "regressions": [dict(r) for r in self.regressions],
            }

    def reset(self) -> None:
        with self._lock:
            self._profiles.clear()
            self._latest.clear()
            self.regressions.clear()


GLOBAL_COST_LEDGER = CostLedger()


def capture_cost_profile(
    fn: str,
    signature: str,
    cost_thunk: Callable[[], Any],
    hints: Optional[Dict[str, float]] = None,
) -> Optional[Dict[str, Any]]:
    """Run one compile seam's cost capture into the global ledger. No-op at
    rate 0; never raises — a broken cost model must not fail the compile
    path it is documenting. Returns the normalized profile (or None)."""
    if not _ENABLED[0]:
        return None
    try:
        raw = cost_thunk()
    except Exception:  # noqa: BLE001 - cost capture is best-effort by contract
        raw = None
    profile = normalize_cost_analysis(raw)
    profile["categories"] = _category_prior(profile, hints)
    GLOBAL_COST_LEDGER.record(fn, signature, profile)
    return profile


# -- sampling -----------------------------------------------------------------

class SampleGate:
    """Deterministic stride sampler: at rate r, every round(1/r)-th call
    samples (rate >= 1 samples every call). No RNG — profiling a seeded run
    cannot perturb its reproducibility, and the off path is one list read."""

    def __init__(self) -> None:
        self._n = 0

    def should_sample(self) -> bool:
        if not _ENABLED[0]:
            return False
        rate = _RATE[0]
        self._n += 1
        if rate >= 1.0:
            return True
        stride = max(1, int(round(1.0 / rate)))
        return (self._n - 1) % stride == 0


# -- per-step comm window -----------------------------------------------------
# threading.local, not a global: each engine's pump thread arms its own
# window, so concurrently stepping replicas never cross-contaminate
class _CommWindow(threading.local):
    ops: Optional[Dict[str, float]] = None


_WIN = _CommWindow()


def comm_window_armed() -> bool:
    return _WIN.ops is not None


def begin_comm_window() -> None:
    _WIN.ops = {}


def end_comm_window() -> Dict[str, float]:
    ops, _WIN.ops = _WIN.ops, None
    return ops or {}


def record_comm(op: str, seconds: float) -> None:
    """Fed by the instrumented collective wrapper while a window is armed."""
    ops = _WIN.ops
    if ops is not None:
        ops[op] = ops.get(op, 0.0) + float(seconds)


# -- step timeline ring -------------------------------------------------------

class StepTimeline:
    """Bounded per-engine ring of sampled step profiles (newest win)."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        cap = int(
            GLOBAL_FLAGS.get("devprof_timeline_size")
            if capacity is None
            else capacity
        )
        if cap < 1:
            raise ValueError(f"timeline capacity must be >= 1, got {cap}")
        self._store: deque = deque(maxlen=cap)
        self._lock = threading.Lock()

    def append(self, entry: Dict[str, Any]) -> None:
        with self._lock:
            self._store.append(entry)

    def entries(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._store]

    def __len__(self) -> int:
        with self._lock:
            return len(self._store)

    def clear(self) -> None:
        with self._lock:
            self._store.clear()


# chrome counter-track buffer drained by profiler.Profiler.export; bounded so
# an exporter that never runs cannot grow host memory
_CHROME_EVENTS: deque = deque(maxlen=4096)
_CHROME_LOCK = threading.Lock()


def record_step_profile(
    fn: str,
    signature: str,
    t0: float,
    call_s: float,
    ret_s: float,
    sync_s: float,
    comm_ops: Optional[Dict[str, float]] = None,
    n_active: int = 0,
    step: int = 0,
    timeline: Optional[StepTimeline] = None,
    flight: Optional[Any] = None,
) -> Dict[str, Any]:
    """Assemble one sampled step's profile from its four timing instants.

    The segments are consecutive differences of the same ``perf_counter``
    readings, so host_prep + dispatch + device tiles the wall EXACTLY —
    the honesty property the devprof test pins. Device time is apportioned
    across categories by the cost prior; wrapper-measured collective time
    overrides the prior's collective share when the window caught any."""
    wall = max(sync_s - t0, 0.0)
    host_prep = max(call_s - t0, 0.0)
    dispatch = max(ret_s - call_s, 0.0)
    device = max(sync_s - ret_s, 0.0)
    prof = GLOBAL_COST_LEDGER.profile_for(fn, signature)
    prior = (
        dict(prof["categories"])
        if prof and isinstance(prof.get("categories"), dict)
        else {"attention": 0.0, "matmul": 0.0, "collective": 0.0, "other": 1.0}
    )
    comm_s = sum((comm_ops or {}).values())
    if comm_s > 0.0 and device > 0.0:
        # the wrapper measured real collective host time inside the window:
        # its share of the device segment is measurement, not prior — the
        # non-collective categories split the remainder by their prior ratio
        coll = min(comm_s / device, 1.0)
        rest_prior = sum(v for k, v in prior.items() if k != "collective")
        shares = {
            k: ((1.0 - coll) * (v / rest_prior) if rest_prior > 0 else 0.0)
            for k, v in prior.items()
            if k != "collective"
        }
        shares["collective"] = coll
        if rest_prior <= 0:
            shares["other"] = 1.0 - coll
        comm_source = "wrapper"
    else:
        shares = prior
        comm_source = (
            "cost_model" if prior.get("collective", 0.0) > 0.0 else "none"
        )
    total = sum(shares.values())
    if total > 0:
        shares = {k: v / total for k, v in shares.items()}
    entry = {
        "t_s": t0,
        "step": int(step),
        "n_active": int(n_active),
        "wall_s": wall,
        "host_prep_s": host_prep,
        "dispatch_s": dispatch,
        "device_s": device,
        "host_bubble_fraction": ((host_prep + dispatch) / wall) if wall > 0 else 0.0,
        "comm_s": comm_s,
        "comm_source": comm_source,
        "categories": {k: round(v, 6) for k, v in shares.items()},
        "cost_model": (prof or {}).get("cost_model", "missing"),
        "signature": str(signature)[:200],
    }
    if timeline is not None:
        timeline.append(entry)
    if flight is not None:
        flight.record(
            "devprof_step",
            step=entry["step"], n_active=entry["n_active"],
            wall_ms=round(wall * 1e3, 4),
            host_prep_ms=round(host_prep * 1e3, 4),
            dispatch_ms=round(dispatch * 1e3, 4),
            device_ms=round(device * 1e3, 4),
            host_bubble_fraction=round(entry["host_bubble_fraction"], 4),
            comm_source=comm_source,
            categories=entry["categories"],
        )
    if _metrics.metrics_enabled():
        for k, v in shares.items():
            _share_hist.labels(category=k).observe(v)
        _bubble_hist.observe(entry["host_bubble_fraction"])
        _device_hist.observe(device)
    with _CHROME_LOCK:
        ts_us = t0 * 1e6
        # counter tracks: device ms per category, plus the segment split —
        # Profiler.export merges these onto the RecordEvent/span timeline
        _CHROME_EVENTS.append(
            {
                "name": "devprof.device_ms_by_category", "ph": "C", "ts": ts_us,
                "pid": 0, "tid": 0,
                "args": {
                    k: round(v * device * 1e3, 4) for k, v in shares.items()
                },
            }
        )
        _CHROME_EVENTS.append(
            {
                "name": "devprof.step_segments_ms", "ph": "C", "ts": ts_us,
                "pid": 0, "tid": 0,
                "args": {
                    "host_prep": round(host_prep * 1e3, 4),
                    "dispatch_gap": round(dispatch * 1e3, 4),
                    "device": round(device * 1e3, 4),
                },
            }
        )
    return entry


def drain_chrome_events() -> List[Dict[str, Any]]:
    """Drain the counter-track buffer (what ``profiler.Profiler.export``
    merges into its traceEvents stream)."""
    import os as _os

    with _CHROME_LOCK:
        out, n = list(_CHROME_EVENTS), len(_CHROME_EVENTS)
        _CHROME_EVENTS.clear()
    pid = _os.getpid()
    tid = threading.get_ident()
    for ev in out:
        ev["pid"], ev["tid"] = pid, tid
    return out[:n]


def summarize_timeline(entries: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate view of a step-timeline ring for /healthz, incident
    snapshots and bench records: mean segment split, mean per-category
    shares, and the measured comm share with its source breakdown."""
    if not entries:
        return {"enabled": _ENABLED[0], "sampled_steps": 0}
    n = len(entries)
    walls = [e.get("wall_s", 0.0) for e in entries]
    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731 - tiny local aggregator
    cats = {k: mean([e.get("categories", {}).get(k, 0.0) for e in entries])
            for k in CATEGORIES}
    sources: Dict[str, int] = {}
    for e in entries:
        src = e.get("comm_source", "none")
        sources[src] = sources.get(src, 0) + 1
    return {
        "enabled": _ENABLED[0],
        "sampled_steps": n,
        "mean_wall_ms": round(mean(walls) * 1e3, 4),
        "mean_host_bubble_fraction": round(
            mean([e.get("host_bubble_fraction", 0.0) for e in entries]), 4
        ),
        "mean_device_ms": round(
            mean([e.get("device_s", 0.0) for e in entries]) * 1e3, 4
        ),
        "mean_category_shares": {k: round(v, 4) for k, v in cats.items()},
        "comm_share_measured": round(cats.get("collective", 0.0), 4),
        "comm_sources": sources,
        "last": dict(entries[-1]),
    }
