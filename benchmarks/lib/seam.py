"""The serving step's seam between host and device (PR 38): how long the
device stands idle at a step's two ends, read without a clock the host and
the device share.

The step is synchronous: step n's last operation ends before step n+1 is
planned. Of one step take two durations, each on its own clock: ``H``, the
host's time from the start of ``paddle_tpu.engine.launch.call`` (the jit call)
to the end of ``paddle_tpu.engine.wait.ready`` (the result is ready), and
``D``, the device's time from its first operation's start to its last
operation's end. ``H - D`` is the device's idle time at the step's two ends,
and an offset between the two clocks cancels in it. Only its DIVISION into
``first`` (call -> first operation) and ``last`` (last operation -> ready)
needs the trace's two clocks tied; neither part can be negative in any step,
so over a slice the offset is confined to an interval ``min(first) +
min(last)`` wide: the most the two parts can be wrong by.

A program without the sub-phases (no ``subphase_s.*`` counter, no
``engine.launch.call`` span: the parent of PR 38) makes every function here
return ``None``, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import json
from typing import Any, Dict, List, Optional, Tuple

from . import phases, xplane

# the sub-phases of engine.launch and engine.wait (paddle_tpu/inference/engine.py::_dispatch), as spans in a trace
SUBPHASES = ("engine.launch.put", "engine.launch.args", "engine.launch.call", "engine.wait.ready", "engine.wait.fetch")
CALL, READY = SUBPHASES[2], SUBPHASES[3]
Step = Dict[str, float]  # H, D, first, last, inner: seconds


# -- counters -------------------------------------------------------------------
def subphase_ms(run: Dict[str, Any], key: str) -> Optional[float]:
    """Per-step milliseconds of one ``subphase_s.*`` counter over the window, or
    ``None`` where the program has no such counter (``per_step_ms`` would raise)."""
    engine = phases.engine_delta(run)
    if engine is None or key not in engine:
        return None
    return phases.per_step_ms(run, key)


# -- the traced slice -----------------------------------------------------------
# pass 2's tolerance: a step whose first operation starts more than this off the slice's
# median distance from its call (a call that blocked in the enqueue) is left out
TOLERANCE_S = 1e-3


def _inside(spans: List[Tuple[str, float, float]], starts: List[float], lo: float, hi: float):
    """The one span that starts in ``[lo, hi)``, or ``None`` if none or several do."""
    i, j = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
    return spans[i] if j - i == 1 else None


def steps(run: Dict[str, Any]) -> Optional[List[Step]]:
    """``H``, ``D``, ``first``, ``last`` of each WHOLE step of the traced slice,
    and ``inner``: the part of ``D`` in which no operation ran (gaps between
    operations inside the step, which belong to neither end).

    A step runs from its ``engine.launch`` to the next step's; its jit call
    (``engine.launch.call``) and its ``engine.wait.ready`` start in between.
    Its first operation is where the idle gap of the device before it ends,
    and its last operation ends where the next step's such gap begins. That
    gap is found in two passes. The first takes, as ``lib/phases.py`` does
    from the launch, the longest gap that ends near the CALL's start (within
    half the distance to the neighbouring calls, 20 ms at most: far more than
    the two clocks can be apart; the call and not the launch, so that a put
    that blocks for a tenth of a second moves nothing), and the median over
    the slice of where it ends says how the clocks are tied in this trace.
    The second takes the longest gap that ends within ``TOLERANCE_S`` of that
    distance from each call, so that a pause of the device INSIDE a step
    (longer, now and then, than the gap between two steps) is never taken for
    a step's end. The slice's first and last steps, which the profile's start
    and stop may have cut, are left out, and so is a step whose spans are
    missing or whose gap is not found, with the step before it."""
    if "_seam_steps" not in run:
        run["_seam_steps"] = _steps(run)
    return run["_seam_steps"]


def _steps(run: Dict[str, Any]) -> Optional[List[Step]]:
    trace = phases.program_trace(run)
    calls, readies = phases.spans_named(run, CALL), phases.spans_named(run, READY)
    launches = phases.spans_named(run, "engine.launch")
    if trace is None or not calls or not readies or len(launches) < 3 or not trace["ops"]:
        return None
    busy = xplane.union([(a, b) for _n, a, b, _d in trace["ops"]])  # over every chip used
    gaps = [(trace["window"][0], busy[0][0])] + [(g0, g1) for (_a, g0), (g1, _b) in zip(busy, busy[1:])]
    ends = [g1 for _g0, g1 in gaps]  # ascending, as the busy intervals are
    gap_starts = [g0 for g0, _g1 in gaps]

    def longest_ending_in(lo: float, hi: float) -> Optional[Tuple[float, float]]:
        found = gaps[bisect.bisect_left(ends, lo):bisect.bisect_right(ends, hi)]
        return max(found, key=lambda g: g[1] - g[0]) if found else None

    at = [a for _n, a, _b in launches] + [float("inf")]
    call_at, ready_at = [s[1] for s in calls], [s[1] for s in readies]
    call = [_inside(calls, call_at, at[k], at[k + 1]) for k in range(len(launches))]
    ready = [_inside(readies, ready_at, at[k], at[k + 1]) for k in range(len(launches))]
    anchor = {k: c[1] for k, c in enumerate(call) if c is not None}
    distances = []  # pass 1: from each call's start to the end of the longest gap near it
    for k, c in anchor.items():
        near = min([0.02] + [0.5 * abs(c - anchor[o]) for o in (k - 1, k + 1) if o in anchor])
        gap = longest_ending_in(c - near, c + near)
        if gap is not None:
            distances.append(gap[1] - c)
    if not distances:
        return None
    typical = _median(distances)
    before = {k: longest_ending_in(c + typical - TOLERANCE_S, c + typical + TOLERANCE_S) for k, c in anchor.items()}
    out: List[Step] = []
    for k in range(1, len(launches) - 1):
        mine, nxt = before.get(k), before.get(k + 1)
        if ready[k] is None or mine is None or nxt is None or nxt[0] <= mine[1]:
            continue
        first_op, last_op = mine[1], nxt[0]
        inside = gaps[bisect.bisect_left(gap_starts, first_op):bisect.bisect_left(gap_starts, last_op)]
        out.append({"H": ready[k][2] - anchor[k], "D": last_op - first_op,
                    "first": first_op - anchor[k], "last": ready[k][2] - last_op,
                    "inner": sum(g1 - g0 for g0, g1 in inside)})
    return out or None


def _median_ms(run: Dict[str, Any], of) -> Optional[float]:
    """Median over the slice's whole steps of ``of(step)``, in milliseconds."""
    got = steps(run)
    return None if got is None else 1e3 * _median([of(s) for s in got])


def _median(values: List[float]) -> float:
    return sorted(values)[len(values) // 2]


def seam_idle_ms(run: Dict[str, Any]) -> Optional[float]:
    """Median over the slice's whole steps of ``H - D``."""
    return _median_ms(run, lambda s: s["H"] - s["D"])


def call_to_first_op_ms(run: Dict[str, Any]) -> Optional[float]:
    return _median_ms(run, lambda s: s["first"])


def last_op_to_wake_ms(run: Dict[str, Any]) -> Optional[float]:
    return _median_ms(run, lambda s: s["last"])


def trace_clock_slack_ms(run: Dict[str, Any]) -> Optional[float]:
    """``min(first) + min(last)``: the width of the interval the clocks' offset
    is confined to. Negative: the trace breaks causality, the parts mean nothing."""
    got = steps(run)
    return None if got is None else 1e3 * (min(s["first"] for s in got) + min(s["last"] for s in got))


def note(run: Dict[str, Any]) -> None:
    """One JSON note a run, printed by the first of the seam's readers to run:
    the slice's whole steps, each quantity's median and range, and how far
    ``first + last`` lies from ``H - D`` in the worst step (0 by construction)."""
    if run.get("_seam_noted"):
        return
    run["_seam_noted"] = True
    got = steps(run)
    if got is None:
        return
    out: Dict[str, Any] = {"note": "seam", "whole_steps": len(got)}
    for key, values in (("H", [s["H"] for s in got]), ("D", [s["D"] for s in got]),
                        ("idle", [s["H"] - s["D"] for s in got]),
                        ("first", [s["first"] for s in got]), ("last", [s["last"] for s in got]),
                        ("inner", [s["inner"] for s in got])):
        out[f"{key}_ms"] = {"median": 1e3 * _median(values), "min": 1e3 * min(values), "max": 1e3 * max(values)}
    out["identity_worst_ms"] = 1e3 * max(abs(s["first"] + s["last"] - (s["H"] - s["D"])) for s in got)
    print(json.dumps(out), flush=True)
