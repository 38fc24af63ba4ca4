"""From a profiler trace (``*.xplane.pb``) to what the per-layer metrics read.

``load(path)`` reads the file with JAX's own ``ProfileData`` and returns plain
Python: device planes with their operation events, and the benchmark's own
host spans (``jax.profiler.TraceAnnotation`` names starting with ``bench.``).
``reduce(trace)`` gives, per device: the union of the intervals in which an
operation ran (busy), the time per operation name, and the idle gaps, each gap
named by the benchmark span the host was in at its midpoint.

Only leaf operations count as busy: the TPU plane's "XLA Ops" line. The
"XLA Modules" and "Steps" lines enclose whole programs, idle parts included.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = re.compile(r"^XLA Ops$")
SPAN_PREFIX = "bench."
Event = Tuple[str, float, float]  # name, start_s, end_s


PALLAS_MARK = "tpu_custom_call"  # a Pallas kernel is a custom call with this target
_HLO = re.compile(r"^(%\S+) = (.*?) ([a-z][\w-]*)\(")


def short_name(full: str) -> str:
    """An "XLA Ops" event is named by its whole HLO instruction. Keep the
    instruction's name, its opcode and its (first) result shape; mark Pallas."""
    m = _HLO.match(full)
    if not m:
        return full[:120]
    shape = re.sub(r"\{[^}]*\}", "", m.group(2)).strip("() ").split(", ")[0]
    return f"{m.group(1)} {m.group(3)} {shape}" + (" pallas" if PALLAS_MARK in full else "")


def result_shapes(full: str) -> str:
    """The result type of an HLO instruction with layouts stripped, e.g.
    ``(bf16[8,32,2048,128], f32[8,32,2048,1])``."""
    m = _HLO.match(full)
    return re.sub(r"\{[^}]*\}", "", m.group(2)).strip() if m else ""


def operand_shapes(full: str) -> str:
    """The operand list of an HLO instruction with layouts stripped."""
    m = _HLO.match(full)
    if not m:
        return ""
    rest = full[m.end():]
    return re.sub(r"\{[^}]*\}", "", rest.split("), ")[0])


def pallas_events(trace: Dict[str, Any]) -> List[Event]:
    return [e for ops in trace["devices"].values() for e in ops if PALLAS_MARK in e[0]]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    lines_seen: Dict[str, List[str]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines_seen[plane.name] = [line.name for line in plane.lines]
        for line in plane.lines:
            if m and OP_LINE.search(line.name):
                ops = devices.setdefault(int(m.group(1) or 0), [])
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"devices": devices, "spans": sorted(spans, key=lambda e: e[1]), "lines": lines_seen}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _span_at(spans: List[Event], t: float) -> str:
    """The innermost (latest-started) benchmark span covering ``t``."""
    best: Optional[Event] = None
    for s in spans:
        if s[1] <= t < s[2] and (best is None or s[1] >= best[1]):
            best = s
    return best[0][len(SPAN_PREFIX):] if best else "outside_spans"


def reduce(trace: Dict[str, Any], window: Optional[Tuple[float, float]] = None) -> Dict[str, Any]:
    """Busy/idle per device over ``window`` (default: first op start to last op
    end over all devices), time per op name, and idle gaps by host span."""
    devices = trace["devices"]
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    if window is None:
        starts = [e[1] for ops in devices.values() for e in ops]
        ends = [e[2] for ops in devices.values() for e in ops]
        window = (min(starts), max(ends))
    lo, hi = window
    per_device = {}
    op_time: Dict[str, float] = {}
    gaps_by_span: Dict[str, float] = {}
    longest_gaps: List[Tuple[str, float]] = []
    for dev, ops in sorted(devices.items()):
        busy = union(_clip([(a, b) for _n, a, b in ops], lo, hi))
        busy_s = sum(b - a for a, b in busy)
        for n, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                key = short_name(n)
                op_time[key] = op_time.get(key, 0.0) + (b - a) / len(devices)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a > 0:
                name = _span_at(trace["spans"], 0.5 * (a + b))
                gaps_by_span[name] = gaps_by_span.get(name, 0.0) + (b - a) / len(devices)
                longest_gaps.append((name, b - a))
        per_device[dev] = {"busy_s": busy_s, "ops": len(ops)}
    n = len(per_device)
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "per_device": per_device,
        "op_time_s": op_time,
        "idle_by_span_s": gaps_by_span,
        "longest_gaps": sorted(longest_gaps, key=lambda g: -g[1])[:10],
    }


def breakdown(reduced: Dict[str, Any]) -> Dict[str, List[List[Any]]]:
    top = sorted(reduced["op_time_s"].items(), key=lambda kv: -kv[1])[:10]
    by_span = sorted(reduced["idle_by_span_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, t] for n, t in top], "idle_gaps": [[n, t] for n, t in by_span]}
