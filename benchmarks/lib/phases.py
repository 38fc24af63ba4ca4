"""What the per-layer metrics of PR 23 share: the program's phase counters
(``engine.stats["phase_s.*"]``, ``["stall_s.*"]``, handed over by the serve
driver as the window's delta of every key), the program's ``paddle_tpu.*``
host spans on the device trace's clock, and device time by kernel name or by
``jax.named_scope``.

A program that has none of these (the parent of PR 23: no counter, no span, no
kernel name, no scope) makes every function here return ``None``, and the
metric is left out of the line.

How a name appears in a trace (TPU v5e, found by PR 23): a Pallas kernel is the
``custom-call`` whose HLO instruction is named after the ``pallas_call``'s
``name=`` (``%paged_attention_chunk.26 = ... custom_call_target=
"tpu_custom_call"``), and an operation's scope is the ``tf_op`` stat of its
event's metadata (``lib/xspace.py``), the ``jax.named_scope`` path with the
primitive last: ``jit(_step_impl)/attention/kv_cache_update/scatter:``.
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import xplane, xspace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SPAN_PREFIX = "paddle_tpu."
_INSTRUCTION = re.compile(r"^%([A-Za-z_][\w-]*?)(?:\.\d+)? = ")

# pallas_call names (paddle_tpu/kernels/*.py, KERNEL_*), by family
PAGED_KERNELS = "paged_attention_"
LOSS_KERNELS = "fused_loss_"
FLASH_KERNELS = "flash_attention_"
# jax.named_scope names (block_attention.py SCOPE_KV_*, optimizer.py SCOPE_UPDATE)
KERNEL_FAMILIES = (PAGED_KERNELS, LOSS_KERNELS, FLASH_KERNELS)
KV_POOL_SCOPES = ("kv_cache_update", "kv_cow")
OPTIMIZER_SCOPE = "optimizer_update"
MODEL_SCOPES = ("embedding", "norm", "attention", "mlp", "lm_head", "loss_head", "sample",
                OPTIMIZER_SCOPE) + KV_POOL_SCOPES
# the program's phases (paddle_tpu/observability/tracing.py phase), as spans in a trace
PHASES = ("frontend.pump", "frontend.deliver", "engine.decode_step", "engine.plan", "engine.launch",
          "engine.wait", "engine.commit")


# -- counters -------------------------------------------------------------------
def engine_delta(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The window's delta of ``engine.stats``, if the program counts phases."""
    engine = run.get("counters", {}).get("engine")
    if not engine or "phase_s.plan" not in engine or not engine.get("steps"):
        return None
    return engine


def per_step_ms(run: Dict[str, Any], key: str) -> Optional[float]:
    engine = engine_delta(run)
    return None if engine is None else 1e3 * engine[key] / engine["steps"]


def window_ms(run: Dict[str, Any], key: str) -> Optional[float]:
    engine = engine_delta(run)
    return None if engine is None or key not in engine else 1e3 * engine[key]


def note(run: Dict[str, Any]) -> None:
    """One JSON note a run, printed by the first of these readers to run: the
    phase counters beside the benchmark's own ``frontend.pump`` spans over the
    same window and the stalls (what the acceptance of PR 23 compares), and of
    a traced slice the share of device time that has a name, the largest
    operations that have none (a ``conditional`` has none of its own, but the
    operations of its branch, which it encloses, do), and the idle gaps by
    program phase."""
    import json

    if run.get("_phases_noted"):
        return
    run["_phases_noted"] = True
    out: Dict[str, Any] = {"note": "phases"}
    engine = engine_delta(run)
    if engine is not None:
        # the driver's delta runs from the window's opening to the END of the run (the
        # tail after the close too): so do the benchmark's own pump spans here
        pumps = [b - a for name, a, b in run["spans"].events if name == "frontend.pump" and a >= run["window"][0]]
        counted = {k[len("phase_s."):]: engine[k] for k in engine if k.startswith("phase_s.")}
        out.update(steps=engine["steps"], phase_s=counted, phase_total_s=sum(counted.values()),
                   bench_pump_total_s=sum(pumps), bench_pumps=len(pumps), stall_steps=engine.get("stall_steps"),
                   stall_host_s=engine.get("stall_s.host"), stall_device_s=engine.get("stall_s.device"))
        if pumps:
            out["phase_share_of_pumps"] = out["phase_total_s"] / out["bench_pump_total_s"]
            out["bench_pump_median_ms"] = 1e3 * sorted(pumps)[len(pumps) // 2]
    trace = program_trace(run)
    if trace is not None and (trace["scopes"] or trace["spans"]):
        labels = sorted({(kernel_of(n) if in_family(n, *KERNEL_FAMILIES) else scope_of(trace, n)) or "" for n, *_ in trace["ops"]})
        _p, busy = _covered_s(trace, lambda _t, _n: False)
        by_name = {label: 100 * _covered_s(trace, lambda t, n, label=label: (
            (kernel_of(n) if in_family(n, *KERNEL_FAMILIES) else scope_of(t, n)) or "") == label)[0] / busy
            for label in labels}
        unnamed: Dict[str, float] = {}
        for n, a, b, _d in trace["ops"]:
            if not _named(trace, n):
                unnamed[xplane.short_name(n)] = unnamed.get(xplane.short_name(n), 0.0) + 100 * (b - a) / busy
        out.update(named_share_pct=named_share_pct(run), idle_by_phase_s=idle_by_phase_s(run),
                   busy_pct_by_name=dict(sorted(((k or "(none)", v) for k, v in by_name.items()), key=lambda kv: -kv[1])),
                   largest_unnamed=sorted(unnamed.items(), key=lambda kv: -kv[1])[:6],
                   program_spans={name: len(spans_named(run, name)) for name in PHASES})
    if len(out) > 1:
        print(json.dumps(out), flush=True)


# -- the traced slice -----------------------------------------------------------
def newest_xplane() -> Optional[str]:
    """The ``.xplane.pb`` of the newest directory under ``.bench_trace/``: the
    run's own (a traced run writes one, after removing its cell's old one)."""
    base = os.path.join(ROOT, ".bench_trace")
    cells = [os.path.join(base, d) for d in os.listdir(base)] if os.path.isdir(base) else []
    for cell in sorted((d for d in cells if os.path.isdir(d)), key=os.path.getmtime, reverse=True):
        try:
            return xplane.find_xplane(cell)
        except FileNotFoundError:
            continue
    return None


def program_trace(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``{"scopes", "spans", "window", "ops"}`` of the run's trace, read once a
    run: ``ops`` are the device events ``(name, start, end, device)`` clipped
    to ``window``, the slice ``lib/tracing.py`` reduces over (first to last
    ``bench.`` span). ``run["xplane_path"]`` names the file for the tests."""
    if not run.get("trace"):
        return None
    if "_program_trace" not in run:
        path = run.get("xplane_path") or newest_xplane()
        raw = run["trace"]["raw"]
        if path is None or not raw.get("devices"):
            run["_program_trace"] = None
            return None
        spans = raw.get("spans") or []
        starts = [e[1] for ops in raw["devices"].values() for e in ops]
        ends = [e[2] for ops in raw["devices"].values() for e in ops]
        lo, hi = (spans[0][1], max(s[2] for s in spans)) if spans else (min(starts), max(ends))
        ops = [(n, max(a, lo), min(b, hi), dev) for dev, events in raw["devices"].items()
               for n, a, b in events if b > lo and a < hi]
        run["_program_trace"] = dict(xspace.read(path, SPAN_PREFIX), window=(lo, hi), ops=ops)
    return run["_program_trace"]


def kernel_of(event_name: str) -> Optional[str]:
    """The instruction name of a device event that is a Pallas kernel: the
    ``pallas_call``'s ``name=``, wrapped by the transforms it was traced under
    (``paged_attention_chunk_fused``; in a train step ``jvp_fused_loss_dw_``).
    The parent's kernels carry their jit scope's instead (``_step_impl``)."""
    if xplane.PALLAS_MARK not in event_name:
        return None
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else None


def in_family(event_name: str, *families: str) -> bool:
    kernel = kernel_of(event_name)
    return kernel is not None and any(f in kernel for f in families)


def scope_of(trace: Dict[str, Any], event_name: str) -> Optional[str]:
    """The innermost known ``jax.named_scope`` of a device event."""
    path = trace["scopes"].get(event_name)
    if not path:
        return None
    known = [part for part in re.split(r"[/()]", path) if part in MODEL_SCOPES]
    return known[-1] if known else None


def _covered_s(trace: Dict[str, Any], pick: Callable[[Dict[str, Any], str], bool]) -> Tuple[float, float]:
    """(seconds of the slice in which a picked operation ran, seconds in which
    any ran), each the union of intervals, mean over chips. Unions, because
    the "XLA Ops" line nests: a ``conditional`` event encloses the events of
    the branch it ran, and a sum of durations would count that time twice."""
    devices = sorted({d for _n, _a, _b, d in trace["ops"]})
    picked = busy = 0.0
    for dev in devices:
        ops = [(n, a, b) for n, a, b, d in trace["ops"] if d == dev]
        busy += sum(b - a for a, b in xplane.union([(a, b) for _n, a, b in ops]))
        picked += sum(b - a for a, b in xplane.union([(a, b) for n, a, b in ops if pick(trace, n)]))
    return picked / max(len(devices), 1), busy / max(len(devices), 1)


def busy_share_pct(run: Dict[str, Any], pick: Callable[[Dict[str, Any], str], bool]) -> Optional[float]:
    """Share of the slice's device-busy time in which an operation that
    ``pick`` takes ran, or ``None`` where it takes none."""
    trace = program_trace(run)
    if trace is None or not trace["ops"]:
        return None
    picked, busy = _covered_s(trace, pick)
    return 100.0 * picked / busy if picked and busy else None


def kernel_share_pct(run: Dict[str, Any], family: str) -> Optional[float]:
    return busy_share_pct(run, lambda _t, n: in_family(n, family))


def scope_share_pct(run: Dict[str, Any], scopes: Tuple[str, ...]) -> Optional[float]:
    return busy_share_pct(run, lambda t, n: scope_of(t, n) in scopes)


def _named(trace: Dict[str, Any], name: str) -> bool:
    return scope_of(trace, name) is not None or in_family(name, *KERNEL_FAMILIES)


def named_share_pct(run: Dict[str, Any]) -> Optional[float]:
    """Share of device-busy time under ANY kernel name of the program's or scope."""
    return busy_share_pct(run, _named)


def spans_named(run: Dict[str, Any], name: str) -> List[Tuple[str, float, float]]:
    trace = program_trace(run)
    return [s for s in (trace["spans"] if trace else []) if s[0] == SPAN_PREFIX + name]


def phase_at(trace: Dict[str, Any], t: float) -> str:
    """The innermost ``paddle_tpu.*`` span covering ``t`` (``outside`` if none):
    which phase the host was in at a gap's midpoint."""
    best = None
    for s in trace["spans"]:
        if s[1] <= t < s[2] and (best is None or s[1] >= best[1]):
            best = s
    return best[0][len(SPAN_PREFIX):] if best else "outside"


def idle_by_phase_s(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The slice's idle gaps (mean over chips) by the program phase the host
    was in at each gap's midpoint: ``xplane.reduce`` names them by ``bench.``
    spans only."""
    trace = program_trace(run)
    if trace is None or not trace["spans"]:
        return None
    lo, hi = trace["window"]
    devices = sorted({d for _n, _a, _b, d in trace["ops"]})
    out: Dict[str, float] = {}
    for dev in devices:
        busy = xplane.union([(a, b) for _n, a, b, d in trace["ops"] if d == dev])
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = phase_at(trace, 0.5 * (a + b))
                out[name] = out.get(name, 0.0) + (b - a) / len(devices)
    return out


def launch_to_first_op_ms(run: Dict[str, Any]) -> Optional[float]:
    """Median over the slice's steps of: the start of the step's first device
    operation minus the start of ``paddle_tpu.engine.launch``, both on the
    trace's clock. The step's first operation is where the longest idle gap
    that ends near the launch (within half the distance to the neighbouring
    launches) ends: the device idles while the host plans, so that gap is the
    one between two steps. The device's clock in a trace is the runtime's
    estimate of the host's, so the difference can come out below zero."""
    launches = spans_named(run, "engine.launch")
    trace = program_trace(run)
    if not launches or trace is None:
        return None
    dev = min(d for _n, _a, _b, d in trace["ops"])
    busy = xplane.union([(a, b) for _n, a, b, d in trace["ops"] if d == dev])
    gaps = [(g0, g1) for (_a, g0), (g1, _b) in zip(busy, busy[1:])]  # idle from g0 to g1
    if busy:
        gaps.insert(0, (trace["window"][0], busy[0][0]))
    at = [a for _n, a, _b in launches]
    ends = [g1 for _g0, g1 in gaps]  # ascending, as the busy intervals are
    waits = []
    for k, a in enumerate(at):
        near = min([0.02] + [0.5 * abs(a - o) for o in (at[k - 1:k] + at[k + 1:k + 2])])
        ending = [(g1 - g0, g1) for g0, g1 in gaps[bisect.bisect_left(ends, a - near):bisect.bisect_right(ends, a + near)]]
        if ending:
            waits.append(max(ending)[1] - a)
    if not waits:
        return None
    waits.sort()
    return 1e3 * waits[len(waits) // 2]
