#!/usr/bin/env python
"""Replay a serving cell's schedule on the host at a constant step time: what
``serve_out_tokens_per_s`` and ``ttft_p95_ms`` would read if a step took so long.

    python tools/serve_schedule_replay.py --workload nemotron3nano.serve_chat --step-ms 33.5,29,25
    python tools/serve_schedule_replay.py --workload mistral7b.serve_chat --step-ms 88,25,10.9

Not a chip run and not a time: arithmetic on the mix's own schedule
(``benchmarks/lib/traffic.py``: the same due times and lengths for every seed)
and the cell's workload file (rate, ramp, slots, prefill chunk), neither of
which it changes. The engine is reduced to what decides the two numbers below
the knee: ``max_slots`` slots, first come first served; a step takes every
live slot ``prefill_chunk`` (16) prompt tokens further or one output token
further, the step that ends a prompt delivers the first token, and every
token is stamped where its step ends; the loop sends what is due before each
step, sleeps to the next due time when nothing is live, runs from ``-ramp_s``,
closes at the first instant at or after the window's end and then pumps until
every request that was sent has its first token, as
``benchmarks/lib/drivers/serve.py::window`` does.

It printed PERF.md section 7's table (issue 28) and issue 39's prediction;
``tests/test_serve_schedule_replay.py`` pins both. What it shows: below the
knee delivered tokens/s = the offered load + what the ramp left owing at the
open - what is still owed at the close, so a shorter step can read LOWER.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

from lib import stats, traffic  # noqa: E402 - benchmarks/lib, read and not changed

WINDOW_S = 50.0  # BENCHMARK.json's run_seconds


def load_cell(name: str) -> Dict[str, Any]:
    with open(ROOT / "benchmarks" / "workloads" / f"{name}.json") as fh:
        return json.load(fh)


def replay(cell: Dict[str, Any], step_s: float, seconds: float = WINDOW_S) -> Dict[str, float]:
    """One window of ``cell`` at ``step_s`` a step: delivered tokens/s (tokens
    stamped in the window over its length), the output tokens owed when the
    window opens and when it closes (of requests due by then, not yet
    delivered), ``ttft_p95_ms`` over the requests due in the window, and the
    instant the loop closed."""
    ramp = float(cell.get("ramp_s", 0.0))
    slots = int(cell["engine"]["max_slots"])
    chunk = int(cell["engine"].get("prefill_chunk", 16))
    requests = traffic.make_requests(traffic.load_mix(cell["traffic"]), cell["rate_rps"], seconds, 2, 0, ramp_s=ramp)
    pending = collections.deque(requests)  # in due order
    queue: collections.deque = collections.deque()
    live: List[Dict[str, Any]] = []
    sent: List[Dict[str, Any]] = []
    t, opened, closed = -ramp, None, None

    while True:
        if opened is None and t >= 0.0:
            opened = t
        if closed is None and t >= seconds:
            while pending and pending[0].due_s <= seconds:  # whatever is due is sent, then nothing more
                queue.append(pending.popleft())
            closed = t
        if closed is not None:
            if not queue and all(rec["stamps"] for rec in live):
                break
        else:
            while pending and pending[0].due_s <= t:
                queue.append(pending.popleft())
        if not (queue or live):
            t = pending[0].due_s if pending else seconds
            continue
        while queue and len(live) < slots:
            request = queue.popleft()
            rec = {"request": request, "prompt_left": len(request.prompt), "stamps": []}
            sent.append(rec)
            live.append(rec)
        t += step_s
        for rec in list(live):
            if rec["prompt_left"] > 0:
                rec["prompt_left"] -= min(chunk, rec["prompt_left"])
                if rec["prompt_left"] > 0:
                    continue
            rec["stamps"].append(t)  # the step that ends the prompt delivers the first token
            if len(rec["stamps"]) >= rec["request"].max_new_tokens:
                live.remove(rec)

    def owed(at: float) -> int:  # every request due by the close has been sent by now
        return sum(rec["request"].max_new_tokens - sum(1 for s in rec["stamps"] if s <= at)
                   for rec in sent if rec["request"].due_s <= at)

    ttft = [rec["stamps"][0] - rec["request"].due_s for rec in sent if rec["request"].due_s >= 0.0]
    delivered = sum(1 for rec in sent for s in rec["stamps"] if 0.0 < s <= seconds)
    return {
        "tokens_per_s": delivered / seconds, "owed_at_open": owed(opened), "owed_at_close": owed(closed),
        "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95), "closed_at_s": closed,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a serving cell of benchmarks/workloads")
    ap.add_argument("--step-ms", required=True, help="step times, comma-separated")
    ap.add_argument("--seconds", type=float, default=WINDOW_S)
    a = ap.parse_args()
    cell = load_cell(a.workload)
    print(f"{a.workload}: {cell['engine']['max_slots']} slots, {cell['rate_rps']} req/s, ramp {cell.get('ramp_s', 0.0)} s, "
          f"window {a.seconds} s (a host replay of the schedule, not a run)")
    print(f"{'step ms':>8s} {'tokens/s':>9s} {'owed at open':>13s} {'at close':>9s} {'ttft_p95 ms':>12s}")
    for step_ms in (float(v) for v in a.step_ms.split(",")):
        r = replay(cell, step_ms / 1e3, a.seconds)
        print(f"{step_ms:8g} {r['tokens_per_s']:9.2f} {r['owed_at_open']:13d} {r['owed_at_close']:9d} {r['ttft_p95_ms']:12.0f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
