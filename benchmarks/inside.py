#!/usr/bin/env python
"""Run one cell as ``run.py`` does, then print what the program recorded about
itself while it ran (a tool run by hand, on the chip; its LAST line is not a
result line):

    python benchmarks/inside.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

- ``step_stalls``: the flight recorder's ``step_stall`` events (a step far
  above the running median: each phase's wall seconds and the thread's CPU
  seconds; ``paddle_tpu/inference/engine.py`` ``close_step``). A ``--trace 0``
  run reads no per-layer metric, so this is where its stalls show.
- ``devprof``: medians over the ring's ``devprof_step`` events, if
  ``FLAGS_devprof_sample_rate`` was set in the environment: devprof's host-prep /
  dispatch-gap / device split (taken from the phases' instants) and its
  per-category shares (a cost-model prior, not a measurement).
- ``tracer``: what ``FLAGS_trace_sample_rate`` left in the span ring.
"""

import json
import statistics
import sys

import run as harness


def main(argv=None):
    rc = harness.main(argv)
    from paddle_tpu import observability as obs

    events = obs.GLOBAL_FLIGHT_RECORDER.snapshot()
    steps = [e for e in events if e.get("kind") == "devprof_step"]
    devprof = None
    if steps:
        devprof = {"sampled_steps_in_ring": len(steps),
                   **{key: statistics.median(e[key] for e in steps)
                      for key in ("wall_ms", "host_prep_ms", "dispatch_ms", "device_ms")},
                   "categories": {k: statistics.median(e["categories"].get(k, 0.0) for e in steps)
                                  for k in sorted({k for e in steps for k in e["categories"]})},
                   "comm_source": steps[-1].get("comm_source")}
    records = obs.GLOBAL_TRACER.records()
    names = {}
    for r in records:
        names[r["name"]] = names.get(r["name"], 0) + 1
    print(json.dumps({"note": "inside", "step_stalls": [e for e in events if e.get("kind") == "step_stall"],
                      "devprof": devprof, "flight_events": len(events),
                      "tracer": {"records": len(records), "dropped": obs.GLOBAL_TRACER.dropped, "by_name": names}}),
          flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
