"""Host scheduling per engine step: wall time of the benchmark's span around
``frontend.pump()`` minus the time a device operation ran inside it (mean over
the chips used), median over the pumps of the traced slice (device trace and
the span on its clock)."""
NAME, UNIT, LAYER, MOVES = "sched_host_ms.serve", "ms", "serving host", "serve_out_tokens_per_s"


def read(run):
    from lib import xplane

    if not run.get("trace"):
        return None
    raw = run["trace"]["raw"]
    pumps = [s for s in raw["spans"] if s[0] == "bench.frontend.pump"]
    if not pumps or not raw["devices"]:
        return None
    busy = [xplane.union([(a, b) for _n, a, b in ops]) for ops in raw["devices"].values()]
    host = []
    for _n, a, b in pumps:
        inside = sum(min(b, d) - max(a, c) for dev in busy for c, d in dev if d > a and c < b)
        host.append((b - a) - inside / len(busy))
    host.sort()
    return 1e3 * host[len(host) // 2]
