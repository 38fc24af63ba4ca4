"""Share of the window's engine steps at whose planning a waiting request was
held back for want of KV blocks while a slot stood free: the window's delta of
``engine.stats["admit_blocked_steps.blocks"]`` over that of ``["steps"]`` (the
program's own counters). A program without the counter reads nothing."""
NAME, UNIT, LAYER, MOVES = "admit_blocked_pct.serve", "%", "serving host", "ttft_p95_ms"


def read(run):
    engine = run["counters"].get("engine", {})
    if "admit_blocked_steps.blocks" not in engine or not engine.get("steps"):
        return None
    return 100.0 * engine["admit_blocked_steps.blocks"] / engine["steps"]
