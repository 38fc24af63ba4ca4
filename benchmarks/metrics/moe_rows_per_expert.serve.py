"""Rows a held expert sees a step, mean over the window: the window's delta of
``engine.stats["moe_rows_local"]`` (assignments that landed on held experts,
summed over a step's expert blocks; the program's own counter) over that of
``["steps"]`` x the configuration's expert blocks x the experts held. The cut
to one chip's requests makes it an eighth of a deployment's (PERF.md section
4), so the grouped matmuls here are more weight-bound than there. A program
without the counter, or a configuration without expert blocks, reads nothing."""
NAME, UNIT, LAYER, MOVES = "moe_rows_per_expert.serve", "rows", "model", "itl_p95_ms"


def read(run):
    from lib import arch

    engine = run["counters"].get("engine", {})
    if "moe_rows_local" not in engine or not engine.get("steps"):
        return None
    cfg = run["cfg"]
    ref = arch.reference(cfg)
    if not hasattr(ref, "EXPERTS"):
        return None
    blocks = ref.count(cfg, run["depth"], ref.EXPERTS)
    if not blocks or not cfg.get("n_routed_experts"):
        return None
    return engine["moe_rows_local"] / (engine["steps"] * blocks * cfg["n_routed_experts"])
