"""Share of the step's slot rows that did work: decode tokens committed plus
prefill chunks taken, per engine step, over ``max_slots`` (engine.stats and
the benchmark's own token count over the window)."""
NAME, UNIT, LAYER, MOVES = "batch_occupancy_pct", "%", "serving host", "serve_out_tokens_per_s"


def read(run):
    c = run["counters"]
    steps = c.get("engine", {}).get("steps", 0)
    if not steps:
        return None
    chunks = c["engine"]["prompt_tokens_computed"] / c["prefill_chunk"]
    return 100.0 * (run["out_tokens_in_window"] + chunks) / (steps * c["max_slots"])
