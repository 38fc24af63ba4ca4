"""Share of the old ``slots x max_blocks_per_seq`` page grid that the paged
kernel's length-bounded walk visits: the window's delta of
``engine.stats["paged_pages_walked"]`` (a step's sum over active slots of
``ceil((cached + new tokens) / block_size)``, counted by the engine as it plans
the step) over that of ``["steps"]`` x ``max_slots`` x ``max_blocks_per_seq``.
A program without the counter (the parent of PR 24) reads nothing."""
NAME, UNIT, LAYER, MOVES = "paged_walk_pct.serve", "%", "Pallas kernels", "itl_p95_ms"


def read(run):
    c = run["counters"]
    engine = c.get("engine", {})
    if "paged_pages_walked" not in engine or not engine.get("steps"):
        return None
    return 100.0 * engine["paged_pages_walked"] / (engine["steps"] * c["max_slots"] * c["max_blocks_per_seq"])
