"""Host time per engine step in ``engine.plan`` (from the entry of ``engine.step()``: admission,
prefix match, prefetch gates, drafting, packing the rows, block growth, CoW
lists, the dense tables; up to the jit call): the window's delta of
``engine.stats["phase_s.plan"]`` over that of ``["steps"]`` (the program's own
counter, always on)."""
NAME, UNIT, LAYER, MOVES = "host_plan_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import phases

    phases.note(run)  # once a run: the counters beside the benchmark's own pump spans
    return phases.per_step_ms(run, "phase_s.plan")
