"""Host time per engine step in ``engine.commit`` (after the sync: CoW release,
lengths, chain registration, token emission, finish and release, pool gauges):
the window's delta of ``engine.stats["phase_s.commit"]`` over that of
``["steps"]``."""
NAME, UNIT, LAYER, MOVES = "host_commit_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import phases

    return phases.per_step_ms(run, "phase_s.commit")
