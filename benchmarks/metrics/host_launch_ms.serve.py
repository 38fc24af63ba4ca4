"""Host time per engine step in ``engine.launch`` (the host-to-device conversions
of the step's arguments and the jit call up to its return): the window's delta
of ``engine.stats["phase_s.launch"]`` over that of ``["steps"]``."""
NAME, UNIT, LAYER, MOVES = "host_launch_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import phases

    return phases.per_step_ms(run, "phase_s.launch")
