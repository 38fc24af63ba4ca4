"""Host time per engine step in ``frontend.deliver`` (everything in
``ServingFrontend.pump()`` outside ``engine.step()``: the controller update at
entry; progress, finalisation, controller and gauges after the step): the
window's delta of ``engine.stats["phase_s.deliver"]`` over that of ``["steps"]``."""
NAME, UNIT, LAYER, MOVES = "host_deliver_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import phases

    return phases.per_step_ms(run, "phase_s.deliver")
