"""Time per engine step the host is blocked on the device (``engine.wait``: the
``np.asarray`` of the step's tokens): the window's delta of
``engine.stats["phase_s.wait"]`` over that of ``["steps"]``."""
NAME, UNIT, LAYER, MOVES = "device_wait_ms.serve", "ms", "device", "itl_p95_ms"


def read(run):
    from lib import phases

    return phases.per_step_ms(run, "phase_s.wait")
