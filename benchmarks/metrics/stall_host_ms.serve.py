"""Host time lost to stalls in the window: what steps far above the running
median (5 x the median of the last 64) spent in plan + launch + commit +
deliver beyond those phases' own median, summed
(``engine.stats["stall_s.host"]``, window delta). 0 in a window without a
stall; the ``step_stall`` flight-recorder event says whether the thread ran or
was descheduled."""
NAME, UNIT, LAYER, MOVES = "stall_host_ms.serve", "ms", "serving host", "ttft_p95_ms"


def read(run):
    from lib import phases

    return phases.window_ms(run, "stall_s.host")
