"""Device time lost to stalls in the window: what stalled steps spent in
``engine.wait`` beyond its own median, summed
(``engine.stats["stall_s.device"]``, window delta)."""
NAME, UNIT, LAYER, MOVES = "stall_device_ms.serve", "ms", "device", "ttft_p95_ms"


def read(run):
    from lib import phases

    return phases.window_ms(run, "stall_s.device")
