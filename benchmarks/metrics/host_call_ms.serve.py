"""Host time per engine step in ``engine.launch.call``: the jit call itself
(flattening the arguments, the cache lookup, the enqueue, wrapping its outputs)
up to its return, the last part of ``engine.launch``: the window's delta of
``engine.stats["subphase_s.launch_call"]`` over that of ``["steps"]``."""
NAME, UNIT, LAYER, MOVES = "host_call_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import seam

    return seam.subphase_ms(run, "subphase_s.launch_call")
