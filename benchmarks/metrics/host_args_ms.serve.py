"""Host time per engine step in ``engine.launch.args``: building the jit
call's argument lists (every weight, every cache plane), the second part of
``engine.launch``: the window's delta of
``engine.stats["subphase_s.launch_args"]`` over that of ``["steps"]``."""
NAME, UNIT, LAYER, MOVES = "host_args_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import seam

    return seam.subphase_ms(run, "subphase_s.launch_args")
