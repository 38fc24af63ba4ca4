"""Host time per engine step in ``engine.wait.fetch``: from the instant the
step's result is ready on the device to the end of its tokens' copy to the
host (``np.asarray``), the last part of ``engine.wait``: the window's delta of
``engine.stats["subphase_s.wait_fetch"]`` over that of ``["steps"]``."""
NAME, UNIT, LAYER, MOVES = "host_fetch_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import seam

    return seam.subphase_ms(run, "subphase_s.wait_fetch")
