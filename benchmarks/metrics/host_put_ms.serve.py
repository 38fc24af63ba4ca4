"""Host time per engine step in ``engine.launch.put``: the step's seven
host-to-device conversions (``jnp.asarray`` of the tokens, the block tables,
the lengths, the row counts, the active mask and the two copy-on-write lists),
the first part of ``engine.launch``: the window's delta of
``engine.stats["subphase_s.launch_put"]`` over that of ``["steps"]``."""
NAME, UNIT, LAYER, MOVES = "host_put_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import seam

    return seam.subphase_ms(run, "subphase_s.launch_put")
