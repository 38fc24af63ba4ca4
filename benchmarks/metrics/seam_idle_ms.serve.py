"""The device's idle time at a step's two ends, median over the traced slice's
whole steps of ``H - D``: ``H`` the host's time from the start of
``paddle_tpu.engine.launch.call`` to the end of ``paddle_tpu.engine.wait.ready``,
``D`` the device's from its first operation's start to its last operation's
end. Each on its own clock: an offset between the two cancels (``lib/seam.py``)."""
NAME, UNIT, LAYER, MOVES = "seam_idle_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import seam

    seam.note(run)  # once a run: the slice's steps, each quantity's median and range
    return seam.seam_idle_ms(run)
