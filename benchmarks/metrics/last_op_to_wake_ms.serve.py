"""From the end of the step's last device operation to the end of
``paddle_tpu.engine.wait.ready`` (the host knows the result is ready), median
over the traced slice's whole steps: the other end of ``seam_idle_ms.serve``;
it needs the trace's two clocks tied (``lib/seam.py``)."""
NAME, UNIT, LAYER, MOVES = "last_op_to_wake_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import seam

    return seam.last_op_to_wake_ms(run)
