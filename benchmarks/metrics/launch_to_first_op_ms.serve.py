"""From the start of ``paddle_tpu.engine.launch`` to the start of the step's
first device operation, median over the traced slice's steps, both on the
device trace's clock (``lib/phases.py``)."""
NAME, UNIT, LAYER, MOVES = "launch_to_first_op_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import phases

    return phases.launch_to_first_op_ms(run)
