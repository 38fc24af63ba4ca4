"""From the start of ``paddle_tpu.engine.launch.call`` to the start of the
step's first device operation, median over the traced slice's whole steps: one
end of ``seam_idle_ms.serve``; it needs the trace's two clocks tied, and
``trace_clock_slack_ms.serve`` says how far they can be off (``lib/seam.py``)."""
NAME, UNIT, LAYER, MOVES = "call_to_first_op_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import seam

    return seam.call_to_first_op_ms(run)
