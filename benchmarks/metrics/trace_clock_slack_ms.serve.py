"""The least ``call -> first operation`` plus the least ``last operation ->
ready`` over the traced slice's whole steps: the width of the interval the
offset between the trace's two clocks is confined to (neither end can be
negative in any step), so the most that ``call_to_first_op_ms.serve`` and
``last_op_to_wake_ms.serve`` can be wrong by. Negative: the trace breaks
causality and the two parts mean nothing (``lib/seam.py``)."""
NAME, UNIT, LAYER, MOVES = "trace_clock_slack_ms.serve", "ms", "serving host", "itl_p95_ms"


def read(run):
    from lib import seam

    return seam.trace_clock_slack_ms(run)
