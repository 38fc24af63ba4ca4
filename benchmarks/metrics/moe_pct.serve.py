"""Share of the traced slice's device-busy time under the model's ``moe``
scope: an expert block's router, the dispatch (sort, gather), the grouped
matmuls over the held experts, the shared expert and the combine. Union of
intervals. A program without the scope reads nothing."""
NAME, UNIT, LAYER, MOVES = "moe_pct.serve", "%", "model", "itl_p95_ms"

SCOPE = "moe"


def read(run):
    import re

    from lib import phases

    return phases.busy_share_pct(run, lambda t, n: SCOPE in re.split(r"[/()]", t["scopes"].get(n) or ""))
