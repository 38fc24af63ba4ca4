"""Share of the traced slice's device-busy time under the model's
``sandwich_norm`` scope: the second norm on each branch's output (two a layer
a pass), which the compiler may or may not fuse into its neighbours. A program
without the scope reads nothing."""
NAME, UNIT, LAYER, MOVES = "sandwich_norm_pct.serve", "%", "model", "itl_p95_ms"

SCOPE = "sandwich_norm"


def read(run):
    import re

    from lib import phases

    return phases.busy_share_pct(run, lambda t, n: SCOPE in re.split(r"[/()]", t["scopes"].get(n) or ""))
