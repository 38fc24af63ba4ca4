"""Share of the traced slice's device-busy time under the model's ``ssm_mixer``
scope: a state-space block's projections, its conv, the scan over the step's
rows (which reads and rewrites every slot's state) and the gated norm. Union
of intervals. A program without the scope reads nothing."""
NAME, UNIT, LAYER, MOVES = "ssm_pct.serve", "%", "model", "itl_p95_ms"

SCOPE = "ssm_mixer"


def read(run):
    import re

    from lib import phases

    return phases.busy_share_pct(run, lambda t, n: SCOPE in re.split(r"[/()]", t["scopes"].get(n) or ""))
