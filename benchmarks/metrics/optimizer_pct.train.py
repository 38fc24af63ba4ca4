"""Share of the traced steps' device-operation time in operations whose scope
is the optimizer's update (``optimizer_update``); a fusion counts by the scope
the trace gives it (its root's)."""
NAME, UNIT, LAYER, MOVES = "optimizer_pct.train", "%", "model", "train_tokens_per_s"


def read(run):
    from lib import phases

    return phases.scope_share_pct(run, (phases.OPTIMIZER_SCOPE,))
