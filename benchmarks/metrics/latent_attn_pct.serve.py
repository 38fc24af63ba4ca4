"""Share of the traced slice's device-busy time under the model's ``mla``
scope: a latent-attention block's query path, its latent path, the two
absorbed matmuls, the append and the walk over the latent pages, and the
output projection. Union of intervals. A program without the scope reads
nothing."""
NAME, UNIT, LAYER, MOVES = "latent_attn_pct.serve", "%", "model", "itl_p95_ms"

SCOPE = "mla"


def read(run):
    import re

    from lib import phases

    return phases.busy_share_pct(run, lambda t, n: SCOPE in re.split(r"[/()]", t["scopes"].get(n) or ""))
