"""Share of the traced slice's device-operation time in the paged attention
kernels, found by their ``pallas_call`` name (``paged_attention_*``)."""
NAME, UNIT, LAYER, MOVES = "paged_attn_pct.serve", "%", "Pallas kernels", "itl_p95_ms"


def read(run):
    from lib import phases

    return phases.kernel_share_pct(run, phases.PAGED_KERNELS)
