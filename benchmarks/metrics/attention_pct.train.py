"""Share of the traced steps' device-operation time in the flash attention
kernels (forward, dq, dk/dv), found by their ``pallas_call`` name
(``flash_attention_*``)."""
NAME, UNIT, LAYER, MOVES = "attention_pct.train", "%", "Pallas kernels", "train_tokens_per_s"


def read(run):
    from lib import phases

    return phases.kernel_share_pct(run, phases.FLASH_KERNELS)
